"""Qwen2 (``Qwen2ForCausalLM``): a dense GQA decoder with q/k/v biases.

The configuration files hold the published ``config.json`` keys.  The
weights are the benchmark's own: drawn from ``--seed`` on the device, in
one jitted program, in the dtype they are served in.  They are drawn in
a canonical per-layer layout (the one ``bench/reference/qwen2.py``
reads) and handed to the program in its own pytree layout;
:func:`canonical_layer` maps them back for the reference.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from bench.harness.model import as_program_tree, seed_key, tree_get, tree_set


def dims(cfg: Dict[str, Any]) -> Dict[str, Any]:
    d = cfg["hidden_size"]
    hq = cfg["num_attention_heads"]
    return dict(model_type=cfg["model_type"], d=d, hq=hq,
                hkv=cfg["num_key_value_heads"],
                hd=cfg.get("head_dim", d // hq),
                ff=cfg["intermediate_size"], layers=cfg["num_hidden_layers"],
                vocab=cfg["vocab_size"])


def model_config(cfg: Dict[str, Any], engine: Dict[str, Any]):
    from repro.configs.base import ModelConfig
    n = dims(cfg)
    return ModelConfig(
        name=cfg["name"], family="dense", num_layers=n["layers"],
        d_model=n["d"], num_heads=n["hq"], num_kv_heads=n["hkv"],
        d_ff=n["ff"], vocab_size=n["vocab"], head_dim=n["hd"],
        mlp_kind="swiglu", qkv_bias=True, rope_theta=cfg["rope_theta"],
        norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"],
        max_seq_len=cfg["max_position_embeddings"],
        dtype=cfg["torch_dtype"], param_dtype=cfg["torch_dtype"],
        attention_impl=engine.get("attention_impl", "xla"))


def _canonical_shapes(n: Dict[str, int], tied: bool
                      ) -> Dict[str, Tuple[Tuple[int, ...], float]]:
    """canonical name -> (shape, init std; 0 = ones)."""
    L, d, ff, v = n["layers"], n["d"], n["ff"], n["vocab"]
    q, kv = n["hq"] * n["hd"], n["hkv"] * n["hd"]
    s = {
        "embed_tokens": ((v, d), 1 / math.sqrt(d)),
        "norm": ((d,), 0.0),
        "input_layernorm": ((L, d), 0.0),
        "q_proj": ((L, d, q), QK_GAIN / math.sqrt(d)),
        "k_proj": ((L, d, kv), QK_GAIN / math.sqrt(d)),
        "v_proj": ((L, d, kv), 1 / math.sqrt(d)),
        "q_bias": ((L, q), BIAS_STD),
        "k_bias": ((L, kv), BIAS_STD),
        "v_bias": ((L, kv), BIAS_STD),
        "o_proj": ((L, q, d), 1 / math.sqrt(q)),
        "post_attention_layernorm": ((L, d), 0.0),
        "gate_proj": ((L, d, ff), 1 / math.sqrt(d)),
        "up_proj": ((L, d, ff), 1 / math.sqrt(d)),
        "down_proj": ((L, ff, d), 1 / math.sqrt(ff)),
    }
    if not tied:
        s["lm_head"] = ((d, v), 1 / math.sqrt(d))
    return s


# std of the q/k/v biases: large enough that a path which drops them
# gives other logits, small beside the unit-variance projections
BIAS_STD = 0.5
# q and k drawn this much wider than 1/sqrt(fan_in), so that attention
# scores spread by about 4 and each row attends to a few positions, as
# a trained model's do.  At 1/sqrt(fan_in) every row of a long context
# averages the whole cache and greedy decoding repeats one token, which
# no rounding of the weights can change, so no check could fail.
QK_GAIN = 2.0


def _program_paths(n: Dict[str, int]) -> Dict[str, Tuple[Tuple, Tuple]]:
    """canonical name -> (path into the program's param pytree, shape
    of the program's leaf)."""
    L, d, ff = n["layers"], n["d"], n["ff"]
    hq, hkv, hd = n["hq"], n["hkv"], n["hd"]
    blk = ("groups", 0, 0)
    return {
        "embed_tokens": (("embed", "tok"), (n["vocab"], d)),
        "lm_head": (("embed", "unembed"), (d, n["vocab"])),
        "norm": (("final_norm", "scale"), (d,)),
        "input_layernorm": (blk + ("ln1", "scale"), (L, d)),
        "q_proj": (blk + ("mix", "wq"), (L, d, hq, hd)),
        "k_proj": (blk + ("mix", "wk"), (L, d, hkv, hd)),
        "v_proj": (blk + ("mix", "wv"), (L, d, hkv, hd)),
        "q_bias": (blk + ("mix", "bq"), (L, hq, hd)),
        "k_bias": (blk + ("mix", "bk"), (L, hkv, hd)),
        "v_bias": (blk + ("mix", "bv"), (L, hkv, hd)),
        "o_proj": (blk + ("mix", "wo"), (L, hq, hd, d)),
        "post_attention_layernorm": (blk + ("ln2", "scale"), (L, d)),
        "gate_proj": (blk + ("ffn", "wi_gate"), (L, d, ff)),
        "up_proj": (blk + ("ffn", "wi_up"), (L, d, ff)),
        "down_proj": (blk + ("ffn", "wo"), (L, ff, d)),
    }


def make_params(cfg: Dict[str, Any], seed: int, dtype=jnp.bfloat16,
                sharding=None):
    """The program's param pytree, drawn on the device from ``seed`` in
    one jitted call (matrices at std 1/sqrt(fan_in), q and k at
    ``QK_GAIN`` times that, norms at one), with
    every leaf in ``sharding`` where one is given."""
    n = dims(cfg)
    shapes = _canonical_shapes(n, cfg["tie_word_embeddings"])
    paths = _program_paths(n)

    def build(key):
        tree: Dict = {}
        for i, (name, (shape, std)) in enumerate(sorted(shapes.items())):
            if std == 0.0:
                leaf = jnp.ones(shape, dtype)
            else:
                leaf = (jax.random.normal(jax.random.fold_in(key, i), shape,
                                          jnp.float32) * std).astype(dtype)
            path, pshape = paths[name]
            tree_set(tree, path, leaf.reshape(pshape))
        return as_program_tree(tree)

    jitted = jax.jit(build) if sharding is None \
        else jax.jit(build, out_shardings=sharding)
    return jitted(seed_key(seed))


def canonical_layer(params, cfg: Dict[str, Any], i: int) -> Dict[str, Any]:
    """Layer ``i``'s weights in the canonical layout (bf16 as served)."""
    n = dims(cfg)
    paths = _program_paths(n)
    shapes = _canonical_shapes(n, True)
    out = {}
    for name, (shape, _) in shapes.items():
        if len(shape) < 2 or name == "embed_tokens":
            continue
        path, _ = paths[name]
        out[name] = tree_get(params, path)[i].reshape(shape[1:])
    return out


def canonical_globals(params, cfg: Dict[str, Any]) -> Dict[str, Any]:
    n = dims(cfg)
    paths = _program_paths(n)
    out = {"embed_tokens": tree_get(params, paths["embed_tokens"][0]),
           "norm": tree_get(params, paths["norm"][0])}
    if not cfg["tie_word_embeddings"]:
        out["lm_head"] = tree_get(params, paths["lm_head"][0])
    return out


def layer_matmul_params(n: Dict[str, int]) -> int:
    d, q, kv, ff = n["d"], n["hq"] * n["hd"], n["hkv"] * n["hd"], n["ff"]
    return d * q + 2 * d * kv + q * d + 3 * d * ff


def decode_token_flops(n: Dict[str, int], kv_len: int) -> float:
    """Model FLOPs of one decoded token: every layer's projections and
    MLP, its attention over ``kv_len`` rows, and the output head."""
    attn = 4 * n["hq"] * n["hd"] * kv_len
    return (n["layers"] * (2 * layer_matmul_params(n) + attn)
            + 2 * n["d"] * n["vocab"])


def flash_decode_row(n: Dict[str, int]) -> Tuple[int, int, int]:
    """Per layer, what the decode-attention kernel moves and computes:
    the elements of q in and o out for one live row, the elements of one
    cached row (K and V), and the FLOPs one live row spends on it."""
    q = n["hq"] * n["hd"]
    return 2 * q, 2 * n["hkv"] * n["hd"], 4 * q
