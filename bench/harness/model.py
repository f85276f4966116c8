"""A configuration file -> the program's model, engine and weights.

The configuration files hold the published ``config.json`` keys of a
dense GQA decoder (Qwen2 family).  The weights are the benchmark's own:
drawn from ``--seed`` on the device, in one jitted program, in the dtype
they are served in.  They are drawn in a canonical per-layer layout (the
one the plain reference reads) and handed to the program in its own
pytree layout; :func:`canonical_layer` maps them back for the reference.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

# traffic "engine" keys passed through to the program's ServeConfig
ENGINE_KEYS = ("split_policy", "use_scheduler_metadata", "prefill_mode",
               "cache_layout", "kv_quant", "num_splits_override")


def dims(cfg: Dict[str, Any]) -> Dict[str, int]:
    d = cfg["hidden_size"]
    hq = cfg["num_attention_heads"]
    return dict(d=d, hq=hq, hkv=cfg["num_key_value_heads"],
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


def serve_config(model_cfg, traffic: Dict[str, Any],
                 overrides: Dict[str, Any] | None = None):
    from repro.configs.base import ServeConfig
    engine = dict(traffic.get("engine", {}), **(overrides or {}))
    kw = {k: engine[k] for k in ENGINE_KEYS if k in engine}
    return ServeConfig(model=model_cfg, seqlen_bucket=traffic["seqlen_bucket"],
                       prefill_bucket=traffic["prefill_bucket"], **kw)


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


def _get(tree, path):
    for p in path:
        tree = tree[p]
    return tree


def _set(tree: Dict, path, value) -> None:
    for p in path[:-1]:
        tree = tree.setdefault(p, {})
    tree[path[-1]] = value


def _as_program_tree(flat: Dict) -> Any:
    """Nested dicts with integer keys -> the program's tuple groups."""
    if isinstance(flat, dict) and flat and all(isinstance(k, int)
                                                for k in flat):
        return tuple(_as_program_tree(flat[i]) for i in range(len(flat)))
    if isinstance(flat, dict):
        return {k: _as_program_tree(v) for k, v in flat.items()}
    return flat


def seed_key(seed: int) -> jax.Array:
    """A PRNG key that keeps every bit of a seed wider than 32 bits."""
    seed = int(seed) % (1 << 64)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


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
            _set(tree, path, leaf.reshape(pshape))
        return _as_program_tree(tree)

    jitted = jax.jit(build) if sharding is None \
        else jax.jit(build, out_shardings=sharding)
    return jitted(seed_key(seed))


def check_layout(params, model) -> None:
    """Fail loudly if the program's param layout is not the one the
    benchmark fills (a refactor of the model must update this file)."""
    want = jax.tree.map(lambda s: tuple(s.shape), model.abstract_params())
    got = jax.tree.map(lambda a: tuple(a.shape), params)
    if want != got:
        raise RuntimeError(f"program param layout changed:\n want {want}"
                           f"\n got {got}")


def build(cell, seed: int, devices, *, overrides=None, obs=None,
          hook=None):
    """The cell's engine with the seed's weights loaded, and the weights:
    the one construction path of every run, sweep and control.  A
    traffic file with ``"mesh": "<dp>,<sp>"`` gets the program's
    mesh-native engine over ``devices``; ``overrides`` replace the
    traffic file's engine settings; ``hook`` gets the engine before its
    weights are loaded (tests break it there)."""
    from repro.models.registry import build_model
    from repro.serving import ServingEngine
    from repro.serving.sampling import GreedySampler
    cfg, tr = cell.config, cell.traffic
    engine_kw = dict(tr.get("engine", {}), **(overrides or {}))
    mcfg = model_config(cfg, engine_kw)
    model = build_model(mcfg)
    scfg = serve_config(mcfg, tr, overrides)
    kw = dict(max_len=tr["max_len"], sampler=GreedySampler(), obs=obs)
    if tr.get("mesh"):
        from jax.sharding import NamedSharding, PartitionSpec
        from repro.shard import ShardedServingEngine, ShardSpec
        spec = ShardSpec.parse(tr["mesh"])
        spec = spec.with_(slots_per_shard=tr["slots"] // spec.dp)
        engine = ShardedServingEngine(model, scfg, spec=spec,
                                      devices=devices, **kw)
        # drawn straight into the replicated layout the engine loads,
        # so that no chip holds a second copy
        params = make_params(cfg, seed, sharding=NamedSharding(
            engine.plan.mesh, PartitionSpec()))
    else:
        engine = ServingEngine(model, scfg, batch_slots=tr["slots"], **kw)
        params = make_params(cfg, seed)
    check_layout(params, model)
    jax.block_until_ready(params)
    if hook is not None:
        hook(engine)
    engine.load(params)
    return engine, params


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
        out[name] = _get(params, path)[i].reshape(shape[1:])
    return out


def canonical_globals(params, cfg: Dict[str, Any]) -> Dict[str, Any]:
    n = dims(cfg)
    paths = _program_paths(n)
    out = {"embed_tokens": _get(params, paths["embed_tokens"][0]),
           "norm": _get(params, paths["norm"][0])}
    if not cfg["tie_word_embeddings"]:
        out["lm_head"] = _get(params, paths["lm_head"][0])
    return out
