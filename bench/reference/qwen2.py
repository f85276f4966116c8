"""Plain float32 reference of a Qwen2 decoder (``Qwen2ForCausalLM``).

Written from the published architecture, independent of the program:
token embedding; per layer an RMSNorm, grouped-query attention with
q/k/v biases and rotary embeddings (rotate-half, ``rope_theta``), a
residual add, an RMSNorm, a SwiGLU MLP and a residual add; a final
RMSNorm and the (tied) output projection.  Every matrix product runs at
``Precision.HIGHEST``, so on a TPU it is float32 and not bf16 passes.

It takes the weights as the benchmark drew them (bf16 values, the ones
the program serves) in the canonical per-layer layout, and runs one
layer at a time over the whole sequence so that it fits beside nothing
else: causal attention is computed in blocks of query rows.
"""
from __future__ import annotations

import functools
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
QUERY_BLOCK = 512


def _dot(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, positions, theta):
    """x: (T, H, D) rotated at ``positions`` (T,), rotate-half layout."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = positions.astype(F32)[:, None] * inv[None, :]
    cos = jnp.cos(jnp.concatenate([ang, ang], -1))[:, None, :]
    sin = jnp.sin(jnp.concatenate([ang, ang], -1))[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


LOWER = {"int8": 127.0, "fp8": 448.0}


def round_weights(w: jax.Array, kind: str) -> jax.Array:
    """A weight matrix (in, out) rounded to ``kind`` with one scale per
    output column, returned in float32: symmetric int8, or
    float8_e4m3fn scaled to its largest finite value."""
    scale = jnp.max(jnp.abs(w), axis=0, keepdims=True) / LOWER[kind]
    scale = jnp.where(scale == 0, 1.0, scale)
    if kind == "int8":
        return jnp.round(w / scale) * scale
    return (w / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


@functools.partial(jax.jit, static_argnames=("hq", "hkv", "theta", "eps",
                                             "lower"))
def layer(w: Dict[str, jax.Array], h: jax.Array, *, hq: int, hkv: int,
          theta: float, eps: float, lower: str = "") -> jax.Array:
    """One decoder layer over ``h`` (T, d) f32; T a multiple of the query
    block.  Rows past the real sequence only ever follow it, so causal
    attention keeps them out of every real row.  ``lower`` ("int8" or
    "fp8") rounds every weight matrix to that type first: a control's
    lower precision."""
    w = {k: v.astype(F32) for k, v in w.items()}
    if lower:
        w = {k: round_weights(v, lower) if v.ndim == 2 else v
             for k, v in w.items()}
    T = h.shape[0]
    x = rms_norm(h, w["input_layernorm"], eps)
    q = (_dot(x, w["q_proj"]) + w["q_bias"]).reshape(T, hq, -1)
    k = (_dot(x, w["k_proj"]) + w["k_bias"]).reshape(T, hkv, -1)
    v = (_dot(x, w["v_proj"]) + w["v_bias"]).reshape(T, hkv, -1)
    D = q.shape[-1]
    pos = jnp.arange(T)
    q, k = rope(q, pos, theta), rope(k, pos, theta)
    g = hq // hkv

    def block(i):
        qs = jax.lax.dynamic_slice_in_dim(q, i * QUERY_BLOCK, QUERY_BLOCK)
        qs = qs.reshape(QUERY_BLOCK, hkv, g, D)
        s = jnp.einsum("qhgd,khd->hgqk", qs, k, precision=HIGHEST) / np.sqrt(D)
        qpos = i * QUERY_BLOCK + jnp.arange(QUERY_BLOCK)
        s = jnp.where(pos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("hgqk,khd->qhgd", p, v, precision=HIGHEST)
        return o.reshape(QUERY_BLOCK, hq * D)

    o = jax.lax.map(block, jnp.arange(T // QUERY_BLOCK)).reshape(T, hq * D)
    h = h + _dot(o, w["o_proj"])
    x = rms_norm(h, w["post_attention_layernorm"], eps)
    mlp = jax.nn.silu(_dot(x, w["gate_proj"])) * _dot(x, w["up_proj"])
    return h + _dot(mlp, w["down_proj"])


@functools.partial(jax.jit, static_argnames=("eps",))
def logit_gaps(h: jax.Array, norm: jax.Array, head: jax.Array,
               tokens: jax.Array, *, eps: float) -> jax.Array:
    """For rows ``h`` (R, d) and the tokens served after them (R,): how
    far each token's logit lies below the best logit of its row.
    ``head`` is (V, d), the tied embedding."""
    x = rms_norm(h, norm.astype(F32), eps)
    logits = jnp.matmul(x, head.astype(F32).T, precision=HIGHEST)
    tok = jnp.take_along_axis(logits, tokens[:, None], axis=1)[:, 0]
    return jnp.max(logits, axis=1) - tok


def padded_len(t: int) -> int:
    q = 1024 if t <= 4096 else 4096
    return -(-t // q) * q


@functools.partial(jax.jit, static_argnames=("eps",))
def argmax_tokens(h: jax.Array, norm: jax.Array, head: jax.Array, *,
                  eps: float) -> jax.Array:
    x = rms_norm(h, norm.astype(F32), eps)
    return jnp.argmax(jnp.matmul(x, head.astype(F32).T, precision=HIGHEST),
                      axis=1)


ROWS = 256


def hidden(layers, globals_: Dict[str, jax.Array], cfg: Dict,
           seq: Sequence[int], lower: str = "") -> jax.Array:
    """The last layer's output rows for ``seq`` (padded).  ``layers``
    yields each layer's weights (canonical layout) in order."""
    T, Tp = len(seq), padded_len(len(seq))
    ids = jnp.asarray(np.pad(np.asarray(seq, np.int32), (0, Tp - T)))
    h = jnp.take(globals_["embed_tokens"], ids, axis=0).astype(F32)
    kw = dict(hq=cfg["num_attention_heads"], hkv=cfg["num_key_value_heads"],
              theta=float(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]))
    for w in layers:
        h = layer(w, h, lower=lower, **kw)
    return h


def _head(globals_):
    head = globals_.get("lm_head")
    return globals_["embed_tokens"] if head is None else head.T


def _row_blocks(h, n, m):
    """Rows n-1 .. n+m-2 of ``h`` in blocks of ``ROWS``, zero-padded."""
    for s in range(0, m, ROWS):
        k = min(ROWS, m - s)
        r = jax.lax.dynamic_slice_in_dim(h, n - 1 + s, ROWS) \
            if n - 1 + s + ROWS <= h.shape[0] else \
            jnp.pad(h[n - 1 + s:n - 1 + s + k], ((0, ROWS - k), (0, 0)))
        yield s, k, r


def gaps_at(h, globals_, cfg, n: int, tokens: Sequence[int]) -> np.ndarray:
    """Gap of ``tokens[j]`` in the row of ``h`` that predicts position
    n + j."""
    toks = np.asarray(tokens, np.int32)
    eps = float(cfg["rms_norm_eps"])
    out = []
    for s, k, r in _row_blocks(h, n, len(toks)):
        tk = jnp.asarray(np.pad(toks[s:s + k], (0, ROWS - k)))
        out.append(np.asarray(logit_gaps(r, globals_["norm"],
                                         _head(globals_), tk, eps=eps))[:k])
    return np.concatenate(out)


def firsts_at(h, globals_, cfg, n: int, m: int) -> np.ndarray:
    """The token that the rows of ``h`` predicting positions n .. n+m-1
    put first."""
    eps = float(cfg["rms_norm_eps"])
    out = [np.asarray(argmax_tokens(r, globals_["norm"], _head(globals_),
                                    eps=eps))[:k]
           for _, k, r in _row_blocks(h, n, m)]
    return np.concatenate(out)
