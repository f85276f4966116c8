"""Decoder-only LM assembly: scan-over-layers, all decoder families.

Families covered here: ``dense`` (GQA/MQA/MHA), ``mla`` (MiniCPM3),
``moe`` (attention + MoE FFN), ``ssm`` (Mamba-2), ``hybrid``
(RecurrentGemma RG-LRU/local-attn pattern), ``vlm`` (PaliGemma: projected
patch prefix + gemma backbone).  ``encdec`` (Whisper) lives in
``encdec.py`` and reuses the same blocks.

Layers are grouped into **scan groups**: a (pattern, repeats) pair whose
parameters are stacked along a leading ``layers`` dim and executed with
``jax.lax.scan`` — one HLO block body regardless of depth (94-layer MoE
compiles as fast as a 2-layer one; remat applies to the body).  Uniform
families have one group ``((kind,), L)``; RecurrentGemma has
``((rglru, rglru, attn), 12)`` plus a remainder group.

Decode steps carry a cache pytree with the *same group structure* as the
params, so a single scan walks (layer_params, layer_cache) together.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import ops
from repro.models import attention as attn_mod
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import ssd as ssd_mod
from repro.models.common import (
    ParamSpec,
    apply_mlp,
    apply_norm,
    embed_specs,
    embed_tokens,
    mlp_specs,
    norm_specs,
    stack_specs,
    unembed,
)
from repro.sharding.ctx import shard_activation

_ACT = ("batch", None, None)             # (B, L, d) layout anchor

Pytree = Any


# ---------------------------------------------------------------------------
# Layer plan
# ---------------------------------------------------------------------------


def layer_kinds(cfg: ModelConfig) -> List[str]:
    if cfg.family == "hybrid":
        p = cfg.hybrid.pattern
        return [("attn_window" if p[i % len(p)] == "attn" else p[i % len(p)])
                for i in range(cfg.num_layers)]
    if cfg.family == "ssm":
        return ["ssd"] * cfg.num_layers
    if cfg.family == "mla":
        return ["mla"] * cfg.num_layers
    return ["attn"] * cfg.num_layers      # dense / moe / vlm


def layer_groups(cfg: ModelConfig) -> List[Tuple[Tuple[str, ...], int]]:
    """-> [(pattern, repeats), ...] covering all layers in order."""
    kinds = layer_kinds(cfg)
    if cfg.family == "hybrid":
        p = tuple("attn_window" if k == "attn" else k
                  for k in cfg.hybrid.pattern)
        n_full, rem = divmod(cfg.num_layers, len(p))
        groups: List[Tuple[Tuple[str, ...], int]] = []
        if n_full:
            groups.append((p, n_full))
        if rem:
            groups.append((p[:rem], 1))
        return groups
    return [((kinds[0],), cfg.num_layers)]


# ---------------------------------------------------------------------------
# Per-block specs / apply
# ---------------------------------------------------------------------------


def _has_mlp(cfg: ModelConfig, kind: str) -> bool:
    return kind != "ssd"


def block_specs(cfg: ModelConfig, kind: str) -> Dict[str, Pytree]:
    d = cfg.d_model
    norm_kind = "layer" if cfg.family == "encdec" else "rms"
    specs: Dict[str, Pytree] = {"ln1": norm_specs(d, norm_kind)}
    if kind in ("attn", "attn_window", "xattn"):
        specs["mix"] = attn_mod.attention_specs(cfg)
    elif kind == "mla":
        specs["mix"] = mla_mod.mla_specs(cfg)
    elif kind == "rglru":
        specs["mix"] = rglru_mod.rglru_specs(cfg)
    elif kind == "ssd":
        specs["mix"] = ssd_mod.ssd_specs(cfg)
    else:
        raise ValueError(kind)
    if _has_mlp(cfg, kind):
        specs["ln2"] = norm_specs(d, norm_kind)
        if cfg.moe is not None:
            specs["ffn"] = moe_mod.moe_specs(cfg)
        else:
            specs["ffn"] = mlp_specs(d, cfg.d_ff, cfg.mlp_kind)
    return specs


def _apply_ffn(params, cfg: ModelConfig, x: jax.Array
               ) -> Tuple[jax.Array, jax.Array]:
    if cfg.moe is not None:
        return moe_mod.apply_moe(params, cfg, x)
    return apply_mlp(params, x, cfg.mlp_kind), jnp.zeros((), jnp.float32)


def block_train(params, cfg: ModelConfig, kind: str, x: jax.Array,
                positions: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """One block, full sequence. Returns (x, aux_loss).

    The ``shard_activation`` anchors keep every residual/norm tensor
    pinned to the (batch, *, *) layout through the scan.  NOTE (§Perf
    train iterations 1-2, both refuted): they do NOT move the backward
    TP all-reduces off the norm-vjp's f32 internals — XLA's partial-sum
    placement there is upstream of sharding constraints; forcing bf16
    backward collectives needs a custom_vjp boundary (recorded as future
    work in EXPERIMENTS.md).
    """
    h = shard_activation(apply_norm(params["ln1"], x, cfg.norm_eps), _ACT)
    if kind == "attn":
        mix = attn_mod.attention_train(params["mix"], cfg, h, positions)
    elif kind == "attn_window":
        mix = attn_mod.attention_train(params["mix"], cfg, h, positions,
                                       window=cfg.hybrid.window)
    elif kind == "mla":
        mix = mla_mod.mla_train(params["mix"], cfg, h, positions)
    elif kind == "rglru":
        mix = rglru_mod.apply_rglru_train(params["mix"], cfg, h)
    elif kind == "ssd":
        mix = ssd_mod.apply_ssd_train(params["mix"], cfg, h)
    else:
        raise ValueError(kind)
    x = shard_activation(x + mix, _ACT)
    aux = jnp.zeros((), jnp.float32)
    if _has_mlp(cfg, kind):
        h2 = shard_activation(apply_norm(params["ln2"], x, cfg.norm_eps),
                              _ACT)
        y, aux = _apply_ffn(params["ffn"], cfg, h2)
        x = shard_activation(x + y, _ACT)
    return x, aux


def block_prefill(params, cfg: ModelConfig, kind: str, x: jax.Array,
                  positions: jax.Array, max_len: int,
                  kv_dtype: str = "bfloat16", plan=None
                  ) -> Tuple[jax.Array, jax.Array, Pytree]:
    """One block, full sequence, also emitting its decode cache.

    Returns (x, aux_loss, cache).  ``plan`` is a prefill-kind
    :class:`~repro.plan.LaunchPlan` (fused-admission path).
    """
    h = apply_norm(params["ln1"], x, cfg.norm_eps)
    if kind == "attn":
        mix, cache = attn_mod.attention_prefill(params["mix"], cfg, h,
                                                positions, max_len,
                                                kv_dtype=kv_dtype,
                                                plan=plan)
    elif kind == "attn_window":
        mix, cache = attn_mod.attention_prefill(
            params["mix"], cfg, h, positions,
            min(cfg.hybrid.window, max_len), window=cfg.hybrid.window,
            kv_dtype=kv_dtype, plan=plan)
    elif kind == "mla":
        mix, cache = mla_mod.mla_prefill(params["mix"], cfg, h, positions,
                                         max_len, plan=plan)
    elif kind == "rglru":
        mix, cache = rglru_mod.apply_rglru_train(params["mix"], cfg, h,
                                                 return_cache=True)
    elif kind == "ssd":
        mix, cache = ssd_mod.apply_ssd_train(params["mix"], cfg, h,
                                             return_cache=True)
    else:
        raise ValueError(kind)
    x = x + mix
    aux = jnp.zeros((), jnp.float32)
    if _has_mlp(cfg, kind):
        h2 = apply_norm(params["ln2"], x, cfg.norm_eps)
        y, aux = _apply_ffn(params["ffn"], cfg, h2)
        x = x + y
    return x, aux, cache


def block_cache_specs(cfg: ModelConfig, kind: str, batch: int,
                      max_len: int, kv_dtype: str = "bfloat16") -> Pytree:
    if kind == "attn":
        return attn_mod.kv_cache_specs(cfg, batch, max_len, dtype=kv_dtype)
    if kind == "attn_window":
        return attn_mod.kv_cache_specs(
            cfg, batch, min(cfg.hybrid.window, max_len), dtype=kv_dtype)
    if kind == "mla":
        return mla_mod.mla_cache_specs(cfg, batch, max_len)
    if kind == "rglru":
        return rglru_mod.rglru_cache_specs(cfg, batch)
    if kind == "ssd":
        return ssd_mod.ssd_cache_specs(cfg, batch)
    raise ValueError(kind)


def block_decode(params, cfg: ModelConfig, kind: str, x: jax.Array,
                 cache: Pytree, t: jax.Array, *,
                 plan=None, layer=None) -> Tuple[jax.Array, Pytree]:
    """One block, one token. x: (B, 1, d).

    ``layer`` (``attn`` blocks): ``cache`` is the group's whole stacked
    cache, written in place at that layer (``attention_decode``).

    ``plan`` is the frozen :class:`~repro.plan.LaunchPlan` (static); it
    applies to full-attention layers, which all see the same decode
    shape.  Window layers attend over the ring cache (L_K = window, a
    DIFFERENT shape), so they fall back to an in-line policy evaluation
    on their own static length instead of consuming a plan frozen for
    the full cache (``attention_decode`` drops the frozen decision,
    keeping the policy overrides).
    """
    h = apply_norm(params["ln1"], x, cfg.norm_eps)
    if kind == "attn":
        mix, cache = attn_mod.attention_decode(
            params["mix"], cfg, h, cache, t, plan=plan, layer=layer)
    elif kind == "attn_window":
        mix, cache = attn_mod.attention_decode(
            params["mix"], cfg, h, cache, t, plan=plan,
            window=cfg.hybrid.window)
    elif kind == "mla":
        mix, cache = mla_mod.mla_decode(
            params["mix"], cfg, h, cache, t, plan=plan)
    elif kind == "rglru":
        mix, cache = rglru_mod.apply_rglru_decode(params["mix"], cfg, h,
                                                  cache)
    elif kind == "ssd":
        mix, cache = ssd_mod.apply_ssd_decode(params["mix"], cfg, h, cache)
    else:
        raise ValueError(kind)
    x = x + mix
    if _has_mlp(cfg, kind):
        h2 = apply_norm(params["ln2"], x, cfg.norm_eps)
        y, _ = _apply_ffn(params["ffn"], cfg, h2)
        x = x + y
    return x, cache


# ---------------------------------------------------------------------------
# Whole-model specs
# ---------------------------------------------------------------------------


def lm_param_specs(cfg: ModelConfig) -> Dict[str, Pytree]:
    groups = []
    for pattern, reps in layer_groups(cfg):
        groups.append(tuple(stack_specs(block_specs(cfg, k), reps)
                            for k in pattern))
    specs: Dict[str, Pytree] = {
        "embed": embed_specs(cfg.vocab_size, cfg.d_model,
                             cfg.tie_embeddings),
        "final_norm": norm_specs(
            cfg.d_model, "layer" if cfg.family == "encdec" else "rms"),
        "groups": tuple(groups),
    }
    if cfg.frontend.kind == "vision":
        specs["patch_proj"] = ParamSpec(
            (cfg.frontend.embed_dim, cfg.d_model), (None, "embed"))
    return specs


def lm_cache_specs(cfg: ModelConfig, batch: int, max_len: int,
                   kv_dtype: str = "bfloat16") -> Tuple[Pytree, ...]:
    groups = []
    for pattern, reps in layer_groups(cfg):
        groups.append(tuple(
            stack_specs(block_cache_specs(cfg, k, batch, max_len,
                                          kv_dtype), reps)
            for k in pattern))
    return tuple(groups)


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------


def lm_forward(
    params: Pytree,
    cfg: ModelConfig,
    tokens: jax.Array,                  # (B, L_text)
    *,
    patches: Optional[jax.Array] = None,  # (B, P, embed_dim) for vlm
    block_wrapper: Optional[Callable] = None,  # e.g. jax.checkpoint
) -> Tuple[jax.Array, jax.Array]:
    """-> (logits (B, L_total, vocab) f32, aux_loss scalar)."""
    x = embed_tokens(params["embed"], tokens)
    if cfg.frontend.kind == "vision":
        assert patches is not None, "vlm forward needs patch embeddings"
        pp = patches.astype(x.dtype) @ params["patch_proj"].astype(x.dtype)
        x = jnp.concatenate([pp, x], axis=1)
    x = shard_activation(x, _ACT)
    B, L, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (B, L))

    aux = jnp.zeros((), jnp.float32)
    for gi, (pattern, reps) in enumerate(layer_groups(cfg)):
        gp = params["groups"][gi]

        def body(carry, layer_params, pattern=pattern):
            xc, auxc = carry
            xc = shard_activation(xc, _ACT)
            for ki, kind in enumerate(pattern):
                xc, a = block_train(layer_params[ki], cfg, kind, xc,
                                    positions)
                auxc = auxc + a
            return (shard_activation(xc, _ACT), auxc), None

        if block_wrapper is not None:
            body = block_wrapper(body)
        if cfg.scan_layers:
            (x, aux), _ = jax.lax.scan(body, (x, aux), gp)
        else:                      # roofline probe: unrolled layers
            for r in range(reps):
                (x, aux), _ = body((x, aux),
                                   jax.tree.map(lambda a: a[r], gp))

    x = apply_norm(params["final_norm"], x, cfg.norm_eps)
    logits = unembed(params["embed"], x)
    logits = shard_activation(logits, ("batch", None, "vocab"))
    return logits, aux


# ---------------------------------------------------------------------------
# Prefill: forward + decode caches in one pass
# ---------------------------------------------------------------------------


def lm_prefill(
    params: Pytree,
    cfg: ModelConfig,
    tokens: jax.Array,                  # (B, L_text)
    max_len: int,
    *,
    patches: Optional[jax.Array] = None,
    kv_dtype: str = "bfloat16",
) -> Tuple[jax.Array, Tuple[Pytree, ...]]:
    """-> (last-position logits (B, vocab) f32, decode caches).

    The caches are laid out exactly as ``lm_decode_step`` consumes them;
    decoding continues at position t = L_total.
    """
    x = embed_tokens(params["embed"], tokens)
    if cfg.frontend.kind == "vision":
        assert patches is not None
        pp = patches.astype(x.dtype) @ params["patch_proj"].astype(x.dtype)
        x = jnp.concatenate([pp, x], axis=1)
    x = shard_activation(x, _ACT)
    B, L, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (B, L))

    caches = []
    for gi, (pattern, reps) in enumerate(layer_groups(cfg)):
        gp = params["groups"][gi]

        def body(xc, layer_params, pattern=pattern):
            xc = shard_activation(xc, _ACT)
            new_lc = []
            for ki, kind in enumerate(pattern):
                xc, _, c = block_prefill(layer_params[ki], cfg, kind, xc,
                                         positions, max_len, kv_dtype)
                new_lc.append(c)
            return shard_activation(xc, _ACT), tuple(new_lc)

        if cfg.scan_layers:
            x, gc = jax.lax.scan(body, x, gp)
        else:
            outs = []
            for r in range(reps):
                x, c = body(x, jax.tree.map(lambda a: a[r], gp))
                outs.append(c)
            gc = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
        caches.append(gc)

    x = apply_norm(params["final_norm"], x, cfg.norm_eps)
    logits = unembed(params["embed"], x[:, -1:])[:, 0]
    return logits, tuple(caches)


# ---------------------------------------------------------------------------
# Fused single-slot prefill (serving admission)
# ---------------------------------------------------------------------------


def write_cache_slot(caches: Pytree, new: Pytree, slot: jax.Array) -> Pytree:
    """Write a batch-1 cache pytree into slot ``slot`` of a multi-slot one.

    Every layer-stacked cache leaf carries batch at axis 1 —
    ``(layers, B, ...)`` — for all families (``stack_specs`` prepends
    the layers dim to per-block ``(B, ...)`` leaves), so one
    ``dynamic_update_slice`` per leaf covers the whole pytree.
    """
    slot = jnp.asarray(slot, jnp.int32)

    def w(c, n):
        start = (0, slot) + (0,) * (c.ndim - 2)
        return jax.lax.dynamic_update_slice(c, n.astype(c.dtype), start)

    return jax.tree.map(w, caches, new)


def lm_prefill_view(
    params: Pytree,
    cfg: ModelConfig,
    tokens: jax.Array,                  # (Lb,) int32 — bucket-padded prompt
    length: jax.Array,                  # scalar int32 — true prompt length
    view_len: int,                      # seq extent of the emitted cache
    *,
    plan=None,
    kv_dtype: str = "bfloat16",
) -> Tuple[jax.Array, Tuple[Pytree, ...]]:
    """Fused single-prompt prefill emitting a batch-1 cache VIEW.

    The storage-agnostic half of the admission prefill: one launch
    computes the whole prompt and returns (last-real-position logits
    (vocab,) f32, batch-1 caches of seq extent ``view_len``).  Where the
    view lands is the cache layout's business — :func:`lm_prefill_slot`
    writes it dense via :func:`write_cache_slot`; the paged layout
    scatters it through the slot's page table
    (:meth:`repro.cache.PagedKVCache.write_slot`).

    Padding correctness: positions >= ``length`` hold garbage K/V, but
    causal attention keeps them out of every real position's output, the
    decode step masks them via ``kv_len = t + 1``, and decoding
    overwrites row ``length`` onward before it ever becomes attendable.
    Families with recurrent per-token state (ssd / rglru) would fold the
    pad garbage into their carried state, so they are gated out at the
    :class:`~repro.models.registry.Model` facade.
    """
    x = embed_tokens(params["embed"], tokens[None])      # (1, Lb, d)
    _, L, _ = x.shape
    positions = jnp.arange(L, dtype=jnp.int32)[None]

    new_groups = []
    for gi, (pattern, reps) in enumerate(layer_groups(cfg)):
        gp = params["groups"][gi]

        def body(xc, layer_params, pattern=pattern):
            new_lc = []
            for ki, kind in enumerate(pattern):
                xc, _, c = block_prefill(layer_params[ki], cfg, kind, xc,
                                         positions, view_len, kv_dtype,
                                         plan=plan)
                new_lc.append(c)
            return xc, tuple(new_lc)

        if cfg.scan_layers:
            x, gc = jax.lax.scan(body, x, gp)
        else:
            outs = []
            for r in range(reps):
                x, c = body(x, jax.tree.map(lambda a: a[r], gp))
                outs.append(c)
            gc = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
        new_groups.append(gc)

    xl = jax.lax.dynamic_slice_in_dim(x, length - 1, 1, axis=1)
    xl = apply_norm(params["final_norm"], xl, cfg.norm_eps)
    logits = unembed(params["embed"], xl)[0, 0]          # (vocab,)
    return logits, tuple(new_groups)


def lm_prefill_slot(
    params: Pytree,
    cfg: ModelConfig,
    caches: Tuple[Pytree, ...],
    tokens: jax.Array,                  # (Lb,) int32 — bucket-padded prompt
    slot: jax.Array,                    # scalar int32 — target decode slot
    length: jax.Array,                  # scalar int32 — true prompt length
    max_len: int,
    *,
    plan=None,
    kv_dtype: str = "bfloat16",
) -> Tuple[jax.Array, Tuple[Pytree, ...]]:
    """Prefill one prompt into slot ``slot`` of an existing DENSE cache.

    One launch writes the whole prompt's KV rows (O(1) launches per
    admission vs O(prompt_len) teacher-forced decode steps) and returns
    the logits at the last real prompt position, ready to sample the
    first generated token.  Returns (logits (vocab,) f32, caches).
    """
    logits, new = lm_prefill_view(params, cfg, tokens, length, max_len,
                                  plan=plan, kv_dtype=kv_dtype)
    return logits, write_cache_slot(caches, new, slot)


def block_suffix_prefill(params, cfg: ModelConfig, x: jax.Array,
                         cache: Pytree, start: jax.Array, *,
                         kv_dtype: str = "bfloat16", plan=None
                         ) -> Tuple[jax.Array, Pytree]:
    """One full-attention block over an unshared suffix (prefix sharing).

    Consumes AND updates the layer's batch-1 cache view: rows
    [0, start) arrive resident from adopted pages, the suffix's K/V is
    placed at [start, start + M).  Only ``attn`` blocks exist here —
    the registry gates prefix sharing to uniform full-attention
    families (windowed/recurrent blocks carry order-dependent state a
    row-offset restart cannot reproduce).
    """
    h = apply_norm(params["ln1"], x, cfg.norm_eps)
    mix, cache = attn_mod.attention_suffix_prefill(
        params["mix"], cfg, h, cache, start, kv_dtype=kv_dtype, plan=plan)
    x = x + mix
    h2 = apply_norm(params["ln2"], x, cfg.norm_eps)
    y, _ = _apply_ffn(params["ffn"], cfg, h2)
    return x + y, cache


def lm_prefill_suffix_view(
    params: Pytree,
    cfg: ModelConfig,
    caches: Tuple[Pytree, ...],         # batch-1 views, prefix resident
    tokens: jax.Array,                  # (Mb,) int32 — bucket-padded suffix
    start: jax.Array,                   # scalar int32 — first suffix row
    length: jax.Array,                  # scalar int32 — TOTAL prompt length
    *,
    plan=None,
    kv_dtype: str = "bfloat16",
) -> Tuple[jax.Array, Tuple[Pytree, ...]]:
    """Suffix-only admission prefill (prefix sharing).

    The counterpart of :func:`lm_prefill_view` when rows [0, start) of
    the slot already hold a shared prefix's K/V: one launch computes
    only the ``length - start`` unshared rows (bucket-padded to ``Mb``),
    attending over prefix + suffix through the causal ``q_offset`` mask,
    and places their K/V into the passed-in cache views.  Returns
    (logits at prompt row ``length - 1`` (vocab,) f32, updated views).

    Like :func:`lm_decode_step` this scans (params, cache) together —
    the view is an input, not an output, because the prefix rows must
    flow through.  Padding rows >= ``length - start`` hold garbage but
    land at key positions no real query attends, exactly the
    ``lm_prefill_view`` padding argument shifted by ``start``.
    """
    x = embed_tokens(params["embed"], tokens[None])      # (1, Mb, d)

    new_groups = []
    for gi, (pattern, reps) in enumerate(layer_groups(cfg)):
        gp = params["groups"][gi]
        gc = caches[gi]
        assert pattern == ("attn",), \
            f"suffix prefill supports uniform attn stacks, got {pattern}"

        def body(xc, scanned):
            layer_params, layer_cache = scanned
            xc, c = block_suffix_prefill(layer_params[0], cfg, xc,
                                         layer_cache[0], start,
                                         kv_dtype=kv_dtype, plan=plan)
            return xc, (c,)

        if cfg.scan_layers:
            x, gc = jax.lax.scan(body, x, (gp, gc))
        else:
            outs = []
            for r in range(reps):
                x, c = body(x, jax.tree.map(lambda a: a[r], (gp, gc)))
                outs.append(c)
            gc = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
        new_groups.append(gc)

    xl = jax.lax.dynamic_slice_in_dim(x, length - 1 - start, 1, axis=1)
    xl = apply_norm(params["final_norm"], xl, cfg.norm_eps)
    logits = unembed(params["embed"], xl)[0, 0]          # (vocab,)
    return logits, tuple(new_groups)


# ---------------------------------------------------------------------------
# Decode step
# ---------------------------------------------------------------------------


def _writes_in_place(pattern: Tuple[str, ...], plan=None) -> bool:
    """Whether a decode step writes a group's cache in place: a group of
    full-attention layers, whose per-layer cache is the dense ``k``/``v``
    dict (with its scales where quantized), and no sequence-sharded plan
    (explicit or ambient), whose write stays in its ``shard_map``."""
    return pattern == ("attn",) and ops.seq_shard_plan(plan) is None


def decode_inplace_layers(cfg: ModelConfig, plan=None) -> int:
    """How many layers :func:`lm_decode_step` writes in place under
    ``plan``; the others go through the scan's outputs.  Encoder-decoder
    models decode through ``encdec.py``'s own scan."""
    if cfg.family == "encdec":
        return 0
    return sum(reps for pattern, reps in layer_groups(cfg)
               if _writes_in_place(pattern, plan))


def decode_cache_update(cfg: ModelConfig, plan=None) -> str:
    """The decode step's cache-update path under ``plan``: ``"in_place"``
    (the layer scan carries the stacked cache and writes one row per
    slot and layer) or ``"scan"`` (each layer's cache goes in as the
    scan's input and comes back rewritten as its output)."""
    return "in_place" if decode_inplace_layers(cfg, plan) else "scan"


def _decode_group_in_place(gp, cfg: ModelConfig, gc, x: jax.Array,
                           t: jax.Array, reps: int, plan=None):
    """One ``("attn",)`` group's decode with its stacked cache in the
    scan's carry, next to the layer index: each layer writes its new rows
    into the stack in place and attends the layer sliced out of it, so
    the cache is neither copied whole nor rebuilt through the scan's
    outputs."""
    def body(carry, layer_params):
        xc, stack, layer = carry
        xc = shard_activation(xc, _ACT)
        xc, stack = block_decode(layer_params[0], cfg, "attn", xc, stack,
                                 t, plan=plan, layer=layer)
        return (shard_activation(xc, _ACT), stack, layer + 1), None

    stack = gc[0]
    if cfg.scan_layers:
        (x, stack, _), _ = jax.lax.scan(body, (x, stack, jnp.int32(0)), gp)
    else:
        for r in range(reps):
            (x, stack, _), _ = body((x, stack, r),
                                    jax.tree.map(lambda a: a[r], gp))
    return x, (stack,)


def lm_decode_step(
    params: Pytree,
    cfg: ModelConfig,
    caches: Tuple[Pytree, ...],
    token: jax.Array,                   # (B,) int32 — the new token
    t: jax.Array,                       # scalar int32 — its position
    *,
    plan=None,
) -> Tuple[jax.Array, Tuple[Pytree, ...]]:
    """One decode step. Returns (logits (B, vocab) f32, new caches).

    ``plan``: precomputed :class:`~repro.plan.LaunchPlan` (the
    metadata-enabled path); threaded into every attention block so the
    split policy never runs inside this (traced) function.  Groups of
    full-attention layers write the cache in place
    (:func:`decode_cache_update`).
    """
    x = embed_tokens(params["embed"], token[:, None])    # (B, 1, d)
    x = shard_activation(x, _ACT)

    new_caches = []
    for gi, (pattern, reps) in enumerate(layer_groups(cfg)):
        gp = params["groups"][gi]
        gc = caches[gi]
        if _writes_in_place(pattern, plan):
            x, nc = _decode_group_in_place(gp, cfg, gc, x, t, reps,
                                           plan=plan)
            new_caches.append(nc)
            continue

        def body(xc, scanned, pattern=pattern):
            layer_params, layer_cache = scanned
            xc = shard_activation(xc, _ACT)
            new_lc = []
            for ki, kind in enumerate(pattern):
                xc, c = block_decode(layer_params[ki], cfg, kind, xc,
                                     layer_cache[ki], t, plan=plan)
                new_lc.append(c)
            return shard_activation(xc, _ACT), tuple(new_lc)

        if cfg.scan_layers:
            x, nc = jax.lax.scan(body, x, (gp, gc))
        else:
            outs = []
            for r in range(reps):
                x, c = body(x, jax.tree.map(lambda a: a[r], (gp, gc)))
                outs.append(c)
            nc = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
        new_caches.append(nc)

    x = apply_norm(params["final_norm"], x, cfg.norm_eps)
    logits = unembed(params["embed"], x)[:, 0]           # (B, vocab)
    logits = shard_activation(logits, ("batch", "vocab"))
    return logits, tuple(new_caches)


# ---------------------------------------------------------------------------
# Speculative verify step (repro.spec)
# ---------------------------------------------------------------------------


def block_verify(params, cfg: ModelConfig, x: jax.Array,
                 cache: Pytree, t: jax.Array, *,
                 plan=None) -> Tuple[jax.Array, Pytree]:
    """One full-attention block over an M-row verify block. x: (B, M, d).

    Only ``attn`` blocks exist here — the registry gates speculation to
    uniform full-attention families, the same restriction as prefix
    sharing: windowed ring caches and recurrent state cannot roll back
    rejected rows by truncating ``kv_len``.
    """
    h = apply_norm(params["ln1"], x, cfg.norm_eps)
    mix, cache = attn_mod.attention_verify(params["mix"], cfg, h, cache, t,
                                           plan=plan)
    x = x + mix
    if _has_mlp(cfg, "attn"):
        h2 = apply_norm(params["ln2"], x, cfg.norm_eps)
        y, _ = _apply_ffn(params["ffn"], cfg, h2)
        x = x + y
    return x, cache


def lm_verify_step(
    params: Pytree,
    cfg: ModelConfig,
    caches: Tuple[Pytree, ...],
    tokens: jax.Array,                  # (B, M) int32 — current + drafts
    t: jax.Array,                       # (B,) int32 — each slot's position
    *,
    plan=None,
) -> Tuple[jax.Array, Tuple[Pytree, ...]]:
    """Speculative verify: score M = k + 1 rows per slot in one launch.

    The multi-token sibling of :func:`lm_decode_step`: ``tokens[:, 0]``
    is each slot's committed current token, ``tokens[:, 1:]`` the k
    drafts, and row ``j`` of the returned logits (B, M, vocab) is the
    model's next-token distribution after feeding rows [0, j] — the
    teacher-forced scores batched accept/reject consumes.  Every
    attention block attends causal-within-block at the slot's own
    offset through the frozen ``("verify", k, bucket)`` plan.
    """
    x = embed_tokens(params["embed"], tokens)            # (B, M, d)
    x = shard_activation(x, _ACT)

    new_caches = []
    for gi, (pattern, reps) in enumerate(layer_groups(cfg)):
        gp = params["groups"][gi]
        gc = caches[gi]
        assert pattern == ("attn",), \
            f"verify step supports uniform attn stacks, got {pattern}"

        def body(xc, scanned):
            layer_params, layer_cache = scanned
            xc = shard_activation(xc, _ACT)
            xc, c = block_verify(layer_params[0], cfg, xc, layer_cache[0],
                                 t, plan=plan)
            return shard_activation(xc, _ACT), (c,)

        if cfg.scan_layers:
            x, nc = jax.lax.scan(body, x, (gp, gc))
        else:
            outs = []
            for r in range(reps):
                x, c = body(x, jax.tree.map(lambda a: a[r], (gp, gc)))
                outs.append(c)
            nc = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
        new_caches.append(nc)

    x = apply_norm(params["final_norm"], x, cfg.norm_eps)
    logits = unembed(params["embed"], x)                 # (B, M, vocab)
    logits = shard_activation(logits, ("batch", None, "vocab"))
    return logits, tuple(new_caches)
