"""Jit-facing dispatch wrappers around the attention kernels.

Two execution paths per op, selected by ``impl``:

- ``"xla"``    — the pure-jnp reference implementations from ``ref.py``.
  Cost-analyzable, differentiable, shardable under pjit; the default for
  train/dry-run (on this CPU container it is also the fast path).
- ``"pallas"`` — the Pallas TPU kernels: compiled by Mosaic on a TPU,
  run by the Pallas interpreter on the CPU backend
  (:func:`repro.kernels.resolve_interpret`; ``interpret=None`` everywhere
  by default).  The TPU-native hot path; numerics validated against
  ``ref.py`` in tests.

``decode_attention`` is the op the paper targets: its split count comes
from a frozen :class:`~repro.plan.LaunchPlan` (the paper's
"metadata-enabled path") — passed explicitly via ``plan=`` / legacy
``metadata=``, or injected ambiently by the serve-step builder through
:func:`repro.plan.plan_scope`.  With no frozen plan in reach, the policy
runs at trace time (the paper's weaker "internal heuristic path") using
the policy/num_cores overrides of whatever context-only plan applies.

The old ``DecodeContext`` / ``AttnContext`` dual context stacks are
deprecated shims over the single ``plan_scope`` stack; they keep old
call sites importing (with a ``DeprecationWarning``) but new code should
push a ``LaunchPlan``.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.scheduler_metadata import get_scheduler_metadata
from repro.kernels import ref
from repro.kernels.flash_combine import flash_combine
from repro.kernels.flash_decode import (DEFAULT_BLOCK_K,
                                        flash_decode_partials,
                                        flash_decode_quant_partials)
from repro.kernels.flash_prefill import flash_prefill
from repro.plan import LaunchPlan, current_plan, plan_scope
from repro.sharding.ctx import current_mesh

_DEFAULT_POLICY = "paper"


# ---------------------------------------------------------------------------
# Deprecated context shims (pre-repro.plan API)
# ---------------------------------------------------------------------------


def DecodeContext(policy: str = _DEFAULT_POLICY,
                  num_cores: Optional[int] = None,
                  metadata: Optional[LaunchPlan] = None,
                  min_splits: int = 1,
                  split_constraint: Optional[Callable] = None,
                  seq_shard_mesh: Optional[object] = None,
                  seq_shard_axis: str = "model") -> LaunchPlan:
    """Deprecated: build a :class:`repro.plan.LaunchPlan` instead."""
    warnings.warn(
        "ops.DecodeContext is deprecated; build a repro.plan.LaunchPlan "
        "(via Planner) and enter it with repro.plan.plan_scope",
        DeprecationWarning, stacklevel=2)
    base = metadata if metadata is not None else LaunchPlan(kind="decode")
    return dataclasses.replace(
        base, kind="decode", policy=policy,
        num_cores=num_cores if num_cores is not None else base.num_cores,
        min_splits=min_splits, split_constraint=split_constraint,
        seq_shard_mesh=seq_shard_mesh, seq_shard_axis=seq_shard_axis)


def AttnContext(seq_shard_mesh: Optional[object] = None,
                seq_shard_axis: str = "model") -> LaunchPlan:
    """Deprecated: build a prefill-kind :class:`repro.plan.LaunchPlan`."""
    warnings.warn(
        "ops.AttnContext is deprecated; build a prefill-kind "
        "repro.plan.LaunchPlan and enter it with repro.plan.plan_scope",
        DeprecationWarning, stacklevel=2)
    return LaunchPlan(kind="prefill", seq_shard_mesh=seq_shard_mesh,
                      seq_shard_axis=seq_shard_axis)


def decode_context(ctx: LaunchPlan):
    """Deprecated alias of :func:`repro.plan.plan_scope`."""
    return plan_scope(ctx)


def attention_context(ctx: LaunchPlan):
    """Deprecated alias of :func:`repro.plan.plan_scope`."""
    return plan_scope(ctx)


def current_decode_context() -> LaunchPlan:
    """Deprecated: the ambient decode plan (or an empty one)."""
    plan = current_plan("decode")
    return plan if plan is not None else LaunchPlan(kind="decode")


def current_attention_context() -> LaunchPlan:
    """Deprecated: the ambient prefill plan (or an empty one)."""
    plan = current_plan("prefill")
    return plan if plan is not None else LaunchPlan(kind="prefill")


# ---------------------------------------------------------------------------
# Observability: in-dispatch policy evaluations
# ---------------------------------------------------------------------------

# How many times the split policy ran INSIDE a decode-attention dispatch
# (the paper's weaker "internal heuristic path").  Happens at trace time
# only — num_splits is static — so a jitted metadata-enabled step must
# leave this untouched; tests and benchmarks assert exactly that.
_POLICY_EVALS: int = 0

# The plan the most recent inline evaluation resolved to (regression
# surface for the scope-precedence rules; trace-time only, like the
# counter above).
_LAST_INLINE: Optional[LaunchPlan] = None


def policy_eval_count() -> int:
    return _POLICY_EVALS


def reset_policy_eval_count() -> None:
    global _POLICY_EVALS, _LAST_INLINE
    _POLICY_EVALS = 0
    _LAST_INLINE = None


def last_inline_plan() -> Optional[LaunchPlan]:
    """The frozen plan produced by the most recent in-dispatch policy
    evaluation (None if every launch so far consumed a precomputed plan)."""
    return _LAST_INLINE


def _resolve_policy(scope: Optional[LaunchPlan], plan: Optional[LaunchPlan],
                    policy: str, num_cores: Optional[int]):
    """Policy/num_cores precedence for the inline-heuristic path.

    Call-site kwargs are the base; the ambient scope overrides them; an
    explicit (context-only) plan overrides the scope.  An override's
    policy applies whenever it was deliberately set — i.e. it differs
    from the default OR its num_cores is pinned.  (The old DecodeContext
    keyed the policy override off ``num_cores is not None`` alone, so a
    context with ``policy="tpu_adaptive"`` but no num_cores was silently
    ignored.)
    """
    pol, cores = policy, num_cores
    for over in (scope, plan):
        if over is None:
            continue
        if over.num_cores is not None:
            cores = over.num_cores
        if over.policy != _DEFAULT_POLICY or over.num_cores is not None:
            pol = over.policy
    return pol, cores


# ---------------------------------------------------------------------------
# Paged-KV layout: the layout-aware gather path (repro.cache)
# ---------------------------------------------------------------------------


class PagedKV(NamedTuple):
    """A paged view of one K or V cache tensor, in place of a dense
    ``(B, L, H, D)`` array.

    ``pages`` is the shared page pool ``(P, page, *rest)``; ``page_table``
    maps each batch slot to its pages ``(B, >= num_pages) int32``; and
    ``num_pages`` is the STATIC number of pages the launch attends over
    (the resident-length bucket divided by the page size) — jitted
    callers specialize on it, exactly like ``num_splits``.  Table entries
    past a slot's allocation point at a trash page whose rows sit at
    positions >= the slot's ``kv_len`` and are therefore masked.
    """
    pages: jax.Array
    page_table: jax.Array
    num_pages: int

    @property
    def view_len(self) -> int:
        return self.num_pages * self.pages.shape[1]


def gather_pages(pages: jax.Array, page_table: jax.Array, *,
                 num_pages: int, axis: int = 0) -> jax.Array:
    """Gather a dense per-slot view from a page pool.

    ``pages``: ``(..., P, page, *rest)`` with the pool dim at ``axis``;
    ``page_table``: ``(B, >= num_pages) int32``.  Returns
    ``(..., B, num_pages * page, *rest)`` — the first ``num_pages`` pages
    of every slot, concatenated in sequence order.
    """
    pt = jax.lax.slice_in_dim(page_table, 0, num_pages, axis=1)
    g = jnp.take(pages, pt, axis=axis)       # (..., B, n, page, *rest)
    shape = (g.shape[:axis + 1]
             + (num_pages * pages.shape[axis + 1],)
             + g.shape[axis + 3:])
    return g.reshape(shape)


def scatter_pages(pages: jax.Array, view: jax.Array,
                  page_table: jax.Array, *, num_pages: int,
                  axis: int = 0) -> jax.Array:
    """Write a dense per-slot view back into the page pool (inverse of
    :func:`gather_pages`).

    Duplicate table entries (every slot's unallocated tail points at the
    shared trash page) make that one page's content nondeterministic —
    harmless, since trash rows are masked by ``kv_len`` everywhere.
    """
    pt = jax.lax.slice_in_dim(page_table, 0, num_pages, axis=1)
    page = pages.shape[axis + 1]
    vp = view.reshape(view.shape[:axis]
                      + (pt.shape[0], num_pages, page)
                      + view.shape[axis + 2:])
    idx = (slice(None),) * axis + (pt,)
    return pages.at[idx].set(vp.astype(pages.dtype))


def _resolve_paged(x):
    """Dense array -> itself; :class:`PagedKV` -> gathered dense view."""
    if isinstance(x, PagedKV):
        return gather_pages(x.pages, x.page_table, num_pages=x.num_pages)
    return x


# ---------------------------------------------------------------------------
# Full-sequence (train / prefill) attention
# ---------------------------------------------------------------------------


def attention(
    q: jax.Array,            # (B, Lq, Hq, D)
    k: jax.Array,            # (B, Lk, Hkv, D)
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    q_offset: int | jax.Array = 0,
    impl: str = "xla",
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Full (training / prefill) attention.

    An ambient prefill-kind :class:`LaunchPlan` (``plan_scope``) with
    ``seq_shard_mesh`` turns on sequence-parallel attention: the QUERY
    rows shard over ``seq_shard_axis`` and each chip runs blocked flash
    on its chunk with the right ``q_offset`` (K/V stay whole).  This is
    the §Perf fix for head counts that don't divide the model axis
    (MiniCPM3: 40, Whisper: 20).
    """
    scope = current_plan("prefill")
    if (scope is not None and scope.seq_shard_mesh is not None
            and impl in ("xla", "naive") and isinstance(q_offset, int)):
        mesh = scope.seq_shard_mesh
        n = mesh.shape[scope.seq_shard_axis]
        if q.shape[1] % n == 0 and q.shape[1] >= 2 * n:
            return _attention_seqpar(
                q, k, v, causal=causal, window=window, q_offset=q_offset,
                mesh=mesh, axis=scope.seq_shard_axis, impl=impl)
    if impl == "pallas":
        if not isinstance(q_offset, int):
            raise ValueError("pallas prefill path needs a static q_offset")
        kernel = functools.partial(flash_prefill, causal=causal,
                                   window=window, q_offset=q_offset,
                                   interpret=interpret)
        mesh = current_mesh()
        if mesh is not None and mesh.size > 1:
            # GSPMD cannot partition a Mosaic kernel: every chip runs it
            # on the whole prompt, as one chip would
            from jax.sharding import PartitionSpec as P
            kernel = jax.shard_map(kernel, mesh=mesh, in_specs=(P(),) * 3,
                                   out_specs=P(), check_vma=False)
        return kernel(q, k, v)
    if impl == "naive":
        return ref.naive_attention(q, k, v, causal=causal, window=window,
                                   q_offset=q_offset)
    return ref.flash_attention_xla(q, k, v, causal=causal, window=window,
                                   q_offset=q_offset)


def _attention_seqpar(q, k, v, *, causal, window, q_offset, mesh,
                      axis: str, impl: str = "xla") -> jax.Array:
    """Sequence-parallel blocked attention: q rows sharded over ``axis``,
    each chip runs local flash on its chunk with the global offset."""
    from jax.sharding import PartitionSpec as P

    B, Lq, Hq, D = q.shape
    n = mesh.shape[axis]
    C = Lq // n
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    bspec = data_axes if (data_axes and B % _prod(
        mesh.shape[a] for a in data_axes) == 0) else None

    def body(qc, kf, vf):
        i = jax.lax.axis_index(axis)
        # dynamic global offset of this chunk's first query row
        off = q_offset + i * C
        if impl == "naive":                  # probe path: exact counting
            return ref.naive_attention(qc, kf, vf, causal=causal,
                                       window=window, q_offset=off)
        return ref.flash_attention_xla(qc, kf, vf, causal=causal,
                                       window=window, q_offset=off)

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(bspec, axis, None, None),
                  P(bspec, None, None, None),
                  P(bspec, None, None, None)),
        out_specs=P(bspec, axis, None, None),
        check_vma=False)
    return fn(q, k, v)


# ---------------------------------------------------------------------------
# Split-KV decode attention
# ---------------------------------------------------------------------------


def decode_attention(
    q: jax.Array,            # (B, Hq, D) — one new token per sequence
    k: jax.Array,            # (B, Lk, Hkv, D) padded KV cache
    v: jax.Array,
    kv_len: jax.Array,       # (B,) int32 valid lengths
    *,
    k_scale: Optional[jax.Array] = None,   # (B, Lk, Hkv) quantized-KV scales
    v_scale: Optional[jax.Array] = None,
    plan: Optional[LaunchPlan] = None,
    metadata: Optional[LaunchPlan] = None,   # legacy alias of ``plan``
    use_ctx_metadata: bool = True,
    policy: str = _DEFAULT_POLICY,
    num_cores: Optional[int] = None,
    impl: str = "xla",
    interpret: Optional[bool] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    """Split-KV decode attention, split count from the paper's policy.

    A frozen ``plan`` (precomputed :class:`LaunchPlan`) is the paper's
    fast path; otherwise the policy runs at trace time (internal-
    heuristic path).  ``num_splits`` is always a static Python int, so
    XLA / Pallas specialize the schedule on it — changing the policy
    changes the *compiled program*, which is exactly what the dry-run
    measures.

    An ambient plan (:func:`repro.plan.plan_scope`, set by the serving
    engine / serve-step builder) supplies the frozen decision when no
    explicit one is passed, overrides policy / num_cores for inline
    evaluation, and can pin the split axis onto a mesh axis (mesh-level
    sequence split of the KV cache).  ``use_ctx_metadata=False`` opts a
    differently-shaped launch (e.g. encdec cross-attention) out of the
    ambient frozen plan.

    ``k`` / ``v`` may also be :class:`PagedKV` views (the
    ``repro.cache`` paged layout): the launch then attends over the
    gathered resident pages — ``L_K`` is the resident-length bucket, not
    the padded slot capacity, so the split decision and the HBM traffic
    both track what is actually resident.

    Quantized caches (``repro.quant``): pass the per-(row, head) scales
    via ``k_scale`` / ``v_scale`` (dense or ``PagedKV`` views — the
    scale pools page with the data pools).  ``impl="pallas"`` then runs
    the fused kernel (storage-dtype KV blocks dequantized in-register);
    the xla/naive impls dequantize up front and attend the f32 arrays —
    the dequant-then-attend reference the fused path is A/B'd against.
    """
    k = _resolve_paged(k)
    v = _resolve_paged(v)
    k_scale = _resolve_paged(k_scale)
    v_scale = _resolve_paged(v_scale)
    scope = current_plan("decode")
    if plan is None:
        plan = metadata
    if (plan is None or not plan.frozen) and use_ctx_metadata \
            and scope is not None and scope.frozen:
        plan = scope

    B, Hq, D = q.shape
    _, Lk, Hkv, _ = k.shape
    if plan is not None and plan.impl is not None:
        impl = plan.impl
    if plan is None or not plan.frozen:
        global _POLICY_EVALS, _LAST_INLINE
        _POLICY_EVALS += 1
        pol, cores = _resolve_policy(scope, plan, policy, num_cores)
        kwargs = {} if cores is None else {"num_cores": cores}
        plan = get_scheduler_metadata(B, 1, Lk, Hq, Hkv, D, policy=pol,
                                      **kwargs)
        _LAST_INLINE = plan
    s = max(1, min(plan.num_splits, Lk))
    min_splits = max(plan.min_splits,
                     scope.min_splits if scope is not None else 1)
    if min_splits > 1:
        # mesh-level split: round s up to a multiple of the sharded axis so
        # the S axis shards evenly (serving pads caches so min_splits | Lk)
        s = -(-s // min_splits) * min_splits
        s = min(s, Lk)
    split_constraint = plan.split_constraint
    if split_constraint is None and scope is not None:
        split_constraint = scope.split_constraint

    if impl == "pallas":
        assert scale is None, "pallas path computes its own scale"
        if k_scale is not None:
            return _decode_pallas_quant(
                q, k, v, k_scale, v_scale, kv_len, num_splits=s,
                block_k=plan.block_k, interpret=interpret)
        return _decode_pallas(q, k, v, kv_len, num_splits=s,
                              block_k=plan.block_k, interpret=interpret)
    if k_scale is not None:
        # unfused reference: materialize the dequantized cache, then
        # attend it (bit-identical to Quantizer.dequantize + attend)
        k = k.astype(jnp.float32) * k_scale[..., None]
        v = v.astype(jnp.float32) * v_scale[..., None]
    if impl == "naive":
        return ref.naive_decode_attention(q, k, v, kv_len, scale=scale)
    return ref.split_decode_xla(q, k, v, kv_len, s, scale=scale,
                                shard_split=split_constraint)


def decode_attention_quant(
    q: jax.Array,            # (B, Hq, D)
    qkv,                     # repro.quant.QuantizedKV (leaves may be PagedKV)
    kv_len: jax.Array,       # (B,) int32
    **kw,
) -> jax.Array:
    """Split-KV decode over a quantized cache artifact.

    Thin entry point for :class:`repro.quant.QuantizedKV` (or any
    4-sequence ``(k, v, k_scale, v_scale)``): one plan-resolution path
    with :func:`decode_attention`, so quantized launches consume frozen
    plans / ambient scopes / inline policy evaluation identically to
    bf16 ones — the split decision differs only through the workload's
    ``dtype_bytes`` / ``kv_dtype`` family.
    """
    k, v, k_scale, v_scale = qkv
    return decode_attention(q, k, v, kv_len,
                            k_scale=k_scale, v_scale=v_scale, **kw)


def verify_attention(
    q: jax.Array,            # (B, M, Hq, D) — k+1-row verify query block
    k: jax.Array,            # (B, Lk, Hkv, D) cache (or PagedKV view)
    v: jax.Array,
    pos: jax.Array,          # (B,) int32 absolute position of q[:, 0]
    *,
    plan: Optional[LaunchPlan] = None,
    use_ctx_metadata: bool = True,
    policy: str = _DEFAULT_POLICY,
    num_cores: Optional[int] = None,
    impl: str = "xla",
    scale: Optional[float] = None,
) -> jax.Array:
    """Speculative-decoding verify attention: one planned launch scoring
    a block of ``M = k + 1`` query rows per slot (the committed current
    token + k drafts), causal *within* the block at the slot's traced
    absolute offset, full prefix outside it.

    Plans come from the same surfaces as :func:`decode_attention` — an
    explicit frozen ``plan`` (the serving engine's
    ``("verify", k, bucket)`` entries) or the ambient decode-family
    scope; with neither, the split policy runs at trace time on the
    M-row workload and counts as an in-dispatch policy evaluation.
    The k-row query block scales ``num_m_blocks``, so the sequence-aware
    policy sees the occupancy shift speculation buys — that is the
    planning-side point of the verify kind.

    ``pos`` is traced (per-slot offsets differ in a lockstep batch), so
    the pallas/seqpar impls — which need static offsets — fall back to
    the xla reference, mirroring ``attention_suffix_prefill``.
    """
    k = _resolve_paged(k)
    v = _resolve_paged(v)
    scope = current_plan("decode")
    if (plan is None or not plan.frozen) and use_ctx_metadata \
            and scope is not None and scope.frozen:
        plan = scope

    B, M, Hq, D = q.shape
    _, Lk, Hkv, _ = k.shape
    if plan is not None and plan.impl is not None:
        impl = plan.impl
    if plan is None or not plan.frozen:
        global _POLICY_EVALS, _LAST_INLINE
        _POLICY_EVALS += 1
        pol, cores = _resolve_policy(scope, plan, policy, num_cores)
        kwargs = {} if cores is None else {"num_cores": cores}
        plan = get_scheduler_metadata(B, M, Lk, Hq, Hkv, D, policy=pol,
                                      **kwargs)
        _LAST_INLINE = plan
    s = max(1, min(plan.num_splits, Lk))
    if impl in ("pallas", "seqpar"):
        impl = "xla"                     # traced per-slot offsets
    if impl == "naive":
        tv = pos.astype(jnp.int32)
        lens = tv[:, None] + jnp.arange(M, dtype=jnp.int32)[None, :] + 1

        def row(qj, lenj):
            return ref.naive_decode_attention(
                qj, k, v, jnp.clip(lenj, 1, Lk), scale=scale)

        return jax.vmap(row, in_axes=(1, 1), out_axes=1)(q, lens)
    return ref.verify_decode_xla(q, k, v, pos, s, scale=scale)


def seq_shard_plan(plan: Optional[LaunchPlan] = None
                   ) -> Optional[LaunchPlan]:
    """The plan that puts a decode's cache write on a sequence-sharded
    mesh, or None: ``plan`` where it has a ``seq_shard_mesh`` (an
    explicit plan overrides the ambient scope, the precedence of
    :func:`decode_attention`), else the ambient decode scope where it
    has one."""
    for p in (plan, current_plan("decode")):
        if p is not None and p.seq_shard_mesh is not None:
            return p
    return None


def decode_attention_update(
    q: jax.Array,            # (B, Hq, Dq) — new token's queries (UNscaled)
    cache_k: jax.Array,      # (B, L, Hkv, Dk)
    cache_v: Optional[jax.Array],   # (B, L, Hkv, Dv) or None (MLA: v ⊂ k)
    k_new: jax.Array,        # (B, Hkv, Dk)
    v_new: Optional[jax.Array],
    t: jax.Array,            # (B,) int32 write positions
    kv_len: jax.Array,       # (B,) int32 valid lengths AFTER the write
    *,
    v_width: Optional[int] = None,  # MLA: v = k[..., :v_width]
    scale: Optional[float] = None,
    plan: Optional[LaunchPlan] = None,
    metadata: Optional[LaunchPlan] = None,   # legacy alias of ``plan``
    use_ctx_metadata: bool = True,
    policy: str = _DEFAULT_POLICY,
    num_cores: Optional[int] = None,
    impl: Optional[str] = None,     # None = xla (a plan's impl overrides)
    interpret: Optional[bool] = None,
    quant: Optional[dict] = None,   # quantized cache: {"k_s","v_s","k_ns","v_ns"}
) -> tuple:
    """Fused cache-write + split decode attention.

    Default path: functional update then :func:`decode_attention` (GSPMD
    decides the collectives).  When the ambient plan has
    ``seq_shard_mesh``, the fused shard_map path runs instead: each chip
    writes only its own cache shard and computes a partial softmax over
    it; the chips all-gather their partials and merge them with
    :func:`ref.lse_combine` — the paper's split-KV combine as explicit
    mesh collectives, in the one-device combine's order.

    Returns (out (B, Hq, Dv), new_cache_k, new_cache_v).
    """
    if plan is None:
        plan = metadata
    shard = seq_shard_plan(plan)
    if shard is not None:
        return _decode_seqsharded(
            q, cache_k, cache_v, k_new, v_new, t, kv_len,
            mesh=shard.seq_shard_mesh, axis=shard.seq_shard_axis,
            v_width=v_width, scale=scale, quant=quant)

    # functional update + policy-split attention (auto-SPMD path)
    def upd(c, new, ti):
        return jax.lax.dynamic_update_slice(
            c, new[None].astype(c.dtype),
            (ti, jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32)))

    def upd2(c, new, ti):
        return jax.lax.dynamic_update_slice(
            c, new[None].astype(c.dtype), (ti, jnp.zeros((), jnp.int32)))

    cache_k = jax.vmap(upd)(cache_k, k_new, t)
    if cache_v is not None:
        cache_v = jax.vmap(upd)(cache_v, v_new, t)
    if quant is not None:
        k_s = jax.vmap(upd2)(quant["k_s"], quant["k_ns"], t)
        v_s = jax.vmap(upd2)(quant["v_s"], quant["v_ns"], t)
        # scales ride into decode_attention: xla/naive dequantize up
        # front (the old dequant-then-attend, numerics unchanged) while
        # a plan carrying impl="pallas" hits the fused in-register path
        out = decode_attention(q, cache_k, cache_v, kv_len,
                               k_scale=k_s, v_scale=v_s,
                               scale=scale, plan=plan,
                               use_ctx_metadata=use_ctx_metadata,
                               policy=policy, num_cores=num_cores,
                               impl=impl or "xla", interpret=interpret)
        return out, cache_k, cache_v, k_s, v_s
    v_used = cache_v if cache_v is not None else cache_k[..., :v_width]
    out = decode_attention(q, cache_k, v_used, kv_len, scale=scale,
                           plan=plan,
                           use_ctx_metadata=use_ctx_metadata,
                           policy=policy, num_cores=num_cores,
                           impl=impl or "xla", interpret=interpret)
    return out, cache_k, cache_v


def _decode_seqsharded(q, cache_k, cache_v, k_new, v_new, t, kv_len, *,
                       mesh, axis: str, v_width: Optional[int],
                       scale: Optional[float],
                       quant: Optional[dict] = None) -> tuple:
    from jax.sharding import PartitionSpec as P

    B, Hq, Dq = q.shape
    _, L, Hkv, Dk = cache_k.shape
    g = Hq // Hkv
    n = mesh.shape[axis]
    assert L % n == 0, f"cache len {L} must divide the {axis} axis ({n})"
    C = L // n
    scale = scale if scale is not None else Dq ** -0.5
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    bspec = data_axes if (data_axes and B % _prod(
        mesh.shape[a] for a in data_axes) == 0) else None

    cache_spec = P(bspec, axis, None, None)
    sc_spec = P(bspec, axis, None)
    vec_spec = P(bspec, None, None)
    hvec_spec = P(bspec, None)
    scal_spec = P(bspec)

    def upd(c, new, ti, ok):
        zeros = (jnp.zeros((), jnp.int32),) * (c.ndim - 1)
        newc = jax.lax.dynamic_update_slice(
            c, new[None].astype(c.dtype), (ti,) + zeros)
        return jnp.where(ok, newc, c)

    def core(qb, kf, vf, lenb, i):
        qf = (qb.astype(jnp.float32) * scale).reshape(-1, Hkv, g, Dq)
        pos = i * C + jnp.arange(C)                       # global positions
        valid = pos[None, :] < lenb[:, None]              # (B_loc, C)
        acc, l, m = ref.decode_partial(qf, kf, vf, valid)
        # gather the n chips' partials and merge them with the one-device
        # LSE combine: the merge then sums in the order of
        # ref.split_decode_xla at S = n, so a sequence-sharded decode
        # reproduces the one-chip split decode instead of re-associating
        # its f32 sums (a psum's order is the collective's choice)
        out = ref.lse_combine(jax.lax.all_gather(acc, axis),
                              jax.lax.all_gather(l, axis),
                              jax.lax.all_gather(m, axis))
        Dv = out.shape[-1]
        return out.reshape(-1, Hq, Dv).astype(qb.dtype)

    def body(qb, kc, vc, kn, vn, tb, lenb):
        # kc: (B_loc, C, Hkv, Dk) — this chip's sequence shard
        i = jax.lax.axis_index(axis)
        local_t = tb - i * C                              # (B_loc,)
        in_range = (local_t >= 0) & (local_t < C)
        lt = jnp.clip(local_t, 0, C - 1)
        kc = jax.vmap(upd)(kc, kn, lt, in_range)
        if vc is not None:
            vc = jax.vmap(upd)(vc, vn, lt, in_range)
            vloc = vc
        else:
            vloc = kc[..., :v_width]
        return core(qb, kc, vloc, lenb, i), kc, vc

    def body_q(qb, kc, vc, ksc, vsc, kn, vn, kns, vns, tb, lenb):
        # int8 cache: scales ride along; dequant happens shard-locally
        # (HBM reads stay int8 — the memory-roofline win)
        from repro.models.attention import dequantize_kv
        i = jax.lax.axis_index(axis)
        local_t = tb - i * C
        in_range = (local_t >= 0) & (local_t < C)
        lt = jnp.clip(local_t, 0, C - 1)
        kc = jax.vmap(upd)(kc, kn, lt, in_range)
        vc = jax.vmap(upd)(vc, vn, lt, in_range)
        ksc = jax.vmap(upd)(ksc, kns, lt, in_range)
        vsc = jax.vmap(upd)(vsc, vns, lt, in_range)
        kf = dequantize_kv(kc, ksc)
        vf = dequantize_kv(vc, vsc)
        return core(qb, kf, vf, lenb, i), kc, vc, ksc, vsc

    if quant is not None:
        fn = jax.shard_map(
            body_q, mesh=mesh,
            in_specs=(vec_spec, cache_spec, cache_spec, sc_spec, sc_spec,
                      vec_spec, vec_spec, hvec_spec, hvec_spec,
                      scal_spec, scal_spec),
            out_specs=(vec_spec, cache_spec, cache_spec, sc_spec, sc_spec),
            check_vma=False)
        return fn(q, cache_k, cache_v, quant["k_s"], quant["v_s"],
                  k_new, v_new, quant["k_ns"], quant["v_ns"], t, kv_len)

    if cache_v is not None:
        fn = jax.shard_map(
            body, mesh=mesh,
            in_specs=(vec_spec, cache_spec, cache_spec, vec_spec,
                      vec_spec, scal_spec, scal_spec),
            out_specs=(vec_spec, cache_spec, cache_spec),
            check_vma=False)
        return fn(q, cache_k, cache_v, k_new, v_new, t, kv_len)

    def body_nov(qb, kc, kn, tb, lenb):
        o, ck, _ = body(qb, kc, None, kn, None, tb, lenb)
        return o, ck

    fn = jax.shard_map(
        body_nov, mesh=mesh,
        in_specs=(vec_spec, cache_spec, vec_spec, scal_spec, scal_spec),
        out_specs=(vec_spec, cache_spec),
        check_vma=False)
    out, ck = fn(q, cache_k, k_new, t, kv_len)
    return out, ck, None


def _prod(it) -> int:
    r = 1
    for x in it:
        r *= x
    return r


def _decode_pallas(q, k, v, kv_len, *, num_splits: int,
                   block_k: Optional[int] = None,
                   interpret: Optional[bool]) -> jax.Array:
    """GQA-pack, pad, run the Pallas split kernel, LSE-combine."""
    B, Hq, D = q.shape
    _, Lk, Hkv, _ = k.shape
    g = Hq // Hkv
    scale = D ** -0.5
    qp = (q.astype(jnp.float32) * scale).reshape(B, Hkv, g, D)

    block_k = min(block_k or DEFAULT_BLOCK_K, Lk)
    # pad cache so blocks divide evenly into splits
    blocks = -(-Lk // block_k)
    blocks = -(-blocks // num_splits) * num_splits
    Lp = blocks * block_k
    if Lp != Lk:
        k = jnp.pad(k, ((0, 0), (0, Lp - Lk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, Lp - Lk), (0, 0), (0, 0)))

    acc, l, m = flash_decode_partials(
        qp, k, v, kv_len, num_splits=num_splits,
        block_k=block_k, interpret=interpret)
    out = flash_combine(acc, l, m, interpret=interpret)  # (B, Hkv, g, D)
    return out.reshape(B, Hq, D).astype(q.dtype)


def _decode_pallas_quant(q, k, v, k_scale, v_scale, kv_len, *,
                         num_splits: int, block_k: Optional[int] = None,
                         interpret: Optional[bool]) -> jax.Array:
    """Quantized-cache twin of :func:`_decode_pallas`: GQA-pack, pad the
    storage-dtype cache AND its scale leaves, run the fused in-register
    dequant kernel, LSE-combine.  Padded tail rows carry zero scales but
    are masked by ``kv_len`` regardless (the repo-wide invariant)."""
    B, Hq, D = q.shape
    _, Lk, Hkv, _ = k.shape
    g = Hq // Hkv
    scale = D ** -0.5
    qp = (q.astype(jnp.float32) * scale).reshape(B, Hkv, g, D)

    block_k = min(block_k or DEFAULT_BLOCK_K, Lk)
    blocks = -(-Lk // block_k)
    blocks = -(-blocks // num_splits) * num_splits
    Lp = blocks * block_k
    if Lp != Lk:
        pad4 = ((0, 0), (0, Lp - Lk), (0, 0), (0, 0))
        k = jnp.pad(k, pad4)
        v = jnp.pad(v, pad4)
        k_scale = jnp.pad(k_scale, pad4[:3])
        v_scale = jnp.pad(v_scale, pad4[:3])

    acc, l, m = flash_decode_quant_partials(
        qp, k, v, k_scale, v_scale, kv_len,
        num_splits=num_splits, block_k=block_k, interpret=interpret)
    out = flash_combine(acc, l, m, interpret=interpret)  # (B, Hkv, g, D)
    return out.reshape(B, Hq, D).astype(q.dtype)
