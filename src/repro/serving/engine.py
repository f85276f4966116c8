"""Request-lifecycle serving engine: submit / step / stream / drain.

A fixed pool of ``B`` decode slots runs in lockstep through plan-
specialized jitted decode steps; each slot carries its own position
(the step takes a (B,) position vector).  The public surface is
request-shaped, the way real metadata-enabled engines (FA3 / vLLM
``get_scheduler_metadata``) are driven — per scheduling step, not per
``generate()`` call:

- :meth:`ServingEngine.submit`  — enqueue a :class:`Request`, get a
  handle back immediately.
- :meth:`ServingEngine.step`    — run one scheduling step (admissions +
  one lockstep decode launch) and return the :class:`Event` list it
  produced (TOKEN per generated token, FINISHED with a
  ``finish_reason``).
- :meth:`ServingEngine.stream`  — iterate one handle's events, pumping
  ``step()`` on demand.
- :meth:`ServingEngine.drain`   — run to completion, return
  :class:`Completion` objects.

Fused bucketed prefill (admission)
----------------------------------
Admitting a request prefills its whole prompt in **O(1) planned
launches** instead of O(prompt_len) teacher-forced decode steps: the
prompt is padded to a ``prefill_bucket``-wide bucket and pushed through
a jitted single-slot prefill (``Model.prefill_slot``) specialized per
bucket.  The prefill launch is planned like any other — a
``kind="prefill"`` :class:`~repro.plan.AttentionSpec` through the same
:class:`~repro.plan.Planner`, resident in the same
:class:`~repro.plan.PlanCache` under ``("prefill", bucket)`` keys — so
``PlanCacheStats`` counts admissions and tests can assert the O(1)
claim structurally.  Families with recurrent per-token state (ssm,
hybrid) or a non-token frontend (vlm) cannot consume a padded prompt in
one pass; they keep the teacher-forcing path
(``prefill_mode="loop"``), which is also the pre-redesign baseline the
serving A/B benchmark measures against.

Sampling
--------
A pluggable :class:`~repro.serving.sampling.Sampler` runs *inside* the
jitted step over per-slot state arrays (temperature / top-k / top-p /
PRNG key), so per-request sampling never recompiles.  Keys derive from
the request's seed and fold in the absolute token position — tokens are
independent of slot packing (``batch_slots`` ∈ {1, 2, 4} agree).

Cache layouts (repro.cache)
---------------------------
The engine no longer owns raw cache arrays: a
:class:`~repro.cache.CacheManager` resolves a
:class:`~repro.cache.CacheSpec` into a layout.  ``dense`` keeps the
pre-redesign ``(layers, B, max_len, ...)`` arrays bit-identically;
``paged`` stores position-linear cache leaves as fixed-size pages with
per-slot page tables.  Under the paged layout every decode launch
gathers a view sized by the RESIDENT-length bucket (``gather_view`` →
model → ``write_token``, writing back only the one row each slot
produced), so mixed-length batches stop paying attention FLOPs/HBM for
the padded tail; admission is gated on free
pages (:meth:`Scheduler.admit_next`'s ``admissible`` hook), and a
mid-generation allocation failure finishes only THAT request with
``finish_reason="cache_capacity"`` — a per-request page-exhaustion
signal instead of the engine-wide ``max_len`` wall.

Metadata-enabled path (paper §5)
--------------------------------
Unchanged from the pre-redesign engine, now owned by the
:class:`~repro.serving.scheduler.Scheduler`: live cache length →
resident bucket → frozen :class:`~repro.plan.LaunchPlan` → per-plan
jitted step, with the policy evaluated **zero** times inside traced
code (``kernels.ops.policy_eval_count`` stays flat — asserted in
tests).  ``use_scheduler_metadata=False`` keeps the paper's weaker
"internal heuristic" path for A/B — one step for all lengths, policy
evaluated at trace time on the PADDED cache length; each such launch
records the resident summary it actually covered in
``PlanCacheStats.fallback_trace`` so A/Bs can attribute it.

Speculative decoding (repro.spec)
---------------------------------
A request opting in via ``SamplingParams.speculation`` (or an engine-
wide ``ServeConfig.speculation`` default) decodes through planned
**verify** launches instead of 1-token decode launches: a host-side
:class:`~repro.spec.Drafter` proposes up to ``k`` draft tokens from the
slot's own token history, the model scores the slot's current token
plus all drafts in ONE ``kind="verify"`` launch (planned and frozen
under ``("verify", k, bucket)`` keys in the same PlanCache), and the
sampler accepts the longest valid draft prefix *inside the jitted step*
(argmax match for greedy; standard rejection sampling for sampled
rows).  Accepted rows commit to the paged cache via an accept-masked
multi-row write-back (``PagedKVCache.write_rows``); rejected rows die
by rolling ``kv_len`` back (``CacheManager.truncate``) — pages are
never freed mid-request, so page conservation holds under any
accept/reject interleaving.  Greedy speculative output is bit-identical
to plain greedy decode by construction of the acceptance rule.
``PlanCacheStats`` carries acceptance-rate / effective-tokens-per-step
counters; ``SpecConfig.max_rejects`` consecutive zero-accept steps
disable speculation for that request (counted in ``spec_disabled``).

:class:`DecodeEngine` is the legacy batch-synchronous facade
(``generate(requests) -> completions``): a thin wrapper pinned to
``prefill_mode="loop"``, bit-identical to the pre-redesign engine for
greedy decoding.
"""
from __future__ import annotations

import functools
import warnings
from collections import deque
from typing import Any, Deque, Dict, Iterator, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ServeConfig
from repro.core.split_policy import KV_DTYPES, get_policy
from repro.models import lm
from repro.models.registry import Model
from repro.obs import atomic_write_json, resolve_obs
from repro.obs.observer import NO_SPAN
from repro.plan import LaunchPlan, PlanCacheStats, Planner, plan_scope
from repro.serving.events import (
    FINISH_CACHE_CAPACITY,
    FINISH_EOS,
    FINISH_LENGTH,
    FINISH_STOP,
    FINISHED,
    TOKEN,
    Event,
)
from repro.serving.sampling import CategoricalSampler, GreedySampler, \
    Sampler
from repro.serving.scheduler import (
    Completion,
    Request,
    Scheduler,
    SlotState,
)
from repro.sharding.ctx import activation_mesh
from repro.sharding.rules import cache_rules, replicated, \
    serve_param_rules, tree_shardings
from repro.spec import Drafter, SpecConfig, get_drafter

Pytree = Any

PREFILL_MODES = ("auto", "fused", "loop")


PARAM_POLICIES = ("replicated", "tp")


def _named_jit(impl, name: str, **static: Any):
    """``jax.jit`` of ``impl`` with ``static`` bound, donating the cache
    (argument 1), under a stable program name: the XLA module reads
    ``jit_<name>`` where an anonymous partial would read
    ``jit__unknown``."""
    fn = functools.partial(impl, **static)
    fn.__name__ = name
    return jax.jit(fn, donate_argnums=(1,))


class ServingEngine:
    """Request-lifecycle engine over one device (default) or one shard
    sub-mesh of the mesh-native topology (``mesh=...``).

    With a mesh bound, the engine is ONE shard of a
    :class:`~repro.shard.ShardedServingEngine`: params land per
    ``param_policy`` ("replicated" or "tp" over the model axis via
    :func:`~repro.sharding.rules.serve_param_rules`), the dense KV cache
    sequence-shards its L dim over the model axis when the mesh has one
    wider than 1 (``seq_shards > 1``), every plan the scheduler freezes
    carries ``mesh_splits`` provenance (``Planner.mesh_plan``), and
    decode launches take the fused shard_map sequence-sharded path
    (per-chip partial softmax + LSE combine).  ``plan_cache`` shares one
    :class:`~repro.plan.PlanCache` (plans AND compiled steps) across
    same-topology engines; ``shard_id`` labels the cache manager so
    page-conservation failures name the owning shard."""

    def __init__(self, model: Model, scfg: ServeConfig, *,
                 max_len: int = 256, batch_slots: int = 4,
                 policy: Optional[str] = None,
                 sampler: Optional[Sampler] = None,
                 prefill_mode: Optional[str] = None,
                 cache_layout: Optional[str] = None,
                 tune_table: Optional[Any] = None,
                 mesh: Optional[Any] = None,
                 plan_cache: Optional[Any] = None,
                 shard_id: Optional[int] = None,
                 param_policy: str = "replicated",
                 obs: Optional[Any] = None):
        self.model = model
        self.cfg = model.cfg
        self.policy = policy or scfg.split_policy
        self.max_len = max_len
        self.B = batch_slots
        self.use_metadata = scfg.use_scheduler_metadata
        if param_policy not in PARAM_POLICIES:
            raise ValueError(f"unknown param_policy {param_policy!r}; "
                             f"known: {PARAM_POLICIES}")
        self.mesh = mesh
        self.shard_id = shard_id
        self.param_policy = param_policy
        self.seq_shards = int(mesh.shape["model"]) if mesh is not None \
            else 1
        if self.seq_shards > 1:
            if not self.use_metadata:
                raise ValueError(
                    "sequence-sharded decode rides the metadata-enabled "
                    "plan path (the fused shard_map kernel is pinned on "
                    "frozen plans); set use_scheduler_metadata=True or "
                    "a model axis of 1")
            if self.cfg.family not in ("dense", "moe", "mla"):
                raise ValueError(
                    f"{self.cfg.family} models cannot sequence-shard "
                    "their decode (needs a position-linear k/v cache "
                    "consumed by the fused split-KV combine); use a "
                    "model axis of 1")
        if scfg.kv_quant is not None:
            from repro.quant import QUANT_DTYPES
            if scfg.kv_quant not in QUANT_DTYPES:
                raise ValueError(
                    f"unknown kv_quant {scfg.kv_quant!r}; "
                    f"known: {sorted(QUANT_DTYPES)}")
        self.kv_dtype = scfg.kv_quant or scfg.kv_cache_dtype
        self._stats_path = scfg.stats_path

        # repro.obs: an injected observer (a ShardedServingEngine's
        # per-shard view) wins and the injector owns the artifact dumps;
        # otherwise resolve from the config's paths — NULL_OBSERVER when
        # both are unset, so the disabled path costs one attribute read
        # per guarded site and allocates nothing
        self._trace_path = scfg.trace_path
        self._metrics_path = scfg.metrics_path
        if obs is not None:
            self._obs = obs
            self._owns_obs = False
        else:
            self._obs = resolve_obs(scfg)
            self._owns_obs = self._obs.enabled
        # KV bytes one cached prompt row avoids recomputing+storing
        # (prefix-shared-bytes counter): K + V across layers, at the
        # engine's effective storage dtype
        kvh = 1 if self.cfg.mla else self.cfg.num_kv_heads
        self._kv_row_bytes = (2 * self.cfg.num_layers * kvh
                              * self.cfg.resolved_head_dim
                              * KV_DTYPES.get(self.kv_dtype, 2))

        # measured policy (repro.tune): resolve the SplitTable once —
        # an explicit object wins over the config's path.  The path may
        # be a DIRECTORY of tables (a registry): the one whose backend
        # fingerprint matches the live jax.devices() is picked, with a
        # counted-warning fallback when none matches.
        self.tune_table = tune_table
        self._table_registry_fallback = False
        if self.tune_table is None and scfg.tune_table_path:
            from repro.tune import select_table
            self.tune_table, matched = \
                select_table(scfg.tune_table_path)
            self._table_registry_fallback = not matched
        if getattr(get_policy(self.policy), "needs_table", False) \
                and not self.use_metadata:
            raise ValueError(
                f"split_policy={self.policy!r} rides the metadata-enabled "
                "plan path (the SplitTable is consulted when plans "
                "freeze, never at trace time); set "
                "use_scheduler_metadata=True or an analytic policy")
        # CategoricalSampler by default so per-request SamplingParams
        # are always honored; it pays vocab sorts inside every step even
        # for all-greedy traffic, so cost-sensitive greedy-only callers
        # (e.g. the legacy DecodeEngine facade) pass GreedySampler,
        # which instead REJECTS sampled requests at submit()
        self.sampler = sampler if sampler is not None else \
            CategoricalSampler()

        mode = prefill_mode or scfg.prefill_mode
        if mode not in PREFILL_MODES:
            raise ValueError(f"unknown prefill_mode {mode!r}; "
                             f"known: {PREFILL_MODES}")
        if mode == "auto":
            mode = "fused" if (self.use_metadata
                               and model.supports_fused_prefill) else "loop"
        elif mode == "fused":
            if not model.supports_fused_prefill:
                raise ValueError(
                    f"{self.cfg.family} models cannot fused-prefill a "
                    "padded prompt (recurrent state / non-token "
                    "frontend); use prefill_mode='loop'")
            if not self.use_metadata:
                raise ValueError(
                    "fused prefill admission rides the metadata-enabled "
                    "plan path; set use_scheduler_metadata=True or "
                    "prefill_mode='loop'")
        self.prefill_mode = mode

        layout = cache_layout or scfg.cache_layout
        if layout == "paged":
            # (family support is checked by Model.cache_spec below)
            if not self.use_metadata:
                raise ValueError(
                    "the paged cache layout rides the metadata-enabled "
                    "plan path (views are gathered per resident-length "
                    "bucket); set use_scheduler_metadata=True or "
                    "cache_layout='dense'")
            for width in (scfg.seqlen_bucket,
                          scfg.prefill_bucket or scfg.seqlen_bucket):
                if width % scfg.cache_page_size:
                    raise ValueError(
                        f"cache_page_size ({scfg.cache_page_size}) must "
                        f"divide the plan bucket widths (got {width})")
        self.cache_layout = layout

        if self.seq_shards > 1:
            # the fused path shards the cache's L dim (dense: max_len;
            # paged: the gathered view, whose length is a page multiple)
            if layout == "paged":
                if scfg.cache_page_size % self.seq_shards:
                    raise ValueError(
                        f"cache_page_size ({scfg.cache_page_size}) must "
                        f"divide over the model axis "
                        f"({self.seq_shards}) for sequence-sharded "
                        "paged decode")
            elif max_len % self.seq_shards:
                raise ValueError(
                    f"max_len ({max_len}) must divide over the model "
                    f"axis ({self.seq_shards}) for sequence-sharded "
                    "decode")

        self.share_prefix = scfg.share_prefix
        if self.share_prefix:
            if layout != "paged":
                raise ValueError(
                    "share_prefix maps prompts onto existing pages; set "
                    "cache_layout='paged'")
            if self.prefill_mode != "fused":
                raise ValueError(
                    "prefix sharing admits through the fused prefill "
                    "path (suffix prefill is its restartable form); "
                    "prefill_mode='loop' cannot start from adopted pages")
            if not model.supports_prefix_sharing:
                raise ValueError(
                    f"{self.cfg.family} models cannot share prefix pages "
                    "(needs a uniform full-attention stack over the "
                    "standard k/v cache)")
        self._cache_kw = dict(kv_dtype=self.kv_dtype, layout=layout,
                              page_size=scfg.cache_page_size,
                              page_budget=scfg.cache_page_budget,
                              share_prefix=scfg.share_prefix,
                              prefix_capacity=scfg.prefix_capacity,
                              label=(f"shard{shard_id}"
                                     if shard_id is not None else ""))
        # residency bookkeeping + layout resolution (storage arrays stay
        # on the engine for the donation flow; load() re-creates both)
        self.cache = model.cache_manager(self.B, self.max_len,
                                         **self._cache_kw)

        self.sched = Scheduler(
            self.cfg, batch_slots=batch_slots, max_len=max_len,
            policy=self.policy,
            num_splits_override=scfg.num_splits_override,
            bucket_width=scfg.seqlen_bucket,
            prefill_bucket=scfg.prefill_bucket,
            plan_capacity=scfg.plan_cache_capacity,
            cache_layout=layout,
            kv_dtype=self.kv_dtype,
            table=self.tune_table,
            mesh=self.mesh,
            seq_shards=self.seq_shards,
            plans=plan_cache)
        if self._table_registry_fallback:
            self.stats.table_registry_fallbacks += 1
            if self._obs.enabled:
                self._obs.on_warning(
                    "table_registry_fallback",
                    f"no table in {scfg.tune_table_path} matches the "
                    "live backend fingerprint; using the registry's "
                    "first table")

        self._params: Optional[Pytree] = None
        self._caches: Optional[Pytree] = None
        self._state: Dict[str, np.ndarray] = {}
        # device copy of the sampler state, refreshed only when an
        # admission dirties a row (not re-uploaded every decode step)
        self._state_dev: Optional[Dict[str, jax.Array]] = None
        # the ONLY copy of each slot's next write position / next fed
        # token.  Dead-slot entries keep their last values on purpose:
        # the lockstep launch always covers all B rows, and keeping the
        # arrays stable keeps the legacy wrapper bit-identical to the
        # pre-redesign engine (whose arrays behaved the same way)
        self._pos = np.zeros(self.B, np.int32)
        self._next_token = np.zeros(self.B, np.int32)

        # engine-wide speculation default (per-request SamplingParams
        # wins); per-slot drafter instances + disable bookkeeping
        self._default_spec: Optional[SpecConfig] = None
        if scfg.speculation:
            self._default_spec = SpecConfig(
                method=scfg.speculation, k=scfg.speculation_k,
                max_rejects=scfg.speculation_max_rejects)
            self._check_speculation(self._default_spec, "ServeConfig")
        self._spec_cfg: List[Optional[SpecConfig]] = [None] * self.B
        self._drafters: List[Optional[Drafter]] = [None] * self.B
        self._spec_rejects = [0] * self.B

        self._next_handle = 0
        self._steps = 0                 # step() calls (engine.step spans)
        self._queues: Dict[int, Deque[Event]] = {}
        self._completions: Dict[int, Completion] = {}
        self._undrained: List[int] = []
        # once-per-engine warnings, one flag PER capacity condition (the
        # max_len wall and page-pool exhaustion are distinct signals; the
        # first must not suppress the other)
        self._warned_len_capacity = False
        self._warned_page_capacity = False

        # internal-heuristic fallback: ONE step for all lengths, policy
        # evaluated at trace time on the padded cache length (the A/B
        # baseline the paper measures its metadata path against; dense
        # only — paged requires the metadata path, enforced above)
        self._fallback_step = jax.jit(self._decode_impl,
                                      donate_argnums=(1,))
        # slot reset: jitted + donated, one compile for every slot (the
        # pre-redesign engine rebuilt the whole cache pytree with
        # un-jitted .at[i].set per admission — a host round trip per
        # refill).  Paged storage resets only the NON-paged leaves:
        # freshly allocated pages hold stale rows strictly above the new
        # request's kv_len, which every consumer masks.
        self._zero_step = jax.jit(
            self._zero_paged_impl if layout == "paged" else self._zero_impl,
            donate_argnums=(0,))
        # device page copy (copy-on-adopt / copy-on-write): applied
        # between launches, before any gather can read the copied-into
        # page (prefix sharing)
        if layout == "paged":
            self._copy_step = jax.jit(self._copy_page_impl,
                                      donate_argnums=(0,))

    # --- observability ------------------------------------------------------

    @property
    def stats(self) -> PlanCacheStats:
        return self.sched.plans.stats

    @property
    def planner(self) -> Planner:
        return self.sched.planner

    def planned_splits(self) -> Dict[int, int]:
        """bucket -> frozen num_splits, for every resident decode plan."""
        return self.sched.planned_splits()

    def planned_prefill_buckets(self) -> List[int]:
        return self.sched.planned_prefill_buckets()

    def planned_suffix_buckets(self) -> List[Any]:
        return self.sched.planned_suffix_buckets()

    def cache_stats(self) -> Dict[str, Any]:
        """The cache manager's layout / residency / page-pool summary."""
        return self.cache.describe()

    def _metadata(self, t_max: int) -> LaunchPlan:
        """Compute (not cache) the decode launch plan for ``t_max``."""
        return self.sched.decode_plan(t_max)

    def _span(self, name: str, **args: int) -> Any:
        """An ``engine.*`` span in the profiler and the observer's trace
        while observing; a shared no-op otherwise."""
        return self._obs.span(name, **args) if self._obs.enabled \
            else NO_SPAN

    def _traced(self, kind: str) -> Any:
        """Entered by a program's Python body, which runs only while JAX
        traces it: counts the trace and spans it while observing."""
        return self._obs.program_trace(kind) if self._obs.enabled \
            else NO_SPAN

    def _traced_decode(self, plan: Optional[LaunchPlan]) -> Any:
        """:meth:`_traced` for a decode program under ``plan``, which also
        records the path its step updates the cache on."""
        if not self._obs.enabled:
            return NO_SPAN
        cfg = self.model.cfg
        return self._obs.decode_trace(lm.decode_cache_update(cfg, plan),
                                      lm.decode_inplace_layers(cfg, plan))

    # --- state --------------------------------------------------------------

    def load(self, params: Pytree) -> None:
        if self.mesh is not None:
            params = self._place_params(params)
        self._params = params
        # a (re)load is a fresh serve session: new storage AND new
        # residency / page-table state (a stale free list over fresh
        # zeroed storage would leak phantom allocations)
        self.cache = self.model.cache_manager(self.B, self.max_len,
                                              **self._cache_kw)
        self._caches = self.cache.init_storage() if self.mesh is None \
            else self._placed_storage()
        self._state = self.sampler.init_state(self.B)
        self._state_dev = None

    def _place_params(self, params: Pytree) -> Pytree:
        """Land params on the shard sub-mesh: replicated (default — the
        dp regime, one full copy per shard) or TP over the model axis
        (``param_policy="tp"``, the serve-step builder's layout)."""
        if self.param_policy == "tp":
            sh = tree_shardings(self.mesh, params,
                                self.model.param_axes(),
                                serve_param_rules())
            return jax.device_put(params, sh)
        return jax.device_put(params, replicated(self.mesh))

    def _placed_storage(self) -> Pytree:
        """Cache storage made directly on the shard sub-mesh, so that no
        device ever holds more than its own share.  Dense storage
        sequence-shards its L dim over the model axis when the fused
        path is on; the paged page pool stays replicated (the gathered
        view is re-partitioned per launch by the shard_map)."""
        init = self.cache.init_storage
        if self.seq_shards > 1 and not self.cache.is_paged:
            axes = self.model.cache_axes(self.B, self.max_len,
                                         self.kv_dtype)
            sh = tree_shardings(self.mesh, jax.eval_shape(init), axes,
                                cache_rules(True))
        else:
            sh = replicated(self.mesh)
        return jax.jit(init, out_shardings=sh)()

    # --- jitted impls -------------------------------------------------------

    def _decode_impl(self, params, caches, token, t, state,
                     plan: Optional[LaunchPlan] = None):
        with self._traced_decode(plan):
            with activation_mesh(self.mesh):
                logits, caches = self.model.decode_step(
                    params, caches, token, t, plan=plan,
                    policy=self.policy)
            tok = self.sampler.sample(logits, state, t)
        return tok, caches

    def _prefill_impl(self, params, caches, tokens, slot, length, state,
                      plan: Optional[LaunchPlan] = None):
        """Fused single-slot prompt prefill + first-token sampling."""
        with self._traced("prefill"):
            with plan_scope(plan), activation_mesh(self.mesh):
                logits, caches = self.model.prefill_slot(
                    params, caches, tokens, slot, length, self.max_len,
                    plan=plan, kv_dtype=self.kv_dtype)
            tok = self.sampler.sample(logits[None], state,
                                      (length - 1)[None])
        return tok[0], caches

    def _zero_impl(self, caches, slot):
        """Zero slot ``slot`` across every cache leaf (batch axis 1 of
        the layer-stacked pytree).  Recurrent states must not leak
        across requests; zeroing KV is harmless since kv_len masks it."""
        def z(a):
            row = jnp.zeros(a.shape[:1] + (1,) + a.shape[2:], a.dtype)
            start = (0, slot) + (0,) * (a.ndim - 2)
            return jax.lax.dynamic_update_slice(a, row, start)
        return jax.tree.map(z, caches)

    def _zero_paged_impl(self, caches, slot):
        return self.cache.layout.zero_slot(caches, slot)

    # --- jitted impls: paged layout -----------------------------------------

    def _decode_paged_impl(self, params, storage, token, t, state, table,
                           plan: Optional[LaunchPlan] = None,
                           num_pages: int = 1):
        """Lockstep decode over the RESIDENT-bucket gathered view.

        ``num_pages`` is static (one jitted specialization per resident
        bucket, exactly mirroring the per-bucket plan specialization):
        gather the first ``num_pages`` pages of every slot into a dense
        view, run the planned decode step on it, then write back ONLY
        the one row each slot produced (``write_token``).  The launch's
        attention L_K is the view length — FLOPs and HBM both track
        residency, not the padded slot capacity.
        """
        lay = self.cache.layout
        with self._traced_decode(plan):
            view = lay.gather_view(storage, table, num_pages)
            with activation_mesh(self.mesh):
                logits, view = self.model.decode_step(
                    params, view, token, t, plan=plan, policy=self.policy)
            tok = self.sampler.sample(logits, state, t)
            storage = lay.write_token(storage, view, table, t, num_pages)
        return tok, storage

    def _prefill_paged_impl(self, params, storage, tokens, slot, length,
                            state, table,
                            plan: Optional[LaunchPlan] = None,
                            num_pages: int = 1):
        """Fused single-slot prefill straight into the slot's pages."""
        lay = self.cache.layout
        with self._traced("prefill"):
            with plan_scope(plan), activation_mesh(self.mesh):
                logits, view = self.model.prefill_slot_view(
                    params, storage, tokens, slot, length,
                    num_pages * self.cache.spec.page_size,
                    plan=plan, kv_dtype=self.kv_dtype)
            tok = self.sampler.sample(logits[None], state,
                                      (length - 1)[None])
            storage = lay.write_slot(storage, view, table, slot, num_pages)
        return tok[0], storage

    def _verify_impl(self, params, caches, tokens, t, dlen, state,
                     plan: Optional[LaunchPlan] = None):
        """Speculative verify over the dense cache: score (B, M = K+1)
        token rows in one planned launch, accept/reject in-batch.
        ``dlen`` (B,) is each slot's TRUE draft count — ``accepted`` is
        clamped by it so mixed-k padding rows never commit."""
        with self._traced("verify"):
            with activation_mesh(self.mesh):
                logits, caches = self.model.verify_step(
                    params, caches, tokens, t, plan=plan)
            toks, acc = self.sampler.verify(logits, tokens[:, 1:], state,
                                            t)
            acc = jnp.minimum(acc, dlen)
        return toks, acc, caches

    def _verify_paged_impl(self, params, storage, tokens, t, dlen, state,
                           table, plan: Optional[LaunchPlan] = None,
                           num_pages: int = 1):
        """Paged verify: gather the resident view, score the K+1-row
        block, then commit ONLY the pages overlapping each slot's
        accepted rows ``[t, t + accepted + 1)`` — rejected draft rows
        never reach storage (their span pages are redirected to the
        trash page inside the jitted step)."""
        lay = self.cache.layout
        with self._traced("verify"):
            view = lay.gather_view(storage, table, num_pages)
            with activation_mesh(self.mesh):
                logits, view = self.model.verify_step(
                    params, view, tokens, t, plan=plan)
            toks, acc = self.sampler.verify(logits, tokens[:, 1:], state,
                                            t)
            acc = jnp.minimum(acc, dlen)
            storage = lay.write_rows(storage, view, table, t, acc + 1,
                                     tokens.shape[1], num_pages)
        return toks, acc, storage

    def _copy_page_impl(self, storage, src, dst):
        return self.cache.layout.copy_page(storage, src, dst)

    def _apply_copies(self) -> None:
        """Apply the cache manager's queued (src, dst) device page
        copies.  MUST run before any launch that could gather a
        copied-into page — until the copy lands the page holds garbage
        (fresh from the free list)."""
        for src, dst in self.cache.drain_copies():
            self._caches = self._copy_step(self._caches,
                                           jnp.asarray(src, jnp.int32),
                                           jnp.asarray(dst, jnp.int32))

    def _suffix_prefill_paged_impl(self, params, storage, tokens, slot,
                                   start, length, state, table,
                                   plan: Optional[LaunchPlan] = None,
                                   num_pages: int = 1):
        """Suffix-only fused prefill (prefix sharing): gather the slot's
        view — rows [0, start) already resident from adopted pages —
        compute only the unshared suffix against it, scatter back."""
        lay = self.cache.layout
        with self._traced("sprefill"):
            view = lay.slot_view(storage, table, slot, num_pages)
            with plan_scope(plan), activation_mesh(self.mesh):
                logits, view = self.model.prefill_suffix_view(
                    params, view, tokens, start, length,
                    plan=plan, kv_dtype=self.kv_dtype)
            tok = self.sampler.sample(logits[None], state,
                                      (length - 1)[None])
            storage = lay.write_slot(storage, view, table, slot, num_pages)
        return tok[0], storage

    def _build_decode(self, plan: LaunchPlan):
        name = f"decode_b{plan.bucket}_s{plan.num_splits}"
        if self.cache.is_paged:
            return _named_jit(self._decode_paged_impl, name, plan=plan,
                              num_pages=self.cache.spec.view_pages(
                                  plan.bucket))
        return _named_jit(self._decode_impl, name, plan=plan)

    def _build_prefill(self, plan: LaunchPlan):
        name = f"prefill_b{plan.bucket}"
        if self.cache.is_paged:
            return _named_jit(self._prefill_paged_impl, name, plan=plan,
                              num_pages=self.cache.spec.view_pages(
                                  plan.bucket))
        return _named_jit(self._prefill_impl, name, plan=plan)

    def _build_verify(self, plan: LaunchPlan):
        name = (f"verify_k{plan.spec.seqlen_q - 1}_b{plan.bucket}"
                f"_s{plan.num_splits}")
        if self.cache.is_paged:
            return _named_jit(self._verify_paged_impl, name, plan=plan,
                              num_pages=self.cache.spec.view_pages(
                                  plan.bucket))
        return _named_jit(self._verify_impl, name, plan=plan)

    def _build_suffix_prefill(self, plan: LaunchPlan):
        # plan.bucket is the VIEW bucket (whole resident prompt): the
        # gather must span prefix + suffix, like decode's resident view
        return _named_jit(
            self._suffix_prefill_paged_impl,
            f"sprefill_b{plan.bucket}_q{plan.spec.seqlen_q}", plan=plan,
            num_pages=self.cache.spec.view_pages(plan.bucket))

    # --- request lifecycle --------------------------------------------------

    def _check_speculation(self, spec: SpecConfig, who: str) -> None:
        """Shared submit-time / engine-default speculation gate: the
        drafter name must resolve, the family must support multi-row
        verify + kv_len rollback, and the verify launch is planned —
        it cannot ride the internal-heuristic fallback."""
        try:
            get_drafter(spec.method)
        except KeyError as e:
            raise ValueError(f"{who}: {e.args[0]}") from None
        if not self.model.supports_speculation:
            raise ValueError(
                f"{who}: {self.cfg.family} models cannot run speculative "
                "verify steps (needs a uniform full-attention stack over "
                "the standard k/v cache; see Model.supports_speculation)")
        if not self.use_metadata:
            raise ValueError(
                f"{who}: speculative decoding rides the metadata-enabled "
                "plan path (verify launches are planned under "
                "('verify', k, bucket) keys); set "
                "use_scheduler_metadata=True or drop the speculation knob")

    def validate(self, req: Request) -> None:
        """Raise on requests that could never run (no state mutated)."""
        self.sched.validate(req)
        self.sampler.check(req.sampling)
        spec = req.sampling.speculation or self._default_spec
        if spec is not None:
            self._check_speculation(spec, f"request {req.request_id}")
        if self.cache.is_paged:
            # +1: the request must also fit its FIRST decode-token row.
            # A prompt whose pages exactly fill the pool would admit,
            # then deadlock the FIFO head forever — alone in the pool,
            # waiting on a page no finish can ever free.
            need = self.cache.pages_for(len(req.prompt) + 1)
            limit = self.cache.max_request_pages()
            if need > limit:
                raise ValueError(
                    f"request {req.request_id}: prompt plus its first "
                    f"decode row needs {need} pages, page budget allows "
                    f"{limit} per request "
                    f"(page_size={self.cache.spec.page_size})")

    def submit(self, req: Request) -> int:
        """Enqueue a request; returns its handle (admission happens on a
        later :meth:`step`)."""
        handle = self._next_handle
        self.validate(req)                      # incl. sampler.check
        st = self.sched.submit(handle, req)
        self._next_handle += 1
        self._completions[handle] = st.completion
        self._queues[handle] = deque()
        self._undrained.append(handle)
        if self._obs.enabled:
            self._obs.on_submit(handle, req.request_id, len(req.prompt))
        return handle

    def has_work(self) -> bool:
        return self.sched.has_work()

    def step(self) -> List[Event]:
        """One scheduling step: admit pending requests into free slots
        (fused prefill = one planned launch each), then one lockstep
        decode launch over the live slots.  Returns the events."""
        assert self._params is not None, "call load(params) first"
        events: List[Event] = []
        self._steps += 1
        with self._span("engine.step", step_num=self._steps):
            while True:
                adm = self.sched.admit_next(self._admissible)
                if adm is None:
                    break
                self._admit(*adm, events)
            live = self.sched.live()
            if live:
                self._decode_launch(live, events)
            if self._obs.enabled:
                occ, slots = self.sched.occupancy()
                kw = (dict(free_pages=self.cache.free_pages,
                           total_pages=self.cache.spec.total_pages)
                      if self.cache.is_paged else {})
                self._obs.sample_occupancy(occ, slots,
                                           self.sched.queue_depth(), **kw)
        return events

    def _admissible(self, st: SlotState) -> bool:
        """Page-budget admission gate (paged layout; dense always
        admits): the queue head needs its whole prompt's pages free —
        under prefix sharing, only the pages its shared prefix does NOT
        already cover."""
        if self.share_prefix:
            return self.cache.can_admit(st.request.prompt)
        return self.cache.can_reserve(len(st.request.prompt))

    def stream(self, handle: int) -> Iterator[Event]:
        """Iterate one handle's events in order, running :meth:`step`
        whenever the queue is empty.  Single consumer per handle; once
        FINISHED is yielded the handle is fully released (the consumer
        saw every token, so :meth:`drain` will not return it again) —
        a streaming-only server holds no per-request state after the
        stream ends."""
        if handle not in self._queues:
            raise ValueError(
                f"handle {handle} is unknown, already streamed to "
                "FINISHED, or drained")
        while True:
            # re-fetch per event: if a concurrent drain() released the
            # handle between yields, stop instead of replaying tokens
            # the drain already delivered from an orphaned queue
            q = self._queues.get(handle)
            if q is None:
                return
            if q:
                ev = q.popleft()
                yield ev
                if ev.kind == FINISHED:
                    self._queues.pop(handle, None)
                    self._completions.pop(handle, None)
                    if handle in self._undrained:
                        self._undrained.remove(handle)
                    return
            elif not self.sched.has_work():
                return
            else:
                self.step()

    def drain(self) -> List[Completion]:
        """Run to completion; returns every not-yet-drained submitted
        request's :class:`Completion`, sorted by request_id.  Drained
        handles are released — a long-lived engine holds state only for
        in-flight and not-yet-drained requests.  With
        ``ServeConfig.stats_path`` set, the plan-cache counters are
        snapshotted to that path as JSON (:meth:`PlanCacheStats.to_json`)."""
        while self.sched.has_work():
            self.step()
        done = []
        for h in self._undrained:
            done.append(self._completions.pop(h))
            self._queues.pop(h, None)
        self._undrained = []
        done.sort(key=lambda c: c.request_id)
        if self._stats_path:
            self.dump_stats(self._stats_path)
        if self._owns_obs:
            self.dump_obs()
        return done

    def _stats_snapshot(self) -> Dict[str, Any]:
        """The PlanCacheStats JSON snapshot plus this engine's identity
        (policy / shard / measured-table version when loaded)."""
        snap = self.stats.to_json()
        snap["policy"] = self.policy
        if self.shard_id is not None:
            snap["shard"] = self.shard_id
        if self.tune_table is not None:
            snap["table_version"] = self.tune_table.version
        return snap

    def dump_stats(self, path: str) -> None:
        """Atomically write the PlanCacheStats JSON snapshot (temp file
        in the target directory + ``os.replace`` — a concurrent reader
        never sees a torn file)."""
        atomic_write_json(path, self._stats_snapshot())

    def dump_obs(self) -> None:
        """Write the trace / metrics artifacts the engine's own
        ``ServeConfig`` paths asked for (no-op when neither is set or
        the observer was injected — the injector owns the dump)."""
        if self._obs.enabled and (self._trace_path or self._metrics_path):
            self._obs.dump(self._trace_path, self._metrics_path,
                           plan_stats=self._stats_snapshot())

    # --- internals ----------------------------------------------------------

    def _admit(self, i: int, st: SlotState, events: List[Event]) -> None:
        with self._span("engine.admit", request_id=st.request.request_id,
                        prompt_len=len(st.request.prompt)):
            self._admit_body(i, st, events)

    def _admit_body(self, i: int, st: SlotState,
                    events: List[Event]) -> None:
        if self._obs.enabled:           # closes the queue_wait span
            self._obs.on_admit_start(st.handle)
        # the whole prompt's pages are reserved up front (all-or-nothing;
        # _admissible already checked the free list, so this cannot fail)
        if self.share_prefix:
            shared = self.cache.admit_prompt(i, st.request.prompt)
            assert shared is not None, "admission raced the page free list"
        else:
            ok = self.cache.reserve(i, len(st.request.prompt))
            assert ok, "admission raced the page free list"
            shared = 0
        # the reset launch is only needed when the admission path leaves
        # any of the slot's cache rows unwritten: always for loop
        # teacher-forcing, and for fused prefill only when the model
        # says so (encdec's cross-cache leaves stay untouched)
        if (self.prefill_mode != "fused"
                or not self.model.prefill_writes_full_slot):
            self._caches = self._zero_step(
                self._caches, jnp.asarray(i, jnp.int32))
        for name, value in self.sampler.slot_state(
                st.request.sampling).items():
            self._state[name][i] = value
        self._state_dev = None                  # row dirtied: re-upload
        spec = st.request.sampling.speculation or self._default_spec
        self._spec_cfg[i] = spec
        self._drafters[i] = get_drafter(spec.method)() if spec else None
        self._spec_rejects[i] = 0
        if self.prefill_mode == "fused":
            self._admit_fused(i, st, events, shared)
        else:
            st.prompt_left = list(st.request.prompt)
            self._pos[i] = 0
            self._next_token[i] = st.prompt_left.pop(0)
            if self._obs.enabled:       # teacher-forcing admission
                self._obs.on_admit_end(st.handle, "loop")

    def _admit_fused(self, i: int, st: SlotState, events: List[Event],
                     shared: int = 0) -> None:
        """Prefill the prompt in one planned launch; the slot joins the
        decode lockstep already holding its first token.  With
        ``shared`` > 0 (prefix sharing) the launch is the SUFFIX-only
        specialization: rows [0, shared) arrived with the adopted pages,
        so only ``n - shared`` rows are computed — and the launch counts
        under an ``("sprefill", ...)`` key, never ``("prefill", ...)``,
        which is the structural form of the zero-prefill-launches-for-
        shared-pages claim."""
        prompt = st.request.prompt
        n = len(prompt)
        state_row = {k: jnp.asarray(v[i:i + 1])
                     for k, v in self._state.items()}
        if shared:
            # adopted boundary rows travel by device page copy — land
            # them before the suffix launch gathers the slot's view
            self._apply_copies()
            entry = self.sched.suffix_prefill_entry(
                n - shared, n, self._build_suffix_prefill)
            toks = np.zeros(entry.key[2], np.int32)
            toks[:n - shared] = prompt[shared:]
            args = (self._params, self._caches, jnp.asarray(toks),
                    jnp.asarray(i, jnp.int32),
                    jnp.asarray(shared, jnp.int32),
                    jnp.asarray(n, jnp.int32), state_row,
                    self.cache.table_device())
        else:
            entry = self.sched.prefill_entry(n, self._build_prefill)
            toks = np.zeros(entry.key[1], np.int32)
            toks[:n] = prompt
            args = (self._params, self._caches, jnp.asarray(toks),
                    jnp.asarray(i, jnp.int32), jnp.asarray(n, jnp.int32),
                    state_row)
            if self.cache.is_paged:
                args += (self.cache.table_device(),)
        with self._span("engine.prefill.dispatch",
                        bucket=entry.plan.bucket) as dispatch:
            tok, self._caches = entry.step(*args)
        with self._span("engine.prefill.sync") as sync:
            tok = int(tok)              # device sync closes the launch
        if self._obs.enabled:
            self._obs.on_launch("sprefill" if shared else "prefill",
                                entry.key, entry.plan, dispatch.t0, sync.t1)
        self.cache.note_write(i, n - 1)
        if self.share_prefix:
            # index this prompt's (now fully resident) full pages so the
            # NEXT request sharing the prefix adopts instead of computing
            self.cache.register_prefix(i, prompt)
        self._pos[i] = n
        st.completion.steps += 1
        if self._obs.enabled:
            # close the admit span BEFORE emitting: the first token may
            # immediately finish the request, and the request span must
            # contain the admit span
            self._obs.on_admit_end(st.handle,
                                   "suffix" if shared else "full",
                                   shared, shared * self._kv_row_bytes)
        self._emit_token(i, st, tok, events)

    def _decode_launch(self, live, events: List[Event]) -> None:
        drafts = self._collect_drafts(live)
        if drafts:
            self._verify_launch(live, drafts, events)
        else:
            self._plain_launch(live, events)

    def _plain_launch(self, live, events: List[Event]) -> None:
        with self._span("engine.decode.prepare", live=len(live)):
            prepared = self._prepare_decode(live, events)
        if prepared is None:
            return
        live, entry, step, args = prepared
        with self._span("engine.decode.dispatch",
                        bucket=entry.key if entry is not None
                        else self.max_len) as dispatch:
            out, self._caches = step(*args)
        with self._span("engine.decode.sync") as sync:
            out = np.asarray(out)           # host sync closes the launch
        if self._obs.enabled:
            self._obs.on_launch(
                "decode",
                entry.key if entry is not None else None,
                entry.plan if entry is not None else None,
                dispatch.t0, sync.t1)
        with self._span("engine.emit", live=len(live)):
            for i, st in live:
                self._advance(i, st, int(out[i]), events)

    def _prepare_decode(self, live, events: List[Event]):
        """Everything a decode launch needs before its dispatch: pages
        and residency, the plan-cache lookup, the uploads.  Returns
        ``(live, entry, step, args)``, or None when no slot is left."""
        if self.cache.is_paged:
            # every live slot is about to write row _pos[i]: allocate its
            # page now, and finish (only) the requests whose allocation
            # the pool cannot cover — the per-request page-exhaustion
            # signal.  A finish releases pages, so later slots in the
            # same pass may succeed because an earlier one was culled.
            for i, st in live:
                if not self.cache.ensure(i, int(self._pos[i])):
                    self._finish_capacity(i, st, events)
            live = self.sched.live()
            if not live:
                return None
            if self.share_prefix:
                # ensure() may have copy-on-written a shared page;
                # its contents must land before this launch's gather
                self._apply_copies()
        for i, _ in live:                       # residency bookkeeping
            self.cache.note_write(i, int(self._pos[i]))
        tok = jnp.asarray(self._next_token)
        t = jnp.asarray(self._pos)
        t_max = max(int(self._pos[i]) for i, _ in live)
        if self.use_metadata:
            entry = self.sched.decode_entry(t_max, self._build_decode)
            step = entry.step
        else:
            entry = None
            step = self._fallback_step
            # attribute this unplanned launch: the policy saw the PADDED
            # cache length at trace time; record what was resident
            self.stats.record_fallback(t_max + 1, self.max_len)
        if self._state_dev is None:
            self._state_dev = {k: jnp.asarray(v)
                               for k, v in self._state.items()}
        args = (self._params, self._caches, tok, t, self._state_dev)
        if self.cache.is_paged:
            args += (self.cache.table_device(),)
        return live, entry, step, args

    # --- speculative verify launch ------------------------------------------

    def _collect_drafts(self, live) -> Dict[int, List[int]]:
        """Ask each speculating slot's drafter for draft tokens.

        Only slots that are past their prompt, still enabled, and with
        generation budget left get to draft; everything else rides the
        launch as a 1-token row.  Returns only NON-empty drafts — an
        empty dict means this step is a plain decode launch."""
        drafts: Dict[int, List[int]] = {}
        for i, st in live:
            spec, drafter = self._spec_cfg[i], self._drafters[i]
            if spec is None or drafter is None or st.prompt_left:
                continue
            # a draft row past the request's remaining budget could
            # never emit — don't pay to verify it.  The cache-wall bound
            # is one stricter than decode's (max_len - 2): the whole
            # accepted run must land strictly below the capacity-finish
            # position, else a multi-token emit would hit the wall after
            # FEWER tokens than sequential decode (the wall check reads
            # the already-advanced position) — breaking bit-equality
            budget = st.request.max_new_tokens \
                - len(st.completion.tokens) - 1
            room = self.max_len - 2 - int(self._pos[i])
            k = min(spec.k, budget, room)
            if k < 1:
                continue
            history = st.completion.prompt + st.completion.tokens
            d = list(drafter.propose(history, k))[:k]
            if d:
                drafts[i] = d
        return drafts

    def _verify_launch(self, live, drafts: Dict[int, List[int]],
                       events: List[Event]) -> None:
        """One planned verify launch: every live slot rides (lockstep),
        speculating slots carry their drafts, the rest take 1-token
        rows (``dlen = 0`` — behaviorally a decode row)."""
        with self._span("engine.verify.prepare", live=len(live)):
            prepared = self._prepare_verify(live, drafts, events)
        if prepared is None:
            return
        live, entry, args = prepared
        if entry is None:               # every draft culled: plain step
            self._plain_launch(live, events)
            return
        with self._span("engine.verify.dispatch",
                        bucket=entry.plan.bucket) as dispatch:
            out, acc, self._caches = entry.step(*args)
        with self._span("engine.verify.sync") as sync:
            out, acc = np.asarray(out), np.asarray(acc)
        if self._obs.enabled:
            self._obs.on_launch("verify", entry.key, entry.plan,
                                dispatch.t0, sync.t1)
        with self._span("engine.emit", live=len(live)):
            for i, st in live:
                self._advance_verified(i, st, drafts.get(i, []),
                                       int(acc[i]), out[i], events)

    def _prepare_verify(self, live, drafts: Dict[int, List[int]],
                        events: List[Event]):
        """Pages, draft rows and uploads of a verify launch.  Returns
        ``(live, entry, args)``; ``entry`` is None when every draft was
        culled, and the whole is None when no slot is left."""
        if self.cache.is_paged:
            # each slot writes rows [pos, pos + dlen]: allocate row pos
            # like decode (failure finishes the request), then extend
            # page-by-page for the draft rows, truncating the draft at
            # the first row the pool cannot cover (speculation must not
            # steal a page a plain decode step would have had)
            for i, st in list(live):
                p = int(self._pos[i])
                if not self.cache.ensure(i, p):
                    drafts.pop(i, None)
                    self._finish_capacity(i, st, events)
                    continue
                d = drafts.get(i)
                if not d:
                    continue
                kept = 0
                while kept < len(d) and self.cache.ensure(i, p + kept + 1):
                    kept += 1
                drafts[i] = d[:kept]
            live = self.sched.live()
            if not live:
                return None
            if self.share_prefix:
                self._apply_copies()
        K = max((len(drafts.get(i, [])) for i, _ in live), default=0)
        if K == 0:
            return live, None, None
        toks = np.zeros((self.B, K + 1), np.int32)
        dlen = np.zeros(self.B, np.int32)
        toks[:, 0] = self._next_token
        t_max = 0
        for i, _ in live:
            d = drafts.get(i, [])
            dlen[i] = len(d)
            toks[i, 1:1 + len(d)] = d
            self.cache.note_write(i, int(self._pos[i]) + len(d))
            t_max = max(t_max, int(self._pos[i]) + len(d))
        entry = self.sched.verify_entry(K, t_max, self._build_verify)
        if self._state_dev is None:
            self._state_dev = {k: jnp.asarray(v)
                               for k, v in self._state.items()}
        args = (self._params, self._caches, jnp.asarray(toks),
                jnp.asarray(self._pos), jnp.asarray(dlen),
                self._state_dev)
        if self.cache.is_paged:
            args += (self.cache.table_device(),)
        return live, entry, args

    def _advance_verified(self, i: int, st: SlotState, d: List[int],
                          a: int, row: np.ndarray,
                          events: List[Event]) -> None:
        """Post-verify bookkeeping for one slot: commit the accepted
        positions, emit ``d[:a]`` plus the correction/bonus token, roll
        ``kv_len`` back over the rejected rows, and run the
        acceptance-rate / max_rejects accounting."""
        st.completion.steps += 1
        if st.prompt_left:              # loop-mode prefill rider
            self._pos[i] += 1
            self._next_token[i] = st.prompt_left.pop(0)
            return
        self._pos[i] += a + 1
        self.cache.truncate(i, int(self._pos[i]))
        emit = d[:a] + [int(row[a])]
        emitted = 0
        for tok in emit:
            self._emit_token(i, st, tok, events)
            emitted += 1
            if st.completion.finish_reason is not None:
                break
        if not d:                       # non-speculating rider
            return
        spec = self._spec_cfg[i]
        self.stats.record_spec_step(len(d), a, emitted)
        drafter = self._drafters[i]
        if drafter is not None:
            drafter.observe(a, len(d))
        if a == 0:
            self._spec_rejects[i] += 1
            if spec is not None and spec.max_rejects is not None \
                    and self._spec_rejects[i] >= spec.max_rejects:
                # this request's traffic doesn't draft well — stop
                # paying for verify rows it keeps rejecting
                self._spec_cfg[i] = None
                self._drafters[i] = None
                self.stats.record_spec_disabled()
        else:
            self._spec_rejects[i] = 0

    def _advance(self, i: int, st: SlotState, tok_out: int,
                 events: List[Event]) -> None:
        self._pos[i] += 1
        st.completion.steps += 1
        if st.prompt_left:                      # loop-mode prefilling
            self._next_token[i] = st.prompt_left.pop(0)
            return
        self._emit_token(i, st, tok_out, events)

    def _release(self, i: int) -> None:
        """Free a finished request's slot AND its cache residency (page
        allocations return to the pool; the slot's table row goes back
        to the trash page so its lockstep writes land harmlessly)."""
        self.sched.finish(i)
        self.cache.release(i)

    def _finish(self, i: int, st: SlotState, reason: str,
                events: List[Event]) -> None:
        """The one finish protocol: stamp the reason, emit FINISHED to
        both the step's event list and the handle's queue, release the
        slot + its cache residency."""
        comp = st.completion
        comp.finish_reason = reason
        fin = Event(FINISHED, st.handle, comp.request_id,
                    finish_reason=reason)
        events.append(fin)
        self._queues[st.handle].append(fin)
        self._release(i)
        if self._obs.enabled:
            self._obs.on_finish(st.handle, reason)

    def _finish_capacity(self, i: int, st: SlotState,
                         events: List[Event]) -> None:
        """Finish ONE request on page-pool exhaustion (pre-launch: no
        token is produced this step — there is nowhere to write its KV
        row).  The rest of the batch keeps decoding."""
        if not self._warned_page_capacity:
            self._warned_page_capacity = True
            warnings.warn(
                f"request {st.request.request_id} exhausted the KV page "
                f"pool ({self.cache.spec.total_pages} pages of "
                f"{self.cache.spec.page_size}) mid-generation; finishing "
                "with finish_reason='cache_capacity' (further page "
                "exhaustions on this engine are silent)",
                RuntimeWarning, stacklevel=3)
            if self._obs.enabled:
                self._obs.on_warning(
                    "page_capacity",
                    f"request {st.request.request_id} exhausted the "
                    f"{self.cache.spec.total_pages}-page KV pool")
        self._finish(i, st, FINISH_CACHE_CAPACITY, events)

    def _finish_reason(self, i: int, st: SlotState,
                       token: int) -> Optional[str]:
        req = st.request
        if req.eos_id is not None and token == req.eos_id:
            return FINISH_EOS
        if token in req.sampling.stop:
            return FINISH_STOP
        if len(st.completion.tokens) >= req.max_new_tokens:
            return FINISH_LENGTH
        if self._pos[i] >= self.max_len - 1:
            if not self._warned_len_capacity:
                self._warned_len_capacity = True
                warnings.warn(
                    f"request {req.request_id} hit the KV cache capacity "
                    f"(max_len={self.max_len}) mid-generation; finishing "
                    "with finish_reason='cache_capacity' (further "
                    "max_len hits on this engine are silent)",
                    RuntimeWarning, stacklevel=3)
                if self._obs.enabled:
                    self._obs.on_warning(
                        "len_capacity",
                        f"request {req.request_id} hit the KV cache "
                        f"capacity (max_len={self.max_len})")
            return FINISH_CACHE_CAPACITY
        return None

    def _emit_token(self, i: int, st: SlotState, token: int,
                    events: List[Event]) -> None:
        comp = st.completion
        comp.tokens.append(token)
        q = self._queues[st.handle]
        ev = Event(TOKEN, st.handle, comp.request_id, token=token,
                   index=len(comp.tokens) - 1)
        events.append(ev)
        q.append(ev)
        if self._obs.enabled:
            self._obs.on_token(st.handle, ev.index)
        reason = self._finish_reason(i, st, token)
        if reason is not None:
            self._finish(i, st, reason, events)
        else:
            self._next_token[i] = token


class DecodeEngine:
    """Legacy batch-synchronous facade: ``generate(requests)``.

    A thin wrapper over :class:`ServingEngine` pinned to
    ``prefill_mode="loop"`` (decode-by-teacher-forcing admission) and
    the pure-argmax :class:`~repro.serving.sampling.GreedySampler` (the
    wrapper's documented contract is greedy-only, and argmax keeps the
    jitted step as cheap as the pre-redesign one — no per-token vocab
    sorts).  That makes its completions bit-identical to the
    pre-redesign engine: same plan buckets, same specialized steps,
    same launch order, same ``jnp.argmax``.  New code should drive
    :class:`ServingEngine` directly (see the README migration map).
    """

    def __init__(self, model: Model, scfg: ServeConfig, *,
                 max_len: int = 256, batch_slots: int = 4,
                 policy: Optional[str] = None,
                 tune_table: Optional[Any] = None):
        self.engine = ServingEngine(model, scfg, max_len=max_len,
                                    batch_slots=batch_slots, policy=policy,
                                    prefill_mode="loop",
                                    sampler=GreedySampler(),
                                    tune_table=tune_table)
        self.model = model
        self.cfg = model.cfg
        self.policy = self.engine.policy
        self.max_len = max_len
        self.B = batch_slots
        self.use_metadata = self.engine.use_metadata
        self.planner = self.engine.planner

    @property
    def stats(self) -> PlanCacheStats:
        return self.engine.stats

    def load(self, params: Pytree) -> None:
        self.engine.load(params)

    def planned_splits(self) -> Dict[int, int]:
        return self.engine.planned_splits()

    def _metadata(self, t_max: int) -> LaunchPlan:
        return self.engine._metadata(t_max)

    def generate(self, requests: Sequence[Request]) -> List[Completion]:
        # validate up front: a bad request must fail fast, not abort the
        # batch mid-flight after other requests already completed
        for req in requests:
            self.engine.validate(req)
        for req in requests:
            self.engine.submit(req)
        return self.engine.drain()
