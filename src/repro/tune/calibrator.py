"""Calibrator: resolves a TuneSpec into a SplitTable by measuring.

Per grid cell and candidate split count, the calibrator times a jitted
``ops.decode_attention`` launch with the split frozen via
``Planner(num_splits_override=s)`` — the exact code path a measured
plan later serves — takes the **median of repeats after a warmup
discard**, and records the whole latency curve plus its argmin.

Timing modes
------------
``wallclock``  real timing of the jitted launch (``block_until_ready``
               around a ``perf_counter`` window).  The production mode
               on real accelerators.
``modeled``    the analytic occupancy cost model
               (:func:`repro.core.occupancy.modeled_latency_us`) stands
               in for the clock.  Deterministic — this is what CI and
               the committed reference table use.
``auto``       ``modeled`` on CPU hosts (interpret-mode timings say
               nothing about TPU occupancy), ``wallclock`` elsewhere.

A ``TuneSpec.budget_s`` wall-clock cap degrades gracefully: once the
budget is spent, the remaining cells fall back to the model, and every
entry records its ``source`` so a mixed table stays auditable.

Determinism: under a fixed seed the grid order, candidate sets, input
tensors and (in modeled mode) every latency are bit-reproducible —
``calibrate()`` twice, get the same ``SplitTable.version``.
"""
from __future__ import annotations

import statistics
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from repro.core.occupancy import modeled_latency_us
from repro.core.split_policy import DecodeWorkload
from repro.plan import AttentionSpec, Planner
from repro.tune.spec import TuneSpec
from repro.tune.table import SplitTable

MODES = ("auto", "wallclock", "modeled")


class Calibrator:
    """Resolve ``spec`` into a :class:`SplitTable` (measure -> decide)."""

    def __init__(self, spec: TuneSpec, *, mode: str = "auto",
                 seed: int = 0):
        if mode not in MODES:
            raise ValueError(f"unknown mode {mode!r}; known: {MODES}")
        if mode == "auto":
            mode = "modeled" if jax.default_backend() == "cpu" \
                else "wallclock"
        self.spec = spec
        self.mode = mode
        self.seed = seed

    # --- timing -------------------------------------------------------------

    def _inputs(self, w: DecodeWorkload, cell: int):
        """Seeded decode-shaped inputs (deterministic per cell index).

        Quantized workloads get a quantized cache: bf16-scale normals
        quantized through the family's :class:`~repro.quant.Quantizer`
        (returning the extra scale leaves), so the timed launch streams
        exactly the bytes a quantized serving step streams.
        """
        from repro.quant import QUANT_DTYPES, Quantizer
        key = jax.random.fold_in(jax.random.PRNGKey(self.seed), cell)
        kq, kk, kv = jax.random.split(key, 3)
        name = w.kv_dtype_name
        quant = name in QUANT_DTYPES
        dt = jnp.bfloat16 if quant else \
            {2: jnp.bfloat16, 4: jnp.float32}[w.dtype_bytes]
        q = jax.random.normal(kq, (w.batch, w.num_heads_q, w.head_dim), dt)
        k = jax.random.normal(
            kk, (w.batch, w.seqlen_k, w.num_heads_kv, w.head_dim), dt)
        v = jax.random.normal(
            kv, (w.batch, w.seqlen_k, w.num_heads_kv, w.head_dim), dt)
        kv_len = jnp.full((w.batch,), w.seqlen_k, jnp.int32)
        if quant:
            qkv = Quantizer.from_kv_dtype(name).quantized_kv(k, v)
            return q, qkv.k, qkv.v, qkv.k_scale, qkv.v_scale, kv_len
        return q, k, v, kv_len

    def _time_wallclock(self, w: DecodeWorkload, impl: str, s: int,
                        cell: int) -> float:
        """Median-of-repeats latency (us) of the jitted frozen launch.

        Quantized families ride the fused harness: the same
        ``ops.decode_attention`` dispatch, with the cell's scale leaves
        passed through — ``impl="pallas"`` times the fused in-register-
        dequant kernel, ``impl="xla"`` times the dequant-then-attend
        reference (each under its own table family).  Pallas launches
        are Mosaic-compiled on an accelerator; only the CPU backend runs
        them in the interpreter (:func:`repro.kernels.resolve_interpret`).
        """
        from repro.kernels import ops   # local: keep import cost off the
        #                                 modeled-only (CI) path
        plan = Planner(num_splits_override=s, impl=impl).plan(
            AttentionSpec.from_workload(w))
        args = self._inputs(w, cell)

        if len(args) == 6:              # quantized cell (fused harness)
            @jax.jit
            def step(q, k, v, k_s, v_s, kv_len):
                return ops.decode_attention(
                    q, k, v, kv_len, k_scale=k_s, v_scale=v_s,
                    plan=plan, impl=impl)
        else:
            @jax.jit
            def step(q, k, v, kv_len):
                return ops.decode_attention(q, k, v, kv_len, plan=plan,
                                            impl=impl)

        for _ in range(max(1, self.spec.warmup)):   # compile + warmup
            step(*args).block_until_ready()
        times = []
        for _ in range(self.spec.repeats):
            t0 = time.perf_counter()
            step(*args).block_until_ready()
            times.append(time.perf_counter() - t0)
        return statistics.median(times) * 1e6

    def _time_modeled(self, w: DecodeWorkload, s: int) -> float:
        return modeled_latency_us(w, s, num_cores=self.spec.num_cores)

    # --- resolution ---------------------------------------------------------

    def calibrate(self) -> SplitTable:
        spec = self.spec
        entries: List[Dict[str, Any]] = []
        t_start = time.perf_counter()
        budget_spent = False
        for cell, (w, impl) in enumerate(spec.workloads()):
            if (spec.budget_s is not None and not budget_spent
                    and time.perf_counter() - t_start > spec.budget_s):
                budget_spent = True
            # quantized cells time through the fused harness (see
            # _time_wallclock) and are labeled "wallclock" — the historic
            # refusal ("no fused-quant harness, model only") is lifted
            quant = w.dtype_bytes == 1
            wallclock = self.mode == "wallclock" and not budget_spent
            lat: Dict[str, float] = {}
            for s in spec.candidate_splits(w):
                t = (self._time_wallclock(w, impl, s, cell) if wallclock
                     else self._time_modeled(w, s))
                # rounded so the JSON round-trips (and hashes) stably
                lat[str(s)] = round(float(t), 4)
            # argmin, ties toward the smallest split (the paper's
            # "smallest split entering the low-latency regime")
            best = min(sorted(lat, key=int), key=lambda k: lat[k])
            entries.append({
                "batch": w.batch, "num_heads_q": w.num_heads_q,
                "num_heads_kv": w.num_heads_kv, "head_dim": w.head_dim,
                "impl": impl, "dtype_bytes": w.dtype_bytes,
                "kv_dtype": w.kv_dtype_name,
                "lk_bucket": w.seqlen_k,
                "best_split": int(best),
                "source": ("wallclock" if wallclock and quant
                           else "measured" if wallclock else "modeled"),
                "latencies_us": lat,
            })
        table = SplitTable(entries, self._fingerprint(entries),
                           spec=spec.describe())
        table.validate()
        return table

    def _fingerprint(self, entries: List[Dict[str, Any]]) -> Dict[str, Any]:
        from repro.tune.table import MEASURED_SOURCES
        n_measured = sum(e["source"] in MEASURED_SOURCES for e in entries)
        if self.mode == "modeled":
            sources = "modeled"
        elif n_measured == len(entries):
            sources = "measured"
        else:             # wallclock degraded mid-run (budget cap)
            sources = "mixed"
        return {
            "mode": self.mode,
            "sources": sources,
            "backend": jax.default_backend(),
            "device": jax.devices()[0].device_kind,
            "jax": jax.__version__,
            "num_cores": self.spec.num_cores,
            "seed": self.seed,
            "fallback": "paper",
        }
