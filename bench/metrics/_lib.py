"""Shared arithmetic of the per-layer metrics: the operations and bytes
the model and its kernels need, from shapes and live cache lengths.

What one architecture counts (a layer's matmul parameters, a decoded
token's FLOPs, the decode-attention kernel's work per row) comes from its
adapter, ``bench/adapters/<model_type>.py``, which the dims a run hands
its readers carry under ``"adapter"`` (``Cell.dims``).

A decode launch processes one token per live slot; a served token with
index ``j >= 1`` of a request with an ``n``-token prompt came from a
decode launch whose input row sat at position ``n + j - 1``, so its
attention read ``kv_len = n + j`` cached rows.
"""
from __future__ import annotations

from typing import Any, Dict, Iterable, List, Optional, Tuple

import numpy as np

KV_BYTES = 2          # bf16 cache
Q_BYTES = 4           # the kernels take the scaled query and return f32


def adapter(n: Dict[str, Any]):
    """The adapter whose ``dims`` made ``n``, as ``Cell.dims`` carries
    it."""
    if "adapter" not in n:
        raise KeyError("dims carry no 'adapter': take them from Cell.dims")
    return n["adapter"]


def layer_matmul_params(n: Dict[str, Any]) -> int:
    return adapter(n).layer_matmul_params(n)


def decode_token_flops(n: Dict[str, Any], kv_len: int) -> float:
    """Model FLOPs of one decoded token at ``kv_len`` cached rows."""
    return adapter(n).decode_token_flops(n, kv_len)


def decode_kv_lens(run) -> List[int]:
    """kv_len of every live row of every decode launch in the traced
    window (host clock)."""
    a, b = run.trace_host_window
    out = []
    for r in run.records.values():
        for j in range(1, len(r.tokens)):
            if a <= r.token_times[j] <= b:
                out.append(r.prompt_len + j)
    return out


def untraced_span(run) -> Optional[Tuple[float, float]]:
    """An open loop's part of the window before the profiler started
    (host clock), where the steps ran as in an untraced run; None for a
    closed loop."""
    if run.loop != "open":
        return None
    return run.window[0], run.trace_host_window[0]


def flash_decode_need(n: Dict[str, Any], kv_lens: Iterable[int],
                      peak: Dict) -> float:
    """Least seconds the decode-attention kernels (split pass and
    combine) could take for these live rows in every layer: q in, o out,
    and the K/V of resident rows, against the HBM bandwidth; or their
    FLOPs against the peak, whichever is larger."""
    kv = np.asarray(list(kv_lens), np.float64)
    qo_row, kv_row, flops_row = adapter(n).flash_decode_row(n)
    rows = len(kv) * n["layers"]
    qo = rows * qo_row * Q_BYTES
    kvb = n["layers"] * kv.sum() * kv_row * KV_BYTES
    flops = n["layers"] * kv.sum() * flops_row
    return max((qo + kvb) / peak["hbm_bytes_s"], flops / peak["bf16_flops"])


def program_ms(run, kind: str) -> Optional[float]:
    """Mean device milliseconds of one launch of the engine's ``kind``
    programs in the traced window (summed over chips)."""
    if run.trace is None:
        return None
    kinds = run.trace.program_kinds()
    mods = [m for m in run.trace.in_window(run.trace.modules)
            if kinds.get(m.name) == kind]
    if not mods:
        return None
    launches = len(mods) / len(run.trace.devices)
    return 1e3 * sum(m.dur for m in mods) / launches


def idle_share(run) -> Optional[float]:
    if run.trace is None:
        return None
    busy = run.trace.mean_busy_s()
    if busy <= 0:
        return None
    return 100.0 * (1.0 - busy / run.trace.window_s())
