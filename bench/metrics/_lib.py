"""Shared arithmetic of the per-layer metrics: the operations and bytes
the model and its kernels need, from shapes and live cache lengths.

A decode launch processes one token per live slot; a served token with
index ``j >= 1`` of a request with an ``n``-token prompt came from a
decode launch whose input row sat at position ``n + j - 1``, so its
attention read ``kv_len = n + j`` cached rows.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Tuple

import numpy as np

KV_BYTES = 2          # bf16 cache
Q_BYTES = 4           # the kernels take the scaled query and return f32


def layer_matmul_params(n: Dict[str, int]) -> int:
    d, q, kv, ff = n["d"], n["hq"] * n["hd"], n["hkv"] * n["hd"], n["ff"]
    return d * q + 2 * d * kv + q * d + 3 * d * ff


def decode_token_flops(n: Dict[str, int], kv_len: int) -> float:
    """Model FLOPs of one decoded token: every layer's projections and
    MLP, its attention over ``kv_len`` rows, and the output head."""
    attn = 4 * n["hq"] * n["hd"] * kv_len
    return (n["layers"] * (2 * layer_matmul_params(n) + attn)
            + 2 * n["d"] * n["vocab"])


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


def flash_decode_need(n: Dict[str, int], kv_lens: Iterable[int],
                      peak: Dict) -> float:
    """Least seconds the decode-attention kernels (split pass and
    combine) could take for these live rows in every layer: q in, o out,
    and the K/V of resident rows, against the HBM bandwidth; or their
    FLOPs against the peak, whichever is larger."""
    kv = np.asarray(list(kv_lens), np.float64)
    rows = len(kv) * n["layers"]
    qo = rows * 2 * n["hq"] * n["hd"] * Q_BYTES
    kvb = n["layers"] * kv.sum() * 2 * n["hkv"] * n["hd"] * KV_BYTES
    flops = n["layers"] * kv.sum() * 4 * n["hq"] * n["hd"]
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
