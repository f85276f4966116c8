"""Share (%) of its roofline reached by the Pallas decode-attention
kernels (``flash_decode_s*`` split pass and ``flash_combine_s*``): the
least time the live rows' bytes and FLOPs allow, over the device time
of those kernels' events in the traced window."""
from bench.metrics._lib import decode_kv_lens, flash_decode_need

KERNELS = ("flash_decode_s", "flash_combine_s")


def read(run):
    if run.trace is None:
        return None
    evs = [e for e in run.trace.in_window(run.trace.ops)
           if e.short.startswith(KERNELS)]
    kv = decode_kv_lens(run)
    t = sum(e.dur for e in evs)
    if not evs or not kv or t <= 0:
        return None
    return 100.0 * flash_decode_need(run.dims, kv, run.peak) / t
