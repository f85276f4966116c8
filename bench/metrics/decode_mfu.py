"""Model FLOPs of every decode launch in the traced window, from shapes
and live cache lengths, over the window's seconds times the chips times
the chip's bf16 peak (%)."""
from bench.metrics._lib import decode_kv_lens, decode_token_flops


def read(run):
    kv = decode_kv_lens(run)
    if not kv or run.trace is None:
        return None
    flops = sum(decode_token_flops(run.dims, k) for k in kv)
    return 100.0 * flops / (run.trace.window_s() * run.chips
                            * run.peak["bf16_flops"])
