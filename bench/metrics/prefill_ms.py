"""Device milliseconds of one fused prefill launch: the time of the
engine's prefill step programs in the traced window over their
launches."""
from bench.metrics._lib import program_ms


def read(run):
    return program_ms(run, "prefill")
