"""Device milliseconds of one decode launch: the time of the engine's
decode step programs in the traced window over their launches."""
from bench.metrics._lib import program_ms


def read(run):
    return program_ms(run, "decode")
