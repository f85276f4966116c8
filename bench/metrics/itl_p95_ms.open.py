"""95th percentile of an open loop's inter-token gaps whose two tokens
were both seen in the window before the profiler started, in ms."""
import numpy as np

from bench.metrics._lib import untraced_span


def read(run):
    span = untraced_span(run)
    if span is None:
        return None
    a, b = span
    gaps = [t1 - t0 for r in run.records.values()
            for t0, t1 in zip(r.token_times, r.token_times[1:])
            if a <= t0 and t1 < b]
    return 1e3 * float(np.percentile(gaps, 95)) if gaps else None
