"""95th percentile over an open loop's requests due in the window
before the profiler started, of the time from the due time to the first
token, in ms.  A request that never got one makes the tail infinite:
nothing is read then, and the run's ``failed`` counts it."""
import math

import numpy as np

from bench.metrics._lib import untraced_span


def read(run):
    span = untraced_span(run)
    if span is None:
        return None
    a, b = span
    ttft = [1e3 * (r.token_times[0] - r.due) if r.tokens else math.inf
            for r in run.records.values() if a <= r.due < b]
    p = float(np.percentile(ttft, 95)) if ttft else math.inf
    return p if math.isfinite(p) else None
