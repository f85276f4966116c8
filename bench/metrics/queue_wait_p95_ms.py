"""95th percentile over the requests due in the window, before the
profiler started, of the wait from the due time to the start of
admission (the program's own admit span, ``repro.obs``), in ms."""
import numpy as np


def read(run):
    waits = run.queue_waits_ms
    return float(np.percentile(waits, 95)) if waits else None
