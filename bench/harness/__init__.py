"""The benchmark's general code: cells, traffic, driving, traces, checks."""
