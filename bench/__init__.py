"""Benchmark of the serving engine on the chip: ``python3 bench/run.py``."""
