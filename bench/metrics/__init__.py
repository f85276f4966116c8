"""Per-layer metrics: one reader per file, ``read(run) -> value | None``."""
