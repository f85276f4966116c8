"""Seconds of set-up spent turning jitted functions into programs: the
backend-compile events JAX records (a compile, or a load from the
persistent compilation cache)."""


def read(run):
    return run.compile_setup_s if run.compile_setup_s > 0 else None
