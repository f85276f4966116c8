"""Pallas attention kernels (``flash_*``), their jnp oracles (``ref``) and
the jit-facing dispatch (``ops``)."""
from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Whether a Pallas kernel runs in interpret mode.

    ``None`` (every entry point's default) interprets exactly when the
    default backend is the CPU, where there is nothing to compile the
    kernel for.  ``False`` is always honoured: it is how a CPU host
    AOT-compiles the kernels for a described TPU.  ``True`` off the CPU
    is refused — an emulated kernel on an accelerator is never what a
    caller meant, and it would time the interpreter.
    """
    on_cpu = jax.default_backend() == "cpu"
    if interpret is None:
        return on_cpu
    if interpret and not on_cpu:
        raise ValueError(
            f"interpret=True on the {jax.default_backend()!r} backend "
            "would emulate the Pallas kernel; pass interpret=None")
    return interpret
