"""Which way the Pallas kernels run: compiled on a TPU, interpreted elsewhere.

Every kernel entry point takes `interpret=None` and resolves it here, so a
direct call on the chip always compiles the kernel and never silently falls
back to the interpreter. Tests on the CPU get interpret mode the same way.
"""
from __future__ import annotations

import jax


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret) -> bool:
    """None -> interpret exactly when no TPU backs the default device."""
    return (not on_tpu()) if interpret is None else bool(interpret)
