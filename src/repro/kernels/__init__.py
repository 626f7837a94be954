"""Pallas TPU kernels for the paged hot paths (see ops.py for the wrappers).

Every ``pallas_call`` here is compiled by Mosaic on a TPU backend and run
by the Pallas interpreter on any other backend (the CPU test suite). The
choice is made at trace time from the backend, never at import: asking
for the backend initializes it.
"""
from __future__ import annotations

import jax


def interpret_mode(interpret: bool | None = None) -> bool:
    """Resolve a kernel's ``interpret`` argument at trace time.

    None (the default everywhere) compiles on a TPU backend and interprets
    elsewhere. An explicit False forces a Mosaic lowering, which is how the
    compile-only tests build kernels for a described TPU from a CPU host.
    Interpret mode on a TPU is refused: a kernel that cannot compile there
    must fail, not run slowly in the interpreter."""
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise ValueError("Pallas interpret mode is refused on a TPU backend")
    return interpret
