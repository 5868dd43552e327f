"""Pallas kernels of the serving hot path (``ops.py`` is the public entry,
``ref.py`` the pure-jnp oracles).

Interpret mode follows the backend: every kernel entry point takes
``interpret=None`` by default, which compiles to Mosaic on a TPU and runs
the Pallas interpreter on any other backend.  Tests pass ``interpret=``
explicitly to pin one or the other.
"""
from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` -> interpret unless the default backend is a TPU."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)
