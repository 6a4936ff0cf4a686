"""Array-backend selection for the DSE engine.

The batched evaluators are written against the NumPy array API surface and
run unchanged under ``jax.numpy``.  JAX is optional: the tier-1 container
ships it, but a CI matrix leg (and any minimal install) runs pure NumPy, so
every import is gated and ``backend="auto"`` quietly falls back.

The JAX path runs under ``repro.device.x64`` (``jax.enable_x64``): the access-count
grids subtract working-set sizes from capacities at very different
magnitudes, and float32 there would visibly drift from the float64 scalar
reference the equivalence tests pin at 1e-9 rtol.
"""

from __future__ import annotations

import contextlib

try:  # pragma: no cover - exercised by which branch imports
    import jax
    import jax.numpy as jnp

    HAVE_JAX = True
except ImportError:  # pragma: no cover
    jax = None
    jnp = None
    HAVE_JAX = False

BACKENDS = ("auto", "numpy", "jax")


def resolve_backend(backend: str = "auto") -> str:
    """Map ``auto`` onto the fastest backend; validate explicit picks.

    ``auto`` resolves to NumPy: at the grid sizes the STCO loop sweeps
    (tens of capacities x a few technologies), NumPy beats the jitted JAX
    path's dispatch/compile overhead by a wide margin.  Request ``jax``
    explicitly for device offload of very large grids or for backend-parity
    testing.
    """
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; expected one of {BACKENDS}")
    if backend == "auto":
        return "numpy"
    if backend == "jax":
        if not HAVE_JAX:
            raise RuntimeError("backend='jax' requested but jax is not installed")
        from repro.device import ensure_compile_cache

        ensure_compile_cache()
    return backend


def array_namespace(backend: str):
    """The array module (``numpy`` or ``jax.numpy``) for a resolved backend."""
    import numpy as np

    return jnp if backend == "jax" else np


def x64_scope(backend: str):
    """Context manager enabling 64-bit math on the JAX path (no-op on NumPy)."""
    if backend == "jax":
        from repro.device import x64

        return x64()
    return contextlib.nullcontext()
