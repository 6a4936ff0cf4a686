"""Device policy: the one place the JAX paths learn about the platform.

* :func:`x64` — the 64-bit scope the replay and DSE device programs run
  under (replay offsets reach ~1e11 ns, where float32 resolution is ~10 us;
  the DSE grids are pinned to the float64 scalar reference at 1e-9 rtol).
* :func:`ensure_compile_cache` — JAX's persistent compilation cache.  Where
  ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing here
  changes it; otherwise the cache lives at :data:`CACHE_DIR`, one fixed,
  git-ignored path in the checkout.  The directory is part of what a cached
  entry is found by, so it is never built from a temp name, a pid or the
  time.  Called when a jax backend is resolved, never at import.
* :func:`device_info` — platform, device kind and count as JAX reports
  them; ``chip_smoke.py`` and the run manifests read it.
"""

from __future__ import annotations

from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def x64():
    """Context manager enabling 64-bit JAX types for the enclosed block."""
    import jax

    return jax.enable_x64(True)


def ensure_compile_cache() -> None:
    """Point JAX's persistent compile cache at :data:`CACHE_DIR` unless a
    directory is already configured (``JAX_COMPILATION_CACHE_DIR`` or an
    earlier call)."""
    import jax

    if jax.config.jax_compilation_cache_dir is None:
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))


def device_info() -> dict:
    """``{"platform", "kind", "count"}`` of JAX's default devices."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
