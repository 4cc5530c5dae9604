"""What every entry point does before its first compile: place the
persistent compilation cache, and say which device the run is on.

The cache directory is decided from outside. ``JAX_COMPILATION_CACHE_DIR``
wins (JAX reads it itself; nothing here overrides it). Without it the cache
is ``<checkout>/.jax_cache``, resolved from this package's location — a
fixed path, so a second process or a second run finds what the first one
compiled.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def device_line() -> str:
    """One line naming the backend as JAX reports it. A run that fell back
    to the CPU says so at start instead of only running slowly."""
    import jax

    devices = jax.devices()
    return (
        f"devices: platform={devices[0].platform} "
        f"device_kind={devices[0].device_kind!r} count={len(devices)}"
    )
