"""The device-facing edge shared by every launcher and executor: where
compiled programs are cached, and what a run record says about the
devices it used.

Importing this module does not import JAX; both functions do so lazily,
and neither initializes a backend the caller has not already asked for.
"""

from __future__ import annotations

import os

#: the checkout that holds this package (``.jax_cache/`` is git-ignored)
_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache somewhere that survives
    the process, and return the directory in effect. Call before the first
    compile.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set in code, so whoever launches the program places the
    cache. Otherwise the cache is ``<checkout>/.jax_cache``: a fixed path,
    because the path is part of what a cache entry is found by."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update(
            "jax_compilation_cache_dir", os.path.join(_CHECKOUT, ".jax_cache")
        )
    return jax.config.jax_compilation_cache_dir


def describe_devices(devices) -> dict:
    """``platform`` / ``device_kind`` / ``device_count`` of the devices an
    executor actually placed its arrays on — the part of a run record that
    says whether a number came from the chip."""
    devices = list(devices)
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
    }
