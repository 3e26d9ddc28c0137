"""The device-facing edge shared by every launcher and executor: where
compiled programs are cached, and what a run record says about the
devices it used.

Importing this module does not import JAX; its functions do so lazily,
and none initializes a backend the caller has not already asked for.
"""

from __future__ import annotations

import os
import threading

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

    count_compiles()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update(
            "jax_compilation_cache_dir", os.path.join(_CHECKOUT, ".jax_cache")
        )
    return jax.config.jax_compilation_cache_dir


#: the jax.monitoring events behind the program's own compile metrics
_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_count_compiles_lock = threading.Lock()
_counting_compiles = False


def _on_jax_event(event, **_kwargs) -> None:
    if event == _CACHE_MISS_EVENT:
        from janusgraph_tpu.observability import registry

        registry.counter("jax.compile.cache_miss").inc()


def _on_jax_duration(event, seconds, **_kwargs) -> None:
    if event == _BACKEND_COMPILE_EVENT:
        from janusgraph_tpu.observability import registry

        registry.timer("jax.compile.backend").update(int(seconds * 1e9))


def count_compiles() -> None:
    """Feed what JAX itself compiled into the registry, once per process
    (``jax.monitoring`` listeners cannot be taken back): counter
    ``jax.compile.cache_miss`` — executables the persistent cache did not
    hold, compiled and written — and timer ``jax.compile.backend`` — count
    and seconds of every backend compile-or-load, cached or not. An
    operator reads on ``GET /metrics`` whether a deployment recompiles;
    ``olap.compile_cache.*`` counts something else (the ``jax.jit``
    wrappers the executor asked for, compiled or not)."""
    global _counting_compiles
    with _count_compiles_lock:
        if _counting_compiles:
            return
        from jax import monitoring

        monitoring.register_event_listener(_on_jax_event)
        monitoring.register_event_duration_secs_listener(_on_jax_duration)
        _counting_compiles = True


def await_arrays(arrays) -> None:
    """Start the device-to-host copy of every array, then wait until the
    device has produced them. The ``np.asarray`` that follows finds the
    copy already under way, as if it had been called first, so waiting
    here costs no round trip; an executor times this as its wait on the
    device (phase ``executor.sync``) and the ``np.asarray`` as the fetch."""
    import jax

    arrays = [a for a in arrays if isinstance(a, jax.Array)]
    for a in arrays:
        a.copy_to_host_async()
    jax.block_until_ready(arrays)


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
