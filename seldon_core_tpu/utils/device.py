"""What the process serves on, and where its compiled programs are kept.

Two facts every entry point that compiles needs before its first JAX call
(``engine/app.py::_serve``, the benchmark's and the smoke's children):

- :func:`configure_compile_cache` places JAX's persistent compilation
  cache.  Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it
  and no directory is set in code; otherwise the cache lives in ONE fixed
  directory inside the checkout.  The directory is part of what a cache
  hit depends on, so it never derives from a temp dir, a pid, a port or
  the time — a directory that moves never hits.
- :func:`serving_device` names the device JAX actually initialised, so a
  server that came up on the CPU is distinguishable from one on the chip.

:func:`xla_compile_count` counts the programs the process asked XLA for,
persistent-cache hits included: the number that must not move once a server
says it is ready.
"""

from __future__ import annotations

import functools
import os
import threading

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache — git-ignored; two levels up from this package
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)


def configure_compile_cache() -> str | None:
    """Enable the persistent compilation cache; returns its directory.

    The thresholds are dropped to zero: the serving ladder is many
    sub-second programs (small prefill buckets, the batcher's low rungs),
    and JAX's defaults (>= 1 s compile time) would skip exactly those.

    A process pinned to the CPU (``JAX_PLATFORMS=cpu``: the tests, a
    rehearsal) keeps no cache and gets ``None``: XLA:CPU executables are
    built for the host's instruction set, its loader warns about a
    mismatch on every hit and can fault on another host, and the CPU path
    is not what the cache is for.
    """
    import jax

    if jax.config.jax_platforms == "cpu":
        return None
    env_dir = os.environ.get(CACHE_DIR_ENV)
    if not env_dir:
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return env_dir or DEFAULT_CACHE_DIR


_compiles = 0
_compiles_lock = threading.Lock()


@functools.cache
def _count_compiles() -> None:
    """Hook JAX's own compile event, once per process."""
    import jax.monitoring

    def on_event(event: str, duration_secs: float, **_) -> None:
        global _compiles
        # wraps compile-or-fetch-from-cache, so persistent hits count too
        if event == "/jax/core/compile/backend_compile_duration":
            with _compiles_lock:  # warmup compiles on several threads
                _compiles += 1

    jax.monitoring.register_event_duration_secs_listener(on_event)


def xla_compile_count() -> int:
    """Programs this process has asked XLA to compile (or fetch from the
    persistent cache) since the first call to this function."""
    _count_compiles()
    return _compiles


def serving_device() -> dict:
    """``platform`` / ``device_kind`` / ``device_count`` as JAX reports
    them, plus whether the native wire codec is loaded.  Initialises the
    JAX backend — call it only in a process that owns the device."""
    import jax

    from seldon_core_tpu.contract import native

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "native_codec": native.available(),
    }
