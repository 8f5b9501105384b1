"""Compile counting and the persistent compile cache, copied from
``repro.launch.serve`` (``CompileClock``, ``use_compile_cache``) so that no
change to the program can change how the benchmark counts."""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[1]

# JAX monitoring events that make up a compile: trace, lower, and the
# backend compile, which fires once per program whether it is compiled or
# read back from the persistent cache
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; call before the first
    compile. ``JAX_COMPILATION_CACHE_DIR``, where set, wins: JAX reads it
    itself and no directory is set here. Otherwise the cache is the fixed
    ``<checkout>/.jax_cache``. Every program is cached, however quickly it
    compiled, so that a later run compiles nothing."""
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


class CompileClock:
    """Counts programs compiled (or read back from the persistent cache)
    and sums their trace + lower + compile seconds, from JAX's own
    monitoring events, while in a ``with`` block."""

    def __init__(self):
        self.seconds = 0.0
        self.programs = 0
        self.cache_hits = 0

    def _on_duration(self, event, duration, **_):
        if event in _COMPILE_EVENTS:
            self.seconds += duration
            self.programs += event == _COMPILE_EVENTS[-1]

    def _on_event(self, event, **_):
        self.cache_hits += event == _CACHE_HIT_EVENT

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)
