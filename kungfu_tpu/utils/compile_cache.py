"""Where the persistent XLA compile cache lives.

A whole-step program of the flagship model takes half a minute to two
minutes to compile for the chip; the persistent cache turns every later
process that compiles the same program into a read.  The directory is
part of each entry's key, so it has to be the same path in every
process and every run: ``JAX_COMPILATION_CACHE_DIR`` when the
environment sets it (JAX reads that variable itself, and this module
then sets nothing), else one fixed directory inside the checkout.

Called where a process first touches JAX on the chip path
(``Peer.start`` under ``-backend tpu``, ``chip_smoke.py``'s legs,
``kfbench/lib/harness.py``).  CPU test processes never call it, so
the tests run with the cache off.
"""

from __future__ import annotations

import os

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"

#: the fixed in-checkout location (git-ignored): never a temporary name,
#: a pid or a time
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on and return its directory."""
    path = os.environ.get(CACHE_DIR_ENV)
    if not path:
        import jax

        path = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path


class CacheCounter:
    """Counts, from JAX's own monitoring events, this process's
    persistent-cache hits and misses (a hit is a compile that was read
    back) and the seconds it spent in backend compiles, reads included."""

    _HIT = "/jax/compilation_cache/cache_hits"
    _MISS = "/jax/compilation_cache/cache_misses"
    _COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.hits = 0
        self.misses = 0
        self.compile_seconds = 0.0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def facts(self) -> dict:
        import jax

        return {"dir": jax.config.jax_compilation_cache_dir,
                "hits": self.hits, "misses": self.misses}

    def _on_event(self, name: str, **_kw) -> None:
        if name == self._HIT:
            self.hits += 1
        elif name == self._MISS:
            self.misses += 1

    def _on_duration(self, name: str, seconds: float, **_kw) -> None:
        if name == self._COMPILE:
            self.compile_seconds += seconds
