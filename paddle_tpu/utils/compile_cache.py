"""Persistent XLA compilation cache for the scripts that run on the chip
(``chip_smoke.py``, ``benchmarks/run.py``, ``tools/run_tpu_checks.py``).

A chip run starts on a fresh machine and a cold llama-8B-width step
program is tens of seconds of compiling; processes of one run, and runs
on a machine that keeps its disk, share compiled programs through JAX's
persistent cache. The cache key includes the directory's path, so the
directory must never move:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; this module
  sets no directory in code;
- unset: ``<checkout>/.jax_cache`` (git-ignored), never a temporary
  name, a pid or a time.

Importing the package does NOT turn the cache on — the tier-1 CPU suite
runs under several xdist workers and must not start writing one; the
scripts call :func:`enable_compile_cache` themselves.
"""

from __future__ import annotations

import os

__all__ = ["compile_cache_dir", "enable_compile_cache", "CacheCounter"]

_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def compile_cache_dir() -> str:
    """Where the persistent cache lives: the environment's directory if
    it names one, else the fixed ``.jax_cache`` inside the checkout."""
    return os.environ.get(_ENV) or os.path.join(_CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on for this process (call
    before the first compile) and return its directory."""
    import jax

    if not os.environ.get(_ENV):
        jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
    return compile_cache_dir()


class CacheCounter:
    """Counts this process's persistent-cache reads (``hits``) and
    writes (``misses``) from JAX's own monitoring events, so a script can
    say whether its compile seconds were cold or warm."""

    _EVENTS = {"/jax/compilation_cache/cache_hits": "hits",
               "/jax/compilation_cache/cache_misses": "misses"}

    def __init__(self):
        import jax

        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **kwargs) -> None:
        field = self._EVENTS.get(event)
        if field is not None:
            setattr(self, field, getattr(self, field) + 1)

    def counts(self) -> dict[str, int]:
        return {"hits": self.hits, "misses": self.misses}
