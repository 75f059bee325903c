"""JAX's persistent compile cache for the entry points.

A process on the chip starts with no compiled code: every jitted peel
loop, at every bucketed shape, compiles again unless the process finds it
in JAX's persistent cache.  The cache directory is part of what makes an
entry findable, so it sits at a fixed place:

* ``JAX_COMPILATION_CACHE_DIR`` when it is set — JAX reads the variable
  itself and this module sets no other directory;
* otherwise ``<repo>/.jax_cache`` (listed in ``.gitignore``).

Called by ``chip_smoke.py`` and ``python -m repro.launch.serve``; the
library itself never configures a cache.
"""
from __future__ import annotations

import dataclasses
import os
import pathlib

import jax

__all__ = ["CompileCache", "enable_compile_cache", "REPO_CACHE_DIR"]

REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"

_HIT = "/jax/compilation_cache/cache_hits"
_WRITE = "/jax/compilation_cache/cache_misses"   # recorded on each write


@dataclasses.dataclass
class CompileCache:
    """Where the cache lives and what this process did with it."""

    directory: str
    hits: int = 0        # executables loaded from the cache
    writes: int = 0      # executables compiled here and stored

    def _on_event(self, event: str, **_kwargs) -> None:
        if event == _HIT:
            self.hits += 1
        elif event == _WRITE:
            self.writes += 1


def enable_compile_cache() -> CompileCache:
    """Point JAX's persistent cache at its fixed directory (see the
    module docstring) and count this process's hits and writes.  Call
    once per process, before the first compile."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    cache = CompileCache(directory=path)
    jax.monitoring.register_event_listener(cache._on_event)
    return cache
