"""Persistent XLA compilation cache — one resolver for where it lives.

The cache directory is part of every entry's key, so a directory that
moves (a temp name, a pid, a run directory) never hits.  Two rules, one
home:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX's own handling of the variable
  is the whole story — nothing here (or anywhere in the repo) calls
  ``jax.config.update("jax_compilation_cache_dir", ...)``;
* unset: the cache goes to one fixed path inside the checkout,
  ``<repo>/.jax_cache/`` (git-ignored).

``train``, ``serve``, ``index`` and ``chip_smoke.py`` call
:func:`enable_compile_cache` before their first compile.  The off
switch is JAX's own (``JAX_ENABLE_COMPILATION_CACHE=false``), which is
how the test suite keeps the cache off (tests/conftest.py).

Thresholds are zeroed so EVERY program is cached: a second run of the
same command then compiles nothing, which :class:`CacheCounter` lets a
caller prove (entries read / written) instead of assume.  The key
includes the operations' metadata (scope names, source lines), so a
profile never shows a cached executable under stale region names.
"""

from __future__ import annotations

import logging
import os
from typing import Dict

log = logging.getLogger("npairloss_tpu.pipeline")

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def compile_cache_dir() -> str:
    """Where the cache lives: the env var if set, else the fixed
    in-checkout path.  Stdlib only (a jax-free parent may ask)."""
    return os.environ.get(CACHE_DIR_ENV) or DEFAULT_CACHE_DIR


def enable_compile_cache() -> str:
    """Turn the persistent cache on at :func:`compile_cache_dir`;
    call before the first compile.  Process-global and idempotent."""
    import jax

    if not os.environ.get(CACHE_DIR_ENV):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # By default the key leaves the operations' metadata out, so an
    # executable cached before a ``named_scope`` was added or renamed
    # comes back with the OLD region names — and device time by region
    # is read off exactly those names in a profile (PERF.md, PR 25:
    # the new pool scopes were invisible on a warm cache).  With the
    # metadata in the key a scope edit recompiles the program it
    # touches; the price is that moving a traced line does too.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    path = compile_cache_dir()
    log.info("persistent compilation cache: %s", path)
    return path


def cache_entries(path: str = "") -> int:
    """Executables currently stored under ``path`` (default: the
    resolved directory).  JAX's file cache writes ``<key>-cache``."""
    path = path or compile_cache_dir()
    try:
        return sum(1 for n in os.listdir(path) if n.endswith("-cache"))
    except FileNotFoundError:
        return 0


class CacheCounter:
    """Count this process's persistent-cache hits and misses (JAX's own
    monitoring events) and the entries it added on disk, so a cold
    recompile is never silent::

        with CacheCounter() as cc:
            ... compile ...
        cc.stats()  # {"dir", "hits", "misses", "entries_before",
                    #  "entries_after"}
    """

    _HIT = "/jax/compilation_cache/cache_hits"
    _MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        self.hits = 0
        self.misses = 0
        self.entries_before = 0

    def _on_event(self, event: str, **_kw) -> None:
        if event == self._HIT:
            self.hits += 1
        elif event == self._MISS:
            self.misses += 1

    def __enter__(self) -> "CacheCounter":
        import jax.monitoring

        self.entries_before = cache_entries()
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> None:
        import jax.monitoring

        jax.monitoring.unregister_event_listener(self._on_event)

    def stats(self) -> Dict[str, object]:
        return {
            "dir": compile_cache_dir(),
            "hits": self.hits,
            "misses": self.misses,
            "entries_before": self.entries_before,
            "entries_after": cache_entries(),
        }
