"""One compile cache, placed from outside, and one place compiles are counted.

The cache is JAX's own persistent compilation cache.  Where
``JAX_COMPILATION_CACHE_DIR`` is set JAX already honours it and this
module sets no directory in code; otherwise the cache lives at the fixed
path ``<checkout>/.jaxcache`` (the directory is part of the cache key, so
it is never a temp name, a pid or a time).  Every program is cached,
however short its compile: a job runs dozens of small programs beside
the big ones, and a sealed machine pays each of them again per process.

The accounting reads JAX's monitoring events, so it covers programs
compiled through ``backends/aotcache.cached_compile`` and through plain
``jax.jit`` alike: per program name the backend-compile count and
seconds, plus the persistent cache's requests, hits and misses.  A
process that found a warm cache reports ``cache_misses == 0``.
"""

from __future__ import annotations

import os
import threading

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_lock = threading.Lock()
_placed = False

#: ``programs`` maps a jitted function's name to [compiles, seconds] of
#: backend compile time (a persistent-cache hit compiles nothing and
#: adds no entry); the cache_* counters are the persistent cache's own.
stats = {"programs": {}, "backend_compile_s": 0.0, "cache_requests": 0,
         "cache_hits": 0, "cache_misses": 0}


def cache_dir() -> str:
    """The directory the compile cache is in for this process."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(_REPO, ".jaxcache"))


def enabled() -> bool:
    """Whether this process persists compiles at all
    (``JAX_ENABLE_COMPILATION_CACHE=0`` switches it off — tier-1 does)."""
    import jax

    return bool(jax.config.jax_enable_compilation_cache)


def _on_event(name: str, **_kw) -> None:
    if name == "/jax/compilation_cache/compile_requests_use_cache":
        stats["cache_requests"] += 1
    elif name == "/jax/compilation_cache/cache_hits":
        stats["cache_hits"] += 1
    elif name == "/jax/compilation_cache/cache_misses":
        stats["cache_misses"] += 1


def _on_duration(name: str, secs: float, **kw) -> None:
    if name != "/jax/core/compile/backend_compile_duration":
        return
    with _lock:
        rec = stats["programs"].setdefault(
            str(kw.get("fun_name", "?")), [0, 0.0])
        rec[0] += 1
        rec[1] += secs
        stats["backend_compile_s"] += secs


def place_compile_cache() -> str:
    """Point JAX at the one compile cache and start the accounting.
    Call before the first compile; idempotent.  Returns the directory."""
    global _placed
    import jax

    with _lock:
        if _placed:
            return cache_dir()
        _placed = True
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", cache_dir())
    # Cache every program: the defaults skip compiles under a second
    # and small executables, which here is most of the program count.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    return cache_dir()


def summary() -> dict:
    """A printable copy of :data:`stats` (seconds rounded)."""
    with _lock:
        return {
            "programs": {k: [n, round(s, 2)]
                         for k, (n, s) in sorted(stats["programs"].items())},
            "backend_compile_s": round(stats["backend_compile_s"], 2),
            "cache_requests": stats["cache_requests"],
            "cache_hits": stats["cache_hits"],
            "cache_misses": stats["cache_misses"],
        }
