"""The benchmark's own count of compiles and compile-cache traffic.

JAX reports every compile request through ``jax.monitoring``.  Listening
there (a public API, nothing of the program's) tells a real compile (a
persistent-cache miss) from a program loaded from the cache, which the
program's ``[compile]`` lines do not.  Used in the harness process (stream
cells) and, through ``hooks/sitecustomize.py``, in the device worker of a
batch job.
"""

from __future__ import annotations

import threading

_lock = threading.Lock()
_counts = {"cache_requests": 0, "cache_hits": 0, "cache_misses": 0,
           "cache_load_s": 0.0, "backend_compile_s": 0.0}
_programs: list = []   # [name, seconds] per backend-compile event, bounded
_installed = False


def _on_event(name: str, **_kw) -> None:
    key = {"/jax/compilation_cache/compile_requests_use_cache":
           "cache_requests",
           "/jax/compilation_cache/cache_hits": "cache_hits",
           "/jax/compilation_cache/cache_misses": "cache_misses"}.get(name)
    if key:
        with _lock:
            _counts[key] += 1


def _on_duration(name: str, secs: float, **kw) -> None:
    key = {"/jax/compilation_cache/cache_retrieval_time_sec": "cache_load_s",
           "/jax/core/compile/backend_compile_duration":
           "backend_compile_s"}.get(name)
    if key:
        with _lock:
            _counts[key] += secs
            if key == "backend_compile_s" and len(_programs) < 64:
                _programs.append([str(kw.get("fun_name", "?")),
                                  round(secs, 3)])


def install() -> None:
    """Start counting in this process (idempotent).  Imports JAX."""
    global _installed
    import jax

    with _lock:
        if _installed:
            return
        _installed = True
    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)


def snapshot() -> dict:
    with _lock:
        return dict(_counts)


def programs() -> list:
    """``[name, seconds]`` of each program this process compiled or loaded
    (for the log: which program a miss was)."""
    with _lock:
        return [list(p) for p in _programs]


def delta(before: dict, after: dict) -> dict:
    return {k: after[k] - before[k] for k in after}
