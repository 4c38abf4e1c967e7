"""Explicitly compiled programs, memoized per process.

``cached_compile`` lowers and compiles one function at fixed avals and
returns the executable, so a caller can compile from shape structs alone
(the warm ladders do) and dispatch without jit's per-call cache lookup::

    fn = cached_compile("corpus_wc", tokenize_fn, example_args,
                        static={"u_cap": 1 << 18})
    out = fn(*args)   # args must match example_args' shapes/dtypes

Persistence across processes is not done here: ``lower().compile()``
goes through JAX's persistent compilation cache, placed by
``utils/compilecache.py``, like every other program.  ``stats`` counts
the explicit compiles of this process (a persistent-cache hit still
counts as one: it is a program this process had to obtain) and the wall
seconds of their two halves, each a span with the program's name as
its ``program`` field: ``lowered_s`` (the ``lower`` span: tracing the function and
lowering it to StableHLO, which no cache saves) and ``compiled_s`` (the
``compile`` span: the backend's compile, or the persistent cache's
load); each program is logged on stderr by name.

Program-name families: ``wc_kernel*`` and ``corpus_wc*`` single-chunk
programs, ``stream_step_*``/``stream_pack_*`` streaming programs,
``tfidf_wave_*`` the pipelined TF-IDF wave step, ``dacc_*`` the device
accumulator's fold/clear/pack.
"""

from __future__ import annotations

import contextlib
import os
import sys
import threading
from typing import Any, Callable, Dict, Tuple

_memo: Dict[tuple, Callable] = {}
_memo_lock = threading.Lock()

# Process-wide counters the bench and the warm-ladder tests read.
stats = {"lowered_s": 0.0, "compiled_s": 0.0, "compiles": 0}


def _key(name: str, example_args: Tuple[Any, ...],
         static: Dict[str, Any],
         donate_argnums: Tuple[int, ...] = ()) -> tuple:
    """Memo key.  ``name`` stands for the function: callers build their
    ``fn`` closures afresh per call, and encode whatever the closure
    captures (mesh size, reduce count, capacities) in the name.  An
    argument's dtype is read as it is, never through ``result_type``:
    outside the x64 scope that canonicalizes a uint64 shape struct to
    uint32 but leaves a uint64 device array alone, and a warm pass
    (structs) and the run (arrays) would then key the same program
    twice."""
    import jax

    avals = tuple((tuple(jax.numpy.shape(a)),
                   str(a.dtype if hasattr(a, "dtype")
                       else jax.numpy.result_type(a)))
                  for a in example_args)
    return (name, avals,
            tuple((k, repr(static[k])) for k in sorted(static)),
            tuple(donate_argnums))


def _log(msg: str) -> None:
    if os.environ.get("DSI_COMPILE_QUIET") != "1":
        print(f"[compile] {msg}", file=sys.stderr, flush=True)


def cached_compile(name: str, fn: Callable, example_args: Tuple[Any, ...],
                   static: Dict[str, Any] | None = None,
                   donate_argnums: Tuple[int, ...] = (),
                   x64: bool = False) -> Callable:
    """Return a compiled callable for ``fn`` at ``example_args``' avals.

    ``static`` are keyword arguments baked into the program (and the memo
    key).  The result accepts positional arrays with exactly the example
    shapes/dtypes.  Thread-safe; per-process memoized.
    ``donate_argnums`` marks input buffers the caller hands to the program
    (jax.jit semantics; the streaming pipeline donates its per-step chunk
    uploads so an in-flight window never doubles HBM residency) — callers
    must not reuse a donated argument after the call.  ``x64=True`` runs
    trace/lower/compile under the scoped x64 flag — required for programs
    whose bodies touch uint64 (utils/jaxcompat.x64_scoped rationale).
    """
    import jax

    static = static or {}
    key = _key(name, example_args, static, donate_argnums)
    with _memo_lock:
        hit = _memo.get(key)
    if hit is not None:
        return hit

    from dsi_tpu.obs import span
    from dsi_tpu.utils.jaxcompat import enable_x64

    jitted = jax.jit(fn, static_argnames=tuple(static),
                     donate_argnums=donate_argnums)
    x64_scope = enable_x64(True) if x64 else contextlib.nullcontext()
    # The first device is the default while lowering: the single-chunk
    # kernels are one-device programs by design, also in a multi-device
    # process; programs that carry their own mesh (the shard_map steps)
    # are unaffected.
    with jax.default_device(jax.devices()[0]), x64_scope:
        with span("lower", lane="host", stats=stats, key="lowered_s",
                  program=name) as low:
            lowered = jitted.lower(*example_args, **static)
        with span("compile", lane="host", stats=stats, key="compiled_s",
                  program=name) as comp:
            compiled = lowered.compile()
    stats["compiles"] += 1
    _log(f"{name}: compiled in {low.elapsed_s + comp.elapsed_s:.1f}s "
         f"(lower {low.elapsed_s:.2f}s, compile {comp.elapsed_s:.2f}s)")
    with _memo_lock:
        _memo[key] = compiled
    return compiled
