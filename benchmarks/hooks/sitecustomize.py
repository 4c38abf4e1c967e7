"""Observer for a device process the harness does not start itself.

In a batch cell the chip belongs to a grandchild (``mrrun`` starts the
``mrworker --backend tpu`` that holds it), and the program has no hook for a
profiler.  The harness puts this directory on the children's ``PYTHONPATH``;
Python imports ``sitecustomize`` at start-up in every one of them, and this
file does nothing unless ``BENCH_HOOK_OUT`` is set and the process's command
line contains ``BENCH_HOOK_MATCH`` (the configuration names its device
process).  In that one process, from a side thread and without touching the
program:

* it waits until the program has initialised JAX's backend, then writes
  what JAX reports about the device;
* it counts compile-cache hits, misses and load seconds (``jaxwatch``);
* with ``BENCH_HOOK_TRACE_S`` > 0 (traced runs only) it takes one
  ``jax.profiler`` trace, from backend-up for at most that many seconds or
  until the process exits, whichever is first;
* at exit it writes the peak device memory and the counts to
  ``$BENCH_HOOK_OUT/device-<pid>.json``.

Only a traced run installs a SIGTERM handler (so that a worker its launcher
terminates still closes the trace); a measured run leaves signals alone.
"""

import os
import sys


def _arm() -> None:
    import atexit
    import json
    import threading
    import time

    out_dir = os.environ["BENCH_HOOK_OUT"]
    trace_s = float(os.environ.get("BENCH_HOOK_TRACE_S", "0") or 0)
    bench_dir = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if bench_dir not in sys.path:
        sys.path.append(bench_dir)
    state = {"info": {"pid": os.getpid()}, "tracing": False,
             "closing": False}
    lock = threading.Lock()
    done = threading.Event()

    def write() -> None:
        os.makedirs(out_dir, exist_ok=True)
        path = os.path.join(out_dir, f"device-{os.getpid()}.json")
        with open(path + ".tmp", "w") as f:
            json.dump(state["info"], f)
        os.replace(path + ".tmp", path)

    def stop_trace() -> None:
        with lock:
            if not state["tracing"]:
                return
            state["tracing"] = False
        import jax

        jax.profiler.stop_trace()
        state["info"]["trace_stop_wall"] = time.time()

    def watch() -> None:
        # The program imports and initialises JAX when it is ready to;
        # this thread only looks.
        import_done = False
        while not done.is_set():
            xb = sys.modules.get("jax._src.xla_bridge")
            ready = getattr(xb, "backends_are_initialized", None)
            if ready is not None and "jax" in sys.modules:
                if not import_done:
                    import jaxwatch

                    jaxwatch.install()  # waits for the program's import
                    import_done = True
                if ready():
                    break
            time.sleep(0.02)
        else:
            return
        import jax

        devices = jax.devices()
        state["info"].update({
            "platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "backend_up_wall": time.time()})
        write()
        if trace_s > 0:
            with lock:
                if state["closing"]:
                    return
                jax.profiler.start_trace(os.path.join(out_dir, "profile"))
                state["tracing"] = True
            state["info"]["trace_start_wall"] = time.time()
            done.wait(trace_s)
            stop_trace()
            write()

    def at_exit() -> None:
        with lock:
            state["closing"] = True
        done.set()
        stop_trace()
        if "platform" in state["info"]:
            import jax
            import jaxwatch

            peaks = []
            for d in jax.devices():
                stats = d.memory_stats() or {}
                peaks.append(int(stats.get("peak_bytes_in_use", 0)))
            state["info"]["memory_peak_bytes"] = max(peaks)
            state["info"]["jax"] = jaxwatch.snapshot()
            state["info"]["programs"] = jaxwatch.programs()
            state["info"]["exit_wall"] = time.time()
            write()

    threading.Thread(target=watch, name="bench-hook", daemon=True).start()
    atexit.register(at_exit)
    if trace_s > 0:
        import signal

        def on_term(_sig, _frame):
            if not state["closing"]:
                sys.exit(143)

        signal.signal(signal.SIGTERM, on_term)


if os.environ.get("BENCH_HOOK_OUT") and os.environ.get("BENCH_HOOK_MATCH") \
        and os.environ["BENCH_HOOK_MATCH"] in " ".join(
            getattr(sys, "orig_argv", sys.argv)):
    _arm()
