"""What one ``Array.is_ready()`` costs, call by call (PR 51; PERF.md
section 6).  Not a test and not a benchmark cell: run it through the chip
tool,

    python scripts/look_micro.py

The starvation account (``dsi_tpu/obs/trace.py``) asks the newest result
whether it is ready, and in a job every look is at a new array.  A spin on
one array (0.25 us an ask on a TPU v5e) says nothing of that: on the CPU
backend the first look at an array takes 4-6 us and a later one 0.3.  Per
mode, over 300 runs of a program of about a millisecond: the first look
(median, 90th percentile), a later look on the pending result, the looks
until it reads ready, the time from the enqueue to that, and a look at the
ready result.  The modes: the result alone (``plain``), with its copy to
the host in flight (``copy``), a sibling output's copy in flight
(``sibling``), all three (``allcopy``), a second program queued behind it
and asked (``behind``), and ``copy`` beside a thread that holds the
interpreter lock half the time (``copy_thread``).  One JSON line on stdout.
"""
import json, statistics as st, threading, time
import jax, jax.numpy as jnp, numpy as np
pc = time.perf_counter

@jax.jit
def step(x):
    def body(i, x): return jnp.tanh(x @ x) * 0.5
    y = jax.lax.fori_loop(0, 6, body, x)
    return y[:1, :8], y[1:2, :16].astype(jnp.int32), y.sum()

x = jnp.ones((1024, 1024), jnp.float32)
jax.block_until_ready(step(x))
t = pc(); jax.block_until_ready(step(x)); prog_ms = (pc() - t) * 1e3

def trial(mode):
    """Returns (first-look us, median later-look us, looks until ready, us from enqueue to ready)."""
    t0 = pc()
    a, b, c = step(x)
    if mode in ("copy", "copy_thread"): a.copy_to_host_async()
    if mode == "sibling": b.copy_to_host_async(); c.copy_to_host_async()
    if mode == "allcopy": a.copy_to_host_async(); b.copy_to_host_async(); c.copy_to_host_async()
    if mode == "behind": a2, b2, c2 = step(x); a = a2
    t = pc(); r = a.is_ready(); first = pc() - t
    later = []; n = 1
    while not r:
        t = pc(); r = a.is_ready(); later.append(pc() - t); n += 1
        if n > 200000: break
    ready_at = pc() - t0
    t = pc(); a.is_ready(); after = pc() - t
    np.asarray(a); jax.block_until_ready((b, c))
    return first * 1e6, (st.median(later) if later else 0.0) * 1e6, (max(later) if later else 0) * 1e6, n, ready_at * 1e6, after * 1e6

res = {"device": jax.devices()[0].device_kind, "program_ms": round(prog_ms, 3)}
stop = False
def busy():
    a = np.random.rand(50000)
    while not stop:
        s = 0
        for i in range(200): s += i      # holds the interpreter lock
        np.sort(a)                        # lets it go
for mode in ("plain", "copy", "sibling", "allcopy", "behind", "copy_thread"):
    th = None
    if mode == "copy_thread":
        th = threading.Thread(target=busy, daemon=True); th.start()
    rows = [trial(mode) for _ in range(300)]
    if th: stop = True; th.join()
    cols = list(zip(*rows))
    res[mode] = {"first_us_med": round(st.median(cols[0]), 2), "first_us_p90": round(sorted(cols[0])[270], 2),
                 "later_us_med": round(st.median(cols[1]), 2), "later_us_max_med": round(st.median(cols[2]), 2),
                 "looks_med": st.median(cols[3]), "enqueue_to_ready_us_med": round(st.median(cols[4]), 1),
                 "ready_look_us_med": round(st.median(cols[5]), 2)}
print(json.dumps(res))
