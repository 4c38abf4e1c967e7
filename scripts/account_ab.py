"""What the starvation account costs a job, part by part (PR 51; PERF.md
sections 6 and 7).  Not a test and not a benchmark cell: run it through the
chip tool, from the root of the repo,

    python scripts/account_ab.py --workload <cell> --seed <n> [--rounds N]
                                 [--variants off,landed,on,timed,every]

One process runs one in-process cell of ``BENCHMARK.json`` as
``benchmarks/run.py`` does (its corpus, its warm-up, its driver's
``run_job``) and switches the account's parts job by job, round robin, so
that every variant sees the same process, data and machine: two processes
of one cell can differ by more than the account costs (``stream-wc-20k``
has two speeds: ROADMAP Speed 17p).  The variants:

``off``     no account and nothing told: ``Tracer._account`` gives none,
            ``enqueued`` does nothing, so ``StepPipeline`` asks nothing
``landed``  no account; the pipeline's one look a step (``results_ready``)
``on``      the program as it is
``timed``   as ``on``, with every look (``Tracer._look``) and every
            boundary (``_Account.enter`` / ``exit``) timed inside the job:
            looks and seconds a job, the longest look, a histogram of the
            looks by duration
``every``   no budget: a look at every boundary with something in flight
``<v>+r``   ``results_ready`` counted also by an engine that opted out
            (``count_ready=False``: the sort's ingest loop)

``--gc-at N`` runs a full collection before job N (Speed 17p's suspect).
A ``JOB`` line a job and an ``AB`` line a variant on stdout: the median,
mean and trimmed mean of the job walls, the medians of the account's keys
and of the step loop's phases.  ``--rehearse-cpu`` checks the paths at the
cell's tiny size on the CPU; its times mean nothing.
"""
import argparse, importlib, json, os, shutil, statistics as st, sys, time
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))  # scripts/ is one below the root
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
sys.path.insert(0, ROOT)
import run as R

p = argparse.ArgumentParser()
p.add_argument("--workload", required=True)
p.add_argument("--seed", type=int, required=True)
p.add_argument("--rounds", type=int, default=10)
p.add_argument("--variants", default="off,landed,on,timed,every")
p.add_argument("--rehearse-cpu", action="store_true")
p.add_argument("--gc-at", type=int, default=-1)
a = p.parse_args()
args = argparse.Namespace(workload=a.workload, seed=a.seed, seconds=1e9,
                          trace=0, rehearse_cpu=a.rehearse_cpu)
bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
cell = R.Cell(bench, args)
driver = importlib.import_module(f"drivers.{cell.config['driver']}")
shutil.rmtree(cell.workroot, ignore_errors=True); os.makedirs(cell.workroot)
driver.claim_device(cell)
R.prepare_inputs(cell)
driver.warm_up(cell)

import dsi_tpu.obs.trace as T
ORIG = {"_account": T.Tracer._account, "enqueued": T.Tracer.enqueued,
        "_look": T.Tracer._look, "enter": T._Account.enter, "exit": T._Account.exit}
C = {"looks": 0, "look_s": 0.0, "look_max": 0.0, "bounds": 0, "bound_s": 0.0,
     "look_hist": [0] * 8}
pc = time.perf_counter

def t_look(self, seq, arr):
    t = pc(); r = ORIG["_look"](self, seq, arr); d = pc() - t
    C["looks"] += 1; C["look_s"] += d
    if d > C["look_max"]: C["look_max"] = d
    b = 0
    x = d * 1e6
    while x >= 1 and b < 7: x /= 4; b += 1   # <1,<4,<16,<64,<256,<1024,<4096,more us
    C["look_hist"][b] += 1
    return r
def t_enter(self, *aa):
    t = pc(); r = ORIG["enter"](self, *aa); C["bounds"] += 1; C["bound_s"] += pc() - t; return r
def t_exit(self, *aa):
    t = pc(); r = ORIG["exit"](self, *aa); C["bounds"] += 1; C["bound_s"] += pc() - t; return r

import dsi_tpu.parallel.pipeline as PL
FORCE_READY = [False]
_pl_init = PL.StepPipeline.__init__
def _init(self, *aa, **kw):
    if FORCE_READY[0]: kw["count_ready"] = True
    _pl_init(self, *aa, **kw)
PL.StepPipeline.__init__ = _init

def set_variant(v):
    FORCE_READY[0] = v.endswith("+r")   # count results_ready also where the engine opted out
    v = v[:-2] if v.endswith("+r") else v
    T.Tracer._account = ORIG["_account"]; T.Tracer.enqueued = ORIG["enqueued"]
    T.Tracer._look = ORIG["_look"]; T._Account.enter = ORIG["enter"]; T._Account.exit = ORIG["exit"]
    T._LOOK_SHARE = 0.005
    if v == "off":
        T.Tracer._account = lambda self, name: None
        T.Tracer.enqueued = lambda self, arr: None
    elif v == "landed":
        T.Tracer._account = lambda self, name: None
    elif v == "timed":
        T.Tracer._look = t_look; T._Account.enter = t_enter; T._Account.exit = t_exit
    elif v == "every":   # no budget: a look at every boundary with something in flight
        T._LOOK_SHARE = 1e9; T.Tracer._look = t_look

variants = a.variants.split(",")
rows = {v: [] for v in variants}
n = 0
for r in range(a.rounds):
    order = variants[r % len(variants):] + variants[:r % len(variants)]
    for v in order:
        set_variant(v)
        if n == a.gc_at:
            import gc
            t = time.perf_counter(); got = gc.collect()
            print("GC", n, got, round(time.perf_counter() - t, 4), flush=True)
        for k in C: C[k] = [0] * 8 if k == "look_hist" else 0
        job = driver.run_job(cell, 1 + n); n += 1
        shutil.rmtree(job["workdir"], ignore_errors=True)
        ps = job["pipeline_stats"] or {}
        row = {"wall": job["wall_s"], "rc": job["rc"], "job_s": ps.get("job_s"),
               "starved_s": ps.get("starved_s"), "dry": ps.get("starved_dry_s"),
               "children": ps.get("job_children_s"),
               "compiles": job.get("compiles")}
        for scope in (ps, ps.get("plan", {}), *(ps.get("stages", {}) or {}).values()):
            if isinstance(scope, dict):
                for k in ("results_ready", "steps", "dispatch_s", "retire_s", "upload_s", "enqueue_s", "finalize_s", "compact_s"):
                    if k in scope: row[k] = row.get(k, 0) + scope[k]
        row["unseen"] = ps.get("starved_unseen_s")
        if v.split("+")[0] in ("timed", "every"): row.update({k: (list(x) if isinstance(x, list) else x) for k, x in C.items()})
        rows[v].append(row)
        print("JOB", r, v, json.dumps(row), flush=True)
set_variant("on")
def tm(xs):
    xs = sorted(xs); k = len(xs) // 5
    return st.mean(xs[k:len(xs) - k] if len(xs) > 4 else xs)
base = None
for v in variants:
    w = [x["wall"] for x in rows[v]]
    out = {"n": len(w), "wall_median": round(st.median(w), 4), "wall_mean": round(st.mean(w), 4),
           "wall_trimmed": round(tm(w), 4), "wall_min": min(w)}
    for k in ("job_s", "children", "starved_s", "dry", "unseen", "results_ready", "steps", "dispatch_s", "retire_s", "upload_s", "enqueue_s", "finalize_s"):
        xs = [x[k] for x in rows[v] if x.get(k) is not None]
        if xs: out[k] = round(st.median(xs), 4)
    if v.split("+")[0] in ("timed", "every"):
        for k in ("looks", "look_s", "look_max", "bounds", "bound_s"):
            out[k] = round(st.median(x[k] for x in rows[v]), 6)
        out["look_hist_us_lt_1_4_16_64_256_1024_4096_more"] = [sum(x["look_hist"][i] for x in rows[v]) for i in range(8)]
    print("AB", a.workload, v, json.dumps(out), flush=True)
shutil.rmtree(cell.workroot, ignore_errors=True)
