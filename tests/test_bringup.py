"""The bring-up contract (ISSUE 21): no silent host or CPU detour.

* ``utils/platformpin.require_device`` passes with the CPU named and raises
  when a non-TPU platform was not asked for;
* ``cli/chips`` assigns at most one device process per chip, counts the
  chips from the PCI bus where the bus can tell and from a probe child where
  it cannot, and a launcher ends its job on a device worker that could
  claim no chip instead of respawning it;
* ``utils/compilecache`` honours ``JAX_COMPILATION_CACHE_DIR`` and otherwise
  gives the fixed ``<checkout>/.jaxcache``; a second process against the
  same directory compiles nothing;
* ``TpuTaskRunner`` counts device and host map tasks;
* ``chip_smoke.py`` runs its phases at a tiny size with the CPU named, and
  every device entry point exits non-zero without a chip.
"""

import json
import os
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ── the platform check ─────────────────────────────────────────────────


def test_require_device_passes_with_cpu_named(monkeypatch):
    from dsi_tpu.utils.platformpin import require_device

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    devices = require_device("test")
    assert devices[0].platform == "cpu"


class _FakeDevice:
    def __init__(self, platform):
        self.platform = platform
        self.device_kind = platform


@pytest.mark.parametrize("env,platform,ok", [
    ({}, "cpu", False),                         # silent CPU: refused
    ({}, "gpu", False),                         # not this system's device
    ({"JAX_PLATFORMS": "cpu"}, "gpu", False),   # asked for cpu, got other
    ({}, "tpu", True),
    ({"DSI_JAX_PLATFORM": "cpu"}, "cpu", True),
])
def test_require_device_with_faked_backend(monkeypatch, env, platform, ok):
    import jax

    from dsi_tpu.utils import platformpin

    for var in ("JAX_PLATFORMS", "DSI_JAX_PLATFORM"):
        monkeypatch.delenv(var, raising=False)
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    monkeypatch.setattr(jax, "devices", lambda: [_FakeDevice(platform)])
    monkeypatch.setattr(jax.config, "update", lambda *a, **k: None)
    if ok:
        assert platformpin.require_device("test")[0].platform == platform
    else:
        with pytest.raises(platformpin.NoAcceleratorError) as e:
            platformpin.require_device("test-entry")
        assert "no TPU" in str(e.value) and "test-entry" in str(e.value)
        assert e.value.code != 0  # an uncaught raise exits non-zero


def test_require_device_reports_backend_init_failure(monkeypatch):
    import jax

    from dsi_tpu.utils import platformpin

    def boom():
        raise RuntimeError("Unable to initialize backend 'tpu': ABORTED")

    monkeypatch.setattr(jax, "devices", boom)
    with pytest.raises(platformpin.NoAcceleratorError) as e:
        platformpin.require_device("test")
    assert "Unable to initialize backend" in str(e.value)


# ── one device process per chip ────────────────────────────────────────


@pytest.mark.parametrize("chips,want", [
    (1, [0, None, None]),
    (4, [0, 1, 2]),
])
def test_assignment_for_faked_chip_counts(chips, want):
    from dsi_tpu.cli.chips import plan_device_workers

    slots, n = plan_device_workers(3, {}, "test", chips=chips)
    assert (slots, n) == (want, chips)


def test_zero_chips_is_an_error_naming_the_chip():
    from dsi_tpu.cli.chips import plan_device_workers

    with pytest.raises(SystemExit) as e:
        plan_device_workers(3, {}, "test-launcher", chips=0)
    assert "no TPU" in str(e.value) and "test-launcher" in str(e.value)


def test_cpu_named_means_no_chip_to_share():
    from dsi_tpu.cli.chips import plan_device_workers

    # No probe runs: every worker may be a device-backend worker.
    assert plan_device_workers(3, {"JAX_PLATFORMS": "cpu"}, "t") == \
        ([0, 0, 0], 0)


def test_chip_env_pins_only_on_a_multichip_host():
    from dsi_tpu.cli.chips import chip_env

    base = {"PATH": "/bin"}
    assert chip_env(base, 0, 1) == base  # one chip: nothing to choose
    envs = [chip_env(base, c, 4) for c in range(4)]
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
    assert len({e["TPU_PROCESS_PORT"] for e in envs}) == 4
    assert all(e["TPU_PROCESS_BOUNDS"] == "1,1,1" for e in envs)


# ── counting the chips without starting a runtime ──────────────────────

_V5E = ("0x1ae0", "0x0063")
_GVNIC = ("0x1ae0", "0x0042")      # Google's, and no TPU
_OTHER = ("0x8086", "0x1237")
# the chip tool's machines describe their one host like this
_ONE_HOST = {"JAX_PLATFORMS": "tpu,cpu", "TPU_ACCELERATOR_TYPE": "v5litepod-4",
             "TPU_CHIPS_PER_HOST_BOUNDS": "2,2,1", "TPU_HOST_BOUNDS": "1,1,1",
             "TPU_RUNTIME_METRICS_PORTS": "8431,8432,8433,8434",
             "TPU_SKIP_MDS_QUERY": "true", "TPU_TOPOLOGY": "2x2",
             "TPU_TOPOLOGY_ALT": "false",
             "TPU_TOPOLOGY_WRAP": "false,false,false",
             "TPU_WORKER_HOSTNAMES": "localhost", "TPU_WORKER_ID": "0"}


def _fake_machine(root, functions, given):
    """A sysfs PCI tree with one IOMMU group per function, and a /dev/vfio
    that holds the groups of the functions listed in ``given``."""
    (root / "vfio").mkdir(parents=True)
    (root / "vfio" / "vfio").write_text("")
    for i, (vendor, device) in enumerate(functions):
        d = root / "devices" / f"0000:00:{i:02x}.0"
        d.mkdir(parents=True)
        (d / "vendor").write_text(vendor + "\n")
        (d / "device").write_text(device + "\n")
        group = root / "iommu_groups" / str(i)
        group.mkdir(parents=True)
        os.symlink(os.path.relpath(group, d), d / "iommu_group")
        if given is None or i in given:
            (root / "vfio" / str(i)).write_text("")
    return str(root / "devices"), str(root / "vfio")


@pytest.mark.parametrize("functions,given,env,want", [
    ([_OTHER, _GVNIC], None, {}, None),         # 0 TPU functions: ask a child
    ([_OTHER, _V5E], None, {}, 1),
    ([_V5E, _OTHER, _V5E, _V5E, _V5E], None, {}, 4),
    ([_GVNIC, _V5E, _GVNIC], None, {}, 1),      # a Google device, no TPU
    ([_V5E] * 4, {2}, _ONE_HOST, 1),    # a shared host: one group is ours
    ([_V5E] * 4, None, _ONE_HOST, 4),
    ([_V5E] * 4, set(), {}, None),              # no group: another driver
    ([_V5E] * 4, None, {"TPU_VISIBLE_CHIPS": "2"}, 1),
    ([_V5E] * 4, None, dict(_ONE_HOST, TPU_VISIBLE_CHIPS="0"), 1),
    ([_V5E] * 4, None, {"TPU_VISIBLE_CHIPS": "7"}, None),    # no such chip
    # a longer list needs process bounds to start at all (measured: "0,1"
    # alone fails on a four-chip v5e), so the child is asked
    ([_V5E] * 4, None, {"TPU_VISIBLE_CHIPS": "1,3"}, None),
    ([_V5E] * 4, None, {"TPU_VISIBLE_CHIPS": "all"}, None),
    ([_V5E] * 4, None, {"TPU_PROCESS_BOUNDS": "2,2,1"}, None),
    ([_V5E] * 4, None, {"CLOUD_TPU_TASK_ID": "1"}, None),
    ([_V5E] * 4, None, dict(_ONE_HOST, TPU_HOST_BOUNDS="2,2,1"), None),
    ([_V5E] * 4, None, dict(_ONE_HOST, TPU_WORKER_HOSTNAMES="a,b"), None),
    ([_V5E] * 4, None, {"TPU_TOPOLOGY": "4x4"}, None),  # how many hosts?
    ([_V5E] * 4, None, {"TPU_LOG_DIR": "disabled"}, 4),
    ([_V5E] * 4, None, {"JAX_PLATFORMS": "cuda,tpu"}, None),
    (None, None, {}, None),                                  # no sysfs
])
def test_bus_count_over_a_fake_sysfs_tree(tmp_path, functions, given, env,
                                          want):
    from dsi_tpu.cli.chips import count_chips_on_bus

    if functions is None:
        bus, vfio = str(tmp_path / "absent"), str(tmp_path / "absent")
    else:
        bus, vfio = _fake_machine(tmp_path, functions, given)
    assert count_chips_on_bus(env, bus, vfio) == want


@pytest.mark.parametrize("how,env,chips,on_bus,want", [
    ("pci", {}, None, 4, ([0, 1, 2], 4)),
    ("child", {}, None, None, ([0, 1, None], 2)),   # the bus cannot tell
    ("given", {}, 1, 4, ([0, None, None], 1)),
    ("cpu", {"JAX_PLATFORMS": "cpu"}, None, 4, ([0, 0, 0], 0)),
])
def test_plan_counts_from_the_bus_else_a_child_and_the_span_says_how(
        tmp_path, monkeypatch, how, env, chips, on_bus, want):
    import dsi_tpu.obs.trace as obs_trace
    from dsi_tpu.cli import chips as chips_mod

    tracer = obs_trace.Tracer(enabled=True, trace_dir=str(tmp_path / "tr"))
    monkeypatch.setattr(obs_trace, "_global", tracer)
    children = []
    monkeypatch.setattr(chips_mod, "count_chips_on_bus", lambda env: on_bus)
    monkeypatch.setattr(chips_mod, "probe_chip_count",
                        lambda env: children.append(1) or 2)
    try:
        got = chips_mod.plan_device_workers(3, env, "t", chips=chips)
        with open(tracer.flush()[0], encoding="utf-8") as f:
            events = [json.loads(line) for line in f][1:]
    finally:
        tracer.enabled = False
    assert got == want
    assert len(children) == (1 if how == "child" else 0)
    (probe,) = [e for e in events if e["name"] == "probe"]
    assert probe["ph"] == "X" and probe["lane"] == "launch"
    assert (probe["chips"], probe["how"]) == (want[1], how)


# ── a device worker that can claim no chip ends the job ────────────────

_HELD_CHIP_WORKER = (
    "import sys\n"
    "with open(sys.argv[1], 'a') as f:\n"
    "    f.write('start\\n')\n"
    "import jax\n"
    "def held():\n"
    "    raise RuntimeError(\"Unable to initialize backend 'tpu': \"\n"
    "                       'ABORTED: the chip is held')\n"
    "jax.devices = held\n"
    "from dsi_tpu.utils.platformpin import require_device\n"
    "require_device('stand-in device worker')\n"
)


def test_no_accelerator_exit_code_is_its_own(tmp_path):
    from dsi_tpu.utils.platformpin import NO_ACCELERATOR_EXIT

    p = subprocess.run(
        [sys.executable, "-c", _HELD_CHIP_WORKER, str(tmp_path / "starts")],
        env=dict(os.environ, PYTHONPATH=REPO), capture_output=True,
        text=True, timeout=120)
    assert p.returncode == NO_ACCELERATOR_EXIT not in (0, 1, 2)
    assert "stand-in device worker: no TPU" in p.stderr
    assert "the chip is held" in p.stderr and "Traceback" not in p.stderr


@pytest.mark.parametrize("plane", ["plain", "net"])
def test_mrrun_ends_on_a_worker_that_could_claim_no_chip(
        tmp_path, monkeypatch, capfd, plane):
    """The stand-in is the device worker of a one-chip fleet whose chip is
    held: it fails in ``require_device`` as the real one does.  The job
    ends non-zero in one worker start, with the worker's message, and the
    slot is not respawned; the host helper beside it is stopped."""
    from dsi_tpu.cli import mrrun

    starts = tmp_path / "starts"
    monkeypatch.setenv("PYTHONPATH", REPO)  # children run in the workdir
    env = dict(os.environ)
    fleet = [([sys.executable, "-c", _HELD_CHIP_WORKER, str(starts),
               "--backend", "tpu"], env),
             ([sys.executable, "-m", "dsi_tpu.cli.mrworker", "--backend",
               "host", "wc"], dict(env, DSI_MR_REDUCE_ONLY="1"))]
    monkeypatch.setattr(mrrun, "_worker_fleet", lambda *a: fleet)
    monkeypatch.setenv("DSI_MR_SOCKET", str(tmp_path / "mr.sock"))
    f = tmp_path / "in.txt"
    f.write_text("a b c\n" * 100)
    t0 = time.monotonic()
    rc = mrrun.main(["--workers", "2", "--nreduce", "2", "--timeout", "120",
                     "--workdir", str(tmp_path / "wd")]
                    + (["--net"] if plane == "net" else [])
                    + ["wc", str(f)])
    assert rc != 0 and time.monotonic() - t0 < 60
    assert starts.read_text() == "start\n"        # no respawn
    err = capfd.readouterr().err
    assert "stand-in device worker: no TPU" in err
    assert "could claim no chip" in err and "without a respawn" in err
    assert "failing repeatedly" not in err and "--timeout" not in err


def test_mrrun_fleet_one_device_worker_then_reduce_only_helpers(monkeypatch):
    """On one chip: worker 0 is the device worker, the others are host
    helpers that decline map tasks; respawns reuse the slot's entry."""
    import argparse

    from dsi_tpu.cli import chips, mrrun

    monkeypatch.setattr(chips, "count_chips_on_bus", lambda env: None)
    monkeypatch.setattr(chips, "probe_chip_count", lambda env: 1)
    args = argparse.Namespace(backend="tpu", workers=3)
    fleet = mrrun._worker_fleet(args, "tpu_wc", {"X": "1"})
    backends = [cmd[cmd.index("--backend") + 1] for cmd, _ in fleet]
    assert backends == ["tpu", "host", "host"]
    assert [e.get("DSI_MR_REDUCE_ONLY") for _, e in fleet] == \
        [None, "1", "1"]
    # A homogeneous fleet is untouched.
    args = argparse.Namespace(backend="host", workers=2)
    assert [c[-2:] for c, _ in mrrun._worker_fleet(args, "wc", {})] == \
        [["host", "wc"]] * 2


def test_reduce_only_worker_waits_out_the_map_phase(tmp_path):
    from dsi_tpu.config import JobConfig
    from dsi_tpu.mr.coordinator import Coordinator
    from dsi_tpu.mr.types import TaskStatus

    f = tmp_path / "in.txt"
    f.write_text("a b c")
    coord = Coordinator([str(f)], 2, JobConfig(workdir=str(tmp_path)))
    try:
        r = coord.request_task({"WorkerId": "helper", "NoMap": True})
        assert r["TaskStatus"] == int(TaskStatus.WAITING)
        r = coord.request_task({"WorkerId": "device"})
        assert r["TaskStatus"] == int(TaskStatus.MAP)
        coord.map_complete({"TaskNumber": r["CMap"], "WorkerId": "device"})
        r = coord.request_task({"WorkerId": "helper", "NoMap": True})
        assert r["TaskStatus"] == int(TaskStatus.REDUCE)
    finally:
        coord.close()


def test_shardrun_refuses_more_device_workers_than_chips(
        tmp_path, monkeypatch, capsys):
    from dsi_tpu.cli import chips, shardrun

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.delenv("DSI_JAX_PLATFORM", raising=False)
    monkeypatch.setattr(chips, "count_chips_on_bus", lambda env: None)
    monkeypatch.setattr(chips, "probe_chip_count", lambda env: 1)
    f = tmp_path / "in.txt"
    f.write_text("a b c\n" * 100)
    rc = shardrun.main(["--workers", "3", "--workdir",
                        str(tmp_path / "wd"), str(f)])
    assert rc == 1
    err = capsys.readouterr().err
    assert "3 device workers but only 1 chip" in err


# ── the one compile cache ──────────────────────────────────────────────


_CACHE_CHILD = (
    "import json, sys\n"
    "from dsi_tpu.utils.platformpin import require_device\n"
    "from dsi_tpu.utils import compilecache\n"
    "require_device('child')\n"
    "from dsi_tpu.ops.wordcount import count_words_host_result\n"
    "assert count_words_host_result(b'one two two')['two'][0] == 2\n"
    "import jax\n"
    "print(json.dumps({'dir': compilecache.cache_dir(),\n"
    "                  'jax_dir': jax.config.jax_compilation_cache_dir,\n"
    "                  **compilecache.summary()}))\n"
)


def _run_cache_child(env):
    env = dict(env, JAX_PLATFORMS="cpu", JAX_ENABLE_COMPILATION_CACHE="1",
               DSI_COMPILE_QUIET="1", PYTHONPATH=REPO)
    env.pop("XLA_FLAGS", None)
    p = subprocess.run([sys.executable, "-c", _CACHE_CHILD], env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_cache_honours_env_dir_and_second_process_compiles_nothing(
        tmp_path):
    cache = tmp_path / "some" / "dir"
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(cache))
    first = _run_cache_child(env)
    assert first["dir"] == str(cache) == first["jax_dir"]
    assert first["cache_misses"] >= 1 and first["cache_hits"] == 0
    assert os.listdir(cache)  # entries landed under the env's directory
    second = _run_cache_child(env)
    assert second["cache_misses"] == 0
    assert second["cache_hits"] == first["cache_misses"]


def test_cache_defaults_to_the_fixed_checkout_path(monkeypatch):
    from dsi_tpu.utils import compilecache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compilecache.cache_dir() == os.path.join(REPO, ".jaxcache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert compilecache.cache_dir() == "/elsewhere"


def test_place_compile_cache_sets_no_directory_over_the_env(monkeypatch):
    import jax

    from dsi_tpu.utils import compilecache

    updates = {}
    monkeypatch.setattr(compilecache, "_placed", False)
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.__setitem__(k, v))
    monkeypatch.setattr(jax.monitoring, "register_event_listener",
                        lambda f: None)
    monkeypatch.setattr(jax.monitoring,
                        "register_event_duration_secs_listener",
                        lambda f: None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/from/outside")
    assert compilecache.place_compile_cache() == "/from/outside"
    assert "jax_compilation_cache_dir" not in updates
    monkeypatch.setattr(compilecache, "_placed", False)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    compilecache.place_compile_cache()
    assert updates["jax_compilation_cache_dir"] == \
        os.path.join(REPO, ".jaxcache")


# ── device and host map tasks are counted ──────────────────────────────


def test_runner_counts_device_and_host_maps(tmp_path):
    from dsi_tpu.apps import tpu_wc
    from dsi_tpu.backends.tpu import TpuTaskRunner
    from dsi_tpu.obs import get_tracer

    ascii_split = tmp_path / "ascii.txt"
    ascii_split.write_bytes(b"alpha beta alpha\n" * 50)
    dirty_split = tmp_path / "dirty.txt"  # mostly non-ASCII: host path
    dirty_split.write_bytes("żółć gęślą jaźń ".encode() * 50)
    runner = TpuTaskRunner(tpu_wc)
    tracer = get_tracer()
    was = tracer.enabled
    tracer.enabled = True
    before = tracer.counters_snapshot()
    try:
        for i, split in enumerate((ascii_split, dirty_split)):
            runner.run_map(tpu_wc.Map, str(split), i, 2, str(tmp_path))
    finally:
        tracer.enabled = was
    after = tracer.counters_snapshot()
    assert (runner.device_maps, runner.host_maps) == (1, 1)
    for name in ("tpu_map_device", "tpu_map_host"):
        assert after.get(name, 0) - before.get(name, 0) == 1
    assert "device_maps=1 host_maps=1" in runner.report()


# ── chip_smoke.py ──────────────────────────────────────────────────────


def _smoke_module():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_phases_dry_run_on_named_cpu(tmp_path, monkeypatch):
    """Every phase of the smoke at a tiny size, the CPU named explicitly
    (two virtual devices, so the sharded-table phase runs too), with a
    compile cache so the cold/warm pair means something."""
    smoke = _smoke_module()
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=2")
    monkeypatch.setenv("JAX_ENABLE_COMPILATION_CACHE", "1")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
    from dsi_tpu.cli.chips import probe_device

    device = probe_device(smoke.child_env())
    assert device == {"platform": "cpu", "kind": "cpu", "count": 2}
    results = smoke.run_phases(device, str(tmp_path / "root"), n_files=2,
                               file_bytes=150_000, batch_vocab=2_000,
                               vocab=30_000, repeats=2,
                               min_distinct=10_000, timeout=300.0)
    names = [r["phase"] for r in results]
    assert names == ["mrrun tpu_wc", "mrrun tpu_grep", "mrrun tpu_indexer",
                     "wcstream-cold", "wcstream-warm", "grepstream",
                     "wcstream", "wcstream-mesh"]
    assert all(r["parity"] for r in results)
    assert all(r["host_maps"] == 0 and r["device_maps"] >= 2
               for r in results[:3])
    # What the smoke itself gates the warm process on.  ``compile_s``
    # is not part of it: ``backend_compile_duration`` on a cache hit is
    # the load, and a slow load (0.5 s under six xdist workers) is booked
    # there with no miss behind it.
    warm = results[4]
    assert warm["cache"]["cache_misses"] == 0
    assert warm["cache"]["cache_hits"] > 0


def test_chip_smoke_fails_a_phase_that_took_the_host_path(tmp_path,
                                                          monkeypatch):
    smoke = _smoke_module()
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("XLA_FLAGS", "")
    root = tmp_path / "root"
    root.mkdir()
    f = tmp_path / "dirty.txt"
    f.write_bytes("żółć gęślą jaźń ".encode() * 2000)
    device = {"platform": "cpu", "kind": "cpu", "count": 1}
    with pytest.raises(smoke.SmokeFailure, match="host"):
        smoke.batch_phase("tpu_wc", [str(f)], str(root), device, 120.0)
    with pytest.raises(smoke.SmokeFailure, match="host path"):
        smoke.stream_phase("wcstream", [str(f)], str(root), device, {},
                           120.0)


# ── no chip, no CPU named: every device entry point refuses ────────────


def _no_pin_env():
    env = dict(os.environ, PYTHONPATH=REPO)
    for var in ("JAX_PLATFORMS", "DSI_JAX_PLATFORM", "XLA_FLAGS"):
        env.pop(var, None)
    return env


@pytest.fixture(scope="module")
def no_tpu_here():
    from dsi_tpu.cli.chips import probe_chip_count

    if probe_chip_count(_no_pin_env()):
        pytest.skip("this machine has a TPU")


@pytest.mark.parametrize("cmd", [
    [os.path.join(REPO, "chip_smoke.py")],
    ["-m", "dsi_tpu.cli.mrworker", "--backend", "tpu", "tpu_wc"],
    ["-m", "dsi_tpu.cli.wcstream", os.path.join(REPO, "README.md")],
    ["-m", "dsi_tpu.cli.mrrun", "--backend", "tpu", "tpu_wc",
     os.path.join(REPO, "README.md")],
])
def test_entry_points_exit_nonzero_without_a_chip(no_tpu_here, tmp_path,
                                                  cmd):
    p = subprocess.run([sys.executable] + cmd, env=_no_pin_env(),
                       cwd=tmp_path, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert ("no TPU" in p.stderr) or ("no accelerator" in p.stderr)
    assert "MB/s" not in p.stdout and '"ok"' not in p.stdout


def test_bench_exits_nonzero_without_a_chip(no_tpu_here, tmp_path):
    env = _no_pin_env()
    env.update({"DSI_BENCH_FILES": "2", "DSI_BENCH_FILE_SIZE": "100000",
                "DSI_BENCH_WORKDIR": str(tmp_path / "wd")})
    p = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 1
    verdict = json.loads(p.stdout.strip().splitlines()[-1])
    assert verdict["value"] == 0 and "no TPU" in verdict["error"]
    assert not [k for k in verdict if k.endswith("_mbps")]


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    import shutil

    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    p = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       env=_no_pin_env(), capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""
