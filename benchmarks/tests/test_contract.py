"""``BENCHMARK.json`` against the files it names: everything a cell needs
is found by name, and the names are in the allowed characters."""

import importlib
import json
import os
import re

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_names_units_and_limits():
    b = _bench()
    assert sorted(b) == sorted(["command", "paths", "run_seconds", "configs",
                                "workloads", "end_to_end", "per_layer"])
    assert 1 <= b["run_seconds"] <= 51
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in b[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group in ("end_to_end", "per_layer"), e["name"]))
    assert len(names) == len(set(names))
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in b["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" for m in b["end_to_end"])
    four = [w for w in b["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(b["workloads"]) // 2)
    for w in b["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200


def test_every_cell_finds_its_files_and_every_metric_its_reader():
    b = _bench()
    configs = {c["name"]: c for c in b["configs"]}
    e2e = {m["name"] for m in b["end_to_end"]}
    cells = {w["name"] for w in b["workloads"]}
    pairs = set()
    for w in b["workloads"]:
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        cfg_path = os.path.join(ROOT, configs[w["config"]]["file"])
        with open(cfg_path) as f:
            cfg = json.load(f)
        assert cfg["source"] == configs[w["config"]]["source"]
        assert os.path.exists(os.path.join(BENCH, "drivers",
                                           cfg["driver"] + ".py"))
        with open(os.path.join(BENCH, "traffic",
                               w["traffic"] + ".json")) as f:
            traffic = json.load(f)
        # the cell reports the throughput metric its mix or, failing that,
        # its configuration names
        rate = traffic.get("throughput_metric", cfg["throughput_metric"])
        assert w["name"] in next(m for m in b["end_to_end"]
                                 if m["name"] == rate)["workloads"]
        assert traffic["kernel"] in cfg["kernels"]
        for key in configs[w["config"]]["reduced"]:
            assert key in cfg and key in cfg["reduced"]
    assert {w["config"] for w in b["workloads"]} == set(configs)
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
        # a split quantity (``name.suffix``) shares the quantity's reader
        mod = importlib.import_module(
            f"layer_metrics.{m['name'].split('.')[0]}")
        assert callable(mod.read)
    # a per-layer metric is reported only where the metric it moves is
    where = {m["name"]: set(m.get("workloads", cells))
             for m in b["end_to_end"]}
    for m in b["per_layer"]:
        assert set(m.get("workloads", cells)) <= where[m["moves"]], m["name"]
    # every cell: setup_s, one more end-to-end metric, one per-layer metric
    for cell in cells:
        mine = [m for m in b["end_to_end"]
                if cell in m.get("workloads", cells)]
        assert {"setup_s"} < {m["name"] for m in mine}
        assert any(cell in m.get("workloads", cells)
                   for m in b["per_layer"])


def test_nothing_under_benchmarks_imports_the_program():
    """The program is reached as a child process or through the entry
    module a configuration names as data, never by an import statement."""
    allowed = set()
    for dirpath, _dirs, files in os.walk(BENCH):
        if os.path.basename(dirpath) == "tests":
            continue
        for name in files:
            if not name.endswith(".py"):
                continue
            with open(os.path.join(dirpath, name)) as f:
                for line in f:
                    s = line.strip()
                    if re.match(r"(from|import)\s+dsi_tpu", s):
                        assert s in allowed, (name, s)
