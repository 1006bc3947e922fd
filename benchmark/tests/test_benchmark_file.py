"""``BENCHMARK.json`` against the files it names, and against the rules
of its contract that can be checked without a run.  The rehearsal's
benchmark, which conftest.py builds from the committed one, is held to
the same."""

import json
import os
import re
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH_DIR)
sys.path.insert(0, REPO)

from benchmark import datagen, run                         # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter",
           "host_clock"}
@pytest.fixture(params=["committed", "rehearsal"])
def bench(request, rehearsal_root):
    root = REPO if request.param == "committed" else rehearsal_root
    traffic = os.path.join(BENCH_DIR if root is REPO else root, "traffic")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f), root, traffic


def test_keys_names_and_limits(bench):
    b, _, _ = bench
    assert sorted(b) == sorted(["command", "paths", "run_seconds",
                                "configs", "workloads", "end_to_end",
                                "per_layer"])
    assert b["paths"] == ["benchmark"]
    assert b["command"][:2] == ["python3", "benchmark/run.py"]
    assert isinstance(b["run_seconds"], int) and 1 <= b["run_seconds"] <= 51
    for c in b["configs"]:
        assert sorted(c) == ["file", "name", "reduced", "source", "why"]
    for w in b["workloads"]:
        assert sorted(w) == ["chips", "config", "name", "traffic", "why"]
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["source"] in SOURCES and "\n" not in m["layer"]
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in b[k]]
    for name in names + [w["traffic"] for w in b["workloads"]] + [
            r for c in b["configs"] for r in c["reduced"]]:
        assert NAME.match(name), name
    metrics = b["end_to_end"] + b["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    assert "setup_s" in {m["name"] for m in b["end_to_end"]}
    four = sum(w["chips"] == 4 for w in b["workloads"])
    assert four <= max(1, len(b["workloads"]) // 2)


def test_every_name_finds_its_files(bench):
    b, root, traffic = bench
    cells = {w["name"] for w in b["workloads"]}
    configs = {c["name"]: c for c in b["configs"]}
    assert {w["config"] for w in b["workloads"]} == set(configs)
    assert len({(w["config"], w["traffic"])
                for w in b["workloads"]}) == len(cells)
    for c in configs.values():
        with open(os.path.join(root, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"] and len(c["source"]) <= 200
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert os.path.exists(os.path.join(
            os.path.dirname(os.path.join(root, c["file"])),
            cfg["server_yaml"]))
        assert set(cfg["limits"]) == {"qtable_diff", "excess_err"}
        assert cfg["limits"]["qtable_diff"] == 0
        # The reference is found by the name the configuration gives.
        ref = run.load_named("references", cfg["reference"],
                             ("compare_request", "control_request"))
        assert ref.__name__ == "benchmark.references." + cfg["reference"]
        # The scale is the configuration's alone: no mix sets its own.
        assert datagen.extent(cfg) == (cfg["images"],
                                       *cfg["level0_tiles"])
    for w in b["workloads"]:
        with open(os.path.join(traffic, w["traffic"] + ".json")) as f:
            mix = json.load(f)
        kind = run.load_named("traffic_kinds", mix["kind"],
                              ("warm_up", "window"))
        assert kind.__name__ == "benchmark.traffic_kinds." + mix["kind"]
        assert not {"level0_tiles", "images"} & set(mix)
    e2e = {m["name"] for m in b["end_to_end"]}
    for m in b["per_layer"]:
        with open(os.path.join(BENCH_DIR, "layer_metrics",
                               m["name"] + ".json")) as f:
            spec = json.load(f)
        for key in ("layer", "unit", "moves", "source"):
            assert spec[key] == m[key], (m["name"], key)
        assert os.path.exists(os.path.join(
            BENCH_DIR, "readers", spec["reader"] + ".py"))
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
    # Every cell reports at least one per-layer metric.
    for cell in cells:
        assert any(cell in m.get("workloads", cells)
                   for m in b["per_layer"])


def test_a_name_with_no_file_fails_the_run():
    with pytest.raises(run.BenchFailure, match="open_loop.py"):
        run.load_named("traffic_kinds", "open_loop", ("warm_up", "window"))
    with pytest.raises(run.BenchFailure, match="lacks"):
        run.load_named("readers", "span_mean", ("warm_up",))


def test_peaks_table_names_its_sources():
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        peaks = json.load(f)
    v5e = peaks["TPU v5 lite"]
    assert v5e["flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in v5e["source"]


def test_the_generator_gives_every_seed_the_same_set():
    """``sweep`` walks the same share from another offset; ``seeded``
    draws from the same share; windows come from the mix's ranges."""
    from benchmark.traffic_kinds import closed_loop
    with open(os.path.join(BENCH_DIR, "configs",
                           "plate3-u16-p2048.json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH_DIR, "traffic", "scan.json")) as f:
        mix = json.load(f)
    items = closed_loop.items_of(config)
    assert len(items) == 192
    for seed in (1, 2**31 + 12345):
        vs = closed_loop.viewers(mix, config, items, seed,
                                 closed_loop.WINDOW)
        assert [len(v.share) for v in vs] == [96, 96]
        walked = [vs[0].next() for _ in range(96)]
        assert sorted(r["item"][0] for r in walked) == list(range(1, 97))
        for r in walked:
            assert "/render_image/" in r["path"] and "tile=" not in r["path"]
            for ws, we in r["windows"]:
                assert mix["window_start"][0] <= ws <= mix["window_start"][1]
                assert mix["window_end"][0] <= we <= mix["window_end"][1]
    a = closed_loop.viewers(mix, config, items, 7, closed_loop.WINDOW)
    b = closed_loop.viewers(mix, config, items, 7, closed_loop.WINDOW)
    assert [a[0].next()["path"] for _ in range(5)] == [
        b[0].next()["path"] for _ in range(5)]
    warm = closed_loop.viewers(mix, config, items, 7, closed_loop.WARMUP)
    assert warm[0].next()["windows"] != closed_loop.viewers(
        mix, config, items, 7, closed_loop.WINDOW)[0].next()["windows"]
