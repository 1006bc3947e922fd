"""The benchmark's new deployment ``stock4-u16-t256`` and its cell
``stock4-u16-t256.pan`` on the CPU (PR 28): the entries against ISSUE
28, the traffic the mix generates, the three new per-layer metrics
against a server that lacks their series (the parent), and the
harness's own rehearsal of ``benchmark/run.py`` through the new cell's
files at 64^2 tiles: end to end, traced, under both planted faults and
with the controls (``tests/bench_rehearsal.py`` says why from here;
``tests/test_benchmark_rehearsal.py`` holds ``BENCHMARK.json`` to its
files and re-drives the cells the harness had).
"""

import importlib
import json
import os

import pytest

from bench_rehearsal import (FIRST_CELL, ONE_DEVICE, REPO, STOCK_CELL,
                             TINY_STOCK_CELL, build_rehearsal, load)

CELL, TINY_CELL = STOCK_CELL, TINY_STOCK_CELL
NEW_METRICS = ("host_route_share", "group_pad_share", "prepare_ms")

rehearsal = load("test_rehearsal")


@pytest.fixture(scope="module")
def rehearsal_root(tmp_path_factory):
    return build_rehearsal(tmp_path_factory)


# ------------------------------------------- BENCHMARK.json and its files

def test_the_new_entries_are_the_issues():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # The third configuration and the third cell (later PRs append).
    assert bench["configs"][2]["name"] == "stock4-u16-t256"
    assert bench["configs"][2]["reduced"] == ["level0_tiles", "images"]
    cell = bench["workloads"][2]
    assert (cell["name"], cell["config"], cell["traffic"],
            cell["chips"]) == (CELL, "stock4-u16-t256", "pan", 1)
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index(NEW_METRICS[0])
    assert names[first:first + 3] == list(NEW_METRICS)
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m["workloads"]}
    assert listed == {m["name"] for m in bench["per_layer"]} - {
        "read_region_ms", "unpack_device_ms",
        "shown_render_roofline",       # PR 32's, of another deployment
        "bucket_fill_share", "stack_pad_device_ms",   # PR 34's: plates
        "source_open_ms", "idle_read_share",   # PR 36's: the scans' open
        "busiest_chip_share", "fleet_hop_ms",  # PR 38's: the fleet's
        "fleet_steal_share", "fleet_render_roofline",
        "fleet_queue_wait_ms",                 # the fleet's too
        "prefetch_stage_ms", "prefetch_used_share",   # the cold pan's
        "rawcache_dup_load_share", "idle_prefetch_share"}
    with open(os.path.join(REPO, "benchmark", "configs",
                           "stock4-u16-t256.json")) as f:
        config = json.load(f)
    with open(os.path.join(REPO, "benchmark", "configs",
                           "wsi4-u16-t1024.json")) as f:
        wsi = json.load(f)
    # 64 x 64 stock tiles: the same 16,384^2 px (2 GiB) as the 1024^2
    # deployment's 16 x 16.
    assert (config["tile_edge"], config["content_edge"],
            config["store_chunk"], config["level0_tiles"]) == (
        256, 256, 256, [64, 64])
    assert [n * config["content_edge"]
            for n in config["level0_tiles"]] == [
        n * wsi["content_edge"] for n in wsi["level0_tiles"]]
    for key in ("route", "channels", "dtype", "itemsize", "images",
                "pyramid", "colors", "format", "quality", "reference"):
        assert config[key] == wsi[key], key
    # The shipped posture, written out: the route's threshold is the
    # shipped default itself (so the parent's program, whose default
    # answers a stock tile from the host, reaches the chip too and its
    # traced run has something to read), and the cap is under test.
    from omero_ms_image_region_tpu.server.config import RendererConfig
    with open(os.path.join(REPO, "benchmark", "configs",
                           config["server_yaml"])) as f:
        posture = f.read()
    assert (f"cpu-fallback-max-px: {RendererConfig().cpu_fallback_max_px}\n"
            in posture)
    assert RendererConfig().cpu_fallback_max_px < config["tile_edge"] ** 2
    assert "max-batch: 8" in posture
    assert 'prewarm: ["4x256@90"]' in posture


def test_pan_gives_each_viewer_four_rows_of_64_stock_tiles():
    from benchmark.traffic_kinds import closed_loop
    with open(os.path.join(REPO, "benchmark", "configs",
                           "stock4-u16-t256.json")) as f:
        config = json.load(f)
    with open(os.path.join(REPO, "benchmark", "traffic",
                           "pan.json")) as f:
        mix = json.load(f)
    assert (mix["viewers"], mix["connections_per_viewer"], mix["think_s"],
            mix["order"], mix["working_set"], mix["warm_fill"]) == (
        16, 6, 0, "sweep", 4096, "all")
    items = closed_loop.items_of(config)
    assert len(items) == 4096 == mix["working_set"]
    vs = closed_loop.viewers(mix, config, items, 2**31 + 28,
                             closed_loop.WINDOW)
    assert [len(v.share) for v in vs] == [256] * 16
    walked = [vs[3].next() for _ in range(256)]
    assert {r["item"] for r in walked} == {
        (1, x, y) for y in range(12, 16) for x in range(64)}
    assert "tile=0," in walked[0]["path"]
    assert walked[0]["path"].split("tile=")[1].split("&")[0].endswith(
        ",256,256")
    assert len({r["path"] for r in walked}) == 256


# ------------------------- the new metrics on a server without their series

def _spec(name: str) -> dict:
    with open(os.path.join(REPO, "benchmark", "layer_metrics",
                           name + ".json")) as f:
        return json.load(f)


def _read(name: str, m0: dict, m1: dict):
    spec = _spec(name)
    reader = importlib.import_module(
        f"benchmark.readers.{spec['reader']}")
    return reader.read({"m0": m0, "m1": m1}, **spec["args"])


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_new_metric_reads_nothing_from_the_parents_metrics(name):
    """The parent exports none of the three series: the metric is left
    out of its line (None), never 0 and never a raise."""
    parent = {"imageregion_tiles_rendered": 640.0,
              "imageregion_batches_dispatched": 10.0,
              'imageregion_span_count{span="batcher.stage"}': 10.0}
    assert _read(name, {}, parent) is None
    assert _read(name, parent, parent) is None


def test_the_new_metrics_read_the_changes_series():
    m0 = {'imageregion_renders_routed_total{route="device"}': 10.0,
          'imageregion_renders_routed_total{route="host"}': 0.0,
          "imageregion_batcher_shape_slots_total": 16.0,
          "imageregion_batcher_padded_slots_total": 6.0,
          'imageregion_span_count{span="handler.prepare"}': 10.0,
          'imageregion_span_ms_sum{span="handler.prepare"}': 20.0}
    m1 = {'imageregion_renders_routed_total{route="device"}': 100.0,
          'imageregion_renders_routed_total{route="host"}': 10.0,
          "imageregion_batcher_shape_slots_total": 16.0 + 128.0,
          "imageregion_batcher_padded_slots_total": 6.0 + 32.0,
          'imageregion_span_count{span="handler.prepare"}': 110.0,
          'imageregion_span_ms_sum{span="handler.prepare"}': 320.0}
    assert _read("host_route_share", m0, m1) == pytest.approx(10.0)
    assert _read("group_pad_share", m0, m1) == pytest.approx(25.0)
    assert _read("prepare_ms", m0, m1) == pytest.approx(3.0)


# ------------------------------------------ run.py through the cell's files

@pytest.fixture()
def one_device(monkeypatch):
    monkeypatch.setenv("XLA_FLAGS", ONE_DEVICE)


def test_rehearsal_end_to_end_line(tmp_path, rehearsal_root, one_device):
    rehearsal.test_end_to_end_line(tmp_path, rehearsal_root, TINY_CELL)


def test_rehearsal_traced_line_reads_the_layer_metrics(
        tmp_path, rehearsal_root, one_device):
    """Every host-side metric that lists the cell finds something in
    it; the whole level 0 is resident, no render takes the host route,
    and requests share groups."""
    with open(os.path.join(rehearsal_root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    proc, lines = rehearsal._run(tmp_path, rehearsal_root, TINY_CELL,
                                 trace=1, seed=2800000123)
    result = rehearsal._result(proc, lines)
    assert result["correct"] is True
    assert result["attempted"] > 32        # more than one round of them
    want = {m["name"] for m in bench["per_layer"]
            if TINY_CELL in m["workloads"]
            and m["source"] != "device_trace"}
    assert set(NEW_METRICS) <= want
    assert set(result["metrics"]) == want
    value = {k: v["value"] for k, v in result["metrics"].items()}
    assert value["host_route_share"] == 0.0
    # PR 33: read in every cell.  Since PR 34 the rehearsal's YAML
    # states its 64^2 tiles (``prewarm: ["4x64@90"]``), they get a
    # bucket of their own and ride to their groups as planes, as the
    # cell's 256^2 tiles do (before: padded into the 256^2 bucket a
    # request at a time, 0 %).
    assert value["plane_stack_share"] == 100.0
    assert value["rawcache_hit_share"] >= 99.0
    assert value["prepare_ms"] > 0.0
    assert 0.0 <= value["group_pad_share"] < 50.0
    # Requests share groups.  That a group passes ``max-batch`` (4 in
    # the rehearsal's posture: the cap follows the bucket) is held by
    # ``tests/test_stock_tile.py``; here the mean would have to pass 4,
    # and on a CPU shared with five other workers the requests reach
    # the batcher one by one (3.0-3.7 a group in two whole runs of the
    # suite, 5-12 alone).
    assert value["group_renders"] > 1.0


def test_rehearsal_part_of_a_group_shed_comes_out_not_correct(
        tmp_path, rehearsal_root, one_device):
    rehearsal.test_part_of_a_group_shed_comes_out_not_correct(
        tmp_path, rehearsal_root, TINY_CELL)


@pytest.mark.parametrize("name", FIRST_CELL)
def test_rehearsal_as_the_harness_first_cell(
        tmp_path, rehearsal_root, one_device, monkeypatch, name):
    """The wrong platform, the altered answer and the controls, which
    the harness drives through ``CELLS[0]``: here that is the new
    cell."""
    monkeypatch.setattr(rehearsal, "CELLS", [TINY_CELL])
    getattr(rehearsal, name)(tmp_path, rehearsal_root)
