"""The benchmark's deployment ``wsi4-u16-t1024x24`` and its cell
``wsi4-u16-t1024x24.coldpan`` on the CPU: the entries against the
table, the configuration (``wsi4-u16-t1024``'s on a slide larger than
the raw cache), its posture and mix, the four new per-layer metrics'
files through their readers, and the harness's own rehearsal of
``benchmark/run.py`` through the new files (24 x 16 tiles of 64^2 in
an 8 MiB raw cache): end to end, traced, and with part of every group
shed (``tests/bench_rehearsal.py`` says why from here)."""

import importlib
import json
import os

import pytest

from bench_rehearsal import (COLDPAN_CELL, ONE_DEVICE, REPO,
                             TINY_COLDPAN_CELL, build_rehearsal, load)

CONFIG = "wsi4-u16-t1024x24"
CELL = COLDPAN_CELL
# name: (unit, better, source, layer, reader)
NEW_METRICS = {
    "prefetch_stage_ms": ("ms", "lower", "program_span", "raw-plane cache",
                          "span_mean"),
    "prefetch_used_share": ("%", "higher", "program_counter",
                            "raw-plane cache", "counter_ratio"),
    "rawcache_dup_load_share": ("loads/load", "lower", "program_counter",
                                "raw-plane cache", "new_counter_ratio"),
    "idle_prefetch_share": ("%", "lower", "device_trace", "device",
                            "labelled_ratio"),
}
# Beyond ``rewindow``'s lists: what the miss path makes read.
READ_PATH = {"read_region_ms", "prepare_ms", "idle_read_share"}
TILE_BYTES = 4 * 1024 * 1024 * 2

rehearsal = load("test_rehearsal")


def _json(*parts) -> dict:
    with open(os.path.join(REPO, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def rehearsal_root(tmp_path_factory):
    return build_rehearsal(tmp_path_factory)


# ------------------------------------------- BENCHMARK.json and its files

def test_the_new_entries_are_the_tables():
    bench = _json("BENCHMARK.json")
    # The seventh configuration, the ninth cell, four metrics after the
    # fleet's queue wait (later changes append).
    entry = bench["configs"][6]
    assert entry["name"] == CONFIG
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["reduced"] == ["level0_tiles", "images"]
    assert entry["source"] != bench["configs"][0]["source"]
    assert len(entry["source"]) <= 200
    cell = bench["workloads"][8]
    assert (cell["name"], cell["config"], cell["traffic"],
            cell["chips"]) == (CELL, CONFIG, "coldpan", 1)
    assert len(cell["why"]) <= 200
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index("prefetch_stage_ms")
    assert names[first - 1] == "fleet_queue_wait_ms"
    assert names[first:first + 4] == list(NEW_METRICS)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name, (unit, better, source, layer, reader) in NEW_METRICS.items():
        # The deep zoom's cell, appended later, reads them too.
        assert by_name[name] == {
            "name": name, "unit": unit, "better": better,
            "source": source, "layer": layer, "moves": "renders_per_s",
            "workloads": [CELL, "wsi4-u16-t1024-24000x16000.zoompan"]}
        spec = _json("benchmark", "layer_metrics", name + ".json")
        assert {k: spec[k] for k in ("layer", "unit", "moves",
                                     "source")} == {
            "layer": layer, "unit": unit, "moves": "renders_per_s",
            "source": source}
        assert spec["reader"] == reader
    # The cell is on every list ``rewindow`` is on, and on the read
    # path's; appended, after the cells of their time.
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m["workloads"]}
    rewindow = {m["name"] for m in bench["per_layer"]
                if "wsi4-u16-t1024.rewindow" in m["workloads"]}
    assert listed == rewindow | READ_PATH | set(NEW_METRICS)
    of_its_time = [w["name"] for w in bench["workloads"]][:9]
    for m in bench["per_layer"]:
        if CELL in m["workloads"]:
            assert [w for w in m["workloads"]
                    if w in of_its_time][-1] == CELL
    # The roofline is ``rewindow``'s: the same 4 x 1024^2 render.
    assert "render_path_roofline" in listed
    # Nothing the benchmark had was taken away or reordered.
    assert [c["name"] for c in bench["configs"]][:6] == [
        "wsi4-u16-t1024", "plate3-u16-p2048", "stock4-u16-t256",
        "cycif40-u16-t1024", "jump5-u16-p1080", "fleet4-wsi4-u16-t1024"]
    assert [w["name"] for w in bench["workloads"]][:8] == [
        "wsi4-u16-t1024.rewindow", "plate3-u16-p2048.scan",
        "stock4-u16-t256.pan", "cycif40-u16-t1024.toggle",
        "stock4-u16-t256.single", "jump5-u16-p1080.scan",
        "wsi4-u16-t1024.single", "fleet4-wsi4-u16-t1024.rewindow"]
    assert bench["run_seconds"] == 51


def test_the_configuration_is_wsi4s_on_a_slide_larger_than_the_cache():
    config = _json("benchmark", "configs", CONFIG + ".json")
    wsi4 = _json("benchmark", "configs", "wsi4-u16-t1024.json")
    own = {"name", "source", "server_yaml", "level0_tiles", "reduced",
           "assumed"}
    assert set(config) == set(wsi4)
    for key in set(wsi4) - own:
        assert config[key] == wsi4[key], key
    assert config["reference"] == "render_jpeg"
    assert config["limits"] == {"qtable_diff": 0, "excess_err": 0.07}
    assert config["level0_tiles"] == [24, 16] and config["images"] == 1
    assert config["assumed"] == {}
    assert list(config["reduced"]) == ["level0_tiles", "images"]
    assert config["reduced"]["images"] == "one slide"
    # 384 tiles of 8 MiB = 3 GiB at level 0, 1.5 times the 2 GiB cache;
    # 1024-pixel chunks tile it exactly.
    assert 24 * 16 * TILE_BYTES == 3 * 2**30
    assert 24 * 16 * TILE_BYTES == 1.5 * 2 * 2**30
    assert "3 GiB" in config["reduced"]["level0_tiles"]
    assert 24 * 1024 % config["store_chunk"] == 0


def test_the_yaml_is_wsi4s_posture_line_for_line():
    def lines(name):
        with open(os.path.join(REPO, "benchmark", "configs", name)) as f:
            return f.readlines()
    assert lines(CONFIG + ".yaml") == lines("wsi4-u16-t1024.yaml")
    from omero_ms_image_region_tpu.server.config import AppConfig
    loaded = AppConfig.from_yaml(os.path.join(
        REPO, "benchmark", "configs", CONFIG + ".yaml"))
    assert loaded.raw_cache.max_bytes == 2 * 2**30
    assert loaded.raw_cache.prefetch is True
    assert loaded.sessions.enabled is False
    assert loaded.renderer.prewarm == ("4x1024@90",)


def test_the_mix_carries_the_tables_parameters():
    mix = _json("benchmark", "traffic", "coldpan.json")
    assert len(mix.pop("why")) > 0
    assert mix == {
        "kind": "closed_loop", "viewers": 4, "connections_per_viewer": 6,
        "think_s": 0, "order": "sweep", "working_set": 384,
        "warm_fill": False, "window_start": [0, 2000],
        "window_end": [20000, 45000], "warm_pass_s": 5,
        "warm_max_passes": 8, "check_sample": 32, "trace_ms": 6000}
    from benchmark.traffic_kinds import closed_loop as kind
    config = _json("benchmark", "configs", CONFIG + ".json")
    items = kind.items_of(config)
    assert len(items) == 384
    viewers = kind.viewers(mix, config, items, 2**31 + 40, kind.WINDOW)
    # Each viewer owns 4 rows of 24 tiles and sweeps them in raster
    # order, cyclically, from a seeded offset.
    assert [v.share for v in viewers] == [
        items[96 * i:96 * (i + 1)] for i in range(4)]
    assert {y for _, _, y in viewers[1].share} == {4, 5, 6, 7}
    v = viewers[2]
    start = v.cursor
    walked = [v.next()["item"] for _ in range(96 + 3)]
    assert walked[:96] == v.share[start:] + v.share[:start]
    assert walked[96:] == walked[:3]


# --------------------------------------- the new metrics through readers

def _read(name: str, m0: dict, m1: dict):
    spec = _json("benchmark", "layer_metrics", name + ".json")
    reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
    return reader.read({"m0": m0, "m1": m1}, **spec["args"])


def test_the_new_metrics_read_the_counters_and_nothing_from_the_parent():
    dup = "imageregion_rawcache_duplicate_loads_total"
    idle = "imageregion_profile_idle_ms_total"
    m0 = {'imageregion_span_count{span="prefetch.stage"}': 10.0,
          'imageregion_span_ms_sum{span="prefetch.stage"}': 100.0,
          "imageregion_prefetch_hits_total": 5.0,
          "imageregion_prefetch_staged_total": 10.0,
          f'{dup}{{by="prefetch"}}': 1.0, f'{dup}{{by="request"}}': 0.0,
          "imageregion_rawcache_channel_loads_total": 100.0,
          f'{idle}{{during="prefetch.stage"}}': 0.0,
          f'{idle}{{during="no_group"}}': 0.0}
    m1 = {'imageregion_span_count{span="prefetch.stage"}': 30.0,
          'imageregion_span_ms_sum{span="prefetch.stage"}': 500.0,
          "imageregion_prefetch_hits_total": 65.0,
          "imageregion_prefetch_staged_total": 90.0,
          f'{dup}{{by="prefetch"}}': 7.0, f'{dup}{{by="request"}}': 4.0,
          "imageregion_rawcache_channel_loads_total": 300.0,
          f'{idle}{{during="prefetch.stage"}}': 30.0,
          f'{idle}{{during="no_group"}}': 90.0}
    assert _read("prefetch_stage_ms", m0, m1) == pytest.approx(20.0)
    assert _read("prefetch_used_share", m0, m1) == pytest.approx(75.0)
    assert _read("rawcache_dup_load_share", m0, m1) == pytest.approx(
        10 / 200)
    assert _read("idle_prefetch_share", m0, m1) == pytest.approx(25.0)
    # The parent: no such span, no such family; it reads nothing.
    parent = [{k: v for k, v in m.items()
               if "prefetch.stage" not in k and not k.startswith(dup)}
              for m in (m0, m1)]
    assert _read("prefetch_stage_ms", *parent) is None
    assert _read("rawcache_dup_load_share", *parent) is None
    # A window in which the prefetcher staged nothing, and a window
    # without a capture: nothing either.
    assert _read("prefetch_used_share", m0, m0) is None
    assert _read("idle_prefetch_share", m0, m0) is None


# ------------------------------------------ run.py through the cell's files

@pytest.fixture()
def one_device(monkeypatch):
    monkeypatch.setenv("XLA_FLAGS", ONE_DEVICE)


def test_rehearsal_end_to_end_line(tmp_path, rehearsal_root, one_device):
    rehearsal.test_end_to_end_line(tmp_path, rehearsal_root,
                                   TINY_COLDPAN_CELL)


def test_rehearsal_traced_line_of_the_cold_pan(tmp_path, rehearsal_root,
                                               one_device):
    """``correct`` against ``render_jpeg``, and every host-side metric
    that lists the cell reads a number: the sweep misses (the slide is
    larger than the cache), the misses are read and uploaded, and the
    prefetcher stages the lattice neighbours on its own threads."""
    with open(os.path.join(rehearsal_root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    proc, lines = rehearsal._run(tmp_path, rehearsal_root,
                                 TINY_COLDPAN_CELL, trace=1,
                                 seed=4000000040)
    result = rehearsal._result(proc, lines)
    assert result["correct"] is True
    assert result["compared"]["unanswered"] == {"value": 0, "limit": 0}
    want = {m["name"] for m in bench["per_layer"]
            if TINY_COLDPAN_CELL in m["workloads"]
            and m["source"] != "device_trace"}
    assert set(result["metrics"]) == want
    value = {k: v["value"] for k, v in result["metrics"].items()}
    assert value["read_region_ms"] > 0.0
    assert value["prefetch_stage_ms"] > 0.0
    assert value["channel_loads_per_render"] > 0.0
    assert value["rawcache_hit_share"] < 100.0
    assert 0.0 <= value["rawcache_dup_load_share"] <= 1.0
    assert value["prefetch_used_share"] >= 0.0


def test_rehearsal_part_of_a_group_shed_comes_out_not_correct(
        tmp_path, rehearsal_root, one_device):
    rehearsal.test_part_of_a_group_shed_comes_out_not_correct(
        tmp_path, rehearsal_root, TINY_COLDPAN_CELL)
