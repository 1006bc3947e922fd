"""The benchmark's deployment ``jump5-u16-p1080`` with its cell
``jump5-u16-p1080.scan``, and the cell ``wsi4-u16-t1024.single``
(PR 34), on the CPU: the entries against ISSUE 34, the mix
``single1024`` on the slide it is for, the two new per-layer metrics'
places, and the harness's own rehearsal of ``benchmark/run.py``
through the new cells' files (5 x 120^2 fields in 64-pixel chunks, a
stated size off the MCU grid as 1080^2 is; 64^2 tiles for the lone
viewer): end to end, traced, under both planted faults and with the
controls (``tests/bench_rehearsal.py`` says why from here)."""

import json
import os

import pytest

from bench_rehearsal import (FIRST_CELL, JUMP_CELL, ONE_DEVICE, REPO,
                             SINGLE1024_CELL, TINY_JUMP_CELL,
                             TINY_SINGLE1024_CELL, build_rehearsal, load)

CONFIG = "jump5-u16-p1080"
NEW_METRICS = ("bucket_fill_share", "stack_pad_device_ms")

rehearsal = load("test_rehearsal")


def _json(*parts) -> dict:
    with open(os.path.join(REPO, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def rehearsal_root(tmp_path_factory):
    return build_rehearsal(tmp_path_factory)


# ------------------------------------------- BENCHMARK.json and its files

def test_the_new_entries_are_the_issues():
    bench = _json("BENCHMARK.json")
    # The fifth configuration, the sixth and seventh cells, the last
    # two metrics (later PRs append).
    entry = bench["configs"][4]
    assert entry["name"] == CONFIG and entry["reduced"] == ["images"]
    assert entry["source"].startswith("JUMP Cell Painting cpg0016")
    assert len(entry["source"]) <= 200
    scan, single = bench["workloads"][5:7]
    assert (scan["name"], scan["config"], scan["traffic"],
            scan["chips"]) == (JUMP_CELL, CONFIG, "scan", 1)
    assert (single["name"], single["config"], single["traffic"],
            single["chips"]) == (SINGLE1024_CELL, "wsi4-u16-t1024",
                                 "single1024", 1)
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index(NEW_METRICS[0])
    assert names[first:first + 2] == list(NEW_METRICS)
    assert first == names.index("plane_stack_share") + 1
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name, layer, source in zip(
            NEW_METRICS, ("batcher", "staging"),
            ("program_counter", "device_trace")):
        m = by_name[name]
        assert (m["layer"], m["source"], m["moves"]) == (
            layer, source, "renders_per_s")
        assert sorted(m["workloads"]) == sorted(
            [JUMP_CELL, "plate3-u16-p2048.scan"])
    listed = {cell: {m["name"] for m in bench["per_layer"]
                     if cell in m["workloads"]}
              for cell in (w["name"] for w in bench["workloads"])}
    # ``jump5-u16-p1080.scan``: every list ``plate3-u16-p2048.scan`` is
    # on but ``unpack_device_ms`` (0 by construction since PR 27), and
    # ``prepare_ms``'s.
    assert listed[JUMP_CELL] == (
        listed["plate3-u16-p2048.scan"] - {"unpack_device_ms"}
        | {"prepare_ms"})
    assert "render_path_roofline" in listed[JUMP_CELL]
    # ``wsi4-u16-t1024.single``: the lists of its 256^2 half but the
    # two that are about 256^2 only.
    assert listed[SINGLE1024_CELL] == listed[
        "stock4-u16-t256.single"] - {"host_route_share",
                                     "group_pad_share"}
    # Nothing the benchmark had was taken away or reordered.
    assert [c["name"] for c in bench["configs"]][:4] == [
        "wsi4-u16-t1024", "plate3-u16-p2048", "stock4-u16-t256",
        "cycif40-u16-t1024"]
    assert [w["name"] for w in bench["workloads"]][:5] == [
        "wsi4-u16-t1024.rewindow", "plate3-u16-p2048.scan",
        "stock4-u16-t256.pan", "cycif40-u16-t1024.toggle",
        "stock4-u16-t256.single"]
    assert bench["run_seconds"] == 51
    assert all(w["chips"] == 1 for w in bench["workloads"])


def test_the_configuration_is_the_issues():
    config = _json("benchmark", "configs", CONFIG + ".json")
    plate = _json("benchmark", "configs", "plate3-u16-p2048.json")
    assert (config["route"], config["channels"], config["dtype"],
            config["itemsize"], config["tile_edge"],
            config["content_edge"], config["level0_tiles"],
            config["store_chunk"], config["pyramid"], config["format"],
            config["quality"], config["reference"],
            config["images"]) == (
        "render_image", 5, "uint16", 2, 1080, 1080, [1, 1], 1024,
        False, "jpeg", 0.9, "render_jpeg", 384)
    # One field of each well of one 384-well plate: 4.17 GiB, twice the
    # raw cache.
    assert 384 * 5 * 1080 * 1080 * 2 / 2**30 == pytest.approx(4.17,
                                                              abs=0.01)
    assert config["colors"] == ["0000FF", "00FF00", "FFFF00", "FF8000",
                                "FF0000"]
    assert list(config["reduced"]) == ["images"]
    assert {"tile_edge", "colors", "store_chunk", "quality"} <= set(
        config["assumed"])
    assert set(plate["guarantees"]) < set(config["guarantees"])
    for key, text in plate["guarantees"].items():
        assert config["guarantees"][key] == text
    assert "1080 x 1080" in config["guarantees"]["size"]
    assert set(config["limits"]) == {"qtable_diff", "excess_err"}
    assert config["limits"]["qtable_diff"] == 0
    # The shipped posture: the sibling's YAML but for the prewarm list,
    # where the site states its plane size.  No key the parent lacks.
    def posture(name):
        with open(os.path.join(REPO, "benchmark", "configs", name)) as f:
            return [ln for ln in f if not ln.startswith("#")]
    mine, theirs = (posture(config["server_yaml"]),
                    posture(plate["server_yaml"]))
    assert [ln for ln in mine if "prewarm" not in ln] == [
        ln for ln in theirs if "prewarm" not in ln]
    assert '    prewarm: ["5x1080@90"]\n' in mine
    from omero_ms_image_region_tpu.server.config import AppConfig
    loaded = AppConfig.from_yaml(os.path.join(
        REPO, "benchmark", "configs", config["server_yaml"]))
    assert loaded.renderer.prewarm == ("5x1080@90",)


def test_the_mixes_carry_the_issues_parameters():
    single = _json("benchmark", "traffic", "single1024.json")
    single.pop("why")
    assert single == {
        "kind": "closed_loop", "viewers": 1, "connections_per_viewer": 1,
        "think_s": 0, "order": "sweep", "working_set": 128,
        "warm_fill": "all", "window_start": [0, 2000],
        "window_end": [20000, 45000], "warm_pass_s": 3,
        "warm_max_passes": 8, "check_sample": 32, "trace_ms": 3000}
    # The accepted mix the new plate cell rides, as the issue read it.
    scan = _json("benchmark", "traffic", "scan.json")
    scan.pop("why")
    assert scan == {
        "kind": "closed_loop", "viewers": 2, "connections_per_viewer": 6,
        "think_s": 0, "order": "sweep", "warm_fill": False,
        "window_start": [0, 2000], "window_end": [20000, 45000],
        "warm_pass_s": 5, "warm_max_passes": 8, "check_sample": 12,
        "trace_ms": 6000}


def test_single1024_fits_the_slide_where_single_does_not():
    """PERF.md section 7 said of this cell "``single.json`` is there":
    its working set of 4,096 exceeds the slide's 256 tiles and the
    generator refuses it.  The cell's own mix walks the 128 tiles the
    fill touches last, one request in flight."""
    from benchmark.traffic_kinds import closed_loop as kind
    config = _json("benchmark", "configs", "wsi4-u16-t1024.json")
    items = kind.items_of(config)
    assert len(items) == 256
    with pytest.raises(ValueError):
        kind.viewers(_json("benchmark", "traffic", "single.json"),
                     config, items, 34, kind.WINDOW)
    mix = _json("benchmark", "traffic", "single1024.json")
    (viewer,) = kind.viewers(mix, config, items, 2**31 + 34, kind.WINDOW)
    assert viewer.share == items[:128]
    walked = [viewer.next() for _ in range(256)]
    assert [r["item"] for r in walked[:128]] == [
        r["item"] for r in walked[128:]]
    assert {r["item"] for r in walked} == set(items[:128])
    assert len({r["path"] for r in walked}) == 256     # fresh windows
    assert "tile=0," in walked[0]["path"] and ",1024,1024" in walked[0][
        "path"]
    # The plate cell: each of two viewers walks its own 192 fields.
    plate = _json("benchmark", "configs", CONFIG + ".json")
    fields = kind.items_of(plate)
    assert fields == [(i + 1, None, None) for i in range(384)]
    a, b = kind.viewers(_json("benchmark", "traffic", "scan.json"),
                        plate, fields, 34, kind.WINDOW)
    assert a.share == fields[:192] and b.share == fields[192:]
    assert "tile=" not in a.next()["path"]


# ------------------------------------------ run.py through the cells' files

@pytest.fixture()
def one_device(monkeypatch):
    monkeypatch.setenv("XLA_FLAGS", ONE_DEVICE)


@pytest.mark.parametrize("cell", [TINY_JUMP_CELL, TINY_SINGLE1024_CELL])
def test_rehearsal_end_to_end_line(tmp_path, rehearsal_root, one_device,
                                   cell):
    rehearsal.test_end_to_end_line(tmp_path, rehearsal_root, cell)


def _traced(tmp_path, rehearsal_root, cell, seed):
    with open(os.path.join(rehearsal_root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    proc, lines = rehearsal._run(tmp_path, rehearsal_root, cell, trace=1,
                                 seed=seed)
    result = rehearsal._result(proc, lines)
    assert result["correct"] is True
    want = {m["name"] for m in bench["per_layer"]
            if cell in m["workloads"] and m["source"] != "device_trace"}
    assert set(result["metrics"]) == want
    return {k: v["value"] for k, v in result["metrics"].items()}


def test_rehearsal_traced_line_of_the_plate_cell(tmp_path, rehearsal_root,
                                                 one_device):
    """Every host-side metric that lists the cell finds something in
    it.  A stated 120^2 field rides its 128^2 bucket (the 1080^2 one's
    1088^2), every group goes down the one program, and the plate is
    larger than the cache, so every field is read."""
    value = _traced(tmp_path, rehearsal_root, TINY_JUMP_CELL, 3400000123)
    assert "bucket_fill_share" in value
    assert "stack_pad_device_ms" not in value       # no device plane here
    assert value["bucket_fill_share"] == pytest.approx(
        100.0 * 120 * 120 / (128 * 128))
    assert value["plane_stack_share"] == 100.0
    assert value["channel_loads_per_render"] == pytest.approx(5.0,
                                                              abs=0.5)
    assert value["rawcache_hit_share"] < 20.0
    assert value["read_region_ms"] > 0.0
    assert value["prepare_ms"] > 0.0
    assert value["group_renders"] >= 1.0


def test_rehearsal_traced_line_of_the_lone_viewer(tmp_path, rehearsal_root,
                                                  one_device):
    value = _traced(tmp_path, rehearsal_root, TINY_SINGLE1024_CELL,
                    3400000124)
    assert value["group_renders"] == 1.0
    assert value["rawcache_hit_share"] == 100.0
    assert value["channel_loads_per_render"] == 0.0
    assert value["prepare_ms"] > 0.0
    assert "bucket_fill_share" not in value
    assert "read_region_ms" not in value


@pytest.mark.parametrize("cell", [TINY_JUMP_CELL, TINY_SINGLE1024_CELL])
def test_rehearsal_part_of_a_group_shed_comes_out_not_correct(
        tmp_path, rehearsal_root, one_device, cell):
    rehearsal.test_part_of_a_group_shed_comes_out_not_correct(
        tmp_path, rehearsal_root, cell)


@pytest.mark.parametrize("name", FIRST_CELL)
def test_rehearsal_as_the_harness_first_cell(
        tmp_path, rehearsal_root, one_device, monkeypatch, name):
    """The wrong platform, the altered answer (the DNA stain under
    another window, on a padded field) and the controls, which the
    harness drives through ``CELLS[0]``: here that is the plate cell."""
    monkeypatch.setattr(rehearsal, "CELLS", [TINY_JUMP_CELL])
    getattr(rehearsal, name)(tmp_path, rehearsal_root)
