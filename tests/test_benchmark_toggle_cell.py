"""The benchmark's deployment ``cycif40-u16-t1024`` with its cell
``cycif40-u16-t1024.toggle``, and the cell ``stock4-u16-t256.single``
(PR 32), on the CPU: the entries against ISSUE 32, the traffic the new
kind generates, the reference that renders the shown subset, the new
reader and the three new per-layer metrics (also against a server that
lacks their series: the parent), and the harness's own rehearsal of
``benchmark/run.py`` through the new cells' files at 64^2 tiles and 8
stored channels: end to end, traced, under both planted faults and with
the controls (``tests/bench_rehearsal.py`` says why from here).
"""

import importlib
import json
import os

import numpy as np
import pytest

from bench_rehearsal import (FIRST_CELL, ONE_DEVICE, REPO, SINGLE_CELL,
                             TINY_SINGLE_CELL, TINY_TOGGLE_CELL,
                             TOGGLE_CELL, build_rehearsal, load)

CONFIG = "cycif40-u16-t1024"
NEW_METRICS = ("shown_render_roofline", "channel_loads_per_render",
               "channel_stack_ms")

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
    # The fourth configuration, the fourth and fifth cells (later PRs
    # append).
    assert [c["name"] for c in bench["configs"]][3] == CONFIG
    assert bench["configs"][3]["reduced"] == ["level0_tiles", "images"]
    toggle, single = bench["workloads"][3:5]
    assert (toggle["name"], toggle["config"], toggle["traffic"],
            toggle["chips"]) == (TOGGLE_CELL, CONFIG, "toggle", 1)
    assert (single["name"], single["config"], single["traffic"],
            single["chips"]) == (SINGLE_CELL, "stock4-u16-t256",
                                 "single", 1)
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index(NEW_METRICS[0])
    assert names[first:first + 3] == list(NEW_METRICS)
    listed = {cell: {m["name"] for m in bench["per_layer"]
                     if cell in m["workloads"]}
              for cell in (w["name"] for w in bench["workloads"])}
    # ``toggle``: every list ``rewindow`` is in but the roofline at the
    # stored channels, plus ``prepare_ms`` and the three new ones.
    # ``read_region_ms`` is NOT listed: every plane of the viewports is
    # resident after the fill, so the span never fires on the change.
    assert listed[TOGGLE_CELL] == (
        listed["wsi4-u16-t1024.rewindow"] - {"render_path_roofline"}
        | {"prepare_ms", "shown_render_roofline"})
    assert "read_region_ms" not in listed[TOGGLE_CELL]
    # ``single``: every list ``pan`` is in.
    assert listed[SINGLE_CELL] == listed["stock4-u16-t256.pan"]
    # Every cell of its time; PR 38's four-chip cell came after.
    for name in NEW_METRICS[1:]:
        assert {m["name"]: m["workloads"] for m in bench["per_layer"]}[
            name] == [w["name"] for w in bench["workloads"]][:7]


def test_the_configuration_is_the_issues():
    config = _json("benchmark", "configs", CONFIG + ".json")
    wsi = _json("benchmark", "configs", "wsi4-u16-t1024.json")
    assert (config["channels"], config["dtype"], config["itemsize"],
            config["tile_edge"], config["content_edge"],
            config["store_chunk"], config["level0_tiles"],
            config["images"], config["pyramid"], config["shown"]) == (
        40, "uint16", 2, 1024, 1024, 1024, [4, 4], 1, True, [5, 6])
    # 16 tiles x 40 channels x 2 MiB = 1.25 GiB at level 0.
    assert 16 * 40 * 1024 * 1024 * 2 == 1.25 * 2**30
    palette = ["FF0000", "00FF00", "FFFF00", "FF00FF", "00FFFF",
               "FFFFFF", "FF8000"]
    assert config["colors"] == ["0000FF"] + [palette[i % 7]
                                             for i in range(39)]
    for key in ("route", "format", "quality"):
        assert config[key] == wsi[key], key
    assert config["reference"] == "render_jpeg_shown"
    assert set(wsi["guarantees"]) < set(config["guarantees"])
    assert sorted(config["reduced"]) == ["images", "level0_tiles"]
    assert {"channels", "shown", "colors", "quality"} <= set(
        config["assumed"])
    assert set(config["limits"]) == {"qtable_diff", "excess_err"}
    # The shipped posture: wsi4's YAML but for the prewarm list.
    with open(os.path.join(REPO, "benchmark", "configs",
                           config["server_yaml"])) as f:
        posture = [ln for ln in f if not ln.startswith("#")]
    with open(os.path.join(REPO, "benchmark", "configs",
                           wsi["server_yaml"])) as f:
        theirs = [ln for ln in f if not ln.startswith("#")]
    assert [ln for ln in posture if "prewarm" not in ln] == [
        ln for ln in theirs if "prewarm" not in ln]
    assert '    prewarm: ["5x1024@90", "6x1024@90"]\n' in posture


def test_the_mixes_carry_the_issues_parameters():
    toggle = _json("benchmark", "traffic", "toggle.json")
    toggle.pop("why")
    assert toggle == {
        "kind": "channel_toggle", "viewers": 4,
        "connections_per_viewer": 6, "think_s": 0,
        "viewport_tiles": [2, 2], "always_shown": [1],
        "markers_shown": 5, "window_start": [0, 2000],
        "window_end": [30000, 65535], "warm_fill": "viewport_channels",
        "warm_fill_block": 5, "warm_pass_s": 3, "warm_max_passes": 8,
        "check_sample": 32, "trace_ms": 3000}
    single = _json("benchmark", "traffic", "single.json")
    pan = _json("benchmark", "traffic", "pan.json")
    assert {k: v for k, v in single.items() if k != "why"} == {
        **{k: v for k, v in pan.items() if k != "why"},
        "viewers": 1, "connections_per_viewer": 1}


# ------------------------------------------------- the traffic kind

@pytest.fixture(scope="module")
def toggle_env():
    return {"config": _json("benchmark", "configs", CONFIG + ".json"),
            "mix": _json("benchmark", "traffic", "toggle.json"),
            "seed": 2**31 + 32}


def test_each_viewer_owns_a_quarter_and_steps_alternate_off_and_on(
        toggle_env):
    from benchmark.traffic_kinds import channel_toggle as kind
    vs = kind.viewers(toggle_env, kind.WINDOW)
    assert len(vs) == 4
    quarters = [{(x, y) for _, x, y in v.share} for v in vs]
    assert quarters == [
        {(bx + x, by + y) for y in (0, 1) for x in (0, 1)}
        for by in (0, 2) for bx in (0, 2)]
    assert len({tuple(v.on) for v in vs}) == 4      # independent draws
    v = vs[2]
    assert 0 not in v.on and len(v.on) == 5
    steps = [[v.next() for _ in range(4)] for _ in range(20)]
    previous = None
    for i, step in enumerate(steps):
        # One step: the viewport's 4 tiles in raster order, one draw of
        # windows, one set of shown channels.
        assert [r["item"] for r in step] == v.share
        assert len({json.dumps(r["windows"]) for r in step}) == 1
        shown = step[0]["shown"]
        assert all(r["shown"] == shown for r in step)
        assert shown[0] == 0 and shown == sorted(set(shown))
        assert len(shown) == (5 if i % 2 == 0 else 6)   # off, on, ...
        if previous is not None:
            # One marker hidden, or one that was not shown added.
            assert len(set(shown) ^ set(previous)) == 1
        previous = shown
    assert len({r["path"] for step in steps for r in step}) == 80
    # Every stored channel is in c=, the hidden ones negative.
    request = steps[3][1]
    c = request["path"].split("c=")[1].split("&")[0].split(",")
    assert len(c) == 40
    assert [int(part.split("|")[0]) for part in c] == [
        n + 1 if n in request["shown"] else -(n + 1) for n in range(40)]
    for part, (ws, we), color in zip(c, request["windows"],
                                     toggle_env["config"]["colors"]):
        assert part.split("|")[1] == f"{ws}:{we}${color}"
        assert 0 <= ws <= 2000 and 30000 <= we <= 65535
    assert "tile=0,1,2,1024,1024" in request["path"]
    # The same seed gives the same traffic; the warm-up's is another.
    again = kind.viewers(toggle_env, kind.WINDOW)[2]
    assert [again.next()["path"] for _ in range(8)] == [
        r["path"] for step in steps[:2] for r in step]
    warm = kind.viewers(toggle_env, kind.WARMUP)[2]
    assert warm.next()["path"] != steps[0][0]["path"]


def test_the_fill_reads_every_tile_and_channel_of_the_viewports_once(
        toggle_env):
    from benchmark.traffic_kinds import channel_toggle as kind
    seen = {}
    for v in kind.viewers(toggle_env, kind.WARMUP):
        fill = v.fill()
        assert len(fill) == 4 * 8               # 39 markers in 8 blocks
        for r in fill:
            assert r["shown"][0] == 0 and len(r["shown"]) in (5, 6)
            for c in r["shown"][1:]:
                seen[r["item"], c] = seen.get((r["item"], c), 0) + 1
    assert len(seen) == 16 * 39 and set(seen.values()) == {1}


# ----------------------------------------------------- the reference

def test_the_reference_renders_the_shown_subset_and_nothing_else():
    from benchmark.references import render_jpeg, render_jpeg_shown
    rng = np.random.default_rng(32)
    config = {"tile_edge": 64, "quality": 0.9,
              "colors": ["0000FF", "FF0000", "00FF00", "FFFF00",
                         "FF00FF", "00FFFF"]}
    images = {1: rng.integers(0, 60000, size=(6, 128, 128)
                              ).astype(np.uint16)}
    windows = [[100 * c, 30000 + 1000 * c] for c in range(6)]
    req = {"item": (1, 1, 0), "windows": windows, "shown": [0, 2, 5]}
    tile = images[1][:, :64, 64:128]
    colors = [(0, 0, 255), (0, 255, 0), (0, 255, 255)]
    body = render_jpeg.control_body(
        tile[[0, 2, 5]], [windows[c] for c in (0, 2, 5)], colors, 90)
    assert body == render_jpeg_shown.control_request(
        images, req, config, quality=90)
    numbers = render_jpeg_shown.compare_request(body, images, req, config)
    assert numbers["qtable_diff"] == 0 and abs(numbers["excess_err"]) < 1e-9
    # The body of another subset (a hidden channel rendered, or a shown
    # one left out) is far from it.
    for other in ([0, 2, 4, 5], [0, 5]):
        wrong = render_jpeg_shown.control_request(
            images, dict(req, shown=other), config, quality=90)
        assert render_jpeg_shown.compare_request(
            wrong, images, req, config)["excess_err"] > 1.0
    with pytest.raises(ValueError):
        render_jpeg_shown.compare_request(body, images,
                                          dict(req, shown=[2, 0]), config)
    with pytest.raises(ValueError):
        render_jpeg_shown.compare_request(body, images,
                                          dict(req, shown=[0, 6]), config)


# ------------------------------------------- the reader and the metrics

def _spec(name: str) -> dict:
    return _json("benchmark", "layer_metrics", name + ".json")


def _read(name: str, ctx: dict):
    spec = _spec(name)
    reader = importlib.import_module(
        f"benchmark.readers.{spec['reader']}")
    return reader.read(ctx, **spec["args"])


def test_the_roofline_counts_the_shown_channels():
    from benchmark import work
    from benchmark.readers import trace_roofline
    peak = _json("benchmark", "peaks.json")["TPU v5 lite"]
    config = _json("benchmark", "configs", CONFIG + ".json")
    ctx = {"trace": {"busy_s": 2.0, "window_s": 3.0},
           "capture": {"renders": 600}, "config": config, "peak": peak,
           "mean_body_bytes": 250000.0}
    got = _read("shown_render_roofline", ctx)
    least, bound = work.least_seconds(
        peak, work.render_bytes(5.5, 1024, 1024, 2, 250000.0),
        work.render_ops(5.5, 1024, 1024))
    assert bound == "bytes"
    assert got == pytest.approx(100.0 * least * 600 / 2.0)
    assert ctx["notes"]["roofline_bound"] == "bytes"
    # The accepted reader on the same capture reckons all 40 stored
    # channels, 80 MiB a tile that nobody reads.
    assert trace_roofline.read(dict(ctx)) == pytest.approx(
        got * (40 * 2**21 + 250000.0) / (5.5 * 2**21 + 250000.0))
    # Nothing to read: never 0.
    assert _read("shown_render_roofline", dict(ctx, trace=None)) is None
    assert _read("shown_render_roofline",
                 dict(ctx, capture={"renders": 0})) is None
    assert _read("shown_render_roofline", dict(
        ctx, config={k: v for k, v in config.items()
                     if k != "shown"})) is None


@pytest.mark.parametrize("name", NEW_METRICS[1:])
def test_a_new_metric_reads_nothing_from_the_parents_metrics(name):
    """The parent exports neither the counter nor the span: the metric
    is left out of its line (None), never 0 and never a raise."""
    parent = {"imageregion_tiles_rendered": 640.0,
              "imageregion_rawcache_hits": 640.0,
              'imageregion_span_count{span="batcher.stage"}': 10.0}
    grown = {k: 2 * v for k, v in parent.items()}
    assert _read(name, {"m0": {}, "m1": parent}) is None
    assert _read(name, {"m0": parent, "m1": grown}) is None


def test_the_new_metrics_read_the_changes_series():
    m0 = {"imageregion_rawcache_channel_loads_total": 640.0,
          "imageregion_tiles_rendered": 100.0,
          'imageregion_span_count{span="handler.channelStack"}': 100.0,
          'imageregion_span_ms_sum{span="handler.channelStack"}': 20.0}
    m1 = {"imageregion_rawcache_channel_loads_total": 640.0 + 50.0,
          "imageregion_tiles_rendered": 300.0,
          'imageregion_span_count{span="handler.channelStack"}': 300.0,
          'imageregion_span_ms_sum{span="handler.channelStack"}': 60.0}
    ctx = {"m0": m0, "m1": m1}
    assert _read("channel_loads_per_render", ctx) == pytest.approx(0.25)
    assert _read("channel_stack_ms", ctx) == pytest.approx(0.2)
    # A resident view: the counter is there and stood still, which is a
    # reading (0), not a missing one.
    still = dict(m1, imageregion_rawcache_channel_loads_total=640.0)
    assert _read("channel_loads_per_render",
                 {"m0": m0, "m1": still}) == 0.0
    # No render in the window: nothing.
    assert _read("channel_loads_per_render", {"m0": m1, "m1": m1}) is None


# ------------------------------------------ run.py through the cells' files

@pytest.fixture()
def one_device(monkeypatch):
    monkeypatch.setenv("XLA_FLAGS", ONE_DEVICE)


@pytest.fixture()
def toggle_kind(monkeypatch):
    """The harness's planted faults are told when the window opens by a
    wrapper around ``closed_loop.window``; this cell's window is the
    new kind's."""
    monkeypatch.setattr(
        rehearsal, "_FAULTY_CHILD", rehearsal._FAULTY_CHILD.replace(
            "benchmark.traffic_kinds.closed_loop",
            "benchmark.traffic_kinds.channel_toggle"))


@pytest.mark.parametrize("cell", [TINY_TOGGLE_CELL, TINY_SINGLE_CELL])
def test_rehearsal_end_to_end_line(tmp_path, rehearsal_root, one_device,
                                   cell):
    rehearsal.test_end_to_end_line(tmp_path, rehearsal_root, cell)


def test_rehearsal_traced_line_reads_the_layer_metrics(
        tmp_path, rehearsal_root, one_device):
    """Every host-side metric that lists the cell finds something in
    it, the new ones among them; every plane of the viewports is
    resident after the fill, so the window loads none, and no count of
    shown channels is padded: the answers are right."""
    with open(os.path.join(rehearsal_root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    proc, lines = rehearsal._run(tmp_path, rehearsal_root,
                                 TINY_TOGGLE_CELL, trace=1,
                                 seed=3200000123)
    result = rehearsal._result(proc, lines)
    assert result["correct"] is True
    assert result["attempted"] > 24
    want = {m["name"] for m in bench["per_layer"]
            if TINY_TOGGLE_CELL in m["workloads"]
            and m["source"] != "device_trace"}
    assert set(NEW_METRICS[1:]) <= want
    assert set(result["metrics"]) == want
    value = {k: v["value"] for k, v in result["metrics"].items()}
    assert value["rawcache_hit_share"] == 100.0
    assert value["channel_loads_per_render"] == 0.0
    assert value["channel_stack_ms"] > 0.0
    assert value["prepare_ms"] > 0.0
    assert value["group_renders"] >= 1.0
    # PR 33: a number in every cell.  Since PR 34 the rehearsal's
    # stated 64^2 tiles (``prewarm: ["3x64@90", "4x64@90"]``) have a
    # bucket of their own and ride to their groups as planes, as the
    # cell's 1024^2 tiles do (before: padded into the 256^2 bucket a
    # request at a time, 0 %).
    assert value["plane_stack_share"] == 100.0


def test_rehearsal_part_of_a_group_shed_comes_out_not_correct(
        tmp_path, rehearsal_root, one_device, toggle_kind):
    rehearsal.test_part_of_a_group_shed_comes_out_not_correct(
        tmp_path, rehearsal_root, TINY_TOGGLE_CELL)


@pytest.mark.parametrize("name", FIRST_CELL)
def test_rehearsal_as_the_harness_first_cell(
        tmp_path, rehearsal_root, one_device, toggle_kind, monkeypatch,
        name):
    """The wrong platform, the altered answer (the nuclear stain, shown
    in every request, under another window) and the controls, which the
    harness drives through ``CELLS[0]``: here that is the new cell."""
    monkeypatch.setattr(rehearsal, "CELLS", [TINY_TOGGLE_CELL])
    getattr(rehearsal, name)(tmp_path, rehearsal_root)
