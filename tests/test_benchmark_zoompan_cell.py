"""The benchmark's deployment ``wsi4-u16-t1024-24000x16000`` and its cell
``wsi4-u16-t1024-24000x16000.zoompan`` on the CPU: the entries against
the tables, the configuration (the cold pan's image class on a slide of
24,000 x 16,000 px, a multiple of the tile in neither side), its posture
and mix, the traffic kind ``zoom_pan``'s geometry, the plain reference
``render_jpeg_levels`` against the store's own levels and against the
program at every level and every edge shape of the tiny deployment, the
planted faults and controls it must catch, the four new metrics through
their readers, and the harness's own rehearsal of ``benchmark/run.py``
through the new files (``tests/bench_rehearsal.py`` says why from
here)."""

import asyncio
import collections
import importlib
import json
import os

import numpy as np
import pytest

from bench_rehearsal import (FIRST_CELL, ONE_DEVICE, REPO,
                             TINY_ZOOMPAN_CELL, ZOOMPAN_CELL,
                             build_rehearsal, load)

CONFIG = "wsi4-u16-t1024-24000x16000"
CELL = ZOOMPAN_CELL
COLDPAN = "wsi4-u16-t1024x24.coldpan"
# name: (unit, better, source, layer, reader)
NEW_METRICS = {
    "level0_render_share": ("%", "higher", "program_counter",
                            "request orchestration", "labelled_ratio"),
    "edge_tile_share": ("%", "lower", "program_counter",
                        "request orchestration", "labelled_ratio"),
    "request_pad_ms": ("ms", "lower", "program_span", "staging",
                       "span_mean"),
    "zoom_render_roofline": ("%", "higher", "device_trace",
                             "device programs", "trace_roofline_pixels"),
}
# Beyond the cold pan's lists: what an edge tile's pad makes read.
PAD_PATH = {"bucket_fill_share", "stack_pad_device_ms"}
SEED = 2**31 + 4400

rehearsal = load("test_rehearsal")


def _json(*parts) -> dict:
    with open(os.path.join(REPO, *parts)) as f:
        return json.load(f)


def _tiny_config() -> dict:
    """The rehearsal's deployment: the configuration with the override
    file's sizes."""
    config = _json("benchmark", "configs", CONFIG + ".json")
    over = _json("benchmark", "tests", "rehearsal",
                 f"overrides_{CONFIG}.json")
    config.update(over["configs"][CONFIG])
    return config


def _tiny_mix() -> dict:
    mix = _json("benchmark", "traffic", "zoompan.json")
    over = _json("benchmark", "tests", "rehearsal",
                 f"overrides_{CONFIG}.json")
    mix.update(over["traffic"]["zoompan"])
    return mix


@pytest.fixture(scope="module")
def rehearsal_root(tmp_path_factory):
    return build_rehearsal(tmp_path_factory)


@pytest.fixture(scope="module")
def tiny_data(tmp_path_factory):
    """The tiny deployment's slide from a seed, written through the
    program's own ingest as the harness writes it."""
    from benchmark import datagen
    root = tmp_path_factory.mktemp("zoom")
    data = datagen.generate(_tiny_config(), SEED, str(root))
    return str(root), data["images"]


# ------------------------------------------- BENCHMARK.json and its files

def test_the_new_entries_are_the_tables():
    bench = _json("BENCHMARK.json")
    # The eighth configuration, the tenth cell, four metrics after the
    # cold pan's split share (later changes append).
    entry = bench["configs"][7]
    assert entry["name"] == CONFIG
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["reduced"] == ["level0_tiles", "images"]
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    assert len({c["source"] for c in bench["configs"]}) == len(
        bench["configs"])
    cell = bench["workloads"][9]
    assert (cell["name"], cell["config"], cell["traffic"],
            cell["chips"]) == (CELL, CONFIG, "zoompan", 1)
    assert len(cell["why"]) <= 200
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index("level0_render_share")
    assert names[first - 1] == "entropy_split_share"
    assert names[first:first + 4] == list(NEW_METRICS)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name, (unit, better, source, layer, reader) in NEW_METRICS.items():
        assert by_name[name] == {
            "name": name, "unit": unit, "better": better,
            "source": source, "layer": layer, "moves": "renders_per_s",
            "workloads": [CELL]}
        spec = _json("benchmark", "layer_metrics", name + ".json")
        assert {k: spec[k] for k in ("layer", "unit", "moves",
                                     "source")} == {
            "layer": layer, "unit": unit, "moves": "renders_per_s",
            "source": source}
        assert spec["reader"] == reader
    # The cell is on every list the cold pan is on but the roofline at
    # tile_edge^2, and on the pad's; appended after the cells of their
    # time.
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m["workloads"]}
    coldpan = {m["name"] for m in bench["per_layer"]
               if COLDPAN in m["workloads"]}
    assert listed == (coldpan - {"render_path_roofline"}) | PAD_PATH \
        | set(NEW_METRICS)
    assert CELL not in by_name["render_path_roofline"]["workloads"]
    for name in set(NEW_METRICS) | {"rawcache_hit_share", "read_region_ms",
                                    "prefetch_used_share",
                                    "prefetch_stage_ms",
                                    "rawcache_dup_load_share",
                                    "plane_stack_share", "entropy_ms",
                                    "compiles_in_window",
                                    "device_idle_share", "idle_read_share",
                                    "request_ms", "queue_wait_ms",
                                    "group_renders"} | PAD_PATH:
        assert by_name[name]["workloads"][-1] == CELL, name
    # Nothing the benchmark had was taken away or reordered.
    assert [c["name"] for c in bench["configs"]][:7] == [
        "wsi4-u16-t1024", "plate3-u16-p2048", "stock4-u16-t256",
        "cycif40-u16-t1024", "jump5-u16-p1080", "fleet4-wsi4-u16-t1024",
        "wsi4-u16-t1024x24"]
    assert [w["name"] for w in bench["workloads"]][:9] == [
        "wsi4-u16-t1024.rewindow", "plate3-u16-p2048.scan",
        "stock4-u16-t256.pan", "cycif40-u16-t1024.toggle",
        "stock4-u16-t256.single", "jump5-u16-p1080.scan",
        "wsi4-u16-t1024.single", "fleet4-wsi4-u16-t1024.rewindow",
        COLDPAN]
    assert bench["run_seconds"] == 51


def test_the_configuration_is_the_cold_pans_image_class_at_24000x16000():
    config = _json("benchmark", "configs", CONFIG + ".json")
    coldpan = _json("benchmark", "configs", "wsi4-u16-t1024x24.json")
    own = {"name", "source", "server_yaml", "reference", "content_edge",
           "guarantees", "reduced", "assumed"}
    assert set(config) == set(coldpan)
    for key in set(coldpan) - own:
        assert config[key] == coldpan[key], key
    assert config["reference"] == "render_jpeg_levels"
    assert config["limits"] == {"qtable_diff": 0, "excess_err": 0.07}
    assert (config["tile_edge"], config["content_edge"],
            config["store_chunk"]) == (1024, 1000, 1024)
    assert config["level0_tiles"] == [24, 16] and config["images"] == 1
    assert config["pyramid"] is True
    assert list(config["reduced"]) == ["level0_tiles", "images"]
    assert sorted(config["assumed"]) == ["extent", "session", "viewport"]
    # The guarantees are the cold pan's, and the levels' besides.
    assert {k: v for k, v in config["guarantees"].items()
            if k != "levels"} == coldpan["guarantees"]
    # 24,000 x 16,000 px: a multiple of the tile in neither side; 2.86
    # GiB at level 0 over the 2 GiB raw cache, levels 1-5 under it.
    from benchmark.traffic_kinds import zoom_pan
    levels = zoom_pan.levels_of(config)
    assert levels[0] == (24000, 16000)
    assert 24000 % 1024 and 16000 % 1024
    plane = 4 * 2
    assert 2 * 2**30 < 24000 * 16000 * plane < 3 * 2**30
    assert sum(w * h * plane for w, h in levels[1:]) < 2**30


def test_the_yaml_is_the_cold_pans_line_for_line():
    def lines(name):
        with open(os.path.join(REPO, "benchmark", "configs", name)) as f:
            return f.readlines()
    assert lines(CONFIG + ".yaml") == lines("wsi4-u16-t1024x24.yaml")
    from omero_ms_image_region_tpu.server.config import AppConfig
    loaded = AppConfig.from_yaml(os.path.join(
        REPO, "benchmark", "configs", CONFIG + ".yaml"))
    assert loaded.raw_cache.max_bytes == 2 * 2**30
    assert loaded.raw_cache.prefetch is True
    assert loaded.renderer.prewarm == ("4x1024@90",)


def test_the_mix_carries_the_tables_parameters():
    mix = _json("benchmark", "traffic", "zoompan.json")
    assert 0 < len(mix.pop("why")) <= 200
    assert mix == {
        "kind": "zoom_pan", "viewers": 4, "connections_per_viewer": 6,
        "think_s": 0, "viewport": [1920, 1080], "pan_steps": 4,
        "warm_fill": False, "window_start": [0, 2000],
        "window_end": [20000, 45000], "warm_pass_s": 5,
        "warm_max_passes": 8, "check_sample": 48, "trace_ms": 6000}


# ----------------------------------------------- the kind's geometry

def _schedule(config: dict, mix: dict, sessions: int, seed: int) -> tuple:
    """(requests a session, share of requests by level in %, share of
    edge tiles in %, the edge shapes) over ``sessions`` of one viewer."""
    from benchmark.traffic_kinds import zoom_pan as kind
    levels = kind.levels_of(config)
    edge = int(config["tile_edge"])
    viewer = kind.Viewer(0, mix, config, seed, kind.WARMUP)
    by_level, shapes, n = collections.Counter(), collections.Counter(), 0
    for _ in range(sessions):
        viewer.new_session()
        for image, level, col, row in viewer.queue:
            _, _, w, h = kind.tile_region(levels[level], edge, col, row)
            n += 1
            by_level[level] += 1
            if (w, h) != (edge, edge):
                shapes[(w, h)] += 1
    return (n / sessions,
            {lv: 100.0 * c / n for lv, c in sorted(by_level.items())},
            100.0 * sum(shapes.values()) / n, shapes)


def test_the_kinds_levels_are_the_stores_halving_rule():
    from benchmark.traffic_kinds import zoom_pan as kind
    config = _json("benchmark", "configs", CONFIG + ".json")
    assert kind.levels_of(config) == [
        (24000, 16000), (12000, 8000), (6000, 4000), (3000, 2000),
        (1500, 1000), (750, 500)]
    assert kind.levels_of(dict(config, pyramid=False)) == [(24000, 16000)]
    from omero_ms_image_region_tpu.ops.pyramid import n_pyramid_levels
    assert len(kind.levels_of(config)) == n_pyramid_levels(16000, 24000)


def test_a_viewport_covers_its_tiles_clamped_inside_the_level():
    from benchmark.traffic_kinds import zoom_pan as kind
    # Centred in the middle of level 0: x 11040..12960, y 7460..8540.
    origin, tiles = kind.viewport_tiles((24000, 16000), 1024,
                                        (1920, 1080), (12000, 8000))
    assert origin == (11040, 7460)
    assert tiles == [(c, r) for r in (7, 8) for c in (10, 11, 12)]
    # At the slide's corner the viewport is pushed back inside.
    origin, tiles = kind.viewport_tiles((24000, 16000), 1024,
                                        (1920, 1080), (23990, 15990))
    assert origin == (22080, 14920)
    assert tiles == [(c, r) for r in (14, 15) for c in (21, 22, 23)]
    # A level smaller than the viewport is shown whole.
    assert kind.viewport_tiles((750, 500), 1024, (1920, 1080),
                               (10, 10)) == ((0, 0), [(0, 0)])
    assert kind.viewport_tiles((1500, 1000), 1024, (1920, 1080),
                               (700, 400))[1] == [(0, 0), (1, 0)]
    # A tile at a level's edge is truncated there.
    assert kind.tile_region((24000, 16000), 1024, 23, 15) == (
        23552, 15360, 448, 640)
    assert kind.tile_region((1500, 1000), 1024, 1, 0) == (1024, 0, 476,
                                                          1000)


def test_a_session_zooms_in_pans_and_zooms_out():
    from benchmark.traffic_kinds import zoom_pan as kind
    config = _json("benchmark", "configs", CONFIG + ".json")
    mix = _json("benchmark", "traffic", "zoompan.json")
    items = kind.session_items(config, mix, 1, (12000.0, 8000.0), (1, 0))
    levels = [lv for _, lv, _, _ in items]
    # 5 -> 0 one level a step, pans at 0, then 1 -> 5.
    runs = [lv for i, lv in enumerate(levels) if i == 0
            or lv != levels[i - 1]]
    assert runs == [5, 4, 3, 2, 1, 0, 1, 2, 3, 4, 5]
    assert all(image == 1 for image, _, _, _ in items)
    # Level 0 in: the six tiles around the centre; four pans right,
    # each asking only the column it exposes (two rows).
    zero = [(c, r) for _, lv, c, r in items if lv == 0]
    assert zero[:6] == [(c, r) for r in (7, 8) for c in (10, 11, 12)]
    assert zero[6:] == [(13, 7), (13, 8), (14, 7), (14, 8), (15, 7),
                        (15, 8), (16, 7), (16, 8)]
    # A pan the edge stops turns back.
    items = kind.session_items(config, mix, 1, (23990.0, 8000.0), (1, 0))
    zero = [(c, r) for _, lv, c, r in items if lv == 0]
    assert zero[6:8] == [(20, 7), (20, 8)]
    # Each step's tiles in raster order: level 3's on the way in.
    first = levels.index(3)
    step = [(c, r) for _, lv, c, r in items[first:first + 9] if lv == 3]
    assert len(step) == 4
    assert step == sorted(step, key=lambda t: (t[1], t[0]))


def test_the_per_session_level_mix_and_edge_shapes():
    """What PERF.md states of the committed schedule: ~53 requests a
    session, ~29 % at level 0, a third edge tiles of 15 shapes."""
    config = _json("benchmark", "configs", CONFIG + ".json")
    mix = _json("benchmark", "traffic", "zoompan.json")
    per, by_level, edge, shapes = _schedule(config, mix, 600, SEED)
    assert 50 <= per <= 56
    assert 26 <= by_level[0] <= 31
    assert [round(by_level[lv]) for lv in range(1, 6)] == pytest.approx(
        [22, 21, 17, 8, 4], abs=2)
    assert 30 <= edge <= 35
    assert set(shapes) <= {
        (448, 1024), (1024, 640), (448, 640), (736, 1024), (1024, 832),
        (736, 832), (880, 1024), (1024, 928), (880, 928), (952, 1024),
        (1024, 976), (952, 976), (1024, 1000), (476, 1000), (750, 500)}
    assert len(shapes) >= 13
    # The tiny deployment keeps the shape of it.
    per, by_level, edge, shapes = _schedule(_tiny_config(), _tiny_mix(),
                                            600, SEED)
    assert 48 <= per <= 56 and 25 <= by_level[0] <= 32 and edge >= 25
    assert len(shapes) == 14


def test_requests_carry_the_level_and_fresh_windows():
    from benchmark.traffic_kinds import zoom_pan as kind
    config = _json("benchmark", "configs", CONFIG + ".json")
    mix = _json("benchmark", "traffic", "zoompan.json")
    a = kind.Viewer(1, mix, config, SEED, kind.WARMUP)
    b = kind.Viewer(1, mix, config, SEED, kind.WARMUP)
    first = [a.next() for _ in range(60)]
    assert [r["path"] for r in first] == [b.next()["path"]
                                          for _ in range(60)]
    assert a.sessions == 2
    image, level, col, row = first[0]["item"]
    assert level == 5
    assert f"tile={level},{col},{row},1024,1024" in first[0]["path"]
    assert first[0]["path"].startswith(
        "/webgateway/render_image_region/1/0/0?")
    assert len({tuple(map(tuple, r["windows"])) for r in first}) == 60
    for r in first:
        for ws, we in r["windows"]:
            assert 0 <= ws <= 2000 and 20000 <= we <= 45000
    other = kind.Viewer(1, mix, config, SEED + 1, kind.WARMUP)
    assert other.next()["path"] != first[0]["path"]


# ------------------------------------------------------- the reference

def test_the_references_levels_are_the_stores_bit_for_bit(tiny_data):
    from benchmark.references import render_jpeg_levels as ref
    from benchmark.traffic_kinds import zoom_pan as kind
    from omero_ms_image_region_tpu.io.store import ChunkedPyramidStore
    from omero_ms_image_region_tpu.server.region import RegionDef
    root, images = tiny_data
    store = ChunkedPyramidStore(os.path.join(root, "1"))
    try:
        dims = store.resolution_descriptions()
        assert dims == kind.levels_of(_tiny_config())
        assert len(dims) == 5
        for n, (w, h) in enumerate(dims):
            mine = ref.level(images, 1, n)
            theirs = np.stack([store.get_region(0, c, 0,
                                                RegionDef(0, 0, w, h), n)
                               for c in range(4)])
            assert mine.shape == theirs.shape == (4, h, w)
            assert np.array_equal(mine, theirs), n
    finally:
        store.close()


def test_the_rounding_is_half_to_even_in_integers():
    from benchmark.references import render_jpeg_levels as ref
    plane = np.array([[[1, 2, 1, 2, 9], [2, 1, 2, 1, 9],
                       [0, 0, 3, 3, 9], [1, 1, 2, 2, 9],
                       [9, 9, 9, 9, 9]]], np.uint16)
    # Sums 6 (1.5 -> 2), 6 (1.5 -> 2), 2 (0.5 -> 0), 10 (2.5 -> 2);
    # the odd last row and column are left out.
    assert ref.downsample(plane).tolist() == [[[2, 2], [0, 2]]]
    top = np.full((1, 2, 2), 65535, np.uint16)
    assert ref.downsample(top).tolist() == [[[65535]]]


def _tiny_requests(config: dict, images: dict) -> list:
    """For every level of the tiny slide, a full tile (where the level
    has one) and each edge shape: the right column, the bottom row and
    the corner."""
    from benchmark.traffic_kinds import zoom_pan as kind
    rng = np.random.default_rng(SEED)
    edge = int(config["tile_edge"])
    reqs = []
    for level, (w, h) in enumerate(kind.levels_of(config)):
        cols, rows = -(-w // edge), -(-h // edge)
        for col, row in {(0, 0), (cols - 1, 0), (0, rows - 1),
                         (cols - 1, rows - 1)}:
            windows = [(int(rng.integers(0, 2001)),
                        int(rng.integers(20000, 45001)))
                       for _ in range(4)]
            item = (1, level, col, row)
            reqs.append({"item": item, "windows": windows,
                         "path": kind.request_path(config, item,
                                                   windows)})
    return reqs


def _judge(numbers: dict, limits: dict) -> bool:
    return "error" not in numbers and all(
        numbers[k] <= limit for k, limit in limits.items())


def test_the_program_agrees_with_the_reference_at_every_level_and_edge(
        tiny_data):
    """The served path in process (the batching renderer with the
    rehearsal's stated 64^2 tiles, the raw cache, the device JPEG) at
    every level and edge shape of the tiny slide, against the
    reference; the handler counts each tile's level and edge from the
    truncated region."""
    import urllib.parse

    from benchmark.references import render_jpeg_levels as ref
    from benchmark.traffic_kinds import zoom_pan as kind
    from omero_ms_image_region_tpu.io.devicecache import DeviceRawCache
    from omero_ms_image_region_tpu.io.service import PixelsService
    from omero_ms_image_region_tpu.ops.lut import LutProvider
    from omero_ms_image_region_tpu.server.batcher import BatchingRenderer
    from omero_ms_image_region_tpu.server.ctx import ImageRegionCtx
    from omero_ms_image_region_tpu.server.handler import (
        ImageRegionHandler, ImageRegionServices)
    from omero_ms_image_region_tpu.services.cache import (CacheConfig,
                                                          Caches)
    from omero_ms_image_region_tpu.services.metadata import (
        CanReadMemo, LocalMetadataService)
    from omero_ms_image_region_tpu.utils import telemetry

    root, images = tiny_data
    config = _tiny_config()
    reqs = _tiny_requests(config, images)
    levels = kind.levels_of(config)
    shapes = {(lv,) + kind.tile_region(levels[lv], 64, c, r)[2:]
              for _, lv, c, r in (q["item"] for q in reqs)}
    # Five levels; every level's edge shapes (the top level, 91 x 61,
    # has two tiles: 64 x 61 and 27 x 61).
    assert {s[0] for s in shapes} == set(range(5))
    assert len(shapes) == 18

    async def serve():
        renderer = BatchingRenderer(max_batch=4, planes=((64, 64),))
        handler = ImageRegionHandler(ImageRegionServices(
            pixels_service=PixelsService(root),
            metadata=LocalMetadataService(root),
            caches=Caches.from_config(CacheConfig()),
            can_read_memo=CanReadMemo(), renderer=renderer,
            lut_provider=LutProvider(), raw_cache=DeviceRawCache(),
            cpu_fallback_max_px=0))
        try:
            bodies = []
            for req in reqs:
                query = dict(urllib.parse.parse_qsl(
                    req["path"].partition("?")[2]))
                query.update(imageId="1", theZ="0", theT="0")
                bodies.append(await handler.render_image_region(
                    ImageRegionCtx.from_params(query)))
            return bodies
        finally:
            await renderer.close()

    telemetry.TILE_RENDERS.reset()
    bodies = asyncio.run(serve())
    worst = 0.0
    for req, body in zip(reqs, bodies):
        numbers = ref.compare_request(body, images, req, config)
        assert _judge(numbers, config["limits"]), (req["item"], numbers)
        worst = max(worst, numbers["excess_err"])
    assert worst <= config["limits"]["excess_err"]
    # Counted where the handler resolved each region.
    want = collections.Counter()
    pixels = 0
    for _, lv, c, r in (q["item"] for q in reqs):
        _, _, w, h = kind.tile_region(levels[lv], 64, c, r)
        want[(lv, "full" if (w, h) == (64, 64) else "edge")] += 1
        pixels += w * h
    assert telemetry.TILE_RENDERS.tiles == dict(want)
    assert telemetry.TILE_RENDERS.pixels == pixels


# ----------------------------------- faults the comparison has to catch

def test_a_body_from_the_wrong_level_is_not_correct(tiny_data):
    """The tile (col, row) of the level above or below answered in its
    place: a whole 64^2 tile either way, so only the samples differ."""
    from benchmark.references import render_jpeg_levels as ref
    _, images = tiny_data
    config = _tiny_config()
    windows = [(100, 30000)] * 4
    for level, wrong in ((1, 0), (0, 1), (3, 2)):
        req = {"item": (1, level, 1, 0), "windows": windows}
        body = ref.control_request(images, {"item": (1, wrong, 1, 0),
                                            "windows": windows}, config,
                                   quality=90)
        numbers = ref.compare_request(body, images, req, config)
        assert not _judge(numbers, config["limits"]), (level, numbers)
        assert numbers["excess_err"] > 10 * config["limits"]["excess_err"]
    # The same body at its own level is correct: the fault is the level.
    body = ref.control_request(images, {"item": (1, 2, 1, 0),
                                        "windows": windows}, config,
                               quality=90)
    assert _judge(ref.compare_request(
        body, images, {"item": (1, 2, 1, 0), "windows": windows},
        config), config["limits"])
    # Where the wrong level's tile is cut otherwise, the shapes differ.
    req = {"item": (1, 3, 1, 1), "windows": windows}
    body = ref.control_request(images, {"item": (1, 2, 1, 1),
                                        "windows": windows}, config,
                               quality=90)
    assert "error" in ref.compare_request(body, images, req, config)


def test_an_edge_tile_padded_and_not_cropped_is_not_correct(tiny_data):
    from benchmark.references import render_jpeg, render_jpeg_levels as ref
    _, images = tiny_data
    config = _tiny_config()
    windows = [(100, 30000)] * 4
    colors = [tuple(int(h[i:i + 2], 16) for i in (0, 2, 4))
              for h in config["colors"]]
    # Level 1 is 732 x 488: column 11 holds 28 pixels, row 7 40.
    for col, row in ((11, 0), (0, 7), (11, 7)):
        req = {"item": (1, 1, col, row), "windows": windows}
        raw, _, _ = ref._inputs(images, req, config)
        assert raw.shape[1:] != (64, 64)
        padded = np.pad(raw, ((0, 0), (0, 64 - raw.shape[1]),
                              (0, 64 - raw.shape[2])), mode="edge")
        body = render_jpeg.control_body(padded, windows, colors, 90)
        numbers = ref.compare_request(body, images, req, config)
        assert "error" in numbers and "expected" in numbers["error"]
        assert not _judge(numbers, config["limits"])


@pytest.mark.parametrize("control", ["bits8_q80", "q80"])
def test_the_control_at_lower_precision_is_not_correct(tiny_data,
                                                       control):
    """``control.py``'s knobs through this reference: the answers one
    step below the configuration come out not correct, as ``run.judge``
    holds a sample (any answer over a limit).  Every answer at levels 0
    and 1 reads over the limit; at the coarse levels, where 8-16 x
    downsampling leaves a noise-like tile that both qualities code
    about as badly, it need not (PERF.md §6)."""
    from benchmark.control import CONTROLS
    from benchmark.references import render_jpeg_levels as ref
    _, images = tiny_data
    config = _tiny_config()
    limit = config["limits"]["excess_err"]
    readings = collections.defaultdict(list)
    for req in _tiny_requests(config, images):
        body = ref.control_request(images, req, config, **CONTROLS[control])
        readings[req["item"][1]].append(ref.compare_request(
            body, images, req, config)["excess_err"])
    assert min(readings[0] + readings[1]) > 2 * limit
    assert max(max(r) for r in readings.values()) > limit


# --------------------------------------- the new metrics through readers

def _read(name: str, ctx: dict):
    spec = _json("benchmark", "layer_metrics", name + ".json")
    reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
    return reader.read(ctx, **spec["args"])


def _tiles(counts: dict) -> dict:
    return {f'imageregion_tile_renders_total{{level="{lv}",edge="{e}"}}':
            float(n) for (lv, e), n in counts.items()}


def test_the_new_metrics_read_the_counters_and_nothing_from_the_parent():
    m0 = {**_tiles({(0, "full"): 10, (0, "edge"): 2, (3, "edge"): 5}),
          'imageregion_span_count{span="batcher.requestPad"}': 4.0,
          'imageregion_span_ms_sum{span="batcher.requestPad"}': 8.0}
    m1 = {**_tiles({(0, "full"): 30, (0, "edge"): 6, (3, "edge"): 25,
                    (10, "full"): 40}),
          'imageregion_span_count{span="batcher.requestPad"}': 14.0,
          'imageregion_span_ms_sum{span="batcher.requestPad"}': 33.0}
    # 24 of 84 tile renders at level 0 (level 10's are not level 0's);
    # 4 + 20 of 84 edge tiles; 25 ms over 10 pads.
    ctx = {"m0": m0, "m1": m1}
    assert _read("level0_render_share", ctx) == pytest.approx(
        100 * 24 / 84)
    assert _read("edge_tile_share", ctx) == pytest.approx(100 * 24 / 84)
    assert _read("request_pad_ms", ctx) == pytest.approx(2.5)
    # The parent: no such family, no such span; it reads nothing.
    parent = {"m0": {"imageregion_renders_routed_total"
                     '{route="device"}': 5.0},
              "m1": {"imageregion_renders_routed_total"
                     '{route="device"}': 90.0}}
    for name in NEW_METRICS:
        assert _read(name, parent) is None, name
    # A window with no tile rendered reads nothing either.
    assert _read("level0_render_share", {"m0": m1, "m1": m1}) is None


def test_the_zoom_roofline_counts_each_renders_own_pixels():
    """``trace_roofline``'s arithmetic at the mean unpadded pixels a
    render: a window of whole 1024^2 tiles reads what ``trace_roofline``
    reads, and one of half-size edge tiles reads half the bytes'
    share (bytes bound), whatever bucket they were padded to."""
    from benchmark import work
    from benchmark.readers import trace_roofline
    peak = _json("benchmark", "peaks.json")["TPU v5 lite"]
    config = _json("benchmark", "configs", CONFIG + ".json")
    routed = 'imageregion_renders_routed_total{route="device"}'

    def ctx(mean_px, renders=100):
        return {"m0": {routed: 0.0,
                       "imageregion_render_pixels_total": 0.0},
                "m1": {routed: float(renders),
                       "imageregion_render_pixels_total":
                       float(mean_px * renders)},
                "trace": {"busy_s": 0.5, "window_s": 6.0, "chips": 1},
                "capture": {"renders": 200}, "config": config,
                "peak": peak, "mean_body_bytes": 0.0}

    whole = _read("zoom_render_roofline", ctx(1024 * 1024))
    assert whole == pytest.approx(trace_roofline.read(ctx(1024 * 1024)))
    least, bound = work.least_seconds(
        peak, work.render_bytes(4, 1024, 1024, 2, 0.0),
        work.render_ops(4, 1024, 1024))
    assert bound == "bytes"
    assert whole == pytest.approx(100 * least * 200 / 0.5)
    half = _read("zoom_render_roofline", ctx(512 * 1024))
    assert half == pytest.approx(whole / 2)
    assert 0 < half < 100
    # Nothing without a trace, without the counter (the parent), or
    # without renders in the window: never 0.
    assert _read("zoom_render_roofline", dict(ctx(1), trace=None)) is None
    parent = ctx(1024 * 1024)
    for m in (parent["m0"], parent["m1"]):
        del m["imageregion_render_pixels_total"]
    assert _read("zoom_render_roofline", parent) is None
    assert _read("zoom_render_roofline", ctx(1024, renders=0)) is None


# ------------------------------------------ run.py through the cell's files

@pytest.fixture()
def one_device(monkeypatch):
    monkeypatch.setenv("XLA_FLAGS", ONE_DEVICE)


@pytest.fixture()
def zoom_kind(monkeypatch):
    """The harness's planted faults are told when the window opens by a
    wrapper around ``closed_loop.window``; this cell's window is the
    new kind's."""
    monkeypatch.setattr(
        rehearsal, "_FAULTY_CHILD", rehearsal._FAULTY_CHILD.replace(
            "benchmark.traffic_kinds.closed_loop",
            "benchmark.traffic_kinds.zoom_pan"))


def test_rehearsal_end_to_end_line(tmp_path, rehearsal_root, one_device):
    rehearsal.test_end_to_end_line(tmp_path, rehearsal_root,
                                   TINY_ZOOMPAN_CELL)


def test_rehearsal_traced_line_of_the_deep_zoom(tmp_path, rehearsal_root,
                                                one_device):
    """``correct`` against ``render_jpeg_levels``, and every host-side
    metric that lists the cell reads a number: tiles at every level,
    edge tiles padded a request at a time, level-0 misses through the
    small raw cache and the prefetcher at the request's level."""
    with open(os.path.join(rehearsal_root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    proc, lines = rehearsal._run(tmp_path, rehearsal_root,
                                 TINY_ZOOMPAN_CELL, trace=1,
                                 seed=4400000044)
    result = rehearsal._result(proc, lines)
    assert result["correct"] is True
    assert result["compared"]["unanswered"] == {"value": 0, "limit": 0}
    want = {m["name"] for m in bench["per_layer"]
            if TINY_ZOOMPAN_CELL in m["workloads"]
            and m["source"] != "device_trace"}
    assert set(result["metrics"]) == want
    value = {k: v["value"] for k, v in result["metrics"].items()}
    assert 0.0 < value["level0_render_share"] < 100.0
    assert 0.0 < value["edge_tile_share"] < 100.0
    assert value["request_pad_ms"] > 0.0
    assert value["read_region_ms"] > 0.0
    assert value["bucket_fill_share"] < 100.0
    assert value["plane_stack_share"] < 100.0
    assert value["rawcache_hit_share"] < 100.0


def test_rehearsal_part_of_a_group_shed_comes_out_not_correct(
        tmp_path, rehearsal_root, one_device, zoom_kind):
    rehearsal.test_part_of_a_group_shed_comes_out_not_correct(
        tmp_path, rehearsal_root, TINY_ZOOMPAN_CELL)


ALTERED = "test_altered_answer_comes_out_not_correct"


@pytest.mark.parametrize("name", [n for n in FIRST_CELL if n != ALTERED])
def test_rehearsal_as_the_harness_first_cell(
        tmp_path, rehearsal_root, one_device, zoom_kind, monkeypatch,
        name):
    """The wrong platform and the controls, which the harness drives
    through ``CELLS[0]``: here that is the new cell."""
    monkeypatch.setattr(rehearsal, "CELLS", [TINY_ZOOMPAN_CELL])
    getattr(rehearsal, name)(tmp_path, rehearsal_root)


def test_rehearsal_altered_answer_comes_out_not_correct(
        tmp_path, rehearsal_root, one_device, zoom_kind):
    """The harness's altered answer (every request's first channel
    under three quarters of its window) through the new cell: not
    correct, every answer came, the tables are right, and the worst
    reading is over the limit.  Unlike at level 0, not every
    sampled answer need read wrong: a tile of the coarse levels is
    noise-like at 64^2 and the comparison cannot see the window there
    (``test_the_altered_window_is_seen_at_the_fine_levels``)."""
    fault = os.path.join(REPO, "benchmark", "tests", "faults",
                         "altered_answer")
    proc, lines = rehearsal._run(
        tmp_path, rehearsal_root, TINY_ZOOMPAN_CELL,
        patch='br.EXPECT_PLATFORM = "cpu"\n'
              + rehearsal._FAULTY_CHILD.format(fault=fault,
                                               repo=rehearsal.REPO))
    result = rehearsal._result(proc, lines)
    assert result["correct"] is False
    assert result["failed"] >= 1
    assert result["compared"]["unanswered"]["value"] == 0
    assert result["compared"]["excess_err"]["value"] \
        > result["compared"]["excess_err"]["limit"]
    assert result["compared"]["qtable_diff"]["value"] == 0
    assert "WRONG answer" in proc.stdout


def test_the_altered_window_is_seen_at_the_fine_levels(tiny_data):
    """The altered answer's fault, rendered by the reference itself at
    quality 90, at every level and edge shape of the tiny slide: every
    answer at levels 0 and 1 reads far over the limit; at levels 3 and
    4 (16-256 samples of level 0 a pixel, sub-pixel blobs) it is
    under, which is what the comparison can see of such a tile."""
    from benchmark.references import render_jpeg, render_jpeg_levels as ref
    _, images = tiny_data
    config = _tiny_config()
    limit = config["limits"]["excess_err"]
    readings = collections.defaultdict(list)
    for req in _tiny_requests(config, images):
        raw, colors, quality = ref._inputs(images, req, config)
        windows = [list(w) for w in req["windows"]]
        windows[0][1] = windows[0][0] + 0.75 * (windows[0][1]
                                                - windows[0][0])
        body = render_jpeg.libjpeg_bytes(
            render_jpeg.render_rgb(raw, windows, colors), quality)
        readings[req["item"][1]].append(ref.compare_request(
            body, images, req, config)["excess_err"])
    assert min(readings[0] + readings[1]) > 1.5 * limit
    assert max(readings[3] + readings[4]) < limit
