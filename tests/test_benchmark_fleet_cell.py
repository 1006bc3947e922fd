"""The benchmark's deployment ``fleet4-wsi4-u16-t1024`` and its cell
``fleet4-wsi4-u16-t1024.rewindow`` (PR 38), on the CPU: the entries
against ISSUE 38, the ring's split of the slide, the combined role's
in-process fleet pinning member i to device i (raw-cache shards,
renders and prewarm on the member's own device, byte for byte a one-chip
server's answers), the two new readers, and the harness's own rehearsal
of ``benchmark/run.py`` through the new files on four virtual CPU
devices: end to end, traced, with every member pinned to device 0 by a
planted fault, and with part of every group shed
(``tests/bench_rehearsal.py`` says why from here)."""

import asyncio
import json
import os
import urllib.parse

import jax
import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from bench_rehearsal import REPO, build_rehearsal, load

CONFIG = "fleet4-wsi4-u16-t1024"
CELL = CONFIG + ".rewindow"
TINY_CELL = "fleet4-tiny4-u16-t64.rewindow"
NEW_METRICS = ("busiest_chip_share", "fleet_hop_ms", "fleet_steal_share",
               "fleet_render_roofline")
# The router's queue wait: appended after the cell's first four.
QUEUE_WAIT = "fleet_queue_wait_ms"
CHIP_NEUTRAL = (
    "rawcache_hit_share", "device_idle_share", "queue_wait_ms",
    "lane_wait_ms", "lane_hold_ms", "dispatch_ms", "device_wait_ms",
    "entropy_ms", "group_renders", "compiles_in_window",
    "compile_ms_in_window", "request_ms", "in_group_ms", "respond_ms",
    "loop_lag_ms", "slot_hold_ms", "slot_start_ms", "settle_lag_ms",
    "group_ms", "stage_ms")
FOUR_DEVICES = "--xla_force_host_platform_device_count=4"
PIN_FAULT = os.path.join(REPO, "tests", "faults", "pin_all_to_device0")
TILE_BYTES = 4 * 1024 * 1024 * 2

rehearsal = load("test_rehearsal")


def _json(*parts) -> dict:
    with open(os.path.join(REPO, *parts)) as f:
        return json.load(f)


# ------------------------------------------- BENCHMARK.json and its files

def test_the_new_entries_are_the_issues():
    bench = _json("BENCHMARK.json")
    # The sixth configuration, the eighth cell, the last four metrics.
    entry = bench["configs"][5]
    assert entry["name"] == CONFIG
    assert entry["reduced"] == ["level0_tiles", "images"]
    assert entry["source"].startswith("BASELINE.json configs[2] 4-ch "
                                      "uint16 WSI, 1024^2 tiles, on a "
                                      "4-chip v5e host")
    assert len(entry["source"]) <= 200
    cell = bench["workloads"][7]
    assert (cell["name"], cell["config"], cell["traffic"],
            cell["chips"]) == (CELL, CONFIG, "rewindow16", 4)
    # The first four-chip cell; later cells are appended after it.
    assert [w["chips"] for w in bench["workloads"]][:8] == [1] * 7 + [4]
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index(NEW_METRICS[0])
    assert names[first:first + 5] == list(NEW_METRICS) + [QUEUE_WAIT]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name, layer, source, moves in zip(
            NEW_METRICS,
            ("device", "fleet router", "fleet router", "device programs"),
            ("program_counter", "program_span", "program_counter",
             "device_trace"),
            ("renders_per_s", "p50_ms", "renders_per_s",
             "renders_per_s")):
        m = by_name[name]
        assert (m["layer"], m["source"], m["moves"], m["workloads"]) == (
            layer, source, moves, [CELL])
        spec = _json("benchmark", "layer_metrics", name + ".json")
        assert os.path.exists(os.path.join(
            REPO, "benchmark", "readers", spec["reader"] + ".py"))
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m["workloads"]}
    assert listed == set(CHIP_NEUTRAL) | set(NEW_METRICS) | {QUEUE_WAIT}
    # The single chip's roofline would read up to four times high here.
    assert CELL not in by_name["render_path_roofline"]["workloads"]
    # Appended after the one-chip cells of its time.
    of_its_time = [w["name"] for w in bench["workloads"]][:8]
    for name in CHIP_NEUTRAL:
        assert [w for w in by_name[name]["workloads"]
                if w in of_its_time][-1] == CELL
    # Nothing the benchmark had was taken away or reordered.
    assert [c["name"] for c in bench["configs"]][:5] == [
        "wsi4-u16-t1024", "plate3-u16-p2048", "stock4-u16-t256",
        "cycif40-u16-t1024", "jump5-u16-p1080"]
    assert bench["run_seconds"] == 51


def test_the_queue_wait_metric_lists_the_fleet_cell_alone():
    bench = _json("BENCHMARK.json")
    entry = {m["name"]: m for m in bench["per_layer"]}[QUEUE_WAIT]
    assert entry == {
        "name": QUEUE_WAIT, "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "fleet router",
        "moves": "p50_ms", "workloads": [CELL]}
    spec = _json("benchmark", "layer_metrics", QUEUE_WAIT + ".json")
    assert (spec["reader"], spec["args"]) == ("span_mean",
                                              {"span": "fleet.queueWait"})
    assert {k: spec[k] for k in ("layer", "unit", "moves", "source")} == {
        k: entry[k] for k in ("layer", "unit", "moves", "source")}


def test_the_configuration_is_wsi4s_on_a_larger_slide():
    config = _json("benchmark", "configs", CONFIG + ".json")
    wsi4 = _json("benchmark", "configs", "wsi4-u16-t1024.json")
    own = {"name", "source", "deployment", "server_yaml", "level0_tiles",
           "guarantees", "reduced", "assumed"}
    assert set(config) == set(wsi4)
    for key in set(wsi4) - own:
        assert config[key] == wsi4[key], key
    assert config["reference"] == "render_jpeg"
    assert config["limits"] == {"qtable_diff": 0, "excess_err": 0.07}
    assert config["level0_tiles"] == [32, 24] and config["images"] == 1
    # 768 tiles, 6 GiB at level 0: three one-chip caches, under four.
    assert 32 * 24 * TILE_BYTES == 6 * 2**30
    for key, text in wsi4["guarantees"].items():
        assert config["guarantees"][key] == text
    assert "one-chip server" in config["guarantees"]["fleet"]
    assert list(config["reduced"]) == ["level0_tiles", "images"]
    assert "host" in config["assumed"]
    assert "2 GiB" in config["deployment"]
    assert "8 GiB" in config["deployment"]


def test_the_yaml_is_wsi4s_posture_plus_the_fleet():
    def posture(name):
        with open(os.path.join(REPO, "benchmark", "configs", name)) as f:
            return [ln for ln in f if not ln.startswith("#")]
    mine = posture(CONFIG + ".yaml")
    assert mine == posture("wsi4-u16-t1024.yaml") + [
        "fleet:\n", "    enabled: true\n", "    members: 4\n"]
    from omero_ms_image_region_tpu.server.config import (AppConfig,
                                                         FleetConfig)
    loaded = AppConfig.from_yaml(os.path.join(
        REPO, "benchmark", "configs", CONFIG + ".yaml"))
    assert loaded.fleet == FleetConfig(enabled=True, members=4)
    assert loaded.raw_cache.max_bytes == 2 * 2**30
    assert loaded.renderer.prewarm == ("4x1024@90",)


def test_the_mix_carries_the_issues_parameters():
    mix = _json("benchmark", "traffic", "rewindow16.json")
    assert len(mix.pop("why")) > 0
    assert mix == {
        "kind": "closed_loop", "viewers": 16, "connections_per_viewer": 6,
        "think_s": 0, "order": "seeded", "working_set": 512,
        "warm_fill": "all", "window_start": [0, 2000],
        "window_end": [20000, 45000], "warm_pass_s": 3,
        "warm_max_passes": 8, "check_sample": 32, "trace_ms": 3000}
    from benchmark.traffic_kinds import closed_loop as kind
    config = _json("benchmark", "configs", CONFIG + ".json")
    items = kind.items_of(config)
    viewers = kind.viewers(mix, config, items, 2**31 + 38, kind.WINDOW)
    # The first 16 rows, one row of 32 tiles a viewer.
    assert [v.share for v in viewers] == [
        items[32 * i:32 * (i + 1)] for i in range(16)]


def _route_of(config: dict, item: tuple) -> str:
    """The route key of ``item``'s request as the server computes it:
    the path the generator sends, parsed by the handler's own
    ``ImageRegionCtx.from_params``."""
    from benchmark.traffic_kinds import closed_loop as kind
    from omero_ms_image_region_tpu.parallel.fleet import plane_route_key
    from omero_ms_image_region_tpu.server.ctx import ImageRegionCtx
    path = kind.request_path(config, item, [(0, 30000)] * 4)
    route, _, query = path.partition("?")
    image, z, t = route.strip("/").split("/")[2:5]
    params = dict(urllib.parse.parse_qsl(query), imageId=image, theZ=z,
                  theT=t)
    return plane_route_key(ImageRegionCtx.from_params(params))


def test_the_rings_split_fits_every_members_cache():
    """ISSUE 38: the largest member's share of the 768 tiles under its
    2 GiB; the split is what ``reduced.level0_tiles`` states."""
    from benchmark.traffic_kinds import closed_loop as kind
    from omero_ms_image_region_tpu.parallel.fleet import HashRing
    config = _json("benchmark", "configs", CONFIG + ".json")
    ring = HashRing(["m0", "m1", "m2", "m3"], replicas=64)
    items = kind.items_of(config)
    owners = [ring.member(_route_of(config, item)) for item in items]
    split = [owners.count(f"m{i}") for i in range(4)]
    assert split == [168, 202, 186, 212]
    assert max(split) * TILE_BYTES < 2 * 2**30
    assert [owners[:512].count(f"m{i}") for i in range(4)] == [
        112, 138, 120, 142]
    text = config["reduced"]["level0_tiles"]
    assert "m0 168, m1 202, m2 186, m3 212" in text
    assert "112 / 138 / 120 / 142" in text


# ------------------------------------ the combined role's in-process fleet

EDGE = 64
WINDOWS = [(100, 30000), (0, 40000), (50, 20000), (0, 60000)]


@pytest.fixture(scope="module")
def slide(tmp_path_factory):
    """4 x 4 tiles of 64^2, four uint16 channels, from the benchmark's
    own generator and the program's ingest; ``(data_dir, config,
    images)``."""
    from benchmark import datagen
    config = dict(_json("benchmark", "configs", CONFIG + ".json"),
                  tile_edge=EDGE, content_edge=EDGE, store_chunk=EDGE,
                  level0_tiles=[4, 4])
    root = str(tmp_path_factory.mktemp("fleet_slide"))
    data = datagen.generate(config, 3800000038, root)
    return root, config, data["images"]


@pytest.fixture()
def four_devices(monkeypatch):
    """The suite's CPU backend has eight virtual devices; the fleet of
    four is given the first four, as a four-chip host gives it four."""
    from omero_ms_image_region_tpu.parallel import fleet
    real = fleet.partition_local_devices
    monkeypatch.setattr(
        fleet, "partition_local_devices",
        lambda n, devices=None: real(n, jax.devices()[:4]))
    return jax.devices()[:4]


def _app_config(data_dir: str, fleet: bool):
    from omero_ms_image_region_tpu.server.config import (AppConfig,
                                                         FleetConfig)
    cfg = AppConfig(data_dir=data_dir)
    cfg.raw_cache.enabled = True
    cfg.renderer.cpu_fallback_max_px = 0
    if fleet:
        cfg.fleet = FleetConfig(enabled=True, members=4)
    return cfg


def _requests(config: dict) -> list:
    from benchmark.traffic_kinds import closed_loop as kind
    return [{"item": item, "windows": WINDOWS,
             "path": kind.request_path(config, item, WINDOWS)}
            for item in kind.items_of(config)]


def _renders_by_device(metrics: str) -> dict:
    out = {}
    for line in metrics.splitlines():
        if line.startswith("imageregion_device_renders_total{"):
            key, _, value = line.rpartition(" ")
            out[key.split('"')[1]] = float(value)
    return out


def _device_renders(metrics: str) -> float:
    return sum(_renders_by_device(metrics).values())


def _serve(cfg, requests: list) -> tuple:
    """Every request's body, ``/readyz``'s document, ``/metrics`` and
    the app, from one server of ``cfg``."""
    from omero_ms_image_region_tpu.server.app import create_app
    from omero_ms_image_region_tpu.utils import telemetry
    telemetry.reset()

    async def main():
        app = create_app(cfg)
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            answers = await asyncio.gather(
                *(client.get(r["path"]) for r in requests))
            bodies = []
            for resp in answers:
                assert resp.status == 200, await resp.text()
                bodies.append(await resp.read())
            ready = await (await client.get("/readyz")).json()
            # A request is answered as its tile is coded (first tile
            # out); its group is counted when the whole group is done.
            for _ in range(100):
                metrics = await (await client.get("/metrics")).text()
                if _device_renders(metrics) >= len(requests):
                    break
                await asyncio.sleep(0.05)
            return bodies, ready, metrics, app
        finally:
            await client.close()

    return asyncio.run(main())


def test_the_combined_fleet_pins_member_i_to_device_i(slide,
                                                      four_devices):
    from omero_ms_image_region_tpu.parallel.fleet import LocalMember
    data_dir, config, _ = slide
    _, ready, metrics, app = _serve(_app_config(data_dir, True),
                                    _requests(config))
    ids = [d.id for d in four_devices]
    assert ready["device"]["members"] == {f"m{i}": ids[i]
                                          for i in range(4)}
    for i in range(4):
        assert (f'imageregion_fleet_member_device{{member="m{i}",'
                f'device="{ids[i]}"}} 1') in metrics
    from omero_ms_image_region_tpu.server.app import FLEET_ROUTER_KEY
    router = app[FLEET_ROUTER_KEY]
    resident = 0
    for i, name in enumerate(router.order):
        member = router.members[name]
        assert isinstance(member, LocalMember)
        assert member.services.pin_device == four_devices[i]
        assert member.services.renderer.device == four_devices[i]
        cache = member.services.raw_cache
        assert cache.device == four_devices[i]
        arrays = list(cache._entries.values())
        resident += len(arrays)
        # The member's shard lives on its own device: what its handler
        # read and what the shared prefetcher staged for it alike.
        for arr in arrays:
            assert arr.devices() == {four_devices[i]}
    assert resident >= 16 * 4
    # Every device rendered, and the counter sums to the answers.
    renders = _renders_by_device(metrics)
    assert set(renders) == {str(i) for i in ids}
    assert all(n > 0 for n in renders.values())
    assert sum(renders.values()) == 16


def test_the_fleets_answers_are_one_chips_bytes_and_the_references(
        slide, four_devices):
    from benchmark.references import render_jpeg
    data_dir, config, images = slide
    requests = _requests(config)
    alone, _, _, _ = _serve(_app_config(data_dir, False), requests)
    fleet, _, metrics, _ = _serve(_app_config(data_dir, True), requests)
    assert fleet == alone
    tiny = dict(config, limits={"qtable_diff": 0, "excess_err": 0.06})
    for body, req in zip(fleet, requests):
        compared = render_jpeg.compare_request(body, images, req, tiny)
        assert compared["qtable_diff"] == 0
        assert compared["excess_err"] <= tiny["limits"]["excess_err"]
    # The other members' shards are on /metrics: their sum is the
    # fleet's.
    for i in (1, 2, 3):
        assert f'imageregion_rawcache_misses{{member="m{i}"}}' in metrics
        assert f'imageregion_tiles_rendered{{member="m{i}"}}' in metrics


def test_prewarm_warms_each_member_on_its_own_device(monkeypatch,
                                                     four_devices):
    """One pass of the stated shapes a member, each under its member's
    device, each in span ``prewarm.member``; ``/readyz`` stays pending
    until the last one is warm."""
    from omero_ms_image_region_tpu.server import prewarm
    from omero_ms_image_region_tpu.utils import telemetry
    from omero_ms_image_region_tpu.utils.stopwatch import REGISTRY
    seen = []

    def fake_warm_one(C, edge, quality, batch_sizes, engine, bucket,
                      raw_dtype, exec_cache=None):
        seen.append((jax.config.jax_default_device, edge, exec_cache,
                     telemetry.READINESS.prewarm_pending))

    monkeypatch.setattr(prewarm, "_warm_one", fake_warm_one)
    before = REGISTRY.snapshot().get("prewarm.member", {}).get("count", 0)
    members = [(f"m{i}", d) for i, d in enumerate(four_devices)]
    prewarm.prewarm_renderer(["4x1024@90"], "sparse", 8,
                             ((1024, 1024),), exec_cache="first",
                             members=members)
    assert [s[0] for s in seen] == list(four_devices)
    assert [s[2] for s in seen] == ["first", None, None, None]
    assert all(s[1] == 1024 and s[3] for s in seen)
    assert telemetry.READINESS.prewarm_pending is False
    assert REGISTRY.snapshot()["prewarm.member"]["count"] == before + 4
    # Without members: the process default device, once, no span.
    seen.clear()
    prewarm.prewarm_renderer(["4x1024@90"], "sparse", 8,
                             ((1024, 1024),))
    assert [s[0] for s in seen] == [None]
    assert REGISTRY.snapshot()["prewarm.member"]["count"] == before + 4


# ------------------------------------------------------------ the readers

def _counts(**by_device) -> dict:
    return {f'imageregion_device_renders_total{{device="{d}"}}': float(n)
            for d, n in by_device.items()}


@pytest.mark.parametrize("m1,want", [
    (_counts(**{"0": 110, "1": 110, "2": 110, "3": 110}), 25.0),
    (_counts(**{"0": 410, "1": 10, "2": 10, "3": 10}), 100.0),
    (_counts(**{"0": 40, "1": 35, "2": 30, "3": 25}), 30 / 90 * 100),
])
def test_busiest_chip_share_reads_the_largest_devices_growth(m1, want):
    from benchmark.readers import labelled_max_share
    m0 = _counts(**{"0": 10, "1": 10, "2": 10, "3": 10})
    got = labelled_max_share.read({"m0": m0, "m1": m1},
                                  family="imageregion_device_renders_total",
                                  label="device")
    assert got == pytest.approx(want)


def test_busiest_chip_share_reads_nothing_without_the_family():
    from benchmark.readers import labelled_max_share
    for m in ({}, _counts(**{"0": 5})):
        assert labelled_max_share.read(
            {"m0": m, "m1": m}, family="imageregion_device_renders_total",
            label="device") is None


def test_the_fleets_roofline_divides_by_every_chips_busy_time():
    from benchmark.readers import trace_roofline, trace_roofline_chips
    config = _json("benchmark", "configs", CONFIG + ".json")
    peaks = _json("benchmark", "peaks.json")
    ctx = {"trace": {"busy_s": 0.5, "window_s": 3.0, "chips": 4},
           "capture": {"renders": 600}, "config": config,
           "peak": peaks["TPU v5 lite"], "mean_body_bytes": 180000.0}
    one_chip = trace_roofline.read(dict(ctx))
    four = trace_roofline_chips.read(ctx)
    assert four == pytest.approx(one_chip / 4)
    assert ctx["notes"]["roofline_bound"] == "bytes"
    assert trace_roofline_chips.read(dict(ctx, trace=None)) is None


# ------------------------------------------ run.py through the cell's files

@pytest.fixture(scope="module")
def rehearsal_root(tmp_path_factory):
    return build_rehearsal(tmp_path_factory)


@pytest.fixture()
def four_device_child(monkeypatch):
    monkeypatch.setenv("XLA_FLAGS", FOUR_DEVICES)


def _traced(tmp_path, rehearsal_root, seed, patch=None) -> dict:
    kw = {} if patch is None else {"patch": patch}
    proc, lines = rehearsal._run(tmp_path, rehearsal_root, TINY_CELL,
                                 trace=1, seed=seed, **kw)
    result = rehearsal._result(proc, lines)
    assert result["correct"] is True
    assert result["device"]["count"] == 4
    return {k: v["value"] for k, v in result["metrics"].items()}


def test_rehearsal_end_to_end_line(tmp_path, rehearsal_root,
                                   four_device_child):
    rehearsal.test_end_to_end_line(tmp_path, rehearsal_root, TINY_CELL)


def test_rehearsal_traced_line_reads_the_cells_metrics(
        tmp_path, rehearsal_root, four_device_child):
    """Every host-side metric that lists the cell finds something in
    it, and the four chips share the renders."""
    with open(os.path.join(rehearsal_root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    value = _traced(tmp_path, rehearsal_root, 3800000123)
    want = {m["name"] for m in bench["per_layer"]
            if TINY_CELL in m["workloads"]
            and m["source"] != "device_trace"}
    assert set(value) == want
    assert 25.0 <= value["busiest_chip_share"] <= 50.0
    assert value["rawcache_hit_share"] == 100.0
    assert value["fleet_hop_ms"] > 0.0
    assert 0.0 <= value["fleet_steal_share"] < 100.0
    # Every member admits what its batcher groups (32 renders), more
    # than the cell holds in flight: each request starts at once.
    assert 0.0 <= value[QUEUE_WAIT] < 10.0


def test_rehearsal_every_member_on_device_0_reads_100(
        tmp_path, rehearsal_root, four_device_child):
    """The planted fault: the partition gives every member the first
    device.  Every answer is still right; the metric gives it away."""
    patch = ('br.EXPECT_PLATFORM = "cpu"\n'
             + rehearsal._FAULTY_CHILD.format(fault=PIN_FAULT, repo=REPO))
    value = _traced(tmp_path, rehearsal_root, 3800000124, patch=patch)
    assert value["busiest_chip_share"] == 100.0


def test_rehearsal_part_of_a_group_shed_comes_out_not_correct(
        tmp_path, rehearsal_root, four_device_child):
    rehearsal.test_part_of_a_group_shed_comes_out_not_correct(
        tmp_path, rehearsal_root, TINY_CELL)
