"""The tile miss path and the pan-ahead prefetcher in the tracing: span
``prefetch.stage`` (one a predicted tile the prefetcher stages, on its
own thread), the counter ``imageregion_rawcache_duplicate_loads_total``
(a key loaded twice at once: the cache has no single flight), and a
tile served cold, after its planes were evicted and from planes the
prefetcher staged, byte for byte the same.  Seeded data, CPU backend."""

import asyncio
import threading

import numpy as np
import pytest

from omero_ms_image_region_tpu.io.devicecache import (DeviceRawCache,
                                                      region_key)
from omero_ms_image_region_tpu.io.service import PixelsService
from omero_ms_image_region_tpu.io.store import build_pyramid
from omero_ms_image_region_tpu.ops.lut import LutProvider
from omero_ms_image_region_tpu.server.ctx import ImageRegionCtx
from omero_ms_image_region_tpu.server.handler import (
    ImageRegionHandler, ImageRegionServices, Renderer,
)
from omero_ms_image_region_tpu.server.region import RegionDef
from omero_ms_image_region_tpu.services.cache import CacheConfig, Caches
from omero_ms_image_region_tpu.services.metadata import (
    CanReadMemo, LocalMetadataService,
)
from omero_ms_image_region_tpu.services.prefetch import TilePrefetcher
from omero_ms_image_region_tpu.utils import telemetry
from omero_ms_image_region_tpu.utils.stopwatch import REGISTRY

IMG = 41
C = 4
EDGE = 64
TILES_X, TILES_Y = 3, 2
PLANE_BYTES = EDGE * EDGE * 2
COLORS = ("FF0000", "00FF00", "0000FF", "FFFF00")


@pytest.fixture(autouse=True)
def _fresh():
    telemetry.DUPLICATE_LOADS.reset()
    yield
    telemetry.DUPLICATE_LOADS.reset()


@pytest.fixture(scope="module")
def data_dir(tmp_path_factory):
    """3 x 2 tiles of 64^2, four uint16 channels."""
    root = tmp_path_factory.mktemp("coldpan")
    rng = np.random.default_rng(40)
    planes = rng.integers(0, 60000, size=(
        C, 1, TILES_Y * EDGE, TILES_X * EDGE)).astype(np.uint16)
    build_pyramid(planes, str(root / str(IMG)), chunk=(EDGE, EDGE),
                  n_levels=1).close()
    return str(root)


def _run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()


def _stages() -> int:
    return REGISTRY.snapshot().get("prefetch.stage", {}).get("count", 0)


def _tile_keys(x: int, y: int) -> list:
    region = (x * EDGE, y * EDGE, EDGE, EDGE)
    return [region_key(IMG, 0, 0, 0, region, c) for c in range(C)]


def _services(data_dir, cache, prefetcher=None):
    return ImageRegionServices(
        pixels_service=PixelsService(data_dir),
        metadata=LocalMetadataService(data_dir),
        caches=Caches.from_config(CacheConfig()),   # no bytes cache
        can_read_memo=CanReadMemo(),
        renderer=Renderer(),
        lut_provider=LutProvider(),
        raw_cache=cache,
        cpu_fallback_max_px=0,        # 64^2 tiles take the device path
        prefetcher=prefetcher)


def _render(handler, x: int, y: int) -> bytes:
    c = ",".join(f"{i + 1}|{200 * i}:{30000 + 1000 * i}${COLORS[i]}"
                 for i in range(C))
    ctx = ImageRegionCtx.from_params({
        "imageId": str(IMG), "theZ": "0", "theT": "0",
        "tile": f"0,{x},{y},{EDGE},{EDGE}", "c": c, "m": "c",
        "format": "jpeg", "q": "0.9"})
    return _run(handler.render_image_region(ctx))


# ------------------------------------------------ the duplicate-load race

@pytest.mark.parametrize("digest_index", [True, False])
@pytest.mark.parametrize("by", ["request", "prefetch"])
def test_two_loads_of_one_key_count_one_duplicate_and_charge_once(
        by, digest_index):
    """Both threads are inside the loader before either inserts: both
    read and upload, the later insert finds the key resident and is
    counted under its caller's ``by``; the bytes are charged once."""
    cache = DeviceRawCache(digest_index=digest_index)
    key = region_key(IMG, 0, 0, 0, (0, 0, EDGE, EDGE), 0)
    plane = np.arange(EDGE * EDGE, dtype=np.uint16).reshape(EDGE, EDGE)
    inside, go = threading.Semaphore(0), threading.Event()

    def loader():
        inside.release()
        assert go.wait(10)
        return plane.copy()

    got = []
    threads = [threading.Thread(target=lambda: got.append(
        cache.get_or_load(key, loader, by=by))) for _ in range(2)]
    for t in threads:
        t.start()
    assert inside.acquire(timeout=10) and inside.acquire(timeout=10)
    go.set()
    for t in threads:
        t.join(10)
    assert len(got) == 2
    for arr in got:
        np.testing.assert_array_equal(np.asarray(arr), plane)
    assert telemetry.DUPLICATE_LOADS.counts == {
        "prefetch": int(by == "prefetch"), "request": int(by == "request")}
    assert (cache.misses, cache.channel_loads) == (2, 2)
    assert (len(cache), cache.size_bytes) == (1, PLANE_BYTES)
    # A third load of the resident key is a hit, and no duplicate.
    cache.get_or_load(key, loader, by=by)
    assert cache.hits == 1
    assert sum(telemetry.DUPLICATE_LOADS.counts.values()) == 1


def test_the_counter_is_on_metrics_and_reset_clears_it():
    telemetry.DUPLICATE_LOADS.count("request")
    lines = telemetry.device_metric_lines(None)
    family = "imageregion_rawcache_duplicate_loads_total"
    assert f'{family}{{by="request"}} 1' in lines
    assert f'{family}{{by="prefetch"}} 0' in lines
    telemetry.reset()
    assert telemetry.DUPLICATE_LOADS.counts == {"prefetch": 0,
                                                "request": 0}


# ----------------------------------------------------- span prefetch.stage

def test_a_load_of_missing_planes_records_one_stage(data_dir):
    cache = DeviceRawCache()
    prefetcher = TilePrefetcher(cache)
    src = PixelsService(data_dir).get_pixel_source(IMG)
    keys = _tile_keys(1, 0)
    # One plane of the tile is resident already: three are missing.
    cache.get_or_load(keys[0], lambda: np.zeros((EDGE, EDGE), np.uint16))
    missing = [(c, key) for c, key in enumerate(keys) if c]
    before, staged = _stages(), telemetry.PREFETCH.staged
    try:
        prefetcher._load(src, cache, missing, "route", 0, 0, 0,
                         RegionDef(EDGE, 0, EDGE, EDGE), ("token",))
    finally:
        prefetcher.close()
    assert _stages() == before + 1
    assert prefetcher.staged == 3
    assert telemetry.PREFETCH.staged == staged + 3
    assert cache.absent(keys) == []
    assert sum(telemetry.DUPLICATE_LOADS.counts.values()) == 0


def test_a_task_that_exits_at_the_budget_records_nothing(data_dir):
    cache = DeviceRawCache()
    prefetcher = TilePrefetcher(cache)
    prefetcher.paused = True
    src = PixelsService(data_dir).get_pixel_source(IMG)
    before = _stages()
    try:
        prefetcher._load(src, cache, list(enumerate(_tile_keys(1, 0))),
                         "route", 0, 0, 0,
                         RegionDef(EDGE, 0, EDGE, EDGE), ("token",))
    finally:
        prefetcher.close()
    assert _stages() == before
    assert len(cache) == 0


def test_served_tiles_stage_their_missing_neighbours_and_no_resident_one(
        data_dir):
    """Tile (0, 0)'s lattice neighbours are (1, 0) and (0, 1): one task
    and one span each.  Served again with both resident, it schedules
    no task and records no span."""
    cache = DeviceRawCache()
    prefetcher = TilePrefetcher(cache)
    handler = ImageRegionHandler(_services(data_dir, cache, prefetcher))
    try:
        before, scheduled = _stages(), prefetcher.scheduled
        _render(handler, 0, 0)
        prefetcher.flush()
        assert prefetcher.scheduled == scheduled + 2
        assert _stages() == before + 2
        assert cache.absent(_tile_keys(1, 0) + _tile_keys(0, 1)) == []
        _render(handler, 0, 0)
        prefetcher.flush()
        assert prefetcher.scheduled == scheduled + 2
        assert _stages() == before + 2
    finally:
        prefetcher.close()


# ------------------------------------ one tile, three ways to its planes

def test_a_tile_is_the_same_bytes_cold_evicted_and_prefetched(data_dir):
    # Cold: a fresh cache reads the tile's planes from the store.
    cold = _render(ImageRegionHandler(
        _services(data_dir, DeviceRawCache())), 1, 1)

    # Evicted: a cache of two tiles reads (1, 1), then two others
    # push its planes out, then (1, 1) again.
    small = DeviceRawCache(max_bytes=2 * C * PLANE_BYTES)
    handler = ImageRegionHandler(_services(data_dir, small))
    _render(handler, 1, 1)
    _render(handler, 0, 0)
    _render(handler, 2, 0)
    assert small.absent(_tile_keys(1, 1)) == _tile_keys(1, 1)
    loads = small.channel_loads
    evicted = _render(handler, 1, 1)
    assert small.channel_loads == loads + C

    # Prefetched: (0, 1) served stages its neighbour (1, 1), whose
    # request then finds every plane resident and counts the hits.
    cache = DeviceRawCache()
    prefetcher = TilePrefetcher(cache)
    handler = ImageRegionHandler(_services(data_dir, cache, prefetcher))
    try:
        _render(handler, 0, 1)
        prefetcher.flush()
        assert cache.absent(_tile_keys(1, 1)) == []
        hits = prefetcher.hits
        prefetched = _render(handler, 1, 1)
        prefetcher.flush()
        assert prefetcher.hits == hits + C
    finally:
        prefetcher.close()

    assert cold[:2] == b"\xff\xd8"
    assert cold == evicted == prefetched
