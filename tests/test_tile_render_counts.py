"""What the handler counts where it resolves a region
(``telemetry.TILE_RENDERS``): tile renders by pyramid level and by
whether the level's edge cut the region below the tile asked for, and
the unpadded pixels of every render; and the span ``batcher.requestPad``
around the one-request-at-a-time stack and pad of a shape its group
cannot take.  Seeded data, CPU backend."""

import asyncio

import numpy as np
import pytest

from omero_ms_image_region_tpu.io.devicecache import DeviceRawCache
from omero_ms_image_region_tpu.io.service import PixelsService
from omero_ms_image_region_tpu.io.store import build_pyramid
from omero_ms_image_region_tpu.ops.lut import LutProvider
from omero_ms_image_region_tpu.server.batcher import BatchingRenderer
from omero_ms_image_region_tpu.server.ctx import ImageRegionCtx
from omero_ms_image_region_tpu.server.handler import (
    ImageRegionHandler, ImageRegionServices,
)
from omero_ms_image_region_tpu.server.region import RegionDef, tile_extent
from omero_ms_image_region_tpu.services.cache import CacheConfig, Caches
from omero_ms_image_region_tpu.services.metadata import (
    CanReadMemo, LocalMetadataService,
)
from omero_ms_image_region_tpu.utils import telemetry
from omero_ms_image_region_tpu.utils.stopwatch import REGISTRY

IMG = 44
# 150 x 100 px in 64^2 tiles: level 0 has a 22-pixel column and a
# 36-pixel row at its edges; level 1 (75 x 50) is all edge.
W, H = 150, 100


@pytest.fixture(scope="module")
def data_dir(tmp_path_factory):
    root = tmp_path_factory.mktemp("edges")
    rng = np.random.default_rng(44)
    planes = rng.integers(0, 60000, size=(4, 1, H, W)).astype(np.uint16)
    build_pyramid(planes, str(root / str(IMG)), chunk=(64, 64),
                  min_level_size=32).close()
    return str(root)


def params(**more) -> dict:
    c = ",".join(f"{i + 1}|{100 * i}:{30000 + 500 * i}$FF0000"
                 for i in range(4))
    return {"imageId": str(IMG), "theZ": "0", "theT": "0", "c": c,
            "m": "c", "format": "jpeg", "q": "0.9", **more}


def serve(data_dir, queries: list) -> list:
    """Each query through the served path in process: the batching
    renderer with stated 64^2 tiles and the raw cache."""
    async def run():
        renderer = BatchingRenderer(max_batch=4, planes=((64, 64),))
        handler = ImageRegionHandler(ImageRegionServices(
            pixels_service=PixelsService(data_dir),
            metadata=LocalMetadataService(data_dir),
            caches=Caches.from_config(CacheConfig()),
            can_read_memo=CanReadMemo(), renderer=renderer,
            lut_provider=LutProvider(), raw_cache=DeviceRawCache(),
            cpu_fallback_max_px=0))
        try:
            return [await handler.render_image_region(
                ImageRegionCtx.from_params(params(**q))) for q in queries]
        finally:
            await renderer.close()
    return asyncio.run(run())


def pads() -> int:
    return REGISTRY.snapshot().get("batcher.requestPad",
                                   {}).get("count", 0)


@pytest.fixture(autouse=True)
def fresh():
    telemetry.TILE_RENDERS.reset()
    yield
    telemetry.TILE_RENDERS.reset()


def test_tiles_are_counted_by_level_and_edge_and_every_render_by_pixels(
        data_dir):
    before = pads()
    bodies = serve(data_dir, [
        {"tile": "0,0,0,64,64"},       # a whole tile of level 0
        {"tile": "0,2,0,64,64"},       # 22 x 64: the right edge
        {"tile": "0,2,1,64,64"},       # 22 x 36: the corner
        {"tile": "1,0,0,64,64"},       # 64 x 50 at level 1
        {"region": "10,20,30,40"},     # no tile: pixels only
    ])
    assert all(b[:2] == b"\xff\xd8" for b in bodies)
    assert telemetry.TILE_RENDERS.tiles == {
        (0, "full"): 1, (0, "edge"): 2, (1, "edge"): 1}
    assert telemetry.TILE_RENDERS.pixels == (
        64 * 64 + 22 * 64 + 22 * 36 + 64 * 50 + 30 * 40)
    # The stated 64^2 tile rides to its group as planes; the three edge
    # tiles and the region are stacked and padded a request at a time.
    assert pads() == before + 4


def test_the_edge_label_is_the_truncated_regions_not_the_buckets(
        data_dir):
    """A tile of 48^2 asked for inside the level is padded to the 64^2
    bucket and is still whole: ``full``.  A 64^2 tile that the level's
    edge cuts to 22 x 64 is padded to the same bucket: ``edge``."""
    before = pads()
    serve(data_dir, [{"tile": "0,0,0,48,48"}, {"tile": "0,2,0,64,64"}])
    assert telemetry.TILE_RENDERS.tiles == {(0, "full"): 1,
                                            (0, "edge"): 1}
    assert pads() == before + 2
    # A tile of the image's native size (w = h = 0) is whole too.
    telemetry.TILE_RENDERS.reset()
    serve(data_dir, [{"tile": "0,1,0,0,0"}])
    assert telemetry.TILE_RENDERS.tiles == {(0, "full"): 1}


@pytest.fixture()
def compiles():
    """Names of the programs JAX compiles while the fixture lives."""
    import logging

    import jax
    names = []

    class Seen(logging.Handler):
        def emit(self, record):
            message = record.getMessage()
            if message.startswith("Compiling "):
                names.append(message.split(" ")[1])

    handler = Seen(level=logging.DEBUG)
    loggers = [logging.getLogger(n) for n in (
        "jax._src.interpreters.pxla", "jax._src.dispatch")]
    levels = [lg.level for lg in loggers]
    for lg in loggers:
        lg.addHandler(handler)
        lg.setLevel(logging.DEBUG)
    jax.config.update("jax_log_compiles", True)
    try:
        yield names
    finally:
        jax.config.update("jax_log_compiles", False)
        for lg, level in zip(loggers, levels):
            lg.removeHandler(handler)
            lg.setLevel(level)


def test_the_first_edge_tile_warms_every_edge_shape_of_the_image(
        tmp_path, compiles):
    """An image of 170 x 90 px in 64^2 tiles (levels 170 x 90 and
    85 x 45; shapes no other test serves): its first edge tile hands
    the renderer all five edge shapes, compiled on a thread of their
    own, after which serving every edge tile compiles no stack and no
    pad on the request path."""
    import threading

    rng = np.random.default_rng(170)
    planes = rng.integers(0, 60000, size=(4, 1, 90, 170)).astype(np.uint16)
    build_pyramid(planes, str(tmp_path / str(IMG)), chunk=(64, 64),
                  min_level_size=32).close()
    edges = [(0, 2, 0), (0, 0, 1), (0, 2, 1), (1, 0, 0), (1, 1, 0)]

    async def run():
        renderer = BatchingRenderer(max_batch=4, planes=((64, 64),))
        handler = ImageRegionHandler(ImageRegionServices(
            pixels_service=PixelsService(str(tmp_path)),
            metadata=LocalMetadataService(str(tmp_path)),
            caches=Caches.from_config(CacheConfig()),
            can_read_memo=CanReadMemo(), renderer=renderer,
            lut_provider=LutProvider(), raw_cache=DeviceRawCache(),
            cpu_fallback_max_px=0))

        async def tile(level, col, row):
            return await handler.render_image_region(
                ImageRegionCtx.from_params(params(
                    tile=f"{level},{col},{row},64,64")))
        try:
            await tile(0, 0, 0)            # a whole tile warms nothing
            assert renderer._warmed_edges == set()
            await tile(*edges[0])
            assert renderer._warmed_edges == {
                (4, h, w, "uint16") for h, w in (
                    (64, 42), (26, 64), (26, 42), (45, 64), (45, 21))}
            for thread in threading.enumerate():
                if thread.name == "edge-warm":
                    thread.join(timeout=120)
                    assert not thread.is_alive()
            del compiles[:]
            bodies = [await tile(*e) for e in edges]
            return bodies
        finally:
            await renderer.close()

    bodies = asyncio.run(run())
    assert all(b[:2] == b"\xff\xd8" for b in bodies)
    assert not [n for n in compiles
                if "stack_channel_planes" in n or "_pad" in n], compiles


def test_tile_extent_is_the_tile_asked_for_clamped():
    assert tile_extent(RegionDef(0, 0, 64, 48), (256, 256), 2048) == (64,
                                                                     48)
    assert tile_extent(RegionDef(0, 0, 0, 0), (256, 128), 2048) == (256,
                                                                   128)
    assert tile_extent(RegionDef(0, 0, 4096, 0), (512, 512), 2048) == (
        2048, 512)


def test_the_two_families_on_metrics():
    stats = telemetry.TileRenderStats()
    assert stats.metric_lines() == ["imageregion_render_pixels_total 0"]
    stats.count(64 * 64, 0, False)
    stats.count(22 * 64, 0, True)
    stats.count(100, 40, True)          # past the bound: the last label
    stats.count(30 * 40)                # a region: pixels only
    assert stats.metric_lines(',process="sidecar"') == [
        'imageregion_tile_renders_total{level="0",edge="edge",'
        'process="sidecar"} 1',
        'imageregion_tile_renders_total{level="0",edge="full",'
        'process="sidecar"} 1',
        'imageregion_tile_renders_total{level="15",edge="edge",'
        'process="sidecar"} 1',
        'imageregion_render_pixels_total{process="sidecar"} '
        f"{64 * 64 + 22 * 64 + 100 + 30 * 40}"]
    telemetry.TILE_RENDERS.count(10, 2, False)
    lines = telemetry.device_metric_lines(None)
    assert 'imageregion_tile_renders_total{level="2",edge="full"} 1' \
        in lines
    assert "imageregion_render_pixels_total 10" in lines
    telemetry.reset()
    assert telemetry.TILE_RENDERS.tiles == {}
