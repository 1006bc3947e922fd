"""OMERO's stock 256^2 tile on the device path (PR 28): the handler's
route under the DEFAULT configuration, the group cap that follows a
bucket's pixels, a group of 64 against the same tiles rendered alone,
and prewarm of the stock shape.  Seeded data, CPU backend."""

import asyncio
import io

import numpy as np
import pytest
from PIL import Image

from omero_ms_image_region_tpu import codecs
from omero_ms_image_region_tpu.flagship import (
    flagship_settings, synthetic_wsi_tiles,
)
from omero_ms_image_region_tpu.io.service import PixelsService
from omero_ms_image_region_tpu.io.store import build_pyramid
from omero_ms_image_region_tpu.ops.lut import LutProvider
from omero_ms_image_region_tpu.server import batcher as batcher_mod
from omero_ms_image_region_tpu.server.batcher import (
    _BATCH_SHAPES, BatchingRenderer, _pad_batch_size, group_cap,
)
from omero_ms_image_region_tpu.server.config import RendererConfig
from omero_ms_image_region_tpu.server.ctx import ImageRegionCtx
from omero_ms_image_region_tpu.server.handler import (
    ImageRegionHandler, ImageRegionServices,
)
from omero_ms_image_region_tpu.server.prewarm import (
    prewarm_batch_sizes, prewarm_renderer,
)
from omero_ms_image_region_tpu.services.cache import CacheConfig, Caches
from omero_ms_image_region_tpu.services.metadata import (
    CanReadMemo, LocalMetadataService,
)
from omero_ms_image_region_tpu.utils import telemetry
from omero_ms_image_region_tpu.utils.stopwatch import REGISTRY

IMG = 11
C = 4
# Two columns of stock tiles; the second row of tiles is a 256 x 40
# sliver (an image's bottom edge).
WIDTH, HEIGHT = 512, 296
QUALITY = 0.9
COLORS = ("FF0000", "00FF00", "0000FF", "FFFF00")


def run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()


def span_count(name: str) -> int:
    return REGISTRY.snapshot().get(name, {}).get("count", 0)


# ------------------------------------------------------------- the cap

@pytest.mark.parametrize("max_batch, edge, cap", [
    (8, 256, 64),        # the shipped default at a stock tile
    (8, 512, 32),
    (8, 1024, 8),        # max_batch keeps its meaning from here up
    (8, 2048, 8),        # never lowered
    (2, 256, 32),
    (1, 512, 4),
    (16, 512, 64),       # held to the shape ladder's top
    (128, 256, 128),     # a configured cap above the ladder stays
])
def test_group_cap_follows_the_buckets_pixels(max_batch, edge, cap):
    assert _BATCH_SHAPES[-1] == 64
    assert group_cap(max_batch, edge * edge) == cap
    assert BatchingRenderer(max_batch=max_batch).group_cap(
        edge * edge) == cap


def test_prewarm_and_padding_take_the_cap_from_the_same_place():
    """Every shape a 256^2 group can pad to is a shape prewarm
    compiles, and none beyond the cap."""
    cap = group_cap(8, 256 * 256)
    sizes = prewarm_batch_sizes(cap)
    assert sizes == (1, 2, 3, 4, 6, 8, 16, 32, 64)
    assert {_pad_batch_size(n, cap) for n in range(1, 97)} == set(sizes)
    assert prewarm_batch_sizes(group_cap(8, 1024 * 1024)) == (
        1, 2, 3, 4, 6, 8)


class _Recording(BatchingRenderer):
    """Groups as the dispatcher pops them; nothing is rendered."""

    def __init__(self, **kw):
        super().__init__(**kw)
        self.popped = []

    def _render_group_jpeg(self, group):
        self.popped.append(len(group))
        return [b""] * len(group)


@pytest.mark.parametrize("edge, pending, groups", [
    (256, 64, [64]),
    (1024, 8, [8]),
    (2048, 16, [8, 8]),
])
def test_pending_requests_pop_by_the_buckets_cap(edge, pending, groups):
    """Shipped defaults (max_batch 8 = max_batch_limit)."""
    _, settings = flagship_settings(1)
    raw = np.zeros((1, edge, edge), np.uint16)

    async def main():
        r = _Recording(max_batch=8, max_batch_limit=8, linger_ms=2.0)
        try:
            await asyncio.gather(*(
                r.render_jpeg(raw, settings, 90, edge, edge)
                for _ in range(pending)))
            return r.popped
        finally:
            await r.close()

    assert run(main()) == groups


# -------------------------------------- a group of 64 against B = 1

@pytest.mark.parametrize("edge", [64, 256])
def test_a_group_of_64_is_byte_identical_to_each_tile_alone(edge):
    """Every body of one 64-tile group equals the same tile rendered
    as a group of one: batching changes no served byte."""
    B, chans = 64, 2
    rng = np.random.default_rng(2800 + edge)
    _, settings = flagship_settings(chans)
    tiles = synthetic_wsi_tiles(rng, B, chans, edge, edge)

    async def main():
        together = _CountingJpeg(max_batch=8, linger_ms=2.0)
        alone = _CountingJpeg(max_batch=8, linger_ms=0.0)
        try:
            grouped = await asyncio.gather(*(
                together.render_jpeg(t, settings, 90, edge, edge)
                for t in tiles))
            single = [await alone.render_jpeg(t, settings, 90, edge, edge)
                      for t in tiles]
            return grouped, single, together, alone
        finally:
            await together.close()
            await alone.close()

    grouped, single, together, alone = run(main())
    assert together.sizes == [B] and alone.sizes == [1] * B
    assert together.padded_slots == 0 and together.shape_slots == B
    for i in range(B):
        assert grouped[i][:2] == b"\xff\xd8"
        assert grouped[i] == single[i], f"tile {i} differs from B = 1"
    assert Image.open(io.BytesIO(grouped[0])).size == (edge, edge)


class _CountingJpeg(BatchingRenderer):
    def __init__(self, **kw):
        super().__init__(**kw)
        self.sizes = []

    def _render_group_jpeg(self, group):
        self.sizes.append(len(group))
        return super()._render_group_jpeg(group)


def test_padded_slots_count_the_ladders_waste():
    """Five tiles launch the 6-shape: one padded slot of six."""
    _, settings = flagship_settings(1)
    tiles = synthetic_wsi_tiles(np.random.default_rng(5), 5, 1, 32, 32)

    async def main():
        r = BatchingRenderer(max_batch=8, linger_ms=2.0)
        try:
            await asyncio.gather(*(
                r.render_jpeg(t, settings, 90, 32, 32) for t in tiles))
            return r
        finally:
            await r.close()

    r = run(main())
    assert (r.tiles_rendered, r.shape_slots, r.padded_slots) == (5, 6, 1)


# ------------------------------------------------- the handler's route

@pytest.fixture(scope="module")
def stock_image(tmp_path_factory):
    root = tmp_path_factory.mktemp("stock")
    rng = np.random.default_rng(28)
    block = synthetic_wsi_tiles(rng, 1, C, 512, 512)[0]
    planes = np.ascontiguousarray(block[:, :HEIGHT, :WIDTH])
    build_pyramid(planes[:, None], str(root / str(IMG)),
                  chunk=(256, 256), n_levels=1).close()
    return str(root), planes


@pytest.fixture()
def default_services(stock_image):
    """The default posture: nothing of the route or the batcher is set
    (``cpu_fallback_max_px`` is the dataclass's own default)."""
    data_dir, _ = stock_image
    return ImageRegionServices(
        pixels_service=PixelsService(data_dir),
        metadata=LocalMetadataService(data_dir),
        caches=Caches.from_config(CacheConfig.enabled_all()),
        can_read_memo=CanReadMemo(),
        renderer=BatchingRenderer(),
        lut_provider=LutProvider(),
    )


def _params(tile: str, windows) -> dict:
    c = ",".join(f"{i + 1}|{ws}:{we}${COLORS[i]}"
                 for i, (ws, we) in enumerate(windows))
    return {"imageId": str(IMG), "theZ": "0", "theT": "0", "tile": tile,
            "c": c, "m": "c", "format": "jpeg", "q": str(QUALITY)}


def _ctx(tile: str, windows) -> ImageRegionCtx:
    return ImageRegionCtx.from_params(_params(tile, windows))


def _assert_close_to_refimpl(body: bytes, raw: np.ndarray,
                             params: dict) -> None:
    """``chip_smoke.py``'s own reference and JPEG tolerance, the ones
    the 1024^2 tiles are held to on the chip: mean abs error under 8
    grey levels and no worse than libjpeg's at q 0.9 x 1.3 + 0.5."""
    import chip_smoke
    assert chip_smoke.QUALITY == QUALITY
    want = chip_smoke.reference_rgba(raw, params, C)
    assert want.shape[:2] == raw.shape[-2:]
    chip_smoke.compare(body, want, "jpeg", params["tile"])


def test_defaults_keep_the_host_route_below_a_stock_tile_only():
    assert RendererConfig().cpu_fallback_max_px == 256 * 256 - 1
    assert ImageRegionServices.__dataclass_fields__[
        "cpu_fallback_max_px"].default == 256 * 256 - 1


def test_a_full_stock_tile_is_a_device_render_and_a_sliver_is_not(
        default_services, stock_image):
    _, planes = stock_image
    renderer = default_services.renderer
    handler = ImageRegionHandler(default_services)
    windows = [(100 + 37 * c, 40000 - 900 * c) for c in range(C)]
    telemetry.ROUTES.reset()

    async def main():
        try:
            cpu0 = span_count("Renderer.renderAsPackedInt.cpu")
            full = await handler.render_image_region(
                _ctx("0,1,0,256,256", windows))
            assert span_count("Renderer.renderAsPackedInt.cpu") == cpu0
            assert telemetry.ROUTES.counts == {"device": 1, "host": 0}
            sliver = await handler.render_image_region(
                _ctx("0,0,1,256,256", windows))
            assert span_count("Renderer.renderAsPackedInt.cpu") == cpu0 + 1
            assert telemetry.ROUTES.counts == {"device": 1, "host": 1}
            return full, sliver
        finally:
            await renderer.close()

    full, sliver = run(main())
    # Read once the group's thread has ended (first-tile-out answers a
    # tile before its group is counted): one group of one, no more.
    assert (renderer.batches_dispatched, renderer.tiles_rendered,
            renderer.shape_slots, renderer.padded_slots) == (1, 1, 1, 0)
    _assert_close_to_refimpl(full, planes[:, :256, 256:512],
                             _params("0,1,0,256,256", windows))
    assert codecs.decode_to_rgba(sliver).shape[:2] == (40, 256)
    _assert_close_to_refimpl(sliver, planes[:, 256:296, :256],
                             _params("0,0,1,256,256", windows))


def test_a_stock_tile_is_adopted_by_the_raw_cache_and_a_sliver_bypasses_it(
        default_services):
    from omero_ms_image_region_tpu.io.devicecache import DeviceRawCache
    from dataclasses import replace
    cache = DeviceRawCache(max_bytes=64 * 1024 * 1024)
    services = replace(default_services, raw_cache=cache)
    handler = ImageRegionHandler(services)
    windows = [(0, 30000)] * C

    async def main():
        try:
            await handler.render_image_region(
                _ctx("0,0,0,256,256", windows))
            first = (cache.hits, cache.misses)
            await handler.render_image_region(
                _ctx("0,0,0,256,256", [(5, 31000)] * C))
            second = (cache.hits, cache.misses)
            await handler.render_image_region(
                _ctx("0,1,1,256,256", windows))
            return first, second, (cache.hits, cache.misses)
        finally:
            await services.renderer.close()

    first, second, after_sliver = run(main())
    # Lookups count channel planes: C misses, then C hits.
    assert first == (0, C) and second == (C, C)
    assert after_sliver == second
    assert (cache.channel_loads, len(cache)) == (C, C)


# --------------------------------------------------------------- prewarm

def test_prewarm_compiles_the_stock_shape_at_every_batch_size(
        monkeypatch, caplog):
    """``4x256@90`` under the default fallback threshold is not
    skipped, and every padded shape up to the bucket's cap goes
    through the serving entry point."""
    import logging

    from omero_ms_image_region_tpu.ops import jpegenc
    seen = []
    real = jpegenc.render_batch_to_jpeg

    def spy(raw, *args, **kw):
        seen.append((tuple(raw.shape), str(raw.dtype), kw["quality"]))
        return real(raw, *args, **kw)

    monkeypatch.setattr(jpegenc, "render_batch_to_jpeg", spy)
    with caplog.at_level(logging.INFO):
        prewarm_renderer(
            ["4x256@90"], "sparse", max_batch=8,
            buckets=batcher_mod.DEFAULT_BUCKETS,
            cpu_fallback_max_px=RendererConfig().cpu_fallback_max_px)
    assert [s[0][0] for s in seen] == [1, 2, 3, 4, 6, 8, 16, 32, 64]
    assert {s[0][1:] for s in seen} == {(4, 256, 256)}
    assert {s[1:] for s in seen} == {("uint16", 90)}
    assert "skipped" not in caplog.text and "failed" not in caplog.text
    assert "prewarmed 4x256@90" in caplog.text
