"""End-to-end handler flows over a synthetic on-disk pyramid: cache-first
ordering, ACL gating, projection, flip, mask caching rules."""

import asyncio
import json

import numpy as np
import pytest

from omero_ms_image_region_tpu import codecs
from omero_ms_image_region_tpu.io.service import PixelsService
from omero_ms_image_region_tpu.io.store import build_pyramid
from omero_ms_image_region_tpu.models.mask import Mask
from omero_ms_image_region_tpu.ops.lut import LutProvider
from omero_ms_image_region_tpu.server.ctx import (
    BadRequestError, ImageRegionCtx, ShapeMaskCtx,
)
from omero_ms_image_region_tpu.server.handler import (
    ImageRegionHandler, ImageRegionServices, NotFoundError, Renderer,
    ShapeMaskHandler,
)
from omero_ms_image_region_tpu.services.cache import CacheConfig, Caches
from omero_ms_image_region_tpu.services.metadata import (
    CanReadMemo, LocalMetadataService, write_mask,
)

IMG = 7
MASK = 5
W = H = 64
Z = 4


@pytest.fixture(scope="module")
def data_dir(tmp_path_factory):
    root = tmp_path_factory.mktemp("data")
    rng = np.random.default_rng(3)
    planes = rng.integers(0, 60000, size=(2, Z, H, W)).astype(np.uint16)
    build_pyramid(planes, str(root / str(IMG)), chunk=(32, 32), n_levels=2)
    bits = np.zeros(H * W, np.uint8)
    bits[: H * W // 2] = 1
    write_mask(str(root), Mask(
        shape_id=MASK, width=W, height=H,
        bytes_=np.packbits(bits).tobytes(), fill_color=None))
    return str(root)


@pytest.fixture()
def services(data_dir):
    return ImageRegionServices(
        pixels_service=PixelsService(data_dir),
        metadata=LocalMetadataService(data_dir),
        caches=Caches.from_config(CacheConfig.enabled_all()),
        can_read_memo=CanReadMemo(),
        renderer=Renderer(),
        lut_provider=LutProvider(),
        # Tests use small tiles; disable the tiny-render CPU fallback so
        # the device kernel path stays exercised (the fallback has its own
        # dedicated test).
        cpu_fallback_max_px=0,
    )


def run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()


def _ctx(**params):
    base = {"imageId": str(IMG), "theZ": "0", "theT": "0"}
    base.update(params)
    return ImageRegionCtx.from_params(base)


class TestImageRegionHandler:
    def test_full_plane_png(self, services):
        handler = ImageRegionHandler(services)
        data = run(handler.render_image_region(_ctx(format="png")))
        rgba = codecs.decode_to_rgba(data)
        assert rgba.shape == (H, W, 4)

    def test_tile_and_region_shapes(self, services):
        handler = ImageRegionHandler(services)
        tile = run(handler.render_image_region(
            _ctx(tile="0,1,1,16,16", format="png")))
        assert codecs.decode_to_rgba(tile).shape == (16, 16, 4)
        region = run(handler.render_image_region(
            _ctx(region="8,8,24,20", format="png")))
        assert codecs.decode_to_rgba(region).shape == (20, 24, 4)

    def test_jpeg_device_path_matches_png_render(self, services):
        """format=jpeg routes through the fused device JPEG front end; the
        decoded image must match the (lossless) PNG path within JPEG
        tolerance."""
        handler = ImageRegionHandler(services)
        png = codecs.decode_to_rgba(
            run(handler.render_image_region(_ctx(format="png"))))
        jpg_bytes = run(handler.render_image_region(_ctx(format="jpeg")))
        assert jpg_bytes[:2] == b"\xff\xd8"
        jpg = codecs.decode_to_rgba(jpg_bytes)
        assert jpg.shape == (H, W, 4)
        err = np.abs(jpg[..., :3].astype(float) - png[..., :3].astype(float))
        assert err.mean() < 8.0

    def test_jpeg_odd_size_region_and_flip(self, services):
        """Non-MCU-aligned regions pad on device and crop via SOF0 dims;
        flips fold into the raw planes."""
        handler = ImageRegionHandler(services)
        jpg = codecs.decode_to_rgba(run(handler.render_image_region(
            _ctx(region="3,5,30,18", format="jpeg"))))
        assert jpg.shape == (18, 30, 4)

        plain = codecs.decode_to_rgba(run(handler.render_image_region(
            _ctx(format="jpeg"))))
        flipped = codecs.decode_to_rgba(run(handler.render_image_region(
            _ctx(format="jpeg", flip="h"))))
        err = np.abs(flipped[:, ::-1, :3].astype(float)
                     - plain[..., :3].astype(float))
        assert err.mean() < 6.0  # JPEG noise only; geometry must mirror

    def test_jpeg_with_lut_channel_uses_gather_tables(self, services):
        """A channel bound to a LUT forces the [C,256,3] gather-table path
        through the device JPEG pipeline."""
        table = np.zeros((256, 3), np.uint8)
        table[:, 1] = np.arange(256)          # green ramp LUT
        services.lut_provider.add("green.lut", table)
        handler = ImageRegionHandler(services)
        jpg = codecs.decode_to_rgba(run(handler.render_image_region(_ctx(
            c="1|0:60000$green.lut,-2", m="c", format="jpeg"))))
        assert jpg.shape == (H, W, 4)
        # Green must dominate: red/blue only via JPEG chroma noise.
        assert jpg[..., 1].astype(int).sum() > 5 * jpg[..., 0].astype(
            int).sum()

    def test_cpu_fallback_for_tiny_renders(self, services):
        """Renders at or below cpu_fallback_max_px take the refimpl path
        and must match the device path within codec tolerance."""
        from dataclasses import replace
        fast = replace(services, cpu_fallback_max_px=16 * 16,
                       caches=Caches.from_config(CacheConfig.enabled_all()))
        handler_cpu = ImageRegionHandler(fast)
        handler_dev = ImageRegionHandler(services)
        from omero_ms_image_region_tpu.utils.stopwatch import REGISTRY
        before = REGISTRY.snapshot().get(
            "Renderer.renderAsPackedInt.cpu", {}).get("count", 0)
        ctx = {"tile": "0,0,0,16,16", "m": "c", "format": "png"}
        cpu = codecs.decode_to_rgba(
            run(handler_cpu.render_image_region(_ctx(**ctx))))
        dev = codecs.decode_to_rgba(
            run(handler_dev.render_image_region(_ctx(**ctx))))
        # The CPU path must actually have run (not a vacuous device==device
        # comparison).
        assert REGISTRY.snapshot()["Renderer.renderAsPackedInt.cpu"][
            "count"] == before + 1
        assert cpu.shape == dev.shape == (16, 16, 4)
        assert np.abs(cpu.astype(int) - dev.astype(int)).max() <= 2

    def test_second_request_hits_cache(self, services):
        handler = ImageRegionHandler(services)
        ctx = _ctx(format="png", tile="0,0,0,16,16")
        first = run(handler.render_image_region(ctx))
        tier = services.caches.image_region.tiers[0]
        hits_before = getattr(tier, "hits", None)
        second = run(handler.render_image_region(ctx))
        assert first == second
        if hits_before is not None:
            assert tier.hits > hits_before

    def test_cache_hit_still_requires_acl(self, services, data_dir):
        import os
        handler = ImageRegionHandler(services)
        ctx = _ctx(format="png")
        run(handler.render_image_region(ctx))          # populate cache
        acl = os.path.join(data_dir, str(IMG), "acl.json")
        with open(acl, "w") as f:
            json.dump({"sessions": ["allowed"]}, f)
        try:
            services.can_read_memo._memo.clear()
            with pytest.raises(NotFoundError):
                run(handler.render_image_region(ctx))
        finally:
            os.remove(acl)

    def test_missing_image_404(self, services):
        handler = ImageRegionHandler(services)
        with pytest.raises(NotFoundError):
            run(handler.render_image_region(_ctx(imageId="999")))

    def test_z_out_of_bounds_400(self, services):
        handler = ImageRegionHandler(services)
        with pytest.raises(BadRequestError):
            run(handler.render_image_region(_ctx(theZ=str(Z))))

    def test_flip_matches_unflipped_mirror(self, services):
        handler = ImageRegionHandler(services)
        plain = codecs.decode_to_rgba(run(handler.render_image_region(
            _ctx(format="png"))))
        flipped = codecs.decode_to_rgba(run(handler.render_image_region(
            _ctx(format="png", flip="h"))))
        np.testing.assert_array_equal(flipped, plain[:, ::-1])

    def test_projection_intmax(self, services, data_dir):
        handler = ImageRegionHandler(services)
        data = run(handler.render_image_region(
            _ctx(format="png", p="intmax|0:3",
                 c="1|0:60000$FF0000,-2|0:60000$00FF00")))
        rgba = codecs.decode_to_rgba(data)
        assert rgba.shape == (H, W, 4)
        # Projection of the max over Z must be >= any single plane render.
        single = codecs.decode_to_rgba(run(handler.render_image_region(
            _ctx(format="png", c="1|0:60000$FF0000,-2|0:60000$00FF00"))))
        assert (rgba[..., 0].astype(int) >= single[..., 0].astype(int)).all()

    def test_projection_intmax_jpeg_device_resident(self, services):
        """Projection feeds the device JPEG path without a host hop:
        the projected planes stay jax-resident into the fused dispatch."""
        handler = ImageRegionHandler(services)
        data = run(handler.render_image_region(
            _ctx(format="jpeg", p="intmax|0:3",
                 c="1|0:60000$FF0000,-2|0:60000$00FF00")))
        assert data[:2] == b"\xff\xd8"
        rgba = codecs.decode_to_rgba(data)
        assert rgba.shape == (H, W, 4)

    def test_greyscale_model(self, services):
        handler = ImageRegionHandler(services)
        data = run(handler.render_image_region(
            _ctx(format="png", m="g",
                 c="1|0:60000$FF0000,2|0:60000$00FF00")))
        rgba = codecs.decode_to_rgba(data)
        # grey: r == g == b everywhere
        np.testing.assert_array_equal(rgba[..., 0], rgba[..., 1])
        np.testing.assert_array_equal(rgba[..., 1], rgba[..., 2])

    def test_resolution_level(self, services):
        """Resolution indexes the largest-first level list directly, as the
        reference's testSelectResolution pins (largest at index 0)."""
        handler = ImageRegionHandler(services)
        # res 0, 32x32 tile at origin == the full-res top-left quadrant ==
        # the same region requested without any resolution at all.
        quad_res0 = run(handler.render_image_region(
            _ctx(format="png", tile="0,0,0,32,32")))
        quad_plain = run(handler.render_image_region(
            _ctx(format="png", region="0,0,32,32")))
        np.testing.assert_array_equal(
            codecs.decode_to_rgba(quad_res0), codecs.decode_to_rgba(quad_plain))
        # res 1 == the downsampled 32x32 level: same shape, different pixels.
        small = run(handler.render_image_region(
            _ctx(format="png", tile="1,0,0,32,32")))
        small_rgba = codecs.decode_to_rgba(small)
        assert small_rgba.shape == (H // 2, W // 2, 4)
        assert not np.array_equal(small_rgba,
                                  codecs.decode_to_rgba(quad_res0))


class TestShapeMaskHandler:
    def test_mask_png_and_cache_rules(self, services):
        handler = ShapeMaskHandler(services)
        ctx = ShapeMaskCtx.from_params({"shapeId": str(MASK)})
        png = run(handler.render_shape_mask(ctx))
        rgba = codecs.decode_to_rgba(png)
        assert rgba.shape == (H, W, 4)
        # top half filled with default yellow, bottom transparent
        assert tuple(rgba[0, 0]) == (255, 255, 0, 255)
        assert rgba[H - 1, 0, 3] == 0
        # no color param => not cached
        assert run(services.caches.shape_mask.get(ctx.cache_key())) is None

        colored = ShapeMaskCtx.from_params(
            {"shapeId": str(MASK), "color": "FF0000"})
        png2 = run(handler.render_shape_mask(colored))
        assert run(services.caches.shape_mask.get(
            colored.cache_key())) == png2

    def test_missing_mask_404(self, services):
        handler = ShapeMaskHandler(services)
        with pytest.raises(NotFoundError):
            run(handler.render_shape_mask(
                ShapeMaskCtx.from_params({"shapeId": "999"})))


def test_banded_cold_staging_matches_single_shot(tmp_path):
    """Large-region loads band rows into overlapped device_puts; the
    assembled device array is identical to the one-shot host read."""
    import asyncio

    import jax.numpy as jnp

    from omero_ms_image_region_tpu.io.devicecache import DeviceRawCache
    from omero_ms_image_region_tpu.io.store import build_pyramid
    from omero_ms_image_region_tpu.server.region import RegionDef

    rng = np.random.default_rng(6)
    planes = rng.integers(0, 60000, size=(2, 1, 1024, 768)).astype(
        np.uint16)
    src = build_pyramid(planes, str(tmp_path / "img"), chunk=(128, 128),
                        n_levels=1)
    services = ImageRegionServices(
        pixels_service=PixelsService(str(tmp_path)),
        metadata=LocalMetadataService(str(tmp_path)),
        caches=Caches.from_config(CacheConfig.enabled_all()),
        can_read_memo=CanReadMemo(),
        renderer=Renderer(),
        lut_provider=LutProvider(),
        cpu_fallback_max_px=0,
        raw_cache=DeviceRawCache(),
    )
    handler = ImageRegionHandler(services)
    ctx = ImageRegionCtx.from_params({
        "imageId": "1", "theZ": "0", "theT": "0", "m": "c",
        "c": "1|0:60000$FF0000,2|0:60000$00FF00"})
    region = RegionDef(32, 16, 700, 1000)     # >= 2 bands of 256 rows
    staged = handler._read_region(src, ctx, region, 0, [0, 1])
    direct = np.stack([
        src.get_region(0, c, 0, region, 0) for c in (0, 1)])
    assert staged.dtype == jnp.uint16        # storage dtype preserved
    np.testing.assert_array_equal(np.asarray(staged), direct)
    # A hit stacks the two resident channel planes again without
    # re-reading either.
    cache = services.raw_cache
    assert (cache.channel_loads, len(cache)) == (2, 2)
    again = handler._read_region(src, ctx, region, 0, [0, 1])
    np.testing.assert_array_equal(np.asarray(again), direct)
    assert (cache.channel_loads, cache.hits) == (2, 2)


# ----------------------------------------------- the source open (PR 36)

def test_a_plate_larger_than_the_lru_opens_a_source_a_request(tmp_path):
    """Three images walked cyclically over ``PixelsService(max_open=2)``:
    every lookup misses and opens, the gauge never passes 2,
    ``PixelsService.openSource`` fires on the misses only and
    ``PixelsService.getPixelBuffer`` on every request."""
    from omero_ms_image_region_tpu.utils import telemetry
    from omero_ms_image_region_tpu.utils.stopwatch import REGISTRY

    rng = np.random.default_rng(36)
    for image in (1, 2, 3):
        build_pyramid(
            rng.integers(0, 60000, size=(1, 1, 32, 32)).astype(np.uint16),
            str(tmp_path / str(image)), chunk=(32, 32), n_levels=1)
    svc = PixelsService(str(tmp_path), max_open=2)
    services = ImageRegionServices(
        pixels_service=svc, metadata=LocalMetadataService(str(tmp_path)),
        caches=Caches.from_config(CacheConfig()),
        can_read_memo=CanReadMemo(), renderer=Renderer(),
        lut_provider=LutProvider(), cpu_fallback_max_px=0)
    handler = ImageRegionHandler(services)

    def count(span):
        return REGISTRY.snapshot().get(span, {}).get("count", 0)

    def lines():
        return telemetry.device_metric_lines(services)

    REGISTRY.reset()
    walk = [1, 2, 3] * 3
    for n, image in enumerate(walk, 1):
        ctx = ImageRegionCtx.from_params(
            {"imageId": str(image), "theZ": "0", "theT": "0",
             "format": "png", "c": f"1|0:{1000 * n}$FF0000"})
        assert run(handler.render_image_region(ctx))[:4] == b"\x89PNG"
        assert svc.opened == n and svc.open_count() <= 2
        assert f"imageregion_pixel_sources_opened_total {n}" in lines()
    assert count("PixelsService.openSource") == len(walk)
    assert count("PixelsService.getPixelBuffer") == len(walk)
    assert "imageregion_pixel_sources_open 2" in lines()
    assert count("PixelsService.gcDrain") == 0
    # A hit: the request's span fires, the open's does not.
    for _ in range(2):
        ctx = ImageRegionCtx.from_params(
            {"imageId": "3", "theZ": "0", "theT": "0", "format": "png"})
        run(handler.render_image_region(ctx))
    assert svc.opened == len(walk)
    assert count("PixelsService.openSource") == len(walk)
    assert count("PixelsService.getPixelBuffer") == len(walk) + 2
    # The forced collection has a span of its own, and so a count.
    svc._gc_and_drain()
    assert count("PixelsService.gcDrain") == 1
    svc.close()


def _stopwatches_around_an_await():
    """``(line, span name)`` of every ``with stopwatch(...)`` in
    ``server/handler.py`` whose body awaits: a profiler annotation
    there stays open on the event loop's thread while other requests
    run on it."""
    import ast
    import inspect

    from omero_ms_image_region_tpu.server import handler
    tree = ast.parse(inspect.getsource(handler))
    found, seen = [], []
    for node in ast.walk(tree):
        if not isinstance(node, (ast.With, ast.AsyncWith)):
            continue
        for item in node.items:
            call = item.context_expr
            if (isinstance(call, ast.Call)
                    and getattr(call.func, "id", "") == "stopwatch"):
                name = call.args[0].value
                seen.append(name)
                if any(isinstance(n, (ast.Await, ast.AsyncFor,
                                      ast.AsyncWith))
                       for body in node.body for n in ast.walk(body)):
                    found.append((node.lineno, name))
    return found, seen


@pytest.mark.parametrize("span", [
    "canRead", "get_pixels_description", "PixelsService.getPixelBuffer",
    "Renderer.renderAsPackedInt", "getMask", "renderShapeMask",
    "renderOverlay"])
def test_no_stopwatch_of_the_handler_encloses_an_await(span):
    """The seven spans that did (PR 36) are recorded from two stamps,
    under the names they had; no other has joined them."""
    found, seen = _stopwatches_around_an_await()
    assert found == []
    assert span not in seen
    assert "PixelsService.readRegion" in seen     # the walk sees spans


@pytest.mark.parametrize("span, where, fmt", [
    ("canRead", ("metadata", "can_read"), "png"),
    ("get_pixels_description",
     ("metadata", "get_pixels_description"), "png"),
    ("PixelsService.getPixelBuffer",
     ("pixels_service", "get_pixel_source"), "png"),
    ("Renderer.renderAsPackedInt", ("renderer", "render"), "png"),
    ("Renderer.renderAsPackedInt", ("renderer", "render_jpeg"), "jpeg")])
def test_a_span_over_an_await_counts_the_request_that_failed(
        services, monkeypatch, span, where, fmt):
    """As under ``stopwatch``'s ``finally``: the requests that end in a
    deadline, a shed or an error, usually the slowest, stay in the
    series' count, p99 and max."""
    from omero_ms_image_region_tpu.utils.stopwatch import REGISTRY

    class Stalled(RuntimeError):
        pass

    def fail(*args, **kw):
        raise Stalled(span)

    monkeypatch.setattr(getattr(services, where[0]), where[1], fail)
    REGISTRY.reset()
    with pytest.raises(Stalled):
        run(ImageRegionHandler(services).render_image_region(
            _ctx(format=fmt)))
    assert REGISTRY.snapshot()[span]["count"] == 1
