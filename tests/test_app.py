"""End-to-end HTTP tests: routes, status mapping, headers, OPTIONS doc."""

import asyncio
import json

import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from omero_ms_image_region_tpu import codecs
from omero_ms_image_region_tpu.io.store import build_pyramid
from omero_ms_image_region_tpu.models.mask import Mask
from omero_ms_image_region_tpu.server.app import create_app
from omero_ms_image_region_tpu.server.config import (AppConfig,
                                                     BatcherConfig,
                                                     RendererConfig)
from omero_ms_image_region_tpu.services.metadata import write_mask

IMG, MASK = 7, 5
H = W = 64


@pytest.fixture(scope="module")
def data_dir(tmp_path_factory):
    root = tmp_path_factory.mktemp("appdata")
    rng = np.random.default_rng(11)
    planes = rng.integers(0, 60000, size=(2, 2, H, W)).astype(np.uint16)
    build_pyramid(planes, str(root / str(IMG)), chunk=(32, 32), n_levels=1)
    grid = np.zeros(H * W, np.uint8)
    grid[:256] = 1
    write_mask(str(root), Mask(shape_id=MASK, width=W, height=H,
                               bytes_=np.packbits(grid).tobytes()))
    return str(root)


def client_fetch(data_dir, *requests, config=None, cookies=None):
    """Run GET/OPTIONS requests against a fresh app; returns
    [(status, headers, body)]."""
    config = config or AppConfig(
        data_dir=data_dir, cache_control_header="private, max-age=3600")
    config.data_dir = data_dir

    async def main():
        app = create_app(config)
        client = TestClient(TestServer(app), cookies=cookies)
        await client.start_server()
        out = []
        try:
            for method, path in requests:
                resp = await client.request(method, path)
                out.append((resp.status, dict(resp.headers),
                            await resp.read()))
        finally:
            await client.close()
        return out

    return asyncio.run(main())


class TestRoutes:
    def test_render_image_region_jpeg(self, data_dir):
        [(status, headers, body)] = client_fetch(
            data_dir,
            ("GET", f"/webgateway/render_image_region/{IMG}/0/0"
                    "?c=1|0:60000$FF0000&m=c"))
        assert status == 200
        assert headers["Content-Type"] == "image/jpeg"
        assert headers["Cache-Control"] == "private, max-age=3600"
        assert body[:2] == b"\xff\xd8"

    def test_all_four_image_routes(self, data_dir):
        reqs = [("GET", f"/{p}/{r}/{IMG}/0/0?format=png&m=c")
                for p in ("webgateway", "webclient")
                for r in ("render_image_region", "render_image")]
        for status, headers, body in client_fetch(data_dir, *reqs):
            assert status == 200
            assert headers["Content-Type"] == "image/png"
            assert codecs.decode_to_rgba(body).shape == (H, W, 4)

    def test_tile_param_png(self, data_dir):
        [(status, _, body)] = client_fetch(
            data_dir,
            ("GET", f"/webgateway/render_image_region/{IMG}/0/0"
                    "?tile=0,0,0,16,16&format=png&m=c"))
        assert status == 200
        assert codecs.decode_to_rgba(body).shape == (16, 16, 4)

    def test_shape_mask_route(self, data_dir):
        [(status, headers, body)] = client_fetch(
            data_dir,
            ("GET", f"/webgateway/render_shape_mask/{MASK}?color=FF0000"))
        assert status == 200
        assert headers["Content-Type"] == "image/png"
        rgba = codecs.decode_to_rgba(body)
        assert tuple(rgba[0, 0]) == (255, 0, 0, 255)

    def test_options_feature_document(self, data_dir):
        [(status, headers, body)] = client_fetch(
            data_dir, ("OPTIONS", "/"))
        assert status == 200
        doc = json.loads(body)
        assert doc["provider"] == "ImageRegionMicroservice"
        assert set(doc["features"]) == {"flip", "mask-color", "png-tiles"}
        assert doc["options"]["maxTileLength"] == 2048
        assert doc["options"]["cacheControl"] == "private, max-age=3600"


class TestMetrics:
    def test_metrics_endpoint_exposes_spans_and_caches(self, data_dir):
        [(s1, _, _), (status, _, body)] = client_fetch(
            data_dir,
            ("GET", f"/webgateway/render_image_region/{IMG}/0/0"
                    "?format=png&m=c"),
            ("GET", "/metrics"),
        )
        assert s1 == 200 and status == 200
        text = body.decode()
        # The 64x64 render takes the default tiny-tile CPU fallback, whose
        # span keeps the reference's name with a .cpu suffix.
        assert ('imageregion_span_count{span="Renderer.renderAsPackedInt'
                in text)
        assert "imageregion_cache_hits" in text


class TestConcurrencyTorture:
    def test_many_mixed_concurrent_requests(self, data_dir):
        """48 concurrent requests across formats, sizes, windows, flips
        and masks — every one must complete correctly."""
        paths = []
        for i in range(16):
            w, h = 8 + (i % 2) * 8, 8 + (i % 3) * 4   # stay inside 64x64
            fmt = ("jpeg", "png")[i % 2]
            flip = ("", "&flip=h", "&flip=v", "&flip=hv")[i % 4]
            paths.append(
                f"/webgateway/render_image_region/{IMG}/0/0"
                f"?tile=0,{i % 3},{i % 2},{w},{h}&format={fmt}&m=c"
                f"&c=1|0:{10000 + i * 2500}$FF0000,2|0:60000$00FF00{flip}")
        paths = paths * 3
        bodies, types, renderer = _gather_requests(data_dir, paths)
        assert len(bodies) == 48
        for p, t, b in zip(paths, types, bodies):
            fmt = "jpeg" if "format=jpeg" in p else "png"
            assert t == f"image/{fmt}"
            assert codecs.decode_to_rgba(b).ndim == 3
        assert renderer.tiles_rendered >= 16  # caches absorb repeats


class TestStatusMapping:
    def test_bad_param_400_with_message(self, data_dir):
        [(status, _, body)] = client_fetch(
            data_dir,
            ("GET", f"/webgateway/render_image_region/{IMG}/0/0"
                    "?tile=bogus"))
        assert status == 400
        assert b"tile" in body

    def test_missing_image_404(self, data_dir):
        [(status, _, body)] = client_fetch(
            data_dir, ("GET", "/webgateway/render_image_region/999/0/0"))
        assert status == 404
        assert body == b""

    def test_z_out_of_bounds_400(self, data_dir):
        [(status, _, _)] = client_fetch(
            data_dir, ("GET", f"/webgateway/render_image_region/{IMG}/9/0"))
        assert status == 400

    def test_missing_mask_404(self, data_dir):
        [(status, _, _)] = client_fetch(
            data_dir, ("GET", "/webgateway/render_shape_mask/999"))
        assert status == 404

    def test_resolution_out_of_range_400(self, data_dir):
        for res in (-1, 9):
            [(status, _, _)] = client_fetch(
                data_dir,
                ("GET", f"/webgateway/render_image_region/{IMG}/0/0"
                        f"?tile={res},0,0"))
            assert status == 400

    def test_non_numeric_image_id_400(self, data_dir):
        [(status, _, _)] = client_fetch(
            data_dir, ("GET", "/webgateway/render_image_region/abc/0/0"))
        assert status == 400


class TestSessionEnforcement:
    """≙ the reference's mandatory OmeroWebSessionRequestHandler
    (ImageRegionMicroserviceVerticle.java:199-212)."""

    def _fetch(self, data_dir, path, required, cookies=None):
        config = AppConfig(data_dir=data_dir,
                           session_store_type="static",
                           session_store_required=required)
        [(status, _, body)] = client_fetch(
            data_dir, ("GET", path), config=config, cookies=cookies)
        return status, body

    def test_no_cookie_rejected_403(self, data_dir):
        status, body = self._fetch(
            data_dir,
            f"/webgateway/render_image_region/{IMG}/0/0?format=png&m=c",
            required=True)
        assert (status, body) == (403, b"")
        status, _ = self._fetch(
            data_dir, f"/webgateway/render_shape_mask/{MASK}",
            required=True)
        assert status == 403

    def test_cookie_resolves_and_serves(self, data_dir):
        status, body = self._fetch(
            data_dir,
            f"/webgateway/render_image_region/{IMG}/0/0?format=png&m=c",
            required=True, cookies={"sessionid": "k1"})
        assert status == 200 and body[:4] == b"\x89PNG"

    def test_static_store_defaults_to_opt_out(self, data_dir):
        # required=None: static stores keep the anonymous posture.
        status, _ = self._fetch(
            data_dir,
            f"/webgateway/render_image_region/{IMG}/0/0?format=png&m=c",
            required=None)
        assert status == 200

    def test_required_without_store_refuses_to_start(self, data_dir):
        config = AppConfig(data_dir=data_dir,
                           session_store_required=True)
        with pytest.raises(ValueError, match="session"):
            create_app(config)

    def test_redis_store_defaults_to_required(self):
        from omero_ms_image_region_tpu.server.config import AppConfig
        cfg = AppConfig.from_dict(
            {"session-store": {"type": "redis"}})
        from omero_ms_image_region_tpu.server.app import _session_required
        assert _session_required(cfg) is True
        cfg = AppConfig.from_dict(
            {"session-store": {"type": "redis", "required": False}})
        assert _session_required(cfg) is False


class TestTrailingWildcardRoutes:
    """Reference routes end in `*` (…Verticle.java:214-231): URLs with
    trailing segments past the last parameter must still resolve."""

    def test_image_route_with_trailing_segment(self, data_dir):
        [(status, headers, body)] = client_fetch(
            data_dir,
            ("GET", f"/webgateway/render_image_region/{IMG}/0/0/extra"
                    "?format=png&m=c"))
        assert status == 200
        assert codecs.decode_to_rgba(body).shape == (H, W, 4)

    def test_mask_route_with_trailing_segment(self, data_dir):
        [(status, _, body)] = client_fetch(
            data_dir,
            ("GET", f"/webgateway/render_shape_mask/{MASK}/trailing/x"))
        assert status == 200
        assert body[:4] == b"\x89PNG"

    def test_tail_does_not_dilute_cache_key(self, data_dir):
        """/7/0/0 and /7/0/0/ must hash to the same region cache key."""
        from omero_ms_image_region_tpu.server.ctx import ImageRegionCtx

        base = {"imageId": str(IMG), "theZ": "0", "theT": "0",
                "format": "png", "m": "c"}
        k1 = ImageRegionCtx.create_cache_key(base)
        k2 = ImageRegionCtx.create_cache_key({**base, "tail": ""})
        assert k1 != k2  # raw params WOULD dilute...
        # ...which is why the app strips `tail` before from_params:
        [(s1, _, b1), (s2, _, b2)] = client_fetch(
            data_dir,
            ("GET", f"/webgateway/render_image_region/{IMG}/0/0"
                    "?format=png&m=c"),
            ("GET", f"/webgateway/render_image_region/{IMG}/0/0/"
                    "?format=png&m=c"))
        assert s1 == s2 == 200 and b1 == b2


def _gather_requests(data_dir, paths, jpeg_engine="sparse"):
    """Boot the batched app, issue ``paths`` concurrently, return
    (bodies, content_types, renderer)."""
    config = AppConfig(
        data_dir=data_dir,
        batcher=BatcherConfig(enabled=True, linger_ms=5.0),
        # These tests use tiny tiles but exist to exercise the batched
        # device path; keep the tiny-render CPU fallback out of the way.
        renderer=RendererConfig(cpu_fallback_max_px=0,
                                jpeg_engine=jpeg_engine))

    async def main():
        app = create_app(config)
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            resps = await asyncio.gather(*(client.get(p) for p in paths))
            bodies = [await r.read() for r in resps]
            assert all(r.status == 200 for r in resps)
            types = [r.headers["Content-Type"] for r in resps]
            from omero_ms_image_region_tpu.server.app import SERVICES_KEY
            return bodies, types, app[SERVICES_KEY].renderer
        finally:
            await client.close()

    return asyncio.run(main())


class TestBatchedApp:
    def test_batching_renderer_serves_requests(self, data_dir):
        bodies, _, renderer = _gather_requests(data_dir, [
            f"/webgateway/render_image_region/{IMG}/0/0"
            f"?tile=0,0,0,16,16&format=png&m=c&"
            f"c=1|0:{(i + 1) * 10000}$FF0000"
            for i in range(6)
        ])
        # different windows -> different images, all decoded fine
        shapes = {codecs.decode_to_rgba(b).shape for b in bodies}
        assert shapes == {(16, 16, 4)}
        assert renderer.tiles_rendered == 6
        assert renderer.batches_dispatched <= 6

    def test_concurrent_jpeg_requests_through_batcher(self, data_dir):
        """Concurrent mixed-size JPEG requests coalesce through the device
        JPEG groups (all bucket to one MCU grid) and every response
        decodes at its own size."""
        sizes = [(16, 16), (20, 12), (32, 32), (8, 24)]
        bodies, types, renderer = _gather_requests(data_dir, [
            f"/webgateway/render_image_region/{IMG}/0/0"
            f"?tile=0,0,0,{w},{h}&format=jpeg&m=c&"
            f"c=1|0:60000$FF0000,2|0:60000$00FF00"
            for w, h in sizes
        ])
        assert all(t == "image/jpeg" for t in types)
        for (w, h), body in zip(sizes, bodies):
            assert codecs.decode_to_rgba(body).shape == (h, w, 4)
        # Same spatial bucket -> the device JPEG groups actually coalesce.
        assert renderer.batches_dispatched < len(sizes)

    @pytest.mark.parametrize("fmt", ["jpeg", "png"])
    def test_a_requests_life_is_four_phases(self, data_dir, fmt):
        """PR 36: accept -> last line in ``handler.prepare``,
        ``batcher.queueWait``, ``batcher.inGroup``, ``handler.respond``,
        one after the other on the request's trace, with nothing
        between them."""
        from omero_ms_image_region_tpu.utils import telemetry
        from omero_ms_image_region_tpu.utils.stopwatch import REGISTRY
        telemetry.reset()
        path = (f"/webgateway/render_image_region/{IMG}/0/0"
                f"?tile=0,0,0,32,32&format={fmt}&m=c&c=1|0:%d$FF0000")
        # The first request compiles; the second is the one read.
        _gather_requests(data_dir, [path % 20000])
        telemetry.reset()
        REGISTRY.reset()
        _gather_requests(data_dir, [path % 30000])
        [trace] = telemetry.TRACES.recent
        [cost] = telemetry.COST_TOPK.snapshot()
        assert cost["trace"] == trace.trace_id
        phases = ["handler.prepare", "batcher.queueWait",
                  "batcher.inGroup", "handler.respond"]
        spans = {s["name"]: s for s in trace.spans}
        assert [n for n in phases if n in spans] == phases
        for name in phases:
            assert [s["name"] for s in trace.spans].count(name) == 1
        seams = []
        for before, after in zip(phases, phases[1:]):
            end = spans[before]["start_ms"] + spans[before]["dur_ms"]
            # No overlap (the stamps are rounded to a microsecond).
            assert spans[after]["start_ms"] >= end - 0.002, after
            seams.append(spans[after]["start_ms"] - end)
        # One stamp ends a phase and begins the next, but at the
        # hand-off, where the batcher pads a request that does not fill
        # its bucket (this 32 x 32 tile; no cell's) before it enqueues.
        assert seams[1] <= 0.002 and seams[2] <= 0.002
        total = sum(spans[n]["dur_ms"] for n in phases) + seams[0]
        assert total == pytest.approx(cost["total_ms"],
                                      abs=max(1.0, 0.05 * total))
        # Inside prepare: the metadata, then the source.
        assert spans["handler.metadata"]["dur_ms"] <= \
            spans["handler.prepare"]["dur_ms"]
        assert "PixelsService.getPixelBuffer" in spans
        assert spans["batcher.inGroup"]["tiles"] == 1
        # The accounting is a span of the process, not of the request.
        assert REGISTRY.snapshot()["http.account"]["count"] == 1
        assert "http.account" not in spans

    def test_huffman_engine_through_batcher(self, data_dir):
        """renderer.jpeg-engine='huffman' serves batched JPEG groups via
        the device fixed-table Huffman wire (exact tiles) and the dense
        path (bucket-padded ones)."""
        sizes = [(16, 16), (20, 12)]
        bodies, types, renderer = _gather_requests(data_dir, [
            f"/webgateway/render_image_region/{IMG}/0/0"
            f"?tile=0,0,0,{w},{h}&format=jpeg&m=c&"
            f"c=1|0:60000$FF0000,2|0:60000$00FF00"
            for w, h in sizes
        ], jpeg_engine="huffman")
        assert renderer.jpeg_engine == "huffman"
        assert all(t == "image/jpeg" for t in types)
        for (w, h), body in zip(sizes, bodies):
            assert codecs.decode_to_rgba(body).shape == (h, w, 4)


class TestPrewarm:
    def test_app_boots_with_prewarm_and_serves(self, data_dir):
        """renderer.prewarm compiles at build_services time; the app
        then serves the warmed shape through the batched device path
        (cpu-fallback disabled so 64x64 doesn't route to the host
        kernel — prewarm skips shapes the fallback would serve)."""
        config = AppConfig(data_dir=data_dir)
        config.renderer.prewarm = ("1x64",)
        config.renderer.cpu_fallback_max_px = 0
        (r,) = client_fetch(data_dir, (
            "GET",
            f"/webgateway/render_image_region/{IMG}/0/0"
            "?tile=0,0,0,64,64&format=jpeg&m=c&c=1|0:60000$FF0000",
        ), config=config)
        status, headers, body = r
        assert status == 200
        assert body[:2] == b"\xff\xd8"


    @pytest.mark.parametrize("engine", ["sparse", "huffman"])
    def test_prewarm_warms_the_one_configured_engine(
            self, data_dir, engine, monkeypatch):
        """build_services hands prewarm ``renderer.jpeg-engine`` as it
        was configured: every warmed program is that engine's, and the
        renderer serves it."""
        import time

        from omero_ms_image_region_tpu.ops import jpegenc
        from omero_ms_image_region_tpu.server.app import build_services
        from omero_ms_image_region_tpu.utils import telemetry

        warmed = []
        real = jpegenc.render_batch_to_jpeg

        def spy(*args, **kw):
            warmed.append((kw["engine"], args[0].shape))
            return real(*args, **kw)

        monkeypatch.setattr(jpegenc, "render_batch_to_jpeg", spy)
        config = AppConfig(data_dir=data_dir)
        # One 1024^2 bucket at max-batch 1: one batch shape to compile.
        config.renderer.prewarm = ("1x1024",)
        config.renderer.jpeg_engine = engine
        config.batcher.max_batch = 1
        services = build_services(config)
        try:
            t_end = time.monotonic() + 300
            while (telemetry.READINESS.prewarm_pending
                   and time.monotonic() < t_end):
                time.sleep(0.05)
            assert not telemetry.READINESS.prewarm_pending
            assert services.renderer.jpeg_engine == engine
            assert warmed == [(engine, (1, 1, 1024, 1024))]
        finally:
            asyncio.run(services.renderer.close())


class TestUncachedPosturesMatch:
    def test_raw_cache_off_serves_identical_bytes(self, data_dir):
        """raw-cache disabled must serve byte-identical output to the
        default posture: both stage STORAGE dtype (the uncached branch
        stopped casting to float32 — it halves that posture's upload
        bytes) and run the same device programs."""
        from omero_ms_image_region_tpu.server.config import (
            RawCacheConfig,
        )

        # Two windows over the same tile: in the cached posture the
        # second render replays the DEVICE-resident raw (distinct byte-
        # cache keys force a re-render); cpu-fallback is disabled so
        # both postures exercise the batched device path this change
        # touches (uint16 staging end to end).
        paths = [(f"/webgateway/render_image_region/{IMG}/0/0"
                  f"?tile=0,0,0,64,64&format=png&m=c"
                  f"&c=1|{lo}:60000$FF0000,2|0:50000$00FF00")
                 for lo in (1000, 2000)]
        reqs = [("GET", p) for p in paths]
        cfg_on = AppConfig(data_dir=data_dir)
        cfg_on.renderer.cpu_fallback_max_px = 0
        cfg_off = AppConfig(data_dir=data_dir,
                            raw_cache=RawCacheConfig(enabled=False))
        cfg_off.renderer.cpu_fallback_max_px = 0
        on = client_fetch(data_dir, *reqs, config=cfg_on)
        off = client_fetch(data_dir, *reqs, config=cfg_off)
        for a, b in zip(on, off):
            assert a[0] == 200 and b[0] == 200
            assert a[2] == b[2]
        assert on[0][2] != on[1][2]   # the two windows truly differ
