"""Mesh-sharded serving: MeshRenderer parity + HTTP integration.

Runs on the 8-device virtual host mesh (``resolve_devices`` falls back to
it when the default platform is narrower), exactly as the driver's
multi-chip dryrun does.
"""

import asyncio

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from omero_ms_image_region_tpu.parallel.mesh import make_mesh, resolve_devices


def _mesh(chan_parallel=2):
    if len(resolve_devices(8)) < 8:
        pytest.skip("no 8-wide device pool (real or virtual) available")
    return make_mesh(8, chan_parallel=chan_parallel)


def _settings(C, windows):
    from omero_ms_image_region_tpu.flagship import flagship_rdef
    from omero_ms_image_region_tpu.ops.render import pack_settings

    rdef = flagship_rdef(C)
    for cb, w in zip(rdef.channel_bindings, windows):
        cb.input_start, cb.input_end = w
    return pack_settings(rdef)


def run(coro):
    return asyncio.new_event_loop().run_until_complete(coro)


class TestMeshRenderer:
    def test_render_parity_with_single_device(self):
        from omero_ms_image_region_tpu.ops.render import (
            render_tile_packed)
        from omero_ms_image_region_tpu.parallel.serve import MeshRenderer

        mesh = _mesh(chan_parallel=2)
        renderer = MeshRenderer(mesh, linger_ms=0.0)
        rng = np.random.default_rng(0)
        # Mixed per-request settings; C=3 forces chan padding (3 -> 4).
        tiles = [rng.integers(0, 60000, (3, 40, 56)).astype(np.float32)
                 for _ in range(3)]
        settings = [_settings(3, [(0, 30000 + 10000 * i)] * 3)
                    for i in range(3)]

        async def go():
            return await asyncio.gather(*(
                renderer.render(t, s) for t, s in zip(tiles, settings)))

        outs = run(go())
        assert renderer.batches_dispatched >= 1
        # Compute the expectation on the mesh's own platform: the mesh may
        # have fallen back to the virtual CPU pool while the default
        # platform is a lone TPU, and float rounding at packed-int
        # boundaries differs across platforms.
        with jax.default_device(next(iter(mesh.devices.flat))):
            for t, s, out in zip(tiles, settings, outs):
                expect = np.asarray(render_tile_packed(
                    t, s["window_start"], s["window_end"], s["family"],
                    s["coefficient"], s["reverse"], s["cd_start"],
                    s["cd_end"], s["tables"]))
                np.testing.assert_array_equal(out, expect)

    def test_render_parity_with_full_lut_tables(self):
        """The [B, C, 256, 3] gather-table path through the mesh (ramp
        weights cover the other branch)."""
        from omero_ms_image_region_tpu.flagship import flagship_rdef
        from omero_ms_image_region_tpu.ops.render import (
            build_channel_tables, pack_settings, render_tile_packed)
        from omero_ms_image_region_tpu.parallel.serve import MeshRenderer

        mesh = _mesh(chan_parallel=2)
        renderer = MeshRenderer(mesh, linger_ms=0.0)
        rng = np.random.default_rng(7)
        rdef = flagship_rdef(2)
        for cb in rdef.channel_bindings:
            cb.reverse_intensity = True   # defeat the ramp-weight fold
        s = pack_settings(rdef)
        if s["tables"].ndim == 2:
            s = dict(s, tables=build_channel_tables(rdef))
        assert s["tables"].ndim == 3      # full [C, 256, 3] tables
        tile = rng.integers(0, 60000, (2, 32, 48)).astype(np.float32)

        async def go():
            return await renderer.render(tile, s)

        out = run(go())
        with jax.default_device(next(iter(mesh.devices.flat))):
            expect = np.asarray(render_tile_packed(
                tile, s["window_start"], s["window_end"], s["family"],
                s["coefficient"], s["reverse"], s["cd_start"],
                s["cd_end"], s["tables"]))
        np.testing.assert_array_equal(out, expect)

    def test_render_jpeg_produces_decodable_tiles(self):
        import io

        from PIL import Image

        from omero_ms_image_region_tpu.parallel.serve import MeshRenderer

        mesh = _mesh(chan_parallel=1)
        renderer = MeshRenderer(mesh, linger_ms=0.0)
        rng = np.random.default_rng(1)
        tiles = [rng.integers(0, 60000, (2, 24, 40)).astype(np.float32)
                 for _ in range(2)]
        settings = [_settings(2, [(0, 50000)] * 2) for _ in range(2)]

        async def go():
            return await asyncio.gather(*(
                renderer.render_jpeg(t, s, 85, t.shape[2], t.shape[1])
                for t, s in zip(tiles, settings)))

        jpegs = run(go())
        for t, j in zip(tiles, jpegs):
            img = Image.open(io.BytesIO(j))
            assert img.size == (t.shape[2], t.shape[1])

    def test_render_jpeg_huffman_engine_matches_sparse_pixels(self):
        """The mesh huffman engine entropy-codes the SAME quantized
        coefficients as the sparse engine, so both decode to identical
        pixels (the wire bytes differ: fixed vs optimal tables)."""
        import io

        from PIL import Image

        from omero_ms_image_region_tpu.parallel.serve import MeshRenderer

        mesh = _mesh(chan_parallel=2)
        sparse = MeshRenderer(mesh, linger_ms=0.0)
        huff = MeshRenderer(mesh, linger_ms=0.0, jpeg_engine="huffman")
        assert huff.jpeg_engine == "huffman"
        rng = np.random.default_rng(3)
        # 32x48 is MCU-grid-exact, so the group takes the packed stream.
        tiles = [rng.integers(0, 60000, (2, 32, 48)).astype(np.float32)
                 for _ in range(2)]
        settings = [_settings(2, [(0, 50000)] * 2) for _ in range(2)]

        def go(renderer):
            async def inner():
                return await asyncio.gather(*(
                    renderer.render_jpeg(t, s, 85, t.shape[2], t.shape[1])
                    for t, s in zip(tiles, settings)))
            return run(inner())

        sp_jpegs, hf_jpegs = go(sparse), go(huff)
        for sj, hj in zip(sp_jpegs, hf_jpegs):
            a = np.asarray(Image.open(io.BytesIO(sj)).convert("RGB"))
            b = np.asarray(Image.open(io.BytesIO(hj)).convert("RGB"))
            np.testing.assert_array_equal(a, b)


class TestMeshRendererTorture:
    def test_mixed_concurrent_load(self):
        """Mixed sizes, channel counts, packed + JPEG, simultaneously:
        every request completes with its own correct result (the group
        builder must never cross-contaminate padded batches)."""
        import io

        from PIL import Image

        from omero_ms_image_region_tpu.ops.render import render_tile_packed
        from omero_ms_image_region_tpu.parallel.serve import MeshRenderer

        mesh = _mesh(chan_parallel=2)
        renderer = MeshRenderer(mesh, linger_ms=1.0)
        rng = np.random.default_rng(11)
        jobs = []
        for i in range(12):
            # Decorrelate channel count from the packed/JPEG flag so both
            # paths see both C=2 (no chan padding) and C=3 (3 -> 4 pad).
            C = 2 + ((i // 2) % 2)
            h, w = [(16, 16), (24, 40), (32, 48)][i % 3]
            tile = rng.integers(0, 60000, (C, h, w)).astype(np.float32)
            s = _settings(C, [(0, 30000 + 5000 * (i % 4))] * C)
            jobs.append((tile, s, i % 2 == 0))  # alternate packed/JPEG

        async def go():
            async def one(tile, s, packed):
                if packed:
                    return await renderer.render(tile, s)
                return await renderer.render_jpeg(
                    tile, s, 85, tile.shape[2], tile.shape[1])
            return await asyncio.gather(*(one(*j) for j in jobs))

        outs = run(go())
        with jax.default_device(next(iter(mesh.devices.flat))):
            for (tile, s, packed), out in zip(jobs, outs):
                if packed:
                    expect = np.asarray(render_tile_packed(
                        tile, s["window_start"], s["window_end"],
                        s["family"], s["coefficient"], s["reverse"],
                        s["cd_start"], s["cd_end"], s["tables"]))
                    np.testing.assert_array_equal(out, expect)
                else:
                    img = Image.open(io.BytesIO(out))
                    assert img.size == (tile.shape[2], tile.shape[1])
        assert renderer.tiles_rendered == len(jobs)


class TestMeshServingHTTP:
    def test_request_served_by_mesh_renderer(self, tmp_path):
        from aiohttp.test_utils import TestClient, TestServer

        from omero_ms_image_region_tpu.io.store import build_pyramid
        from omero_ms_image_region_tpu.parallel.serve import MeshRenderer
        from omero_ms_image_region_tpu.server.app import (SERVICES_KEY,
                                                          create_app)
        from omero_ms_image_region_tpu.server.config import (
            AppConfig, ParallelConfig, RendererConfig)

        if len(resolve_devices(8)) < 8:
            pytest.skip("no 8-wide device pool (real or virtual)")

        rng = np.random.default_rng(5)
        planes = rng.integers(0, 60000, (2, 1, 64, 64)).astype(np.uint16)
        build_pyramid(planes, str(tmp_path / "1"), n_levels=1)

        config = AppConfig(
            data_dir=str(tmp_path),
            parallel=ParallelConfig(enabled=True, chan_parallel=2,
                                    n_devices=8),
            renderer=RendererConfig(cpu_fallback_max_px=0),
        )

        async def go():
            app = create_app(config)
            services = app[SERVICES_KEY]
            assert isinstance(services.renderer, MeshRenderer)
            assert services.renderer.mesh.size == 8
            client = TestClient(TestServer(app))
            await client.start_server()
            try:
                resp = await client.get(
                    "/webgateway/render_image_region/1/0/0"
                    "?tile=0,0,0,32,32&format=jpeg&m=c"
                    "&c=1|0:60000$FF0000,2|0:60000$00FF00")
                body = await resp.read()
                return resp.status, body, services.renderer
            finally:
                await client.close()

        status, body, renderer = run(go())
        assert status == 200
        assert body[:2] == b"\xff\xd8"
        assert renderer.batches_dispatched >= 1
        assert renderer.tiles_rendered >= 1

    def test_mesh_honors_huffman_engine_config(self, tmp_path):
        from aiohttp.test_utils import TestClient, TestServer

        from omero_ms_image_region_tpu.io.store import build_pyramid
        from omero_ms_image_region_tpu.parallel.serve import MeshRenderer
        from omero_ms_image_region_tpu.server.app import (SERVICES_KEY,
                                                          create_app)
        from omero_ms_image_region_tpu.server.config import (
            AppConfig, ParallelConfig, RendererConfig)

        if len(resolve_devices(8)) < 8:
            pytest.skip("no 8-wide device pool (real or virtual)")

        rng = np.random.default_rng(6)
        planes = rng.integers(0, 60000, (2, 1, 64, 64)).astype(np.uint16)
        build_pyramid(planes, str(tmp_path / "1"), n_levels=1)

        config = AppConfig(
            data_dir=str(tmp_path),
            parallel=ParallelConfig(enabled=True, chan_parallel=2,
                                    n_devices=8),
            renderer=RendererConfig(cpu_fallback_max_px=0,
                                    jpeg_engine="huffman"),
        )

        async def go():
            app = create_app(config)
            services = app[SERVICES_KEY]
            assert isinstance(services.renderer, MeshRenderer)
            assert services.renderer.jpeg_engine == "huffman"
            client = TestClient(TestServer(app))
            await client.start_server()
            try:
                resp = await client.get(
                    "/webgateway/render_image_region/1/0/0"
                    "?tile=0,0,0,32,32&format=jpeg&m=c"
                    "&c=1|0:60000$FF0000,2|0:60000$00FF00")
                return resp.status, await resp.read()
            finally:
                await client.close()

        status, body = run(go())
        assert status == 200
        assert body[:2] == b"\xff\xd8"


class TestMeshOverflowLockstep:
    """Wire-cap overflow on the 8-device mesh: the one-shot cap-
    widening rescue must produce a DETERMINISTIC launch sequence
    (base cap, then 2x, then memo-started 2x) and byte-identical
    output to the single-device serving path — the property multi-host
    lockstep rests on (``parallel/serve.py`` cap memos driven by
    replicated totals)."""

    B, C, H, W = 8, 4, 64, 64

    def _overflow_group(self, quality=85):
        """Deterministic mid-density content whose wire totals land in
        (cap, 2*cap] for every tile (probed: band=10 noise columns over
        a flat background, seed 7)."""
        from omero_ms_image_region_tpu.flagship import flagship_rdef
        from omero_ms_image_region_tpu.ops.render import pack_settings
        from omero_ms_image_region_tpu.server.batcher import _Pending

        rng = np.random.default_rng(7)
        flat = np.full((self.C, self.H, self.W), 20000, np.float32)
        settings = pack_settings(flagship_rdef(self.C))
        group = []
        for _ in range(self.B):
            raw = flat.copy()
            raw[:, :, :10] = rng.uniform(
                0, 60000, (self.C, self.H, 10)).astype(np.float32)
            group.append(_Pending(raw=raw, settings=settings,
                                  h=self.H, w=self.W, quality=quality))
        return group

    @pytest.mark.parametrize("engine", ["huffman", "sparse"])
    def test_overflow_rescue_launch_sequence_and_parity(self, engine):
        from omero_ms_image_region_tpu.ops import jpegenc as je
        from omero_ms_image_region_tpu.flagship import batched_args
        from omero_ms_image_region_tpu.parallel.serve import MeshRenderer

        je._CAP_MEMO.clear()
        renderer = MeshRenderer(_mesh(), jpeg_engine=engine)
        launches = []
        orig = MeshRenderer._jpeg_step

        def spy(self, quality, cap, engine_="sparse", cap_words=None):
            step = orig(self, quality, cap, engine_, cap_words)

            def wrapped(*args):
                launches.append((engine_, quality, cap, cap_words))
                return step(*args)
            return wrapped

        MeshRenderer._jpeg_step = spy
        try:
            jpegs1 = renderer._render_group_jpeg(self._overflow_group())
            jpegs2 = renderer._render_group_jpeg(self._overflow_group())
        finally:
            MeshRenderer._jpeg_step = orig
        base_cap = je.default_sparse_cap(self.H, self.W, 85)
        base_words = je.default_words_cap(self.H, self.W, 85)
        if engine == "huffman":
            want = [("huffman", 85, base_cap, base_words),
                    ("huffman", 85, 2 * base_cap, 2 * base_words),
                    ("huffman", 85, 2 * base_cap, 2 * base_words)]
        else:
            want = [("sparse", 85, base_cap, None),
                    ("sparse", 85, 2 * base_cap, None),
                    ("sparse", 85, 2 * base_cap, None)]
        # Group 1: base dispatch + one rescue at 2x; group 2: the memo
        # starts at 2x directly.  NO dense fallbacks (rescue covered
        # every tile) and NO extra launches.
        assert launches == want

        # Byte parity with the single-device serving path on the same
        # pixels/settings (its own memo key; fresh = same rescue).
        group = self._overflow_group()
        raw = np.stack([p.raw for p in group])
        s = group[0].settings
        args = batched_args(s, raw)
        plain = je.render_batch_to_jpeg(
            raw, *args[1:], quality=85,
            dims=[(self.W, self.H)] * self.B, engine=engine)
        assert plain == jpegs1 == jpegs2
        run(renderer.close())
        je._CAP_MEMO.clear()


def test_mesh_multihost_disables_batch_growth(monkeypatch):
    """Host-local max_batch growth would diverge multi-host SPMD
    launches; the mesh renderer disables it when process_count > 1."""
    import jax

    from omero_ms_image_region_tpu.parallel.mesh import (
        make_mesh, resolve_devices)
    from omero_ms_image_region_tpu.parallel.serve import MeshRenderer

    if len(resolve_devices(8)) < 8:
        pytest.skip("no 8-wide device pool")
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    r = MeshRenderer(make_mesh(8, chan_parallel=1))
    assert r._growth_enabled is False
    r2 = BatchingRendererForTest()
    assert r2._growth_enabled is True


def BatchingRendererForTest():
    from omero_ms_image_region_tpu.server.batcher import BatchingRenderer
    return BatchingRenderer(max_batch=2, linger_ms=0.0)
