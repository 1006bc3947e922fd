"""Micro-batcher: correctness vs the direct path, coalescing, ragged pads."""

import asyncio

import numpy as np
import pytest

from omero_ms_image_region_tpu.flagship import flagship_settings
from omero_ms_image_region_tpu.models.rendering import (
    RenderingModel, default_rendering_def,
)
from omero_ms_image_region_tpu.models.pixels import Pixels
from omero_ms_image_region_tpu.ops.render import pack_settings
from omero_ms_image_region_tpu.server.batcher import (
    BatchingRenderer, pick_bucket,
)
from omero_ms_image_region_tpu.server.handler import Renderer


def _settings(C=3):
    pixels = Pixels(image_id=1, pixels_type="uint16", size_x=64, size_y=64,
                    size_c=C)
    rdef = default_rendering_def(pixels)
    rdef.model = RenderingModel.RGB
    colors = [(255, 0, 0), (0, 255, 0), (0, 0, 255)]
    for c, cb in enumerate(rdef.channel_bindings):
        cb.red, cb.green, cb.blue = colors[c % 3]
        cb.input_start, cb.input_end = 0.0, 60000.0
    return pack_settings(rdef)


def run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()


class TestPickBucket:
    def test_rounds_up(self):
        assert pick_bucket(100, 200) == (256, 256)
        assert pick_bucket(256, 257) == (512, 512)
        assert pick_bucket(1, 1) == (256, 256)

    def test_oversize_passthrough(self):
        assert pick_bucket(5000, 100) == (5000, 100)


class TestBatchingRenderer:
    def test_matches_direct_renderer(self):
        rng = np.random.default_rng(0)
        settings = _settings()
        raw = rng.integers(0, 60000, size=(3, 40, 56)).astype(np.float32)

        async def main():
            batcher = BatchingRenderer(linger_ms=0.5)
            try:
                direct = await Renderer().render(raw, settings)
                batched = await batcher.render(raw, settings)
                return direct, batched
            finally:
                await batcher.close()

        direct, batched = run(main())
        assert batched.shape == (40, 56)       # cropped back from 256 pad
        np.testing.assert_array_equal(direct, batched)

    def test_jpeg_group_cobatches_same_mcu_grid(self):
        """Different true sizes sharing one 16-aligned grid batch together;
        each SOF0 carries its own dimensions."""
        import io

        from PIL import Image

        rng = np.random.default_rng(4)
        settings = _settings()
        raw_a = rng.integers(0, 60000, size=(3, 20, 28)).astype(np.float32)
        raw_b = rng.integers(0, 60000, size=(3, 32, 32)).astype(np.float32)

        async def main():
            batcher = BatchingRenderer(max_batch=4, linger_ms=20.0)
            try:
                outs = await asyncio.gather(
                    batcher.render_jpeg(raw_a, settings, 85, 28, 20),
                    batcher.render_jpeg(raw_b, settings, 85, 32, 32))
            finally:
                # close() awaits the in-flight group, so the dispatch
                # counter read below cannot race the group tail when
                # first-tile-out settles the waiters early.
                await batcher.close()
            return outs, batcher.batches_dispatched

        (a, b), dispatched = run(main())
        assert dispatched == 1
        assert Image.open(io.BytesIO(a)).size == (28, 20)
        assert Image.open(io.BytesIO(b)).size == (32, 32)

    def test_jpeg_matches_direct_renderer_jpeg(self):
        rng = np.random.default_rng(5)
        settings = _settings()
        raw = rng.integers(0, 60000, size=(3, 48, 48)).astype(np.float32)

        async def main():
            batcher = BatchingRenderer(linger_ms=0.5)
            try:
                direct = await Renderer().render_jpeg(
                    raw, settings, 85, 48, 48)
                batched = await batcher.render_jpeg(
                    raw, settings, 85, 48, 48)
                return direct, batched
            finally:
                await batcher.close()

        direct, batched = run(main())
        assert direct == batched  # same kernel, same entropy coder

    def test_concurrent_requests_coalesce(self):
        rng = np.random.default_rng(1)
        settings = _settings()
        raws = [rng.integers(0, 60000, size=(3, 32, 32)).astype(np.float32)
                for _ in range(8)]

        async def main():
            batcher = BatchingRenderer(max_batch=8, linger_ms=20.0)
            try:
                outs = await asyncio.gather(*(
                    batcher.render(r, settings) for r in raws))
                return outs, batcher.batches_dispatched
            finally:
                await batcher.close()

        outs, n_batches = run(main())
        assert n_batches < len(raws)           # actually coalesced
        direct = Renderer()
        for raw, out in zip(raws, outs):
            expected = run(direct.render(raw, settings))
            np.testing.assert_array_equal(out, expected)

    def test_mixed_settings_share_batch(self):
        """Different windows/colors must still produce per-tile results."""
        rng = np.random.default_rng(2)
        raw = rng.integers(0, 60000, size=(3, 16, 16)).astype(np.float32)
        s1, s2 = _settings(), _settings()
        s2["window_start"] = s2["window_start"] + 1000.0
        s2["tables"] = s2["tables"][..., ::-1].copy()    # swap rgb

        async def main():
            batcher = BatchingRenderer(max_batch=4, linger_ms=20.0)
            try:
                return await asyncio.gather(
                    batcher.render(raw, s1), batcher.render(raw, s2))
            finally:
                await batcher.close()

        out1, out2 = run(main())
        exp1 = run(Renderer().render(raw, s1))
        exp2 = run(Renderer().render(raw, s2))
        np.testing.assert_array_equal(out1, exp1)
        np.testing.assert_array_equal(out2, exp2)
        assert not np.array_equal(out1, out2)

    def test_different_channel_counts_do_not_mix(self):
        rng = np.random.default_rng(3)
        raw3 = rng.integers(0, 60000, size=(3, 16, 16)).astype(np.float32)
        raw4 = rng.integers(0, 60000, size=(4, 16, 16)).astype(np.float32)
        _, s4 = flagship_settings(4)

        async def main():
            batcher = BatchingRenderer(linger_ms=5.0)
            try:
                return await asyncio.gather(
                    batcher.render(raw3, _settings(3)),
                    batcher.render(raw4, s4))
            finally:
                await batcher.close()

        out3, out4 = run(main())
        assert out3.shape == out4.shape == (16, 16)

    def test_render_error_propagates(self):
        settings = _settings()
        bad = np.zeros((2, 16, 16), np.float32)   # C mismatch vs settings

        async def main():
            batcher = BatchingRenderer(linger_ms=0.5)
            try:
                with pytest.raises(Exception):
                    await batcher.render(bad, settings)
            finally:
                await batcher.close()

        run(main())


def _golden_decoded(raw, rdef, width, height, quality):
    """PIL's decode of the JFIF that ``refimpl`` + the host coders give
    for one tile: the reference render of the edge-padded planes, the
    same coefficient front end, the dense entropy coder."""
    import io

    from PIL import Image

    from omero_ms_image_region_tpu.ops.jpegenc import (
        dense_encoder, pad_planes_to_mcu, quant_tables,
        rgb_to_jpeg_coefficients)
    from omero_ms_image_region_tpu.refimpl import render_ref

    rgba = render_ref(pad_planes_to_mcu(raw), rdef)
    qy, qc = (t.astype(np.int32) for t in quant_tables(quality))
    y, cb, cr = (np.asarray(a)[0] for a in rgb_to_jpeg_coefficients(
        rgba[None, ..., :3].astype(np.float32), qy, qc))
    body = dense_encoder()(y, cb, cr, width, height, quality)
    return np.asarray(Image.open(io.BytesIO(body)).convert("RGB"))


@pytest.mark.parametrize("dims", [(64, 64), (56, 40)],
                         ids=["exact", "ragged"])
@pytest.mark.parametrize("kind", ["direct", "batched"])
@pytest.mark.parametrize("engine", ["sparse", "huffman"])
def test_both_engines_through_both_single_chip_renderers(engine, kind,
                                                         dims):
    """The engine string goes from the constructor to
    ``render_batch_to_jpeg`` untouched: either renderer, under either
    engine, answers with the coefficients of the reference render (the
    decoded pixels are the golden's, whatever Huffman tables framed
    them), and a ``huffman`` batcher codes a tile smaller than its
    bucket's grid ``sparse``, byte for byte."""
    import io

    from PIL import Image

    from omero_ms_image_region_tpu.flagship import (
        flagship_settings, synthetic_wsi_tiles)

    width, height = dims
    rdef, settings = flagship_settings(2)
    # Soft content, inside the default wire caps (no dense fall-back).
    raw = (synthetic_wsi_tiles(np.random.default_rng(42), 1, 2, height,
                               width)[0].astype(np.float32)
           / 8.0 + 15000.0)

    def build(eng):
        if kind == "direct":
            return Renderer(jpeg_engine=eng)
        return BatchingRenderer(jpeg_engine=eng, linger_ms=0.0,
                                buckets=((64, 64),))

    async def serve(eng):
        renderer = build(eng)
        assert renderer.jpeg_engine == eng
        try:
            return await renderer.render_jpeg(raw, settings, 85, width,
                                              height)
        finally:
            if kind == "batched":
                await renderer.close()

    body = run(serve(engine))
    got = np.asarray(Image.open(io.BytesIO(body)).convert("RGB"))
    assert got.shape == (height, width, 3)
    np.testing.assert_array_equal(
        got, _golden_decoded(raw, rdef, width, height, 85))
    if engine == "huffman":
        # The Huffman stream covers a group's whole grid: the direct
        # renderer's grid is the tile's own, the batcher's its bucket's.
        fell_back = kind == "batched" and dims == (56, 40)
        assert (body == run(serve("sparse"))) == fell_back


class TestPipelining:
    def test_groups_overlap_up_to_depth(self):
        """With pipeline_depth=2, a second group dispatches while the
        first is still rendering (the loop must not serialize on the
        full render)."""
        import threading

        from omero_ms_image_region_tpu.server.batcher import (
            BatchingRenderer)

        gate = threading.Event()
        concurrent = {"now": 0, "peak": 0}
        lock = threading.Lock()

        class SlowRenderer(BatchingRenderer):
            def _render_group(self, group):
                with lock:
                    concurrent["now"] += 1
                    concurrent["peak"] = max(concurrent["peak"],
                                             concurrent["now"])
                # Both groups must be in flight before either finishes.
                if concurrent["peak"] < 2:
                    gate.wait(timeout=30)
                else:
                    gate.set()
                with lock:
                    concurrent["now"] -= 1
                return super()._render_group(group)

        # 1024^2 bucket: max_batch=1 means one render a group there
        # (a smaller bucket's cap is a multiple: group_cap).
        r = SlowRenderer(max_batch=1, linger_ms=0.0, pipeline_depth=2,
                         buckets=((1024, 1024),))
        rng = np.random.default_rng(3)
        from omero_ms_image_region_tpu.flagship import flagship_rdef
        from omero_ms_image_region_tpu.ops.render import pack_settings
        s = pack_settings(flagship_rdef(1))

        async def go():
            tiles = [rng.integers(0, 60000, (1, 16, 16))
                     .astype(np.float32) for _ in range(2)]
            return await asyncio.gather(
                *(r.render(t, s) for t in tiles))

        outs = asyncio.run(go())
        assert concurrent["peak"] == 2
        assert all(o.shape == (16, 16) for o in outs)

    def test_depth_one_serializes(self):
        import threading

        from omero_ms_image_region_tpu.server.batcher import (
            BatchingRenderer)

        concurrent = {"now": 0, "peak": 0}
        lock = threading.Lock()

        class Probe(BatchingRenderer):
            def _render_group(self, group):
                with lock:
                    concurrent["now"] += 1
                    concurrent["peak"] = max(concurrent["peak"],
                                             concurrent["now"])
                try:
                    return super()._render_group(group)
                finally:
                    with lock:
                        concurrent["now"] -= 1

        r = Probe(max_batch=1, linger_ms=0.0, pipeline_depth=1)
        rng = np.random.default_rng(4)
        from omero_ms_image_region_tpu.flagship import flagship_rdef
        from omero_ms_image_region_tpu.ops.render import pack_settings
        s = pack_settings(flagship_rdef(1))

        async def go():
            tiles = [rng.integers(0, 60000, (1, 16, 16))
                     .astype(np.float32) for _ in range(4)]
            return await asyncio.gather(
                *(r.render(t, s) for t in tiles))

        asyncio.run(go())
        assert concurrent["peak"] == 1


class TestTwoStagePipeline:
    def test_stage_span_recorded_and_results_match_direct(self):
        """The fetch/stage half records its own span and the split
        changes no pixels: batched output equals the direct renderer."""
        from omero_ms_image_region_tpu.utils.stopwatch import REGISTRY

        rng = np.random.default_rng(11)
        settings = _settings()
        raw = rng.integers(0, 60000, size=(3, 24, 24)).astype(np.float32)
        before = REGISTRY.snapshot().get("batcher.stage",
                                         {}).get("count", 0)

        async def main():
            batcher = BatchingRenderer(linger_ms=0.5, device_lanes=2)
            try:
                return await batcher.render(raw, settings)
            finally:
                await batcher.close()

        batched = run(main())
        direct = run(Renderer().render(raw, settings))
        np.testing.assert_array_equal(batched, direct)
        after = REGISTRY.snapshot()["batcher.stage"]["count"]
        assert after == before + 1

    def test_device_lanes_bound_execute_concurrency(self):
        """With device_lanes=1 and pipeline_depth=2, two groups overlap
        in fetch/stage but never in device-execute."""
        import threading

        from omero_ms_image_region_tpu.ops import render as render_ops

        concurrent = {"now": 0, "peak": 0, "staged": 0}
        lock = threading.Lock()
        both_staged = threading.Event()
        real = render_ops.render_tile_batch_packed

        class Probe(BatchingRenderer):
            def _stage_group(self, group):
                out = super()._stage_group(group)
                with lock:
                    concurrent["staged"] += 1
                    if concurrent["staged"] >= 2:
                        both_staged.set()
                # Hold every group in the stage->execute handoff until
                # BOTH have staged, so execute concurrency is actually
                # contested.
                both_staged.wait(timeout=30)
                return out

        def counting_kernel(*args, **kw):
            with lock:
                concurrent["now"] += 1
                concurrent["peak"] = max(concurrent["peak"],
                                         concurrent["now"])
            try:
                import time as _t
                _t.sleep(0.05)    # force overlap if the gate leaked
                return real(*args, **kw)
            finally:
                with lock:
                    concurrent["now"] -= 1

        r = Probe(max_batch=1, linger_ms=0.0, pipeline_depth=2,
                  device_lanes=1, buckets=((1024, 1024),))
        rng = np.random.default_rng(12)
        from omero_ms_image_region_tpu.flagship import flagship_rdef
        from omero_ms_image_region_tpu.ops.render import pack_settings
        s = pack_settings(flagship_rdef(1))
        import omero_ms_image_region_tpu.server.batcher as batcher_mod
        orig = batcher_mod.render_tile_batch_packed
        batcher_mod.render_tile_batch_packed = counting_kernel
        try:
            async def go():
                tiles = [rng.integers(0, 60000, (1, 16, 16))
                         .astype(np.float32) for _ in range(2)]
                return await asyncio.gather(
                    *(r.render(t, s) for t in tiles))

            outs = asyncio.run(go())
        finally:
            batcher_mod.render_tile_batch_packed = orig
        assert concurrent["staged"] == 2    # stages ran for both groups
        assert concurrent["peak"] == 1      # executes never overlapped
        assert all(o.shape == (16, 16) for o in outs)

    def test_device_lanes_validation(self):
        with pytest.raises(ValueError):
            BatchingRenderer(device_lanes=0)

    def test_queue_wait_max_gauge_tracks_high_water(self):
        rng = np.random.default_rng(13)
        settings = _settings()
        raw = rng.integers(0, 60000, size=(3, 16, 16)).astype(np.float32)

        async def main():
            batcher = BatchingRenderer(linger_ms=5.0)
            try:
                await asyncio.gather(*(
                    batcher.render(raw, settings) for _ in range(4)))
                return batcher.queue_wait_max_ms
            finally:
                await batcher.close()

        max_ms = run(main())
        assert max_ms > 0.0
        # The gauge reaches /metrics through device_metric_lines.
        from omero_ms_image_region_tpu.utils import telemetry

        class _Services:
            renderer = None
        svc = _Services()

        async def gauge():
            svc.renderer = BatchingRenderer(linger_ms=0.0)
            try:
                lines = telemetry.device_metric_lines(svc)
                return [ln for ln in lines
                        if "queue_wait_max_ms" in ln]
            finally:
                await svc.renderer.close()

        assert run(gauge())


class TestLaneCoversTheDeviceHalfOnly:
    """A JPEG group holds its device lane from the jitted call until
    its wire rows are in host memory (``render_batch_to_wire``) and not
    through the host's entropy coding (``finish_wire_to_jpegs``)."""

    @staticmethod
    def _tile(seed):
        rng = np.random.default_rng(seed)
        return rng.integers(0, 60000, size=(3, 32, 32)).astype(np.float32)

    @staticmethod
    def _batcher(**kw):
        # A 64^2 bucket: small programs.  Each request is sent alone
        # (linger 0, an idle renderer skips it), so a group is a tile.
        return BatchingRenderer(linger_ms=0.0, buckets=((64, 64),), **kw)

    def test_next_device_half_enters_under_a_blocked_host_half(
            self, monkeypatch):
        """device_lanes=1, pipeline_depth=2: while the first group's
        host half blocks, the second group's device half is entered
        (before PR 31 it waited for the lane until the first group's
        last byte)."""
        import threading

        from omero_ms_image_region_tpu.ops import jpegenc

        host_entered, release = threading.Event(), threading.Event()
        second_device_entered = threading.Event()
        device_calls = []
        real_wire = jpegenc.render_batch_to_wire
        real_finish = jpegenc.finish_wire_to_jpegs

        def wire(*args, **kw):
            device_calls.append(1)
            if len(device_calls) == 2:
                second_device_entered.set()
            return real_wire(*args, **kw)

        def finish(*args, **kw):
            first = not host_entered.is_set()
            host_entered.set()
            if first:
                release.wait(timeout=60)
            return real_finish(*args, **kw)

        monkeypatch.setattr(jpegenc, "render_batch_to_wire", wire)
        monkeypatch.setattr(jpegenc, "finish_wire_to_jpegs", finish)
        settings = _settings()
        a, b = self._tile(21), self._tile(22)

        async def main():
            batcher = self._batcher(pipeline_depth=2, device_lanes=1)
            try:
                first = asyncio.ensure_future(
                    batcher.render_jpeg(a, settings, 85, 32, 32))
                assert await asyncio.to_thread(host_entered.wait, 60)
                second = asyncio.ensure_future(
                    batcher.render_jpeg(b, settings, 85, 32, 32))
                entered = await asyncio.to_thread(
                    second_device_entered.wait, 20)
                blocked = not first.done()
                release.set()
                return entered, blocked, await first, await second
            finally:
                release.set()
                await batcher.close()

        entered, blocked, first, second = run(main())
        assert blocked      # the first host half had not returned
        assert entered      # and the lane was free all the same
        direct = Renderer()
        assert first == run(direct.render_jpeg(a, settings, 85, 32, 32))
        assert second == run(direct.render_jpeg(b, settings, 85, 32, 32))

    def test_device_halves_never_overlap_under_one_lane(self,
                                                        monkeypatch):
        """The gate still bounds what it is for: with device_lanes=1
        two groups' device halves never run at once, whatever their
        host halves do."""
        import threading
        import time as _t

        from omero_ms_image_region_tpu.ops import jpegenc

        lock = threading.Lock()
        concurrent = {"now": 0, "peak": 0, "calls": 0}
        real_wire = jpegenc.render_batch_to_wire

        def wire(*args, **kw):
            with lock:
                concurrent["now"] += 1
                concurrent["calls"] += 1
                concurrent["peak"] = max(concurrent["peak"],
                                         concurrent["now"])
            try:
                _t.sleep(0.05)      # force overlap if the gate leaked
                return real_wire(*args, **kw)
            finally:
                with lock:
                    concurrent["now"] -= 1

        monkeypatch.setattr(jpegenc, "render_batch_to_wire", wire)
        settings = _settings()
        # Different channel counts never share a group: two groups.
        _, s4 = flagship_settings(4)
        raw4 = np.random.default_rng(23).integers(
            0, 60000, size=(4, 32, 32)).astype(np.float32)

        async def main():
            batcher = self._batcher(pipeline_depth=2, device_lanes=1)
            try:
                return await asyncio.gather(
                    batcher.render_jpeg(self._tile(24), settings, 85,
                                        32, 32),
                    batcher.render_jpeg(raw4, s4, 85, 32, 32))
            finally:
                await batcher.close()

        outs = run(main())
        assert all(o[:2] == b"\xff\xd8" for o in outs)
        assert concurrent["calls"] == 2 and concurrent["peak"] == 1

    def test_lane_released_when_either_half_raises(self, monkeypatch):
        """The device half of one group raises, the host half of the
        next: both fail their waiters, and a third group renders on the
        only lane."""
        from omero_ms_image_region_tpu.ops import jpegenc

        calls = {"wire": 0, "finish": 0}
        real_wire = jpegenc.render_batch_to_wire
        real_finish = jpegenc.finish_wire_to_jpegs

        def wire(*args, **kw):
            calls["wire"] += 1
            if calls["wire"] == 1:
                raise ValueError("device half down")
            return real_wire(*args, **kw)

        def finish(*args, **kw):
            calls["finish"] += 1
            if calls["finish"] == 1:
                raise ValueError("host half down")
            return real_finish(*args, **kw)

        monkeypatch.setattr(jpegenc, "render_batch_to_wire", wire)
        monkeypatch.setattr(jpegenc, "finish_wire_to_jpegs", finish)
        settings = _settings()
        raw = self._tile(25)

        async def main():
            batcher = self._batcher(pipeline_depth=1, device_lanes=1)
            try:
                for half in ("device", "host"):
                    with pytest.raises(ValueError, match=half):
                        await batcher.render_jpeg(raw, settings, 85,
                                                  32, 32)
                third = await asyncio.wait_for(
                    batcher.render_jpeg(raw, settings, 85, 32, 32), 60)
                # The lane is back: the only one can be taken at once.
                free = batcher._device_gate.acquire(blocking=False)
                if free:
                    batcher._device_gate.release()
                return third, free
            finally:
                await batcher.close()

        third, free = run(main())
        assert free and calls == {"wire": 3, "finish": 2}
        assert third == run(Renderer().render_jpeg(raw, settings, 85,
                                                   32, 32))

    def test_group_trace_holds_the_lane_hold_around_the_device_spans(
            self):
        """A served JPEG group's trace holds ``batcher.laneHold``;
        ``device.dispatch``, ``device.wait`` and ``wire.d2h`` begin and
        end inside it, ``jfif.encodeBatch`` begins after it ends."""
        from omero_ms_image_region_tpu.utils import telemetry

        settings = _settings()
        raw = self._tile(26)

        async def main():
            batcher = self._batcher()
            with telemetry.trace_scope(telemetry.new_trace_id(),
                                       "test") as trace:
                try:
                    await batcher.render_jpeg(raw, settings, 85, 32, 32)
                finally:
                    # close() awaits the group's tail: first-tile-out
                    # answers before ``jfif.encodeBatch`` has closed.
                    await batcher.close()
            return trace

        trace = run(main())
        spans = {}
        for s in trace.export_spans():
            spans.setdefault(s["name"], []).append(
                (s["start_ms"], s["start_ms"] + s["dur_ms"]))
        for name in ("batcher.laneWait", "batcher.laneHold",
                     "device.dispatch", "device.wait", "wire.d2h",
                     "jfif.encodeBatch",
                     "Renderer.renderAsPackedInt.batch"):
            assert len(spans.get(name, [])) == 1, (name, sorted(spans))
        # A span's start is its end less its duration, each rounded to
        # the microsecond.
        eps = 0.01
        hold = spans["batcher.laneHold"][0]
        for name in ("device.dispatch", "device.wait", "wire.d2h"):
            start, end = spans[name][0]
            assert hold[0] - eps <= start and end <= hold[1] + eps, name
        assert spans["batcher.laneWait"][0][1] <= hold[0] + eps
        assert spans["jfif.encodeBatch"][0][0] >= hold[1] - eps
        # The batch span still covers both halves, and not the wait.
        batch = spans["Renderer.renderAsPackedInt.batch"][0]
        assert hold[0] - eps <= batch[0]
        assert batch[1] + eps >= spans["jfif.encodeBatch"][0][1]


class TestTransientRetry:
    """One host-local retry of a group whose dispatch died on a
    transient transport error (utils.transient: a JaxRuntimeError
    INTERNAL/UNAVAILABLE with a transport-level message)."""

    @staticmethod
    def _transient_error():
        # Name-matched by is_transient_device_error (the real class
        # lives in jax.errors; the classifier is import-light).
        cls = type("JaxRuntimeError", (RuntimeError,), {})
        return cls("INTERNAL: read body: response body closed before "
                   "all bytes were read")

    def test_classifier(self):
        from omero_ms_image_region_tpu.utils.transient import (
            is_transient_device_error,
        )
        assert is_transient_device_error(self._transient_error())
        # Deterministic program/runtime failures must not match.
        cls = type("JaxRuntimeError", (RuntimeError,), {})
        assert not is_transient_device_error(
            cls("RESOURCE_EXHAUSTED: out of memory"))
        assert not is_transient_device_error(
            ValueError("response body closed"))

    def test_retry_once_then_propagate(self):
        from omero_ms_image_region_tpu.utils.transient import (
            retry_transient,
        )
        calls = {"n": 0}

        def flaky():
            calls["n"] += 1
            if calls["n"] == 1:
                raise self._transient_error()
            return "ok"

        from omero_ms_image_region_tpu.utils import telemetry
        before = telemetry.RESILIENCE.retries.get("drill", 0)
        assert retry_transient(flaky, "drill", backoff_s=0.0) == "ok"
        assert calls["n"] == 2
        # Every firing is counted (imageregion_retries_total{op=...}):
        # a run can say from the server's series whether this path
        # ever fired on its chip.
        assert telemetry.RESILIENCE.retries["drill"] == before + 1

        calls["n"] = 0

        def always_broken():
            calls["n"] += 1
            raise self._transient_error()

        with pytest.raises(RuntimeError):
            retry_transient(always_broken, backoff_s=0.0)
        assert calls["n"] == 2   # exactly one retry

    def test_group_render_survives_one_transient_failure(self):
        settings = _settings()
        rng = np.random.default_rng(1)
        raw = rng.integers(0, 60000, size=(3, 16, 16)).astype(np.float32)
        fails = {"left": 1}
        outer = self

        class Flaky(BatchingRenderer):
            def _render_group(self, group):
                if fails["left"]:
                    fails["left"] -= 1
                    raise outer._transient_error()
                return super()._render_group(group)

        async def main():
            batcher = Flaky(linger_ms=0.0)
            try:
                out = await batcher.render(raw, settings)
                assert out.shape == (16, 16)
            finally:
                await batcher.close()

        run(main())

    def test_multihost_gate_disables_retry(self):
        settings = _settings()
        rng = np.random.default_rng(2)
        raw = rng.integers(0, 60000, size=(3, 16, 16)).astype(np.float32)
        outer = self

        class Flaky(BatchingRenderer):
            def __init__(self, **kw):
                super().__init__(**kw)
                self._transient_retry_enabled = False

            def _render_group(self, group):
                raise outer._transient_error()

        async def main():
            batcher = Flaky(linger_ms=0.0)
            try:
                with pytest.raises(RuntimeError):
                    await batcher.render(raw, settings)
            finally:
                await batcher.close()

        run(main())


class TestPrewarm:
    def test_prewarm_compiles_and_serving_matches(self):
        """prewarm_renderer runs the real serving entry points; a
        subsequent batched render of the warmed shape still produces
        correct output (programs warm, semantics untouched)."""
        from omero_ms_image_region_tpu.server.prewarm import (
            prewarm_renderer,
        )

        prewarm_renderer(["3x64"], "sparse", max_batch=2,
                         buckets=((64, 64),))

        settings = _settings()
        rng = np.random.default_rng(5)
        raw = rng.integers(0, 60000, size=(3, 64, 64)).astype(np.float32)

        async def main():
            batcher = BatchingRenderer(linger_ms=0.0,
                                       buckets=((64, 64),))
            try:
                direct = await Renderer().render(raw, settings)
                batched = await batcher.render(raw, settings)
                np.testing.assert_array_equal(np.asarray(direct),
                                              np.asarray(batched))
                jpeg = await batcher.render_jpeg(raw, settings, 85,
                                                 64, 64)
                assert jpeg[:2] == b"\xff\xd8"
            finally:
                await batcher.close()

        run(main())

    def test_prewarm_failure_is_nonfatal(self):
        from omero_ms_image_region_tpu.server.prewarm import (
            prewarm_renderer,
        )

        # 8192 channels is out of parse range -> ValueError (caught at
        # config load normally); prewarm_renderer itself must raise for
        # malformed specs (the loader's contract) ...
        import pytest as _pytest
        with _pytest.raises(ValueError):
            prewarm_renderer(["0x64"], "sparse", 2, ((64, 64),))
        # ... but a VALID spec whose compile dies is logged, not fatal.
        prewarm_renderer(["3x64"], "no-such-engine", 2, ((64, 64),))

    def test_prewarm_skips_cpu_fallback_shapes_and_dtype_specs(self):
        """Shapes the CPU fallback serves are skipped (their device
        program would never be hit); a spec's :dtype suffix warms the
        storage dtype those images actually stage."""
        from omero_ms_image_region_tpu.server.prewarm import (
            prewarm_renderer,
        )

        # 64*64 = 4096 <= threshold -> skipped (returns instantly even
        # with a bogus engine that would fail compile).
        prewarm_renderer(["3x64"], "no-such-engine", 2, ((64, 64),),
                         cpu_fallback_max_px=64 * 64)
        # Non-default storage dtype (uint8 sources) compiles fine.
        prewarm_renderer(["3x64:uint8"], "sparse", 2, ((64, 64),))


class TestQueuePressure:
    def test_queue_pressure_grows_batch(self):
        """Sustained full-batch backlog doubles max_batch up to the
        limit; light load never grows it."""
        from omero_ms_image_region_tpu.flagship import flagship_rdef
        from omero_ms_image_region_tpu.ops.render import pack_settings
        from omero_ms_image_region_tpu.server.batcher import (
            BatchingRenderer)

        # A 1024^2 bucket, where max_batch counts renders as it is
        # written (a smaller bucket's cap is a multiple: group_cap).
        r = BatchingRenderer(max_batch=2, linger_ms=1.0,
                             max_batch_limit=8,
                             buckets=((1024, 1024),))
        rdef = flagship_rdef(1)
        settings = pack_settings(rdef)
        rng = np.random.default_rng(1)

        async def flood(n):
            raws = [rng.uniform(0, 60000, (1, 32, 32)).astype(
                np.float32) for _ in range(n)]
            return await asyncio.gather(
                *[r.render(raw, settings) for raw in raws])

        loop = asyncio.new_event_loop()
        try:
            out = loop.run_until_complete(flood(64))
            assert len(out) == 64
            assert 2 < r.max_batch <= 8
        finally:
            loop.run_until_complete(r.close())
            loop.close()


class TestGroupPop:
    def test_a_backlog_pops_cap_sized_groups_in_arrival_order(self):
        """A backlog larger than the bucket's cap leaves in groups of
        the cap, oldest first, the last group taking what is left."""
        from omero_ms_image_region_tpu.flagship import flagship_rdef
        from omero_ms_image_region_tpu.ops.render import pack_settings

        popped = []

        class Probe(BatchingRenderer):
            def _render_group(self, group):
                popped.append([int(p.raw[0, 0, 0]) for p in group])
                return [np.zeros((p.h, p.w), np.uint32) for p in group]

        # A 1024^2 bucket, so the cap is max_batch; no growth.
        r = Probe(max_batch=3, max_batch_limit=3, linger_ms=0.0,
                  pipeline_depth=1, buckets=((1024, 1024),))
        settings = pack_settings(flagship_rdef(1))

        async def burst():
            try:
                return await asyncio.gather(*(
                    r.render(np.full((1, 8, 8), i, np.float32), settings)
                    for i in range(8)))
            finally:
                await r.close()

        out = asyncio.run(burst())
        assert len(out) == 8
        assert popped == [[0, 1, 2], [3, 4, 5], [6, 7]]


class TestLingerBypass:
    def test_lone_idle_request_skips_linger(self, monkeypatch):
        """A single request on an idle renderer dispatches immediately
        (single-tile p50 must not pay the coalescing linger)."""
        from omero_ms_image_region_tpu.flagship import flagship_rdef
        from omero_ms_image_region_tpu.ops.render import pack_settings
        from omero_ms_image_region_tpu.server.batcher import (
            BatchingRenderer)

        sleeps = []
        real_sleep = asyncio.sleep

        async def spy_sleep(s):
            if s > 0:
                sleeps.append(s)
            await real_sleep(0)

        r = BatchingRenderer(max_batch=8, linger_ms=50.0)
        rdef = flagship_rdef(1)
        settings = pack_settings(rdef)
        raw = np.zeros((1, 32, 32), np.float32)

        async def one():
            monkeypatch.setattr(asyncio, "sleep", spy_sleep)
            try:
                return await r.render(raw, settings)
            finally:
                monkeypatch.setattr(asyncio, "sleep", real_sleep)

        loop = asyncio.new_event_loop()
        try:
            out = loop.run_until_complete(one())
            assert out.shape == (32, 32)
            assert 0.05 not in sleeps    # the linger was bypassed
        finally:
            loop.run_until_complete(r.close())
            loop.close()


class TestSlotSpans:
    """A pipeline slot's turn in spans (PR 36): ``batcher.slot`` is
    ``batcher.slotStart`` + ``batcher.group`` + ``batcher.settleLag``,
    recorded for a slot that carried a group and for no other; and a
    request's own ``batcher.inGroup`` ends where ITS answer came."""

    @staticmethod
    def _tile(seed):
        rng = np.random.default_rng(seed)
        return rng.integers(0, 60000, size=(3, 32, 32)).astype(np.float32)

    @staticmethod
    def _spans():
        from omero_ms_image_region_tpu.utils.stopwatch import REGISTRY
        return REGISTRY.snapshot()

    @pytest.fixture(autouse=True)
    def _fresh(self):
        from omero_ms_image_region_tpu.utils import telemetry
        from omero_ms_image_region_tpu.utils.stopwatch import REGISTRY
        REGISTRY.reset()
        telemetry.TRACES.reset()
        yield
        REGISTRY.reset()
        telemetry.TRACES.reset()

    @pytest.mark.parametrize("route", ["jpeg", "packed"])
    def test_a_slot_is_its_three_parts(self, route):
        from omero_ms_image_region_tpu.utils.stopwatch import REGISTRY

        settings = _settings()
        tiles = [self._tile(s) for s in (31, 32, 33)]

        async def main():
            batcher = BatchingRenderer(linger_ms=5.0,
                                       buckets=((64, 64),))

            async def three():
                if route == "jpeg":
                    return await asyncio.gather(*(
                        batcher.render_jpeg(t, settings, 85, 32, 32)
                        for t in tiles))
                return await asyncio.gather(*(
                    batcher.render(t, settings) for t in tiles))
            try:
                await three()       # compiles; the series' first records
                # First-tile-out answers before the group has settled.
                while batcher._inflight:
                    await asyncio.sleep(0.005)
                REGISTRY.reset()
                return await three()
            finally:
                await batcher.close()

        assert len(run(main())) == 3
        spans = self._spans()
        groups = spans["batcher.group"]["count"]
        assert 1 <= groups <= 3
        for name in ("batcher.slot", "batcher.slotStart",
                     "batcher.settleLag", "batcher.slotWait"):
            assert spans[name]["count"] == groups, name
        parts = sum(spans[name]["total_ms"] for name in (
            "batcher.slotStart", "batcher.group", "batcher.settleLag"))
        slot = spans["batcher.slot"]["total_ms"]
        # What lies between the parts is a stamp and a span's own
        # record: microseconds (2 % more on a machine that is shared).
        slack = 0.5 * groups + 0.02 * slot
        assert parts - slack <= slot <= parts + slack
        # One inGroup a request, never longer than its group's slot.
        assert spans["batcher.inGroup"]["count"] == 3
        assert spans["batcher.inGroup"]["max_ms"] <= \
            spans["batcher.slot"]["max_ms"] + 0.5

    @pytest.mark.parametrize("path", ["lane_cap", "dead_member"])
    def test_a_slot_given_back_records_no_slot_span(self, path,
                                                    monkeypatch):
        """The dispatcher takes a slot and gives it straight back under
        an engaged ``cap_lanes`` step, and when everything it popped
        was already settled: neither is a slot's turn."""
        import threading

        from omero_ms_image_region_tpu.ops import jpegenc

        entered, release = threading.Event(), threading.Event()
        real_finish = jpegenc.finish_wire_to_jpegs

        def finish(*args, **kw):
            first = not entered.is_set()
            entered.set()
            if first:
                release.wait(timeout=60)
            return real_finish(*args, **kw)

        monkeypatch.setattr(jpegenc, "finish_wire_to_jpegs", finish)
        settings = _settings()
        a, b = self._tile(41), self._tile(42)

        async def main():
            batcher = BatchingRenderer(
                linger_ms=0.0, buckets=((64, 64),),
                pipeline_depth=2 if path == "lane_cap" else 1)
            if path == "lane_cap":
                batcher.set_lane_cap(1)
            try:
                first = asyncio.ensure_future(
                    batcher.render_jpeg(a, settings, 85, 32, 32))
                assert await asyncio.to_thread(entered.wait, 60)
                second = asyncio.ensure_future(
                    batcher.render_jpeg(b, settings, 85, 32, 32))
                # The dispatcher meets the second request while the
                # first group holds its slot.
                await asyncio.sleep(0.1)
                if path == "dead_member":
                    second.cancel()
                held = self._spans().get("batcher.slot",
                                         {}).get("count", 0)
                release.set()
                await first
                if path == "lane_cap":
                    await second
                else:
                    await asyncio.gather(second, return_exceptions=True)
                    # Let the dispatcher pop the corpse.
                    await asyncio.sleep(0.1)
                return held
            finally:
                release.set()
                await batcher.close()

        assert run(main()) == 0     # nothing recorded while it was held
        spans = self._spans()
        groups = 2 if path == "lane_cap" else 1
        assert spans["batcher.group"]["count"] == groups
        assert spans["batcher.slot"]["count"] == groups
        assert spans["batcher.slotWait"]["count"] == groups
        if path == "dead_member":
            assert spans["batcher.queueWait.cancelled"]["count"] == 1
            assert spans["batcher.inGroup"]["count"] == 1

    def test_an_early_settled_tile_leaves_its_group_first(
            self, monkeypatch):
        """First-tile-out: tile 0's answer is stamped when its bytes
        exist, the last tile's at the group's settle."""
        import time

        from omero_ms_image_region_tpu.ops import jpegenc
        from omero_ms_image_region_tpu.utils import telemetry

        real_finish = jpegenc.finish_wire_to_jpegs

        def finish(wire, on_tile=None, **kw):
            jpegs = real_finish(wire, **kw)
            on_tile(0, jpegs[0])
            time.sleep(0.05)            # the rest of the entropy tail
            return jpegs

        monkeypatch.setattr(jpegenc, "finish_wire_to_jpegs", finish)
        settings = _settings()

        async def one(batcher, tid, seed):
            with telemetry.trace_scope(tid):
                return await batcher.render_jpeg(
                    self._tile(seed), settings, 85, 32, 32)

        async def main():
            batcher = BatchingRenderer(linger_ms=20.0,
                                       buckets=((64, 64),))
            try:
                return await asyncio.gather(
                    one(batcher, "early", 51), one(batcher, "late", 52))
            finally:
                await batcher.close()

        assert all(body[:2] == b"\xff\xd8" for body in run(main()))
        assert self._spans()["batcher.group"]["count"] == 1
        early = telemetry.TRACES.get_or_create("early")
        late = telemetry.TRACES.get_or_create("late")

        def only(trace, name):
            [span] = [s for s in trace.spans if s["name"] == name]
            return span

        first, last = (only(t, "batcher.inGroup") for t in (early, late))
        assert first["tiles"] == last["tiles"] == 2
        assert last["dur_ms"] - first["dur_ms"] >= 40.0
        assert late.t_answered - early.t_answered >= 0.04
        # Where the queue wait ended, inGroup began.
        for trace in (early, late):
            wait = only(trace, "batcher.queueWait")
            in_group = only(trace, "batcher.inGroup")
            assert in_group["start_ms"] == pytest.approx(
                wait["start_ms"] + wait["dur_ms"], abs=0.002)

    @pytest.mark.parametrize("route", ["jpeg", "packed"])
    def test_a_slots_turn_goes_on_no_members_trace(self, route):
        """The turn is the group's: its spans and the dispatcher's wait
        for the slot are series only (a copy a member is a record a
        request on the loop's thread); a member's trace keeps its own
        two phases in the batcher and the group's span."""
        from omero_ms_image_region_tpu.utils import telemetry

        settings = _settings()
        tids = [f"slot-{route}-{n}" for n in range(3)]

        async def one(batcher, tid, seed):
            with telemetry.trace_scope(tid):
                if route == "jpeg":
                    return await batcher.render_jpeg(
                        self._tile(seed), settings, 85, 32, 32)
                return await batcher.render(self._tile(seed), settings)

        async def main():
            batcher = BatchingRenderer(linger_ms=20.0,
                                       buckets=((64, 64),))
            try:
                return await asyncio.gather(*(
                    one(batcher, tid, 60 + n)
                    for n, tid in enumerate(tids)))
            finally:
                await batcher.close()

        assert len(run(main())) == 3
        spans = self._spans()
        assert spans["batcher.group"]["count"] == 1
        for part in ("batcher.slot", "batcher.slotStart",
                     "batcher.settleLag", "batcher.slotWait"):
            assert spans[part]["count"] == 1, part
        names = {tid: [s["name"] for s in
                       telemetry.TRACES.get_or_create(tid).spans]
                 for tid in tids}
        assert not any(name.startswith("batcher.slot")
                       for n in names.values() for name in n)
        for n in names.values():
            assert n.count("batcher.queueWait") == 1
            assert n.count("batcher.inGroup") == 1
            assert n.count("batcher.group") == 1


# ------------------ a group's tiles coded side by side (PR 37)

def test_a_groups_members_are_answered_from_the_coding_threads(
        monkeypatch, coding_pool):
    """Six requests, one group: its tiles are coded by the group's
    thread and the pool's, each member's future is settled from the
    thread that coded its tile (first-tile-out crosses threads with
    ``call_soon_threadsafe``), every answer is the direct renderer's,
    and ``/metrics`` says the tail was pooled."""
    import threading

    from omero_ms_image_region_tpu.ops import jpegenc
    from omero_ms_image_region_tpu.utils import telemetry

    real = jpegenc.sparse_run_encoder()
    threads, met = set(), threading.Event()

    def meeting_encoder():
        def encode(rows, dims, quality, cap):
            # The first thread waits here for a second to bring a run.
            threads.add(threading.get_ident())
            if len(threads) > 1:
                met.set()
            met.wait(10.0)
            return real(rows, dims, quality, cap)
        return encode

    monkeypatch.setattr(jpegenc, "sparse_run_encoder", meeting_encoder)
    settings = _settings()
    rng = np.random.default_rng(37)
    tiles = [rng.integers(0, 60000, size=(3, 64, 64)).astype(np.float32)
             for _ in range(6)]

    async def main():
        batcher = BatchingRenderer(max_batch=8, linger_ms=50.0,
                                   buckets=((64, 64),))
        direct = Renderer()
        try:
            got = await asyncio.gather(*[
                batcher.render_jpeg(t, settings, 85, 64, 64)
                for t in tiles])
            want = [await direct.render_jpeg(t, settings, 85, 64, 64)
                    for t in tiles]
        finally:
            await batcher.close()
        return got, want, batcher.batches_dispatched

    got, want, dispatched = run(main())
    assert dispatched == 1 and got == want
    assert len(threads) > 1
    # The direct renderer's six tiles were groups of one, in line.
    assert coding_pool.TILES == {"pooled": 6, "inline": 6}
    text = telemetry.finalize_exposition(
        telemetry.device_metric_lines(None))
    assert 'imageregion_entropy_tiles_total{path="pooled"} 6\n' in text
    assert 'imageregion_entropy_tiles_total{path="inline"} 6\n' in text
    assert "# TYPE imageregion_entropy_tiles_total counter" in text
    assert text.count("# HELP imageregion_entropy_tiles_total") == 1


def test_a_batcher_tells_the_coding_pool_its_depth(monkeypatch):
    import os

    from omero_ms_image_region_tpu.utils import entropypool

    monkeypatch.setattr(entropypool, "_GROUP_THREADS", 1)
    monkeypatch.setattr(os, "sched_getaffinity",
                        lambda pid: set(range(13)))

    async def main():
        batcher = BatchingRenderer(pipeline_depth=4)
        await batcher.close()

    run(main())
    assert entropypool.pool_threads() == 8
