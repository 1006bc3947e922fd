"""Adaptive wire-engine controller: EWMA engine flips on injected
bandwidth signals, hysteresis, idle/steady-state re-probes, and the
batcher's queue-pressure batch growth (VERDICT r3 item 1)."""

import asyncio

import numpy as np
import pytest

from omero_ms_image_region_tpu.utils.adaptive import (
    MIN_OBSERVATION_BYTES, AdaptiveEngine)


class FakeClock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t


def mb(rate_mb_s, nbytes=4 << 20):
    """(nbytes, seconds) pair observing the given rate."""
    return nbytes, nbytes / 1e6 / rate_mb_s


class TestAdaptiveEngine:
    def test_flips_to_huffman_when_link_craters(self):
        ctrl = AdaptiveEngine(initial_rate_mb_s=100.0,
                              probe=lambda: 100.0)
        assert ctrl.engine == "sparse"
        for _ in range(8):
            ctrl.observe_fetch(*mb(3.0))
        assert ctrl.engine == "huffman"
        assert ctrl.switches == 1

    def test_flips_back_on_probed_recovery(self):
        clock = FakeClock()
        probes = []

        def probe():
            probes.append(clock.t)
            return 200.0

        ctrl = AdaptiveEngine(initial_rate_mb_s=3.0, probe=probe,
                              clock=clock, reprobe_interval_s=20.0)
        assert ctrl.engine == "huffman"
        # Steady huffman traffic: small fetches carry no bandwidth
        # signal, so recovery is only observable via the re-probe.
        assert ctrl.current() == "huffman"     # not yet due
        clock.t += 21.0
        assert ctrl.current() == "sparse"      # probed 200 MB/s
        assert probes and ctrl.switches == 1

    def test_hysteresis_holds_inside_band(self):
        ctrl = AdaptiveEngine(initial_rate_mb_s=100.0,
                              crossover_mb_s=12.0, hysteresis=0.25)
        assert ctrl.engine == "sparse"
        # 11 MB/s is below the crossover but inside the +-25% band.
        for _ in range(20):
            ctrl.observe_fetch(*mb(11.0))
        assert ctrl.engine == "sparse"
        for _ in range(20):
            ctrl.observe_fetch(*mb(8.0))       # clearly below the band
        assert ctrl.engine == "huffman"

    def test_small_fetches_carry_no_signal(self):
        ctrl = AdaptiveEngine(initial_rate_mb_s=100.0)
        ctrl.observe_fetch(MIN_OBSERVATION_BYTES - 1, 10.0)  # ~0 MB/s
        assert ctrl.engine == "sparse"
        assert ctrl.rate_mb_s == 100.0

    def test_idle_gap_triggers_reprobe(self):
        clock = FakeClock()
        rates = [3.0]
        ctrl = AdaptiveEngine(initial_rate_mb_s=100.0,
                              probe=lambda: rates[0], clock=clock,
                              idle_reprobe_s=30.0)
        assert ctrl.current() == "sparse"      # fresh, no probe
        clock.t += 31.0
        assert ctrl.current() == "huffman"     # idle probe saw 3 MB/s

    def test_failed_probe_keeps_engine(self):
        clock = FakeClock()

        def probe():
            raise OSError("link down")

        ctrl = AdaptiveEngine(initial_rate_mb_s=100.0, probe=probe,
                              clock=clock, idle_reprobe_s=30.0)
        clock.t += 31.0
        assert ctrl.current() == "sparse"


class TestBatcherIntegration:
    def test_fetch_observer_feeds_controller(self):
        """The jpegenc fetchers report wire fetches to the observer."""
        from omero_ms_image_region_tpu.ops import jpegenc

        seen = []
        jpegenc.set_fetch_observer(
            lambda n, s, c=False: seen.append((n, s, c)))
        try:
            f = jpegenc.SparseWireFetcher(256, 256, cap=1024)
            width = f.width
            buf = np.zeros((2, width), np.uint8)
            f.fetch(buf)
            assert seen and seen[0][0] > 0
            # The first fetch of a dispatched program is flagged as
            # compute-conflated (its rate is only a lower bound).
            assert seen[0][2] is True
        finally:
            jpegenc.set_fetch_observer(None)

    def test_batcher_consults_controller_per_group(self, monkeypatch):
        """An engine flip between groups changes the dispatched wire
        format (the injected-signal end-to-end check)."""
        from omero_ms_image_region_tpu.flagship import flagship_rdef
        from omero_ms_image_region_tpu.ops import jpegenc
        from omero_ms_image_region_tpu.ops.render import pack_settings
        from omero_ms_image_region_tpu.server.batcher import (
            BatchingRenderer)

        engines_used = []
        real = jpegenc.render_batch_to_jpeg

        def spying(*args, **kwargs):
            engines_used.append(kwargs.get("engine"))
            return real(*args, **kwargs)

        monkeypatch.setattr(jpegenc, "render_batch_to_jpeg", spying)

        # Huge re-probe interval: on a COLD compilation cache the first
        # render takes tens of seconds, and the huffman steady-state
        # re-probe (stubbed at a healthy 100 MB/s) would flip the
        # engine back before the second assertion.  Re-probing has its
        # own tests; this one is about per-group consultation.
        ctrl = AdaptiveEngine(initial_rate_mb_s=100.0,
                              probe=lambda: 100.0,
                              reprobe_interval_s=1e9,
                              idle_reprobe_s=1e9)
        r = BatchingRenderer(max_batch=2, linger_ms=0.0,
                             jpeg_engine="sparse",
                             engine_controller=ctrl)
        rdef = flagship_rdef(1)
        settings = pack_settings(rdef)
        raw = np.random.default_rng(0).uniform(
            0, 60000, (1, 64, 64)).astype(np.float32)

        async def one():
            return await r.render_jpeg(raw, settings, 80, 64, 64)

        loop = asyncio.new_event_loop()
        try:
            body = loop.run_until_complete(one())
            assert body[:2] == b"\xff\xd8"
            assert engines_used[-1] == "sparse"
            # Inject a cratered link; the next group must go huffman.
            for _ in range(8):
                ctrl.observe_fetch(*mb(3.0))
            body = loop.run_until_complete(one())
            assert body[:2] == b"\xff\xd8"
            assert engines_used[-1] == "huffman"
        finally:
            loop.run_until_complete(r.close())
            loop.close()

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


class TestConflatedSamples:
    def test_low_conflated_reading_never_flips_directly(self):
        probes = []
        ctrl = AdaptiveEngine(initial_rate_mb_s=100.0,
                              probe=lambda: probes.append(1) or 3.0)
        for _ in range(3):
            ctrl.observe_fetch(*mb(2.0), conflated=True)
        assert ctrl.engine == "sparse"       # no direct flip
        assert ctrl.rate_mb_s == 100.0       # EWMA untouched

    def test_suspicion_streak_forces_probe(self):
        clock = FakeClock()
        probes = []

        def probe():
            probes.append(clock.t)
            return 3.0

        ctrl = AdaptiveEngine(initial_rate_mb_s=100.0, probe=probe,
                              clock=clock)
        for _ in range(ctrl.SUSPECT_STREAK):
            ctrl.observe_fetch(*mb(2.0), conflated=True)
        assert ctrl.current() == "huffman"   # probe saw the real 3 MB/s
        assert len(probes) == 1

    def test_high_conflated_reading_counts(self):
        ctrl = AdaptiveEngine(initial_rate_mb_s=3.0,
                              probe=lambda: 3.0)
        assert ctrl.engine == "huffman"
        for _ in range(8):
            # Lower bound 100 MB/s: the link carried at least that.
            ctrl.observe_fetch(*mb(100.0), conflated=True)
        assert ctrl.engine == "sparse"


class TestFlipUnderLoad:
    def test_engine_flips_mid_load_are_safe(self):
        """The controller flipping engines WHILE concurrent groups are
        in flight (pipeline_depth > 1, worker threads reading
        ``current()`` racily) must never corrupt output: every JPEG
        decodes, whatever engine its group drew."""
        from omero_ms_image_region_tpu import codecs
        from omero_ms_image_region_tpu.flagship import flagship_rdef
        from omero_ms_image_region_tpu.ops.render import pack_settings
        from omero_ms_image_region_tpu.server.batcher import (
            BatchingRenderer)

        # Stubbed-probe re-probes disabled for the same cold-cache
        # reason as test_batcher_consults_controller_per_group; the
        # flipper task is the only rate source.
        ctrl = AdaptiveEngine(initial_rate_mb_s=100.0,
                              probe=lambda: 100.0,
                              reprobe_interval_s=1e9,
                              idle_reprobe_s=1e9)
        r = BatchingRenderer(max_batch=4, linger_ms=0.5,
                             jpeg_engine="sparse",
                             engine_controller=ctrl,
                             pipeline_depth=3)
        rdef = flagship_rdef(2)
        settings = pack_settings(rdef)
        rng = np.random.default_rng(9)
        tiles = [rng.uniform(0, 60000, (2, 48, 48)).astype(np.float32)
                 for _ in range(24)]

        async def flipper():
            # Alternate cratered/recovered signals while renders run.
            for k in range(12):
                rate = 3.0 if k % 2 == 0 else 100.0
                for _ in range(8):
                    ctrl.observe_fetch(*mb(rate))
                await asyncio.sleep(0.002)

        async def main():
            jobs = [r.render_jpeg(t, settings, 80, 48, 48)
                    for t in tiles]
            out, _ = await asyncio.gather(asyncio.gather(*jobs),
                                          flipper())
            return out

        loop = asyncio.new_event_loop()
        try:
            bodies = loop.run_until_complete(main())
        finally:
            loop.run_until_complete(r.close())
            loop.close()
        assert len(bodies) == 24
        assert ctrl.switches >= 2   # flips really happened mid-run
        for b in bodies:
            rgba = codecs.decode_to_rgba(b)
            assert rgba.shape[:2] == (48, 48)
