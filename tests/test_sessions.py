"""Session-aware serving: the viewport model, per-session fairness
token buckets, tiered QoS dequeue, and the predictive budgeted
prefetcher (services.viewport / server.admission / parallel.fleet /
services.prefetch).

The session identity under test everywhere is
``ctx.omero_session_key`` — the ONE identity the session middleware
resolves, the fleet single-flight folds (PR 8), the token buckets
meter, and the viewport tracker models.  A dedicated test asserts the
buckets and the single-flight read the SAME ctx attribute (no second
session-resolution path).
"""

import asyncio
import threading

import numpy as np
import pytest

from omero_ms_image_region_tpu.server import pressure
from omero_ms_image_region_tpu.server.admission import (
    AdmissionController, SessionTokenBuckets)
from omero_ms_image_region_tpu.server.config import AppConfig
from omero_ms_image_region_tpu.server.ctx import ImageRegionCtx
from omero_ms_image_region_tpu.server.errors import OverloadedError
from omero_ms_image_region_tpu.services.viewport import (
    TilePrediction, ViewportTracker)
from omero_ms_image_region_tpu.utils import telemetry


@pytest.fixture(autouse=True)
def _fresh():
    telemetry.reset()
    pressure.uninstall()
    yield
    pressure.uninstall()
    telemetry.reset()


class FakeClock:
    def __init__(self, t=1000.0):
        self.t = t

    def __call__(self):
        return self.t


# ---------------------------------------------------- viewport model

class TestViewportTracker:
    def _pan(self, tracker, key, points, image_id=1, resolution=0):
        for x, y in points:
            tracker.observe(key, image_id, 0, 0, resolution, x, y)

    def test_pan_velocity_is_median_of_deltas(self):
        tracker = ViewportTracker(clock=FakeClock())
        self._pan(tracker, "s", [(0, 0), (1, 0), (2, 0), (3, 0)])
        assert tracker.velocity("s") == (1, 0)

    def test_predict_extrapolates_lookahead_steps(self):
        tracker = ViewportTracker(clock=FakeClock())
        self._pan(tracker, "s", [(2, 5), (3, 5), (4, 5)])
        preds = tracker.predict("s", lookahead=2)
        assert [(p.x, p.y, p.step) for p in preds] == [
            (5, 5, 1), (6, 5, 2)]
        assert all(p.resolution == 0 and p.z == 0 and p.t == 0
                   and p.image_id == 1 for p in preds)

    def test_diagonal_and_negative_velocity(self):
        tracker = ViewportTracker(clock=FakeClock())
        self._pan(tracker, "s", [(5, 5), (4, 6), (3, 7)])
        assert tracker.velocity("s") == (-1, 1)
        preds = tracker.predict("s", lookahead=2)
        assert [(p.x, p.y) for p in preds] == [(2, 8), (1, 9)]

    def test_prediction_stops_at_the_lattice_edge(self):
        tracker = ViewportTracker(clock=FakeClock())
        self._pan(tracker, "s", [(1, 0), (0, 0)])   # heading off-plane
        assert tracker.predict("s", lookahead=3) == []

    def test_no_trajectory_means_no_predictions(self):
        tracker = ViewportTracker(clock=FakeClock())
        tracker.observe("s", 1, 0, 0, 0, 3, 3)
        assert tracker.velocity("s") is None
        assert tracker.predict("s") == []
        assert tracker.predict("never-seen") == []

    def test_image_switch_breaks_the_trajectory(self):
        tracker = ViewportTracker(clock=FakeClock())
        self._pan(tracker, "s", [(0, 0), (1, 0)], image_id=1)
        tracker.observe("s", 2, 0, 0, 0, 7, 7)   # teleport: new image
        assert tracker.velocity("s") is None

    def test_stale_observations_never_vote(self):
        clock = FakeClock()
        tracker = ViewportTracker(clock=clock)
        self._pan(tracker, "s", [(0, 0), (1, 0)])
        clock.t += 60.0                      # the viewer had a coffee
        assert tracker.velocity("s") is None

    def test_resume_after_pause_does_not_vote_the_teleport_delta(self):
        """A pause then a resume at a distant tile: the single
        (stale_prev, fresh_cur) pair spanning the pause must not
        become the lone velocity vote — the intra-pair gap is as
        disqualifying as absolute staleness."""
        clock = FakeClock()
        tracker = ViewportTracker(clock=clock)
        self._pan(tracker, "s", [(0, 0), (1, 0)])
        clock.t += 60.0
        tracker.observe("s", 1, 0, 0, 0, 35, 0)    # teleport resume
        assert tracker.velocity("s") is None       # no (34, 0) vote
        tracker.observe("s", 1, 0, 0, 0, 36, 0)
        # Two FRESH observations re-establish the real velocity.
        assert tracker.velocity("s") == (1, 0)

    def test_zoom_in_predicts_the_four_children(self):
        tracker = ViewportTracker(clock=FakeClock())
        tracker.observe("s", 1, 0, 0, 2, 3, 1)
        tracker.observe("s", 1, 0, 0, 1, 3, 1)   # index DOWN = zoom in
        assert tracker.zoom_direction("s") == -1
        preds = tracker.predict("s")
        assert {(p.resolution, p.x, p.y) for p in preds} == {
            (0, 6, 2), (0, 7, 2), (0, 6, 3), (0, 7, 3)}

    def test_zoom_out_predicts_the_parent(self):
        tracker = ViewportTracker(clock=FakeClock())
        tracker.observe("s", 1, 0, 0, 0, 6, 2)
        tracker.observe("s", 1, 0, 0, 1, 6, 2)
        assert tracker.zoom_direction("s") == 1
        preds = tracker.predict("s", max_level=4)
        assert {(p.resolution, p.x, p.y) for p in preds} == {
            (2, 3, 1)}

    def test_zoom_past_max_level_predicts_nothing(self):
        tracker = ViewportTracker(clock=FakeClock())
        tracker.observe("s", 1, 0, 0, 0, 2, 2)
        tracker.observe("s", 1, 0, 0, 1, 2, 2)
        assert tracker.predict("s", max_level=1) == []

    def test_lru_bound_evicts_oldest_session(self):
        tracker = ViewportTracker(max_sessions=2, clock=FakeClock())
        self._pan(tracker, "a", [(0, 0), (1, 0)])
        self._pan(tracker, "b", [(0, 0), (1, 0)])
        self._pan(tracker, "c", [(0, 0), (1, 0)])
        assert len(tracker) == 2
        assert tracker.evictions == 1
        assert tracker.velocity("a") is None       # evicted
        assert tracker.velocity("c") == (1, 0)
        assert telemetry.SESSIONS.evicted == 1
        assert telemetry.SESSIONS.tracked == 2

    def test_sessionless_traffic_shares_the_anonymous_state(self):
        tracker = ViewportTracker(clock=FakeClock())
        tracker.observe(None, 1, 0, 0, 0, 0, 0)
        tracker.observe("", 1, 0, 0, 0, 1, 0)
        assert len(tracker) == 1
        assert tracker.velocity(None) == (1, 0)

    def test_constructor_validation(self):
        with pytest.raises(ValueError):
            ViewportTracker(max_sessions=0)
        with pytest.raises(ValueError):
            ViewportTracker(history=1)

    def test_predictions_are_frozen_value_objects(self):
        p = TilePrediction(1, 0, 0, 0, 2, 3)
        with pytest.raises(Exception):
            p.x = 9


# ------------------------------------------- per-session token buckets

class TestSessionTokenBuckets:
    def test_burst_then_refused_then_refills(self):
        clock = FakeClock()
        buckets = SessionTokenBuckets(refill_per_s=2.0, burst=3.0,
                                      clock=clock)
        assert all(buckets.try_take("s") for _ in range(3))
        assert buckets.try_take("s") is False
        assert buckets.refused_total == 1
        clock.t += 1.0                       # refills 2 tokens
        assert buckets.try_take("s")
        assert buckets.try_take("s")
        assert buckets.try_take("s") is False

    def test_retry_after_reports_the_honest_deficit(self):
        clock = FakeClock()
        buckets = SessionTokenBuckets(refill_per_s=2.0, burst=1.0,
                                      clock=clock)
        assert buckets.try_take("s")
        assert buckets.retry_after_s("s") == pytest.approx(0.5)
        # A 4-token bulk draw against an empty bucket: 2 s at 2/s.
        assert buckets.retry_after_s("s", cost=4.0) == \
            pytest.approx(2.0)

    def test_bulk_cost_drains_faster(self):
        buckets = SessionTokenBuckets(refill_per_s=1.0, burst=8.0,
                                      bulk_cost=4.0,
                                      clock=FakeClock())
        assert buckets.try_take("s", cost=buckets.bulk_cost)
        assert buckets.try_take("s", cost=buckets.bulk_cost)
        assert buckets.try_take("s", cost=buckets.bulk_cost) is False
        # The same budget would have served 8 interactive tiles.
        assert all(buckets.try_take("t") for _ in range(8))

    def test_sessions_are_isolated(self):
        buckets = SessionTokenBuckets(refill_per_s=1.0, burst=1.0,
                                      clock=FakeClock())
        assert buckets.try_take("hog")
        assert buckets.try_take("hog") is False
        assert buckets.try_take("calm")    # untouched by the hog

    def test_anonymous_traffic_shares_one_bucket(self):
        buckets = SessionTokenBuckets(refill_per_s=1.0, burst=2.0,
                                      clock=FakeClock())
        assert buckets.try_take(None)
        assert buckets.try_take("")
        assert buckets.try_take(None) is False

    def test_lru_bound_evicted_session_restarts_full(self):
        buckets = SessionTokenBuckets(refill_per_s=0.001, burst=1.0,
                                      max_sessions=2,
                                      clock=FakeClock())
        assert buckets.try_take("a")
        assert buckets.try_take("a") is False
        buckets.try_take("b")
        buckets.try_take("c")                # evicts "a"
        assert len(buckets) == 2
        assert buckets.try_take("a")         # full burst again

    def test_constructor_validation(self):
        for kw in ({"refill_per_s": 0.0}, {"burst": 0.5},
                   {"max_sessions": 0}, {"bulk_cost": 0.5}):
            with pytest.raises(ValueError):
                SessionTokenBuckets(**{"refill_per_s": 1.0,
                                       "burst": 1.0, **kw})


# ------------------------------------------------- fairness admission

def _tile_ctx(session=None):
    ctx = ImageRegionCtx.from_params({
        "imageId": "1", "theZ": "0", "theT": "0",
        "tile": "0,0,0,64,64", "format": "jpeg", "m": "c",
        "c": "1|0:60000$FF0000"})
    ctx.omero_session_key = session
    return ctx


def _bulk_ctx(session=None):
    ctx = ImageRegionCtx.from_params({
        "imageId": "1", "theZ": "0", "theT": "0",
        "format": "jpeg", "m": "c", "c": "1|0:60000$FF0000"})
    ctx.omero_session_key = session
    return ctx


class TestFairnessAdmission:
    def _admission(self, **bucket_kw):
        clock = bucket_kw.pop("clock", FakeClock())
        buckets = SessionTokenBuckets(
            refill_per_s=bucket_kw.pop("refill_per_s", 1.0),
            burst=bucket_kw.pop("burst", 2.0),
            clock=clock, **bucket_kw)
        return AdmissionController(max_queue=100,
                                   session_buckets=buckets), clock

    def test_over_budget_session_sheds_with_fairness_reason(self):
        adm, _ = self._admission()
        adm.release(adm.admit(_tile_ctx("hog")))
        adm.release(adm.admit(_tile_ctx("hog")))
        with pytest.raises(OverloadedError) as ei:
            adm.admit(_tile_ctx("hog"))
        # Retry-After covers the bucket's actual deficit.
        assert ei.value.retry_after_s >= 1.0
        assert telemetry.RESILIENCE.shed.get("fairness") == 1
        assert telemetry.QOS.shed.get("interactive") == 1
        kinds = [e["kind"] for e in telemetry.FLIGHT.snapshot()]
        assert "qos.shed" in kinds
        # A fairness shed never claims a slot.
        assert adm.inflight == 0

    def test_other_sessions_admission_is_untouched(self):
        adm, _ = self._admission()
        adm.release(adm.admit(_tile_ctx("hog")))
        adm.release(adm.admit(_tile_ctx("hog")))
        with pytest.raises(OverloadedError):
            adm.admit(_tile_ctx("hog"))
        # The global bound never tightened against anyone else.
        adm.release(adm.admit(_tile_ctx("calm")))

    def test_bulk_requests_draw_bulk_cost(self):
        adm, _ = self._admission(burst=4.0, bulk_cost=4.0)
        adm.release(adm.admit(_bulk_ctx("exporter")))
        with pytest.raises(OverloadedError):
            adm.admit(_bulk_ctx("exporter"))
        assert telemetry.QOS.shed.get("bulk") == 1

    def test_global_shed_refunds_the_session_tokens(self):
        """Admission granted by the fairness gate but refused by the
        GLOBAL depth bound must refund the debit: a well-behaved
        retrier during global overload is never drained into
        misattributed \"fairness\" sheds."""
        buckets = SessionTokenBuckets(refill_per_s=0.001, burst=2.0,
                                      clock=FakeClock())
        adm = AdmissionController(max_queue=1,
                                  session_buckets=buckets)
        t = adm.admit(_tile_ctx("viewer"))     # fills the queue
        for _ in range(5):                     # far past the burst
            with pytest.raises(OverloadedError):
                adm.admit(_tile_ctx("viewer"))
        # Every global shed refunded: no fairness shed ever fired...
        assert telemetry.RESILIENCE.shed.get("fairness") is None
        assert telemetry.RESILIENCE.shed.get("queue-full") == 5
        adm.release(t)
        # ...and the bucket still covers the burst minus the one
        # genuinely admitted render.
        adm.release(adm.admit(_tile_ctx("viewer")))
        with pytest.raises(OverloadedError):   # now truly over budget
            adm.admit(_tile_ctx("viewer"))
        assert telemetry.RESILIENCE.shed.get("fairness") == 1

    def test_ctx_none_preserves_anonymous_global_behavior(self):
        adm, _ = self._admission()
        for _ in range(10):                  # far past any burst
            adm.release(adm.admit())
        assert adm.shed_total == 0

    def test_no_buckets_means_sessions_unmetered(self):
        adm = AdmissionController(max_queue=100)
        for _ in range(10):
            adm.release(adm.admit(_tile_ctx("hog")))
        assert adm.shed_total == 0


# --------------------------------------------- weighted QoS dequeue

class TestQosDequeue:
    def _queue(self, weight, arrivals):
        """A _MemberQueue holding ``arrivals`` ('i'/'b' chars)."""
        from omero_ms_image_region_tpu.parallel.fleet import (
            _MemberQueue, _Work)
        queue = _MemberQueue(qos_weight=weight)
        for i, cls in enumerate(arrivals):
            ctx = (_bulk_ctx() if cls == "b"
                   else _tile_ctx())
            ctx.seq = i
            work = _Work(ctx, asyncio.Future(
                loop=asyncio.new_event_loop()), "m0", None)
            queue.append(work)
        return queue

    def _drain(self, queue):
        out = []
        while queue:
            work = queue.popleft()
            out.append("b" if work.bulk else "i")
        return out

    def test_weight_zero_is_plain_fifo(self):
        queue = self._queue(0, "bbiii")
        assert self._drain(queue) == list("bbiii")
        assert telemetry.QOS.jumps == 0

    def test_interactive_jumps_bulk_backlog(self):
        queue = self._queue(4, "bbiii")
        assert self._drain(queue) == list("iiibb")
        assert telemetry.QOS.jumps == 3
        assert telemetry.QOS.dequeued == {"interactive": 3, "bulk": 2}

    def test_bulk_cannot_starve_past_the_weight(self):
        # 6 interactive vs 2 bulk at weight 2: after every 2
        # interactive pops one bulk pops.
        queue = self._queue(2, "bbiiiiii")
        assert self._drain(queue) == list("iibiibii")

    def test_single_class_resets_the_quota(self):
        queue = self._queue(2, "iii")
        assert self._drain(queue) == list("iii")
        assert telemetry.QOS.jumps == 0

    def test_bulk_work_is_never_stealable(self):
        queue = self._queue(4, "bib")
        assert queue.steal_depth() == 1
        work = queue.steal_pop()
        assert work is not None and work.bulk is False
        assert queue.steal_depth() == 0
        assert queue.steal_pop() is None
        assert len(queue) == 2               # both bulk units remain

    def test_arrival_order_preserved_within_each_class(self):
        queue = self._queue(1, "ibib")
        drained = []
        while queue:
            work = queue.popleft()
            drained.append((("b" if work.bulk else "i"),
                            work.ctx.seq))
        assert drained == [("i", 0), ("b", 1), ("i", 2), ("b", 3)]


# ----------------------------- one session identity across the stack

class TestSessionKeyPlumbingUnderFleet:
    """PR 8's single-flight hardening resolves the caller's session
    once (``ctx.omero_session_key``); the token buckets must key on
    the SAME identity — a coalesced follower pays no tokens, two
    sessions with identical render params never share a budget."""

    def _handler(self, buckets):
        from omero_ms_image_region_tpu.parallel.fleet import (
            FleetImageHandler)
        from omero_ms_image_region_tpu.server.singleflight import (
            SingleFlight)

        dispatched = []

        class FakeRouter:
            device_lanes = 2

            async def dispatch(self, ctx):
                dispatched.append(ctx.omero_session_key)
                await asyncio.sleep(0.01)
                return b"pixels"

            def healthy_members(self):
                return ["m0"]

        admission = AdmissionController(max_queue=100,
                                        session_buckets=buckets)
        # s=None: the proxy-fleet posture whose single-flight key
        # FOLDS the session (per-session leaders).
        return FleetImageHandler(FakeRouter(),
                                 single_flight=SingleFlight(),
                                 admission=admission), dispatched

    def test_every_caller_pays_its_own_token_before_coalescing(self):
        buckets = SessionTokenBuckets(refill_per_s=0.001, burst=3.0,
                                      clock=FakeClock())
        handler, dispatched = self._handler(buckets)

        async def scenario():
            # Two CONCURRENT identical same-session requests coalesce
            # onto one leader — ONE dispatch, but the fairness gate
            # runs PER CALLER (before single-flight, like the ACL
            # gate): each request pays its own token, so coalescing
            # never launders budget.
            a, b = await asyncio.gather(
                handler.render_image_region(_tile_ctx("viewer")),
                handler.render_image_region(_tile_ctx("viewer")))
            assert a == b == b"pixels"

        asyncio.run(scenario())
        assert len(dispatched) == 1
        assert buckets.taken_total == 2
        # Both debits hit the SAME bucket the next solo request draws
        # from: one token left of the burst of three.
        assert buckets.try_take("viewer")
        assert buckets.try_take("viewer") is False

    def test_global_shed_through_the_fleet_refunds_every_caller(self):
        from omero_ms_image_region_tpu.parallel.fleet import (
            FleetImageHandler)

        class FullRouter:
            device_lanes = 1

            async def dispatch(self, ctx):   # pragma: no cover
                raise AssertionError("never admitted")

            def healthy_members(self):
                return ["m0"]

        buckets = SessionTokenBuckets(refill_per_s=0.001, burst=2.0,
                                      clock=FakeClock())
        adm = AdmissionController(max_queue=1, session_buckets=buckets)
        adm.inflight = 1                     # global bound saturated
        handler = FleetImageHandler(FullRouter(), admission=adm)

        async def scenario():
            for _ in range(4):               # far past the burst
                with pytest.raises(OverloadedError):
                    await handler.render_image_region(
                        _tile_ctx("viewer"))

        asyncio.run(scenario())
        # Every global shed refunded the caller's token: no fairness
        # shed ever fired, and the bucket still holds its burst.
        assert telemetry.RESILIENCE.shed.get("fairness") is None
        assert telemetry.RESILIENCE.shed.get("queue-full") == 4
        assert buckets.try_take("viewer")
        assert buckets.try_take("viewer")

    def test_sessions_never_share_budget_or_leader(self):
        buckets = SessionTokenBuckets(refill_per_s=0.001, burst=1.0,
                                      clock=FakeClock())
        handler, dispatched = self._handler(buckets)

        async def scenario():
            # Identical params, different sessions: the folded
            # single-flight key keeps leaders per-session, so the
            # hog's empty bucket cannot shed the calm session (and
            # the calm session's render cannot serve the hog).
            await handler.render_image_region(_tile_ctx("hog"))
            with pytest.raises(OverloadedError):
                await handler.render_image_region(_tile_ctx("hog"))
            out = await handler.render_image_region(
                _tile_ctx("calm"))
            assert out == b"pixels"

        asyncio.run(scenario())
        assert dispatched == ["hog", "calm"]
        assert telemetry.RESILIENCE.shed.get("fairness") == 1


class TestViewportWiring:
    def test_viewport_gated_on_sessions_enabled(self, tmp_path):
        """Without the session tier every request is anonymous — one
        SHARED trajectory interleaving unrelated viewers would
        predict garbage while suppressing the lattice fallback, so
        build_services only wires the viewport model when
        ``sessions.enabled`` is on."""
        from omero_ms_image_region_tpu.server.app import (
            build_services)
        from omero_ms_image_region_tpu.server.config import (
            RawCacheConfig, SessionsConfig)

        config = AppConfig(
            data_dir=str(tmp_path),
            raw_cache=RawCacheConfig(enabled=True, prefetch=True))
        services = build_services(config)
        try:
            assert services.prefetcher is not None
            assert services.prefetcher.viewport is None
        finally:
            services.prefetcher.close()
            services.pixels_service.close()

        config.sessions = SessionsConfig(enabled=True,
                                         prefetch_lookahead=3)
        services = build_services(config)
        try:
            assert services.prefetcher.viewport is not None
            assert services.prefetcher.lookahead == 3
        finally:
            services.prefetcher.close()
            services.pixels_service.close()


# ------------------------------------------------ predictive prefetch

class _FakeSrc:
    """Minimal pixel source for TilePrefetcher: records region reads,
    optionally blocking the FIRST read until released."""

    def __init__(self, block_first=False):
        self.calls = []
        self.block_first = block_first
        self.first_started = threading.Event()
        self.release = threading.Event()

    def get_region(self, z, c, t, region, level):
        first = not self.calls
        self.calls.append((region.x, region.y))
        if self.block_first and first:
            self.first_started.set()
            assert self.release.wait(5.0)
        return np.zeros((region.height, region.width), np.uint16)


def _prefetcher(viewport=None, max_workers=1, max_pending=16,
                cache=None, **kw):
    from omero_ms_image_region_tpu.io.devicecache import DeviceRawCache
    from omero_ms_image_region_tpu.services.prefetch import (
        TilePrefetcher)
    cache = cache if cache is not None else DeviceRawCache(
        digest_index=False)
    return TilePrefetcher(cache, max_workers=max_workers,
                          max_pending=max_pending,
                          viewport=viewport, **kw), cache


def _serve(prefetcher, src, x, y, session=None, levels=((96, 96),)):
    from omero_ms_image_region_tpu.server.region import RegionDef
    prefetcher.tile_served(
        src, 1, 0, 0, 0, levels,
        RegionDef(x=x, y=y, width=16, height=16), (16, 16), 2048,
        (0,), session_key=session)


class TestPredictivePrefetch:
    def test_trajectory_prefetches_predicted_tiles_not_neighbors(self):
        tracker = ViewportTracker(clock=FakeClock())
        prefetcher, cache = _prefetcher(viewport=tracker)
        src = _FakeSrc()
        try:
            _serve(prefetcher, src, 1, 2, session="s")   # no history
            prefetcher.flush()
            lattice = set(src.calls)
            assert len(lattice) == 4                     # fallback
            _serve(prefetcher, src, 2, 2, session="s")   # velocity 1,0
            prefetcher.flush()
            predicted = set(src.calls[4:])
            # The pan-ahead tiles (48,32)/(64,32) in pixels, minus any
            # the lattice already staged.
            assert predicted == {(48, 32), (64, 32)} - lattice
            assert prefetcher.predicted >= 2
            assert telemetry.PREFETCH.predicted >= 2
            kinds = [e["kind"] for e in telemetry.FLIGHT.snapshot()]
            assert "prefetch.predict" in kinds
        finally:
            prefetcher.close()

    def test_foreground_hit_accounting(self):
        tracker = ViewportTracker(clock=FakeClock())
        prefetcher, cache = _prefetcher(viewport=tracker)
        src = _FakeSrc()
        try:
            _serve(prefetcher, src, 0, 0, session="s")
            _serve(prefetcher, src, 1, 0, session="s")
            prefetcher.flush()
            assert prefetcher.staged > 0
            # The foreground read for the predicted tile finds it
            # resident and reports the hit back.
            from omero_ms_image_region_tpu.io.devicecache import (
                region_key)
            key = region_key(1, 0, 0, 0, (32, 0, 16, 16), 0)
            assert cache.get(key) is not None
            prefetcher.note_hit(key)
            assert prefetcher.hits == 1
            assert telemetry.PREFETCH.hits == 1
            assert prefetcher.hit_rate() == pytest.approx(
                1.0 / prefetcher.staged)
            # A key this prefetcher never staged is not a hit.
            prefetcher.note_hit(("not", "ours"))
            assert prefetcher.hits == 1
        finally:
            prefetcher.close()

    def test_budget_scales_max_pending_continuously(self):
        prefetcher, _ = _prefetcher(max_pending=16)
        try:
            assert prefetcher.effective_max_pending() == 16
            prefetcher.budget_scale = 0.5
            assert prefetcher.effective_max_pending() == 8
            prefetcher.budget_scale = 0.0
            assert prefetcher.effective_max_pending() == 0
            assert prefetcher.paused is True
            prefetcher.paused = False        # ladder release
            assert prefetcher.effective_max_pending() == 16
        finally:
            prefetcher.close()

    def test_governor_budget_multiplies_in(self):
        raw = {"pressure": {"enabled": True}}
        config = AppConfig.from_dict(raw).pressure
        value = {"queue": 0.0}
        gov = pressure.PressureGovernor(
            config, {}, {"queue": lambda: value["queue"]})
        pressure.install(gov)
        prefetcher, _ = _prefetcher(max_pending=16)
        try:
            assert prefetcher.effective_budget() == 1.0
            value["queue"] = 48.0            # elevated
            gov.tick()
            assert prefetcher.effective_budget() == pytest.approx(0.5)
            assert prefetcher.effective_max_pending() == 8
            # The local ladder actuator floors it regardless of level.
            prefetcher.paused = True
            assert prefetcher.effective_budget() == 0.0
        finally:
            prefetcher.close()

    def test_pause_mid_flight_cancels_queued_work_and_flush_settles(
            self):
        """The PR 9 regression: a budget hitting zero MID-FLIGHT must
        bind queued-but-unstarted pool items — flush() during a pause
        settles without loading work nobody wants."""
        prefetcher, cache = _prefetcher(max_workers=1)
        src = _FakeSrc(block_first=True)
        try:
            _serve(prefetcher, src, 1, 1)    # 4 neighbors scheduled
            assert prefetcher.scheduled == 4
            assert src.first_started.wait(5.0)
            # Pause while one load is in flight and three are queued.
            prefetcher.paused = True
            src.release.set()
            prefetcher.flush(timeout=5.0)
            # The in-flight load completed; the queued three exited at
            # the budget check without touching the source.
            assert len(src.calls) == 1
            assert prefetcher.staged == 1
            assert len(cache) == 1
            assert telemetry.PREFETCH.skipped.get("paused") == 3
        finally:
            src.release.set()
            prefetcher.close()

    def test_budget_zero_schedules_nothing_at_all(self):
        prefetcher, _ = _prefetcher()
        src = _FakeSrc()
        try:
            prefetcher.paused = True
            _serve(prefetcher, src, 1, 1)
            prefetcher.flush()
            assert prefetcher.scheduled == 0
            assert src.calls == []
            assert telemetry.PREFETCH.skipped.get("budget") == 1
        finally:
            prefetcher.close()

    def test_fleet_route_seam_stages_into_the_owning_shard(self):
        from omero_ms_image_region_tpu.io.devicecache import (
            DeviceRawCache)

        routed_cache = DeviceRawCache(digest_index=False)
        routes = []

        def cache_for_route(route_key):
            routes.append(route_key)
            return routed_cache

        prefetcher, local_cache = _prefetcher(
            cache_for_route=cache_for_route)
        src = _FakeSrc()
        try:
            _serve(prefetcher, src, 1, 1)
            prefetcher.flush()
            # Every staged plane went to the member the router owns
            # for that plane — none into the local shard.
            assert len(routes) == 4
            assert len(routed_cache) == 4
            assert len(local_cache) == 0
        finally:
            prefetcher.close()


class TestMaskFairness:
    """Masks join the session model (the PR 10 follow-on closed by
    the autoscaler PR): ``render_shape_mask`` debits session fairness
    tokens, QoS-classed INTERACTIVE — a hostile mask-scraping session
    used to bypass the meter entirely."""

    @staticmethod
    def _mask_ctx(session, shape_id=5):
        from omero_ms_image_region_tpu.server.ctx import ShapeMaskCtx
        return ShapeMaskCtx.from_params(
            {"shapeId": str(shape_id), "color": "FF0000"}, session)

    def test_mask_ctx_is_qos_classed_interactive(self):
        ctx = self._mask_ctx("viewer")
        assert pressure.is_bulk(ctx) is False
        # ...including shape id 0 (a falsy id is still a mask).
        assert pressure.is_bulk(self._mask_ctx("v", 0)) is False

    def test_mask_scraper_sheds_on_its_own_budget(self):
        clock = FakeClock()
        buckets = SessionTokenBuckets(refill_per_s=1.0, burst=2.0,
                                      clock=clock)
        adm = AdmissionController(max_queue=100,
                                  session_buckets=buckets)
        adm.refund_session(None)
        assert adm.admit_session(self._mask_ctx("scraper"))
        assert adm.admit_session(self._mask_ctx("scraper"))
        with pytest.raises(OverloadedError):
            adm.admit_session(self._mask_ctx("scraper"))
        assert telemetry.QOS.shed.get("interactive") == 1
        # Another session's masks — and tiles — stay admitted.
        assert adm.admit_session(self._mask_ctx("calm"))
        assert adm.admit_session(_tile_ctx("calm2"))

    def test_masks_and_tiles_share_one_session_budget(self):
        """One meter per session, not per route: tiles spend the same
        bucket the masks do."""
        clock = FakeClock()
        buckets = SessionTokenBuckets(refill_per_s=1.0, burst=2.0,
                                      clock=clock)
        adm = AdmissionController(max_queue=100,
                                  session_buckets=buckets)
        assert adm.admit_session(_tile_ctx("mixed"))
        assert adm.admit_session(self._mask_ctx("mixed"))
        with pytest.raises(OverloadedError):
            adm.admit_session(self._mask_ctx("mixed"))

    def test_viewport_activity_keeps_the_session_without_a_vote(self):
        """observe_activity keeps a mask-only session live in the LRU
        (the demand figure the autoscaler reads) without polluting
        the pan trajectory."""
        clock = FakeClock()
        tracker = ViewportTracker(max_sessions=4, clock=clock)
        tracker.observe_activity("masker")
        assert len(tracker) == 1
        assert tracker.predict("masker") == []
        assert tracker.velocity("masker") is None
        # A panning session's trajectory is untouched by interleaved
        # mask activity.
        for x in range(4):
            tracker.observe("panner", 1, 0, 0, 0, x, 2)
            tracker.observe_activity("panner")
        assert tracker.velocity("panner") == (1, 0)

    def test_mask_route_sheds_503_with_fairness_and_refunds(
            self, tmp_path):
        """End to end: a mask-scraping session exhausts ITS bucket and
        gets the fairness 503 + Retry-After on the mask ROUTE; a calm
        session keeps rendering; a failed mask refunds the token."""
        import numpy as np
        from aiohttp.test_utils import TestClient, TestServer

        from omero_ms_image_region_tpu.io.store import build_pyramid
        from omero_ms_image_region_tpu.models.mask import Mask
        from omero_ms_image_region_tpu.server.app import create_app
        from omero_ms_image_region_tpu.services.metadata import (
            write_mask)

        root = tmp_path / "data"
        root.mkdir()
        rng = np.random.default_rng(5)
        planes = rng.integers(0, 60000,
                              size=(1, 1, 64, 64)).astype("uint16")
        build_pyramid(planes, str(root / "1"), n_levels=1)
        grid = np.zeros(64 * 64, np.uint8)
        grid[:64] = 1
        write_mask(str(root), Mask(shape_id=5, width=64, height=64,
                                   bytes_=np.packbits(grid)
                                   .tobytes()))
        config = AppConfig.from_dict({
            "data-dir": str(root),
            "batcher": {"enabled": False},
            "session-store": {"type": "static", "required": False},
            "sessions": {"enabled": True, "bucket-refill-per-s": 0.5,
                         "bucket-burst": 2},
        })

        async def scenario():
            client = TestClient(TestServer(create_app(config)))
            await client.start_server()
            try:
                url = "/webgateway/render_shape_mask/5?color=FF0000"
                scraper = {"sessionid": "scraper"}
                statuses = []
                for i in range(4):
                    r = await client.get(
                        url + f"&_v={i}", cookies=scraper)
                    statuses.append(r.status)
                    retry_after = r.headers.get("Retry-After")
                assert statuses[:2] == [200, 200]
                assert 503 in statuses[2:]
                assert retry_after is not None
                # The calm session is untouched by the scraper's shed.
                r = await client.get(url,
                                     cookies={"sessionid": "calm"})
                assert r.status == 200
                # 404 scraping is METERED too: tokens pay for the
                # attempt (the image route's contract — refunding
                # request-level failures would let a hostile session
                # scrape nonexistent shape ids unmetered forever).
                misses = {"sessionid": "misser"}
                for _ in range(2):
                    r = await client.get(
                        "/webgateway/render_shape_mask/999",
                        cookies=misses)
                    assert r.status == 404
                statuses = []
                for _ in range(2):
                    r = await client.get(
                        "/webgateway/render_shape_mask/999",
                        cookies=misses)
                    statuses.append(r.status)
                assert 503 in statuses
            finally:
                await client.close()

        asyncio.run(scenario())
        assert telemetry.RESILIENCE.shed.get("fairness", 0) >= 1

    def test_cached_masks_cost_no_tokens(self, tmp_path):
        """Tile-route footing for masks: with the shape-mask byte
        cache on, repeat views of a cached mask serve PAST the
        session's burst — already-rendered bytes never cost a token
        and never shed."""
        import numpy as np
        from aiohttp.test_utils import TestClient, TestServer

        from omero_ms_image_region_tpu.io.store import build_pyramid
        from omero_ms_image_region_tpu.models.mask import Mask
        from omero_ms_image_region_tpu.server.app import create_app
        from omero_ms_image_region_tpu.services.metadata import (
            write_mask)

        root = tmp_path / "data"
        root.mkdir()
        rng = np.random.default_rng(6)
        planes = rng.integers(0, 60000,
                              size=(1, 1, 64, 64)).astype("uint16")
        build_pyramid(planes, str(root / "1"), n_levels=1)
        grid = np.zeros(64 * 64, np.uint8)
        grid[:64] = 1
        write_mask(str(root), Mask(shape_id=5, width=64, height=64,
                                   bytes_=np.packbits(grid)
                                   .tobytes()))
        config = AppConfig.from_dict({
            "data-dir": str(root),
            "batcher": {"enabled": False},
            "shape-mask-cache": {"enabled": True},
            "session-store": {"type": "static", "required": False},
            "sessions": {"enabled": True, "bucket-refill-per-s": 0.5,
                         "bucket-burst": 2},
        })

        async def scenario():
            client = TestClient(TestServer(create_app(config)))
            await client.start_server()
            try:
                url = "/webgateway/render_shape_mask/5?color=FF0000"
                viewer = {"sessionid": "repeat-viewer"}
                # 8 repeat views on a burst-2 budget: the first
                # renders (1 token), every repeat is a byte-cache hit
                # BEFORE the fairness gate — all 200, zero sheds.
                for _ in range(8):
                    r = await client.get(url, cookies=viewer)
                    assert r.status == 200
            finally:
                await client.close()

        asyncio.run(scenario())
        assert telemetry.RESILIENCE.shed.get("fairness", 0) == 0
