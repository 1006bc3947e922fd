"""Data-parallel device fleet (``parallel.fleet``): consistent-hash
routing stability, HBM shard accounting, bounded work stealing, and the
deterministic member-death chaos drill.

The hash-ring goldens are the load-bearing tests here: the ring is the
fleet's shard map, so ANY change to its math silently re-homes every
plane in every deployed HBM cache.  A deliberate ring change must
re-pin the goldens — and accept that rollouts pay a full re-stage."""

import asyncio
import time

import pytest

from omero_ms_image_region_tpu.parallel.fleet import (
    FleetImageHandler, FleetRouter, HashRing, LocalMember,
    plane_route_key)
from omero_ms_image_region_tpu.server.config import HotkeyConfig
from omero_ms_image_region_tpu.server.ctx import ImageRegionCtx
from omero_ms_image_region_tpu.utils import decisions, telemetry


def _ctx(image_id="1", z="0", t="0", tile="0,0,0,128,128", **extra):
    params = {"imageId": image_id, "theZ": z, "theT": t, "m": "c"}
    if tile is not None:
        params["tile"] = tile
    params.update(extra)
    return ImageRegionCtx.from_params(params)


# ------------------------------------------------------------ hash ring

class TestHashRing:
    def test_golden_assignments_pinned(self):
        """Digest->member map is FROZEN.  A failure here means the
        ring's hash math changed and every deployed fleet's HBM shard
        map would silently reshuffle on restart — re-pin only for a
        deliberate, migration-aware ring change."""
        ring = HashRing(["m0", "m1", "m2", "m3"], replicas=64)
        golden = {
            "plane-000": "m3", "plane-001": "m0", "plane-002": "m2",
            "plane-003": "m0", "plane-004": "m2", "plane-005": "m2",
            "plane-006": "m3", "plane-007": "m3", "plane-008": "m0",
            "plane-009": "m0", "plane-010": "m1", "plane-011": "m1",
        }
        assert {k: ring.member(k) for k in golden} == golden

    def test_golden_failover_chain_pinned(self):
        """The failover order is part of the contract too: a dead
        member's keys move to a DETERMINISTIC successor."""
        ring = HashRing(["m0", "m1", "m2", "m3"], replicas=64)
        assert ring.chain("plane-000") == ["m3", "m2", "m0", "m1"]

    def test_deterministic_across_instances(self):
        a = HashRing(["m0", "m1", "m2"], replicas=32)
        b = HashRing(["m0", "m1", "m2"], replicas=32)
        keys = [f"k{i}" for i in range(200)]
        assert [a.member(k) for k in keys] == [b.member(k) for k in keys]

    def test_keyspace_split_near_uniform(self):
        ring = HashRing([f"m{i}" for i in range(4)], replicas=64)
        counts = {}
        for i in range(10000):
            owner = ring.member(f"k{i}")
            counts[owner] = counts.get(owner, 0) + 1
        for owner, n in counts.items():
            # Fair share is 2500; virtual nodes keep every member
            # within a loose band of it.
            assert 1500 < n < 3500, (owner, counts)

    @pytest.mark.parametrize("n", [4, 8])
    def test_remap_bound_on_member_leave(self, n):
        """The consistent-hash contract: removing one of N members
        moves only that member's keys (~1/N of the space) — every
        other key keeps its owner, so a membership change can never
        silently re-home the whole fleet's HBM cache."""
        members = [f"m{i}" for i in range(n)]
        before = HashRing(members, replicas=64)
        after = HashRing(members[:-1], replicas=64)
        keys = [f"k{i}" for i in range(10000)]
        moved = sum(1 for k in keys
                    if before.member(k) != after.member(k))
        # Expected fraction is exactly the departed member's share.
        departed = sum(1 for k in keys
                       if before.member(k) == members[-1])
        assert moved == departed
        assert moved / len(keys) < (1.0 / n) * 1.6 + 0.02

    def test_remap_bound_on_member_join(self):
        """Joining an (N+1)th member steals ~1/(N+1) of the space and
        nothing else changes hands."""
        before = HashRing(["m0", "m1", "m2", "m3"], replicas=64)
        after = HashRing(["m0", "m1", "m2", "m3", "m4"], replicas=64)
        keys = [f"k{i}" for i in range(10000)]
        moved = [k for k in keys
                 if before.member(k) != after.member(k)]
        # Every moved key moved TO the joiner, never between old
        # members.
        assert all(after.member(k) == "m4" for k in moved)
        assert len(moved) / len(keys) < (1.0 / 5) * 1.6 + 0.02

    def test_validation(self):
        with pytest.raises(ValueError):
            HashRing([])
        with pytest.raises(ValueError):
            HashRing(["m0", "m0"])


class TestPlaneRouteKey:
    def test_settings_do_not_move_the_shard(self):
        """Re-window / re-color / format changes hash to the SAME
        member: the route key is the source plane's identity, which is
        what makes the HBM tier shard instead of duplicate."""
        base = _ctx(c="1|0:60000$FF0000")
        rewindow = _ctx(c="1|1000:30000$00FF00")
        reformat = _ctx(c="1|0:60000$FF0000", format="png")
        assert plane_route_key(base) == plane_route_key(rewindow)
        assert plane_route_key(base) == plane_route_key(reformat)

    def test_plane_identity_moves_the_shard(self):
        seen = {plane_route_key(_ctx()),
                plane_route_key(_ctx(z="1")),
                plane_route_key(_ctx(t="1")),
                plane_route_key(_ctx(tile="0,1,0,128,128")),
                plane_route_key(_ctx(image_id="9"))}
        assert len(seen) == 5

    def test_golden_route_keys_pinned(self):
        """Route-key digests frozen alongside the ring goldens — the
        two together pin the full digest->member path."""
        assert plane_route_key(_ctx()) == \
            "673758f592968bbaa5606b21d12bff3b"
        assert plane_route_key(_ctx(tile="0,1,0,128,128")) == \
            "08d8586d9be30dd7e71d112376e59ef7"
        assert plane_route_key(_ctx(z="3")) == \
            "7fad960a17faea5a64e1143f33e7c8ee"


# --------------------------------------------------------------- router

class _FakeHandler:
    """Duck-typed ImageRegionHandler: records (ctx, adopt_cache) calls,
    optionally delays, optionally dies (ConnectionError) after N
    successful renders."""

    def __init__(self, name, delay_s=0.0, die_after=None):
        self.name = name
        self.calls = []
        self.delay_s = delay_s
        self.die_after = die_after

    async def render_image_region(self, ctx, adopt_cache=True):
        if self.die_after is not None \
                and len(self.calls) >= self.die_after:
            raise ConnectionError(f"{self.name} killed by chaos drill")
        self.calls.append((ctx, adopt_cache))
        if self.delay_s:
            await asyncio.sleep(self.delay_s)
        return f"{self.name}".encode()


def _fleet(n, lane_width=1, steal_min_backlog=0, **handler_kw):
    handlers = [_FakeHandler(f"m{i}", **handler_kw) for i in range(n)]
    members = [LocalMember(f"m{i}", handlers[i]) for i in range(n)]
    router = FleetRouter(members, lane_width=lane_width,
                         steal_min_backlog=steal_min_backlog)
    return router, handlers


class TestFleetRouter:
    def setup_method(self):
        telemetry.reset()

    def test_routes_by_plane_identity(self):
        """Every render of one plane — whatever its settings — lands
        on the ring owner's handler; distinct planes spread."""
        async def main():
            router, handlers = _fleet(4)
            try:
                ctxs = [_ctx(tile=f"0,{x},{y},128,128")
                        for x in range(3) for y in range(3)]
                ctxs += [_ctx(c="1|5:999$00FF00")]       # re-window
                out = await asyncio.gather(
                    *(router.dispatch(c) for c in ctxs))
                assert all(out)
                by_member = {h.name: len(h.calls) for h in handlers}
                assert sum(by_member.values()) == len(ctxs)
                # The re-window of tile (0,0) went to tile (0,0)'s
                # owner (golden: m3).
                owner = router.ring.member(plane_route_key(ctxs[0]))
                assert owner == "m3"
                tile00 = [h for h in handlers if h.name == owner][0]
                settings_seen = {id(c) for c, _ in tile00.calls}
                assert id(ctxs[0]) in settings_seen
                assert id(ctxs[-1]) in settings_seen
            finally:
                await router.close()

        asyncio.run(main())

    def test_full_plane_and_projection_pin_to_mesh_lane(self):
        """Full-plane and z-projection jobs go to member 0 — the lane
        whose renderer is the lockstep MeshRenderer in mesh
        deployments — and never shard."""
        async def main():
            router, handlers = _fleet(4)
            try:
                full = _ctx(tile=None)
                proj = _ctx(tile=None, p="intmax|0:3")
                await router.dispatch(full)
                await router.dispatch(proj)
                assert len(handlers[0].calls) == 2
            finally:
                await router.close()

        asyncio.run(main())

    def test_work_stealing_is_bounded_and_cache_neutral(self):
        """A backlogged member's OLDEST work is stolen by idle peers;
        stolen renders carry adopt_cache=False so stealing never
        fragments the shard map."""
        async def main():
            router, handlers = _fleet(
                4, lane_width=1, steal_min_backlog=2, delay_s=0.01)
            try:
                # 12 renders of ONE plane identity: all owned by m3
                # (golden), so its queue backs up past the threshold
                # and the three idle members steal.
                ctxs = [_ctx(c=f"1|{i}:60000$FF0000")
                        for i in range(12)]
                out = await asyncio.gather(
                    *(router.dispatch(c) for c in ctxs))
                assert all(out)
                owner = [h for h in handlers if h.name == "m3"][0]
                others = [h for h in handlers if h.name != "m3"]
                stolen = [c for h in others for c in h.calls]
                assert stolen, "no work was stolen from the backlog"
                # Every stolen render declined cache adoption; every
                # owned render adopted.
                assert all(adopt is False for _, adopt in stolen)
                assert all(adopt is True for _, adopt in owner.calls)
                assert telemetry.FLEET.totals()["stolen"] \
                    == len(stolen)
            finally:
                await router.close()

        asyncio.run(main())

    def test_steal_disabled_at_zero_threshold(self):
        async def main():
            router, handlers = _fleet(
                4, lane_width=1, steal_min_backlog=0, delay_s=0.002)
            try:
                ctxs = [_ctx(c=f"1|{i}:60000$FF0000")
                        for i in range(8)]
                await asyncio.gather(
                    *(router.dispatch(c) for c in ctxs))
                owner = [h for h in handlers if h.name == "m3"][0]
                assert len(owner.calls) == len(ctxs)
                assert telemetry.FLEET.totals()["stolen"] == 0
            finally:
                await router.close()

        asyncio.run(main())

    def test_fleet_depth_counts_queued_and_inflight(self):
        async def main():
            router, _ = _fleet(2, lane_width=1, delay_s=0.05)
            try:
                tasks = [asyncio.create_task(router.dispatch(_ctx(
                    c=f"1|{i}:60000$FF0000"))) for i in range(4)]
                await asyncio.sleep(0.02)
                assert router.queue_depth() >= 1
                await asyncio.gather(*tasks)
                assert router.queue_depth() == 0
            finally:
                await router.close()

        asyncio.run(main())

    def test_lanes_do_not_inherit_the_first_requests_deadline(self):
        """Lane tasks are spawned lazily from the FIRST dispatch's
        context: they must be detached from its deadline contextvar,
        or every later render inherits that budget and the whole
        fleet 504s forever once it expires."""
        from omero_ms_image_region_tpu.utils import transient

        class _DeadlineAware(_FakeHandler):
            async def render_image_region(self, ctx,
                                          adopt_cache=True):
                transient.check_deadline("render pipeline")
                return await super().render_image_region(
                    ctx, adopt_cache)

        async def main():
            handlers = [_DeadlineAware(f"m{i}") for i in range(2)]
            members = [LocalMember(f"m{i}", handlers[i])
                       for i in range(2)]
            router = FleetRouter(members, lane_width=1)
            try:
                with transient.deadline_scope(80):
                    assert await router.dispatch(_ctx())
                await asyncio.sleep(0.12)   # first budget now dead
                # Budget-free requests keep serving on every member.
                for i in range(4):
                    assert await router.dispatch(
                        _ctx(c=f"1|{i}:60000$FF0000"))
            finally:
                await router.close()

        asyncio.run(main())

    def test_local_oserror_is_a_request_failure_not_member_death(self):
        """A missing/truncated source file (OSError from a LOCAL
        render) fails that one request; the member stays in the ring
        and keeps serving — one bad file must never cascade into
        marking the whole fleet down."""
        class _BadFileHandler(_FakeHandler):
            async def render_image_region(self, ctx,
                                          adopt_cache=True):
                if ctx.z == 1:
                    raise FileNotFoundError("pyramid level missing")
                return await super().render_image_region(
                    ctx, adopt_cache)

        async def main():
            handlers = [_BadFileHandler(f"m{i}") for i in range(2)]
            members = [LocalMember(f"m{i}", handlers[i])
                       for i in range(2)]
            router = FleetRouter(members, lane_width=1)
            try:
                with pytest.raises(FileNotFoundError):
                    await router.dispatch(_ctx(z="1"))
                assert router.healthy_members() == ["m0", "m1"]
                assert telemetry.FLEET.totals()["failed_over"] == 0
                assert await router.dispatch(_ctx())
            finally:
                await router.close()

        asyncio.run(main())

    def test_pinned_mesh_jobs_are_never_stolen(self):
        """Full-plane/z-projection work pins to member 0's lockstep
        lane even under backlog: an idle peer must not steal it onto
        a plain single-device renderer."""
        async def main():
            router, handlers = _fleet(
                3, lane_width=1, steal_min_backlog=2, delay_s=0.02)
            try:
                ctxs = [_ctx(tile=None, p="intmax|0:1")
                        for _ in range(6)]
                out = await asyncio.gather(
                    *(router.dispatch(c) for c in ctxs))
                assert all(out)
                assert len(handlers[0].calls) == 6
                assert telemetry.FLEET.totals()["stolen"] == 0
            finally:
                await router.close()

        asyncio.run(main())

    def test_close_fails_pending_cleanly(self):
        async def main():
            router, _ = _fleet(2, lane_width=1, delay_s=0.2)
            try:
                tasks = [asyncio.create_task(router.dispatch(_ctx(
                    c=f"1|{i}:60000$FF0000"))) for i in range(6)]
                await asyncio.sleep(0.02)
            finally:
                await router.close()
            results = await asyncio.gather(*tasks,
                                           return_exceptions=True)
            # Whatever was in flight either finished or failed with
            # the shutdown error — never a hang, never a bare cancel.
            for r in results:
                assert isinstance(r, (bytes, RuntimeError,
                                      ConnectionError)), r

        asyncio.run(main())


# ------------------------------------------------- admitted concurrency

class _Gate(_FakeHandler):
    """A handler whose renders wait for ``release``; ``running`` and
    ``peak`` count the renders inside it at once."""

    def __init__(self, name):
        super().__init__(name)
        self.release = asyncio.Event()
        self.running = self.peak = 0

    async def render_image_region(self, ctx, adopt_cache=True):
        self.calls.append((ctx, adopt_cache))
        self.running += 1
        self.peak = max(self.peak, self.running)
        try:
            await self.release.wait()
        finally:
            self.running -= 1
        return self.name.encode()


def _batching_services(pipeline_depth=4, max_batch=8):
    from types import SimpleNamespace

    from omero_ms_image_region_tpu.server.batcher import BatchingRenderer
    return SimpleNamespace(renderer=BatchingRenderer(
        pipeline_depth=pipeline_depth, max_batch=max_batch))


async def _settle(turns=5):
    for _ in range(turns):
        await asyncio.sleep(0)


class TestAdmittedConcurrency:
    """A member runs as many renders at once as its own batcher can
    group (``pipeline_depth x max_batch``); a member that states no
    capacity runs ``lane_width``."""

    def setup_method(self):
        telemetry.reset()

    @pytest.mark.parametrize("batching,want", [(True, 32), (False, 3)])
    def test_a_member_runs_its_capacity_at_once(self, batching, want):
        async def main():
            gate = _Gate("m0")
            member = LocalMember(
                "m0", gate,
                services=_batching_services() if batching else None)
            router = FleetRouter([member], lane_width=3)
            try:
                assert router.member_capacity("m0") == want
                tasks = [asyncio.create_task(router.dispatch(_ctx(
                    c=f"1|{i}:60000$FF0000"))) for i in range(want + 8)]
                await _settle()
                assert gate.running == want
                assert router.member_inflight("m0") == want
                assert router.member_depth("m0") == 8
                gate.release.set()
                assert all(await asyncio.gather(*tasks))
                assert gate.peak == want
                assert router.queue_depth() == 0
            finally:
                await router.close()

        asyncio.run(main())

    def test_the_lockstep_renderer_states_no_capacity(self):
        services = _batching_services()
        services.renderer.lockstep = True
        member = LocalMember("m0", _FakeHandler("m0"), services=services)
        assert member.render_capacity() is None
        assert FleetRouter([member], lane_width=2).member_capacity(
            "m0") == 2

    def test_device_lanes_is_the_sum_of_the_members_capacities(self):
        members = [
            LocalMember("m0", _FakeHandler("m0"),
                        services=_batching_services()),
            LocalMember("m1", _FakeHandler("m1"),
                        services=_batching_services(2, 4)),
            LocalMember("m2", _FakeHandler("m2"))]
        router = FleetRouter(members, lane_width=2)
        assert [router.member_capacity(n) for n in router.order] == [
            32, 8, 2]
        assert router.device_lanes == 42

    @pytest.mark.parametrize("pipeline_depth", [1, 4, 16])
    def test_a_dispatch_costs_the_loop_the_same_at_any_capacity(
            self, pipeline_depth, monkeypatch):
        """Counted by ``_pop_work``'s calls: one when the dispatch
        starts its render, one when the render finds nothing more to
        take; while the member is busy, one a dispatch.  No task sits
        idle, so none is woken."""
        async def main():
            gate = _Gate("m0")
            member = LocalMember(
                "m0", gate,
                services=_batching_services(pipeline_depth, 8))
            router = FleetRouter([member, LocalMember(
                "m1", _FakeHandler("m1"))], lane_width=1,
                steal_min_backlog=2)
            pops = []
            real = router._pop_work
            monkeypatch.setattr(router, "_pop_work",
                                lambda name: pops.append(name)
                                or real(name))
            ctxs = [_ctx(c=f"1|{i}:60000$FF0000") for i in range(4)]
            assert {router.owner_of(c) for c in ctxs} == {"m0"}
            try:
                tasks = []
                for c in ctxs:
                    before = len(pops)
                    tasks.append(asyncio.create_task(router.dispatch(c)))
                    await _settle()
                    assert len(pops) - before == 1
                gate.release.set()
                await asyncio.gather(*tasks)
                await _settle()
                # Each of the four tasks found nothing more.
                assert pops == ["m0"] * 8
            finally:
                await router.close()

        asyncio.run(main())

    def test_a_full_members_oldest_unit_is_stolen_past_the_backlog(self):
        """Owner full (capacity 1, busy): its queue fills to
        ``steal_min_backlog`` and the peer with room takes the OLDEST
        unit, without adopting cache ownership; the newer one waits
        for its owner."""
        async def main():
            owner, peer = _Gate("m0"), _Gate("m1")
            router = FleetRouter(
                [LocalMember("m0", owner), LocalMember("m1", peer)],
                lane_width=1, steal_min_backlog=2)
            ctxs = [_ctx(c=f"1|{i}:60000$FF0000") for i in range(3)]
            assert {router.owner_of(c) for c in ctxs} == {"m0"}
            try:
                tasks = []
                for c in ctxs[:2]:
                    tasks.append(asyncio.create_task(router.dispatch(c)))
                    await _settle()
                # One running on the owner, one queued: under the bar.
                assert [c for c, _ in owner.calls] == [ctxs[0]]
                assert peer.calls == []
                tasks.append(asyncio.create_task(router.dispatch(
                    ctxs[2])))
                await _settle()
                assert peer.calls == [(ctxs[1], False)]
                assert router.member_depth("m0") == 1
                assert telemetry.FLEET.totals()["stolen"] == 1
                owner.release.set()
                peer.release.set()
                assert await asyncio.gather(*tasks) == [b"m0", b"m1",
                                                        b"m0"]
                assert owner.calls[-1] == (ctxs[2], True)
            finally:
                await router.close()

        asyncio.run(main())

    def test_capacity_holds_under_random_load_with_steals(self):
        """Stress: 600 renders of random length over members of
        capacity 32, 8, 1 and 1, stealing on; no member ever runs more
        than its capacity, every waiter gets its member's bytes, and
        every place is given back."""
        import random

        class _Counting(_FakeHandler):
            def __init__(self, name, cap, rng):
                super().__init__(name)
                self.cap, self.rng = cap, rng
                self.running = self.peak = 0

            async def render_image_region(self, ctx, adopt_cache=True):
                self.running += 1
                self.peak = max(self.peak, self.running)
                assert self.running <= self.cap
                try:
                    for _ in range(self.rng.randrange(4)):
                        await asyncio.sleep(0)
                    await asyncio.sleep(self.rng.random() * 0.002)
                finally:
                    self.running -= 1
                return self.name.encode()

        async def main():
            rng = random.Random(39)
            caps = [32, 8, 1, 1]
            handlers = [_Counting(f"m{i}", c, rng)
                        for i, c in enumerate(caps)]
            members = [LocalMember(
                f"m{i}", handlers[i],
                services=_batching_services(4, 8) if i == 0
                else _batching_services(2, 4) if i == 1 else None)
                for i in range(4)]
            router = FleetRouter(members, lane_width=1,
                                 steal_min_backlog=2)
            try:
                out = await asyncio.wait_for(asyncio.gather(*(
                    router.dispatch(_ctx(
                        tile=f"0,{rng.randrange(6)},{rng.randrange(6)},"
                             "128,128", c=f"1|{i}:60000$FF0000"))
                    for i in range(600))), timeout=60)
                assert len(out) == 600
                assert all(b in (b"m0", b"m1", b"m2", b"m3") for b in out)
                assert [h.peak <= h.cap for h in handlers] == [True] * 4
                assert telemetry.FLEET.totals()["stolen"] > 0
                await _settle()
                assert router._running == {f"m{i}": 0 for i in range(4)}
                assert router.queue_depth() == 0
            finally:
                await router.close()

        asyncio.run(main())

    def test_queue_wait_is_a_series_and_a_span_on_the_trace(self):
        from omero_ms_image_region_tpu.utils.stopwatch import (
            REGISTRY, span_lines)

        async def main():
            gate = _Gate("m0")
            router = FleetRouter([LocalMember("m0", gate)], lane_width=1)
            try:
                tids = [telemetry.new_trace_id() for _ in range(2)]
                tasks = []
                for i, tid in enumerate(tids):
                    with telemetry.trace_scope(tid, "drill"):
                        tasks.append(asyncio.create_task(router.dispatch(
                            _ctx(c=f"1|{i}:60000$FF0000"))))
                await _settle()
                await asyncio.sleep(0.05)
                gate.release.set()
                await asyncio.gather(*tasks)
                waits = [[s for s in telemetry.TRACES.finish(tid)
                          .export_spans()
                          if s["name"] == "fleet.queueWait"]
                         for tid in tids]
            finally:
                await router.close()
            return waits

        before = REGISTRY.snapshot().get("fleet.queueWait", {}).get(
            "count", 0)
        first, second = asyncio.run(main())
        # The first started at once; the second waited for the first.
        assert len(first) == len(second) == 1
        assert first[0]["dur_ms"] < 5.0
        assert second[0]["dur_ms"] >= 45.0
        assert first[0]["member"] == "m0"
        assert REGISTRY.snapshot()["fleet.queueWait"]["count"] \
            == before + 2
        assert any(ln.startswith(
            'imageregion_span_count{span="fleet.queueWait"}')
            for ln in span_lines())


# ---------------------------------------------------------- chaos drill

class TestFleetChaos:
    def setup_method(self):
        telemetry.reset()

    def test_member_death_mid_burst_zero_failures(self):
        """The acceptance drill: kill one member mid-burst.  Its shard
        fails over hash-ring-next, its queued work is re-assigned, and
        EVERY request still gets bytes — zero 5xx-without-shed."""
        async def main():
            handlers = [_FakeHandler(f"m{i}", delay_s=0.005)
                        for i in range(4)]
            # m3 (the golden owner of the hot plane) dies after 2
            # successful renders — deterministically, mid-burst.
            handlers[3].die_after = 2
            members = [LocalMember(f"m{i}", handlers[i])
                       for i in range(4)]
            router = FleetRouter(members, lane_width=1,
                                 steal_min_backlog=0)
            try:
                ctxs = [_ctx(c=f"1|{i}:60000$FF0000")
                        for i in range(10)]
                out = await asyncio.gather(
                    *(router.dispatch(c) for c in ctxs),
                    return_exceptions=True)
                assert all(isinstance(b, bytes) for b in out), out
                # The victim is down; its shard's new owner is the
                # ring's next healthy member (golden chain for the
                # hot plane's route key: m3 -> m0 -> m2 -> m1).
                assert not members[3].healthy
                assert router.owner_of(ctxs[0]) == "m0"
                totals = telemetry.FLEET.totals()
                assert totals["failed_over"] >= 1
                # The failed-over work ran on the successor (ADOPTING
                # — it is the shard's new ring owner, not a thief).
                m0 = handlers[0]
                assert any(adopt is True for _, adopt in m0.calls)
            finally:
                await router.close()

        asyncio.run(main())

    def test_revived_member_rejoins_the_ring(self):
        async def main():
            router, handlers = _fleet(4)
            try:
                victim = router.members["m3"]
                victim.mark_down()
                hot = _ctx()
                assert router.owner_of(hot) == "m0"
                victim.revive()
                assert router.owner_of(hot) == "m3"
            finally:
                await router.close()

        asyncio.run(main())

    def test_stolen_work_returns_to_its_healthy_owner(self):
        """A dead STEALER's loot goes home: failover excludes the
        member that failed, not ``work.owner`` — in a 2-member fleet
        the healthy shard owner must serve it (not a 503)."""
        from omero_ms_image_region_tpu.parallel.fleet import _Work

        async def main():
            router, handlers = _fleet(2)
            try:
                ctx = _ctx()          # 2-member golden owner: m0
                assert router.owner_of(ctx) == "m0"
                work = _Work(ctx,
                             asyncio.get_running_loop()
                             .create_future(), "m0", None)
                work.stolen = True    # m1 stole it, then died
                router.members["m1"].mark_down()
                router._route_failover(work)
                assert work.owner == "m0"
                assert work.stolen is False
                assert work in router._queues["m0"]
            finally:
                await router.close()

        asyncio.run(main())

    def test_failover_disabled_fails_shard_with_member(self):
        """fleet.failover=false contract: a dead member's requests —
        in flight AND queued — fail as the member does; nothing is
        re-homed, nothing adopts."""
        async def main():
            handlers = [_FakeHandler(f"m{i}", delay_s=0.005)
                        for i in range(4)]
            handlers[3].die_after = 0      # hot-plane owner is dead
            members = [LocalMember(f"m{i}", handlers[i])
                       for i in range(4)]
            router = FleetRouter(members, lane_width=1,
                                 steal_min_backlog=0, failover=False)
            try:
                ctxs = [_ctx(c=f"1|{i}:60000$FF0000")
                        for i in range(6)]
                out = await asyncio.gather(
                    *(router.dispatch(c) for c in ctxs),
                    return_exceptions=True)
                assert all(isinstance(r, ConnectionError)
                           for r in out), out
                assert telemetry.FLEET.totals()["failed_over"] == 0
                assert not handlers[0].calls and not handlers[1].calls
            finally:
                await router.close()

        asyncio.run(main())

    def test_failover_disabled_new_arrivals_fail_too(self):
        """owner_of's contract symmetry with _fail_queue: with
        failover off, requests arriving AFTER a member's death still
        route to the dead owner and fail — silently re-homing them
        onto the ring successor (with adopt and no failed_over tick)
        would be exactly the shard migration the operator disabled."""
        async def main():
            handlers = [_FakeHandler(f"m{i}") for i in range(4)]
            handlers[3].die_after = 0      # hot-plane owner is dead
            members = [LocalMember(f"m{i}", handlers[i])
                       for i in range(4)]
            router = FleetRouter(members, lane_width=1,
                                 steal_min_backlog=0, failover=False)
            try:
                with pytest.raises(ConnectionError):
                    await router.dispatch(_ctx())
                assert not members[3].healthy
                # A fresh request for the dead member's shard.
                with pytest.raises(ConnectionError):
                    await router.dispatch(_ctx(c="1|9:60000$FF0000"))
                assert telemetry.FLEET.totals()["failed_over"] == 0
                assert not any(h.calls for h in handlers)
            finally:
                await router.close()

        asyncio.run(main())

    def test_local_member_readmits_after_cooldown(self):
        """LocalMember down state is a COOLDOWN, not a latch: the
        combined role's members share host-side services, so one
        transient outage (metadata DB, network pixel store) can mark
        every member down within a single failover chain — without
        timed re-admission the whole fleet would stay dead until a
        process restart."""
        member = LocalMember("m0", _FakeHandler("m0"),
                             down_cooldown_s=0.01)
        member.mark_down()
        assert not member.healthy
        time.sleep(0.03)
        assert member.healthy

    def test_fast_fail_does_not_extend_cooldown(self):
        """A request routed to an ALREADY-down member fast-fails
        without re-marking it down.  Re-marking would push the
        cooldown forward on every routed request, so any shard seeing
        >= 1 request per cooldown window would keep its member down
        forever after the outage healed (the shared-service case:
        every member down, owner_of still hands the ring owner the
        call so the 503 contract surfaces)."""
        async def main():
            router, _handlers = _fleet(2)
            try:
                for m in router.members.values():
                    m.mark_down()
                marks = {n: m._down_until
                         for n, m in router.members.items()}
                with pytest.raises(ConnectionError):
                    await router.dispatch(_ctx())
                # No member's cooldown moved: the fast-fail is not a
                # fresh death observation.
                assert {n: m._down_until
                        for n, m in router.members.items()} == marks
            finally:
                await router.close()

        asyncio.run(main())

    def test_fleet_recovers_under_steady_traffic_after_outage(self):
        """Requests keep arriving while every member is down; once the
        cooldown expires the fleet serves again — traffic during the
        outage must not have re-latched the members."""
        async def main():
            handlers = [_FakeHandler(f"m{i}") for i in range(2)]
            members = [LocalMember(f"m{i}", handlers[i],
                                   down_cooldown_s=0.1)
                       for i in range(2)]
            router = FleetRouter(members, lane_width=1,
                                 steal_min_backlog=0)
            try:
                for m in members:
                    m.mark_down()
                deadline = time.monotonic() + 0.15
                while time.monotonic() < deadline:
                    try:
                        await router.dispatch(_ctx())
                        break          # cooldown expired, served
                    except ConnectionError:
                        await asyncio.sleep(0.01)
                assert await router.dispatch(_ctx())
                assert all(m.healthy for m in members)
            finally:
                await router.close()

        asyncio.run(main())

    def test_prechecked_member_skips_member_level_byte_cache(self):
        """build_local_members marks its members byte_cache_prechecked
        — the fleet handler probed the shared byte tier and ran the
        caller's ACL immediately before dispatch, so the member-level
        handler must skip its duplicate probe (a guaranteed-miss walk
        of the memory/disk byte tiers on every routed render)."""
        class _Spy:
            kwargs = None

            async def render_image_region(self, ctx, adopt_cache=True,
                                          skip_byte_cache=False):
                self.kwargs = {"adopt_cache": adopt_cache,
                               "skip_byte_cache": skip_byte_cache}
                return b"x"

        async def main():
            spy = _Spy()
            member = LocalMember("m0", spy,
                                 byte_cache_prechecked=True)
            assert await member.render(_ctx()) == b"x"
            assert spy.kwargs == {"adopt_cache": True,
                                  "skip_byte_cache": True}
            # Default members (tests, duck-typed handlers) keep the
            # two-arg call shape.
            spy2 = _Spy()

            class _TwoArg:
                async def render_image_region(self, ctx,
                                              adopt_cache=True):
                    spy2.kwargs = {"adopt_cache": adopt_cache}
                    return b"y"

            member2 = LocalMember("m1", _TwoArg())
            assert await member2.render(_ctx(),
                                        adopt_cache=False) == b"y"
            assert spy2.kwargs == {"adopt_cache": False}

        asyncio.run(main())

    def test_timed_out_dispatch_is_never_rendered(self):
        """A waiter whose budget dies while its unit is QUEUED cancels
        the unit: the lane skips it instead of rendering bytes nobody
        will retrieve."""
        from omero_ms_image_region_tpu.utils import transient

        async def main():
            router, handlers = _fleet(1, lane_width=1, delay_s=0.15)
            try:
                blocker = asyncio.create_task(
                    router.dispatch(_ctx(c="1|1:60000$FF0000")))
                await asyncio.sleep(0.02)   # lane busy on blocker
                with transient.deadline_scope(30):
                    with pytest.raises(
                            transient.DeadlineExceededError):
                        await router.dispatch(
                            _ctx(c="1|2:60000$FF0000"))
                await blocker
                await asyncio.sleep(0.05)   # lane drains the queue
                # Only the blocker ever rendered.
                assert len(handlers[0].calls) == 1
            finally:
                await router.close()

        asyncio.run(main())

    def test_all_members_down_surfaces_connection_error(self):
        """Total fleet death maps to the ConnectionError -> 503
        contract, never an unroutable internal error."""
        async def main():
            router, handlers = _fleet(2)
            for h in handlers:
                h.die_after = 0
            try:
                with pytest.raises(ConnectionError):
                    await router.dispatch(_ctx())
            finally:
                await router.close()

        asyncio.run(main())


# ----------------------------------------------------- fleet-wide tiers

class TestFleetImageHandler:
    def setup_method(self):
        telemetry.reset()

    def test_single_flight_coalesces_fleet_wide(self):
        """Identical renders coalesce ABOVE the router: one member
        executes once, every waiter shares the bytes."""
        from omero_ms_image_region_tpu.server.singleflight import (
            SingleFlight)

        async def main():
            router, handlers = _fleet(4, delay_s=0.02)
            handler = FleetImageHandler(router,
                                        single_flight=SingleFlight())
            try:
                ctx = _ctx()
                out = await asyncio.gather(
                    *(handler.render_image_region(ctx)
                      for _ in range(8)))
                assert len(set(out)) == 1
                assert sum(len(h.calls) for h in handlers) == 1
            finally:
                await router.close()

        asyncio.run(main())

    def test_admission_sees_total_fleet_depth(self):
        """The router IS the admission controller's renderer: its
        queue_depth() spans every member, so shedding triggers on the
        fleet's total backlog."""
        from omero_ms_image_region_tpu.server.admission import (
            AdmissionController)
        from omero_ms_image_region_tpu.server.errors import (
            OverloadedError)

        async def main():
            router, _ = _fleet(2, lane_width=1, delay_s=0.05)
            admission = AdmissionController(2, renderer=router)
            handler = FleetImageHandler(router, admission=admission)
            try:
                out = await asyncio.gather(
                    *(handler.render_image_region(_ctx(
                        c=f"1|{i}:60000$FF0000")) for i in range(6)),
                    return_exceptions=True)
                served = [r for r in out if isinstance(r, bytes)]
                shed = [r for r in out
                        if isinstance(r, OverloadedError)]
                # The bound is FLEET-wide: 2 admitted across both
                # members (each member's own queue never filled), the
                # rest shed 503+Retry-After.
                assert len(served) >= 2
                assert shed, out
                assert all(isinstance(r, (bytes, OverloadedError))
                           for r in out)
            finally:
                await router.close()

        asyncio.run(main())


    def test_combined_acl_gates_every_coalesced_caller(self,
                                                       monkeypatch):
        """The render_identity_key contract: ACL gates PER CALLER
        before the shared render is awaited — a follower session that
        cannot read the image gets its 404 even while an authorized
        leader's render is in flight."""
        from omero_ms_image_region_tpu.server import handler as hmod
        from omero_ms_image_region_tpu.server.errors import (
            NotFoundError)
        from omero_ms_image_region_tpu.server.singleflight import (
            SingleFlight)

        class _NoCache:
            async def get(self, key):
                return None

        class _Services:
            class caches:
                image_region = _NoCache()

        async def fake_can_read(services, object_type, object_id,
                                session_key):
            return session_key != "intruder"

        monkeypatch.setattr(hmod, "check_can_read", fake_can_read)

        async def main():
            router, handlers = _fleet(2, delay_s=0.05)
            fleet_handler = FleetImageHandler(
                router, single_flight=SingleFlight(),
                base_services=_Services())
            try:
                allowed = _ctx()
                allowed.omero_session_key = "viewer"
                denied = _ctx()
                denied.omero_session_key = "intruder"
                leader = asyncio.create_task(
                    fleet_handler.render_image_region(allowed))
                await asyncio.sleep(0.01)   # leader render in flight
                with pytest.raises(NotFoundError):
                    await fleet_handler.render_image_region(denied)
                assert await leader
                # The denied caller never reached a member.
                assert sum(len(h.calls) for h in handlers) == 1
            finally:
                await router.close()

        asyncio.run(main())

    def test_proxy_fleet_coalesces_per_session_only(self):
        """A proxy fleet (no local ACL services) folds the session
        into the single-flight key: identical renders from DIFFERENT
        sessions each reach a member (whose sidecar runs the full ACL
        gate on its own ctx); same-session duplicates still coalesce."""
        from omero_ms_image_region_tpu.server.singleflight import (
            SingleFlight)

        async def main():
            router, handlers = _fleet(2, delay_s=0.03)
            fleet_handler = FleetImageHandler(
                router, single_flight=SingleFlight())
            try:
                def ctx_for(session):
                    c = _ctx()
                    c.omero_session_key = session
                    return c

                out = await asyncio.gather(
                    fleet_handler.render_image_region(ctx_for("a")),
                    fleet_handler.render_image_region(ctx_for("a")),
                    fleet_handler.render_image_region(ctx_for("b")))
                assert all(out)
                # Two member renders: sessions a (coalesced x2) + b.
                assert sum(len(h.calls) for h in handlers) == 2
            finally:
                await router.close()

        asyncio.run(main())

    def test_total_fleet_death_serves_degraded_fallback(self):
        """With every member gone, a configured DegradedCpuHandler
        keeps tiles servable — but a LIVE fleet's errors never fall
        back."""
        class _Fallback:
            def __init__(self):
                self.calls = 0

            async def render_image_region(self, ctx):
                self.calls += 1
                return b"degraded-bytes"

        async def main():
            router, handlers = _fleet(2)
            fallback = _Fallback()
            fleet_handler = FleetImageHandler(router,
                                              fallback=fallback)
            try:
                for m in router.members.values():
                    m.mark_down()
                out = await fleet_handler.render_image_region(_ctx())
                assert out == b"degraded-bytes"
                assert fallback.calls == 1
                # Fleet back: members serve, fallback stays cold.
                for m in router.members.values():
                    m.revive()
                out = await fleet_handler.render_image_region(_ctx())
                assert out != b"degraded-bytes"
                assert fallback.calls == 1
            finally:
                await router.close()

        asyncio.run(main())


# ------------------------------------------- hot-plane replication

class TestHotPlaneReplication:
    """Lifecycle property drill for popularity-aware placement: a
    route promoted past the heat threshold gets a DETERMINISTIC ring-
    chain prefix as its replica set, demotion is hysteretic and driven
    by the live dispatch path, re-promotion reuses the identical
    prefix, and the per-epoch staging guard never double-stages.  The
    ring goldens above stay the authority on WHERE the prefix points —
    these tests only consume ``chain()``, never re-derive it."""

    def setup_method(self):
        telemetry.reset()
        decisions.LEDGER.reset()

    def teardown_method(self):
        decisions.LEDGER.reset()

    def _hot_fleet(self, n=4, threshold=5.0, decay_s=10.0, **kw):
        handlers = [_FakeHandler(f"m{i}") for i in range(n)]
        members = [LocalMember(f"m{i}", handlers[i])
                   for i in range(n)]
        clk = {"t": 0.0}
        router = FleetRouter(
            members, lane_width=1, steal_min_backlog=0,
            hotkey=HotkeyConfig(enabled=True, threshold=threshold,
                                decay_s=decay_s, max_replicas=2,
                                **kw))
        # Injectable heat clock: the whole thermal trajectory —
        # promotion, hysteresis, re-promotion — is deterministic.
        router._heat.clock = lambda: clk["t"]
        return router, handlers, clk

    def test_promote_demote_repromote_deterministic(self):
        async def main():
            router, handlers, clk = self._hot_fleet()
            try:
                hot = _ctx()
                cool = _ctx(tile="0,2,2,128,128")
                route = plane_route_key(hot)
                chain = router.ring.chain(route)
                # Below threshold: nothing promotes.
                for _ in range(4):
                    await router.dispatch(hot)
                assert not router.is_hot_route(route)
                assert router.replica_set(route) == chain[:1]
                # The 5th observation crosses threshold=5: the route
                # gets exactly the 2-member chain prefix, owner first.
                await router.dispatch(hot)
                assert router.is_hot_route(route)
                first = router.replica_set(route)
                assert first == chain[:2]
                assert router.replica_pressure() >= 1.0
                await asyncio.gather(          # let the stage task run
                    *list(router._putback_tasks),
                    return_exceptions=True)
                # Hysteresis: at demote_fraction=0.5 the route stays
                # promoted while heat > 2.5 (5 * e^-0.5 ~ 3.03)...
                clk["t"] = 5.0
                await router.dispatch(cool)
                assert router.is_hot_route(route)
                # ...and the LIVE dispatch path demotes it once decay
                # crosses under (5 * e^-0.8 ~ 2.25 at t=8).
                clk["t"] = 8.0
                await router.dispatch(cool)
                assert not router.is_hot_route(route)
                assert router.replica_set(route) == chain[:1]
                # Re-promotion from the residual heat rebuilds the
                # IDENTICAL prefix — replicas never wander.
                for _ in range(3):
                    await router.dispatch(hot)
                assert router.is_hot_route(route)
                assert router.replica_set(route) == first
                await asyncio.gather(*list(router._putback_tasks),
                                     return_exceptions=True)
                totals = telemetry.HOTKEY.totals()
                assert totals["promoted"] == 2
                assert totals["demoted"] == 1
                # The full promote/demote/re-promote cycle never
                # double-stages a (route, replica) pair...
                assert totals["duplicate_staged"] == 0
                # ...and a forced second stage inside one epoch trips
                # the guard instead of re-shipping the slice.
                await router._stage_replicas(route, first)
                assert telemetry.HOTKEY.totals()[
                    "duplicate_staged"] == len(first) - 1
                # Both transitions are on the decision ledger.
                ledger = decisions.LEDGER.snapshot()
                verdicts = [r["verdict"] for r in ledger
                            if r["kind"] == "hotkey"]
                assert verdicts.count("promoted") == 2
                assert verdicts.count("demoted") == 1
            finally:
                await router.close()

        asyncio.run(main())

    def test_unroutable_replicas_drop_within_one_transition(self):
        """Drains and deaths fall out of the balanced read set on the
        very NEXT routing decision — no grace window in which reads
        keep landing on a member that can no longer serve them."""
        async def main():
            router, handlers, clk = self._hot_fleet()
            try:
                hot = _ctx()
                route = plane_route_key(hot)
                for _ in range(5):
                    await router.dispatch(hot)
                owner, replica = router.replica_set(route)
                # Idle fleet: ties break in chain order, owner wins.
                assert router._serving_member(route) == owner
                # Draining replica: immediately out of the read set.
                router.members[replica].draining = True
                assert router._serving_member(route) == owner
                router.members[replica].draining = False
                # Dead owner: the surviving replica serves reads.
                router.members[owner].mark_down()
                assert router._serving_member(route) == replica
                # Whole replica set unroutable: plain chain walk, so
                # deaths degrade exactly like an unpromoted route.
                router.members[replica].mark_down()
                assert router._serving_member(route) \
                    == router.ring.chain(route)[2]
                # Promotion state itself is untouched by the outage.
                assert router.is_hot_route(route)
            finally:
                await router.close()

        asyncio.run(main())

    def test_shed_replicas_demotes_everything(self):
        """The cache-pressure ladder's hook: one call returns the
        fleet to R=1 everywhere (HBM reclaim itself is the eviction
        ladder's job — shedding only removes the routing protection)."""
        async def main():
            router, handlers, clk = self._hot_fleet()
            try:
                a, b = _ctx(), _ctx(z="3")
                for _ in range(5):
                    await router.dispatch(a)
                    await router.dispatch(b)
                assert router.hot_route_count() == 2
                assert router.shed_replicas() == 2
                assert router.hot_route_count() == 0
                assert router.replica_set(plane_route_key(a)) \
                    == router.ring.chain(plane_route_key(a))[:1]
                # Re-heating re-promotes cleanly after a shed.
                for _ in range(5):
                    await router.dispatch(a)
                assert router.is_hot_route(plane_route_key(a))
                assert telemetry.HOTKEY.totals()[
                    "duplicate_staged"] == 0
            finally:
                await router.close()

        asyncio.run(main())


# ------------------------------------------------------------ telemetry

class TestFleetTelemetry:
    def setup_method(self):
        telemetry.reset()

    def test_metric_lines_and_exposition(self):
        async def main():
            router, _ = _fleet(3)
            try:
                await router.dispatch(_ctx())
                router.members["m1"].mark_down()
                lines = telemetry.fleet_metric_lines(router)
                text = telemetry.finalize_exposition(lines)
                assert "imageregion_fleet_members 3" in text
                assert "imageregion_fleet_members_healthy 2" in text
                assert ('imageregion_fleet_member_healthy'
                        '{member="m1"} 0') in text
                assert 'imageregion_fleet_routed_total{member=' in text
                # Every family annotated exactly once.
                for fam in ("imageregion_fleet_members",
                            "imageregion_fleet_member_depth",
                            "imageregion_fleet_routed_total"):
                    assert text.count(f"# TYPE {fam} ") == 1
                    assert text.count(f"# HELP {fam} ") == 1
            finally:
                await router.close()

        asyncio.run(main())

    def test_member_label_cardinality_bounded(self):
        for i in range(200):
            telemetry.FLEET.count_routed(f"bogus-{i}")
        assert len(telemetry.FLEET.routed) \
            <= telemetry.FleetStats._MAX_MEMBERS + 1
        assert telemetry.FLEET.routed.get("_overflow", 0) > 0

    def test_reset_clears_fleet_counters(self):
        telemetry.FLEET.count_routed("m0")
        telemetry.FLEET.count_stolen("m1")
        telemetry.FLEET.count_failed_over("m2")
        telemetry.reset()
        assert telemetry.FLEET.totals() == {
            "routed": 0, "stolen": 0, "failed_over": 0}
        assert telemetry.FLEET.metric_lines() == []
