"""Elastic fleet autoscaler (server.autoscaler).

Three layers:

* POLICY — hysteresis/hold/cooldown/floor/ceiling over a fake router
  (pure decisions, injectable clock).
* SAFETY — the floor invariant property-tested over the REAL
  ``FleetRouter`` with seeded random trajectories of concurrent
  scale-down ticks, member deaths/revivals and operator drains: the
  number of non-draining members never goes below the floor, no
  member is double-drained, and operator drains are never undrained
  by the controller.
* THE DRILL — a real 3-member fleet under open-loop load-model
  bursts: scale down to the floor, joiners come back WARM
  (pre-stage-back asserted member by member), a full grow-and-shrink
  cycle with ZERO 5xx-without-shed, and no flapping beyond the
  cooldown bound.
"""

import asyncio
import os
import random
import tempfile

import numpy as np
import pytest

from omero_ms_image_region_tpu.flagship import synthetic_wsi_tiles
from omero_ms_image_region_tpu.io.devicecache import DeviceRawCache
from omero_ms_image_region_tpu.io.store import build_pyramid
from omero_ms_image_region_tpu.parallel.fleet import (
    FleetImageHandler, FleetRouter, LocalMember, build_local_members)
from omero_ms_image_region_tpu.server.admission import (
    AdmissionController)
from omero_ms_image_region_tpu.server.app import build_services
from omero_ms_image_region_tpu.server.autoscaler import Autoscaler
from omero_ms_image_region_tpu.server.config import (AppConfig,
                                                     BatcherConfig,
                                                     RawCacheConfig,
                                                     RendererConfig)
from omero_ms_image_region_tpu.server.ctx import ImageRegionCtx
from omero_ms_image_region_tpu.server.singleflight import SingleFlight
from omero_ms_image_region_tpu.services.loadmodel import (
    LoadModel, run_open_loop)
from omero_ms_image_region_tpu.utils import telemetry

GRID = 4
EDGE = 64


@pytest.fixture(autouse=True)
def _fresh():
    telemetry.reset()
    yield
    telemetry.reset()


@pytest.fixture(scope="module")
def data_dir():
    rng = np.random.default_rng(33)
    with tempfile.TemporaryDirectory() as tmp:
        planes = synthetic_wsi_tiles(
            rng, 2, 1, GRID * EDGE, GRID * EDGE).reshape(
            2, 1, GRID * EDGE, GRID * EDGE)
        build_pyramid(planes, os.path.join(tmp, "1"), n_levels=1)
        yield tmp


# ------------------------------------------------------------ fakes

class _FakeClock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


class _FakeMember:
    remote = False

    def __init__(self, name):
        self.name = name
        self.healthy = True
        self.draining = False
        self.drain_intent = None


class _FakeRouter:
    """Pure-policy router: membership flags + a settable depth."""

    def __init__(self, n, lane_width=2):
        self.order = [f"m{i}" for i in range(n)]
        self.members = {name: _FakeMember(name) for name in self.order}
        self.lane_width = lane_width
        self.depth = 0
        self.drains = []
        self.undrains = []

    def queue_depth(self):
        return self.depth

    def member_capacity(self, name):
        return self.lane_width

    async def drain_member(self, name, intent="operator", **_kw):
        member = self.members[name]
        member.draining = True
        member.drain_intent = intent
        self.drains.append((name, intent))
        await asyncio.sleep(0)
        return {"member": name, "intent": intent}

    def undrain_member(self, name):
        member = self.members[name]
        member.draining = False
        member.drain_intent = None
        self.undrains.append(name)

    def draining_members(self, intent=None):
        return [n for n in self.order
                if self.members[n].draining
                and (intent is None
                     or self.members[n].drain_intent == intent)]


def _config(**overrides):
    raw = {"fleet": {"enabled": True, "members": 3},
           "autoscaler": {"enabled": True, "hold-ticks": 2,
                          "cooldown-s": 30,
                          "queue-high-per-lane": 3,
                          "queue-low-per-lane": 0.5,
                          **overrides}}
    return AppConfig.from_dict(raw).autoscaler


async def _ticks(scaler, n, advance=None, clock=None):
    out = []
    for _ in range(n):
        if advance is not None:
            clock.advance(advance)
        out.append(scaler.tick())
        await scaler.wait_op()
    return out


class TestPolicy:
    def test_hold_then_scale_down_with_autoscale_intent(self):
        async def main():
            clock = _FakeClock()
            router = _FakeRouter(3)
            scaler = Autoscaler(_config(), router, clock=clock)
            # depth 0 <= low watermark: wants down, held one tick.
            assert scaler.tick() is None
            verdict = scaler.tick()
            await scaler.wait_op()
            assert verdict == "down"
            assert router.drains == [("m2", "autoscale")]
            assert router.members["m2"].draining
            assert scaler.active_members() == ["m0", "m1"]
            assert telemetry.AUTOSCALER.transitions == {"down": 1}
            kinds = [e["kind"] for e in telemetry.FLIGHT.snapshot()]
            assert "autoscale.down" in kinds

        asyncio.run(main())

    def test_cooldown_blocks_consecutive_transitions(self):
        async def main():
            clock = _FakeClock()
            router = _FakeRouter(3)
            scaler = Autoscaler(_config(floor=1), router, clock=clock)
            assert (await _ticks(scaler, 2))[-1] == "down"
            # Still under cooldown: the next sustained want is refused.
            assert (await _ticks(scaler, 2))[-1] == "blocked:cooldown"
            clock.advance(31)
            # The held streak transitions on the first post-cooldown
            # tick.
            assert "down" in await _ticks(scaler, 2)
            assert telemetry.AUTOSCALER.blocked.get("cooldown") == 1

        asyncio.run(main())

    def test_floor_blocks_the_last_members(self):
        async def main():
            clock = _FakeClock()
            router = _FakeRouter(2)
            scaler = Autoscaler(_config(floor=2), router, clock=clock)
            verdicts = await _ticks(scaler, 3)
            assert "down" not in verdicts
            assert verdicts[-1] == "blocked:floor"
            assert router.drains == []

        asyncio.run(main())

    def test_scale_up_rejoins_the_last_parked_member(self):
        async def main():
            clock = _FakeClock()
            router = _FakeRouter(3)
            scaler = Autoscaler(_config(), router, clock=clock)
            await _ticks(scaler, 2)                 # down: m2
            clock.advance(31)
            await _ticks(scaler, 2)                 # down: m1
            clock.advance(31)
            router.depth = 100                      # lanes saturate
            verdict = (await _ticks(scaler, 2))[-1]
            assert verdict == "up"
            assert router.undrains == ["m1"]        # LIFO rejoin
            clock.advance(31)
            assert (await _ticks(scaler, 2))[-1] == "up"
            assert router.undrains == ["m1", "m2"]
            assert telemetry.AUTOSCALER.transitions == {"down": 2,
                                                        "up": 2}

        asyncio.run(main())

    def test_ceiling_blocks_growth(self):
        async def main():
            clock = _FakeClock()
            router = _FakeRouter(3)
            scaler = Autoscaler(_config(ceiling=3), router,
                                clock=clock)
            router.depth = 100
            assert (await _ticks(scaler, 2))[-1] == "blocked:ceiling"

        asyncio.run(main())

    def test_never_undrains_an_operator_drain(self):
        async def main():
            clock = _FakeClock()
            router = _FakeRouter(3)
            scaler = Autoscaler(_config(), router, clock=clock)
            # Operator parks m2 out-of-band.
            await router.drain_member("m2", intent="operator")
            router.depth = 100
            verdict = (await _ticks(scaler, 2))[-1]
            assert verdict == "blocked:no-member"
            assert router.undrains == []

        asyncio.run(main())

    def test_pressure_critical_wants_up(self):
        class _Gov:
            level = 2

        async def main():
            clock = _FakeClock()
            router = _FakeRouter(3)
            scaler = Autoscaler(_config(), router, governor=_Gov(),
                                clock=clock)
            await router.drain_member("m2", intent="autoscale")
            scaler._scaled_down.append("m2")
            # Queue is empty but the governor reads critical: grow.
            assert (await _ticks(scaler, 2))[-1] == "up"

        asyncio.run(main())

    def test_demand_signal_scales_both_ways(self):
        async def main():
            clock = _FakeClock()
            router = _FakeRouter(3)
            demand = {"tps": 0.0}
            scaler = Autoscaler(
                _config(**{"lane-capacity-tps": 10}), router,
                demand_source=lambda: demand["tps"], clock=clock)
            # Predicted demand over routable capacity (3*2*10=60):
            # scale up even with an empty queue... but nothing is
            # parked yet, so the refusal names the reason.
            demand["tps"] = 100.0
            # Every member already active: the growth want forms
            # (queue is empty — only demand drives it) and stops at
            # the ceiling.
            assert (await _ticks(scaler, 2))[-1] == "blocked:ceiling"
            # Demand under the post-shrink capacity: down proceeds.
            demand["tps"] = 20.0
            clock.advance(31)
            assert (await _ticks(scaler, 2))[-1] == "down"
            # Demand above post-shrink capacity: down refused (the
            # want never forms, so the verdict is steady None).
            clock.advance(31)
            demand["tps"] = 35.0
            assert (await _ticks(scaler, 3)) == [None, None, None]

        asyncio.run(main())

    def test_status_doc(self):
        async def main():
            clock = _FakeClock()
            router = _FakeRouter(3)
            scaler = Autoscaler(_config(), router, clock=clock)
            await _ticks(scaler, 2)
            doc = scaler.status()
            assert doc["floor"] == 1 and doc["ceiling"] == 3
            assert doc["active"] == ["m0", "m1"]
            assert doc["autoscale_drained"] == ["m2"]
            assert doc["cooldown_remaining_s"] > 0
            assert doc["transitions"][-1]["action"] == "down"
            assert "queue_per_lane" in doc["signals"]

        asyncio.run(main())


# ------------------------------------------------- floor property test

class _StubMember:
    """Minimal member for the REAL FleetRouter: membership,
    drain-handoff and shard surfaces only (no rendering)."""

    remote = False

    def __init__(self, name):
        self.name = name
        self.healthy = True
        self.draining = False
        self.drain_intent = None

    def mark_down(self):
        self.healthy = False

    def revive(self):
        self.healthy = True

    def queue_depth(self):
        return 0

    def resident_digests(self):
        return set()

    def resident_planes(self):
        return 0

    async def shard_manifest(self, limit=0):
        return []

    async def prestage_manifest(self, entries):
        return 0


class _DepthRouter(FleetRouter):
    """Real router with a settable queue-depth reading (the policy
    signal) — drain/undrain/membership stay the real code paths."""

    depth_override = 0

    def queue_depth(self):
        return self.depth_override


class TestFloorProperty:
    def test_floor_holds_under_concurrent_ticks_and_deaths(self):
        """Seeded random trajectories: bursts of ticks WITHOUT
        awaiting the drain op (concurrent-tick races), random member
        deaths/revivals, random operator drains/undrains, random
        queue spikes.  Invariants at EVERY step: non-draining members
        never fall below the floor; a member is never drained twice
        concurrently; operator drains stay drained."""
        rng = random.Random(2026)

        async def trial(trial_i):
            n = rng.choice((2, 3, 4, 5))
            floor = rng.randrange(1, n)
            members = [_StubMember(f"m{i}") for i in range(n)]
            router = _DepthRouter(members, lane_width=2,
                                  steal_min_backlog=0)
            clock = _FakeClock()
            scaler = Autoscaler(
                _config(floor=floor, **{"hold-ticks": 1,
                                        "cooldown-s": 0}),
                router, clock=clock,
                drain_kwargs={"prestage": False,
                              "settle_timeout_s": 0.2})
            operator_drained = set()
            downs = 0
            try:
                for _ in range(80):
                    move = rng.random()
                    name = rng.choice(router.order)
                    member = router.members[name]
                    if move < 0.15:
                        member.mark_down()
                    elif move < 0.30:
                        member.revive()
                    elif move < 0.40 and not member.draining:
                        # Model the /admin/drain guard: operators
                        # cannot drain the last routable member.
                        if [m for m in router.order
                                if router._routable(m)
                                and m != name]:
                            await router.drain_member(
                                name, prestage=False,
                                settle_timeout_s=0.2)
                            operator_drained.add(name)
                    elif move < 0.45 and name in operator_drained:
                        router.undrain_member(name)
                        operator_drained.discard(name)
                    elif move < 0.55:
                        router.depth_override = rng.choice(
                            (0, 0, 200))
                    else:
                        for _ in range(rng.randrange(1, 4)):
                            clock.advance(1)
                            verdict = scaler.tick()
                            if verdict == "down":
                                downs += 1
                                # THE floor property: every down the
                                # CONTROLLER issues leaves at least
                                # ``floor`` members active AND
                                # routable, whatever the operator and
                                # the deaths did around it.
                                active_now = [
                                    m for m in router.order
                                    if not router.members[m]
                                    .draining]
                                routable_now = [
                                    m for m in active_now
                                    if router.members[m].healthy]
                                assert len(active_now) >= floor, \
                                    f"trial {trial_i}: down " \
                                    f"breached the active floor"
                                assert len(routable_now) >= floor, \
                                    f"trial {trial_i}: down " \
                                    f"breached the routable floor"
                        await scaler.wait_op()
                    # ---- invariants, checked EVERY step ----
                    active = [m for m in router.order
                              if not router.members[m].draining]
                    if not operator_drained:
                        # With no operator interference the global
                        # bound holds outright (operators may
                        # legitimately park past the autoscaler's
                        # floor — the controller just never helps).
                        assert len(active) >= floor, \
                            f"trial {trial_i}: floor breached"
                    assert len(router.draining_members()) == len(
                        set(router.draining_members()))
                    for op_name in operator_drained:
                        # The controller never resurrects an
                        # operator's drain.
                        assert (router.members[op_name].draining
                                or op_name not in
                                scaler._scaled_down), \
                            f"trial {trial_i}: operator drain undone"
                        assert router.members[op_name] \
                            .drain_intent != "autoscale" \
                            or not router.members[op_name].draining
            finally:
                await scaler.wait_op()
                await router.close()
            return downs

        total_downs = 0
        for trial_i in range(12):
            total_downs += asyncio.run(trial(trial_i))
        # The trajectories really exercised scale-downs (a vacuous
        # pass would prove nothing).
        assert total_downs > 5


# ------------------------------------------------------------ the drill

class TestElasticityDrill:
    def test_full_grow_and_shrink_cycle_with_warm_joiners(
            self, data_dir):
        """THE acceptance drill: idle -> scale down to the floor ->
        open-loop burst (load model arrivals) grows the fleet back
        member by member, each joiner provably WARM (pre-stage-back:
        its drained shard is HBM-resident again, and its first owned
        requests hit >= 0.8) -> quiet -> shrink back to the floor.
        Zero 5xx-without-shed across the whole drill; transitions
        bounded by the cooldown (no flapping)."""
        exec_ms = 50.0
        cooldown = 60.0

        class VirtualDeviceMember(LocalMember):
            async def render(self, ctx, adopt_cache=True):
                data = await super().render(ctx, adopt_cache)
                await asyncio.sleep(exec_ms / 1000.0)
                return data

        def working_set():
            out = []
            for v in range(2):
                for x in range(GRID):
                    for y in range(GRID):
                        w = 30000 + v * 800
                        out.append(ImageRegionCtx.from_params({
                            "imageId": "1", "theZ": "0", "theT": "0",
                            "tile": f"0,{x},{y},{EDGE},{EDGE}",
                            "format": "png", "m": "c",
                            "c": f"1|0:{w}$FF0000,"
                                 f"2|0:{w - 700}$00FF00",
                        }))
            return out

        model = LoadModel(viewers=48, seed=37, duration_s=60.0,
                          grid=GRID, diurnal_amplitude=0.0,
                          bulk_fraction=0.0, mask_fraction=0.0,
                          zoom_fraction=0.0)
        natural = model.events()

        async def drill():
            config = AppConfig(
                data_dir=data_dir,
                batcher=BatcherConfig(enabled=False),
                raw_cache=RawCacheConfig(enabled=True,
                                         prefetch=False),
                renderer=RendererConfig(cpu_fallback_max_px=0))
            services = build_services(config)
            members = [VirtualDeviceMember(
                m.name, m.handler, m.services,
                down_cooldown_s=m.down_cooldown_s,
                byte_cache_prechecked=m.byte_cache_prechecked)
                for m in build_local_members(config, services, 3)]
            router = FleetRouter(members, lane_width=2,
                                 steal_min_backlog=0)
            handler = FleetImageHandler(
                router, single_flight=SingleFlight(),
                admission=AdmissionController(4096, renderer=router),
                base_services=services)
            clock = _FakeClock()
            scaler = Autoscaler(
                _config(floor=1, **{
                    "hold-ticks": 1,
                    "cooldown-s": cooldown,
                    "queue-high-per-lane": 2.0,
                    "queue-low-per-lane": 0.25,
                }), router, clock=clock,
                drain_kwargs={"prestage": True, "max_planes": 256,
                              "settle_timeout_s": 10.0})

            async def submit(arrival):
                sid = int(arrival.session.rsplit("-", 1)[1])
                w = 21000 + (sid * 131 + arrival.step * 37) % 18000
                ctx = ImageRegionCtx.from_params({
                    "imageId": "1", "theZ": "0", "theT": "0",
                    "tile": f"0,{arrival.x},{arrival.y},{EDGE},"
                            f"{EDGE}",
                    "format": "png", "m": "c",
                    "c": f"1|0:{w}$FF0000,2|0:{w - 900}$00FF00",
                })
                ctx.omero_session_key = arrival.session
                out = await handler.render_image_region(ctx)
                assert out

            reports = []
            try:
                working = working_set()
                # Warm the whole working set: every member's shard
                # holds planes to hand over.
                await asyncio.gather(*(
                    handler.render_image_region(c) for c in working))
                shard_at_drain = {}

                # ---- RAMP DOWN to the floor (quiet fleet) ----
                for expect in ("m2", "m1"):
                    clock.advance(cooldown + 1)
                    shard_at_drain[expect] = set(
                        router.members[expect].resident_digests())
                    verdict = scaler.tick()
                    await scaler.wait_op()
                    assert verdict == "down", verdict
                    assert router.members[expect].draining
                    assert router.members[expect].drain_intent == \
                        "autoscale"
                clock.advance(cooldown + 1)
                assert scaler.tick() == "blocked:floor"
                assert scaler.active_members() == ["m0"]

                # "Restart" the parked members: cold HBM (exactly
                # what a real scale-down teardown drops).
                for name in ("m1", "m2"):
                    member = router.members[name]
                    member.services.raw_cache = DeviceRawCache(
                        member.services.raw_cache.max_bytes)

                # ---- RAMP UP: open-loop bursts grow the fleet ----
                # member by member; each joiner must come back WARM.
                for expect in ("m1", "m2"):
                    nominal_m0 = 2 * 1000.0 / exec_ms     # 40 tps
                    burst = model.window(3.0 * nominal_m0, 2.0,
                                         natural)
                    burst_task = asyncio.create_task(
                        run_open_loop(submit, burst))
                    grown = None
                    for _ in range(400):
                        # Tick only once the queue signal is live:
                        # the drill's fake clock jumps past the
                        # cooldown per tick, so an empty-queue tick
                        # between bursts would read as a sustained
                        # quiet period and scale DOWN mid-ramp.
                        if router.queue_depth() >= 2 * 2 * 2:
                            clock.advance(cooldown + 1)
                            verdict = scaler.tick()
                            if verdict == "up":
                                grown = verdict
                                break
                        await asyncio.sleep(0.01)
                    assert grown == "up", "burst never grew the fleet"
                    assert not router.members[expect].draining
                    reports.append(await burst_task)
                    # Pre-stage-back: the drain-time shard manifest
                    # replayed into the joiner — resident BEFORE we
                    # measure its first owned requests.
                    task = router.last_undrain_prestage
                    assert task is not None, \
                        f"{expect}: no pre-stage-back scheduled"
                    await task
                    member = router.members[expect]
                    back = set(member.resident_digests())
                    assert shard_at_drain[expect] <= back, \
                        f"{expect}: rejoined cold " \
                        f"({len(back)}/{len(shard_at_drain[expect])})"
                    # Warm-hit rate on the joiner's owned working
                    # set (quiet fleet — the burst settled above).
                    owned = [c for c in working
                             if router.owner_of(c) == expect]
                    if owned:
                        hits_before = member.services.raw_cache.hits
                        for c in owned:
                            await handler.render_image_region(c)
                        rate = (member.services.raw_cache.hits
                                - hits_before) / len(owned)
                        assert rate >= 0.8, \
                            f"{expect}: warm-hit {rate:.2f} < 0.8"

                # ---- RAMP DOWN again (the shrink half) ----
                for _ in range(2):
                    clock.advance(cooldown + 1)
                    verdict = scaler.tick()
                    await scaler.wait_op()
                    assert verdict == "down", verdict
                assert scaler.active_members() == ["m0"]
            finally:
                await router.close()
                services.pixels_service.close()
            return scaler, reports

        scaler, reports = asyncio.run(drill())
        # Zero 5xx-without-shed across every open-loop burst (with
        # the admission bound this high, zero sheds too).
        for report in reports:
            assert report.errors == [], report.errors[:3]
            assert report.sheds == 0
            assert report.served > 0
        # One full grow-and-shrink cycle, exactly — flapping bounded
        # by the cooldown: every consecutive transition pair is
        # separated by at least the cooldown on the policy clock.
        actions = [t["action"] for t in scaler.transitions]
        assert actions == ["down", "down", "up", "up", "down", "down"]
        times = [t["t"] for t in scaler.transitions]
        assert all(b - a >= cooldown
                   for a, b in zip(times, times[1:]))
        assert telemetry.AUTOSCALER.transitions == {"down": 4,
                                                    "up": 2}
        kinds = [e["kind"] for e in telemetry.FLIGHT.snapshot()]
        assert "autoscale.down" in kinds and "autoscale.up" in kinds


# -------------------------------------------------- app-level surfaces

def _app_config(data_dir, **autoscaler_overrides):
    config = AppConfig.from_dict({
        "data-dir": data_dir,
        "batcher": {"enabled": False},
        "raw-cache": {"enabled": True, "prefetch": False},
        "renderer": {"cpu-fallback-max-px": 0},
        "fleet": {"enabled": True, "members": 2},
        "autoscaler": {"enabled": True, "interval-s": 30,
                       **autoscaler_overrides},
    })
    return config


class TestAppSurfaces:
    def test_admin_autoscaler_status_endpoint(self, data_dir):
        from aiohttp.test_utils import TestClient, TestServer

        from omero_ms_image_region_tpu.server.app import create_app

        async def scenario():
            client = TestClient(TestServer(
                create_app(_app_config(data_dir))))
            await client.start_server()
            try:
                r = await client.get("/admin/autoscaler")
                assert r.status == 200
                doc = await r.json()
                assert doc["enabled"] is True
                assert doc["floor"] == 1 and doc["ceiling"] == 2
                assert doc["active"] == ["m0", "m1"]
                assert "queue_per_lane" in doc["signals"]
                # /readyz carries the controller annotation.
                body = await (await client.get("/readyz")).json()
                assert body["checks"]["autoscaler"] == \
                    "2/2 active (floor 1)"
            finally:
                await client.close()

        asyncio.run(scenario())

    def test_autoscaler_disabled_answers_400(self, data_dir):
        from aiohttp.test_utils import TestClient, TestServer

        from omero_ms_image_region_tpu.server.app import create_app

        async def scenario():
            config = AppConfig(
                data_dir=data_dir,
                batcher=BatcherConfig(enabled=False),
                raw_cache=RawCacheConfig(enabled=True,
                                         prefetch=False),
                renderer=RendererConfig(cpu_fallback_max_px=0))
            client = TestClient(TestServer(create_app(config)))
            await client.start_server()
            try:
                r = await client.get("/admin/autoscaler")
                assert r.status == 400
            finally:
                await client.close()

        asyncio.run(scenario())

    def test_autoscale_drain_never_trips_fail_readyz(self, data_dir):
        """THE drain-flavor satellite: with ``drain.fail-readyz`` ON,
        an operator drain answers /readyz 503 (the rolling-restart
        posture) but an AUTOSCALE drain of the same member keeps
        /readyz 200 and annotates — a routine scale-down must not
        read as the instance leaving rotation."""
        from aiohttp.test_utils import TestClient, TestServer

        from omero_ms_image_region_tpu.server.app import (
            FLEET_ROUTER_KEY, create_app)

        async def scenario(intent):
            config = _app_config(data_dir)
            config.drain.fail_readyz = True
            app = create_app(config)
            client = TestClient(TestServer(app))
            await client.start_server()
            try:
                router = app[FLEET_ROUTER_KEY]
                await router.drain_member(
                    "m1", prestage=False, settle_timeout_s=2.0,
                    intent=intent)
                r = await client.get("/readyz")
                body = await r.json()
                status, note = r.status, body["checks"]["drain"]
                router.undrain_member("m1")
                assert (await client.get("/readyz")).status == 200
                return status, note
            finally:
                await client.close()

        status, note = asyncio.run(scenario("operator"))
        assert status == 503 and note == "draining: m1"
        status, note = asyncio.run(scenario("autoscale"))
        assert status == 200
        assert note == "draining: m1(autoscale)"

    def test_drain_status_carries_the_intent(self, data_dir):
        from aiohttp.test_utils import TestClient, TestServer

        from omero_ms_image_region_tpu.server.app import (
            FLEET_ROUTER_KEY, create_app)

        async def scenario():
            app = create_app(_app_config(data_dir))
            client = TestClient(TestServer(app))
            await client.start_server()
            try:
                router = app[FLEET_ROUTER_KEY]
                await router.drain_member(
                    "m1", prestage=False, settle_timeout_s=2.0,
                    intent="autoscale")
                doc = await (await client.get("/admin/drain")).json()
                assert doc["members"]["m1"]["intent"] == "autoscale"
                assert doc["members"]["m0"]["intent"] is None
                # Operator undrain reclaims the member: intent clears.
                r = await client.post("/admin/undrain?member=m1")
                doc = await r.json()
                assert doc["members"]["m1"]["intent"] is None
            finally:
                await client.close()

        asyncio.run(scenario())


class TestQuiesceReadyzPosture:
    def test_sigterm_quiesce_still_trips_fail_readyz(self, data_dir):
        """The SIGTERM shutdown chain quiesces members by flipping
        ``draining`` with NO intent — that must keep pulling the
        instance under ``drain.fail-readyz`` exactly like an operator
        drain (only the explicit ``autoscale`` flavor is exempt)."""
        from aiohttp.test_utils import TestClient, TestServer

        from omero_ms_image_region_tpu.server.app import (
            FLEET_ROUTER_KEY, create_app)

        async def scenario():
            config = _app_config(data_dir)
            config.drain.fail_readyz = True
            app = create_app(config)
            client = TestClient(TestServer(app))
            await client.start_server()
            try:
                router = app[FLEET_ROUTER_KEY]
                # The quiesce hook's exact effect (server.shutdown):
                # draining flag only, no intent.
                for name in router.order:
                    router.members[name].draining = True
                assert (await client.get("/readyz")).status == 503
            finally:
                await client.close()

        asyncio.run(scenario())


# ------------------------------------------------ unit lifecycle drill

class _FakeProc:
    def __init__(self):
        self.alive = True
        self.terminated = 0
        self.pid = 4242

    def poll(self):
        return None if self.alive else 0

    def terminate(self):
        self.terminated += 1
        self.alive = False

    def wait(self, timeout=None):
        return 0

    def kill(self):
        self.alive = False


class TestUnitLifecycle:
    """PR 13 follow-on: the supervisor actually STOPS parked sidecar
    units (after their drain settles — the warm handoff needs the
    live process) and RESTARTS them before undrain on scale-up,
    instead of parking pre-provisioned warm processes."""

    def _lifecycle(self, names):
        from omero_ms_image_region_tpu.server.sidecar import (
            SidecarUnit, SidecarUnitLifecycle)
        spawned = []

        def spawn_fn():
            proc = _FakeProc()
            spawned.append(proc)
            return proc

        lc = SidecarUnitLifecycle(
            {n: SidecarUnit(n, spawn_fn) for n in names})
        return lc, spawned

    def test_unit_start_stop_idempotent(self):
        lc, spawned = self._lifecycle(["m0"])
        lc.start("m0")
        lc.start("m0")                      # no double spawn
        assert len(spawned) == 1 and lc.alive("m0")
        lc.stop("m0")
        lc.stop("m0")                       # no double terminate
        assert spawned[0].terminated == 1 and not lc.alive("m0")
        lc.start("m0")                      # restart spawns fresh
        assert len(spawned) == 2
        lc.stop("unknown")                  # unknown member: no-op
        assert telemetry.FLIGHT is not None

    def test_drill_scale_down_stops_unit_scale_up_restarts_first(self):
        """THE drill: park a member -> its drain completes -> its
        PROCESS stops; demand returns -> the unit respawns and only
        then does the member undrain (routes never land on a dead
        socket).  Order is asserted through an event tape."""
        async def main():
            clock = _FakeClock()
            router = _FakeRouter(3)
            lc, spawned = self._lifecycle(router.order)
            lc.start_all()
            assert all(lc.alive(n) for n in router.order)
            tape = []

            real_drain = router.drain_member

            async def drain_spy(name, **kw):
                tape.append(("drain", name))
                return await real_drain(name, **kw)

            router.drain_member = drain_spy
            real_undrain = router.undrain_member
            router.undrain_member = \
                lambda name: (tape.append(("undrain", name)),
                              real_undrain(name))[1]

            unit = lc.units["m2"]
            real_stop, real_start = unit.stop, unit.start
            unit.stop = lambda *a, **k: (tape.append(("stop", "m2")),
                                         real_stop(*a, **k))[1]
            unit.start = lambda: (tape.append(("start", "m2")),
                                  real_start())[1]

            scaler = Autoscaler(_config(), router, lifecycle=lc,
                                clock=clock)
            verdicts = await _ticks(scaler, 2)
            assert verdicts[-1] == "down"
            # The parked member's PROCESS is gone; the others live.
            assert not lc.alive("m2")
            assert lc.alive("m0") and lc.alive("m1")
            assert tape == [("drain", "m2"), ("stop", "m2")]

            clock.advance(31)
            router.depth = 100              # lanes saturate: want up
            verdict = (await _ticks(scaler, 2))[-1]
            assert verdict == "up"
            assert lc.alive("m2")           # respawned
            assert not router.members["m2"].draining
            # Start STRICTLY before undrain.
            assert tape == [("drain", "m2"), ("stop", "m2"),
                            ("start", "m2"), ("undrain", "m2")]
            kinds = [e["kind"] for e in telemetry.FLIGHT.snapshot()]
            assert "autoscale.unit-stop" in kinds
            assert "autoscale.unit-start" in kinds

        asyncio.run(main())

    def test_failed_respawn_reparks_the_member_for_retry(self):
        async def main():
            clock = _FakeClock()
            router = _FakeRouter(2)

            from omero_ms_image_region_tpu.server.sidecar import (
                SidecarUnit, SidecarUnitLifecycle)
            attempts = []

            def flaky_spawn():
                attempts.append(1)
                if len(attempts) < 3:
                    raise RuntimeError("socket never appeared")
                return _FakeProc()

            lc = SidecarUnitLifecycle(
                {"m1": SidecarUnit("m1", flaky_spawn)})
            scaler = Autoscaler(_config(floor=1), router,
                                lifecycle=lc, clock=clock)
            assert (await _ticks(scaler, 2))[-1] == "down"
            clock.advance(31)
            router.depth = 100
            # First up attempt: spawn fails, the member stays parked
            # (draining, autoscale intent) and is retried later.
            assert (await _ticks(scaler, 2))[-1] == "up"
            assert router.members["m1"].draining
            assert scaler._scaled_down == ["m1"]
            clock.advance(31)
            assert (await _ticks(scaler, 2))[-1] == "up"
            assert router.members["m1"].draining          # failed again
            clock.advance(31)
            assert (await _ticks(scaler, 2))[-1] == "up"
            assert not router.members["m1"].draining      # third's a charm
            assert lc.alive("m1")

        asyncio.run(main())
