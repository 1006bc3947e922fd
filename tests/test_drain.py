"""Zero-downtime rolling drains (parallel.fleet + /admin/drain).

The headline drill: drain -> restart -> undrain EVERY fleet member in
sequence under a sustained mixed-digest load, with ZERO failed
requests (not even sheds) and the drained member's shard arriving
WARM on its ring successors (pre-staged via the drain manifest's
routing identities — never cold-missed)."""

import asyncio
import os
import tempfile

import numpy as np
import pytest

from omero_ms_image_region_tpu.flagship import synthetic_wsi_tiles
from omero_ms_image_region_tpu.io.devicecache import DeviceRawCache
from omero_ms_image_region_tpu.io.store import build_pyramid
from omero_ms_image_region_tpu.parallel.fleet import (
    FleetImageHandler, FleetRouter, build_local_members)
from omero_ms_image_region_tpu.server.admission import (
    AdmissionController)
from omero_ms_image_region_tpu.server.app import build_services
from omero_ms_image_region_tpu.server.config import (AppConfig,
                                                     BatcherConfig,
                                                     FleetConfig,
                                                     RawCacheConfig,
                                                     RendererConfig,
                                                     WireConfig)
from omero_ms_image_region_tpu.server.ctx import ImageRegionCtx
from omero_ms_image_region_tpu.server.singleflight import SingleFlight
from omero_ms_image_region_tpu.utils import telemetry

GRID = 4
EDGE = 128


@pytest.fixture(autouse=True)
def _fresh():
    telemetry.reset()
    yield
    telemetry.reset()


@pytest.fixture(scope="module")
def data_dir():
    rng = np.random.default_rng(21)
    with tempfile.TemporaryDirectory() as tmp:
        planes = synthetic_wsi_tiles(
            rng, 2, 1, GRID * EDGE, GRID * EDGE).reshape(
            2, 1, GRID * EDGE, GRID * EDGE)
        build_pyramid(planes, os.path.join(tmp, "1"), n_levels=1)
        yield tmp


def _ctxs(variants=2):
    """Mixed-digest working set: every tile of the grid, each at
    ``variants`` window settings (same plane identity -> same shard
    owner; different settings -> distinct renders)."""
    out = []
    for v in range(variants):
        for x in range(GRID):
            for y in range(GRID):
                w = 30000 + v * 800
                out.append(ImageRegionCtx.from_params({
                    "imageId": "1", "theZ": "0", "theT": "0",
                    "tile": f"0,{x},{y},{EDGE},{EDGE}",
                    "format": "png", "m": "c",
                    "c": f"1|0:{w}$FF0000,2|0:{w - 700}$00FF00",
                }))
    return out


def _fleet(tmp, n=3):
    config = AppConfig(
        data_dir=tmp,
        batcher=BatcherConfig(enabled=False),
        raw_cache=RawCacheConfig(enabled=True, prefetch=False),
        renderer=RendererConfig(cpu_fallback_max_px=0))
    services = build_services(config)
    members = build_local_members(config, services, n)
    router = FleetRouter(members, lane_width=2, steal_min_backlog=0)
    handler = FleetImageHandler(
        router, single_flight=SingleFlight(),
        admission=AdmissionController(512, renderer=router),
        base_services=services)
    return services, members, router, handler


class TestRollingRestartDrill:
    def test_drain_restart_undrain_every_member_zero_failures(
            self, data_dir):
        working = _ctxs()
        errors: list = []
        served = {"n": 0}

        async def drill():
            services, members, router, handler = _fleet(data_dir)
            stop = asyncio.Event()

            async def load():
                """Sustained mixed-digest load for the whole drill;
                ANY failure (even a shed) is a drill failure."""
                i = 0
                while not stop.is_set():
                    ctx = working[i % len(working)]
                    i += 1
                    try:
                        out = await handler.render_image_region(ctx)
                        assert out
                        served["n"] += 1
                    except Exception as e:     # noqa: BLE001
                        errors.append(repr(e))
                    await asyncio.sleep(0)

            loader = asyncio.create_task(load())
            warm_rates = []
            try:
                # Warm the whole working set once so every shard has
                # resident planes to hand over.
                await asyncio.gather(*(
                    handler.render_image_region(c) for c in working))
                for name in list(router.order):
                    member = router.members[name]
                    owned = [c for c in working
                             if router.owner_of(c) == name]
                    shard_digests = set(member.resident_digests())
                    doc = await router.drain_member(
                        name, prestage=True, max_planes=256,
                        settle_timeout_s=10.0)
                    assert doc["settled"] is True
                    # The handed-over shard is RESIDENT on the
                    # surviving members before any request asks.
                    survivors = set()
                    for other in router.order:
                        if other != name:
                            survivors |= router.members[other] \
                                .resident_digests()
                    assert shard_digests <= survivors, \
                        f"{name}: shard not pre-staged warm"
                    # Warm-hit rate on the successors: rendering the
                    # drained member's working set must hit HBM, not
                    # re-read the pixel store.
                    hits_before = sum(
                        router.members[o].services.raw_cache.hits
                        for o in router.order if o != name)
                    await asyncio.gather(*(
                        handler.render_image_region(c)
                        for c in owned))
                    hits_after = sum(
                        router.members[o].services.raw_cache.hits
                        for o in router.order if o != name)
                    if owned:
                        rate = (hits_after - hits_before) / len(owned)
                        warm_rates.append((name, rate))
                        assert rate >= 0.8, \
                            f"{name}: warm-hit {rate:.2f} < 0.8"
                    # "Restart": the member comes back with a COLD
                    # HBM cache (exactly what a process restart
                    # drops), then rejoins the ring.
                    member.services.raw_cache = DeviceRawCache(
                        member.services.raw_cache.max_bytes)
                    router.undrain_member(name)
                    assert name not in router.draining_members()
                    # Pre-stage BACK (the PR 9 follow-on): the drain
                    # manifest replays into the rejoined member, so
                    # its shard is HBM-resident again BEFORE its
                    # first routed request — a rolling restart ends
                    # with a warm fleet, not a cold rejoiner.
                    if shard_digests:
                        task = router.last_undrain_prestage
                        assert task is not None, \
                            f"{name}: no pre-stage-back scheduled"
                        await task
                        back = set(member.resident_digests())
                        assert shard_digests <= back, \
                            f"{name}: rejoined cold " \
                            f"({len(back)}/{len(shard_digests)} " \
                            f"planes back)"
            finally:
                stop.set()
                await loader
                await router.close()
                services.pixels_service.close()
            return warm_rates

        warm_rates = asyncio.run(drill())
        # ZERO 5xx-without-shed — in this drill, zero failures at all.
        assert errors == [], f"load failures during drill: {errors[:5]}"
        assert served["n"] > 0
        assert len(warm_rates) >= 2      # m0 may own 0 of the set
        # Drain accounting: every member drained once, planes were
        # pre-staged, and the phases hit the black box.
        assert telemetry.DRAIN.drains_total == 3
        assert telemetry.DRAIN.prestaged_planes > 0
        kinds = [e["kind"] for e in telemetry.FLIGHT.snapshot()]
        assert "drain.phase" in kinds

    def test_draining_member_takes_no_new_routes(self, data_dir):
        async def scenario():
            services, members, router, handler = _fleet(data_dir)
            try:
                working = _ctxs(variants=1)
                name = router.order[1]
                await router.drain_member(name, prestage=False,
                                          settle_timeout_s=2.0)
                owners = {router.owner_of(c) for c in working}
                assert name not in owners
                router.undrain_member(name)
                owners = {router.owner_of(c) for c in working}
                # Rejoined: its ring arcs flow back (the working set
                # spans every member at this size).
                assert name in owners
            finally:
                await router.close()
                services.pixels_service.close()

        asyncio.run(scenario())


class TestAdminDrainEndpoint:
    def _app_client(self, data_dir):
        from aiohttp.test_utils import TestClient, TestServer

        from omero_ms_image_region_tpu.server.app import create_app
        from omero_ms_image_region_tpu.server.config import FleetConfig

        config = AppConfig(
            data_dir=data_dir,
            batcher=BatcherConfig(enabled=False),
            raw_cache=RawCacheConfig(enabled=True, prefetch=False),
            renderer=RendererConfig(cpu_fallback_max_px=0))
        config.fleet = FleetConfig(enabled=True, members=2)
        app = create_app(config)
        return TestClient(TestServer(app))

    def test_fail_readyz_pulls_a_draining_instance_from_rotation(
            self, data_dir):
        """Satellite (PR 9 follow-on): with ``drain.fail-readyz`` on,
        /readyz answers 503 while any member drains — nginx/k8s pull
        the instance during a rolling restart — and recovers to 200
        on undrain.  The default posture stays annotation-only."""
        from aiohttp.test_utils import TestClient, TestServer

        from omero_ms_image_region_tpu.server.app import create_app
        from omero_ms_image_region_tpu.server.config import FleetConfig

        async def scenario(fail_readyz):
            config = AppConfig(
                data_dir=data_dir,
                batcher=BatcherConfig(enabled=False),
                raw_cache=RawCacheConfig(enabled=True, prefetch=False),
                renderer=RendererConfig(cpu_fallback_max_px=0))
            config.fleet = FleetConfig(enabled=True, members=2)
            config.drain.fail_readyz = fail_readyz
            client = TestClient(TestServer(create_app(config)))
            await client.start_server()
            try:
                assert (await client.get("/readyz")).status == 200
                r = await client.post("/admin/drain?member=m1")
                assert r.status == 200
                r = await client.get("/readyz")
                draining_status = r.status
                body = await r.json()
                # The annotation is present in BOTH postures.
                assert "m1" in body["checks"].get("drain", "")
                await client.post("/admin/undrain?member=m1")
                assert (await client.get("/readyz")).status == 200
                return draining_status
            finally:
                await client.close()

        assert asyncio.run(scenario(True)) == 503
        assert asyncio.run(scenario(False)) == 200

    def test_drain_undrain_roundtrip_and_last_member_guard(
            self, data_dir):
        async def scenario():
            client = self._app_client(data_dir)
            await client.start_server()
            try:
                r = await client.get("/admin/drain")
                assert r.status == 200
                doc = await r.json()
                assert set(doc["members"]) == {"m0", "m1"}

                r = await client.post("/admin/drain?member=m1")
                assert r.status == 200
                doc = await r.json()
                assert doc["member"] == "m1"
                assert doc["members"]["m1"]["draining"] is True

                # Draining the LAST routable member is refused.
                r = await client.post("/admin/drain?member=m0")
                assert r.status == 409

                # Drain state is on /readyz (annotation) and /metrics.
                r = await client.get("/readyz")
                body = await r.json()
                assert "m1" in body["checks"].get("drain", "")
                r = await client.get("/metrics")
                text = await r.text()
                assert 'imageregion_drain_state{member="m1"} 2' \
                    in text

                r = await client.post("/admin/undrain?member=m1")
                assert r.status == 200
                doc = await r.json()
                assert doc["members"]["m1"]["draining"] is False

                r = await client.post("/admin/drain?member=nope")
                assert r.status == 400
            finally:
                await client.close()

        asyncio.run(scenario())


def test_server_and_fleet_members_build_one_renderer(data_dir):
    """The single-chip server's renderer and an extra fleet member's
    are built by one function: one ``AppConfig`` gives both the same
    batcher knobs and stated planes, and each member its own raw cache,
    both pinned to the member's device."""
    import jax

    from omero_ms_image_region_tpu.server.batcher import BatchingRenderer

    config = AppConfig(
        data_dir=data_dir,
        batcher=BatcherConfig(enabled=True, max_batch=4,
                              max_batch_limit=6, linger_ms=3.5,
                              pipeline_depth=3),
        raw_cache=RawCacheConfig(enabled=True, prefetch=False,
                                 max_bytes=64 << 20),
        renderer=RendererConfig(cpu_fallback_max_px=0,
                                prewarm=("2x1080@90",)),
        # A fleet's base stack: its programs are warmed once the
        # members exist, so nothing compiles here.
        fleet=FleetConfig(enabled=True, members=2),
        wire=WireConfig(streaming=False))
    services = build_services(config)
    try:
        devices = jax.devices()[:2]
        members = build_local_members(
            config, services, 2, device_sets=[[d] for d in devices])
        base, member = services, members[1].services
        knobs = ("max_batch", "max_batch_limit", "linger_ms",
                 "pipeline_depth", "device_lanes", "jpeg_engine",
                 "planes", "first_tile_out")
        assert type(base.renderer) is type(member.renderer) \
            is BatchingRenderer
        assert {k: getattr(member.renderer, k) for k in knobs} == \
            {k: getattr(base.renderer, k) for k in knobs} == {
                "max_batch": 4, "max_batch_limit": 6, "linger_ms": 3.5,
                "pipeline_depth": 3, "device_lanes": 2,
                "jpeg_engine": "sparse",
                "planes": frozenset({(1080, 1080)}),
                "first_tile_out": False}
        assert base.renderer.device is devices[0]
        assert base.raw_cache.device is devices[0]
        assert member.renderer.device is devices[1]
        assert isinstance(member.raw_cache, DeviceRawCache)
        assert member.raw_cache is not base.raw_cache
        assert member.raw_cache.device is devices[1]
        assert member.raw_cache.max_bytes == base.raw_cache.max_bytes \
            == 64 << 20
    finally:
        services.pixels_service.close()
