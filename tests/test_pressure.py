"""Pressure governor + brownout ladder (server.pressure).

The load-bearing claim is the PROPERTY test: for ANY pressure
trajectory, ladder steps engage in configured order, the engaged set
is always a prefix of the ladder, steps release in exact reverse with
hysteresis (never before ``release_hold_ticks`` consecutive ok ticks),
and interactive-availability shedding (``tighten_admission``) is never
engaged without bulk shedding (``shed_bulk``) already engaged.
"""

import asyncio
import random

import pytest

from omero_ms_image_region_tpu.server import pressure
from omero_ms_image_region_tpu.server.admission import (
    AdmissionController)
from omero_ms_image_region_tpu.server.config import AppConfig
from omero_ms_image_region_tpu.server.ctx import ImageRegionCtx
from omero_ms_image_region_tpu.server.errors import OverloadedError
from omero_ms_image_region_tpu.utils import telemetry


@pytest.fixture(autouse=True)
def _fresh():
    telemetry.reset()
    pressure.uninstall()
    yield
    pressure.uninstall()
    telemetry.reset()


def _governor(ladder=None, actuators=None, **overrides):
    """A governor driven by ONE controllable 'queue' signal."""
    raw = {"pressure": {"enabled": True, **overrides}}
    if ladder is not None:
        raw["pressure"]["ladder"] = list(ladder)
    config = AppConfig.from_dict(raw).pressure
    value = {"queue": 0.0}
    gov = pressure.PressureGovernor(
        config, actuators or {}, {"queue": lambda: value["queue"]})
    return gov, value, config


# Signal values that deterministically produce each level through the
# classifier (high=48 default: ok < low=16, elevated >= 48, critical
# >= 48 * 1.25).
_LEVEL_VALUES = {0: 0.0, 1: 48.0, 2: 60.0}


class TestLadderProperty:
    def test_any_trajectory_engages_in_order_releases_in_reverse(self):
        rng = random.Random(1234)
        for trial in range(20):
            telemetry.reset()
            gov, value, config = _governor()
            ladder = gov.ladder
            engaged_history = [tuple()]
            ok_streak = 0
            for tick in range(120):
                level = rng.choice((0, 0, 1, 1, 2))
                value["queue"] = _LEVEL_VALUES[level]
                gov.tick()
                now = tuple(gov.engaged_steps())
                prev = engaged_history[-1]
                # Always a PREFIX of the configured ladder.
                assert now == ladder[:len(now)]
                if len(now) == len(prev) + 1:
                    # Engaged exactly the next step, in order.
                    assert now[:len(prev)] == prev
                elif len(now) == len(prev) - 1:
                    # Released exactly the LAST step (reverse order),
                    # and only after the hysteresis hold of ok ticks.
                    assert prev[:len(now)] == now
                    assert ok_streak + 1 >= config.release_hold_ticks
                else:
                    # No multi-step jumps, ever.
                    assert now == prev
                # The availability-ordering invariant: interactive
                # shedding never without bulk shedding.
                if "tighten_admission" in now:
                    assert "shed_bulk" in now
                ok_streak = ok_streak + 1 if level == 0 else 0
                engaged_history.append(now)

    def test_sustained_critical_walks_whole_ladder_then_recovers(self):
        gov, value, config = _governor()
        value["queue"] = _LEVEL_VALUES[2]
        for _ in range(len(gov.ladder) + 2):
            gov.tick()
        assert gov.engaged_steps() == list(gov.ladder)
        assert gov.level == pressure.LEVEL_CRITICAL
        value["queue"] = 0.0
        # Release is one step per release_hold_ticks, reverse order.
        for expect in range(len(gov.ladder) - 1, -1, -1):
            for _ in range(config.release_hold_ticks):
                gov.tick()
            assert gov.engaged_steps() == list(gov.ladder[:expect])
        assert gov.level == pressure.LEVEL_OK

    def test_elevated_engages_slower_than_critical(self):
        gov, value, config = _governor()
        value["queue"] = _LEVEL_VALUES[1]
        gov.tick()
        assert gov.engaged_steps() == []     # hold not yet met
        for _ in range(config.step_hold_ticks - 1):
            gov.tick()
        assert len(gov.engaged_steps()) == 1

    def test_signal_hysteresis_holds_level_between_watermarks(self):
        gov, value, _ = _governor()
        value["queue"] = 48.0
        gov.tick()
        assert gov.level == pressure.LEVEL_ELEVATED
        # Between low (16) and high (48): stays elevated.
        value["queue"] = 30.0
        gov.tick()
        assert gov.level == pressure.LEVEL_ELEVATED
        # Below low: drops to ok.
        value["queue"] = 10.0
        gov.tick()
        assert gov.level == pressure.LEVEL_OK

    def test_transitions_and_level_ride_telemetry(self):
        gov, value, _ = _governor()
        value["queue"] = _LEVEL_VALUES[2]
        gov.tick()
        assert telemetry.PRESSURE.level == 2
        assert telemetry.PRESSURE.steps_engaged[gov.ladder[0]] == 1
        kinds = [e["kind"] for e in telemetry.FLIGHT.snapshot()]
        assert "pressure.level" in kinds
        assert "pressure.step" in kinds


class TestPrefetchBudget:
    """The continuous prefetch budget (PR 10): a pure function of the
    folded level and the ``pause_prefetch`` ladder state — it scales
    DOWN with pressure before the binary pause engages, and whatever
    path the level took down, the identical path back up restores the
    identical budgets in reverse."""

    def _expected(self, gov, config):
        if gov.step_engaged("pause_prefetch"):
            return 0.0
        if gov.level >= pressure.LEVEL_CRITICAL:
            return config.prefetch_budget_critical
        if gov.level >= pressure.LEVEL_ELEVATED:
            return config.prefetch_budget_elevated
        return 1.0

    def test_budget_is_a_pure_function_over_any_trajectory(self):
        rng = random.Random(4321)
        for trial in range(10):
            telemetry.reset()
            gov, value, config = _governor()
            for tick in range(120):
                value["queue"] = _LEVEL_VALUES[rng.choice(
                    (0, 0, 1, 1, 2))]
                gov.tick()
                budget = gov.prefetch_budget()
                assert budget == self._expected(gov, config)
                # The binary pause is exactly the budget's floor.
                assert (budget == 0.0) == gov.step_engaged(
                    "pause_prefetch")
                # Published gauge follows every transition.
                assert telemetry.PREFETCH.budget_scale == budget

    def test_budget_scales_down_before_pause_and_releases_reverse(
            self):
        """A rising-pressure trajectory (ok -> elevated -> critical)
        cuts the budget via the LEVEL strictly before the ladder's
        binary ``pause_prefetch`` floors it at 0; release walks the
        ladder back in reverse and the budget restores with it."""
        gov, value, config = _governor()
        budgets = [gov.prefetch_budget()]

        def tick():
            gov.tick()
            budgets.append(gov.prefetch_budget())

        value["queue"] = _LEVEL_VALUES[1]    # elevated: holds lag
        tick()
        assert not gov.step_engaged("pause_prefetch")
        assert gov.prefetch_budget() == \
            config.prefetch_budget_elevated   # scaled BEFORE pause
        value["queue"] = _LEVEL_VALUES[2]
        while not gov.step_engaged("pause_prefetch"):
            tick()
        down_path = [b for b, prev in zip(budgets, [None] + budgets)
                     if b != prev]
        assert down_path[0] == 1.0
        assert down_path[-1] == 0.0
        # The continuous cut came strictly before the binary floor.
        assert config.prefetch_budget_elevated in down_path[1:-1]
        # Release: the ladder lifts pause (reverse order: it released
        # LAST of the engaged steps) and the budget restores fully.
        value["queue"] = 0.0
        while gov.engaged != 0 or gov.level != pressure.LEVEL_OK:
            tick()
        assert not gov.step_engaged("pause_prefetch")
        assert budgets[-1] == 1.0
        # Budget-zero spans exactly the pause engagement: once the
        # release walk lifted it, the budget never read 0 again.
        lifted = len(budgets) - 1 - budgets[::-1].index(0.0)
        assert all(b == 1.0 for b in budgets[lifted + 1:])

    def test_elevated_level_halves_before_critical_quarters(self):
        gov, value, config = _governor()
        value["queue"] = _LEVEL_VALUES[1]
        gov.tick()
        assert gov.prefetch_budget() == \
            config.prefetch_budget_elevated == 0.5
        value["queue"] = _LEVEL_VALUES[2]
        gov.tick()
        # Critical level quarters even while pause is not yet engaged
        # (step holds lag the level).
        if not gov.step_engaged("pause_prefetch"):
            assert gov.prefetch_budget() == \
                config.prefetch_budget_critical == 0.25

    def test_budget_transitions_ride_the_flight_recorder(self):
        gov, value, _ = _governor()
        value["queue"] = _LEVEL_VALUES[1]
        gov.tick()                           # elevated, pause lags
        events = [e for e in telemetry.FLIGHT.snapshot()
                  if e["kind"] == "prefetch.budget"]
        assert events and events[-1]["scale"] == 0.5
        assert events[-1]["prev"] == 1.0
        assert events[-1]["paused"] is False
        value["queue"] = _LEVEL_VALUES[2]
        while not gov.step_engaged("pause_prefetch"):
            gov.tick()
        events = [e for e in telemetry.FLIGHT.snapshot()
                  if e["kind"] == "prefetch.budget"]
        assert events[-1]["scale"] == 0.0
        assert events[-1]["paused"] is True

    def test_budget_config_validation_is_monotone(self):
        with pytest.raises(ValueError):
            AppConfig.from_dict({"pressure": {
                "enabled": True,
                "prefetch-budget-elevated": 0.2,
                "prefetch-budget-critical": 0.6}})


class TestCgroupRssDefaults:
    """Satellite: host-RSS watermarks default from the cgroup memory
    limit (v2 ``memory.max``, v1 fallback) when the knob is unset —
    the explicit knob always wins."""

    def test_v2_limit_parses_to_mb(self, tmp_path):
        v2 = tmp_path / "memory.max"
        v2.write_text("1073741824\n")
        assert pressure.read_cgroup_memory_limit_mb(
            v2_path=str(v2), v1_path=str(tmp_path / "nope")) == 1024.0

    def test_v2_max_means_unlimited(self, tmp_path):
        v2 = tmp_path / "memory.max"
        v2.write_text("max\n")
        assert pressure.read_cgroup_memory_limit_mb(
            v2_path=str(v2), v1_path=str(tmp_path / "nope")) is None

    def test_v1_fallback_and_absurd_limit_means_unlimited(
            self, tmp_path):
        v1 = tmp_path / "memory.limit_in_bytes"
        v1.write_text("536870912\n")
        assert pressure.read_cgroup_memory_limit_mb(
            v2_path=str(tmp_path / "nope"), v1_path=str(v1)) == 512.0
        v1.write_text(str(1 << 62))          # PAGE_COUNTER_MAX class
        assert pressure.read_cgroup_memory_limit_mb(
            v2_path=str(tmp_path / "nope"), v1_path=str(v1)) is None

    def test_not_in_a_cgroup_means_none(self, tmp_path):
        assert pressure.read_cgroup_memory_limit_mb(
            v2_path=str(tmp_path / "a"),
            v1_path=str(tmp_path / "b")) is None

    def test_defaults_applied_only_when_knob_unset(self):
        config = AppConfig().pressure
        assert config.host_rss_high_mb == 0     # unset by default
        pressure.apply_cgroup_rss_defaults(config, limit_mb=1000.0)
        assert config.host_rss_high_mb == 800.0
        assert config.host_rss_low_mb == 650.0

    def test_explicit_knob_always_wins(self):
        config = AppConfig.from_dict({"pressure": {
            "enabled": True, "host-rss-high-mb": 300,
            "host-rss-low-mb": 200}}).pressure
        pressure.apply_cgroup_rss_defaults(config, limit_mb=1000.0)
        assert config.host_rss_high_mb == 300
        assert config.host_rss_low_mb == 200

    def test_no_limit_leaves_the_signal_disabled(self):
        config = AppConfig().pressure
        pressure.apply_cgroup_rss_defaults(config, limit_mb=None)
        assert config.host_rss_high_mb == 0


class TestActuators:
    def test_actuator_hooks_fire_on_engage_and_release(self):
        calls = []
        actuators = {
            "pause_prefetch": pressure.StepActuator(
                engage=lambda: calls.append("engage"),
                release=lambda: calls.append("release"),
                while_engaged=lambda: calls.append("held")),
        }
        gov, value, config = _governor(ladder=("pause_prefetch",),
                                       actuators=actuators)
        value["queue"] = _LEVEL_VALUES[2]
        gov.tick()
        assert calls == ["engage", "held"]
        gov.tick()
        assert calls[-1] == "held"
        value["queue"] = 0.0
        for _ in range(config.release_hold_ticks):
            gov.tick()
        assert calls[-1] == "release"

    def test_failing_actuator_never_stalls_the_ladder(self):
        def boom():
            raise RuntimeError("actuator bug")
        gov, value, _ = _governor(
            ladder=("pause_prefetch", "shed_bulk"),
            actuators={"pause_prefetch":
                       pressure.StepActuator(engage=boom)})
        value["queue"] = _LEVEL_VALUES[2]
        gov.tick()
        gov.tick()
        assert gov.engaged_steps() == ["pause_prefetch", "shed_bulk"]

    def test_build_actuators_pause_and_evict(self):
        """The standard wiring really flips the prefetcher/warmstate
        flags and walks the HBM cache to low water."""
        import numpy as np

        from omero_ms_image_region_tpu.io.devicecache import (
            DeviceRawCache)

        class Services:
            pass

        cache = DeviceRawCache(max_bytes=4096, digest_index=False)
        for i in range(4):
            cache.get_or_load(
                ("k", i), lambda i=i: np.full((16, 16), i,
                                              np.uint16))
        assert cache.size_bytes > 0

        class Flagged:
            paused = False

        services = Services()
        services.prefetcher = Flagged()
        services.warmstate = Flagged()
        services.raw_cache = cache
        services.caches = None
        services.renderer = None
        config = AppConfig.from_dict(
            {"pressure": {"enabled": True,
                          "evict-to-frac": 0.25}}).pressure
        actuators = pressure.build_actuators(config,
                                             services=services)
        actuators["pause_prefetch"].engage()
        actuators["pause_snapshots"].engage()
        assert services.prefetcher.paused is True
        assert services.warmstate.paused is True
        before = cache.size_bytes
        actuators["evict_caches"].engage()
        assert cache.size_bytes <= max(1, int(4096 * 0.25)) \
            or cache.size_bytes < before
        actuators["pause_prefetch"].release()
        assert services.prefetcher.paused is False


def _tile_ctx():
    return ImageRegionCtx.from_params({
        "imageId": "1", "theZ": "0", "theT": "0",
        "tile": "0,0,0,64,64", "format": "jpeg", "m": "c",
        "c": "1|0:60000$FF0000"})


def _bulk_ctx():
    return ImageRegionCtx.from_params({
        "imageId": "1", "theZ": "0", "theT": "0",
        "format": "jpeg", "m": "c", "c": "1|0:60000$FF0000"})


class TestConsumerHooks:
    def _installed(self, engaged_steps):
        gov, value, _ = _governor()
        value["queue"] = _LEVEL_VALUES[2]
        while len(gov.engaged_steps()) < len(engaged_steps):
            gov.tick()
            assert set(gov.engaged_steps()) <= set(gov.ladder)
        assert gov.engaged_steps() == list(engaged_steps)
        pressure.install(gov)
        return gov

    def test_admission_tightens_under_pressure(self):
        gov = self._installed(list(
            AppConfig().pressure.ladder))       # all steps engaged
        admission = AdmissionController(max_queue=100)
        assert admission.effective_max_queue() == 25   # scale 0.25
        admission.inflight = 25
        with pytest.raises(OverloadedError):
            admission.admit()
        assert telemetry.RESILIENCE.shed.get("pressure") == 1
        pressure.uninstall()
        assert admission.effective_max_queue() == 100

    def test_bulk_sheds_before_interactive(self):
        ladder = AppConfig().pressure.ladder
        self._installed(list(ladder[:ladder.index("shed_bulk") + 1]))
        with pytest.raises(OverloadedError):
            pressure.shed_bulk_under_pressure(_bulk_ctx())
        # Interactive tiles pass the same gate untouched.
        pressure.shed_bulk_under_pressure(_tile_ctx())
        assert telemetry.RESILIENCE.shed.get("pressure-bulk") == 1

    def test_quality_cap_hits_interactive_tiles_only(self):
        ladder = AppConfig().pressure.ladder
        self._installed(list(
            ladder[:ladder.index("drop_quality") + 1]))
        tile = _tile_ctx()
        assert pressure.pressure_quality(90, tile) == 60
        assert getattr(tile, "_pressure_quality_capped") is True
        bulk = _bulk_ctx()
        assert pressure.pressure_quality(90, bulk) == 90
        # Below the cap: untouched, and no cache-skip mark.
        tile2 = _tile_ctx()
        assert pressure.pressure_quality(50, tile2) == 50
        assert not getattr(tile2, "_pressure_quality_capped", False)

    def test_lane_cap_actuator_on_batcher(self):
        from omero_ms_image_region_tpu.server.batcher import (
            BatchingRenderer)

        async def scenario():
            renderer = BatchingRenderer(max_batch=2, linger_ms=0)
            config = AppConfig.from_dict(
                {"pressure": {"enabled": True,
                              "lane-cap": 1}}).pressure

            class Services:
                pass
            services = Services()
            services.renderer = renderer
            services.prefetcher = None
            services.warmstate = None
            services.raw_cache = None
            services.caches = None
            actuators = pressure.build_actuators(config,
                                                 services=services)
            actuators["cap_lanes"].engage()
            assert renderer._lane_cap == 1
            actuators["cap_lanes"].release()
            assert renderer._lane_cap == 0
            await renderer.close()

        asyncio.run(scenario())


class TestLoopLag:
    """PR 36: one measure of the event loop's lag, always on
    (``utils.stopwatch.LoopLagSampler``, span ``loop.lag``); the
    governor's ``loop_lag_ms`` signal reads it and times nothing."""

    @staticmethod
    def _lag_count():
        from omero_ms_image_region_tpu.utils.stopwatch import REGISTRY
        return REGISTRY.snapshot().get("loop.lag", {}).get("count", 0)

    @pytest.mark.parametrize("enabled", [False, True])
    def test_the_app_samples_the_loop_governor_on_or_off(self, tmp_path,
                                                         enabled):
        from aiohttp.test_utils import TestClient, TestServer

        from omero_ms_image_region_tpu.server.app import create_app
        from omero_ms_image_region_tpu.utils.stopwatch import (
            REGISTRY, LoopLagSampler)

        config = AppConfig.from_dict(
            {"pressure": {"enabled": enabled, "interval-s": 0.05}})
        config.data_dir = str(tmp_path)
        assert config.pressure.enabled is enabled
        REGISTRY.reset()

        async def main():
            client = TestClient(TestServer(create_app(config)))
            await client.start_server()
            try:
                await asyncio.sleep(3.5 * LoopLagSampler.INTERVAL_S)
                text = await (await client.get("/metrics")).text()
                return self._lag_count(), text, pressure.active()
            finally:
                await client.close()

        samples, text, governor = asyncio.run(main())
        assert 2 <= samples <= 4
        assert 'imageregion_span_count{span="loop.lag"}' in text
        assert (governor is not None) is enabled
        if enabled:
            # The governor ticked, and its signal is the sampler's.
            assert 'imageregion_pressure_signal{signal="loop_lag_ms"}' \
                in text
            assert governor.sources["loop_lag_ms"]() is not None

    def test_the_governors_signal_follows_the_sampler(self):
        from omero_ms_image_region_tpu.utils.stopwatch import (
            LoopLagSampler)

        sampler = LoopLagSampler()
        sources = pressure.build_sources(loop_lag=sampler)
        config = AppConfig.from_dict(
            {"pressure": {"enabled": True, "loop-lag-high-ms": 100,
                          "loop-lag-low-ms": 20}}).pressure
        gov = pressure.PressureGovernor(
            config, {}, {"loop_lag_ms": sources["loop_lag_ms"]})
        assert not hasattr(gov, "loop_lag_ms")
        gov.tick()
        assert gov.level == pressure.LEVEL_OK
        sampler.ewma_ms = 150.0
        gov.tick()
        assert gov.level >= pressure.LEVEL_ELEVATED
        sampler.ewma_ms = 1.0
        gov.tick()
        assert gov.level == pressure.LEVEL_OK
        # No sampler, no signal (a stack built without a loop).
        assert pressure.build_sources()["loop_lag_ms"]() is None

    def test_a_blocked_loop_reads_as_lag(self):
        import time

        from omero_ms_image_region_tpu.utils.stopwatch import (
            REGISTRY, LoopLagSampler)

        REGISTRY.reset()
        sampler = LoopLagSampler()

        async def main():
            task = asyncio.ensure_future(sampler.run())
            await asyncio.sleep(0.01)       # the sampler sleeps
            # The loop runs nothing for 80 ms past the sample's due time.
            time.sleep(LoopLagSampler.INTERVAL_S + 0.08)
            await asyncio.sleep(0.01)
            task.cancel()
            await asyncio.gather(task, return_exceptions=True)

        asyncio.run(main())
        lag = REGISTRY.snapshot()["loop.lag"]
        assert lag["count"] == 1 and lag["max_ms"] >= 60.0
        assert sampler.ewma_ms == pytest.approx(
            LoopLagSampler.ALPHA * lag["max_ms"], rel=1e-3)

    def test_one_pause_is_not_sustained_lag(self):
        """The signal keeps the three seconds of memory the governor's
        own timing had (0.3 of a one-second tick's lateness), fed every
        100 ms: one 300 ms GC pause stays under the shipped low mark at
        every sample after it, a loop that is late every time climbs
        past the high mark in seconds, and comes down as slowly."""
        from omero_ms_image_region_tpu.server.config import PressureConfig
        from omero_ms_image_region_tpu.utils.stopwatch import (
            LoopLagSampler)

        marks = PressureConfig()
        per_s = round(1.0 / LoopLagSampler.INTERVAL_S)
        assert (1 - LoopLagSampler.ALPHA) ** per_s == pytest.approx(
            0.7, abs=0.005)
        sampler = LoopLagSampler()
        peak = 0.0
        for lag_ms in [300.0] + [1.0] * (3 * per_s):
            sampler.observe(lag_ms)
            peak = max(peak, sampler.ewma_ms)
        assert peak < marks.loop_lag_low_ms / 4
        # Late by 300 ms at every wake-up: sustained.
        samples = 0
        while sampler.ewma_ms <= marks.loop_lag_high_ms:
            sampler.observe(300.0)
            samples += 1
        assert 3 * per_s < samples < 8 * per_s
        # One second on time does not clear it.
        for _ in range(per_s):
            sampler.observe(0.0)
        assert sampler.ewma_ms > marks.loop_lag_low_ms

    def test_pressure_times_no_sleep_of_its_own(self):
        import inspect
        source = inspect.getsource(pressure)
        assert "import time" not in source
        assert "perf_counter" not in source and "monotonic" not in source
