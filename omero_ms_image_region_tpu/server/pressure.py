"""Resource-pressure governor + brownout ladder: degrade by choice
before degrading by accident.

Every fault-tolerance layer so far (breakers, shedding, failover —
PR 3/PR 8) reacts to a component that is DEAD.  Nothing reacted to a
component that is merely *drowning*: HBM occupancy creeping toward the
raw-cache budget, host RSS toward the cgroup limit, the disk byte tier
toward its low-water thrash point, queue depth toward the admission
cliff, the event loop lagging behind its own timers.  The reference
survives production behind nginx because a JVM that bloats gets
recycled (PAPER.md L0/L5); this module is the TPU build's cheaper
answer — notice the drowning EARLY and walk a configurable degradation
ladder so overload costs quality before it costs availability.

Mechanics:

* A periodic sampler (:class:`PressureGovernor.tick`, driven by an
  asyncio task at ``pressure.interval-s``) reads a fixed set of
  signals — HBM fraction from ``DeviceRawCache``, host RSS from
  ``/proc/self/status``, disk byte-cache fill, renderer/fleet queue
  depth, and the governor's own event-loop lag — and folds them into
  ONE level (``ok`` / ``elevated`` / ``critical``) with per-signal
  hysteresis (enter at the ``high`` watermark, exit only below
  ``low``), so a signal hovering at the boundary cannot flap the
  level.
* The **brownout ladder** is an ordered list of steps from
  :data:`KNOWN_STEPS`.  Under sustained ``elevated`` pressure the
  governor engages the next step every ``step-hold-ticks`` ticks;
  under ``critical`` it engages one step EVERY tick; after
  ``release-hold-ticks`` consecutive ``ok`` ticks it releases the last
  engaged step — so for ANY pressure trajectory the engaged set is
  always a PREFIX of the configured ladder, steps engage in order and
  release in exact reverse (the property test in
  ``tests/test_pressure.py`` pins this).
* Config validation (``server.config``) enforces the availability
  ordering invariant: ``shed_bulk`` must precede
  ``tighten_admission``, so interactive tile availability is never
  shed before bulk/projection work.

Consumers read the governor through the module-global
:func:`install`/:func:`active` pair (the ``utils.faultinject`` idiom),
so the hot path pays one ``is None`` check when the governor is off:

* ``services.prefetch.TilePrefetcher.paused`` / ``services.warmstate
  .WarmStateManager.paused`` — flipped by the ``pause_prefetch`` /
  ``pause_snapshots`` actuators;
* ``io.devicecache.DeviceRawCache.evict_to_fraction`` and the disk
  tier's ``evict_to_fraction`` — re-applied every tick while
  ``evict_caches`` is engaged (traffic refills what one evict freed);
* ``server.batcher.BatchingRenderer.set_lane_cap`` — ``cap_lanes``;
* ``server.handler`` — ``drop_quality`` caps interactive-tile JPEG
  quality, ``shed_bulk`` sheds full-plane/projection work with
  503 + Retry-After;
* ``server.admission.AdmissionController`` — ``tighten_admission``
  scales the effective queue bound down, so shedding becomes
  pressure-aware, not just depth-aware.

Every level transition and every ladder step engage/release is a
flight-recorder event and an ``imageregion_pressure_*`` series.
"""

from __future__ import annotations

import logging
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Tuple

from ..utils import telemetry

log = logging.getLogger("omero_ms_image_region_tpu.pressure")

# Ladder-step vocabulary; config validation rejects anything else.
KNOWN_STEPS = (
    "pause_prefetch",     # stop pan-ahead staging (frees link + HBM)
    "pause_snapshots",    # stop warm-state manifest writes (disk/CPU)
    "evict_caches",       # walk HBM + disk byte tier to low water
    "cap_lanes",          # bound concurrent group renders
    "drop_quality",       # lower interactive-tile JPEG quality
    "shed_bulk",          # 503 full-plane / z-projection work
    "tighten_admission",  # scale the admission queue bound down
)

LEVEL_OK, LEVEL_ELEVATED, LEVEL_CRITICAL = 0, 1, 2
LEVEL_NAMES = ("ok", "elevated", "critical")


def read_rss_mb() -> Optional[float]:
    """Host RSS in MB from ``/proc/self/status`` (no psutil in the
    image); None where /proc is unavailable."""
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return float(line.split()[1]) / 1024.0
    except (OSError, ValueError, IndexError):
        pass
    return None


# cgroup v2 exposes the memory limit at memory.max ("max" = unlimited);
# v1 at memory/memory.limit_in_bytes (an absurdly large number =
# unlimited — kernels report PAGE_COUNTER_MAX there).
_CGROUP_V2_LIMIT = "/sys/fs/cgroup/memory.max"
_CGROUP_V1_LIMIT = "/sys/fs/cgroup/memory/memory.limit_in_bytes"
_CGROUP_UNLIMITED_BYTES = 1 << 60


def read_cgroup_memory_limit_mb(
        v2_path: str = _CGROUP_V2_LIMIT,
        v1_path: str = _CGROUP_V1_LIMIT) -> Optional[float]:
    """The container's memory limit in MB from the cgroup filesystem
    (v2 first, v1 fallback); None when unlimited or not in a cgroup."""
    for path in (v2_path, v1_path):
        try:
            with open(path) as f:
                raw = f.read().strip()
        except OSError:
            continue
        if raw == "max":
            return None
        try:
            limit = int(raw)
        except ValueError:
            continue
        if limit <= 0 or limit >= _CGROUP_UNLIMITED_BYTES:
            return None
        return limit / (1024.0 * 1024.0)
    return None


# Auto-wired host-RSS watermarks as fractions of the cgroup limit:
# enter elevated at 80% (the JVM-recycle class of bloat the reference
# survives behind nginx — PAPER.md L0 — caught BEFORE the OOM killer),
# release below 65%.
_RSS_HIGH_FRAC = 0.80
_RSS_LOW_FRAC = 0.65


def apply_cgroup_rss_defaults(config,
                              limit_mb: Optional[float] = None):
    """Default the host-RSS watermarks from the cgroup memory limit
    when the operator left them unset (``host-rss-high-mb: 0``).  The
    explicit knob always wins; with no cgroup limit the signal simply
    stays disabled, as before.  Returns the config for chaining."""
    if config.host_rss_high_mb > 0:
        return config            # explicit override: never touched
    limit = limit_mb if limit_mb is not None \
        else read_cgroup_memory_limit_mb()
    if limit is None or limit <= 0:
        return config
    config.host_rss_high_mb = round(limit * _RSS_HIGH_FRAC, 1)
    config.host_rss_low_mb = round(limit * _RSS_LOW_FRAC, 1)
    log.info("pressure: host-RSS watermarks defaulted from the cgroup "
             "limit (%.0f MB): high %.0f / low %.0f",
             limit, config.host_rss_high_mb, config.host_rss_low_mb)
    return config


@dataclass
class StepActuator:
    """What a ladder step DOES.  ``engage``/``release`` fire on the
    transition; ``while_engaged`` re-fires every tick the step stays
    engaged (eviction steps need re-applying — traffic refills what
    one pass freed).  All three are guarded: a failing actuator logs
    and never stalls the governor."""

    engage: Optional[Callable[[], None]] = None
    release: Optional[Callable[[], None]] = None
    while_engaged: Optional[Callable[[], None]] = None


class _SignalState:
    __slots__ = ("engaged",)

    def __init__(self):
        self.engaged = False


class PressureGovernor:
    """Tick-driven pressure sampler + brownout ladder walker.

    ``sources`` maps signal name -> zero-arg callable returning the
    current reading (None = signal unavailable this tick); thresholds
    come from the config block.  The governor itself is synchronous —
    :meth:`tick` is called by the asyncio runner in ``server.app`` and
    directly by tests (deterministic trajectories, no clock).
    """

    def __init__(self, config, actuators: Dict[str, StepActuator],
                 sources: Dict[str, Callable[[], Optional[float]]]):
        self.config = config
        self.ladder: Tuple[str, ...] = tuple(config.ladder)
        self.actuators = actuators
        self.sources = sources
        self.level = LEVEL_OK
        self.engaged = 0            # ladder prefix length
        self._hot_streak = 0
        self._ok_streak = 0
        self._signal_states: Dict[str, _SignalState] = {}
        # Last published prefetch budget (change detection for the
        # flight event + gauge — the budget is a pure function of
        # level/ladder state, so publishing on transitions only keeps
        # the tape quiet).
        self._last_prefetch_budget = 1.0
        telemetry.PRESSURE.declare_steps(self.ladder)
        telemetry.PREFETCH.set_budget(1.0)

    # ---------------------------------------------------------- signals

    def _thresholds(self, name: str) -> Tuple[float, float]:
        c = self.config
        return {
            "hbm": (c.hbm_high, c.hbm_low),
            "host_rss_mb": (c.host_rss_high_mb, c.host_rss_low_mb),
            "disk": (c.disk_high, c.disk_low),
            "queue": (float(c.queue_high), float(c.queue_low)),
            "loop_lag_ms": (c.loop_lag_high_ms, c.loop_lag_low_ms),
        }.get(name, (0.0, 0.0))

    def _classify(self, name: str, value: float) -> int:
        """One signal's level with enter-high/exit-low hysteresis."""
        high, low = self._thresholds(name)
        if high <= 0:
            return LEVEL_OK           # signal disabled by config
        state = self._signal_states.setdefault(name, _SignalState())
        if value >= high * self.config.critical_factor:
            state.engaged = True
            return LEVEL_CRITICAL
        if value >= high:
            state.engaged = True
            return LEVEL_ELEVATED
        if state.engaged and value > low:
            # Between the watermarks: stays elevated until it falls
            # below low — the hysteresis that stops level flapping.
            return LEVEL_ELEVATED
        state.engaged = False
        return LEVEL_OK

    def sample(self) -> Dict[str, float]:
        samples: Dict[str, float] = {}
        for name, source in self.sources.items():
            try:
                value = source()
            except Exception:
                value = None
            if value is None:
                continue
            samples[name] = float(value)
            telemetry.PRESSURE.set_signal(name, float(value))
        return samples

    # ------------------------------------------------------------ ladder

    def _run_hook(self, step: str, hook: Optional[Callable]) -> None:
        if hook is None:
            return
        try:
            hook()
        except Exception:
            log.warning("pressure actuator %r failed", step,
                        exc_info=True)

    def _engage_next(self) -> None:
        step = self.ladder[self.engaged]
        self.engaged += 1
        actuator = self.actuators.get(step)
        if actuator is not None:
            self._run_hook(step, actuator.engage)
        telemetry.PRESSURE.set_step(step, True)
        telemetry.FLIGHT.record("pressure.step", step=step,
                                action="engage", engaged=self.engaged)
        log.warning("pressure brownout: engaged ladder step %r "
                    "(%d/%d)", step, self.engaged, len(self.ladder))

    def _release_last(self) -> None:
        self.engaged -= 1
        step = self.ladder[self.engaged]
        actuator = self.actuators.get(step)
        if actuator is not None:
            self._run_hook(step, actuator.release)
        telemetry.PRESSURE.set_step(step, False)
        telemetry.FLIGHT.record("pressure.step", step=step,
                                action="release", engaged=self.engaged)
        log.info("pressure recovered: released ladder step %r (%d/%d)",
                 step, self.engaged, len(self.ladder))

    def tick(self) -> int:
        """One governor evaluation; returns the folded level.  Called
        from the asyncio runner and directly by tests."""
        samples = self.sample()
        level = LEVEL_OK
        for name, value in samples.items():
            level = max(level, self._classify(name, value))
        if level != self.level:
            telemetry.FLIGHT.record(
                "pressure.level", level=LEVEL_NAMES[level],
                prev=LEVEL_NAMES[self.level],
                **{k: round(v, 3) for k, v in samples.items()})
            log.log(logging.WARNING if level > self.level
                    else logging.INFO,
                    "pressure level %s -> %s (%s)",
                    LEVEL_NAMES[self.level], LEVEL_NAMES[level],
                    {k: round(v, 2) for k, v in samples.items()})
        self.level = level
        telemetry.PRESSURE.set_level(level)
        if level >= LEVEL_ELEVATED:
            self._ok_streak = 0
            self._hot_streak += 1
            hold = (1 if level == LEVEL_CRITICAL
                    else self.config.step_hold_ticks)
            if (self.engaged < len(self.ladder)
                    and self._hot_streak >= hold):
                self._engage_next()
                self._hot_streak = 0
        else:
            self._hot_streak = 0
            self._ok_streak += 1
            if (self.engaged > 0
                    and self._ok_streak >= self.config.release_hold_ticks):
                self._release_last()
                self._ok_streak = 0
        # Re-apply sustained-effect steps (eviction) while engaged.
        for i in range(self.engaged):
            actuator = self.actuators.get(self.ladder[i])
            if actuator is not None and actuator.while_engaged:
                self._run_hook(self.ladder[i], actuator.while_engaged)
        # Publish the continuous prefetch budget on transitions: the
        # budget scales DOWN with the level before the binary
        # ``pause_prefetch`` step ever engages, and restores in exact
        # reverse on release (the pause/release pair is just the
        # budget's floor).
        budget = self.prefetch_budget()
        if budget != self._last_prefetch_budget:
            telemetry.PREFETCH.set_budget(budget)
            telemetry.FLIGHT.record(
                "prefetch.budget", scale=budget,
                prev=self._last_prefetch_budget,
                level=LEVEL_NAMES[level],
                paused=self.step_engaged("pause_prefetch"))
            self._last_prefetch_budget = budget
        return level

    # ------------------------------------------------- consumer queries

    def step_engaged(self, step: str) -> bool:
        try:
            return self.ladder.index(step) < self.engaged
        except ValueError:
            return False

    def engaged_steps(self) -> List[str]:
        return list(self.ladder[:self.engaged])

    def quality_cap(self) -> Optional[int]:
        """JPEG quality ceiling for interactive tiles while
        ``drop_quality`` is engaged; None = no cap."""
        if self.step_engaged("drop_quality"):
            return self.config.quality_cap
        return None

    def admission_scale(self) -> float:
        """Multiplier on the admission queue bound (``<= 1``);
        1.0 while ``tighten_admission`` is not engaged."""
        if self.step_engaged("tighten_admission"):
            return self.config.admission_scale
        return 1.0

    def bulk_shed_active(self) -> bool:
        return self.step_engaged("shed_bulk")

    def prefetch_budget(self) -> float:
        """The continuous prefetch budget scale in [0, 1]: a pure
        function of the folded level and the ``pause_prefetch`` ladder
        state, so it is symmetric by construction — whatever path the
        level took down, the identical path back up restores the
        identical budgets in reverse.

        * ok        -> 1.0
        * elevated  -> ``prefetch-budget-elevated`` (default 0.5)
        * critical  -> ``prefetch-budget-critical`` (default 0.25)
        * ``pause_prefetch`` engaged -> 0.0 (the ladder's binary pause
          is now the budget's floor, not a separate mechanism)

        Consumers (``services.prefetch.TilePrefetcher``) multiply this
        into their ``max_pending``, so speculative staging shrinks
        smoothly as the service starts drowning instead of running at
        full tilt until the ladder slams it off.
        """
        if self.step_engaged("pause_prefetch"):
            return 0.0
        if self.level >= LEVEL_CRITICAL:
            return getattr(self.config, "prefetch_budget_critical",
                           0.25)
        if self.level >= LEVEL_ELEVATED:
            return getattr(self.config, "prefetch_budget_elevated",
                           0.5)
        return 1.0

    def summary(self) -> str:
        """One-line /readyz annotation."""
        if self.engaged == 0 and self.level == LEVEL_OK:
            return "ok"
        steps = ",".join(self.engaged_steps()) or "-"
        return f"{LEVEL_NAMES[self.level]}; steps={steps}"

    # ------------------------------------------------------------ runner

    async def run(self) -> None:
        """Asyncio tick loop.  The loop's own scheduling lag (a loop
        that cannot keep a sleep on schedule is a loop that cannot keep
        responses on schedule) is not timed here: the ``loop_lag_ms``
        source reads the serving loop's one sampler
        (``utils.stopwatch.LoopLagSampler``, span ``loop.lag``)."""
        import asyncio

        interval = max(0.05, self.config.interval_s)
        while True:
            await asyncio.sleep(interval)
            self.tick()


def is_bulk(ctx) -> bool:
    """Bulk/projection classification for ``shed_bulk``: z-projection
    jobs and full-plane (no tile, no region) renders — the work class
    the ladder sheds FIRST, before any interactive degradation.

    Shape-mask requests (``ShapeMaskCtx``, identified by their
    ``shape_id``) are QoS-classed INTERACTIVE: a mask overlay is part
    of the viewer's pan loop, and it draws 1 fairness token like a
    tile — the mask-scraping loophole (no tile, no region used to
    read as bulk-or-crash here) closed with the session-model
    satellite of the autoscaler PR."""
    if getattr(ctx, "shape_id", None) is not None:
        return False
    return ctx.projection is not None or (
        ctx.tile is None and ctx.region is None)


def shed_bulk_under_pressure(ctx) -> None:
    """Brownout ladder "shed_bulk": while engaged, full-plane and
    z-projection work sheds with 503 + Retry-After BEFORE any
    read/stage cost — bulk work is the first availability sacrifice,
    always ahead of interactive tiles (the ladder-order invariant
    validated at config load).  Shared by the in-process and fleet
    handlers so the classification cannot drift.  Device-free (this
    module) so proxy-role frontends can call it too."""
    governor = active()
    if governor is None or not governor.bulk_shed_active() \
            or not is_bulk(ctx):
        return
    from .errors import OverloadedError
    telemetry.RESILIENCE.count_shed("pressure-bulk")
    telemetry.FLIGHT.record("admission.shed", reason="pressure-bulk",
                            image=ctx.image_id)
    raise OverloadedError(
        "bulk/projection work shed under resource pressure",
        retry_after_s=5.0)


def pressure_quality(quality: int, ctx) -> int:
    """Brownout ladder "drop_quality": cap INTERACTIVE tile JPEG
    quality while engaged (full-plane/bulk work is the shed step's
    problem, not this one's).  A capped render marks the ctx so the
    byte-cache write-back is skipped — lower-quality bytes must never
    be cached under the full-quality request key and outlive the
    brownout."""
    governor = active()
    if governor is None or ctx.tile is None:
        return quality
    cap = governor.quality_cap()
    if cap is not None and quality > cap:
        ctx._pressure_quality_capped = True
        return cap
    return quality


def build_sources(services=None, renderer=None, router=None,
                  loop_lag=None
                  ) -> Dict[str, Callable[[], Optional[float]]]:
    """The standard signal set over a service stack.  Every source is
    duck-typed and None-safe, so one missing subsystem just drops its
    signal rather than failing the governor.  ``loop_lag`` is the
    serving loop's ``LoopLagSampler``: the ``loop_lag_ms`` signal is
    its smoothed reading."""
    raw_cache = getattr(services, "raw_cache", None)
    caches = getattr(services, "caches", None)
    disk = getattr(caches, "disk", None)
    renderer = renderer or getattr(services, "renderer", None)

    def hbm() -> Optional[float]:
        if raw_cache is None or not getattr(raw_cache, "max_bytes", 0):
            return None
        return raw_cache.size_bytes / raw_cache.max_bytes

    def disk_frac() -> Optional[float]:
        if disk is None or not getattr(disk, "max_bytes", 0):
            return None
        return disk.size_bytes / disk.max_bytes

    def queue() -> Optional[float]:
        depth = None
        if router is not None:
            depth = router.queue_depth()
        elif hasattr(renderer, "queue_depth"):
            depth = renderer.queue_depth()
        return None if depth is None else float(depth)

    return {
        "hbm": hbm,
        "host_rss_mb": lambda: read_rss_mb(),
        "disk": disk_frac,
        "queue": queue,
        "loop_lag_ms": lambda: (None if loop_lag is None
                                else loop_lag.ewma_ms),
    }


def build_actuators(config, services=None, renderer=None, router=None
                    ) -> Dict[str, StepActuator]:
    """The standard actuator set.  Flag-only steps (``drop_quality``,
    ``shed_bulk``, ``tighten_admission``) carry no actuator — their
    consumers query the governor directly.  ``router`` (a FleetRouter)
    lets the evict step demote hot-route replica sets first: replica
    HBM is the cheapest thing to give back under pressure (the ring
    owner still holds the plane)."""
    prefetcher = getattr(services, "prefetcher", None)
    warmstate = getattr(services, "warmstate", None)
    raw_cache = getattr(services, "raw_cache", None)
    disk = getattr(getattr(services, "caches", None), "disk", None)
    renderer = renderer or getattr(services, "renderer", None)
    actuators: Dict[str, StepActuator] = {}

    if prefetcher is not None:
        def _pf(paused):
            def hook():
                prefetcher.paused = paused
            return hook
        actuators["pause_prefetch"] = StepActuator(
            engage=_pf(True), release=_pf(False))

    if warmstate is not None:
        def _ws(paused):
            def hook():
                warmstate.paused = paused
            return hook
        actuators["pause_snapshots"] = StepActuator(
            engage=_ws(True), release=_ws(False))

    def evict():
        # Replica demotion FIRST: hot-route replica planes are
        # redundant by construction (the ring owner keeps its copy),
        # so shedding them turns the subsequent LRU pass into the one
        # that reclaims them — the "eviction deferred to cache
        # pressure" half of the hot-key lifecycle.
        if router is not None and hasattr(router, "shed_replicas"):
            try:
                router.shed_replicas()
            except Exception:
                log.debug("replica shed failed", exc_info=True)
        frac = config.evict_to_frac
        if raw_cache is not None and hasattr(raw_cache,
                                             "evict_to_fraction"):
            raw_cache.evict_to_fraction(frac)
        if disk is not None and hasattr(disk, "evict_to_fraction"):
            disk.evict_to_fraction(frac)

    if raw_cache is not None or disk is not None or router is not None:
        actuators["evict_caches"] = StepActuator(
            engage=evict, while_engaged=evict)

    if renderer is not None and hasattr(renderer, "set_lane_cap"):
        actuators["cap_lanes"] = StepActuator(
            engage=lambda: renderer.set_lane_cap(config.lane_cap),
            release=lambda: renderer.set_lane_cap(0))

    return actuators


# ------------------------------------------------------- module global

_INSTALLED: Optional[PressureGovernor] = None


def install(governor: Optional[PressureGovernor]
            ) -> Optional[PressureGovernor]:
    """Install the process-global governor (None uninstalls); the
    faultinject idiom — consumers pay one ``is None`` check when the
    layer is off."""
    global _INSTALLED
    _INSTALLED = governor
    return _INSTALLED


def uninstall() -> None:
    global _INSTALLED
    _INSTALLED = None


def active() -> Optional[PressureGovernor]:
    return _INSTALLED
