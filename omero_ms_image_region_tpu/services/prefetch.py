"""Predictive, budgeted tile prefetch into the HBM raw cache.

SURVEY.md §2b maps the reference's ``PixelBuffer`` surface to "a tile
reader service with host-pinned staging -> HBM, async prefetch"; this is
the prefetch half — now SESSION-AWARE.  Each served tile feeds the
per-session viewport model (:mod:`services.viewport`), and what gets
speculatively staged is that session's PREDICTED next tiles (velocity
extrapolation, next-zoom children/parent) instead of a blind lattice
guess; sessions with no trajectory yet fall back to the classic four
lattice neighbors.

Three contracts this layer holds:

* **Budgeted, never binary.**  ``max_pending`` is scaled continuously:
  by this prefetcher's own ``budget_scale`` and by the pressure
  governor's :meth:`~..server.pressure.PressureGovernor.prefetch_budget`
  (elevated pressure halves the budget, critical quarters it, the
  ``pause_prefetch`` ladder step floors it at 0).  Budget changes take
  effect on QUEUED work too: a pool item that starts after the budget
  hit zero exits without reading a byte — ``flush()`` during a pause no
  longer waits out loads nobody wants (the PR 9 pause/flush bug).
* **Fleet-aware.**  With ``cache_for_route`` installed (the combined
  fleet wires ``FleetRouter.cache_for_route``), every predicted tile
  stages into the HBM shard of the member that will SERVE it — routed
  by the same ``plane_route_key`` the router hashes — so prefetch warms
  the right shard and never duplicates a plane across members (the
  digest-deduped staging path is unchanged underneath).
* **Accountable.**  Staged keys are remembered (bounded) and the
  handler reports foreground hits back through :meth:`note_hit`, so the
  predictive hit rate is a measured number (``imageregion_prefetch_*``,
  ``bench.py --smoke --sessions``), not a hope.

Prefetch stays strictly best-effort: failures are swallowed (the
foreground path re-reads on demand), and nothing is scheduled when the
region is not tile-shaped (full-plane and arbitrary-region requests
don't pan).
"""

from __future__ import annotations

import concurrent.futures
import logging
import threading
from collections import OrderedDict
from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np

from ..io.devicecache import DeviceRawCache, region_key
from ..utils import telemetry
from ..utils.stopwatch import stopwatch

logger = logging.getLogger(__name__)

# Staged-key memory bound: enough to cover every plane the HBM tiers
# can hold, small enough to never matter.
_STAGED_KEYS_MAX = 8192


class _RouteStub:
    """The minimal ctx shape ``parallel.fleet.plane_route_key`` hashes:
    a predicted tile's SOURCE-PLANE identity, built exactly the way the
    future foreground request will build it — so the prefetch route and
    the serve route can never disagree."""

    __slots__ = ("image_id", "z", "t", "resolution", "tile", "region")

    def __init__(self, image_id, z, t, resolution, tile):
        self.image_id = image_id
        self.z = z
        self.t = t
        self.resolution = resolution
        self.tile = tile
        self.region = None


class TilePrefetcher:
    """Stages predicted next tiles of each session into the device
    cache tier that will serve them."""

    def __init__(self, raw_cache: DeviceRawCache, max_workers: int = 2,
                 max_pending: int = 16, viewport=None,
                 cache_for_route: Optional[Callable] = None,
                 lookahead: int = 2):
        self.raw_cache = raw_cache
        self.max_pending = max_pending
        # services.viewport.ViewportTracker (None = lattice-only).
        self.viewport = viewport
        # Fleet seam: route_key -> the owning member's DeviceRawCache
        # (None return = stage locally).  Installed by create_app for
        # combined fleets; absent everywhere else.
        self.cache_for_route = cache_for_route
        # Cross-host seam (parallel.federation): when the predicted
        # plane's ring owner is a REMOTE member,
        # ``remote_prestage(route, entry) -> bool`` hints the owner's
        # host to stage it from ITS pixel store (fire-and-forget wire
        # op) — speculation warms the member that will serve the
        # request, never this host's wrong shard.  Installed by
        # create_app for federated fleets; absent everywhere else.
        self.remote_prestage = None
        # Hot-key seam (``FleetRouter.local_replica_caches``): a
        # promoted route is read-balanced across an R>1 replica set,
        # so its predictions must warm EVERY local replica shard — a
        # balanced read landing on a cold replica re-reads from disk
        # and the promotion buys nothing.  Empty/None for unpromoted
        # routes and non-fleet deployments.
        self.replica_caches: Optional[Callable] = None
        self.lookahead = max(1, int(lookahead))
        # Local budget scale in [0, 1]; multiplied with the pressure
        # governor's prefetch_budget().  The brownout ladder's
        # ``pause_prefetch`` actuator drives this through the ``paused``
        # property (budget 0 — the binary flag is now the budget floor).
        self.budget_scale = 1.0
        self._pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=max_workers, thread_name_prefix="tile-prefetch")
        self._lock = threading.Lock()
        self._pending: set = set()
        self._futures: set = set()
        # Keys this prefetcher staged, awaiting their foreground hit.
        self._staged_keys: "OrderedDict" = OrderedDict()
        self.scheduled = 0
        self.staged = 0
        self.hits = 0
        self.predicted = 0

    # ------------------------------------------------------------ budget

    @property
    def paused(self) -> bool:
        """Binary view of the budget floor (kept for the PR 9 ladder
        actuator and its tests): paused == budget 0."""
        return self.budget_scale <= 0.0

    @paused.setter
    def paused(self, value: bool) -> None:
        self.budget_scale = 0.0 if value else 1.0

    def effective_budget(self) -> float:
        """This instant's combined budget scale: local x governor."""
        scale = self.budget_scale
        if scale <= 0.0:
            return 0.0
        from ..server.pressure import active
        governor = active()
        if governor is not None:
            scale *= governor.prefetch_budget()
        return max(0.0, min(1.0, scale))

    def effective_max_pending(self) -> int:
        """The pending-slot bound this instant (0 = fully paused)."""
        return int(self.max_pending * self.effective_budget())

    # ------------------------------------------------------- accounting

    def _mark_staged(self, key) -> None:
        with self._lock:
            self._staged_keys[key] = True
            while len(self._staged_keys) > _STAGED_KEYS_MAX:
                self._staged_keys.popitem(last=False)

    def note_hit(self, key) -> None:
        """The foreground path found ``key`` resident: if this
        prefetcher staged it, that is a PREDICTIVE HIT — the pan/zoom
        step paid render + encode only."""
        if not self._staged_keys:
            return      # nothing staged: no lock on the serving path
        with self._lock:
            if self._staged_keys.pop(key, None) is None:
                return
            self.hits += 1
        telemetry.PREFETCH.count_hit()

    def hit_rate(self) -> Optional[float]:
        """Predictive hit rate: staged planes the foreground came back
        for, over planes staged.  None before anything staged."""
        if self.staged == 0:
            return None
        return self.hits / self.staged

    # ------------------------------------------------------- candidates

    def _candidates(self, ctx_like: Tuple, session_key: Optional[str],
                    tile) -> List[Tuple[Optional[int], int, int, int,
                                        int]]:
        """Predicted (resolution, z, t, x, y) tuples for this serve —
        the session's viewport predictions when a trajectory exists,
        else the four lattice neighbors of the served tile."""
        image_id, z, t, resolution = ctx_like
        out: List[Tuple[Optional[int], int, int, int, int]] = []
        if self.viewport is not None:
            predictions = self.viewport.predict(
                session_key, lookahead=self.lookahead)
            for p in predictions:
                if p.image_id != image_id:
                    continue
                out.append((p.resolution, p.z, p.t, p.x, p.y))
            if out:
                self.predicted += len(out)
                telemetry.PREFETCH.count_predicted(len(out))
                telemetry.FLIGHT.record(
                    "prefetch.predict", n=len(out),
                    session=(session_key or "-")[:16],
                    x=tile.x, y=tile.y)
                return out
        for dx, dy in ((-1, 0), (1, 0), (0, -1), (0, 1)):
            nx, ny = tile.x + dx, tile.y + dy
            if nx < 0 or ny < 0:
                continue
            out.append((resolution, z, t, nx, ny))
        return out

    # --------------------------------------------------------- schedule

    def tile_served(self, src, image_id: int, z: int, t: int,
                    resolution, levels, tile, tile_size,
                    max_tile_length: int, active: Sequence[int],
                    flip_horizontal: bool = False,
                    flip_vertical: bool = False,
                    session_key: Optional[str] = None) -> None:
        """Feed the viewport model and schedule the session's predicted
        tiles.

        Candidate regions resolve through the same ``get_region_def`` /
        ``clamp_region_to_plane`` pipeline (flips included) as the
        foreground read, so the cache keys are guaranteed identical to
        the ones the next pan/zoom request will compute.
        """
        from ..server.region import (RegionDef, clamp_region_to_plane,
                                     get_region_def)

        if tile is None:
            return
        if self.viewport is not None:
            self.viewport.observe(session_key, image_id, z, t,
                                  resolution, tile.x, tile.y)
        budget = self.effective_max_pending()
        if budget <= 0:
            telemetry.PREFETCH.count_skipped("budget")
            return
        for (nres, nz, nt, nx, ny) in self._candidates(
                (image_id, z, t, resolution), session_key, tile):
            if nres is not None and not 0 <= nres < len(levels):
                continue
            ntile = RegionDef(x=nx, y=ny, width=tile.width,
                              height=tile.height)
            region = get_region_def(levels, nres, ntile, None,
                                    tile_size, max_tile_length,
                                    flip_horizontal, flip_vertical)
            clamp_region_to_plane(levels, nres, region)
            if region.width <= 0 or region.height <= 0:
                continue
            level = nres or 0
            # One cache identity a channel plane, as the foreground
            # read builds them.
            keys = [region_key(image_id, nz, nt, level,
                               region.as_tuple(), c) for c in active]
            # Fleet routing: the predicted tile stages into the HBM
            # shard of the member that will serve it (route computed
            # from the REQUEST identity, exactly like the router).
            from ..parallel.fleet import plane_route_key
            route = plane_route_key(_RouteStub(image_id, nz, nt, nres,
                                               ntile))
            cache = self.raw_cache
            if self.cache_for_route is not None:
                routed = self.cache_for_route(route)
                if routed is not None:
                    cache = routed
                elif self.remote_prestage is not None:
                    # No local cache owns this route: its ring owner
                    # lives on another host — hand IT the prediction
                    # (a prestage hint; the owner reads the region
                    # from its own store through the digest-deduped
                    # staging path) and spend nothing here.
                    sent = [self.remote_prestage(
                        route, {"key": [image_id, nz, nt, level,
                                        list(region.as_tuple()), c],
                                "route": route}) for c in active]
                    if any(sent):
                        self.predicted += 1
                        continue
            # Hot-route replication: when the router promoted this
            # route, stage the prediction into every LOCAL replica
            # shard, not just the routed owner's.
            targets = [cache]
            if self.replica_caches is not None:
                try:
                    reps = list(self.replica_caches(route) or ())
                except Exception:
                    reps = []
                targets += [c for c in reps if c is not cache]
            for tcache in targets:
                if tcache is None:
                    continue
                # One pool task a predicted tile: the channel planes of
                # it that this shard lacks (a viewer that toggled one
                # channel finds the others resident).
                absent = set(tcache.absent(keys))
                if not absent:
                    continue   # already resident: no pool churn
                missing = [(c, key) for c, key in zip(active, keys)
                           if key in absent]
                # Replica stagings carry a per-cache token so two
                # shards can hold the same tile in flight at once.
                token = tuple(key for _, key in missing)
                if tcache is not cache:
                    token = (id(tcache), token)
                with self._lock:
                    if token in self._pending:
                        # Already in flight: dedupe, not a budget
                        # signal — counting it as one would read as
                        # exhaustion on dashboards while slots sit
                        # free.
                        continue
                    if len(self._pending) >= budget:
                        telemetry.PREFETCH.count_skipped("budget")
                        continue
                    self._pending.add(token)
                try:
                    future = self._pool.submit(
                        self._load, src, tcache, missing, route, nz, nt,
                        level, region, token)
                except RuntimeError:   # pool shut down mid-request
                    with self._lock:
                        self._pending.discard(token)
                    return
                self.scheduled += 1
                telemetry.PREFETCH.count_scheduled()
                with self._lock:
                    self._futures.add(future)
                future.add_done_callback(
                    lambda f: self._futures.discard(f))

    def _load(self, src, cache, missing, route, z: int, t: int,
              level: int, region, token) -> None:
        """Stage the ``missing`` (channel, key) planes of one predicted
        tile, under span ``prefetch.stage`` (on this pool thread's host
        line too: the device's idle gaps can be put down to it).
        ``staged`` and the predictive hits count planes."""
        try:
            # Budget changes bind QUEUED work too: an item whose turn
            # comes after the budget hit zero exits without touching
            # the store — pausing mid-flight cancels the backlog's
            # effect, and flush() during a pause settles immediately.
            if self.effective_budget() <= 0.0:
                telemetry.PREFETCH.count_skipped("paused")
                return

            with stopwatch("prefetch.stage", tiles=1,
                           planes=len(missing)):
                for c, key in missing:
                    loaded = [False]

                    def loader(c=c, loaded=loaded) -> np.ndarray:
                        loaded[0] = True
                        return src.get_region(z, c, t, region, level)

                    cache.get_or_load(key, loader, route_key=route,
                                      by="prefetch")
                    if loaded[0]:
                        self.staged += 1
                        telemetry.PREFETCH.count_staged()
                        self._mark_staged(key)
        except Exception as e:  # best-effort: foreground re-reads on miss
            logger.debug("prefetch failed for %s: %r", token, e)
        finally:
            with self._lock:
                self._pending.discard(token)

    def flush(self, timeout: float = 10.0) -> None:
        """Wait for in-flight prefetches (tests/shutdown).  Paused
        (budget-0) backlogs settle immediately — queued items exit at
        the budget check instead of loading."""
        with self._lock:
            outstanding = list(self._futures)
        concurrent.futures.wait(outstanding, timeout=timeout)

    def close(self) -> None:
        self._pool.shutdown(wait=False, cancel_futures=True)
