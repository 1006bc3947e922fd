"""Warm-state snapshot/rehydrate: the lifecycle BETWEEN process lives.

BENCH_r05: 26 tiles/s warm vs 0.73 cold.  The disk byte cache
(``services.diskcache``) and the serialized executables
(``server.execcache``) make the expensive state durable; this module is
the engine that (a) periodically — and on SIGTERM, through the ordered
shutdown chain — writes a MANIFEST of what is hot, and (b) on boot
replays it in the background so the first interactive minute serves
warm instead of at wire+compile speed.

The manifest records three ladders of hot state:

* **byte keys** — the memory LRU's most-recent keys per named cache
  (recency is the access-frequency proxy; the bytes themselves are
  already durable in the disk tier).  Rehydrate promotes disk→memory
  through the cache stack's own read-through, so a promoted key serves
  at memory speed from request one.
* **planes** — the HBM raw cache's resident region entries: source
  coords + content digest.  Rehydrate re-reads each region from the
  pixel store and re-stages it through the EXISTING staging path
  (packed wire, digest dedup), so the pan/zoom hot set is back in HBM
  before users ask.
* **executables** — the serialized compiled-program keys
  (``server.execcache``).  Rehydrate deserializes them so the first
  group of each shape calls a compiled program, no trace/compile.

Everything is strictly best-effort: a missing/corrupt/foreign manifest
is a clean cold boot; the rehydrator yields to live traffic and aborts
on shutdown; no failure here may ever fail a request or the boot.
"""

from __future__ import annotations

import json
import logging
import os
import threading
import time
from typing import List, Optional

from ..utils import telemetry

log = logging.getLogger("omero_ms_image_region_tpu.warmstate")

MANIFEST_VERSION = 1
MANIFEST_NAME = "manifest.json"

_CACHE_NAMES = ("image_region", "pixels_metadata", "shape_mask")

# Disk-tier key namespaces (services.cache.Caches.from_config).
_DISK_PREFIXES = {"image_region": "img:", "pixels_metadata": "meta:",
                  "shape_mask": "mask:"}


def restage_plane_entry(raw_cache, pixels_service, entry: dict) -> bool:
    """Re-read ONE manifest plane entry from the pixel store and stage
    it into HBM through the existing staging path (packed wire, digest
    dedup).  Shared by the boot rehydrator and the rolling-drain
    pre-stager (``parallel.fleet`` hands a draining member's shard
    manifest to its ring successor through this).  Returns False on a
    malformed entry; read errors propagate to the caller's guard."""
    from ..io.devicecache import entry_region_key
    from ..server.region import RegionDef

    try:
        key = entry_region_key(entry)
    except (KeyError, TypeError, ValueError):
        # Malformed, or an older manifest's entry (a list of channels
        # where one channel stands now): a cold miss later.
        return False
    if key in raw_cache:
        return True

    def load():
        image_id, z, t, level, (x, y, w, h), channel = key
        src = pixels_service.get_pixel_source(image_id)
        return src.get_region(z, channel, t, RegionDef(x, y, w, h),
                              level)

    # Carry the entry's recorded routing identity onto the receiving
    # cache: a restaged plane that loses its route would fall back to
    # key-repr spreading on the NEXT drain's handoff, silently handing
    # planes to ring members that will never serve their requests.
    raw_cache.get_or_load(key, load, route_key=entry.get("route"))
    return True


def _load_manifest(path: str) -> Optional[dict]:
    """Parse-or-None: a truncated, corrupt or non-JSON manifest is a
    cold boot, never an exception."""
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return None
    if not isinstance(doc, dict) or doc.get("version") != MANIFEST_VERSION:
        return None
    return doc


class WarmStateManager:
    """Snapshot timer + boot rehydrator for one device-owning process.

    ``services`` is duck-typed (``server.handler.ImageRegionServices``):
    the manager reads its caches, raw cache, renderer (exec cache) and
    pixel service; it never holds the request path.
    """

    def __init__(self, directory: str, services,
                 snapshot_interval_s: float = 60.0,
                 snapshot_top_k: int = 512,
                 max_plane_entries: int = 256,
                 rehydrate_concurrency: int = 2):
        self.directory = directory
        self.services = services
        self.snapshot_interval_s = snapshot_interval_s
        self.snapshot_top_k = snapshot_top_k
        self.max_plane_entries = max_plane_entries
        self.rehydrate_concurrency = max(1, rehydrate_concurrency)
        self._stop = threading.Event()
        self._snapshot_lock = threading.Lock()
        self._timer_thread: Optional[threading.Thread] = None
        self._rehydrate_thread: Optional[threading.Thread] = None
        # Brownout ladder hook (server.pressure "pause_snapshots"):
        # while paused the periodic timer skips its snapshot — the
        # manifest write is disk + CPU work a drowning process can
        # defer.  Explicit snapshots (SIGTERM chain, /debug/warmstate,
        # drains) still run: those are the moments the manifest is FOR.
        self.paused = False

    # ------------------------------------------------------------ start

    def start(self, rehydrate: bool = True) -> None:
        """Kick the boot rehydrator and the periodic snapshot timer
        (both daemon threads; both end at ``close``)."""
        os.makedirs(self.directory, exist_ok=True)
        if rehydrate:
            self._rehydrate_thread = threading.Thread(
                target=self._rehydrate_guarded,
                name="warmstate-rehydrate", daemon=True)
            self._rehydrate_thread.start()
        if self.snapshot_interval_s > 0:
            self._timer_thread = threading.Thread(
                target=self._timer_loop, name="warmstate-snapshot",
                daemon=True)
            self._timer_thread.start()

    def close(self, timeout_s: float = 5.0) -> None:
        self._stop.set()
        for t in (self._rehydrate_thread, self._timer_thread):
            if t is not None and t.is_alive():
                t.join(timeout=timeout_s)

    def _timer_loop(self) -> None:
        while not self._stop.wait(self.snapshot_interval_s):
            if self.paused:
                continue
            try:
                self.snapshot_now()
            except Exception:
                # snapshot_now is internally guarded; this is the
                # thread-never-dies belt over those braces.
                log.warning("periodic warm-state snapshot failed",
                            exc_info=True)

    # --------------------------------------------------------- snapshot

    @property
    def manifest_path(self) -> str:
        return os.path.join(self.directory, MANIFEST_NAME)

    def _collect_manifest(self) -> dict:
        doc = {"version": MANIFEST_VERSION, "ts": round(time.time(), 3),
               "byte_keys": {}, "planes": [], "executables": []}
        caches = getattr(self.services, "caches", None)
        disk_keys: Optional[List[str]] = None
        for name in _CACHE_NAMES:
            stack = getattr(caches, name, None)
            tiers = getattr(stack, "tiers", ())
            keys: List[str] = []
            if tiers:
                recency = getattr(tiers[0], "keys_by_recency", None)
                if recency is not None:
                    keys = recency(self.snapshot_top_k)
            if not keys:
                # The native C++ memory tier has no key enumeration;
                # fall back to the durable tier's own recency order
                # (mtime MRU-first — reads bump it, so this IS the
                # hot set as the disk saw it).
                disk = getattr(caches, "disk", None)
                if disk is not None:
                    if disk_keys is None:
                        disk_keys = disk.keys_sync()
                    prefix = _DISK_PREFIXES[name]
                    keys = [k[len(prefix):] for k in disk_keys
                            if k.startswith(prefix)][
                                :self.snapshot_top_k]
            doc["byte_keys"][name] = keys
        raw_cache = getattr(self.services, "raw_cache", None)
        if raw_cache is not None and hasattr(raw_cache,
                                             "snapshot_entries"):
            doc["planes"] = raw_cache.snapshot_entries(
                self.max_plane_entries)
        exec_cache = getattr(getattr(self.services, "renderer", None),
                             "exec_cache", None)
        if exec_cache is not None:
            doc["fingerprint"] = exec_cache.fingerprint()
            doc["executables"] = exec_cache.stored_keys()
        # The perf sentinel's learned latency baselines ride the same
        # manifest: a restart must not re-learn "normal" from scratch
        # (a regression deployed WITH the restart would become the new
        # baseline before the sentinel could see it).  Lazy import —
        # services must not import server at module scope.
        from ..server import sentinel as sentinel_mod
        engine = sentinel_mod.active()
        if engine is not None:
            baselines = engine.export_baseline()
            if baselines.get("baselines"):
                doc["sentinel"] = baselines
        return doc

    def snapshot_now(self) -> Optional[str]:
        """Write the manifest atomically; returns the path or None.
        Never raises — it runs inside signal-time shutdown chains and
        the periodic timer alike.  Serialized against itself (the
        SIGTERM chain may race the timer)."""
        t0 = time.perf_counter()
        with self._snapshot_lock:
            try:
                doc = self._collect_manifest()
                os.makedirs(self.directory, exist_ok=True)
                path = self.manifest_path
                tmp = path + f".tmp.{os.getpid()}"
                with open(tmp, "w") as f:
                    json.dump(doc, f)
                os.replace(tmp, path)
            except Exception:
                telemetry.PERSIST.count_snapshot(0.0, error=True)
                log.warning("warm-state snapshot failed", exc_info=True)
                return None
        duration_ms = (time.perf_counter() - t0) * 1000.0
        telemetry.PERSIST.count_snapshot(duration_ms)
        telemetry.FLIGHT.record(
            "warmstate.snapshot",
            keys=sum(len(v) for v in doc["byte_keys"].values()),
            planes=len(doc["planes"]),
            executables=len(doc["executables"]),
            ms=round(duration_ms, 1))
        return path

    # -------------------------------------------------------- rehydrate

    def _yield_to_live_load(self) -> None:
        """Best-effort politeness: while serving traffic is queued or
        in flight, the rehydrator waits — briefly and boundedly, so a
        continuously loaded boot still trickles warm state in instead
        of starving forever."""
        renderer = getattr(self.services, "renderer", None)
        depth = getattr(renderer, "queue_depth", None)
        inflight = getattr(renderer, "inflight", None)
        if depth is None:
            return
        waited = 0.0
        while not self._stop.is_set() and waited < 2.0:
            busy = depth() > 0 or (inflight is not None
                                   and inflight() > 0)
            if not busy:
                return
            time.sleep(0.05)
            waited += 0.05

    def _rehydrate_guarded(self) -> None:
        t0 = time.perf_counter()
        try:
            self._rehydrate()
        except Exception:
            # Strictly best-effort: a rehydrate bug is a slow first
            # minute, never a failed boot.
            telemetry.PERSIST.rehydrate_end(
                (time.perf_counter() - t0) * 1000.0, aborted=True)
            log.warning("warm-state rehydrate failed; serving cold",
                        exc_info=True)

    def _rehydrate(self) -> None:
        doc = _load_manifest(self.manifest_path)
        if doc is None:
            telemetry.PERSIST.rehydrate_begin(0)
            telemetry.PERSIST.rehydrate_end(0.0)
            log.info("no usable warm-state manifest; cold boot")
            return
        exec_cache = getattr(getattr(self.services, "renderer", None),
                             "exec_cache", None)
        exec_keys = list(doc.get("executables") or ())
        if exec_cache is not None and doc.get("fingerprint") not in (
                None, exec_cache.fingerprint()):
            # Different jax/jaxlib/device than the life that wrote the
            # manifest: its executables cannot load here.  Bytes and
            # planes are hardware-independent and still replay.
            log.info("warm-state manifest fingerprint differs; "
                     "skipping executable rehydrate")
            exec_keys = []
        # Sentinel baseline rehydrate first — it is a dict copy, not
        # I/O, and the engine should know "normal" before the first
        # post-boot windows close.  Best-effort like everything here.
        sentinel_doc = doc.get("sentinel")
        if sentinel_doc:
            try:
                from ..server import sentinel as sentinel_mod
                engine = sentinel_mod.active()
                if engine is not None:
                    n = engine.load_baseline(sentinel_doc)
                    if n:
                        log.info("restored %d sentinel baselines", n)
            except Exception:
                log.warning("sentinel baseline rehydrate failed",
                            exc_info=True)
        byte_items = [(name, key)
                      for name in _CACHE_NAMES
                      for key in (doc.get("byte_keys") or {}).get(name,
                                                                  ())]
        plane_items = list(doc.get("planes") or ())
        exec_items = (len(exec_keys) if exec_cache is not None else 0)
        total = len(byte_items) + len(plane_items) + exec_items
        telemetry.PERSIST.rehydrate_begin(total)
        telemetry.FLIGHT.record("warmstate.rehydrate.start",
                                items=total)
        t0 = time.perf_counter()
        aborted = False

        # 1. Executables first: they are what the first GROUP of each
        # shape needs, and deserializing is milliseconds against the
        # seconds a compile costs.  One progress item per manifest key,
        # loaded or not, so items_done always converges on items_total
        # (the rolling-deploy runbook waits for "done N/N").
        if exec_items:
            n = exec_cache.preload(exec_keys)
            for _ in range(n):
                telemetry.PERSIST.rehydrate_step("executable")
            for _ in range(exec_items - n):
                telemetry.PERSIST.rehydrate_step("executable",
                                                 error=True)

        # 2. Disk -> memory byte promotion: the stack's own
        # read-through back-fills the memory tier on a disk hit, so a
        # promoted key's next request is a memory hit.
        caches = getattr(self.services, "caches", None)
        for name, key in byte_items:
            if self._stop.is_set():
                aborted = True
                break
            self._yield_to_live_load()
            try:
                value = self._promote_byte(caches, name, key)
                telemetry.PERSIST.rehydrate_step(
                    "byte", nbytes=len(value) if value else 0,
                    error=value is None)
            except Exception:
                telemetry.PERSIST.rehydrate_step("byte", error=True)

        # 3. Plane re-stage to HBM through the existing staging path
        # (packed wire + digest dedup), bounded by the concurrency
        # knob — staging is link work and must not saturate the
        # host->device wire under live load.
        if plane_items and not aborted and not self._stop.is_set():
            aborted = self._restage_planes(plane_items) or aborted
        telemetry.PERSIST.rehydrate_end(
            (time.perf_counter() - t0) * 1000.0, aborted=aborted)
        telemetry.FLIGHT.record("warmstate.rehydrate.done",
                                aborted=aborted,
                                ms=round((time.perf_counter() - t0)
                                         * 1000.0, 1))
        log.info("warm-state rehydrate %s (%d items)",
                 "aborted" if aborted else "complete", total)

    def _promote_byte(self, caches, name: str,
                      key: str) -> Optional[bytes]:
        """Disk tier -> memory tier for one key; returns the bytes or
        None (not durable / corrupt — both fine, the next request
        re-renders)."""
        stack = getattr(caches, name, None)
        tiers = getattr(stack, "tiers", ())
        memory = tiers[0] if tiers else None
        disk = None
        for tier in tiers:
            inner = getattr(tier, "inner", None)
            if inner is not None and hasattr(inner, "get_sync"):
                disk = tier
                break
        if memory is None or disk is None:
            return None
        if not isinstance(key, str):
            return None
        value = disk.inner.get_sync(disk.prefix + key)
        if value is None:
            return None
        set_sync = getattr(memory, "set_sync", None)
        if set_sync is None:
            return None
        set_sync(key, value)
        return value

    def _restage_planes(self, plane_items: List[dict]) -> bool:
        """Re-read manifest regions from the pixel store and stage them
        back into HBM (worker pool of ``rehydrate_concurrency``).
        Returns True when aborted by shutdown."""
        import concurrent.futures as cf

        raw_cache = getattr(self.services, "raw_cache", None)
        pixels_service = getattr(self.services, "pixels_service", None)
        if raw_cache is None or pixels_service is None:
            for _ in plane_items:
                telemetry.PERSIST.rehydrate_step("plane", error=True)
            return False

        def restage(entry: dict) -> bool:
            return restage_plane_entry(raw_cache, pixels_service,
                                       entry)

        aborted = False
        with cf.ThreadPoolExecutor(
                max_workers=self.rehydrate_concurrency,
                thread_name_prefix="warmstate-stage") as pool:
            pending = []
            for entry in plane_items:
                if self._stop.is_set():
                    aborted = True
                    break
                self._yield_to_live_load()
                pending.append(pool.submit(restage, entry))
            for fut in pending:
                try:
                    ok = fut.result()
                    telemetry.PERSIST.rehydrate_step("plane",
                                                     error=not ok)
                except Exception:
                    telemetry.PERSIST.rehydrate_step("plane",
                                                     error=True)
        return aborted
