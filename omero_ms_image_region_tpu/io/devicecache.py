"""Device-resident raw tile cache: HBM as the hot tier of the tile store.

SURVEY.md §2b maps the reference's ``PixelBuffer`` to "a tile reader
service with host-pinned staging -> HBM".  This is the HBM half: raw
channel planes are settings-independent, and the interactive OMERO.web
pattern is re-requesting the same tiles with different windows/colors/
LUTs — so after the first read, a settings change costs zero host->device
bytes (the dominant cost on link-constrained deployments; the encoded
region cache above this one only covers byte-identical requests).

Keyed by (image, z, t, level, region, channel): ONE channel plane
``[h, w]`` an entry (:func:`region_key`), so a viewer that switches one
of its shown channels reads and uploads that one plane, every sample is
resident at most once however the shown sets overlap, and the stack
a render takes is put together from the planes on the device, never
kept beside them: a group's ``[B, C_active, h, w]`` by one program
over its members' planes (``ops.render.stack_group_planes``), or one
request's ``[C_active, h, w]`` where it needs a flip or a pad of its
own (``ops.render.stack_channel_planes``).  Bounded by device bytes
with LRU eviction (dropping the
reference frees the HBM buffer).  Raw planes stay in their storage
dtype (uint16 halves HBM vs float32); the render kernels cast on
device.

Content addressing: with ``digest_index`` on (the default), every host
plane stack staged through :meth:`DeviceRawCache.get_or_load` is also
indexed by its content digest (:func:`plane_digest`).  A plane whose
bytes are already resident — under ANY key: a wire-pushed
``("plane", digest)`` entry, or the same content read for a different
region identity — is never re-shipped over the host->device link; the
new key aliases the resident buffer.  This is what backs the sidecar's
digest-first wire protocol (``server.sidecar``: probe by digest, upload
only on miss).
"""

from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Callable, Dict, Hashable, Optional, Set, Tuple


def plane_digest(arr) -> str:
    """Content address of a host plane stack: dtype + shape + bytes.

    BLAKE2b-128 — collision-safe at cache scale and ~GB/s on host, so
    digesting an 8 MB tile costs ~ms against the 100s-of-ms its upload
    costs on a thin link.
    """
    import hashlib

    import numpy as np

    a = np.ascontiguousarray(arr)
    h = hashlib.blake2b(digest_size=16)
    h.update(str(a.dtype).encode())
    h.update(",".join(str(s) for s in a.shape).encode())
    h.update(memoryview(a).cast("B"))
    return h.hexdigest()


class DeviceRawCache:
    """LRU of device-resident raw tile arrays.

    ``get_or_load(key, loader)`` returns a ``jax.Array``; ``loader()``
    supplies the host ndarray on miss.  Thread-safe (the render path runs
    in worker threads); the device transfer happens outside the lock, and
    concurrent misses on one key may both load — last write wins, which
    is correct for immutable pixel data.  The load that finds its key
    resident when it inserts is counted, by its caller's ``by``
    (``telemetry.DUPLICATE_LOADS``): the read and upload the race cost.
    """

    def __init__(self, max_bytes: int = 2 * 1024 * 1024 * 1024,
                 digest_index: bool = True):
        self.max_bytes = max_bytes
        self.digest_index = digest_index
        # The device a host plane is uploaded to: a fleet member's pin
        # (``parallel.fleet.build_local_members``), so that whoever
        # loads into this shard (the member's handler, the shared
        # prefetcher, a drain's prestage) lands it on the member's
        # chip.  None = the process default device.
        self.device = None
        self._lock = threading.Lock()
        self._entries: "OrderedDict[Hashable, object]" = OrderedDict()
        # Content-digest index: digest -> the keys whose entries hold
        # that content (aliases share ONE device buffer).
        self._digests_of: Dict[Hashable, str] = {}
        self._keys_by_digest: Dict[str, Set[Hashable]] = {}
        # Request-routing identity of each region entry (the fleet's
        # ``plane_route_key``), recorded at staging: what lets a
        # rolling drain hand each plane of this shard to the member
        # that will actually SERVE its future requests.
        self._route_of: Dict[Hashable, str] = {}
        self._bytes = 0
        # Lookups of entries (a region entry is one channel plane).
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        # Channel planes that were read from the pixel store and
        # uploaded (a region key's loader ran): /metrics
        # imageregion_rawcache_channel_loads_total.
        self.channel_loads = 0
        # Uploads skipped (served) / paid because of the content digest.
        self.plane_hits = 0
        self.plane_misses = 0

    # ------------------------------------------------------------ digest

    def get_by_digest(self, digest: str, bump: bool = True):
        """Device buffer holding this content under any key; None when
        the content is not resident.  ``bump=False`` skips the LRU
        touch (the internal alias lookup: the NEW key gets its own LRU
        position, and the alias source's age must stay its own)."""
        with self._lock:
            for key in self._keys_by_digest.get(digest, ()):
                arr = self._entries.get(key)
                if arr is not None:
                    if bump:
                        self._entries.move_to_end(key)
                    return arr
        return None

    def count_plane(self, hit: bool) -> None:
        """Lock-protected plane-counter bump — every mutation of the
        hit/miss counters goes through the lock (worker threads race
        these), including the external staging helper
        (``io.staging.stage_deduped``)."""
        with self._lock:
            if hit:
                self.plane_hits += 1
            else:
                self.plane_misses += 1

    def resident_digest(self, digest: str, count: bool = True) -> bool:
        """Digest-probe residency (the sidecar wire's ``plane_probe``
        answer).  ``count`` feeds the plane-cache HIT counter only — a
        probe hit is an upload that never happens.  A probe miss is NOT
        counted here: the upload that follows lands in
        :meth:`get_or_load`, which records the one miss, so one actual
        upload is exactly one ``plane_misses`` increment."""
        with self._lock:
            resident = bool(self._keys_by_digest.get(digest))
            if count and resident:
                self.plane_hits += 1
            return resident

    def _index_digest(self, key: Hashable, digest: Optional[str]) -> None:
        """Record key->digest under the lock (caller holds it)."""
        if digest is None:
            return
        self._digests_of[key] = digest
        self._keys_by_digest.setdefault(digest, set()).add(key)

    def _drop_digest(self, key: Hashable) -> None:
        digest = self._digests_of.pop(key, None)
        if digest is None:
            return
        keys = self._keys_by_digest.get(digest)
        if keys is not None:
            keys.discard(key)
            if not keys:
                del self._keys_by_digest[digest]

    def _release_bytes(self, key: Hashable, arr) -> None:
        """Remove a key's accounting (lock held).  Digest aliases share
        ONE device buffer, so its bytes leave the budget only when the
        LAST key referencing that content goes."""
        self._route_of.pop(key, None)
        digest = self._digests_of.get(key)
        self._drop_digest(key)
        if digest is None or not self._keys_by_digest.get(digest):
            self._bytes -= arr.nbytes

    # ------------------------------------------------------------- loads

    def get_or_load(self, key: Hashable, loader: Callable,
                    digest: Optional[str] = None,
                    route_key: Optional[str] = None,
                    by: str = "request"):
        with self._lock:
            arr = self._entries.get(key)
            if arr is not None:
                self._entries.move_to_end(key)
                self.hits += 1
                return arr
            self.misses += 1
        import jax
        import numpy as np

        from .staging import pin_scope
        loaded = loader()
        arr = None
        if isinstance(loaded, np.ndarray):
            if self.digest_index:
                # Content-addressed staging skip: bytes already resident
                # under another key (a wire-pushed plane, or the same
                # content at a different region identity) alias the
                # resident buffer — zero host->device bytes.
                digest = digest or plane_digest(loaded)
                arr = self.get_by_digest(digest, bump=False)
                self.count_plane(hit=arr is not None)
                if arr is not None:
                    # Cost ledger: the upload this request did NOT pay
                    # (dedup-skipped HBM bytes).  No-op outside a
                    # request trace context (prefetch, prewarm).
                    from ..utils import telemetry
                    telemetry.add_cost("staged_bytes_skipped",
                                       loaded.nbytes)
            if arr is None:
                # Host ndarray miss: the plane goes up as it is, one
                # asynchronous transfer in its storage dtype, to this
                # shard's device (uncommitted, as prewarm's planes:
                # a committed array would key other programs).
                with pin_scope(self.device):
                    arr = jax.device_put(loaded)
                from ..utils import telemetry
                telemetry.add_cost("staged_bytes", loaded.nbytes)
        else:
            # Already device-resident (banded staging path); content
            # digests are host-side only, so these entries carry none.
            arr = jax.device_put(loaded)
            digest = None
        with self._lock:
            if _is_region_key(key):
                self.channel_loads += 1
            old = self._entries.pop(key, None)
            if old is not None:
                # Another thread loaded this key during this read.
                self._release_bytes(key, old)
            digest = digest if self.digest_index else None
            if digest is not None:
                # Re-probe under the lock: a racing miss for the SAME
                # content may have landed since the pre-stage check.
                # Adopt its buffer (dropping the one this thread just
                # staged) so digest aliases always share one HBM
                # allocation and the byte charge stays buffer-accurate
                # — without this, two live buffers would carry one
                # budget charge and max_bytes would no longer bound
                # real device memory.
                for k in self._keys_by_digest.get(digest, ()):
                    existing = self._entries.get(k)
                    if existing is not None:
                        arr = existing
                        break
            self._entries[key] = arr
            if route_key is not None:
                self._route_of[key] = route_key
            # Aliases share one device buffer: its bytes enter the
            # budget once, with the digest's FIRST key — so effective
            # capacity GROWS with dedup instead of shrinking under
            # double counting.
            if digest is None or not self._keys_by_digest.get(digest):
                self._bytes += arr.nbytes
            self._index_digest(key, digest)
            evicted_labels = []
            while self._bytes > self.max_bytes and len(self._entries) > 1:
                evicted_key, evicted = self._entries.popitem(last=False)
                self._release_bytes(evicted_key, evicted)
                self.evictions += 1
                evicted_labels.append((str(evicted_key)[:80],
                                       evicted.nbytes))
        from ..utils import telemetry
        if old is not None:
            telemetry.DUPLICATE_LOADS.count(by)
        if evicted_labels:
            # Black box (outside the lock): an eviction storm right
            # before a stall is the "hot set no longer fits" signature.
            for label, nbytes in evicted_labels:
                telemetry.FLIGHT.record("rawcache.evict", key=label,
                                        bytes=nbytes)
        return arr

    def get(self, key: Hashable):
        """Pure hit probe WITH the LRU bump; None on miss (the serving
        fast path — callers fall back to ``get_or_load`` off-loop)."""
        return self.get_planes((key,))[0]

    def get_planes(self, keys) -> list:
        """:meth:`get` for each of ``keys`` under one hold of the lock:
        the resident entries, None where one is missing (the probe of a
        request's channel planes; only the hits are counted here, a
        missing plane's miss by the ``get_or_load`` that follows)."""
        with self._lock:
            out = []
            for key in keys:
                arr = self._entries.get(key)
                if arr is not None:
                    self._entries.move_to_end(key)
                    self.hits += 1
                out.append(arr)
            return out

    def __contains__(self, key: Hashable) -> bool:
        """Residency probe without an LRU bump (prefetch skip check)."""
        with self._lock:
            return key in self._entries

    def absent(self, keys) -> list:
        """Those of ``keys`` that are not resident, under one hold of
        the lock and without an LRU bump (the prefetcher's check of a
        predicted tile's channel planes)."""
        with self._lock:
            return [key for key in keys if key not in self._entries]

    def resident_digests(self) -> Set[str]:
        """Snapshot of every content digest currently resident (fleet
        shard accounting: across members these sets should be pairwise
        disjoint — a digest on two members means a plane was staged
        twice, the duplication the consistent-hash router exists to
        prevent)."""
        with self._lock:
            return set(self._keys_by_digest)

    def resident_route(self, route_key: str) -> bool:
        """Residency by ROUTING identity (``plane_route_key``), no LRU
        bump: the explain plane's "is this plane warm on its owner"
        probe.  O(resident entries) over the recorded routes —
        operator-surface economics, never on the serving path."""
        with self._lock:
            return route_key in self._route_of.values()

    def evict_to_fraction(self, frac: float) -> int:
        """Brownout eviction (server.pressure "evict_caches"): walk
        LRU-first until resident bytes are at most ``frac`` of the
        budget, returning entries dropped.  The early, chosen form of
        the eviction that would otherwise happen per-miss at the worst
        moment — when the cache is already over budget mid-burst."""
        target = max(0, int(self.max_bytes * frac))
        evicted = []
        with self._lock:
            while self._bytes > target and len(self._entries) > 1:
                key, arr = self._entries.popitem(last=False)
                self._release_bytes(key, arr)
                self.evictions += 1
                evicted.append((str(key)[:80], arr.nbytes))
        if evicted:
            from ..utils import telemetry
            telemetry.FLIGHT.record("rawcache.pressure-evict",
                                    entries=len(evicted),
                                    bytes=sum(b for _, b in evicted))
        return len(evicted)

    @property
    def size_bytes(self) -> int:
        return self._bytes

    def __len__(self) -> int:
        return len(self._entries)

    def snapshot_entries(self, limit: int = 0):
        """Warm-state manifest export: the resident REGION entries
        (source coords + content digest), most-recently-used first.
        Only region keys are restageable from source at boot; content-
        only ``("plane", digest)`` entries and projection planes are
        skipped — their bytes exist nowhere but HBM.  ``limit`` 0 =
        all."""
        out = []
        with self._lock:
            keys = list(reversed(self._entries.keys()))   # MRU first
            for key in keys:
                if not _is_region_key(key):
                    continue
                entry = {
                    "key": _manifest_key(key),
                    "digest": self._digests_of.get(key),
                }
                route = self._route_of.get(key)
                if route is not None:
                    # Routing identity for drain handoffs: which ring
                    # member will serve this plane's future requests.
                    entry["route"] = route
                out.append(entry)
                if limit and len(out) >= limit:
                    break
        return out

    def entries_for_route(self, route_key: str):
        """The restageable entries of ONE routing identity — the
        hot-key replica staging manifest (``FleetRouter
        ._stage_replicas`` ships exactly the promoted plane, not the
        whole shard).  Same entry shape as :meth:`snapshot_entries`,
        MRU first, no LRU bump."""
        out = []
        with self._lock:
            for key in reversed(self._entries.keys()):   # MRU first
                if self._route_of.get(key) != route_key:
                    continue
                if not _is_region_key(key):
                    continue
                out.append({
                    "key": _manifest_key(key),
                    "digest": self._digests_of.get(key),
                    "route": route_key,
                })
        return out


def region_key(image_id: int, z: int, t: int, level: int,
               region: Tuple[int, int, int, int], channel: int) -> tuple:
    """The raw-read identity of ONE channel plane: everything its pixel
    data depends on and nothing the rendering settings touch, the set
    of channels shown beside it included."""
    return (image_id, z, t, level, region, channel)


def entry_region_key(entry: dict) -> tuple:
    """The :func:`region_key` a manifest entry names (``"key":
    [image, z, t, level, [x, y, w, h], channel]``, as
    :meth:`DeviceRawCache.snapshot_entries` writes it): what the
    warm-state rehydrator, the sidecar's ``shard_transfer`` and the
    fleet's hand-off rebuild a key from.  Raises ``KeyError`` /
    ``TypeError`` / ``ValueError`` on anything else, an entry of the
    older format (a LIST of channels in the last place) among them:
    its callers skip such an entry, which is then a cold miss later."""
    image_id, z, t, level, region, channel = entry["key"]
    x, y, w, h = (int(v) for v in region)
    return region_key(int(image_id), int(z), int(t), int(level),
                      (x, y, w, h), int(channel))


def _is_region_key(key) -> bool:
    """A :func:`region_key` (restageable from source), as against a
    content-only ``("plane", digest)`` or a ``("proj", ...)`` entry."""
    return (isinstance(key, tuple) and len(key) == 6
            and isinstance(key[0], int))


def _manifest_key(key: tuple) -> list:
    """A region key as a manifest entry writes it (JSON)."""
    image_id, z, t, level, region, channel = key
    return [image_id, z, t, level, list(region), channel]


def plane_key(digest: str) -> tuple:
    """Cache key of a content-addressed (wire-pushed) plane entry."""
    return ("plane", digest)
