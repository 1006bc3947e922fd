"""Data-parallel device fleet: sharded serving across N device sets.

The reference scales horizontally by clustering verticle JVMs over
Hazelcast (``-cluster``): every node consumes the same event-bus
address and the cluster's consistent view decides who serves what.
The TPU-native form here is a :class:`FleetRouter` in the frontend: N
members — in-process device lanes (``--role combined``) or render
sidecars each owning a device set (``--role frontend`` +
``fleet.sockets``) — each own a *shard* of the hot HBM state.

Routing is a consistent hash of the request's **plane identity**
(:func:`plane_route_key`: image, z, t, resolution, tile/region — the
source bytes' address, never the rendering settings), so every render
of one plane lands on the one member whose ``DeviceRawCache`` holds
it: the fleet's HBM tier *shards* instead of duplicating, and
staged-once semantics ride the existing digest probes unchanged.
Re-window/re-color traffic for a hot plane always finds its bytes
already resident on its owner.

Load skew is handled by **bounded work stealing**: each member drains
its own queue through ``lane_width`` worker lanes, and an idle lane
may steal the oldest queued request from the most-backlogged member —
the stolen render runs from source bytes *without adopting cache
ownership* (``adopt_cache=False`` rides the wire as the ``adopt``
header), so stealing never fragments the shard map.

Membership is decided by the PR-3 breaker/supervisor machinery: a
member whose connection died through every policy retry (or whose
breaker is open) is marked down, its shard fails over **hash-ring-
next** (the classic consistent-hash contract: only ~1/N of the key
space moves), and its queued work is re-assigned.  The supervisor
brings the process back; the ring re-adopts it after the cooldown.

Fleet-aware single-flight and admission live *above* the router
(:class:`FleetImageHandler`): identical renders coalesce once
fleet-wide, and shedding sees the fleet's total depth.  The lockstep
``MeshRenderer`` stays behind the router for full-plane/z-projection
jobs — those pin to the first member (the mesh lane) and are never
stolen.
"""

from __future__ import annotations

import asyncio
import collections
import hashlib
import bisect
import logging
import math
import time
from typing import Deque, Dict, List, Optional, Sequence, Tuple

logger = logging.getLogger(__name__)


# ------------------------------------------------------------ hash ring

class HashRing:
    """Consistent hash ring with virtual nodes.

    Deterministic across processes and runs (BLAKE2b over the literal
    strings — never Python's salted ``hash()``), so a frontend fleet
    restart can never silently reshuffle which member owns which
    plane.  ``replicas`` virtual nodes per member keep the key-space
    split near-uniform; member join/leave moves only the keys whose
    ring arcs changed hands (~1/N of the space — pinned by the remap
    bound test in tier-1).
    """

    def __init__(self, members: Sequence[str], replicas: int = 64,
                 seed: str = ""):
        if not members:
            raise ValueError("hash ring needs at least one member")
        if len(set(members)) != len(members):
            raise ValueError("duplicate fleet member names")
        self.replicas = max(1, int(replicas))
        # Federation namespace (``federation.ring-seed``): folded into
        # every point hash so two federations sharing member NAMES can
        # never silently share a key space.  The empty default keeps
        # every pre-federation ring's golden assignments bit-exact.
        self.seed = str(seed)
        self.members: Tuple[str, ...] = tuple(members)
        self._points: List[int] = []
        self._owners: List[str] = []
        prefix = f"{self.seed}|" if self.seed else ""
        points = []
        for name in self.members:
            for v in range(self.replicas):
                points.append((self._point(f"{prefix}{name}#{v}"),
                               name))
        points.sort()
        self._points = [p for p, _ in points]
        self._owners = [o for _, o in points]

    @staticmethod
    def _point(s: str) -> int:
        return int.from_bytes(
            hashlib.blake2b(s.encode(), digest_size=8).digest(),
            "big")

    def _key_point(self, key: str) -> int:
        return self._point(f"{self.seed}|{key}" if self.seed else key)

    def chain(self, key: str) -> List[str]:
        """Members in ring order from ``key``'s arc, deduplicated: the
        first entry owns the key; the rest are its failover order
        (hash-ring-next), so one member's death moves each of its keys
        to a *deterministic* successor."""
        if not self._points:
            return []
        i = bisect.bisect(self._points, self._key_point(key)) \
            % len(self._points)
        seen = []
        for step in range(len(self._points)):
            owner = self._owners[(i + step) % len(self._points)]
            if owner not in seen:
                seen.append(owner)
                if len(seen) == len(self.members):
                    break
        return seen

    def member(self, key: str) -> str:
        """The key's owning member."""
        return self.chain(key)[0]


def plane_route_key(ctx) -> str:
    """The request's source-plane identity — everything that pins WHICH
    bytes are read, nothing the rendering settings touch.  All renders
    of one plane (re-window, re-color, LUT flips, format changes) hash
    to the same member, which is exactly what makes the fleet's HBM
    tier shard instead of duplicate."""
    tile = (ctx.tile.x, ctx.tile.y, ctx.tile.width, ctx.tile.height) \
        if ctx.tile is not None else None
    region = (ctx.region.x, ctx.region.y, ctx.region.width,
              ctx.region.height) if ctx.region is not None else None
    parts = (ctx.image_id, ctx.z, ctx.t, ctx.resolution, tile, region)
    return hashlib.blake2b(repr(parts).encode(),
                           digest_size=16).hexdigest()


def _entry_key(entry: dict) -> tuple:
    """Canonical identity of a restageable manifest entry (the region
    key as a hashable tuple) — matches exported bytes back to their
    hint entries across JSON round-trips (lists vs tuples)."""
    from ..io.devicecache import entry_region_key
    try:
        return entry_region_key(entry)
    except (KeyError, TypeError, ValueError):
        return (id(entry),)


# ------------------------------------------------------------- hot keys

class HeatTracker:
    """Decayed per-route request-rate tracker (the hot-key detector).

    Each :func:`plane_route_key` observation adds one unit of heat;
    heat decays exponentially with time constant ``decay_s`` (lazy —
    applied on read, no timer).  Under a sustained rate of ``r``
    requests/s a route's heat converges to ``r * decay_s``, so
    ``threshold`` reads as "this many seconds' worth of one member's
    demand concentrated on one plane".

    Cardinality is bounded at ``top_k`` routes: a new route may enter
    a full table only by evicting a COLDER one (its decayed heat below
    the newcomer's single unit), so the hot set can never be churned
    out by a long tail of one-hit routes — the same guarantee
    space-saving top-K sketches give, in the degenerate form that
    suffices when ``top_k`` is orders of magnitude above the number of
    simultaneously-hot planes.

    ``clock`` is injectable for deterministic trajectory tests.
    """

    def __init__(self, threshold: float, decay_s: float,
                 top_k: int = 128, clock=time.monotonic):
        self.threshold = float(threshold)
        self.decay_s = max(1e-3, float(decay_s))
        self.top_k = max(1, int(top_k))
        self.clock = clock
        self._heat: Dict[str, Tuple[float, float]] = {}

    def _decayed(self, heat: float, last: float, now: float) -> float:
        if now <= last:
            return heat
        return heat * math.exp(-(now - last) / self.decay_s)

    def observe(self, route: str) -> float:
        """Count one request for ``route``; returns its decayed heat
        including this observation."""
        now = self.clock()
        held = self._heat.get(route)
        if held is None:
            if len(self._heat) >= self.top_k:
                coldest = min(
                    self._heat,
                    key=lambda r: self._decayed(*self._heat[r], now))
                if self._decayed(*self._heat[coldest], now) > 1.0:
                    # Table full of hotter routes: the observation is
                    # real but untracked — bounded cardinality wins.
                    return 1.0
                del self._heat[coldest]
            heat = 1.0
        else:
            heat = self._decayed(held[0], held[1], now) + 1.0
        self._heat[route] = (heat, now)
        return heat

    def heat(self, route: str) -> float:
        """Decayed heat without counting a request (sweeps, explain)."""
        held = self._heat.get(route)
        if held is None:
            return 0.0
        return self._decayed(held[0], held[1], self.clock())

    def tracked(self) -> int:
        return len(self._heat)

    def forget(self, route: str) -> None:
        self._heat.pop(route, None)


# -------------------------------------------------------------- members

class MemberDownError(ConnectionError):
    """A member's fast-fail refusal while it is ALREADY marked down.

    The lane must not treat this as a fresh death observation:
    re-marking on every routed request would push ``_down_until``
    forward each time, so any shard seeing >= 1 request per cooldown
    window would keep its member down forever — after the outage
    healed.  Only a failure of a render the member actually accepted
    (re-)marks it down."""


class LocalMember:
    """An in-process device lane: its own renderer + HBM cache behind
    an ``ImageRegionHandler`` (host-side services — pixel stores, byte
    caches, metadata, ACL memo — are shared with the other members).

    Down state is a COOLDOWN, exactly like :class:`RemoteMember`'s: the
    shared host-side services mean one transient outage (a metadata DB
    or network pixel-store hiccup surfacing as ``ConnectionError``) can
    mark every member down within a single failover chain, and a latch
    with no re-admission path would leave the whole fleet dead until a
    process restart.  A served render — or the cooldown expiring —
    re-admits the member.

    ``byte_cache_prechecked`` marks that the fleet handler above the
    router already ran the byte-cache probe and the caller's ACL gate
    for every dispatched ctx (``build_local_members`` sets it — the
    combined role always fronts members with ``FleetImageHandler``),
    so the member's own handler skips its duplicate byte-cache get.

    ``services`` is kept for shard accounting (``raw_cache``) and
    teardown; ``handler`` is duck-typed so tests can wrap it with
    deterministic failure injectors."""

    remote = False

    def __init__(self, name: str, handler, services=None,
                 down_cooldown_s: float = 5.0,
                 byte_cache_prechecked: bool = False,
                 devices: Optional[Sequence] = None):
        self.name = name
        self.handler = handler
        self.services = services
        self.down_cooldown_s = down_cooldown_s
        self.byte_cache_prechecked = byte_cache_prechecked
        # Per-member device set (cross-host federation: the combined
        # role owns REAL devices per member when the host has several
        # — ``federation.partition_local_devices``).  The first device
        # is the member's dispatch pin (``services.pin_device``); an
        # empty set means the process default device, the pre-pinning
        # behavior.
        self.devices: Tuple = tuple(devices or ())
        self._down_until = 0.0
        # Rolling-drain state (router.drain_member): a DRAINING member
        # finishes its in-flight work but accepts no new routes — on
        # purpose, distinct from down (a drain is not a death and must
        # not look like one).  ``drain_intent`` says WHO drained it:
        # "operator" (/admin/drain — the rolling-restart posture the
        # drain.fail-readyz flag surfaces to LBs) or "autoscale" (a
        # routine scale-down that must NOT read as the instance
        # leaving rotation).
        self.draining = False
        self.drain_intent: Optional[str] = None

    @property
    def healthy(self) -> bool:
        return time.monotonic() >= self._down_until

    def mark_down(self) -> None:
        self._down_until = time.monotonic() + self.down_cooldown_s

    def revive(self) -> None:
        self._down_until = 0.0

    async def render(self, ctx, adopt_cache: bool = True) -> bytes:
        if not self.healthy:
            raise MemberDownError(
                f"fleet member {self.name} is down")
        if self.byte_cache_prechecked:
            data = await self.handler.render_image_region(
                ctx, adopt_cache=adopt_cache, skip_byte_cache=True)
        else:
            data = await self.handler.render_image_region(
                ctx, adopt_cache=adopt_cache)
        self.revive()          # a served call re-admits the member
        return data

    def queue_depth(self) -> int:
        renderer = getattr(self.services, "renderer", None)
        return (renderer.queue_depth()
                if hasattr(renderer, "queue_depth") else 0)

    def resident_digests(self):
        cache = getattr(self.services, "raw_cache", None)
        if cache is None or not hasattr(cache, "resident_digests"):
            return set()
        return cache.resident_digests()

    def resident_planes(self) -> int:
        cache = getattr(self.services, "raw_cache", None)
        return len(cache) if cache is not None else 0

    async def shard_manifest(self, limit: int = 0) -> List[dict]:
        """This member's HBM shard as restageable region entries —
        the drain handoff's pre-stage hint list (MRU first, so a
        bounded pre-stage warms the hottest planes)."""
        cache = getattr(self.services, "raw_cache", None)
        if cache is None or not hasattr(cache, "snapshot_entries"):
            return []
        return cache.snapshot_entries(limit)

    async def route_manifest(self, route: str) -> List[dict]:
        """ONE route's restageable entries (hot-plane replication:
        the promotion stager hands exactly the hot plane's shard slice
        to its replicas, not the member's whole manifest)."""
        cache = getattr(self.services, "raw_cache", None)
        if cache is None:
            return []
        if hasattr(cache, "entries_for_route"):
            return cache.entries_for_route(route)
        if not hasattr(cache, "snapshot_entries"):
            return []
        return [e for e in cache.snapshot_entries(0)
                if e.get("route") == route]

    # ---- fleet-global byte tier (combined role shares ONE byte-cache
    # chain across members, so these exist for API symmetry and tests;
    # the router only crosses the wire for REMOTE peers).  ``tier``
    # picks the byte namespace: "region" (rendered tiles, the PR 11
    # identity) or "mask" (ShapeMask PNGs under their cache_key).

    def _byte_stack(self, tier: str = "region"):
        caches = getattr(self.services, "caches", None)
        stack = getattr(caches,
                        "shape_mask" if tier == "mask"
                        else "image_region", None)
        return stack if (stack is not None
                         and getattr(stack, "enabled", False)) else None

    async def byte_probe(self, keys: List[str],
                         tier: str = "region") -> List[bool]:
        stack = self._byte_stack(tier)
        if stack is None:
            return [False] * len(keys)
        return [(await stack.get(str(k))) is not None for k in keys]

    async def byte_fetch(self, key: str, image_id=None,
                         session=None, tier: str = "region",
                         obj: str = "Image") -> Optional[bytes]:
        stack = self._byte_stack(tier)
        if stack is None:
            return None
        data = await stack.get(str(key))
        if data is None or image_id is None:
            return data
        from ..server.handler import check_can_read
        if not await check_can_read(self.services, obj,
                                    int(image_id), session):
            return None
        return data

    async def byte_put(self, key: str, value: bytes,
                       tier: str = "region") -> bool:
        stack = self._byte_stack(tier)
        if stack is None:
            return False
        await stack.set(str(key), bytes(value))
        return True

    async def explain_residency(self, key: str, route: str) -> dict:
        """Dry-run residency report for the explain plane: does this
        member hold the rendered bytes (and in which tier) and/or the
        source plane in HBM?  Read-only — no render, no staging.  ONE
        shared implementation (``server.explain.residency_doc``) so
        combined, fleet-local and remote members cannot drift."""
        from ..server.explain import residency_doc
        return await residency_doc(
            self._byte_stack(),
            getattr(self.services, "raw_cache", None), key, route)

    async def prestage_manifest(self, entries: List[dict]) -> int:
        """Stage a handed-over shard manifest into THIS member's HBM
        (drain handoff, successor side) through the existing staging
        path — digest-deduped, so re-handing an already-warm entry is
        a probe hit, never a duplicate buffer."""
        from ..services.warmstate import restage_plane_entry
        cache = getattr(self.services, "raw_cache", None)
        pixels = getattr(self.services, "pixels_service", None)
        if cache is None or pixels is None:
            return 0

        def stage_all() -> int:
            staged = 0
            for entry in entries:
                try:
                    if restage_plane_entry(cache, pixels, entry):
                        staged += 1
                except Exception:
                    continue    # best-effort: a bad entry is a cold
                    # miss later, never a failed drain
            return staged

        return await asyncio.to_thread(stage_all)

    async def shard_export(self, limit: int = 0) -> List[dict]:
        """This member's HBM shard as entries WITH the plane bytes —
        the cross-host drain handoff's payload (``shard_transfer``):
        a successor on ANOTHER host cannot re-read this host's pixel
        store, so the warm bytes themselves ride the wire.  MRU-first
        like :meth:`shard_manifest`; entries whose buffer is already
        gone (eviction race) are skipped."""
        import numpy as np
        from ..io.devicecache import entry_region_key
        cache = getattr(self.services, "raw_cache", None)
        if cache is None or not hasattr(cache, "snapshot_entries"):
            return []
        entries = cache.snapshot_entries(limit)

        def export() -> List[dict]:
            out = []
            for entry in entries:
                try:
                    key = entry_region_key(entry)
                except (KeyError, TypeError, ValueError):
                    continue
                arr = cache.get(key)
                if arr is None:
                    continue
                host = np.asarray(arr)
                out.append({**entry, "dtype": str(host.dtype),
                            "shape": list(host.shape),
                            "bytes": host.tobytes()})
            return out

        return await asyncio.to_thread(export)

    async def shard_transfer(self, entries: List[dict]) -> int:
        """Stage handed-over plane BYTES into this member's HBM
        (cross-host handoff, successor side — the in-process mirror of
        the ``shard_transfer`` wire op, so the router's handoff code
        is member-kind-agnostic).  Digest-deduped like every staging
        path: re-handing a resident plane aliases, never duplicates."""
        import numpy as np
        from ..io.devicecache import entry_region_key
        cache = getattr(self.services, "raw_cache", None)
        if cache is None:
            return 0

        def stage_all() -> int:
            staged = 0
            for entry in entries:
                try:
                    key = entry_region_key(entry)
                    arr = np.frombuffer(
                        entry["bytes"], dtype=entry["dtype"]).reshape(
                        tuple(entry["shape"]))
                except (KeyError, TypeError, ValueError):
                    continue
                cache.get_or_load(key, lambda a=arr: a,
                                  digest=entry.get("digest"),
                                  route_key=entry.get("route"))
                staged += 1
            return staged

        return await asyncio.to_thread(stage_all)


class RemoteMember:
    """A render sidecar owning a device set, reached over the wire.

    Health is the PR-3 machinery's verdict: the client's circuit
    breaker open, or a connection death observed by a lane worker,
    marks the member down for ``down_cooldown_s`` — its shard fails
    over hash-ring-next while the supervisor restarts the process, and
    the ring re-adopts it at the next successful call after cooldown.
    """

    remote = True

    def __init__(self, name: str, client, down_cooldown_s: float = 5.0):
        self.name = name
        self.client = client
        # Stitching dimension: spans the client grafts from this
        # member's process carry its fleet name, so a stolen or
        # failed-over render reads as a multi-member tree.
        try:
            client.member_label = name
        except AttributeError:      # duck-typed test clients
            pass
        self.down_cooldown_s = down_cooldown_s
        self._down_until = 0.0
        self.draining = False
        self.drain_intent: Optional[str] = None

    @property
    def healthy(self) -> bool:
        breaker = getattr(self.client, "breaker", None)
        if breaker is not None and breaker.state == breaker.OPEN:
            return False
        return time.monotonic() >= self._down_until

    def mark_down(self) -> None:
        self._down_until = time.monotonic() + self.down_cooldown_s

    def revive(self) -> None:
        self._down_until = 0.0

    def _fed_span(self, kind: str, t0: float, t1: float,
                  **meta) -> None:
        """One ``fed.hop`` span per cross-HOST wire exchange: {host,
        member, kind} names where the hop landed and why.  Gated on
        ``federation.remote_host_of`` — same-host members (and
        un-federated fleets) record nothing — and ``record_span`` is
        a no-op outside a trace context, so production gossip/drain
        loops pay nothing for it."""
        from . import federation
        from ..utils import telemetry
        host = federation.remote_host_of(self.name)
        if not host:
            return
        telemetry.record_span("fed.hop", t0, (t1 - t0) * 1000.0,
                              host=host, member=self.name,
                              kind=kind, **meta)

    async def render(self, ctx, adopt_cache: bool = True) -> bytes:
        from ..server.sidecar import _map_response
        from ..utils import provenance
        extra = None if adopt_cache else {"adopt": 0}
        resp_header, payload = await self.client.call_full(
            "image", ctx.to_json(), extra=extra)
        self.revive()          # a served call re-admits the member
        provenance.merge_wire(ctx, resp_header.get("prov"))
        if resp_header.get("quality_capped"):
            # The sidecar's brownout ladder capped this render's JPEG
            # quality: mirror the mark onto the FRONTEND's ctx so the
            # byte-tier write-backs here (peer put-back, combined byte
            # cache) keep the PR 9 contract — degraded bytes are never
            # stored under the full-quality key.
            ctx._pressure_quality_capped = True
        return _map_response(resp_header, payload)

    # ---- fleet-global byte tier (the peer transport: the router's
    # probe short-circuit and the thief write-back ride these three
    # idempotent-where-safe wire ops; every failure degrades to None/
    # False — the peer tier may only ever REMOVE work).

    async def byte_probe(self, keys: List[str],
                         tier: str = "region") -> List[bool]:
        import json as _json
        try:
            extra = {"keys": [str(k) for k in keys]}
            if tier != "region":
                extra["tier"] = tier
            status, body = await self.client.call(
                "byte_probe", {}, extra=extra)
            if status != 200 or not body:
                return [False] * len(keys)
            doc = _json.loads(bytes(body).decode())
            present = [bool(p) for p in (doc.get("present") or ())]
            present += [False] * (len(keys) - len(present))
            return present[:len(keys)]
        except Exception:
            return [False] * len(keys)

    async def byte_fetch(self, key: str, image_id=None,
                         session=None, tier: str = "region",
                         obj: str = "Image") -> Optional[bytes]:
        """None = authority MISS (or ACL refusal) — an honest 404;
        transport failures RAISE so the caller can count a fallback
        (a miss means render, a failure means the peer tier is
        degraded — the router's telemetry keeps them distinct)."""
        extra = {"key": str(key)}
        if tier != "region":
            # Tier rides the wire only when non-default: a legacy
            # sidecar ignoring it would serve the WRONG namespace, but
            # mask keys ("<shape>:<color>...") never collide with
            # render identity keys, so the worst case is a miss.
            extra["tier"] = tier
        if image_id is not None:
            # The serving sidecar runs its OWN ACL gate for this
            # session before any byte leaves it — the same
            # contract as the `image` op.
            extra["image_id"] = int(image_id)
            extra["session"] = session
            if obj != "Image":
                extra["obj"] = obj
        t0 = time.perf_counter()
        resp_header, payload = await self.client.call_full(
            "byte_fetch", {}, extra=extra)
        self._fed_span("byte_fetch", t0, time.perf_counter(),
                       hit=int(resp_header.get("status") == 200
                               and payload is not None))
        if resp_header.get("status") != 200 or payload is None:
            return None
        return bytes(payload)

    async def byte_put(self, key: str, value: bytes,
                       tier: str = "region") -> bool:
        import hashlib as _hashlib
        try:
            digest = _hashlib.blake2b(bytes(value),
                                      digest_size=16).hexdigest()
            extra = {"key": str(key), "digest": digest}
            if tier != "region":
                extra["tier"] = tier
            t0 = time.perf_counter()
            status, _body = await self.client.call(
                "byte_put", {}, body=bytes(value),
                extra=extra)
            self._fed_span("byte_put", t0, time.perf_counter(),
                           bytes=len(value))
            return status == 200
        except Exception:
            return False

    def queue_depth(self) -> int:
        return 0               # the sidecar's own gauge carries this

    def resident_digests(self):
        return set()

    def resident_planes(self) -> int:
        return 0

    async def shard_manifest(self, limit: int = 0) -> List[dict]:
        """The sidecar's HBM shard over the wire (``shard_manifest``
        op); unreachable/legacy sidecars answer an empty hint list —
        the drain proceeds, the successor just warms lazily."""
        import json as _json
        try:
            status, body = await self.client.call(
                "shard_manifest", {}, extra={"limit": limit})
            if status != 200 or not body:
                return []
            return list(_json.loads(bytes(body).decode())
                        .get("entries") or ())
        except Exception:
            return []

    async def explain_residency(self, key: str, route: str) -> dict:
        """Residency report over the read-only ``explain`` wire op;
        unreachable/legacy sidecars answer an honest unknown."""
        import json as _json
        try:
            status, body = await self.client.call(
                "explain", {}, extra={"key": key, "route": route})
            if status != 200 or not body:
                return {"error": f"explain op status {status}"}
            return dict(_json.loads(bytes(body).decode()))
        except Exception as e:
            return {"error": str(e)[:120]}

    async def prestage_manifest(self, entries: List[dict]) -> int:
        """Hand the drained shard's hint list to this sidecar
        (``prestage`` op): it re-reads the regions from its own pixel
        store and stages them into its HBM shard."""
        import json as _json
        try:
            t0 = time.perf_counter()
            status, body = await self.client.call(
                "prestage", {}, extra={"entries": entries})
            self._fed_span("remote_prestage", t0, time.perf_counter(),
                           entries=len(entries))
            if status != 200 or not body:
                return 0
            return int(_json.loads(bytes(body).decode())
                       .get("staged", 0))
        except Exception:
            return 0

    # ---- cross-host federation (parallel.federation): manifest
    # agreement at join, membership gossip, and warm shard transfer —
    # the three new v3-wire ops.  manifest_hello / member_gossip are
    # idempotent reads (retried); shard_transfer ships state and is
    # never blind-retried, exactly the plane_put contract.

    async def manifest_hello(self, doc: dict,
                             probe_keys: Optional[List[str]] = None
                             ) -> Optional[dict]:
        """Exchange fleet manifests with this member's process: send
        ours, learn whether the peer's agrees (digest match), and —
        when ``probe_keys`` ride along — the peer's ring owner for
        each, so golden assignments are verified AGAINST THE PEER'S
        OWN MATH, not our copy of it.  None = unreachable/legacy."""
        import json as _json

        from . import federation
        extra = {"manifest": doc,
                 # The sender's host identity: an inbound hello feeds
                 # the receiver's quorum tracker (heard-from proof).
                 "from_host": federation.self_host()}
        if probe_keys:
            extra["probe_keys"] = list(probe_keys)
        try:
            status, body = await self.client.call(
                "manifest_hello", {}, extra=extra)
            if status != 200 or not body:
                return None
            return dict(_json.loads(bytes(body).decode()))
        except Exception:
            return None

    async def member_gossip(self, view: dict) -> Optional[dict]:
        """Swap membership views (name -> health/draining, versioned
        ``(incarnation, seq)``) and the manifest (version, digest) —
        the rack-scale liveness channel that propagates drains and
        deaths between hosts faster than per-request failures would."""
        import json as _json

        from . import federation
        try:
            status, body = await self.client.call(
                "member_gossip", {},
                extra={"view": view,
                       "from_host": federation.self_host()})
            if status != 200 or not body:
                return None
            return dict(_json.loads(bytes(body).decode()))
        except Exception:
            return None

    async def epoch_propose(self, doc: dict) -> Optional[dict]:
        """Two-phase epoch roll, phase 1: offer the next manifest to
        this member's process (it records PENDING and acks — nothing
        activates).  Idempotent by contract, so the retry policy may
        re-issue it.  None = unreachable."""
        import json as _json

        from . import federation
        try:
            status, body = await self.client.call(
                "epoch_propose", {},
                extra={"manifest": doc,
                       "from_host": federation.self_host()})
            if status != 200 or not body:
                return None
            return dict(_json.loads(bytes(body).decode()))
        except Exception:
            return None

    async def epoch_commit(self, doc: dict,
                           digest: str = "") -> Optional[dict]:
        """Two-phase epoch roll, phase 2: commit the agreed manifest
        — the receiver digest-verifies, activates, and swaps its ring.
        Idempotent on the receiver (already-active answers ack), so
        safe to re-push (the gossip loop's anti-entropy catch-up does
        exactly that)."""
        import json as _json

        from . import federation
        extra = {"manifest": doc,
                 "from_host": federation.self_host()}
        if digest:
            extra["digest"] = digest
        try:
            status, body = await self.client.call(
                "epoch_commit", {}, extra=extra)
            if status != 200 or not body:
                return None
            return dict(_json.loads(bytes(body).decode()))
        except Exception:
            return None

    async def shard_transfer(self, entries: List[dict]) -> int:
        """Ship warm plane BYTES into this member's HBM over the wire
        (cross-host drain handoff): one frame per plane — the body is
        the raw buffer (shm-ring eligible), the header carries the
        restage identity (key/digest/route/dtype/shape).  Best-effort
        per entry; a failed ship is a cold miss later, never a failed
        drain."""
        import json as _json
        from . import federation
        staged = 0
        for entry in entries:
            payload = entry.get("bytes")
            if payload is None:
                continue
            meta = {k: entry.get(k) for k in
                    ("key", "digest", "route", "dtype", "shape")}
            try:
                t_send = time.perf_counter()
                status, body = await self.client.call(
                    "shard_transfer", {}, body=bytes(payload),
                    extra={"entry": meta})
                t_recv = time.perf_counter()
                doc = (_json.loads(bytes(body).decode())
                       if status == 200 and body else {})
                self._fed_span("shard_transfer", t_send, t_recv,
                               bytes=len(payload),
                               staged=int(bool(doc.get("staged"))))
                if doc.get("staged"):
                    staged += 1
                    # Counted HERE, per ship that actually landed —
                    # the bytes of failed entries never reach the
                    # transfer gauge.
                    from ..utils import telemetry
                    telemetry.FEDERATION.count_transfer(len(payload))
                    # Remote-side graft: the serving sidecar anchors
                    # its stage work (t_anchor on ITS perf clock, ms)
                    # and the per-host offset from the hello/gossip
                    # exchanges maps it into OUR timeline, clamped
                    # into this call's [send, recv] bracket.  Peers
                    # answering without the anchor fields (older
                    # builds, no derived offset yet) degrade to the
                    # wrapper span alone — never an error.
                    host = federation.remote_host_of(self.name)
                    anchored = federation.anchor_remote_time(
                        doc.get("host") or host, doc.get("t_anchor"),
                        (t_send, t_recv)) if host else None
                    if anchored is not None:
                        dur = max(0.0, min(
                            float(doc.get("ms") or 0.0),
                            (t_recv - anchored) * 1000.0))
                        telemetry.record_span(
                            "fed.hop", anchored, dur,
                            host=doc.get("host") or host,
                            member=self.name, kind="stage")
            except Exception:
                continue
        return staged


# --------------------------------------------------------------- router

class _Work:
    __slots__ = ("ctx", "future", "owner", "stolen", "hops",
                 "deadline", "t_enqueue", "bulk", "trace_ids",
                 "route_key")

    def __init__(self, ctx, future, owner: str, deadline):
        self.ctx = ctx
        self.future = future
        self.owner = owner
        self.stolen = False
        self.hops = 0
        self.deadline = deadline
        self.t_enqueue = time.perf_counter()
        # The requester's trace id(s), captured at enqueue: the lane
        # tasks run OUTSIDE any request context (they must — a lane is
        # long-lived), so every hop span and the member render itself
        # re-adopt these explicitly.  Without this, every lane span
        # would attach to whichever request's context happened to
        # spawn the lanes (the classic contextvars-snapshot leak).
        from ..utils import telemetry
        self.trace_ids = telemetry.current_trace_ids()
        # QoS class, computed ONCE at enqueue: the same
        # ``pressure.is_bulk`` verdict the ladder's shed_bulk step and
        # the mesh-lane pin use — the three must never drift apart.
        from ..server.pressure import is_bulk
        self.bulk = is_bulk(ctx)
        # Routed plane identity (short hash) for hop-span forensics;
        # pinned/bulk work carries the literal "pinned".  Only hashed
        # when a trace is listening (pay-for-what-you-use: untraced
        # internal dispatches skip the digest).
        self.route_key = ("pinned" if self.bulk
                          else plane_route_key(ctx)[:12]
                          if self.trace_ids else "")


class _MemberQueue:
    """One member's pending work as a weighted two-class queue.

    ``qos_weight`` 0 is plain FIFO (the pre-QoS behavior, bit for
    bit).  With weight w > 0, while BOTH classes wait, up to w
    interactive units pop per bulk unit — interactive tiles jump a
    bulk-export backlog instead of convoying behind it, and bulk still
    cannot starve (after the quota one bulk unit always pops).
    Arrival order is preserved WITHIN each class.
    """

    __slots__ = ("_items", "qos_weight", "_ic_run", "_ic")

    def __init__(self, qos_weight: int = 0):
        self._items: Deque[_Work] = collections.deque()
        self.qos_weight = max(0, int(qos_weight))
        self._ic_run = 0
        # Interactive-unit count, maintained O(1) on every mutation:
        # idle lanes poll steal_depth() on every wake evaluation, and
        # a deep bulk backlog must not turn that into a deque walk.
        self._ic = 0

    def append(self, work: _Work) -> None:
        self._items.append(work)
        if not work.bulk:
            self._ic += 1

    def __len__(self) -> int:
        return len(self._items)

    def __bool__(self) -> bool:
        return bool(self._items)

    def __contains__(self, work) -> bool:
        # O(n) deque scan: tests/diagnostics only — never call this
        # per-dispatch (the _ic counter exists precisely so the hot
        # path needs no queue walks).
        return work in self._items

    def _first_index(self, bulk: bool) -> Optional[int]:
        for i, w in enumerate(self._items):
            if w.bulk == bulk:
                return i
        return None

    def _on_pop(self, work: _Work) -> _Work:
        if not work.bulk:
            self._ic -= 1
        return work

    def popleft(self) -> _Work:
        """The next unit under the weighted-dequeue policy."""
        from ..utils import telemetry
        items = self._items
        if self.qos_weight <= 0:
            return self._on_pop(items.popleft())
        if self._ic == 0 or self._ic == len(items):
            # One class present: plain FIFO, quota resets (the mix is
            # what the quota meters).  O(1) — the scans below only
            # run while the classes are actually interleaved.
            self._ic_run = 0
            work = self._on_pop(items.popleft())
        elif self._ic_run >= self.qos_weight:
            # Quota spent: one bulk unit pops — no starvation.
            i_bulk = self._first_index(True)
            work = self._on_pop(items[i_bulk])
            del items[i_bulk]
            self._ic_run = 0
        else:
            i_ic = self._first_index(False)
            work = self._on_pop(items[i_ic])
            del items[i_ic]
            self._ic_run += 1
            if i_ic > 0:
                # Mixed queue and the first interactive unit was not
                # at the head: it overtook a bulk unit that arrived
                # first — the jump the QoS tier exists for.
                telemetry.QOS.count_jump()
        telemetry.QOS.count_dequeued("bulk" if work.bulk
                                     else "interactive")
        return work

    def pop_raw(self) -> _Work:
        """Arrival-order pop, policy-free (reassign/fail/close paths)."""
        return self._on_pop(self._items.popleft())

    def steal_depth(self) -> int:
        """Stealable units: interactive only — bulk work is pinned to
        the mesh lane by the same is_bulk verdict, never stolen."""
        return self._ic

    def steal_pop(self) -> Optional[_Work]:
        """The OLDEST stealable (interactive) unit, or None."""
        if self._ic == 0:
            return None
        i = self._first_index(False)
        work = self._on_pop(self._items[i])
        del self._items[i]
        return work


class FleetRouter:
    """Consistent-hash request router over N fleet members.

    Per-member queues drained by ``lane_width`` asyncio lanes each (a
    lane models one device lane of that member's set); an idle lane
    steals the oldest request from the most-backlogged peer once that
    backlog reaches ``steal_min_backlog`` — bounded, oldest-first, and
    cache-ownership-neutral (stolen renders carry
    ``adopt_cache=False``).  Member death (ConnectionError through the
    retry policy / breaker) marks the member down, re-assigns its
    queued work hash-ring-next and fails the dead call over the same
    way, so a mid-burst kill yields zero 5xx-without-shed.
    """

    def __init__(self, members: Sequence, lane_width: int = 2,
                 steal_min_backlog: int = 2, hash_replicas: int = 64,
                 failover: bool = True, qos_weight: int = 0,
                 peer_fetch: bool = True,
                 peer_timeout_s: float = 0.5,
                 ring_seed: str = "",
                 wire_handoff: bool = False,
                 hotkey=None):
        if not members:
            raise ValueError("fleet needs at least one member")
        if lane_width < 1:
            raise ValueError("fleet lane_width must be >= 1")
        self.members: Dict[str, object] = {m.name: m for m in members}
        if len(self.members) != len(members):
            raise ValueError("duplicate fleet member names")
        self.order: List[str] = [m.name for m in members]
        self.ring = HashRing(self.order, replicas=hash_replicas,
                             seed=ring_seed)
        # Cross-host drains (parallel.federation): when the draining
        # member is LOCAL and its successor is REMOTE, hand the warm
        # bytes themselves over the shard_transfer op — a successor on
        # another host cannot re-read this host's pixel store, so a
        # hint-list prestage would arrive cold.
        self.wire_handoff = bool(wire_handoff)
        self.lane_width = lane_width
        # 0 disables stealing entirely.
        self.steal_min_backlog = max(0, int(steal_min_backlog))
        self.failover = failover
        # Tiered QoS (config.qos): interactive units jump bulk
        # backlogs at this weight; 0 = plain FIFO (pre-QoS behavior).
        self.qos_weight = max(0, int(qos_weight))
        # The admission controller reads this as the fleet's service
        # parallelism (estimated wait = depth * EWMA / lanes).
        self.device_lanes = lane_width * len(members)
        self._queues: Dict[str, _MemberQueue] = {
            name: _MemberQueue(self.qos_weight)
            for name in self.order}
        self._inflight: Dict[str, int] = {n: 0 for n in self.order}
        # ONE wake event for all idle lanes: stealing means any lane
        # may be interested in any member's new work, and at fleet
        # scale (N <= ~16 members) a broadcast wake is cheaper than a
        # correct per-member + steal-candidate wake dance.
        self._wake: Optional[asyncio.Event] = None
        self._lanes: List[asyncio.Task] = []
        self._closed = False
        # Fleet-global byte tier (deploy/DEPLOY.md "Edge caching"):
        # probe the shard authority's byte cache before any
        # re-render, and write a thief's render back to it.
        self.peer_fetch = peer_fetch
        self.peer_timeout_s = peer_timeout_s
        # Combined-role fleets have no remote peers — every member
        # shares ONE byte-cache chain the handler already probes — so
        # the peer path short-circuits to a single attribute read.
        self._has_remote_members = any(
            getattr(m, "remote", False) for m in members)
        self._putback_tasks: set = set()
        # Per-member shard manifests captured at drain time, replayed
        # BACK into the member on undrain (pre-stage-back); the last
        # replay task is exposed so drills/operators can await it.
        self._drain_manifests: Dict[str, List[dict]] = {}
        self.last_undrain_prestage: Optional[asyncio.Task] = None
        # Hot-plane replication (popularity-aware placement): a
        # decayed heat tracker over the dispatch stream promotes
        # past-threshold routes to an R>1 replica set — a
        # DETERMINISTIC prefix of the ring chain, so every federated
        # host computes the same set — and reads balance least-queued
        # across the live replicas.  Writes and byte-tier authority
        # stay with the ring owner (chain[0]); ``hotkey=None`` or
        # ``enabled=False`` keeps every pre-replication behavior
        # bit-exact.
        self.hotkey = (hotkey if hotkey is not None
                       and getattr(hotkey, "enabled", False)
                       and len(self.order) > 1 else None)
        self._heat: Optional[HeatTracker] = None
        if self.hotkey is not None:
            self._heat = HeatTracker(
                threshold=getattr(self.hotkey, "threshold", 12.0),
                decay_s=getattr(self.hotkey, "decay_s", 20.0),
                top_k=getattr(self.hotkey, "top_k", 128))
        # route -> replica member names (chain prefix; [0] is the ring
        # owner / write authority).  All bookkeeping is loop-confined
        # like the queues.
        self._replica_sets: Dict[str, List[str]] = {}
        # route -> member names already staged THIS promotion epoch
        # (cleared on demote): the never-double-stage guard.
        self._replica_staged: Dict[str, set] = {}
        # Every route ever promoted (bounded): shard accounting
        # separates deliberate replication from duplicate staging.
        self._hot_ever: set = set()

    # ----------------------------------------------------------- routing

    @staticmethod
    def _pinned(ctx) -> bool:
        """Full-plane and z-projection jobs pin to the mesh lane
        (member 0) and are never stolen or ring-routed.  THE bulk
        classification lives in ``server.pressure.is_bulk`` — the
        governor's shed_bulk step and this pin must never drift apart
        (work the ladder stops shedding must be work the fleet still
        pins, and vice versa)."""
        from ..server.pressure import is_bulk
        return is_bulk(ctx)

    def _routable(self, name: str) -> bool:
        """May NEW work land on this member: alive and not draining.
        Draining is deliberately distinct from down — a draining
        member still finishes in-flight work and answers pre-stage
        handoffs, it just accepts no new routes."""
        member = self.members[name]
        return member.healthy and not member.draining

    def owner_of(self, ctx) -> str:
        """The routable member SERVING this request's plane (hash-
        ring-next past down AND draining members; least-queued among
        the live replica set for a promoted hot route).  Full-plane
        and z-projection jobs pin to the first member — the lane whose
        renderer is the lockstep ``MeshRenderer`` in mesh deployments
        — and never shard."""
        if self._pinned(ctx):
            return self._walk_chain(list(self.order))  # 0 = mesh lane
        return self._serving_member(plane_route_key(ctx))

    def _serving_member(self, route: str, record: bool = False) -> str:
        """Replica-balanced read routing: a promoted route picks the
        least-queued of its LIVE replicas (ties break in chain order,
        so the ring owner wins an idle fleet); drained/dead replicas
        drop out via the same ``_routable`` verdict as everything
        else, and a fully-unroutable replica set falls back to the
        plain chain walk — deaths behave exactly like today."""
        replicas = self._replica_sets.get(route) \
            if self._replica_sets else None
        if replicas:
            live = [n for n in replicas if self._routable(n)]
            if live:
                target = min(
                    live,
                    key=lambda n: (len(self._queues[n])
                                   + self._inflight[n],
                                   replicas.index(n)))
                if record and target != replicas[0]:
                    from ..utils import telemetry
                    telemetry.HOTKEY.count_balanced(target)
                return target
        return self._walk_chain(self.ring.chain(route))

    def _walk_chain(self, chain: List[str]) -> str:
        from . import federation
        fenced = self.failover and federation.is_fenced()
        if not self.failover or fenced:
            # Contract symmetry with _fail_queue: failover=false means
            # a dead member's shard FAILS — for queued work and new
            # arrivals alike.  Walking past an unhealthy owner here
            # would silently re-home its planes onto the ring
            # successor (with adopt_cache=True and no failed_over
            # tick), exactly the shard migration the operator
            # disabled.  DRAINING is the exception: a drain is an
            # operator-ordered handoff, so its re-home is the point.
            # A FENCED minority island takes the same no-re-home walk:
            # adopting a silent peer's shard during a netsplit is how
            # split brains write — the owner's call fails over the
            # 503-with-shed contract instead, counted as a refusal.
            for name in chain:
                if not self.members[name].draining:
                    if fenced and not self._routable(name):
                        federation.quorum_allow("adoption")
                    return name
            return chain[0]
        for name in chain:
            if self._routable(name):
                return name
        # Every member down: hand the ring owner the call anyway so
        # the failure surfaces as the ConnectionError -> 503 contract
        # instead of an unroutable internal error.
        return chain[0]

    # ----------------------------------------------- hot-plane replication

    def _observe_heat(self, route: str) -> None:
        """One dispatch observation: bump the route's heat, promote it
        past the threshold, and sweep cooled promotions back down.
        Loop-confined (dispatch only), like all queue bookkeeping."""
        heat = self._heat.observe(route)
        if heat >= self._heat.threshold \
                and route not in self._replica_sets:
            from . import federation
            if federation.quorum_allow("promotion"):
                self._promote_route(route, heat)
            # Fenced: promotion would stage bytes onto replicas this
            # island cannot prove it owns — refused (counted); the
            # route re-promotes on first hot dispatch after restore.
        self._sweep_hot_routes()

    def _promote_route(self, route: str, heat: float) -> None:
        """Give a hot route an R>1 replica set: a deterministic PREFIX
        of its ring chain (chain[0] stays the write / byte-tier
        authority), then stage the owner's warm slice onto the new
        replicas through the digest-deduped staging path —
        fire-and-forget, never blocking the hot dispatch itself."""
        from ..utils import telemetry
        chain = self.ring.chain(route)
        r = min(max(2, int(getattr(self.hotkey, "max_replicas", 2))),
                len(chain))
        replicas = chain[:r]
        self._replica_sets[route] = replicas
        self._hot_ever.add(route)
        while len(self._hot_ever) > 4096:
            self._hot_ever.pop()
        telemetry.HOTKEY.count_promoted()
        telemetry.HOTKEY.set_hot_routes(len(self._replica_sets))
        telemetry.FLIGHT.record("hotkey.promote", route=route[:12],
                                heat=round(heat, 1),
                                replicas=",".join(replicas))
        from ..utils import decisions
        decisions.record("hotkey", "promoted",
                         detail={"route": route[:16],
                                 "heat": round(heat, 2),
                                 "replicas": list(replicas)})
        try:
            task = asyncio.get_running_loop().create_task(
                self._stage_replicas(route, replicas))
        except RuntimeError:
            return                 # no loop (sync tests): lazy warm
        self._putback_tasks.add(task)
        task.add_done_callback(self._putback_tasks.discard)

    async def _stage_replicas(self, route: str,
                              replicas: List[str]) -> int:
        """Stage the hot route's owner slice onto its replicas.  Each
        (route, replica) pair stages at most once per promotion epoch
        (``_replica_staged``), and the staging path itself digest-
        dedups, so re-promotion after a demote is a residency probe
        hit — never a duplicate HBM buffer."""
        from ..utils import telemetry
        owner = self.members.get(replicas[0])
        if owner is None:
            return 0
        route_fn = getattr(owner, "route_manifest", None)
        try:
            if route_fn is not None:
                entries = await route_fn(route)
            else:
                entries = [e for e in await owner.shard_manifest(0)
                           if e.get("route") == route]
        except Exception:
            entries = []
        staged_members = self._replica_staged.setdefault(route, set())
        total = 0
        for name in replicas[1:]:
            if name in staged_members:
                # The never-double-stage guard: a second stage of the
                # same (route, replica) pair in one epoch would be a
                # bookkeeping bug — counted, visible, asserted == 0.
                telemetry.HOTKEY.count_duplicate_staged()
                continue
            member = self.members.get(name)
            if member is None or not member.healthy:
                continue
            staged_members.add(name)
            if not entries:
                # Nothing warm to hand over yet: the replica warms
                # through its own balanced renders (the same
                # digest-deduped staging path) — no work to ship.
                continue
            try:
                n = await member.prestage_manifest(entries)
            except Exception:
                staged_members.discard(name)
                continue
            total += n
            telemetry.HOTKEY.count_staged(n)
            telemetry.FLIGHT.record("hotkey.stage", route=route[:12],
                                    member=name, entries=n)
        return total

    def _sweep_hot_routes(self) -> None:
        """Demote promoted routes whose decayed heat fell under the
        demote fraction of the threshold (hysteresis: promotion at
        ``threshold``, demotion below ``threshold * demote_fraction``
        — no flapping at the boundary).  Replica HBM entries are NOT
        evicted here: reclaim is deferred to the cache-pressure ladder
        (``evict_to_fraction`` takes cold entries LRU-first), so a
        re-heating route finds its replicas still warm."""
        if not self._replica_sets:
            return
        demote_at = (self._heat.threshold
                     * float(getattr(self.hotkey, "demote_fraction",
                                     0.5)))
        for route in list(self._replica_sets):
            if self._heat.heat(route) <= demote_at:
                self._demote_route(route)

    def _demote_route(self, route: str) -> None:
        from ..utils import telemetry
        self._replica_sets.pop(route, None)
        self._replica_staged.pop(route, None)
        telemetry.HOTKEY.count_demoted()
        telemetry.HOTKEY.set_hot_routes(len(self._replica_sets))
        telemetry.FLIGHT.record("hotkey.demote", route=route[:12])
        from ..utils import decisions
        decisions.record("hotkey", "demoted",
                         detail={"route": route[:16]})

    def shed_replicas(self) -> int:
        """Demote EVERY promoted route (the cache-pressure ladder's
        evict step calls this before ``evict_to_fraction``): replicas
        are pure duplicates, so under memory pressure they are the
        first HBM the fleet can afford to lose."""
        routes = list(self._replica_sets)
        for route in routes:
            self._demote_route(route)
        return len(routes)

    def apply_manifest(self, manifest) -> bool:
        """Swap the routing ring to ``manifest``'s geometry at an
        epoch COMMIT — the ONLY moment a live router's ring ever
        changes (a propose leaves routing untouched; in-flight work
        finishes on the old owners, the next dispatch routes on the
        new ring).  Same-membership rolls (seed / replica-count /
        epoch bumps) are the supported surface: a membership change
        needs member construction this router cannot do and raises.
        Promoted hot routes are shed first — their replica sets are
        chain prefixes of the OLD ring and would pin stale owners
        across the swap (re-heating routes re-promote on the new
        ring's chains)."""
        names = set(manifest.names())
        if names != set(self.order):
            raise ValueError(
                "epoch roll changed fleet membership "
                f"({sorted(names ^ set(self.order))}); a live router "
                "only swaps ring geometry — membership changes need "
                "a restart")
        shed = self.shed_replicas()
        self.ring = HashRing(self.order, replicas=manifest.replicas,
                             seed=manifest.ring_seed)
        from ..utils import telemetry
        telemetry.FLIGHT.record("fleet.ring-swap",
                                epoch=manifest.version,
                                seed=str(manifest.ring_seed)[:16],
                                replicas=manifest.replicas,
                                shed_hot=shed)
        return True

    def replica_set(self, route: str) -> List[str]:
        """The route's CURRENT replica set ([owner] when not
        promoted) — /debug/explain's replica-set line."""
        replicas = self._replica_sets.get(route)
        if replicas:
            return list(replicas)
        chain = self.ring.chain(route)
        return chain[:1]

    def route_heat(self, route: str) -> float:
        return self._heat.heat(route) if self._heat is not None else 0.0

    def is_hot_route(self, route: str) -> bool:
        return route in self._replica_sets

    def hot_route_count(self) -> int:
        return len(self._replica_sets)

    def hot_owned(self, name: str) -> int:
        """Promoted routes whose replica set includes ``name`` (the
        gossip view's per-member hot figure)."""
        return sum(1 for reps in self._replica_sets.values()
                   if name in reps)

    def replica_pressure(self) -> float:
        """Sustained hot-route demand in units of the promotion
        threshold: max over promoted routes of heat / threshold.  >= 1
        while a promoted route is still at promotion heat; grows with
        demand concentration — the autoscaler's scale-up signal for
        'one plane is outrunning one member', distinct from plain
        queue depth."""
        if self._heat is None or not self._replica_sets:
            from ..utils import telemetry
            telemetry.HOTKEY.set_pressure(0.0)
            return 0.0
        pressure = max((self._heat.heat(r) / self._heat.threshold
                        for r in self._replica_sets), default=0.0)
        from ..utils import telemetry
        telemetry.HOTKEY.set_pressure(pressure)
        return pressure

    def local_replica_caches(self, route: str) -> List:
        """The HBM caches of the LOCAL replicas of a promoted route,
        balanced-read order (the prefetcher stages a hot route's
        predicted tiles into every balanced reader, not just the ring
        owner).  Empty for unpromoted routes."""
        out = []
        for name in self._replica_sets.get(route, ()):
            if not self._routable(name):
                continue
            member = self.members[name]
            if getattr(member, "remote", False):
                continue
            cache = getattr(getattr(member, "services", None),
                            "raw_cache", None)
            if cache is not None:
                out.append(cache)
        return out

    def queue_depth(self) -> int:
        """Queued + executing across the whole fleet (what fleet-aware
        admission and /readyz see)."""
        return (sum(len(q) for q in self._queues.values())
                + sum(self._inflight.values()))

    def member_depth(self, name: str) -> int:
        return len(self._queues[name])

    def member_inflight(self, name: str) -> int:
        return self._inflight[name]

    def healthy_members(self) -> List[str]:
        return [n for n in self.order if self.members[n].healthy]

    def cache_for_route(self, route_key: str):
        """The HBM raw cache of the member that OWNS ``route_key`` —
        the predictive prefetcher's fleet seam: a predicted plane
        stages into the shard that will serve its future request, so
        prefetch warms the right member and the shard map never
        duplicates.  None for remote members (their sidecars prefetch
        for themselves) or when the owner has no cache."""
        for name in self.ring.chain(route_key):
            if self._routable(name):
                member = self.members[name]
                return getattr(getattr(member, "services", None),
                               "raw_cache", None)
        return None

    def remote_prestage_for_route(self, route_key: str,
                                  entry: dict) -> bool:
        """Shard-aware prefetch, cross-host seam: a PREDICTED plane
        whose ring owner is a REMOTE member stages on ITS owner's
        host — a fire-and-forget ``prestage`` hint (the owner re-reads
        the region from its own pixel store through the digest-deduped
        staging path), so speculation warms the member that will serve
        the request instead of this host's wrong shard.  False when
        the owner is local (``cache_for_route`` handles it in-process)
        or unroutable."""
        for name in self.ring.chain(route_key):
            if not self._routable(name):
                continue
            member = self.members[name]
            if not getattr(member, "remote", False):
                return False
            from ..utils import telemetry

            async def hint() -> None:
                try:
                    await member.prestage_manifest([entry])
                except Exception:
                    pass           # speculation only removes work

            try:
                task = asyncio.get_running_loop().create_task(hint())
            except RuntimeError:
                return False       # no loop: prefetch pool thread
            telemetry.FEDERATION.count_remote_prestage()
            self._putback_tasks.add(task)
            task.add_done_callback(self._putback_tasks.discard)
            return True
        return False

    def draining_members(self, intent: Optional[str] = None
                         ) -> List[str]:
        """Draining member names; ``intent`` filters to one drain
        flavor ("operator" / "autoscale") — the /readyz fail posture
        only counts operator drains, so a routine autoscale
        scale-down never pulls the instance from LB rotation."""
        return [n for n in self.order
                if self.members[n].draining
                and (intent is None
                     or getattr(self.members[n], "drain_intent",
                                None) == intent)]

    # ----------------------------------------------------------- drains

    async def drain_member(self, name: str, prestage: bool = True,
                           max_planes: int = 256,
                           settle_timeout_s: float = 30.0,
                           intent: str = "operator") -> dict:
        """Zero-downtime rolling drain of one member.

        Phases (each a flight-recorder event and a
        ``imageregion_drain_*`` transition):

        1. **draining** — the member stops accepting routes (new
           arrivals and failovers walk past it; its lanes stop
           stealing) and its QUEUED work re-homes hash-ring-next with
           adoption, exactly the failover remap bound (~1/N).
        2. **settle** — in-flight renders finish on the member (a
           drain interrupts nothing; ``settle_timeout_s`` bounds the
           wait, not the work).
        3. **handoff** — the member's HBM shard manifest (MRU-first,
           bounded by ``max_planes``) is handed to each plane's NEW
           ring owner, which pre-stages it through the digest-deduped
           staging path — the shard arrives WARM on the successor
           instead of cold-missing.
        4. **drained** — the member is safe to restart; ``undrain``
           rejoins it with the same remap bound as a ring join.

        Idempotent: draining an already-draining member just re-runs
        the settle + handoff."""
        import time as _time
        from ..utils import decisions, telemetry

        if name not in self.members:
            raise KeyError(f"unknown fleet member {name!r}")
        member = self.members[name]
        member.draining = True
        # The drain FLAVOR: "operator" (rolling restart — what
        # drain.fail-readyz surfaces to LBs) vs "autoscale" (routine
        # scale-down — annotation only, /readyz stays 200).
        member.drain_intent = intent
        telemetry.DRAIN.set_state(name, "draining")
        telemetry.FLIGHT.record("drain.phase", member=name,
                                phase="draining", intent=intent,
                                queued=len(self._queues[name]),
                                inflight=self._inflight[name])
        # Queued work re-homes NOW (the lanes would drain it anyway,
        # but re-homing bounds the drain's tail latency by the
        # in-flight work only).
        self._reassign(name, reason="drain")
        t0 = _time.monotonic()
        while (self._inflight[name] > 0
               and _time.monotonic() - t0 < settle_timeout_s):
            await asyncio.sleep(0.02)
        settled = self._inflight[name] == 0
        manifest = await member.shard_manifest(max_planes)
        # Stashed for the rejoin: undrain replays this manifest BACK
        # through the digest-deduped staging path so the member's
        # shard is warm before its first routed request (a restart
        # drops the HBM cache; the manifest is what it held).
        if manifest:
            self._drain_manifests[name] = manifest
        prestaged = 0
        if prestage and manifest:
            telemetry.FLIGHT.record("drain.phase", member=name,
                                    phase="handoff",
                                    planes=len(manifest))
            prestaged = await self._prestage_handoff(name, manifest)
            telemetry.DRAIN.count_prestaged(prestaged)
        telemetry.DRAIN.set_state(name, "drained")
        telemetry.FLIGHT.record("drain.phase", member=name,
                                phase="drained", settled=settled,
                                planes=len(manifest),
                                prestaged=prestaged)
        logger.info("fleet member %s drained (settled=%s, %d shard "
                    "planes, %d pre-staged on successors)", name,
                    settled, len(manifest), prestaged)
        # Ledger verdict: "failed" means the settle window expired
        # with work still in flight — the drain completed anyway, but
        # the controller's intent (interrupt nothing) did not hold.
        decisions.record("drain", "done" if settled else "failed",
                         member=name, detail={
                             "intent": intent, "settled": settled,
                             "planes": len(manifest),
                             "prestaged": prestaged})
        return {"member": name, "settled": settled, "intent": intent,
                "planes": len(manifest), "prestaged": prestaged}

    async def _prestage_handoff(self, draining: str,
                                manifest: List[dict]) -> int:
        """Hand each manifest plane to the member that will SERVE it:
        its recorded routing identity walks the ring exactly like a
        live request (the draining member is no longer routable, so
        the walk lands on the true successor).  Entries missing a
        route (legacy manifests, wire-pushed planes) spread by their
        raw key — deterministic, and still warm-on-SOME-member."""
        by_successor: Dict[str, List[dict]] = {}
        for entry in manifest:
            route = entry.get("route") or repr(entry.get("key"))
            for candidate in self.ring.chain(route):
                if candidate != draining and self._routable(candidate):
                    by_successor.setdefault(candidate,
                                            []).append(entry)
                    break
        from ..utils import decisions
        staged = 0
        failed = 0
        draining_member = self.members[draining]
        # Cross-host warm handoff: a LOCAL drainer's HBM bytes ship
        # over the wire to REMOTE successors (their host cannot
        # re-read this host's pixel store).  Exported once, bounded by
        # the manifest the drain already capped; any export/ship
        # failure degrades to the hint-list prestage below.
        exported: Dict[tuple, dict] = {}
        if self.wire_handoff and not draining_member.remote and any(
                self.members[s].remote for s in by_successor):
            try:
                for entry in await draining_member.shard_export(
                        len(manifest)):
                    exported[_entry_key(entry)] = entry
            except Exception:
                logger.warning("shard export from %s failed; "
                               "hint-list handoff", draining,
                               exc_info=True)
        for successor, entries in by_successor.items():
            member = self.members[successor]
            try:
                if exported and member.remote:
                    with_bytes = [exported[_entry_key(e)]
                                  for e in entries
                                  if _entry_key(e) in exported]
                    # Ship the warm bytes (shard_transfer counts each
                    # landed entry's bytes itself); entries whose
                    # buffer was already evicted fall back to hints.
                    staged += await member.shard_transfer(with_bytes)
                    rest = [e for e in entries
                            if _entry_key(e) not in exported]
                    if rest:
                        staged += await member.prestage_manifest(rest)
                else:
                    staged += await member.prestage_manifest(entries)
            except Exception:
                failed += 1
                logger.warning("drain handoff to %s failed",
                               successor, exc_info=True)
        decisions.record("handoff", "failed" if failed else "done",
                         member=draining, detail={
                             "planes": len(manifest), "staged": staged,
                             "successors": len(by_successor),
                             "failed_successors": failed})
        return staged

    def undrain_member(self, name: str,
                       prestage_back: bool = True) -> None:
        """Rejoin a drained member: routes flow back onto its ring
        arcs at the next dispatch — the same ~1/N remap bound as a
        ring join (the ring itself never changed).

        **Pre-stage BACK**: the shard manifest captured when this
        member drained replays into it through the digest-deduped
        ``restage_plane_entry`` path, so a member that restarted with
        a cold HBM cache rejoins WARM — its first routed request hits
        instead of paying the cold read/stage the drain existed to
        avoid.  Background + best-effort (the member serves either
        way); the task is exposed as ``last_undrain_prestage`` so the
        drill (and a scripted roll) can await completion."""
        from ..utils import decisions, telemetry
        if name not in self.members:
            raise KeyError(f"unknown fleet member {name!r}")
        member = self.members[name]
        member.draining = False
        member.drain_intent = None
        telemetry.DRAIN.set_state(name, "active")
        telemetry.FLIGHT.record("drain.phase", member=name,
                                phase="undrained")
        decisions.record("undrain", "done", member=name, detail={
            "prestage_back": bool(prestage_back
                                  and self._drain_manifests.get(name))})
        entries = self._drain_manifests.pop(name, None)
        self.last_undrain_prestage = None
        if prestage_back and entries:
            async def _restage_back() -> None:
                try:
                    staged = await member.prestage_manifest(entries)
                except Exception:
                    logger.warning("undrain pre-stage-back into %s "
                                   "failed", name, exc_info=True)
                    return
                telemetry.DRAIN.count_prestaged(staged)
                telemetry.FLIGHT.record(
                    "drain.phase", member=name, phase="prestage-back",
                    planes=len(entries), prestaged=staged)
                logger.info("fleet member %s pre-staged back %d/%d "
                            "shard planes on undrain", name, staged,
                            len(entries))

            try:
                loop = asyncio.get_running_loop()
            except RuntimeError:
                loop = None   # sync caller with no loop: serve cold
            if loop is not None:
                task = loop.create_task(_restage_back())
                self.last_undrain_prestage = task
                # Tracked with the put-back shipments so close()
                # cancels an in-flight replay instead of leaking it.
                self._putback_tasks.add(task)
                task.add_done_callback(self._putback_tasks.discard)
        logger.info("fleet member %s undrained (rejoined the ring)",
                    name)

    # ---------------------------------------------------------- dispatch

    def _ensure_lanes(self) -> None:
        if self._lanes or self._closed:
            return
        from ..utils import transient
        self._wake = asyncio.Event()
        # Lanes are spawned lazily from the FIRST request's context —
        # detach them from its deadline contextvar (create_task
        # snapshots the context), or every render in every lane would
        # permanently inherit that one request's budget and start
        # 504ing fleet-wide the moment it expires.  Each unit's own
        # budget is re-established around its render from
        # ``work.deadline``.
        with transient.deadline_scope(None):
            for name in self.order:
                for lane in range(self.lane_width):
                    self._lanes.append(asyncio.create_task(
                        self._lane(name), name=f"fleet-{name}-l{lane}"))

    async def dispatch(self, ctx) -> bytes:
        """Route one render to its shard owner and await the bytes.
        Runs on the event loop; all queue bookkeeping is loop-confined
        (no lock), like the single-flight table."""
        from ..utils import telemetry, transient

        if self._closed:
            raise ConnectionError("fleet router is closed")
        self._ensure_lanes()
        if self._heat is not None and not self._pinned(ctx):
            # Hot-key tier: every dispatched (non-pinned) request
            # feeds the heat tracker; a promoted route's reads then
            # balance least-queued across its live replicas.
            route = plane_route_key(ctx)
            self._observe_heat(route)
            owner = self._serving_member(route, record=True)
        else:
            owner = self.owner_of(ctx)
        work = _Work(ctx, asyncio.get_running_loop().create_future(),
                     owner, transient.deadline())
        if work.trace_ids:
            # Hop 1 of the stitched waterfall: the ROUTE decision —
            # which member's shard this plane hashed to.  Zero-width
            # span at enqueue time; the render hop below shows where
            # the work actually ran (steal/failover may move it).
            telemetry.record_span(
                "fleet.hop", work.t_enqueue, 0.0,
                trace_ids=work.trace_ids, member=owner, hop="route",
                plane=work.route_key)
        self._queues[owner].append(work)
        telemetry.FLEET.count_routed(owner)
        self._wake.set()
        remaining = transient.remaining_ms()
        if remaining is None:
            return await work.future
        try:
            # The member render enforces its own budget too; this
            # bound covers a lane wedged in an uncancellable render.
            return await asyncio.wait_for(
                asyncio.shield(work.future),
                timeout=max(0.0, remaining) / 1000.0)
        except asyncio.TimeoutError:
            # The waiter is gone: cancel the unit so a lane popping
            # it later skips instead of rendering bytes nobody will
            # retrieve (and so no 'exception never retrieved' noise).
            if not work.future.done():
                work.future.cancel()
            raise transient.DeadlineExceededError(
                "deadline exceeded awaiting fleet render")
        except asyncio.CancelledError:
            if not work.future.done():
                work.future.cancel()
            raise

    async def fetch_peer_bytes(self, ctx) -> Optional[bytes]:
        """The offload ladder's peer rung: when routing would hand
        this render to a member that is NOT the chain's byte
        authority (the ring owner is draining or down and the shard
        moved hash-ring-next), probe the authority's byte tier and
        fetch the already-rendered bytes over the idempotent
        ``byte_probe``/``byte_fetch`` wire ops INSTEAD of re-rendering
        on the successor.  The authority is the first chain member
        alive enough to answer — healthy OR draining (a draining
        member finishes work and serves handoffs by design; its byte
        tier is exactly where the just-rendered bytes live).

        Combined-role members share ONE byte-cache chain the fleet
        handler already probed, so only REMOTE peers are asked.  Every
        failure (timeout, dead peer, ACL refusal, miss) returns None
        and the render path proceeds — the peer tier can only ever
        remove work, never add a failure mode."""
        if not self.peer_fetch or not self._has_remote_members \
                or self._pinned(ctx):
            return None
        from ..utils import telemetry
        serving = self.owner_of(ctx)
        for name in self.ring.chain(plane_route_key(ctx)):
            if name == serving:
                # The serving member probes its own tier first thing
                # in its handler — a frontend pre-probe of the SAME
                # tier would only double the round-trips.
                return None
            member = self.members[name]
            if not member.remote \
                    or not (member.healthy or member.draining):
                continue
            # ONE round-trip: byte_fetch itself is the probe (None =
            # authority miss -> render; the batched byte_probe op
            # exists for bulk callers).  A transport failure counts a
            # FALLBACK — distinct from a miss, so degraded peering is
            # visible on /metrics rather than reading as cold tiles.
            telemetry.HTTPCACHE.count_peer_probe()
            key = ctx.cache_key    # == settings.render_identity_key
            t0 = time.perf_counter()
            try:
                data = await asyncio.wait_for(
                    member.byte_fetch(key, image_id=ctx.image_id,
                                      session=ctx.omero_session_key),
                    self.peer_timeout_s)
            except Exception:
                telemetry.HTTPCACHE.count_peer_fallback()
                return None
            if data is None:
                # The authority has no bytes: nothing newer down the
                # chain would (writes land authority-first) — render.
                return None
            telemetry.HTTPCACHE.count_peer_hit()
            telemetry.HTTPCACHE.count_peer_fetch()
            # Hop span (request context — fetch runs in the handler)
            # + provenance: the bytes came from a PEER's tier.
            telemetry.record_span(
                "fleet.hop", t0,
                (time.perf_counter() - t0) * 1000.0,
                member=name, hop="byte_fetch",
                plane=plane_route_key(ctx)[:12])
            from ..utils import provenance
            provenance.mark(ctx, tier="peer", member=name)
            telemetry.FLIGHT.record("fleet.byte-peer",
                                    authority=name,
                                    serving=serving,
                                    nbytes=len(data))
            return data
        return None

    @staticmethod
    def _mask_route(ctx) -> str:
        """Ring route for a mask's byte authority: its byte-cache key
        (the storage identity the PR 11 ETag folds), namespaced so a
        mask and a render identity can never share an arc owner by
        accident."""
        return f"mask|{ctx.cache_key()}"

    async def fetch_peer_mask(self, ctx) -> Optional[bytes]:
        """Federated byte tier for ShapeMask PNGs: probe the mask's
        ring-authority host over the same idempotent ``byte_fetch``
        wire op as tiles (``tier=mask``) so a mask rendered on one
        host is every host's hit.  Only explicit-color masks are
        byte-cached (the reference's staleness rule), so only those
        are asked for; local members share THIS host's already-probed
        ``shape_mask`` stack and are skipped.  None on miss, ACL
        refusal or any transport failure — the peer tier only ever
        removes work."""
        if not self.peer_fetch or not self._has_remote_members \
                or getattr(ctx, "color", None) is None:
            return None
        from ..utils import provenance, telemetry
        key = str(ctx.cache_key())
        for name in self.ring.chain(self._mask_route(ctx)):
            member = self.members[name]
            if not getattr(member, "remote", False) \
                    or not (member.healthy or member.draining):
                continue
            telemetry.HTTPCACHE.count_peer_probe()
            try:
                data = await asyncio.wait_for(
                    member.byte_fetch(
                        key, image_id=ctx.shape_id,
                        session=ctx.omero_session_key,
                        tier="mask", obj="Mask"),
                    self.peer_timeout_s)
            except Exception:
                telemetry.HTTPCACHE.count_peer_fallback()
                return None
            if data is None:
                return None
            telemetry.HTTPCACHE.count_peer_hit()
            telemetry.HTTPCACHE.count_peer_fetch()
            provenance.mark(ctx, tier="peer", member=name)
            telemetry.FLIGHT.record("fleet.mask-peer", authority=name,
                                    nbytes=len(data))
            return data
        return None

    def put_peer_mask(self, ctx, data: bytes) -> None:
        """Ship a just-rendered explicit-color mask PNG to its ring
        authority's mask byte tier (fire-and-forget ``byte_put``,
        never blind-retried) — the write-back half of the federated
        mask tier.  A local authority needs nothing: the render path
        already wrote this host's shared ``shape_mask`` stack."""
        if not self.peer_fetch or not self._has_remote_members \
                or getattr(ctx, "color", None) is None:
            return
        from ..utils import telemetry
        key = str(ctx.cache_key())
        for name in self.ring.chain(self._mask_route(ctx)):
            member = self.members[name]
            if not (member.healthy or member.draining):
                continue
            if not getattr(member, "remote", False):
                return            # local authority: already stored
            from . import federation
            if not federation.quorum_allow("write_authority"):
                return        # fenced: no cross-split mask write-back
            async def put() -> None:
                try:
                    if await member.byte_put(key, data, tier="mask"):
                        telemetry.HTTPCACHE.count_peer_putback()
                except Exception:
                    pass           # best-effort by contract
            try:
                task = asyncio.get_running_loop().create_task(put())
            except RuntimeError:
                return
            self._putback_tasks.add(task)
            task.add_done_callback(self._putback_tasks.discard)
            return

    def _byte_putback(self, work: _Work, data: bytes) -> None:
        """A thief finished another member's render: ship the bytes to
        the shard AUTHORITY's byte tier (fire-and-forget, over the
        state-changing ``byte_put`` op — never blind-retried, exactly
        the plane_put contract) so the owner answers the next probe
        itself — one member's render becomes every member's hit."""
        if not self.peer_fetch:
            return
        owner = self.members.get(work.owner)
        if owner is None or not owner.remote or not owner.healthy:
            return
        from . import federation
        if not federation.quorum_allow("write_authority"):
            # Fenced minority: the byte-tier authority may have moved
            # on the majority side — writing back across the split
            # would be split-brain state.  Drop the ship (counted);
            # the owner re-renders or re-probes after restore.
            return
        if getattr(work.ctx, "_pressure_quality_capped", False):
            # Brownout-capped bytes never land under the full-quality
            # key (the PR 9 drop_quality contract) — peers included.
            return
        from ..utils import telemetry
        key = work.ctx.cache_key   # == settings.render_identity_key
        if work.trace_ids:
            # Hop: the write-back SHIP (recorded synchronously, before
            # the requester's trace finishes — the put itself is
            # fire-and-forget and lands after the response; its
            # completion is the peer_putbacks counter + flight event).
            telemetry.record_span(
                "fleet.hop", time.perf_counter(), 0.0,
                trace_ids=work.trace_ids, member=work.owner,
                hop="byte_put", plane=work.route_key)

        async def put() -> None:
            try:
                if await owner.byte_put(key, data):
                    telemetry.HTTPCACHE.count_peer_putback()
            except Exception:
                pass               # best-effort by contract

        task = asyncio.get_running_loop().create_task(put())
        self._putback_tasks.add(task)
        task.add_done_callback(self._putback_tasks.discard)

    def _takeable(self, name: str) -> bool:
        """Is there work this member's lanes could take right now —
        its own backlog, or a peer backlog past the steal threshold?"""
        if self._queues[name]:
            return True
        if self.steal_min_backlog <= 0 or not self._routable(name):
            return False
        # Mirrors _pop_work's steal candidates exactly (stealable =
        # INTERACTIVE backlog; pinned/bulk units are never stealable)
        # — a backlog this lane can NEVER steal must park it on the
        # wake event, not busy-spin it.
        return any(
            self._queues[other].steal_depth() >= self.steal_min_backlog
            for other in self.order if other != name)

    def _pop_work(self, name: str) -> Optional[_Work]:
        """This lane's next unit: own queue first (weighted dequeue —
        interactive jumps bulk backlogs when QoS is on); otherwise
        steal the OLDEST interactive request from the most-backlogged
        healthy-owned queue at or past the steal threshold
        (oldest-first keeps the latency tail honest — LIFO stealing
        would starve the convoy head).  Pinned mesh-lane (bulk) jobs
        are never stealable — they exist to run on member 0's lockstep
        renderer, not a single-device lane."""
        queue = self._queues[name]
        if queue:
            return queue.popleft()
        if self.steal_min_backlog <= 0 or not self._routable(name):
            # A draining member's lanes drain their own queue (the
            # reassign empties it) but never steal new work.
            return None
        victim = None
        depth = 0
        for other in self.order:
            if other == name:
                continue
            qlen = self._queues[other].steal_depth()
            if qlen >= self.steal_min_backlog and qlen > depth:
                victim, depth = other, qlen
        if victim is None:
            return None
        work = self._queues[victim].steal_pop()
        if work is None:
            return None
        work.stolen = True
        from ..utils import telemetry
        telemetry.FLEET.count_stolen(name)
        telemetry.FLIGHT.record("fleet.steal", by=name,
                                owner=work.owner, backlog=depth)
        if work.trace_ids:
            # Hop: the steal decision — this unit leaves its owner's
            # queue for the thief's lane (cache-ownership-neutral).
            telemetry.record_span(
                "fleet.hop", time.perf_counter(), 0.0,
                trace_ids=work.trace_ids, member=name, hop="steal",
                plane=work.route_key)
        return work

    def _reassign(self, dead: str, reason: str = "failover") -> None:
        """A member died (or is draining): move its queued work to
        each item's hash-ring-next healthy owner (the failover shard
        owner — the work ADOPTS there, it is not a steal).  ``reason``
        distinguishes the death remap from the operator-ordered drain
        re-home on the hop spans and provenance flags."""
        from ..utils import telemetry
        queue = self._queues[dead]
        moved = 0
        while queue:
            work = queue.pop_raw()
            self._route_failover(work, reason=reason)
            moved += 1
        if moved:
            telemetry.FLIGHT.record("fleet.drain", member=dead,
                                    moved=moved)
            self._wake.set()

    def _fail_queue(self, dead: str, error: Exception) -> None:
        """failover=False: a dead member's queued work fails with it."""
        queue = self._queues[dead]
        while queue:
            work = queue.pop_raw()
            if not work.future.done():
                work.future.set_exception(ConnectionError(str(error)))

    def _route_failover(self, work: _Work,
                        reason: str = "failover") -> None:
        """Re-enqueue one unit on the first healthy ring member.  The
        member that just failed is excluded by the health check alone
        (it was marked down before this runs) — NOT by ``work.owner``:
        for STOLEN work the owner is a healthy member that never
        failed, and it is exactly where the unit should land (a dead
        stealer's loot goes home; a 2-member fleet must not 503 a
        request whose shard owner is alive)."""
        from . import federation
        from ..utils import provenance, telemetry
        if reason == "failover" and not federation.quorum_allow(
                "adoption"):
            # Fenced minority: a death re-home is a shard ADOPTION —
            # refused during a partition (the dead member may be alive
            # and serving on the majority side).  The unit fails over
            # the same ConnectionError -> 503-with-shed contract as an
            # all-down fleet; operator drains stay allowed.
            if not work.future.done():
                work.future.set_exception(ConnectionError(
                    "fenced minority partition: shard adoption "
                    "refused"))
            return
        chain = (list(self.order) if self._pinned(work.ctx)
                 else self.ring.chain(plane_route_key(work.ctx)))
        tried = work.hops
        for name in chain:
            if not self._routable(name):
                continue
            work.owner = name
            work.hops = tried + 1
            work.stolen = False
            self._queues[name].append(work)
            telemetry.FLEET.count_failed_over(name)
            if work.trace_ids:
                # Hop: the re-home — "drain" when an operator ordered
                # it, "failover" when a death did.
                telemetry.record_span(
                    "fleet.hop", time.perf_counter(), 0.0,
                    trace_ids=work.trace_ids, member=name, hop=reason,
                    plane=work.route_key)
            provenance.mark(
                work.ctx,
                **{("drain_rehomed" if reason == "drain"
                    else "failed_over"): True})
            return
        if not work.future.done():
            work.future.set_exception(ConnectionError(
                "no healthy fleet member for shard"))

    async def _lane(self, name: str) -> None:
        from ..utils import provenance, telemetry, transient
        from ..utils.stopwatch import record_since

        # Lanes are long-lived tasks spawned from the FIRST request's
        # context; detach from its trace ids or every span any render
        # ever records here would graft onto that one request's
        # waterfall (each unit re-adopts its own ids around its
        # render below).
        telemetry.clear_context()
        member = self.members[name]
        while not self._closed:
            work = self._pop_work(name)
            if work is None:
                self._wake.clear()
                # Re-check under the cleared event for work THIS lane
                # could take (a dispatch between pop and clear must
                # not be lost — but peers' sub-threshold backlogs must
                # not busy-spin a lane that cannot steal them).
                if self._takeable(name):
                    continue
                await self._wake.wait()
                continue
            if work.future.done():
                continue              # waiter gave up while queued
            if work.deadline is not None \
                    and time.monotonic() >= work.deadline:
                telemetry.RESILIENCE.count_deadline_cancelled(1)
                if not work.future.done():
                    work.future.set_exception(
                        transient.DeadlineExceededError(
                            "deadline exceeded in fleet queue"))
                continue
            self._inflight[name] += 1
            # Provenance: the member actually serving, and how the
            # unit got there (marked before the render so a failing
            # member still leaves an attributable record).
            provenance.mark(work.ctx, member=name,
                            **({"stolen": True} if work.stolen
                               else {}))
            t_render = time.perf_counter()
            try:
                # A stolen render executes on THIS member from source
                # bytes without adopting cache ownership; owned (and
                # failed-over) work adopts — the failover target IS
                # the shard's new ring owner.  The unit's remaining
                # budget re-enters the context here (the lane task
                # itself is deadline-free), so the member pipeline's
                # own check_deadline / wire deadline_ms still bite.
                # The unit's OWN trace ids re-enter too (group_trace):
                # member-side spans — and, for remote members, the
                # trace id riding the wire — attach to the requester's
                # waterfall, not to whatever context spawned the lane.
                with telemetry.group_trace(work.trace_ids):
                    if work.deadline is not None:
                        remaining_ms = max(
                            1.0, (work.deadline - time.monotonic())
                            * 1000.0)
                        with transient.deadline_scope(remaining_ms):
                            data = await member.render(
                                work.ctx, adopt_cache=not work.stolen)
                    else:
                        data = await member.render(
                            work.ctx, adopt_cache=not work.stolen)
            except (ConnectionError, OSError) as e:
                if not member.remote \
                        and not isinstance(e, ConnectionError):
                    # A LOCAL render's OSError (missing/truncated
                    # pyramid file, EIO) is that one request's
                    # failure, never member death — treating it as
                    # death would cascade a bad file into marking
                    # every member down in failover order.
                    if not work.future.done():
                        work.future.set_exception(e)
                    continue
                if not isinstance(e, MemberDownError):
                    # A fast-fail from an already-down member is not
                    # a new death — re-marking would extend the
                    # cooldown on every request and the member could
                    # never rejoin under steady traffic.
                    member.mark_down()
                    telemetry.FLIGHT.record("fleet.member-down",
                                            member=name,
                                            error=str(e)[:120])
                if not self.failover:
                    # Contract: the shard fails as the member does —
                    # queued work included, never re-homed.
                    logger.warning("fleet member %s down (%s); "
                                   "failover disabled, failing its "
                                   "shard", name, e)
                    self._fail_queue(name, e)
                    if not work.future.done():
                        work.future.set_exception(e)
                    continue
                logger.warning("fleet member %s down (%s); failing "
                               "its shard over hash-ring-next", name, e)
                self._reassign(name)
                if work.hops < len(self.order) - 1:
                    self._route_failover(work)
                    self._wake.set()
                elif not work.future.done():
                    work.future.set_exception(e)
            except asyncio.CancelledError:
                # Router teardown mid-render: waiters sit in HTTP
                # handlers whose ``except Exception`` must map this to
                # a 500, never a dropped connection.
                if not work.future.done():
                    work.future.set_exception(
                        RuntimeError("fleet router shut down"))
                raise
            except Exception as e:
                if not work.future.done():
                    work.future.set_exception(e)
            else:
                # The render hop itself: which member executed, and
                # under what acquisition (owned / stolen / failed-over)
                # — the widest lane of the stitched waterfall, and the
                # one hop with a duration, so the one on /metrics.
                record_since(
                    "fleet.hop", t_render, trace_ids=work.trace_ids,
                    member=name, hop="render", plane=work.route_key,
                    **({"stolen": 1} if work.stolen else {}))
                if not work.future.done():
                    work.future.set_result(data)
                if work.stolen:
                    # The thief's render lands on the shard authority's
                    # byte tier too (fire-and-forget byte_put): one
                    # member's render becomes every member's hit.
                    self._byte_putback(work, data)
            finally:
                self._inflight[name] -= 1

    # --------------------------------------------------------- accounting

    def shard_report(self) -> dict:
        """HBM shard accounting across local members: per-member
        resident planes, and how many content digests are resident on
        MORE than one member (the duplicate-staging figure the fleet
        exists to hold at ~0).  Digests whose route was DELIBERATELY
        replicated by the hot-key tier are reported separately
        (``replicated_digests``) — replication must never masquerade
        as, nor mask, a duplicate-staging bug."""
        per_member = {}
        seen: Dict[str, int] = {}
        for name in self.order:
            digests = self.members[name].resident_digests()
            per_member[name] = self.members[name].resident_planes()
            for d in digests:
                seen[d] = seen.get(d, 0) + 1
        duplicates = replicated = 0
        if any(n > 1 for n in seen.values()):
            routes = (self._local_digest_routes()
                      if self._hot_ever else {})
            for d, n in seen.items():
                if n <= 1:
                    continue
                if routes.get(d) in self._hot_ever:
                    replicated += 1
                else:
                    duplicates += 1
        return {
            "members": per_member,
            "resident_digests": len(seen),
            "duplicate_digests": duplicates,
            "replicated_digests": replicated,
        }

    def _local_digest_routes(self) -> Dict[str, str]:
        """digest -> route over every local member's resident entries
        (accounting only — one locked snapshot per member)."""
        out: Dict[str, str] = {}
        for name in self.order:
            cache = getattr(getattr(self.members[name], "services",
                                    None), "raw_cache", None)
            if cache is None or not hasattr(cache, "snapshot_entries"):
                continue
            for entry in cache.snapshot_entries(0):
                digest = entry.get("digest")
                if digest:
                    out[digest] = entry.get("route")
        return out

    async def close(self) -> None:
        self._closed = True
        for task in self._lanes:
            task.cancel()
        if self._lanes:
            await asyncio.gather(*self._lanes, return_exceptions=True)
        self._lanes = []
        for task in list(self._putback_tasks):
            task.cancel()
        if self._putback_tasks:
            await asyncio.gather(*self._putback_tasks,
                                 return_exceptions=True)
        self._putback_tasks.clear()
        for queue in self._queues.values():
            while queue:
                work = queue.pop_raw()
                if not work.future.done():
                    work.future.set_exception(
                        RuntimeError("fleet router shut down"))


# ------------------------------------------------------ frontend handler

class FleetImageHandler:
    """The fleet-topology drop-in for ``ImageRegionHandler`` /
    ``SidecarImageHandler``: byte-cache-first (combined role — hits
    never shed), then fleet-wide single-flight, then fleet-aware
    admission, then the router.

    ``base_services`` (combined role) supplies the shared byte caches
    and the ACL memo; proxy fleets pass None — their sidecars own
    caches and ACL, exactly like the single-sidecar posture, and the
    single-flight key folds the caller's session in (see below).

    ``fallback`` (``server.degraded.DegradedCpuHandler``, proxy fleets
    only) keeps tiles servable when the WHOLE fleet is unreachable —
    same seam as ``SidecarImageHandler``; a live member's own verdict
    (shed, 4xx, deadline) never falls back."""

    def __init__(self, router: FleetRouter, single_flight=None,
                 admission=None, base_services=None, fallback=None):
        self.router = router
        self.single_flight = single_flight
        self.admission = admission
        self.s = base_services
        self.fallback = fallback

    async def _cached(self, ctx) -> Optional[bytes]:
        if self.s is None:
            return None
        from ..server.errors import NotFoundError
        from ..server.handler import check_can_read
        from ..services.cache import get_with_tier
        from ..utils import provenance, telemetry
        t0 = time.perf_counter()
        cached, tier_label = await get_with_tier(
            self.s.caches.image_region, ctx.cache_key)
        if cached is None:
            return None
        if not await check_can_read(self.s, "Image", ctx.image_id,
                                    ctx.omero_session_key):
            raise NotFoundError(f"Cannot find Image:{ctx.image_id}")
        telemetry.record_span("cache.hit", t0,
                              (time.perf_counter() - t0) * 1000.0)
        provenance.mark(ctx, tier=("disk" if tier_label == "disk"
                                   else "byte_cache"))
        return cached

    async def render_image_region(self, ctx) -> bytes:
        from ..server.errors import NotFoundError, OverloadedError
        from ..utils import telemetry, transient

        t0 = time.perf_counter()
        cached = await self._cached(ctx)
        if cached is not None:
            return cached
        if self.s is not None:
            # ACL gates PER CALLER before the shared render is
            # awaited (the render_identity_key contract): a follower
            # must never receive coalesced pixels its session cannot
            # read.
            from ..server.handler import check_can_read
            if not await check_can_read(self.s, "Image", ctx.image_id,
                                        ctx.omero_session_key):
                raise NotFoundError(
                    f"Cannot find Image:{ctx.image_id}")

        # Fleet-global byte tier: before fairness, single-flight and
        # admission (same footing as the byte-cache probe above —
        # already-rendered bytes never shed and never cost a token),
        # ask the shard AUTHORITY's byte tier when routing would land
        # this render elsewhere.  The serving sidecar ACL-gates the
        # fetch for this caller's session; combined role gated above.
        # getattr: drill/test routers are duck-typed dispatchers.
        peer_fetch = getattr(self.router, "fetch_peer_bytes", None)
        peer = (await peer_fetch(ctx)
                if peer_fetch is not None else None)
        if peer is not None:
            if self.s is not None:
                # Local write-back: the shared byte tier answers the
                # next repeat view without even the peer round-trip.
                await self.s.caches.image_region.set(ctx.cache_key,
                                                     peer)
            telemetry.record_span(
                "cache.peer", t0,
                (time.perf_counter() - t0) * 1000.0)
            return peer

        admission = self.admission
        # Per-session fairness runs PER CALLER, before coalescing —
        # like the combined role's ACL gate above: single-flight
        # shares the leader's outcome across sessions, so a hostile
        # session's over-budget 503 inside the producer would
        # propagate to coalesced followers from under-budget
        # sessions.  Every request pays its own token
        # (ctx.omero_session_key — the identity the session
        # middleware resolved and the proxy single-flight key folds)
        # and sheds only itself.
        debit = admission.admit_session(ctx) if admission is not None \
            else None
        if debit is not None:
            from ..utils import provenance
            provenance.mark(ctx, tokens=debit[1])

        async def produce() -> bytes:
            from ..server.pressure import shed_bulk_under_pressure
            shed_bulk_under_pressure(ctx)
            # GLOBAL admission: leader-only (a coalesced follower
            # adds no work, so only the pipeline run claims a slot).
            t_admit = admission.admit() if admission is not None \
                else None
            completed = False
            try:
                transient.check_deadline("fleet render")
                try:
                    data = await self.router.dispatch(ctx)
                except (ConnectionError, OverloadedError):
                    # Degraded mode: only when NO member is left to
                    # serve — a live member's shed/verdict stands.
                    if (self.fallback is None
                            or self.router.healthy_members()):
                        raise
                    telemetry.RESILIENCE.count_degraded_render()
                    from ..utils import provenance
                    provenance.mark(ctx, tier="degraded")
                    data = await \
                        self.fallback.render_image_region(ctx)
                completed = True
                return data
            finally:
                if admission is not None:
                    admission.release(t_admit, completed=completed)

        try:
            if self.single_flight is None:
                remaining = transient.remaining_ms()
                if remaining is None:
                    return await produce()
                try:
                    return await asyncio.wait_for(
                        produce(),
                        timeout=max(0.0, remaining) / 1000.0)
                except asyncio.TimeoutError:
                    raise transient.DeadlineExceededError(
                        "deadline exceeded awaiting fleet render")
            from ..server.settings import render_identity_key
            key = render_identity_key(ctx)
            if self.s is None:
                # Proxy fleet: this process CANNOT check ACL, so
                # identical renders coalesce per-session only — each
                # session's leader carries its own ctx to a sidecar
                # whose handler runs the full ACL gate.  (Combined
                # role checked above, so cross-session coalescing
                # stays.)
                key = f"{key}|{ctx.omero_session_key or ''}"
            data, coalesced = await self.single_flight.run(key,
                                                           produce)
        except OverloadedError:
            # Refused GLOBALLY (queue/deadline/pressure — directly or
            # via the coalesced-onto leader) after the fairness gate
            # debited tokens: refund them — the session never got the
            # render.
            if admission is not None:
                admission.refund_session(debit)
            raise
        if coalesced:
            telemetry.record_span(
                "dedup.coalesced", t0,
                (time.perf_counter() - t0) * 1000.0)
            from ..utils import provenance
            provenance.mark(ctx, coalesced=True)
        return data

    async def render_image_region_stream(self, ctx):
        """Chunked-response surface parity: the fleet answer is one
        body (each member's own first-tile-out settlement already
        pulled its latency in); the HTTP layer keeps its one uniform
        chunked path."""
        yield await self.render_image_region(ctx)


# ---------------------------------------------------------- construction

def partition_local_devices(n_members: int,
                            devices: Optional[Sequence] = None
                            ) -> List[list]:
    """Partition this process's devices across ``n_members`` local
    members — contiguous, deterministic, remainder to the earliest
    members (so member 0, the mesh/bulk lane, is never the short one).
    Fewer devices than members leaves the tail members unpinned
    (process default device) rather than oversubscribing one chip with
    two members' pins.  The in-process fleet's and the federation's
    local members are pinned from this one partition."""
    if n_members < 1:
        raise ValueError("partition needs >= 1 member")
    if devices is None:
        import jax
        devices = jax.local_devices()
    devices = list(devices)
    n_dev = len(devices)
    if n_dev == 0:
        return [[] for _ in range(n_members)]
    base, extra = divmod(n_dev, n_members)
    out: List[list] = []
    i = 0
    for m in range(n_members):
        take = base + (1 if m < extra else 0)
        out.append(devices[i:i + take])
        i += take
    return out


def build_local_members(config, base_services, n: int,
                        device_sets: Optional[Sequence] = None
                        ) -> List[LocalMember]:
    """N in-process fleet members over a shared host-side service
    stack: member 0 IS the base stack (its renderer may be the
    lockstep ``MeshRenderer``); members 1..N-1 get their own renderer
    + ``DeviceRawCache`` (their shard of HBM) and share everything
    host-side — pixel stores, byte caches, metadata, ACL memo, LUTs.

    One JAX process, one chip a member where ``device_sets`` gives one
    (the combined role passes :func:`partition_local_devices`): the
    member's renderer dispatches its groups there, its raw cache
    uploads there and its handler reads there (``pin_scope``), so a
    four-chip host serves one slide from four HBM shards.  A member
    without a device set (more members than devices) uses the
    process's default device.

    Member-level single-flight and admission are disabled on the extra
    members: both concerns live fleet-wide above the router."""
    from ..io.devicecache import DeviceRawCache
    from ..server.batcher import BatchingRenderer
    from ..server.handler import (ImageRegionHandler,
                                  ImageRegionServices, Renderer)
    from ..server.prewarm import stated_planes

    def devices_for(i: int) -> tuple:
        if not device_sets or i >= len(device_sets):
            return ()
        return tuple(device_sets[i] or ())

    cooldown = config.fleet.down_cooldown_s
    # The lockstep MeshRenderer is mesh-topology-bound: it already
    # spans its whole device set and must NEVER be pinned narrower
    # (parallel.serve marks it ``lockstep``) — member 0 then keeps
    # the process default dispatch.
    lockstep = getattr(base_services.renderer, "lockstep", False)
    base_services.pin_device = (devices_for(0)[0]
                                if devices_for(0) and not lockstep
                                else None)
    if base_services.pin_device is not None:
        if hasattr(base_services.renderer, "device"):
            base_services.renderer.device = base_services.pin_device
        if base_services.raw_cache is not None:
            base_services.raw_cache.device = base_services.pin_device
    members = [LocalMember(
        "m0",
        ImageRegionHandler(base_services), services=base_services,
        down_cooldown_s=cooldown, byte_cache_prechecked=True,
        devices=devices_for(0))]
    for i in range(1, n):
        if config.batcher.enabled and not config.parallel.enabled:
            renderer = BatchingRenderer(
                max_batch=config.batcher.max_batch,
                max_batch_limit=config.batcher.max_batch_limit,
                linger_ms=config.batcher.linger_ms,
                jpeg_engine=config.renderer.jpeg_engine,
                pipeline_depth=config.batcher.pipeline_depth,
                target_inflight=config.batcher.target_inflight,
                device_lanes=config.batcher.device_lanes,
                planes=stated_planes(config.renderer.prewarm))
            renderer.first_tile_out = config.wire.streaming
        else:
            renderer = Renderer(jpeg_engine=config.renderer.jpeg_engine)
        if devices_for(i):
            renderer.device = devices_for(i)[0]
        raw_cache = (DeviceRawCache(
            config.raw_cache.max_bytes,
            digest_index=config.raw_cache.digest_dedup)
            if config.raw_cache.enabled else None)
        if raw_cache is not None and devices_for(i):
            raw_cache.device = devices_for(i)[0]
        services = ImageRegionServices(
            pixels_service=base_services.pixels_service,
            metadata=base_services.metadata,
            caches=base_services.caches,
            can_read_memo=base_services.can_read_memo,
            renderer=renderer,
            lut_provider=base_services.lut_provider,
            max_tile_length=base_services.max_tile_length,
            raw_cache=raw_cache,
            cpu_fallback_max_px=base_services.cpu_fallback_max_px,
            pin_device=(devices_for(i)[0] if devices_for(i)
                        else None),
        )
        members.append(LocalMember(
            f"m{i}",
            ImageRegionHandler(services), services=services,
            down_cooldown_s=cooldown, byte_cache_prechecked=True,
            devices=devices_for(i)))
    return members
