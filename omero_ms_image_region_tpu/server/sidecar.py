"""Render sidecar: the frontend/compute process boundary.

The reference isolates HTTP handling from rendering across the Vert.x
event bus — the HTTP verticle serializes the request ctx onto the
``omero.render_image_region`` address and worker verticles (possibly in
other JVMs) decode and render (``ImageRegionVerticle.java:128-136``,
``ImageRegionMicroserviceVerticle.java:294-352``).  Here the bus is a
unix-domain socket — or, given a ``host:port`` address, TCP, so
frontends can live on different hosts than the device process (the
cross-host half of the clustered bus) — carrying length-prefixed
JSON+binary frames: N frontend processes (HTTP parse, session
resolution, status mapping) share ONE sidecar process that owns the
device, the batcher, the pixel stores and the caches.  A frontend crash leaves the sidecar serving — the device
never recompiles because an HTTP process died — and frontends restart
in milliseconds because they import no device stack at all.

Wire format, little-endian (the ctx payloads are the same JSON the
in-process path round-trips through ``ImageRegionCtx.to_json`` — the
reference's Jackson bus encoding, ``ImageRegionCtxTest.java:205-208``):

  frame:    u32 frame_len | payload
  request:  u32 header_len | header JSON {id, op, ctx, v} | body
  response: u32 header_len | header JSON {id, status, error?} | body
            (the Content-Type stays a frontend concern — both sides
            derive it from the ctx, exactly like the reference's HTTP
            verticle does after a bus reply,
            ``ImageRegionMicroserviceVerticle.java:326-345``)

Responses are multiplexed by ``id`` and may arrive out of order, so one
connection carries a frontend's full concurrency.

Protocol v2 adds the digest-first plane ops backing the device-resident
plane cache (``io.devicecache``): ``plane_probe`` ({digest}) answers
whether that content is already HBM-resident, and ``plane_put``
({digest, dtype, shape} + raw bytes body) stages a plane into the
device cache.  A client ALWAYS probes before shipping
(:meth:`SidecarClient.stage_plane`), so a plane already on the device —
pushed by any frontend/ingester, or read by the sidecar itself — never
crosses the wire twice.  v1 peers reject the new ops with status 400
and everything else is unchanged, so mixed-version deployments degrade
to always-upload, never to an error surface.

Fault-tolerance fields (all optional, all tolerated absent, so they
are not a wire-version bump): a request may carry ``deadline_ms`` —
the requester's REMAINING budget, re-anchored on the server's own
clock (absolute times never cross the wire); a spent budget answers
status 504 without rendering.  Responses may carry status 503
(admission shed) with ``retry_after`` seconds, and 504 (deadline).
Client-side policy — op-aware retry with capped backoff + jitter and a
consecutive-failure circuit breaker — lives in
:class:`SidecarClient`/:mod:`..utils.transient`; ``plane_put`` is
never auto-retried.

Protocol v3 is the streaming zero-copy wire (``WireConfig`` knobs,
DEPLOY.md "Wire transport"), three independent legs that each degrade
to the v2 behavior against an older peer:

* **Scatter-gather frame coalescing** — every connection's outbound
  frames queue in a :class:`FrameWriter` and flush as ONE vectored
  ``writer.writelines`` + ONE ``drain()`` (gather, then write), so
  N multiplexed frames cost one syscall and one round-trip instead
  of N.  Sender-local: the byte stream is identical, so no
  negotiation and no version gate.
* **Progressive chunk streaming** — a request carrying ``stream: 1``
  may be answered as ordered chunk frames ``{id, seq}`` + body
  followed by a final ``{id, status, fin: true}`` frame (which still
  carries the spans/costs exports).  Concatenated chunks are
  byte-identical to the v2 single-frame body.  A v2 server ignores the
  unknown ``stream`` key and answers one frame; the client treats that
  as a single-chunk stream — per-request degradation, no handshake.
Fleet routing (``parallel.fleet``) adds one optional request key, not
a version bump: ``adopt: 0`` on an ``image`` op marks a STOLEN render
— the server renders from source bytes without inserting into its HBM
raw cache, so work stealing never fragments the fleet's shard map.
Absent (every non-fleet client), behavior is unchanged.

* **Same-host shared-memory ring** — negotiated by a ``hello`` op at
  connection setup: the client creates BOTH directions' ring segments
  (``server.shmring``) and offers their names; a server that attaches
  answers ``ring: true`` and MB-scale bodies (``plane_put`` uploads,
  rendered tiles) then ride the ring with only a tiny
  ``ring: [offset, length]`` descriptor on the socket.  A v2 server
  answers the unknown ``hello`` with 400 — the client destroys the
  segments and everything runs on the socket; ring exhaustion falls
  back per-body.
"""

from __future__ import annotations

import asyncio
import collections
import json
import logging
import os
import struct
import time
from typing import Deque, Dict, List, Optional, Tuple

from ..utils import telemetry
from . import sentinel as sentinel_mod
from .ctx import BadRequestError, ImageRegionCtx, ShapeMaskCtx
from .errors import NotFoundError
from .shmring import RingError, ShmRing

logger = logging.getLogger(__name__)

_MAX_FRAME = 256 * 1024 * 1024
# Wire protocol generation: 2 = the digest-first plane ops
# (plane_probe / plane_put); 3 = the streaming zero-copy wire (hello
# negotiation, chunked responses, shm-ring descriptors).  Sent in
# every request header; servers tolerate its absence and every v3
# feature degrades per-feature against a v2 peer.
WIRE_VERSION = 3


def parse_address(addr: str):
    """``host:port`` / ``[v6]:port`` -> ("tcp", host, port); anything
    else is a unix socket path.  TCP lets frontends live on DIFFERENT
    hosts than the device process — the cross-host half of the
    reference's clustered event bus."""
    if addr.startswith("["):                    # "[::1]:8476"
        host, sep, port = addr.partition("]:")
        if sep and port.isdigit():
            return ("tcp", host[1:], int(port))
        return ("unix", addr, None)
    if "/" not in addr and addr.count(":") == 1:
        host, _, port = addr.partition(":")
        if port.isdigit():
            return ("tcp", host or "127.0.0.1", int(port))
    return ("unix", addr, None)


def _set_nodelay(writer: asyncio.StreamWriter) -> None:
    """Small request/response frames must not sit behind Nagle's
    algorithm on the cross-host hot path."""
    import socket as pysocket

    sock = writer.get_extra_info("socket")
    if sock is not None and sock.family in (pysocket.AF_INET,
                                            pysocket.AF_INET6):
        try:
            sock.setsockopt(pysocket.IPPROTO_TCP,
                            pysocket.TCP_NODELAY, 1)
        except OSError:
            pass


async def open_sidecar_connection(addr: str):
    kind, host, port = parse_address(addr)
    if kind == "tcp":
        reader, writer = await asyncio.open_connection(host, port)
        _set_nodelay(writer)
        return reader, writer
    return await asyncio.open_unix_connection(host)


def _pack(header: dict, body: bytes = b"") -> bytes:
    h = json.dumps(header).encode()
    return (struct.pack("<II", 4 + len(h) + len(body), len(h))
            + h + bytes(body))


def _pack_prefix(header: dict, body_len: int) -> bytes:
    """Frame prefix (lengths + header JSON) WITHOUT the body: large
    bodies (plane uploads) are written as their own buffer instead of
    being copied into one concatenated frame — an 8 MB plane paid an
    extra 8 MB memcpy per upload through :func:`_pack`."""
    h = json.dumps(header).encode()
    return struct.pack("<II", 4 + len(h) + body_len, len(h)) + h


async def _read_frame(reader: asyncio.StreamReader):
    raw_len = await reader.readexactly(4)
    (frame_len,) = struct.unpack("<I", raw_len)
    if frame_len > _MAX_FRAME:
        raise ValueError(f"frame of {frame_len} bytes exceeds limit")
    payload = await reader.readexactly(frame_len)
    (header_len,) = struct.unpack("<I", payload[:4])
    header = json.loads(payload[4:4 + header_len])
    return header, payload[4 + header_len:]


def _ring_body(ring: Optional[ShmRing], header: dict, body: bytes):
    """Resolve a frame's body: a ``ring: [off, len]`` descriptor reads
    (and releases) the shared-memory ring; anything else is the socket
    body as-is.  Raises :class:`shmring.RingError` on a descriptor with
    no negotiated ring or one outside the live window — hostile input
    degrades to a clean protocol error, never an out-of-window read."""
    rd = header.get("ring")
    if rd is None:
        return body
    if ring is None:
        raise RingError("ring descriptor on a connection with no "
                        "negotiated ring")
    if not isinstance(rd, (list, tuple)) or len(rd) != 2:
        raise RingError(f"malformed ring descriptor {rd!r}")
    return ring.read_release(rd[0], rd[1])


class FrameWriter:
    """Per-connection scatter-gather frame writer (protocol v3 leg 1).

    Frames enqueue here and ONE flusher task hands the whole backlog to
    ``writer.writelines`` as a list of buffers with a single ``drain()``
    per flush — N small frames cost one syscall and one round-trip
    instead of N (gather, then write).  This also retires the old
    ``respond()`` hazard: no lock is held across ``drain()`` anymore, so a
    slow-reading peer backpressures only the flusher — concurrent
    responders keep enqueueing and their frames coalesce into the next
    flush instead of serializing behind the stalled drain.

    When a same-host ring is negotiated (``self.ring``), bodies of at
    least ``ring_min_bytes`` ride it and the frame shrinks to a
    descriptor; ring exhaustion falls back to a socket body per-frame.
    Ring allocations happen at ENQUEUE time on the event loop, so
    descriptor order on the socket equals allocation order — the
    consumer's in-order release needs nothing more.
    """

    def __init__(self, writer: asyncio.StreamWriter,
                 max_frames: int = 64, max_bytes: int = 1 << 20):
        self.writer = writer
        self.max_frames = max(1, int(max_frames))
        self.max_bytes = max(4096, int(max_bytes))
        self.ring: Optional[ShmRing] = None
        self.ring_min_bytes = 4096
        self._pending: Deque[tuple] = collections.deque()
        self._wake = asyncio.Event()
        self._dead: Optional[BaseException] = None
        self._task: Optional[asyncio.Task] = \
            asyncio.create_task(self._flush_loop())

    def _buffers(self, header: dict, body) -> list:
        n = len(body) if body else 0
        if self.ring is not None and n >= self.ring_min_bytes:
            off = self.ring.alloc_write(body)
            if off is not None:
                header = dict(header)
                header["ring"] = [off, n]
                telemetry.WIRE.count_ring(n, hit=True)
                return [_pack_prefix(header, 0)]
            telemetry.WIRE.count_ring(n, hit=False)
        prefix = _pack_prefix(header, n)
        if not n:
            return [prefix]
        # No concatenation: MB-scale bodies (plane uploads, tile
        # chunks) go to the transport as their own buffer.
        return [prefix, body if isinstance(body, (bytes, memoryview))
                else memoryview(body)]

    async def send(self, header: dict, body=b"") -> None:
        """Enqueue one frame and wait until its flush drained (so a
        sender sees the same ConnectionError surface the direct write
        had).  Frames enqueued while a flush is in flight coalesce
        into the next one."""
        if self._dead is not None:
            raise ConnectionError(str(self._dead)
                                  or "wire writer closed")
        fut = asyncio.get_running_loop().create_future()
        self._pending.append((self._buffers(header, body), fut))
        self._wake.set()
        await fut

    async def _flush_loop(self) -> None:
        try:
            while True:
                await self._wake.wait()
                self._wake.clear()
                while self._pending:
                    batch = []
                    nbytes = 0
                    while (self._pending
                           and len(batch) < self.max_frames
                           and nbytes < self.max_bytes):
                        bufs, fut = self._pending.popleft()
                        batch.append((bufs, fut))
                        nbytes += sum(len(b) for b in bufs)
                    try:
                        self.writer.writelines(
                            [b for bufs, _ in batch for b in bufs])
                        await self.writer.drain()
                    except asyncio.CancelledError:
                        self._fail(ConnectionError(
                            "wire writer closed"), batch)
                        raise
                    except Exception as e:
                        # ConnectionError/OSError is the expected
                        # class; anything else still must not strand
                        # senders parked on their flush futures.
                        self._fail(e, batch)
                        return
                    telemetry.WIRE.observe_flush(len(batch), nbytes)
                    for _, fut in batch:
                        if not fut.done():
                            fut.set_result(None)
        except asyncio.CancelledError:
            self._fail(ConnectionError("wire writer closed"), ())
            raise

    def _fail(self, exc: BaseException, batch) -> None:
        self._dead = exc
        for _, fut in list(batch) + list(self._pending):
            if not fut.done():
                fut.set_exception(exc)
        self._pending.clear()

    def close(self) -> None:
        """Stop the flusher and fail queued senders; idempotent."""
        if self._dead is None:
            self._dead = ConnectionError("wire writer closed")
        task, self._task = self._task, None
        if task is not None:
            task.cancel()


# ---------------------------------------------------------------- server

def _byte_stack(image_handler, header: dict):
    """Resolve the byte-cache chain a byte op addresses.  The default
    (and the only tier a pre-mask-federation peer ever sends) is the
    render byte tier; ``tier: "mask"`` addresses the shape-mask PNG
    chain — mask keys derive from ``ShapeMaskCtx.cache_key()`` and can
    never collide with render identities, so a legacy sidecar that
    ignores the tier answers a harmless miss, never wrong bytes."""
    handler_services = getattr(image_handler, "s", None)
    caches = getattr(handler_services, "caches", None)
    name = ("shape_mask" if str(header.get("tier") or "region")
            == "mask" else "image_region")
    return handler_services, getattr(caches, name, None)


async def _plane_put(image_handler, header: dict,
                     req_body: bytes) -> bytes:
    """Stage a wire-pushed plane into the device cache (protocol v2).

    The claimed digest is VERIFIED against the received bytes before
    anything reaches the cache — the socket is unauthenticated (private
    interface only), and a digest/content mismatch must poison nothing:
    it is a 400, not a cache entry.
    """
    import numpy as np

    cache = getattr(getattr(image_handler, "s", None), "raw_cache",
                    None)
    if cache is None or not getattr(cache, "digest_index", False):
        raise BadRequestError(
            "device plane cache is disabled on this sidecar "
            "(raw-cache.enabled / raw-cache.digest-dedup)")
    digest = str(header.get("digest") or "")
    try:
        dtype = np.dtype(str(header["dtype"]))
        shape = tuple(int(s) for s in header["shape"])
        if dtype.kind not in "uif":
            # Pixel storage is numeric only; anything else ("O",
            # datetime64, ...) would blow up in frombuffer/device_put
            # as a 500 instead of this 400.
            raise ValueError(f"non-numeric dtype {dtype}")
    except (KeyError, TypeError, ValueError) as e:
        raise BadRequestError(f"malformed plane_put header: {e}")
    if not shape or any(s <= 0 for s in shape):
        # Checked BEFORE np.prod: an even count of negative dims would
        # multiply out positive and sail past the size check into a
        # reshape ValueError (a 500, not the contract's 400).
        raise BadRequestError(f"plane_put shape {list(shape)} must be "
                              f"all-positive")
    expected = int(np.prod(shape)) * dtype.itemsize
    if expected != len(req_body):
        raise BadRequestError(
            f"plane_put body is {len(req_body)} bytes, shape/dtype "
            f"say {expected}")
    arr = np.frombuffer(req_body, dtype).reshape(shape)

    def stage_verified():
        from ..io.devicecache import plane_digest
        from ..io.staging import stage_deduped
        actual = plane_digest(arr)
        if digest and digest != actual:
            raise BadRequestError(
                f"plane_put digest mismatch: claimed {digest}, "
                f"content is {actual}")
        _, _, was_resident = stage_deduped(arr, cache, digest=actual)
        return actual, was_resident

    # Digesting + packing + the device transfer are CPU/link work;
    # keep the event loop (and the other multiplexed renders) free.
    actual, was_resident = await asyncio.to_thread(stage_verified)
    return json.dumps({"digest": actual,
                       "resident": was_resident}).encode()


async def _shard_transfer(image_handler, header: dict,
                          req_body: bytes) -> bytes:
    """Stage a cross-host drain handoff plane into THIS member's HBM
    (``parallel.federation``): like :func:`_plane_put` — unauthenticated
    socket, so the digest is VERIFIED before anything reaches the
    cache — but the entry carries its full REGION identity and routing
    key, so the plane lands restageable and drain-able exactly as if
    this member had read it from its own store."""
    import numpy as np

    from ..io.devicecache import entry_region_key, plane_digest

    cache = getattr(getattr(image_handler, "s", None), "raw_cache",
                    None)
    if cache is None:
        raise BadRequestError(
            "device plane cache is disabled on this sidecar "
            "(raw-cache.enabled)")
    entry = header.get("entry")
    if not isinstance(entry, dict):
        raise BadRequestError("shard_transfer requires an entry doc")
    digest = str(entry.get("digest") or "")
    try:
        key = entry_region_key(entry)
        dtype = np.dtype(str(entry["dtype"]))
        shape = tuple(int(s) for s in entry["shape"])
        if dtype.kind not in "uif":
            raise ValueError(f"non-numeric dtype {dtype}")
    except (KeyError, TypeError, ValueError) as e:
        raise BadRequestError(f"malformed shard_transfer entry: {e}")
    if not shape or any(s <= 0 for s in shape):
        raise BadRequestError(f"shard_transfer shape {list(shape)} "
                              f"must be all-positive")
    expected = int(np.prod(shape)) * dtype.itemsize
    if expected != len(req_body):
        raise BadRequestError(
            f"shard_transfer body is {len(req_body)} bytes, "
            f"shape/dtype say {expected}")
    arr = np.frombuffer(req_body, dtype).reshape(shape)
    route = entry.get("route")

    def stage_verified() -> str:
        actual = plane_digest(arr)
        if digest and digest != actual:
            raise BadRequestError(
                f"shard_transfer digest mismatch: claimed {digest}, "
                f"content is {actual}")
        cache.get_or_load(key, lambda: arr, digest=actual,
                          route_key=(str(route) if route else None))
        return actual

    t_anchor = time.perf_counter()
    actual = await asyncio.to_thread(stage_verified)
    stage_ms = (time.perf_counter() - t_anchor) * 1000.0
    telemetry.FEDERATION.count_transfer(len(req_body))
    # Anchor fields: OUR perf-clock instant the stage started, its
    # duration, and our federation host identity — the shipping side
    # grafts the stage as a clock-anchored child span in ITS trace
    # (``federation.anchor_remote_time``).  Old callers ignore them.
    from ..parallel import federation
    return json.dumps({"staged": True, "digest": actual,
                       "t_anchor": t_anchor,
                       "ms": round(stage_ms, 3),
                       "host": federation.self_host()}).encode()


def _server_hello(header: dict, frames: FrameWriter, wire) -> tuple:
    """Negotiate the ``hello`` op server-side: attach the client's ring
    segments when offered (and enabled), answer the feature document.
    Returns ``(body, recv_ring, attached)`` — ``recv_ring`` resolves
    request-body descriptors, ``attached`` lists rings to close at
    teardown.  ANY attach failure degrades to ``ring: false`` (socket
    bodies), never an error surface."""
    ring_ok = False
    recv_ring = None
    attached: list = []
    rings = header.get("rings")
    ring_enabled = wire is None or wire.ring_bytes > 0
    if isinstance(rings, dict) and ring_enabled:
        try:
            c2s_spec, s2c_spec = rings["c2s"], rings["s2c"]
            c2s = ShmRing.attach(str(c2s_spec["name"]),
                                 int(c2s_spec["size"]))
            attached.append(c2s)
            s2c = ShmRing.attach(str(s2c_spec["name"]),
                                 int(s2c_spec["size"]))
            attached.append(s2c)
            recv_ring = c2s
            frames.ring = s2c
            if wire is not None:
                frames.ring_min_bytes = wire.ring_min_body_bytes
            ring_ok = True
        except Exception as e:
            # Cross-host TCP peer, /dev/shm unavailable, size
            # mismatch, hostile hello: all the same degrade.
            for r in attached:
                r.close()
            attached = []
            recv_ring = None
            frames.ring = None
            logger.info("shm ring negotiation failed (%s); "
                        "socket bodies", e)
    member = header.get("member")
    if isinstance(member, str) and member:
        # The frontend's fleet name for THIS sidecar (RemoteMember
        # stamps its client): from here on the process's own flight
        # events — and its SIGTERM/breach dumps — carry the member
        # identity, so a raw per-process ring stays attributable
        # without the frontend's merge.  Positional per config, so
        # agreeing frontends agree on the name; re-stamped per hello.
        telemetry.FLIGHT.set_member(member[:32])
    telemetry.WIRE.count_negotiation(ring=ring_ok)
    # ``clock``: this process's monotonic clock at hello time.  The
    # client derives a per-connection offset from it, so exported span
    # anchors (``t_anchor`` on responses) map onto the CLIENT's
    # timeline and a multi-member waterfall stays causally ordered —
    # re-anchored on every reconnect, so clock drift is bounded by a
    # connection's life, never accumulated.  Extra key: v2 clients
    # ignore it (no version bump).
    body = json.dumps({"v": WIRE_VERSION, "ring": ring_ok,
                       "clock": time.perf_counter()}).encode()
    return body, recv_ring, attached


async def _serve_connection(image_handler, mask_handler, reader, writer,
                            status_fn=None, profile_fn=None,
                            warmstate_fn=None, wire=None):
    """One frontend connection: demux requests, run each as a task.

    ``status_fn`` answers the ``ping`` op (readiness state for the
    frontend's ``/readyz``); None keeps a bare liveness answer.
    ``profile_fn(ms)`` serves the ``profile`` op (on-demand
    ``jax.profiler`` capture in THIS device-owning process); None
    rejects the op.  ``warmstate_fn(snapshot)`` serves the
    ``warmstate`` op — persistence status (+ on-demand snapshot) from
    the process that owns the warm state; None rejects the op.
    ``wire`` is the ``WireConfig`` (None = defaults): coalescing
    bounds, ring acceptance, chunk sizing."""
    frames = FrameWriter(
        writer,
        max_frames=(wire.coalesce_max_frames if wire is not None
                    else 64),
        max_bytes=(wire.coalesce_max_bytes if wire is not None
                   else 1 << 20))
    chunk_max = (wire.chunk_max_bytes if wire is not None
                 else 256 * 1024)
    tasks = set()
    # The client's c2s ring (attached at hello) resolving request-body
    # descriptors; list-wrapped so the read loop sees the swap.
    ring_state: dict = {"recv": None, "attached": []}

    async def respond(header: dict, body: bytes = b"") -> None:
        # Enqueue-and-flush through the FrameWriter: the old form held
        # a write lock across ``drain()``, so ONE slow-reading frontend
        # serialized every response on the connection behind its
        # stalled socket; now concurrent responders coalesce into the
        # next vectored flush instead.
        await frames.send(header, body)

    async def handle(header: dict, req_body: bytes = b"") -> None:
        from ..utils import faultinject, transient
        from .errors import OverloadedError

        rid = header.get("id")
        spans = None
        costs = None
        anchor = None
        prov = None
        quality_capped = False
        inj = faultinject.active()
        if inj is not None and inj.sidecar_should_die():
            # Supervision drill: die MID-call, the way a real crash
            # does — the peer sees the connection drop with this
            # request unanswered, and the supervisor must bring the
            # process back without operator action.
            logger.error("fault injection: sidecar self-kill "
                         "(die-after-requests)")
            os._exit(23)
        # Re-anchor the requester's remaining budget on this process's
        # clock; an already-spent budget answers 504 without rendering.
        budget = header.get("deadline_ms")
        try:
            budget = float(budget) if budget is not None else None
        except (TypeError, ValueError):
            budget = None
        # Per-task set, no scope: this handler task's context dies
        # with it, and a generator scope would be GC'd cross-context
        # when teardown cancels in-flight handlers.
        transient.set_task_deadline(budget)
        try:
            op = header["op"]
            transient.check_deadline(f"sidecar {op}")
            if op == "image" or op == "mask":
                # Join the frontend's trace: device-side spans (render,
                # wire fetch, encode) carry the requester's trace id,
                # so the request yields ONE waterfall across processes.
                # In a real split the trace is unknown here, so the
                # spans recorded below are exported on the response and
                # the local orphan entry is retired; an in-process
                # sidecar (tests) shares the frontend's live trace and
                # must neither export (duplicates) nor finish it.
                trace_id = header.get("trace")
                shared = bool(trace_id
                              and telemetry.TRACES.is_active(trace_id))
                ctx = None
                try:
                    with telemetry.adopt_trace(trace_id):
                        import time as _time
                        t0 = _time.perf_counter()
                        if op == "image":
                            ctx = ImageRegionCtx.from_json(
                                header["ctx"])
                            if header.get("adopt") in (0, False):
                                # Fleet work stealing: a stolen render
                                # reads from source bytes and must not
                                # adopt HBM shard ownership here
                                # (parallel.fleet).  Only the explicit
                                # header opts out, so v3-and-earlier
                                # peers are untouched.
                                body = await \
                                    image_handler.render_image_region(
                                        ctx, adopt_cache=False)
                            else:
                                body = await \
                                    image_handler.render_image_region(
                                        ctx)
                        else:
                            ctx = ShapeMaskCtx.from_json(header["ctx"])
                            body = await \
                                mask_handler.render_shape_mask(ctx)
                        _elapsed_ms = \
                            (_time.perf_counter() - t0) * 1000.0
                        telemetry.record_span(
                            "sidecar.render", t0, _elapsed_ms, op=op)
                        # Perf-sentinel sketch insert: the sidecar
                        # watches its OWN render latency (the frontend
                        # watches wire-inclusive time) — one probe
                        # when the sentinel is off.
                        _sentinel = sentinel_mod.active()
                        if _sentinel is not None:
                            _sentinel.observe(
                                "render_image_region"
                                if op == "image" else "shape_mask",
                                len(body), _elapsed_ms,
                                trace_id)
                        # Brownout quality cap: exported on the reply
                        # so the FRONTEND's byte-tier write-backs
                        # (fleet peer put-back) can honor the
                        # never-cache-degraded-bytes contract too.
                        quality_capped = bool(getattr(
                            ctx, "_pressure_quality_capped", False))
                finally:
                    # Error paths too: retire the orphan and export
                    # whatever was recorded, so a failed request still
                    # shows its device-side spans (and its cost
                    # ledger) on the frontend waterfall instead of
                    # leaking a registry entry.
                    if trace_id and not shared:
                        trace = telemetry.TRACES.finish(trace_id)
                        if trace is not None:
                            spans = trace.export_spans()
                            costs = trace.export_costs()
                            # Span anchor on THIS process's monotonic
                            # clock: with the hello clock offset the
                            # client maps the spans onto its own
                            # timeline instead of guessing from send
                            # time (the stitched-waterfall contract).
                            anchor = trace.t0
                    if ctx is not None:
                        # Provenance marks made in this process (byte
                        # tier / HBM / cold) ride the reply so the
                        # frontend's record names what REALLY served.
                        from ..utils import provenance
                        prov = provenance.marks(ctx) or None
            elif op == "metrics":
                # Device-process series (spans, caches, batcher gauges,
                # compile events, link health); frontends merge these
                # into their /metrics exposition.  No # TYPE lines here
                # — the frontend's finalizer owns the headers.
                from ..utils.stopwatch import span_lines
                lines = span_lines(',process="sidecar"')
                handler_services = getattr(image_handler, "s", None)
                if handler_services is not None:
                    lines += telemetry.device_metric_lines(
                        handler_services, ',process="sidecar"')
                # Device-side resilience counters (admission sheds,
                # queue deadline cancellations) — the breaker gauge is
                # frontend-local and stays out of this copy.
                lines += telemetry.resilience_metric_lines(
                    extra_labels=',process="sidecar"')
                # This side of the wire: server-side flush coalescing,
                # ring traffic, chunk streams.
                lines += telemetry.wire_metric_lines(
                    ',process="sidecar"')
                # Self-preservation families: the governor/watchdog
                # run in this process too when enabled.
                lines += telemetry.robustness_metric_lines(
                    ',process="sidecar"')
                # This process's own perf-sentinel view (verdict,
                # live-vs-baseline p99) — the frontend's merge makes
                # the fleet drift picture.
                lines += telemetry.SENTINEL.metric_lines(
                    ',process="sidecar"')
                body = ("\n".join(lines) + "\n").encode()
            elif op == "plane_probe":
                # Digest-first residency probe: the peer only ships the
                # plane bytes when this answers resident=false.  The
                # batched form (``digests``: list) answers N planes in
                # ONE wire round-trip — the per-plane probe RTT is the
                # dominant tax on bulk staging across hosts (a full
                # round trip each, against ~ms of digesting).
                cache = getattr(getattr(image_handler, "s", None),
                                "raw_cache", None)
                enabled = bool(cache is not None
                               and getattr(cache, "digest_index",
                                           False))
                doc = {
                    # enabled=false tells the client to SKIP the put
                    # (nothing to push into), not to error.
                    "enabled": enabled,
                }
                digests = header.get("digests")
                if isinstance(digests, list):
                    doc["resident"] = [
                        bool(enabled and d
                             and cache.resident_digest(str(d)))
                        for d in digests]
                else:
                    digest = str(header.get("digest") or "")
                    doc["resident"] = bool(
                        enabled and digest
                        and cache.resident_digest(digest))
                body = json.dumps(doc).encode()
            elif op == "plane_put":
                body = await _plane_put(image_handler, header, req_body)
            elif op == "byte_probe":
                # Fleet-global byte tier, step 1: does THIS member's
                # byte-cache chain (memory -> disk -> redis) hold the
                # rendered bytes for these render identities?  Batched
                # like plane_probe — N keys, one wire round-trip.
                # Presence only: no ACL (the key derives from request
                # params, never pixels), no bytes move.
                handler_services, stack = _byte_stack(image_handler,
                                                      header)
                enabled = bool(stack is not None
                               and getattr(stack, "enabled", False))
                keys = header.get("keys")
                if not isinstance(keys, list):
                    keys = [header.get("key")]
                present = []
                for k in keys:
                    v = (await stack.get(str(k))
                         if enabled and k else None)
                    present.append(v is not None)
                body = json.dumps({"enabled": enabled,
                                   "present": present}).encode()
            elif op == "byte_fetch":
                # Step 2: the bytes themselves — ONLY after this
                # process's own ACL gate passes for the caller's
                # session (the exact contract of the `image` op: bytes
                # never leave a sidecar a session could not read).
                # Misses answer 404; MB-scale bodies ride the shm ring
                # like any response body.
                handler_services, stack = _byte_stack(image_handler,
                                                      header)
                key = str(header.get("key") or "")
                data = (await stack.get(key)
                        if stack is not None and key else None)
                if data is None:
                    raise NotFoundError(f"byte tier miss for {key!r}")
                image_id = header.get("image_id")
                if image_id is not None \
                        and handler_services is not None:
                    # The ACL object type follows the tier: mask
                    # fetches gate on the Mask's own readability (the
                    # exact check ShapeMaskHandler applies locally).
                    obj = str(header.get("obj") or "Image")
                    if obj not in ("Image", "Mask"):
                        raise BadRequestError(
                            f"byte_fetch obj {obj!r} unsupported")
                    from .handler import check_can_read
                    if not await check_can_read(
                            handler_services, obj, int(image_id),
                            header.get("session")):
                        raise NotFoundError(
                            f"Cannot find {obj}:{image_id}")
                body = bytes(data)
            elif op == "byte_put":
                # Peer write-back (a thief's render landing on its
                # shard authority).  State-changing like plane_put:
                # NEVER auto-retried by the client, and the body is
                # digest-verified so a corrupt frame can never poison
                # the byte tier under a healthy key.
                handler_services, stack = _byte_stack(image_handler,
                                                      header)
                key = str(header.get("key") or "")
                if not key:
                    raise BadRequestError("byte_put requires a key")
                value = bytes(req_body)
                claimed = str(header.get("digest") or "")
                if claimed:
                    import hashlib as _hashlib
                    actual = _hashlib.blake2b(
                        value, digest_size=16).hexdigest()
                    if actual != claimed:
                        raise BadRequestError(
                            f"byte_put digest mismatch: claimed "
                            f"{claimed}, body is {actual}")
                from ..parallel import federation as _fed
                fenced = not _fed.quorum_allow("write_authority")
                stored = False
                if not fenced and stack is not None \
                        and getattr(stack, "enabled", False):
                    await stack.set(key, value)
                    stored = True
                # A fenced minority refuses byte-tier write authority
                # (counted) but answers gracefully — the sender's
                # put is fire-and-forget best-effort by contract.
                doc = {"stored": stored}
                if fenced:
                    doc["fenced"] = True
                body = json.dumps(doc).encode()
            elif op == "shard_manifest":
                # Rolling drain, step 1 (remote members): this
                # member's HBM shard as restageable region entries —
                # the pre-stage hint list its ring successor warms
                # from (parallel.fleet.RemoteMember.shard_manifest).
                cache = getattr(getattr(image_handler, "s", None),
                                "raw_cache", None)
                entries = (cache.snapshot_entries(
                    int(header.get("limit", 0) or 0))
                    if cache is not None
                    and hasattr(cache, "snapshot_entries") else [])
                body = json.dumps({"entries": entries}).encode()
            elif op == "prestage":
                # Rolling drain, step 2 (remote members): stage the
                # handed-over shard manifest into THIS member's HBM so
                # the drained member's planes arrive WARM instead of
                # cold-missing.  Bounded, best-effort, off-loop.
                from ..services.warmstate import restage_plane_entry
                handler_services = getattr(image_handler, "s", None)
                cache = getattr(handler_services, "raw_cache", None)
                pixels = getattr(handler_services, "pixels_service",
                                 None)
                entries = header.get("entries") or []
                if not isinstance(entries, list):
                    raise BadRequestError("prestage entries must be "
                                          "a list")

                def _prestage() -> int:
                    staged = 0
                    for entry in entries:
                        try:
                            if restage_plane_entry(cache, pixels,
                                                   entry):
                                staged += 1
                        except Exception:
                            continue   # best-effort: a bad entry is
                            # a cold miss later, never a failed drain
                    return staged

                from ..parallel import federation as _fed
                if not _fed.quorum_allow("transfer"):
                    # Fenced: inbound staging is shard adoption by
                    # another name — refused (counted), gracefully.
                    body = json.dumps({"staged": 0,
                                       "fenced": True}).encode()
                else:
                    staged = (await asyncio.to_thread(_prestage)
                              if cache is not None
                              and pixels is not None else 0)
                    body = json.dumps({"staged": staged}).encode()
            elif op == "manifest_hello":
                # Cross-host federation, join time: compare the
                # joiner's fleet manifest against this process's
                # installed one (digest agreement, epoch-ordered
                # adoption) and answer OUR ring owner for any probe
                # keys — the cross-process golden-assignment check.
                from ..parallel import federation
                body = json.dumps(
                    federation.handle_manifest_hello(header)).encode()
            elif op == "member_gossip":
                # Membership gossip: merge the sender's health view
                # (newest observation per member wins), answer ours +
                # the manifest identity so drift surfaces.
                from ..parallel import federation
                body = json.dumps(
                    federation.handle_member_gossip(header)).encode()
            elif op == "shard_transfer":
                # Cross-host drain handoff: warm HBM plane BYTES from
                # another host's draining member, staged here with
                # their full region + routing identity.  State-changing
                # like plane_put: digest-verified, never blind-retried.
                from ..parallel import federation as _fed
                if not _fed.quorum_allow("transfer"):
                    # Fenced minority: accepting another host's shard
                    # bytes IS the adoption a partition forbids.
                    body = json.dumps({"staged": False,
                                       "fenced": True}).encode()
                else:
                    body = await _shard_transfer(image_handler,
                                                 header, req_body)
            elif op == "epoch_propose":
                # Orchestrated roll, phase 1: hold the proposed
                # manifest PENDING (digest-checked, crash-resumable)
                # and ack — routing is untouched until commit.
                from ..parallel import federation
                body = json.dumps(
                    federation.handle_epoch_propose(header)).encode()
            elif op == "epoch_commit":
                # Orchestrated roll, phase 2: activate the pending (or
                # carried) manifest if it is newer than the active
                # epoch — idempotent, so coordinators retry freely.
                from ..parallel import federation
                body = json.dumps(
                    federation.handle_epoch_commit(header)).encode()
            elif op == "partition":
                # Netsplit drill control: edit THIS process's OUTBOUND
                # link-partition table (utils.faultinject.PARTITIONS).
                # The op itself is exempt from partition checks —
                # drills must always be able to heal what they broke.
                from ..parallel import federation
                from ..utils import faultinject
                action = str(header.get("action") or "show")
                try:
                    if action == "add":
                        faultinject.PARTITIONS.add(
                            str(header.get("src") or ""),
                            str(header.get("dst") or ""),
                            mode=str(header.get("mode") or "drop"),
                            bidirectional=bool(
                                header.get("bidirectional")))
                    elif action == "remove":
                        faultinject.PARTITIONS.remove(
                            str(header.get("src") or ""),
                            str(header.get("dst") or ""),
                            bidirectional=bool(
                                header.get("bidirectional")))
                    elif action == "clear":
                        faultinject.PARTITIONS.clear()
                    elif action != "show":
                        raise BadRequestError(
                            f"partition action {action!r} must be "
                            f"add/remove/clear/show")
                except ValueError as e:
                    raise BadRequestError(str(e))
                active = federation.current()
                body = json.dumps({
                    "rules": faultinject.PARTITIONS.snapshot(),
                    "quorum": federation.quorum_status(),
                    # Active epoch rides along so a drill can watch a
                    # healed minority converge over this exempt op.
                    "epoch": (active.version
                              if active is not None else None),
                }).encode()
            elif op == "explain":
                # Dry-run residency probe (the /debug/explain plane):
                # READ-ONLY by contract — no render, no admission, no
                # staging.  The one shared implementation lives in
                # server.explain.residency_doc (combined, fleet-local
                # and remote members must never drift on "warm").
                from .explain import residency_doc
                handler_services = getattr(image_handler, "s", None)
                doc = await residency_doc(
                    getattr(getattr(handler_services, "caches",
                                    None), "image_region", None),
                    getattr(handler_services, "raw_cache", None),
                    str(header.get("key") or ""),
                    str(header.get("route") or ""))
                doc["prewarm_pending"] = \
                    telemetry.READINESS.prewarm_pending
                body = json.dumps(doc).encode()
            elif op == "ping":
                doc = status_fn() if status_fn is not None \
                    else {"ok": True}
                body = json.dumps(doc).encode()
            elif op == "flightrecorder":
                # This process's black-box ring; the frontend merges
                # it into its /debug/flightrecorder answer.
                body = json.dumps({
                    "events": telemetry.FLIGHT.snapshot(),
                    "events_total": telemetry.FLIGHT.events_total,
                    "dumps_written": telemetry.FLIGHT.dumps_written,
                }).encode()
            elif op == "decisions":
                # This process's decision-ledger ring; the frontend
                # merges every member's into ONE ts-sorted fleet
                # timeline on /debug/decisions.
                from ..utils import decisions as _decisions
                body = json.dumps({
                    "ring": _decisions.LEDGER.snapshot(
                        int(header.get("limit", 0) or 0)),
                    "status": _decisions.LEDGER.status(),
                }).encode()
            elif op == "warmstate":
                # Proxy-mode rehydrate/snapshot surface: the warm
                # state lives with the device process; frontends
                # relay /debug/warmstate here.
                if warmstate_fn is None:
                    raise BadRequestError(
                        "warm-state persistence is not enabled on "
                        "this sidecar")
                doc = await asyncio.to_thread(
                    warmstate_fn, bool(header.get("snapshot")))
                body = json.dumps(doc).encode()
            elif op == "sentinel":
                # This process's perf-sentinel view: the engine's
                # LIVE summary (no tick advance) plus anything it
                # ingested over gossip; the frontend folds it into
                # its /debug/sentinel fleet merge.
                engine = sentinel_mod.active()
                doc = dict(telemetry.SENTINEL.merged())
                doc["local"] = (engine.summary()
                                if engine is not None else None)
                body = json.dumps(doc).encode()
            elif op == "profile":
                # On-demand jax.profiler capture around the live
                # batcher lanes of THIS device-owning process.
                if profile_fn is None:
                    raise BadRequestError(
                        "profiling is not available on this sidecar")
                try:
                    ms = float(header.get("ms", 500.0))
                except (TypeError, ValueError):
                    raise BadRequestError("profile ms must be a number")
                doc = await asyncio.to_thread(profile_fn, ms)
                body = json.dumps(doc).encode()
            else:
                raise BadRequestError(f"unknown op {op!r}")
        except telemetry.ProfileInProgressError as e:
            # Single-flight: a capture is already running; the caller
            # retries after it finishes (concurrent captures would
            # interleave one trace file).
            body, out = b"", {"id": rid, "status": 409,
                              "error": str(e)}
        except transient.DeadlineExceededError as e:
            # The budget died while this request queued or rendered:
            # 504, and the frontend does NOT retry (more attempts
            # cannot make a spent budget whole).
            body, out = b"", {"id": rid, "status": 504,
                              "error": str(e)}
        except OverloadedError as e:
            # Admission shed: 503 + how long to back off.
            body, out = b"", {"id": rid, "status": 503,
                              "error": str(e),
                              "retry_after": e.retry_after_s}
        except BadRequestError as e:
            body, out = b"", {"id": rid, "status": 400, "error": str(e)}
        except (NotFoundError, FileNotFoundError):
            body, out = b"", {"id": rid, "status": 404}
        except Exception as e:
            if transient.is_transient_device_error(e):
                # A transport drop that survived even the group-render
                # retry is an AVAILABILITY failure, not a server bug:
                # 503 + Retry-After, the shed class — never a bare 500
                # for weather the client should simply retry through.
                logger.warning("render failed on a transient device "
                               "transport error: %s", e)
                body, out = b"", {"id": rid, "status": 503,
                                  "error": "transient device "
                                           "transport error",
                                  "retry_after": 1.0}
            else:
                logger.exception("sidecar render failed")
                body, out = b"", {"id": rid, "status": 500}
        else:
            out = {"id": rid, "status": 200}
        if spans:
            out["spans"] = spans
            if anchor is not None:
                out["t_anchor"] = anchor
        if costs:
            out["costs"] = costs
        if prov:
            out["prov"] = prov
        if quality_capped:
            out["quality_capped"] = 1
        if out["status"] >= 400:
            # Black box: failed sidecar ops are forensic events (the
            # routine 200 stream would only launder the ring).
            telemetry.FLIGHT.record("sidecar.op-error", op=header.get(
                "op"), status=out["status"])
        try:
            if (header.get("stream") and out["status"] == 200 and body
                    and header.get("op") in ("image", "mask")):
                # Progressive answer (protocol v3 leg 2): the body
                # leaves as ordered chunk frames the moment it exists —
                # which, with the batcher's first-tile-out settlement,
                # is one batch-tail EARLIER than the v2 barrier — and
                # the final fin frame carries status + spans/costs.
                # Concatenated chunks are byte-identical to the v2
                # single-frame body; a v2 client never sets ``stream``.
                mv = memoryview(body)
                seq = 0
                for off in range(0, len(mv), chunk_max):
                    # The slice goes down as a memoryview: the frame
                    # writer (and the ring) take buffers as-is, so a
                    # streamed body costs zero extra copies on the
                    # socket path — ``body`` outlives the awaited
                    # flush by construction.
                    await respond({"id": rid, "seq": seq},
                                  mv[off:off + chunk_max])
                    seq += 1
                out["fin"] = True
                out["chunks"] = seq
                telemetry.WIRE.count_stream(seq)
                await respond(out)
            else:
                await respond(out, body)
        except (ConnectionError, OSError):
            # The frontend died mid-response (its crash is survivable by
            # design); the render itself completed fine.
            logger.debug("frontend went away before response %s", rid)

    try:
        while True:
            try:
                header, req_body = await _read_frame(reader)
            except (asyncio.IncompleteReadError, ConnectionResetError):
                break
            except ValueError as e:
                # Malformed frame (oversize, bad lengths, broken JSON):
                # hostile or corrupt input answers a clean protocol
                # error and the connection closes — never an unhandled
                # exception wedging the serve task.
                telemetry.FLIGHT.record("wire.frame-error",
                                        error=str(e)[:120])
                try:
                    await respond({"id": None, "status": 400,
                                   "error": f"malformed frame: {e}"})
                except (ConnectionError, OSError):
                    pass
                break
            try:
                req_body = _ring_body(ring_state["recv"], header,
                                      req_body)
            except RingError as e:
                # A descriptor outside the live window poisons the
                # ring's release ordering: answer the op cleanly, then
                # drop the connection (the client reconnects; v2
                # socket bodies would resume on the new connection if
                # negotiation keeps failing).
                telemetry.FLIGHT.record("wire.ring-error",
                                        error=str(e)[:120])
                try:
                    await respond({"id": header.get("id"),
                                   "status": 400,
                                   "error": f"bad ring descriptor: "
                                            f"{e}"})
                except (ConnectionError, OSError):
                    pass
                break
            if header.get("op") == "hello":
                # Handshake, inline (never a task): the recv ring must
                # be live before any later frame's descriptor resolves.
                body, recv_ring, attached = _server_hello(
                    header, frames, wire)
                ring_state["recv"] = recv_ring
                ring_state["attached"] += attached
                try:
                    await respond({"id": header.get("id"),
                                   "status": 200}, body)
                except (ConnectionError, OSError):
                    break
                continue
            t = asyncio.create_task(handle(header, req_body))
            tasks.add(t)
            t.add_done_callback(tasks.discard)
    finally:
        # Cancel AND await the per-request tasks: a bare cancel() only
        # schedules the CancelledError, and the sidecar's teardown must
        # not close services while a render is still unwinding on them.
        for t in list(tasks):
            t.cancel()
        if tasks:
            await asyncio.gather(*tasks, return_exceptions=True)
        frames.close()
        for r in ring_state["attached"]:
            # Attach-side close only: the client created the segments
            # and owns their unlink.
            r.close()
        writer.close()


async def run_sidecar(config, socket_path: Optional[str] = None,
                      services_out: Optional[dict] = None) -> None:
    """Serve renders on the unix socket until cancelled.  Owns the full
    device-side stack (``app.build_services``).  ``services_out``
    (when given) receives the built services under ``"services"`` so
    the process entry's shutdown chain can snapshot warm state at
    SIGTERM."""
    from .app import build_services
    from .handler import ImageRegionHandler, ShapeMaskHandler

    socket_path = socket_path or config.sidecar.socket
    kind, host, port = parse_address(socket_path)

    # A stale unix socket from a crashed run must be cleared — but a
    # LIVE one must not be stolen (a second sidecar would silently
    # split serving state with the first).  Probe BEFORE building the
    # device stack so an accidental double-start fails instantly and
    # side-effect-free (build_services grabs the device and may join
    # jax.distributed).  TCP needs no probe: bind fails on a live port.
    if kind == "unix" and os.path.exists(socket_path):
        probe_ok = False
        try:
            _r, _w = await asyncio.wait_for(
                asyncio.open_unix_connection(socket_path), timeout=2.0)
            _w.close()
            probe_ok = True
        except (OSError, asyncio.TimeoutError):
            pass
        if probe_ok:
            raise RuntimeError(
                f"another render sidecar is already serving on "
                f"{socket_path}")
        os.unlink(socket_path)

    services = build_services(config)
    if services_out is not None:
        services_out["services"] = services
    fed_manifest = None
    if getattr(config, "federation", None) is not None \
            and config.federation.enabled:
        # Federated member process: install the manifest so the
        # manifest_hello / member_gossip / epoch_* ops answer from
        # this process's own copy of the agreed membership.
        from ..parallel import federation
        fed_manifest = federation.FleetManifest.from_config(
            config.federation)
        federation.install(fed_manifest,
                           self_host=config.federation.host)
        if getattr(config.federation, "quorum", False):
            federation.install_quorum(federation.QuorumTracker(
                fed_manifest, self_host=config.federation.host,
                suspect_after_s=config.federation.suspect_after_s))
    db_metadata = None
    if config.metadata_backend == "postgres":
        from ..services.db_metadata import PostgresMetadataService
        try:
            services.metadata = db_metadata = \
                await PostgresMetadataService.connect(config.metadata_dsn)
        except ImportError:
            logger.warning("metadata-service.type is 'postgres' but "
                           "asyncpg is unavailable; using the local "
                           "backend")
    image_handler = ImageRegionHandler(services)
    mask_handler = ShapeMaskHandler(services)

    # Self-preservation layer for the device-owning process: the
    # pressure governor (HBM/RSS/disk/queue/loop-lag -> brownout
    # ladder) and the stuck-lane watchdog run HERE, where the device
    # lanes live; the frontend's copies watch its own wire side.
    from . import pressure as pressure_mod
    from .watchdog import build_watchdog
    from ..utils.stopwatch import LoopLagSampler
    loop_lag = LoopLagSampler()
    robustness_tasks: list = [asyncio.create_task(
        loop_lag.run(), name="loop-lag")]
    governor = None
    if config.pressure.enabled:
        governor = pressure_mod.PressureGovernor(
            config.pressure,
            pressure_mod.build_actuators(config.pressure,
                                         services=services),
            pressure_mod.build_sources(services=services,
                                       loop_lag=loop_lag))
        pressure_mod.install(governor)
        robustness_tasks.append(asyncio.create_task(
            governor.run(), name="pressure-governor"))
    if config.watchdog.enabled \
            and hasattr(services.renderer, "watchdog_scan"):
        def _escalate(event: dict) -> None:
            telemetry.FLIGHT.record("watchdog.escalate", **{
                k: v for k, v in event.items() if k != "escalate"})
            logger.error("watchdog escalation: %s on %s",
                         event.get("action"), event.get("target"))
        wd = build_watchdog(config.watchdog,
                            renderer=services.renderer,
                            escalate_cb=_escalate)
        robustness_tasks.append(asyncio.create_task(
            wd.run(), name="watchdog"))
    if fed_manifest is not None \
            and config.federation.gossip_interval_s > 0:
        # Host-level gossip loop: a device-owning member process runs
        # its OWN failure detector against the other manifest HOSTS
        # (one handle per remote host, deduped) so its quorum verdict
        # — and therefore its fence — is local knowledge, not
        # something a frontend must push to it.  No router: the
        # coordinator only gossips and answers rolls.
        from ..parallel import federation
        from ..parallel.fleet import RemoteMember
        gossip_handles = []
        seen_hosts: set = set()
        for spec in fed_manifest.remote_members(
                config.federation.host):
            if spec.host in seen_hosts or not spec.address:
                continue
            seen_hosts.add(spec.host)
            peer_client = SidecarClient(spec.address,
                                        wire=config.wire)
            peer_client.peer_host = spec.host
            gossip_handles.append(RemoteMember(spec.name,
                                               peer_client))
        if gossip_handles:
            fed_coord = federation.FederationCoordinator(
                fed_manifest, self_host=config.federation.host,
                gossip_interval_s=(
                    config.federation.gossip_interval_s),
                handles=gossip_handles)
            robustness_tasks.append(asyncio.create_task(
                fed_coord.run(), name="federation-gossip"))

    # The device process runs its OWN perf sentinel (its render
    # latency is the signal the frontend's wire-inclusive clock
    # muddies); the summary rides gossip replies and the ``sentinel``
    # wire op into the frontend's fleet merge.
    sentinel_engine = None
    if getattr(config, "sentinel", None) is not None \
            and config.sentinel.enabled:
        sentinel_engine = sentinel_mod.engine_from_config(
            config.sentinel,
            member=(getattr(getattr(config, "federation", None),
                            "host", "") or "sidecar"))
        sentinel_mod.install(sentinel_engine)
        robustness_tasks.append(asyncio.create_task(
            sentinel_engine.run(), name="perf-sentinel"))

    def status_fn() -> dict:
        """The ping op's readiness document (frontend /readyz rolls
        this into its own verdict)."""
        renderer = services.renderer
        depth = (renderer.queue_depth()
                 if hasattr(renderer, "queue_depth") else 0)
        doc = {
            "ok": True,
            "prewarm_pending": telemetry.READINESS.prewarm_pending,
            "queue_depth": depth,
            # What this process serves from (the frontend's /readyz
            # relays both), and how much it has rendered — a fleet
            # member that never did work reads 0 here.
            "device": services.device,
            "native": services.native,
            "tiles_rendered": getattr(renderer, "tiles_rendered", 0),
        }
        if services.warmstate is not None:
            # /readyz annotation material: how far the boot
            # rehydrator has replayed the warm-state manifest.
            doc["rehydrate"] = telemetry.PERSIST.rehydrate_summary()
        from ..parallel import federation as _fed
        quorum = _fed.quorum_status()
        if quorum is not None:
            # Fencing is an ANNOTATION, not unreadiness: a fenced
            # minority keeps answering for its own shards.
            doc["quorum"] = quorum
        return doc

    def profile_fn(ms: float) -> dict:
        """The ``profile`` op: capture in THIS process (it owns the
        device); the frontend only relays the manifest."""
        return telemetry.capture_profile(
            config.telemetry.profile_dir,
            min(ms, config.telemetry.profile_max_ms))

    warmstate_fn = None
    if services.warmstate is not None:
        def warmstate_fn(snapshot: bool) -> dict:
            doc = {
                "enabled": True,
                "rehydrate": telemetry.PERSIST.rehydrate_summary(),
                "snapshots": telemetry.PERSIST.snapshots,
                "snapshot_errors": telemetry.PERSIST.snapshot_errors,
            }
            if snapshot:
                doc["snapshot_path"] = \
                    services.warmstate.snapshot_now()
            return doc

    # Server.close() only stops the LISTENER; established connections
    # and their handler coroutines would outlive a shutdown (and keep
    # serving from half-torn-down services).  Track them and cancel at
    # teardown so a restart is clean.
    conn_tasks: set = set()

    async def on_conn(reader, writer):
        _set_nodelay(writer)
        task = asyncio.current_task()
        conn_tasks.add(task)
        try:
            await _serve_connection(image_handler, mask_handler, reader,
                                    writer, status_fn=status_fn,
                                    profile_fn=profile_fn,
                                    warmstate_fn=warmstate_fn,
                                    wire=getattr(config, "wire", None))
        finally:
            conn_tasks.discard(task)

    if kind == "tcp":
        server = await asyncio.start_server(on_conn, host, port)
        bound_ino = None
    else:
        server = await asyncio.start_unix_server(on_conn,
                                                 path=socket_path)
        bound_ino = os.stat(socket_path).st_ino
    logger.info("render sidecar serving on %s", socket_path)
    try:
        # NOT serve_forever()/`async with server`: BOTH await
        # wait_closed() on cancellation, which (3.12.1+) blocks until
        # every live connection handler finishes — with frontends
        # holding connections open, shutdown would deadlock before we
        # could cancel the handlers.  The server is already accepting
        # (start_unix_server starts serving); just park until
        # cancelled, then close the listener, cancel the handlers, and
        # only THEN wait.
        await asyncio.Event().wait()
    finally:
        server.close()
        for task in robustness_tasks:
            task.cancel()
        if robustness_tasks:
            await asyncio.gather(*robustness_tasks,
                                 return_exceptions=True)
        if governor is not None \
                and pressure_mod.active() is governor:
            pressure_mod.uninstall()
        if sentinel_engine is not None:
            sentinel_engine.close()
            if sentinel_mod.active() is sentinel_engine:
                sentinel_mod.uninstall()
        for task in list(conn_tasks):
            task.cancel()
        if conn_tasks:
            await asyncio.gather(*conn_tasks, return_exceptions=True)
        try:
            await server.wait_closed()
        except Exception:
            pass
        if kind == "unix" and bound_ino is not None:
            # Unlink ONLY our own socket file: a replacement sidecar may
            # have already re-bound the path while this process drained
            # its last renders, and deleting ITS socket would strand
            # every frontend.
            try:
                if os.stat(socket_path).st_ino == bound_ino:
                    os.unlink(socket_path)
            except OSError:
                pass
        # Same teardown order as the combined app's on_cleanup: DB
        # metadata and renderer first, then prefetch workers BEFORE the
        # pixel stores close under them, then the shared cache clients.
        from .batcher import BatchingRenderer
        if services.warmstate is not None:
            # Stop the snapshot timer / abort rehydrate before the
            # stores it reads close under it.  (On SIGTERM the entry's
            # shutdown chain snapshots CONCURRENTLY from its own
            # thread, started at signal time; snapshot_now serializes
            # against itself, so this close never loses that write.)
            await asyncio.to_thread(services.warmstate.close)
        if db_metadata is not None:
            await db_metadata.close()
        if isinstance(services.renderer, BatchingRenderer):
            await services.renderer.close()
        if services.prefetcher is not None:
            services.prefetcher.flush(timeout=2.0)
            services.prefetcher.close()
        services.pixels_service.close()
        close_caches = getattr(services.caches, "close", None)
        if close_caches is not None:
            await close_caches()


# ---------------------------------------------------------------- client

class _StreamSink:
    """Chunk-frame consumer for one streaming call (protocol v3): the
    read loop pushes ordered chunk frames and the final status frame;
    :meth:`SidecarClient.call_stream` drains them as a generator."""

    def __init__(self):
        self.queue: asyncio.Queue = asyncio.Queue()

    def push(self, header: dict, body: bytes) -> None:
        self.queue.put_nowait(("chunk", header, body))

    def finish(self, header: dict, body: bytes) -> None:
        self.queue.put_nowait(("final", header, body))

    def fail(self, exc: BaseException) -> None:
        self.queue.put_nowait(("error", exc, b""))


class _Conn:
    """One connection generation: its writer, its pending waiters, its
    read loop, its negotiated wire features.  A stale generation's
    failure can then never touch a newer generation's state."""

    def __init__(self, reader: asyncio.StreamReader,
                 writer: asyncio.StreamWriter):
        self.reader = reader
        self.writer = writer
        # rid -> asyncio.Future (unary call) or _StreamSink (stream).
        self.pending: Dict[int, object] = {}
        self.reader_task: Optional[asyncio.Task] = None
        self.frames: Optional[FrameWriter] = None
        # v3 negotiation state: a peer that rejected the hello is v2 —
        # streaming requests still go out (the key is ignored there),
        # but the ring stays down for the connection's life.
        self.peer_v3 = False
        self.recv_ring: Optional[ShmRing] = None
        # Client-created segments (both directions); closed AND
        # unlinked with the connection.
        self.owned_rings: Tuple[ShmRing, ...] = ()
        # Set (to the failure) BEFORE pendings are drained: a caller
        # that raced the read loop's death — ensure_connected returned
        # this generation an await ago — must fail at registration, not
        # park a future no reader will ever resolve.
        self.dead: Optional[BaseException] = None
        # Per-connection clock mapping (hello negotiation): the
        # server's perf_counter at hello plus our send/receive window
        # midpoint yield ``clock_offset`` — server_time + offset ≈
        # client_time.  Exported span anchors (``t_anchor``) then land
        # on OUR timeline; None (v2 peer) keeps the send-time
        # anchoring.  Re-derived on every reconnect, so drift never
        # outlives a connection.
        self.clock_offset: Optional[float] = None
        # Hung-wire watchdog stamp: bumped on every frame RECEIVED and
        # when a request starts a fresh in-flight episode (first
        # registration onto an empty pending map), so "in-flight
        # requests with no activity past wire_hang_s" means the peer is
        # wedged mid-frame — not that the connection was merely idle
        # before this request.  Frames SENT while requests are already
        # parked never bump it: sends to a wedged peer are not
        # progress, and sustained request traffic would otherwise
        # reset the hang clock forever in exactly the scenario the
        # watchdog exists for.
        self.last_activity = time.monotonic()

    def register(self, rid: int, waiter) -> None:
        """Park a waiter (future or stream sink); refuses (raising the
        death cause) once the connection is marked dead, closing the
        enqueue/fail_pending race that could strand a request forever."""
        if self.dead is not None:
            raise ConnectionError(str(self.dead) or
                                  "render sidecar went away")
        if not self.pending:
            # Episode start: the hang clock anchors at the first
            # in-flight request, not at connection creation (an idle
            # connection must not read as already-hung).
            self.last_activity = time.monotonic()
        self.pending[rid] = waiter

    def fail_pending(self, exc: BaseException) -> None:
        self.dead = exc
        # Drain-until-empty, not a one-shot swap: anything registered
        # between the swap and the loop's end (same-tick callbacks)
        # would otherwise hang.  New registrations are already refused
        # via ``dead`` above.
        while self.pending:
            _, waiter = self.pending.popitem()
            if isinstance(waiter, _StreamSink):
                waiter.fail(exc)
            elif not waiter.done():
                waiter.set_exception(exc)

    def release_rings(self) -> None:
        """Teardown of this generation's ring segments (creator side:
        close + unlink)."""
        for r in self.owned_rings:
            r.close()
        self.owned_rings = ()
        self.recv_ring = None


class SidecarClient:
    """Multiplexed unix-socket client (one connection, many in-flight
    requests).  Reconnects lazily; in-flight requests fail fast when the
    sidecar goes away, mirroring the reference's ReplyException
    propagation from a dead bus consumer.

    Failure policy (utils.transient): idempotent ops (renders, probes,
    ping, metrics) retry with capped exponential backoff + jitter when
    the connection dies under them; ``plane_put`` — a state-changing
    upload — is NEVER auto-retried.  Consecutive failures trip the
    circuit breaker, after which calls fail fast
    (``errors.OverloadedError`` -> 503) until a half-open trial
    succeeds; pass ``breaker=None``/``retry=None`` to disable either."""

    _DEFAULT = object()   # "construct the standard policy" sentinel

    def __init__(self, socket_path: str, breaker=_DEFAULT,
                 retry=_DEFAULT, wire=None):
        from ..utils.transient import CircuitBreaker, RetryPolicy
        from .config import WireConfig
        self.socket_path = socket_path
        self.breaker = (CircuitBreaker()
                        if breaker is self._DEFAULT else breaker)
        self.retry = (RetryPolicy()
                      if retry is self._DEFAULT else retry)
        self.wire = wire if wire is not None else WireConfig()
        self._conn: Optional[_Conn] = None
        self._next_id = 0
        self._conn_lock = asyncio.Lock()
        self._write_lock = asyncio.Lock()
        # Hung-wire watchdog knobs (server.watchdog wires them from
        # WatchdogConfig): a connection with in-flight requests and no
        # frame activity for wire_hang_s is wedged mid-frame and gets
        # dropped (the retry policy re-issues idempotent calls on a
        # fresh connection).  0 disables the scan.
        self.wire_hang_s = 0.0
        self.watchdog_escalate_after = 2
        self._wire_fires = 0     # consecutive; a served reply resets
        # Fleet identity of the member this client reaches (set by
        # ``parallel.fleet.RemoteMember``): grafted spans carry it as
        # their ``member`` dimension so a multi-member waterfall stays
        # attributable.  None (plain proxy) adds nothing.
        self.member_label: Optional[str] = None
        # Federation host this client reaches (set by
        # ``parallel.federation.build_federated_members`` for
        # cross-host members): the netsplit drill's partition table
        # matches on (self_host, peer_host) links — an unstamped
        # client (same-host proxy) can never be partitioned.
        self.peer_host: str = ""

    async def _ensure_connected(self) -> _Conn:
        conn = self._conn
        if conn is not None and not conn.writer.is_closing():
            return conn
        async with self._conn_lock:
            conn = self._conn
            if conn is not None and not conn.writer.is_closing():
                return conn
            reader, writer = await open_sidecar_connection(
                self.socket_path)
            conn = _Conn(reader, writer)
            conn.frames = FrameWriter(
                writer, max_frames=self.wire.coalesce_max_frames,
                max_bytes=self.wire.coalesce_max_bytes)
            conn.reader_task = asyncio.create_task(
                self._read_loop(conn))
            try:
                await self._negotiate(conn)
            except BaseException:
                self._drop_conn(conn)
                raise
            self._conn = conn
            return conn

    async def _negotiate(self, conn: _Conn) -> None:
        """Protocol v3 handshake (one RTT per connection LIFE, not per
        call): offer the client-created ring segments, learn the peer's
        generation.  A v2 peer answers the unknown ``hello`` op with
        400 — the segments are destroyed and every feature degrades to
        its v2 behavior; only a dead connection raises."""
        rings: Tuple[ShmRing, ...] = ()
        if self.wire.ring_bytes > 0:
            created: list = []
            try:
                created.append(ShmRing.create(self.wire.ring_bytes))
                created.append(ShmRing.create(self.wire.ring_bytes))
                rings = tuple(created)
            except Exception as e:
                # No /dev/shm (or an exhausted one): socket bodies.
                # The FIRST segment must not leak when the second
                # create is what failed.
                logger.info("shm ring unavailable (%s); socket "
                            "bodies", e)
                for r in created:
                    r.close()
                rings = ()
        self._next_id += 1
        rid = self._next_id
        fut = asyncio.get_running_loop().create_future()
        header = {"id": rid, "op": "hello", "v": WIRE_VERSION}
        if self.member_label:
            # Tell the sidecar which fleet member it IS (it cannot
            # know otherwise): its own flight events then carry the
            # identity.  Extra key — older peers ignore it.
            header["member"] = self.member_label
        if rings:
            header["rings"] = {
                "c2s": {"name": rings[0].name,
                        "size": self.wire.ring_bytes},
                "s2c": {"name": rings[1].name,
                        "size": self.wire.ring_bytes},
            }
        t_hello = time.perf_counter()
        try:
            conn.register(rid, fut)
            await conn.frames.send(header)
            resp_header, resp_body = await asyncio.wait_for(fut, 10.0)
        except asyncio.TimeoutError:
            # A peer that answers nothing to an unknown op (no known
            # generation does this, but the wire is a contract): treat
            # as v2 rather than failing the connection.
            conn.pending.pop(rid, None)
            for r in rings:
                r.close()
            telemetry.WIRE.count_negotiation(ring=False)
            return
        except BaseException:
            # ConnectionError, register on a dead conn, CancelledError
            # (the caller's request task torn down mid-handshake): the
            # segments are not yet owned by the conn, so nobody else
            # can release them — a leak here compounds 2x ring-bytes
            # per reconnect attempt.
            for r in rings:
                r.close()
            raise
        doc = {}
        if resp_header.get("status") == 200:
            try:
                doc = json.loads(bytes(resp_body).decode())
            except (ValueError, AttributeError):
                doc = {}
        server_clock = doc.get("clock")
        if isinstance(server_clock, (int, float)):
            # Symmetric estimate: the server read its clock somewhere
            # inside our send->receive window; the midpoint bounds the
            # error by half the hello RTT.  Span-graft anchoring also
            # clamps to the request's own send time, so even a bad
            # estimate can never reorder a parent under its child.
            mid = (t_hello + time.perf_counter()) / 2.0
            conn.clock_offset = mid - float(server_clock)
        ring_ok = bool(rings and doc.get("ring")
                       and int(doc.get("v", 2)) >= 3)
        conn.peer_v3 = int(doc.get("v", 2)) >= 3 \
            if resp_header.get("status") == 200 else False
        if ring_ok:
            conn.owned_rings = rings
            conn.frames.ring = rings[0]            # c2s: our bodies out
            conn.frames.ring_min_bytes = self.wire.ring_min_body_bytes
            conn.recv_ring = rings[1]              # s2c: peer bodies in
        else:
            for r in rings:
                r.close()
        telemetry.WIRE.count_negotiation(ring=ring_ok)

    def _drop_conn(self, conn: _Conn,
                   reason: str = "render sidecar went away") -> None:
        """Generation-local teardown (send failure, protocol
        corruption, watchdog hang): fail its waiters, stop its
        flusher, release its rings; a newer generation is untouched."""
        conn.fail_pending(ConnectionError(reason))
        if conn.frames is not None:
            conn.frames.close()
        if conn.reader_task is not None:
            conn.reader_task.cancel()
        conn.writer.close()
        conn.release_rings()
        if self._conn is conn:
            self._conn = None

    def watchdog_scan(self, now: Optional[float] = None) -> List[dict]:
        """Hung-wire scan-and-heal (``server.watchdog`` target
        contract): requests are parked on the connection and NO frame
        has moved in either direction for ``wire_hang_s`` — the peer
        is wedged mid-frame (a stalled partial response can hold a
        ``readexactly`` forever without ever erroring).  The smallest
        heal: drop the connection, which fails the parked waiters with
        the ConnectionError class the retry policy already re-issues
        idempotent ops through on a FRESH connection.  Consecutive
        hangs without one served reply escalate (``escalate=True`` on
        the event) — the wire itself, not one connection, is sick."""
        if not self.wire_hang_s:
            return []
        now = time.monotonic() if now is None else now
        conn = self._conn
        if conn is None or not conn.pending:
            return []
        idle = now - conn.last_activity
        if idle < self.wire_hang_s:
            return []
        self._wire_fires += 1
        escalate = self._wire_fires >= self.watchdog_escalate_after
        parked = len(conn.pending)
        self._drop_conn(conn,
                        reason="watchdog: sidecar wire hung mid-frame")
        return [{
            "action": "escalate" if escalate else "drop-connection",
            "target": f"wire:{self.socket_path}",
            "escalate": escalate,
            "pending": parked,
            "idle_s": round(idle, 3),
        }]

    async def _read_loop(self, conn: _Conn) -> None:
        try:
            while True:
                header, body = await _read_frame(conn.reader)
                conn.last_activity = time.monotonic()
                body = _ring_body(conn.recv_ring, header, body)
                rid = header.get("id")
                waiter = conn.pending.get(rid)
                if isinstance(waiter, _StreamSink):
                    if "status" in header:
                        # fin frame: status + spans/costs (or the v2
                        # single-frame answer with the whole body).
                        conn.pending.pop(rid, None)
                        waiter.finish(header, body)
                    else:
                        waiter.push(header, body)
                else:
                    conn.pending.pop(rid, None)
                    if waiter is not None and not waiter.done():
                        waiter.set_result((header, body))
        except (asyncio.IncompleteReadError, ConnectionResetError,
                asyncio.CancelledError, OSError):
            pass
        except (RingError, ValueError) as e:
            # A corrupt frame or descriptor means the stream can no
            # longer be trusted; fail cleanly and reconnect — never
            # hand garbage bytes to a waiter.
            logger.warning("sidecar wire protocol error: %s", e)
            telemetry.FLIGHT.record("wire.protocol-error",
                                    error=str(e)[:120])
        finally:
            # Strictly generation-local: fail THIS connection's waiters
            # and close THIS writer; a newer generation opened by a
            # retry is untouched.
            conn.fail_pending(
                ConnectionError("render sidecar went away"))
            if conn.frames is not None:
                conn.frames.close()
            conn.writer.close()
            conn.release_rings()
            if self._conn is conn:
                self._conn = None

    async def call(self, op: str, ctx_json: dict, body: bytes = b"",
                   extra: Optional[dict] = None):
        """Returns (status, body_or_error)."""
        resp_header, resp_body = await self.call_full(
            op, ctx_json, body=body, extra=extra)
        return (resp_header["status"],
                resp_body if resp_header["status"] == 200
                else resp_header.get("error", ""))

    async def call_full(self, op: str, ctx_json: dict,
                        body: bytes = b"",
                        extra: Optional[dict] = None):
        """Returns (response_header, response_body).

        Retries transparently when the connection dies under the
        request — at send time OR while awaiting the reply (on asyncio
        a write to a dead peer usually buffers fine and the failure
        only surfaces through the read loop) — but ONLY for ops the
        retry policy declares idempotent: renders and probes are pure
        reads, so re-issuing one the dead sidecar may or may not have
        executed is safe; ``plane_put`` is not re-issued.  Consecutive
        failures trip the breaker (fail-fast ``OverloadedError``); the
        context's deadline caps backoffs and rides the wire as
        ``deadline_ms`` so the device process inherits the remaining
        budget."""
        import time as _time

        from ..utils import faultinject, transient
        from .errors import OverloadedError

        attempts = (self.retry.attempts_for(op)
                    if self.retry is not None else 1)
        attempt = 0
        while True:
            # Deadline BEFORE the breaker: a spent budget must not
            # claim (and then abandon) the half-open probe slot.
            transient.check_deadline(f"sidecar {op}")
            if self.breaker is not None and not self.breaker.allow():
                raise OverloadedError(
                    f"sidecar circuit breaker open (op {op})",
                    retry_after_s=self.breaker.retry_after_s() or 1.0)
            conn: Optional[_Conn] = None
            fut: Optional[asyncio.Future] = None
            rid = 0
            try:
                self._check_partition(op)
                conn = await self._ensure_connected()
                self._next_id += 1
                rid = self._next_id
                loop = asyncio.get_running_loop()
                fut = loop.create_future()
                conn.register(rid, fut)
                header = {"id": rid, "op": op, "ctx": ctx_json,
                          "v": WIRE_VERSION}
                if extra:
                    header.update(extra)
                remaining = transient.remaining_ms()
                if remaining is not None:
                    # The REMAINING budget, not an absolute time: the
                    # device process re-anchors on its own clock (wall
                    # clocks never cross the wire).
                    header["deadline_ms"] = max(0.0, round(remaining, 1))
                trace_id = telemetry.current_trace_id()
                if trace_id:
                    # The trace rides the wire so device-side spans
                    # join the requesting frontend's waterfall.
                    header["trace"] = trace_id
                t_call = _time.perf_counter()
                inj = faultinject.active()
                if inj is not None:
                    delay = inj.wire_delay_s()
                    if delay:
                        await asyncio.sleep(delay)
                    fault = inj.wire_fault()
                    if fault is not None:
                        await self._inject_wire_fault(conn, fault,
                                                      header, body)
                # Vectored path: the frame queues on the connection's
                # FrameWriter and flushes with whatever else is
                # pending as ONE writelines + drain (bodies ride the
                # negotiated shm ring when they qualify).
                await conn.frames.send(header, body)
                if remaining is not None:
                    # A wedged sidecar must not hold this caller past
                    # its budget: stop waiting at budget end.  The
                    # connection stays up — a late reply just finds no
                    # parked future and is dropped by the read loop.
                    try:
                        resp_header, resp_body = await asyncio.wait_for(
                            fut, timeout=max(0.0, remaining) / 1000.0)
                    except asyncio.TimeoutError:
                        conn.pending.pop(rid, None)
                        raise transient.DeadlineExceededError(
                            f"sidecar {op}: deadline exceeded awaiting "
                            f"reply")
                else:
                    resp_header, resp_body = await fut
            except (ConnectionError, OSError) as exc:
                if (fut is not None and fut.done()
                        and not fut.cancelled()):
                    fut.exception()   # mark retrieved (no noise)
                attempt = await self._retry_step(op, conn, rid,
                                                 attempt, attempts, exc)
                continue
            if self.breaker is not None:
                was_closed = self.breaker.state == self.breaker.CLOSED
                self.breaker.record_success()
                if not was_closed:
                    # Half-open probe succeeded: the episode is over.
                    telemetry.FLIGHT.record("breaker.close", op=op)
            telemetry.RESILIENCE.observe_attempts(op, attempt + 1)
            self._wire_fires = 0    # a served reply ends the episode
            self._graft_response(resp_header, t_call, conn)
            return resp_header, resp_body

    def _check_partition(self, op: str) -> None:
        """Netsplit drill hook: when a link partition blocks traffic
        from THIS host to ``peer_host``, the frame never leaves — the
        call dies with the same ``ConnectionError`` a dead wire
        raises, so it feeds the normal retry / breaker / mark-down
        ladder (= 503-with-shed at the edge, never a bare 5xx).  The
        ``partition`` control op is exempt: a drill must always be
        able to heal what it broke."""
        if op == "partition" or not self.peer_host:
            return
        from ..parallel import federation
        from ..utils import faultinject
        src = federation.self_host()
        mode = faultinject.partitioned(src, self.peer_host)
        if mode is not None:
            raise ConnectionError(
                f"link partitioned ({mode}): {src} -> "
                f"{self.peer_host}")

    async def _retry_step(self, op: str, conn: Optional[_Conn],
                          rid: int, attempt: int, attempts: int,
                          exc: BaseException) -> int:
        """ONE failure-bookkeeping ladder shared by the unary and
        streaming calls (a drifted copy here is a resilience-contract
        bug): drop the dead connection generation, feed the breaker,
        count the retry (or raise on exhaustion), and sleep the
        deadline-capped backoff.  Returns the incremented attempt."""
        from ..utils import transient

        if conn is not None:
            conn.pending.pop(rid, None)
            # The write half can die while the read loop still parks
            # on a healthy-looking socket: close + clear so the next
            # attempt reconnects instead of reusing the dead writer.
            conn.writer.close()
            if self._conn is conn:
                self._conn = None
        if self.breaker is not None:
            opens_before = self.breaker.opens
            self.breaker.record_failure()
            if self.breaker.opens > opens_before:
                # Breaker transition: exactly the black-box event
                # class — the seconds before a shedding episode began.
                telemetry.FLIGHT.record("breaker.open", op=op,
                                        opens=self.breaker.opens)
        attempt += 1
        if attempt >= attempts:
            telemetry.RESILIENCE.observe_attempts(op, attempt)
            telemetry.FLIGHT.record("sidecar.exhausted", op=op,
                                    attempts=attempt)
            raise ConnectionError("render sidecar went away") from exc
        telemetry.RESILIENCE.count_retry(op)
        telemetry.FLIGHT.record("sidecar.retry", op=op,
                                attempt=attempt)
        backoff = self.retry.backoff_s(attempt - 1)
        remaining = transient.remaining_ms()
        if remaining is not None:
            # Never sleep past the caller's budget: the next loop
            # iteration turns an exhausted budget into a
            # DeadlineExceededError instead of a long stall.
            backoff = min(backoff, max(0.0, remaining / 1000.0))
        if backoff > 0:
            await asyncio.sleep(backoff)
        return attempt

    def _graft_response(self, resp_header: dict, t_call: float,
                        conn: Optional[_Conn] = None) -> None:
        """Join the device process's exported spans/costs onto the
        requesting trace (shared by the unary and streaming paths).

        Anchoring: span offsets are relative to the sidecar's request
        arrival.  When the response carries ``t_anchor`` (the server's
        monotonic arrival stamp) AND the connection negotiated a clock
        offset at hello, the anchor maps onto OUR clock — accurate to
        half the hello RTT instead of a full request hop.  Either way
        the anchor is CLAMPED into [send time, now]: a drifted peer
        clock can shift a child span, but it can never open a child
        before its parent or after the response that contains it."""
        trace_id = telemetry.current_trace_id()
        if trace_id and resp_header.get("spans"):
            anchor = t_call
            offset = getattr(conn, "clock_offset", None)
            t_anchor = resp_header.get("t_anchor")
            if offset is not None \
                    and isinstance(t_anchor, (int, float)):
                anchor = min(max(t_call, float(t_anchor) + offset),
                             time.perf_counter())
            member = getattr(self, "member_label", None)
            for s in resp_header["spans"]:
                try:
                    meta = {k: v for k, v in s.items()
                            if k not in ("name", "start_ms",
                                         "dur_ms")}
                    if member is not None:
                        # The fleet stitches by member: every grafted
                        # span names the member whose process ran it
                        # (its own meta wins — drain/steal events
                        # already carry one).
                        meta.setdefault("member", member)
                    telemetry.record_span(
                        s["name"],
                        anchor + s["start_ms"] / 1000.0,
                        s["dur_ms"], trace_ids=(trace_id,), **meta)
                except (KeyError, TypeError):
                    pass    # malformed span: drop it, keep serving
        if trace_id and isinstance(resp_header.get("costs"), dict):
            # Device-side ledger entries (device-execute ms,
            # staged bytes) join the frontend's per-request ledger.
            telemetry.merge_costs(trace_id, resp_header["costs"])

    async def call_stream(self, op: str, ctx_json: dict,
                          extra: Optional[dict] = None,
                          final_out: Optional[dict] = None):
        """Progressive call (protocol v3 leg 2): an async generator
        yielding body chunks as their frames arrive; the final frame's
        status maps through the same exception contract as
        :meth:`call_full` (raised before the first yield when the
        request failed outright).  A v2 peer — or a server that chose
        not to stream this answer — degrades to one yield of the whole
        body.  ``final_out`` (when given) receives the fin frame's
        header fields — the caller's window onto the response's
        exported provenance/quality marks, which a generator cannot
        return.

        Retry policy: identical to :meth:`call_full` UP TO the first
        chunk — a connection that dies under the request before any
        bytes surfaced is re-issued per the op-aware policy and feeds
        the breaker.  Once a chunk has been yielded, bytes may already
        be on the HTTP wire, so a mid-stream death surfaces as a
        ConnectionError for the caller to truncate on.
        """
        import time as _time

        from ..utils import faultinject, transient
        from .errors import OverloadedError

        async def sink_get(sink):
            remaining = transient.remaining_ms()
            if remaining is None:
                return await sink.queue.get()
            try:
                return await asyncio.wait_for(
                    sink.queue.get(),
                    timeout=max(0.0, remaining) / 1000.0)
            except asyncio.TimeoutError:
                raise transient.DeadlineExceededError(
                    f"sidecar {op}: deadline exceeded awaiting stream")

        attempts = (self.retry.attempts_for(op)
                    if self.retry is not None else 1)
        attempt = 0
        while True:
            # Pre-first-chunk window: same deadline/breaker/retry
            # contract as the unary call.
            transient.check_deadline(f"sidecar {op}")
            if self.breaker is not None and not self.breaker.allow():
                raise OverloadedError(
                    f"sidecar circuit breaker open (op {op})",
                    retry_after_s=self.breaker.retry_after_s() or 1.0)
            conn = None
            rid = 0
            sink = _StreamSink()
            try:
                self._check_partition(op)
                conn = await self._ensure_connected()
                self._next_id += 1
                rid = self._next_id
                conn.register(rid, sink)
                header = {"id": rid, "op": op, "ctx": ctx_json,
                          "v": WIRE_VERSION, "stream": 1}
                if extra:
                    header.update(extra)
                remaining = transient.remaining_ms()
                if remaining is not None:
                    header["deadline_ms"] = max(0.0,
                                                round(remaining, 1))
                trace_id = telemetry.current_trace_id()
                if trace_id:
                    header["trace"] = trace_id
                t_call = _time.perf_counter()
                inj = faultinject.active()
                if inj is not None:
                    delay = inj.wire_delay_s()
                    if delay:
                        await asyncio.sleep(delay)
                    fault = inj.wire_fault()
                    if fault is not None:
                        await self._inject_wire_fault(conn, fault,
                                                      header, b"")
                await conn.frames.send(header)
                kind, first_h, first_body = await sink_get(sink)
                if kind == "error":
                    raise ConnectionError(
                        str(first_h) or "render sidecar went away")
            except (ConnectionError, OSError) as exc:
                attempt = await self._retry_step(op, conn, rid,
                                                 attempt, attempts, exc)
                continue
            except BaseException:
                # Deadline death (or cancellation) while parked on the
                # sink: the waiter entry must not outlive this call.
                if conn is not None:
                    conn.pending.pop(rid, None)
                raise
            break
        telemetry.RESILIENCE.observe_attempts(op, attempt + 1)
        self._wire_fires = 0    # a served reply ends the hang episode
        try:
            expected_seq = 0
            final = None
            final_body = b""
            kind, h, body = kind, first_h, first_body
            while True:
                if kind == "error":
                    raise ConnectionError(str(h) or
                                          "render sidecar went away")
                if kind == "chunk":
                    seq = h.get("seq")
                    if seq != expected_seq:
                        # Reordered/alien chunk framing: the stream
                        # can't be trusted — clean error, drop the
                        # generation (never serve spliced bytes).
                        self._drop_conn(conn)
                        raise ConnectionError(
                            f"stream chunk seq {seq!r} != expected "
                            f"{expected_seq} (op {op})")
                    expected_seq += 1
                    if expected_seq == 1:
                        telemetry.record_span(
                            "wire.firstChunk", t_call,
                            (_time.perf_counter() - t_call) * 1000.0,
                            op=op)
                    yield bytes(body)
                else:
                    final, final_body = h, body
                    break
                kind, h, body = await sink_get(sink)
            if self.breaker is not None:
                was_closed = self.breaker.state == self.breaker.CLOSED
                self.breaker.record_success()
                if not was_closed:
                    telemetry.FLIGHT.record("breaker.close", op=op)
            self._graft_response(final, t_call, conn)
            if final_out is not None:
                final_out.update(final)
            status = final.get("status")
            if status != 200:
                if expected_seq:
                    # Bytes already surfaced: a status can't be
                    # re-mapped under them.
                    raise ConnectionError(
                        f"stream failed mid-flight ({status})")
                _map_status(status, final.get("error", ""),
                            retry_after_s=final.get("retry_after"))
                return
            if expected_seq == 0 and final_body:
                # v2 single-frame answer (or an unstreamed body).
                yield bytes(final_body)
        finally:
            conn.pending.pop(rid, None)

    async def _inject_wire_fault(self, conn: _Conn, kind: str,
                                 header: dict, body: bytes) -> None:
        """Chaos hook: make the connection die under this request the
        way a real wire failure would — ``drop`` never sends, and
        ``truncate`` ships a partial frame (the sidecar's read loop
        sees the mid-frame EOF too) — then raise the ConnectionError
        the retry/breaker path handles."""
        if kind == "truncate":
            frame = _pack(header, body)
            async with self._write_lock:
                conn.writer.write(frame[:max(1, len(frame) // 2)])
                try:
                    await conn.writer.drain()
                except (ConnectionError, OSError):
                    pass
        conn.writer.close()
        if self._conn is conn:
            self._conn = None
        raise ConnectionError(f"injected wire fault: {kind}")

    async def stage_plane(self, arr, digest: Optional[str] = None):
        """Digest-first plane push (protocol v2): probe the sidecar's
        device plane cache, upload ONLY on miss.

        ``arr`` is a host ndarray in storage dtype.  Returns
        ``(digest, was_resident)``: resident True means zero plane
        bytes crossed the wire — the content was already in HBM (a
        previous push from any frontend, or the sidecar's own reads).
        Used by ingest/prewarm-style producers to land planes on the
        device ahead of the first interactive request.

        Degrades, never errors, against a peer that cannot take the
        push: a v1 sidecar (probe op unknown -> 400) or one with the
        plane cache disabled returns ``(digest, False)`` without
        uploading anything — the sidecar still stages its own reads,
        the push optimization just is not available there.
        """
        results = await self.stage_planes(
            [arr], digests=None if digest is None else [digest])
        return results[0]

    async def stage_planes(self, arrs, digests=None,
                           concurrency: int = 4):
        """Bulk digest-first plane push: ONE probe round-trip for the
        whole list, then concurrent uploads of just the misses.

        The per-plane form paid 2 wire RTTs per plane (probe, put),
        serialized — a floor that caps bulk staging regardless of
        link rate.
        Batched: one probe RTT amortized over N planes, puts for the
        misses issued ``concurrency`` at a time so transfers overlap
        the wire instead of queueing behind each other's round-trips.

        Returns ``[(digest, was_resident), ...]`` aligned with
        ``arrs``; degrades exactly like :meth:`stage_plane` against v1
        or plane-cache-disabled peers.
        """
        import numpy as np

        from ..io.devicecache import plane_digest

        def prepare():
            out = []
            for i, a in enumerate(arrs):
                a = np.ascontiguousarray(a)
                d = (digests[i] if digests is not None
                     and digests[i] else plane_digest(a))
                out.append((a, d))
            return out

        # Digesting is ~GB/s CPU work over possibly-MB planes: off the
        # event loop, so in-flight renders never stall behind BLAKE2b.
        prepared = await asyncio.to_thread(prepare)
        dlist = [d for _, d in prepared]
        status, payload = await self.call(
            "plane_probe", {}, extra={"digests": dlist})
        if status != 200:
            # v1 sidecar: no plane ops.  Degrade to no-push.
            return [(d, False) for d in dlist]
        try:
            doc = json.loads(bytes(payload).decode())
        except (ValueError, AttributeError):
            doc = {}
        if not doc.get("enabled", True):
            # Plane cache disabled sidecar-side: nothing to push into.
            return [(d, False) for d in dlist]
        resident = doc.get("resident")
        if not isinstance(resident, list) or len(resident) != len(dlist):
            # Previous-round v2 peer: the batched ``digests`` form is
            # unknown to it (its scalar answer reads an absent
            # ``digest`` as never-resident).  Fall back to per-digest
            # scalar probes — one RTT per plane, the old cost — so
            # wire dedup SURVIVES the mixed-version posture instead of
            # silently re-uploading every resident plane.
            resident = []
            for d in dlist:
                status, payload = await self.call(
                    "plane_probe", {}, extra={"digest": d})
                if status != 200:
                    resident.append(False)
                    continue
                try:
                    pdoc = json.loads(bytes(payload).decode())
                except (ValueError, AttributeError):
                    pdoc = {}
                resident.append(bool(pdoc.get("resident")))

        sem = asyncio.Semaphore(max(1, concurrency))
        results: list = [None] * len(prepared)

        async def put_one(i: int, arr, digest: str) -> None:
            async with sem:
                status, payload = await self.call(
                    "plane_put", {},
                    body=memoryview(arr).cast("B"),
                    extra={"digest": digest, "dtype": str(arr.dtype),
                           "shape": list(arr.shape)})
            if status != 200:
                raise RuntimeError(
                    f"plane_put failed ({status}): {payload}")
            doc = json.loads(bytes(payload).decode())
            results[i] = (doc.get("digest", digest),
                          bool(doc.get("resident")))

        # Intra-batch dedup: duplicate content within one batch ships
        # ONCE — only the first index of each missing digest uploads;
        # the aligned duplicates report resident (zero bytes crossed
        # the wire for them), exactly as the serial probe-per-plane
        # path would have answered.
        puts = []
        uploading: set = set()
        dup_indices: list = []
        for i, ((arr, digest), res) in enumerate(zip(prepared,
                                                     resident)):
            if res:
                results[i] = (digest, True)
            elif digest in uploading:
                dup_indices.append((i, digest))
            else:
                uploading.add(digest)
                puts.append(put_one(i, arr, digest))
        if puts:
            # Settle EVERY upload before surfacing a failure: a bare
            # gather would raise on the first failed put while sibling
            # tasks keep writing MB-scale bodies into a connection the
            # caller is about to close/retry over.
            outcomes = await asyncio.gather(*puts,
                                            return_exceptions=True)
            errors = [o for o in outcomes
                      if isinstance(o, BaseException)]
            if errors:
                raise errors[0]
        for i, digest in dup_indices:
            results[i] = (digest, True)
        return results

    async def close(self) -> None:
        conn, self._conn = self._conn, None
        if conn is None:
            return
        # Fail waiters BEFORE cancelling the reader: its finally would
        # otherwise beat us to it with the misleading "sidecar went
        # away" on what is a deliberate client shutdown.
        conn.fail_pending(ConnectionError("client closed"))
        if conn.frames is not None:
            conn.frames.close()
        if conn.reader_task is not None:
            conn.reader_task.cancel()
            try:
                await conn.reader_task
            except asyncio.CancelledError:
                pass
        conn.writer.close()
        conn.release_rings()


class SidecarImageHandler:
    """Drop-in for ``ImageRegionHandler`` on the frontend side: same
    call surface, same exception contract (the app's status mapping is
    reused verbatim).

    ``fallback`` (``server.degraded.DegradedCpuHandler``) is the
    graceful-degradation seam: when the device backend is UNREACHABLE —
    the connection (and every policy retry) died, or the circuit
    breaker is open — the render runs on the frontend's in-process CPU
    reference path instead, so tiles stay servable at reduced rate.
    Sidecar-reported errors (it answered: 4xx, its own shed, deadline)
    never fall back — a live sidecar's verdict stands."""

    def __init__(self, client: SidecarClient, fallback=None):
        self.client = client
        self.fallback = fallback

    async def render_image_region(self, ctx: ImageRegionCtx) -> bytes:
        from ..utils import provenance
        from .errors import OverloadedError
        from .pressure import shed_bulk_under_pressure
        # Frontend-side brownout: bulk work sheds BEFORE crossing the
        # wire when this process's governor has shed_bulk engaged.
        shed_bulk_under_pressure(ctx)
        try:
            resp_header, payload = await self.client.call_full(
                "image", ctx.to_json())
        except (ConnectionError, OverloadedError):
            if self.fallback is None:
                raise
            telemetry.RESILIENCE.count_degraded_render()
            provenance.mark(ctx, tier="degraded")
            return await self.fallback.render_image_region(ctx)
        provenance.merge_wire(ctx, resp_header.get("prov"))
        if resp_header.get("quality_capped"):
            # Mirror the sidecar's brownout mark onto the frontend ctx
            # so the HTTP layer strips the cache headers — a degraded
            # body must never be edge-cached under the full-quality
            # ETag (the PR 9 drop_quality contract at L5).
            ctx._pressure_quality_capped = True
        return _map_response(resp_header, payload)

    async def render_image_region_stream(self, ctx: ImageRegionCtx):
        """Progressive render: yields body chunks as their wire frames
        arrive (concatenation is byte-identical to
        :meth:`render_image_region`).  ANY pre-first-chunk failure of
        the v3 stream (exhausted retries, chunk-framing corruption,
        breaker) degrades to the unary path — which carries its own
        CPU fallback — so a streaming-feature failure is never an
        error surface the unary wire would have served through.  A
        mid-stream death propagates (bytes are already on the HTTP
        wire — the frontend truncates)."""
        from ..utils import provenance
        from .errors import OverloadedError
        offset = 0
        final_out: dict = {}
        try:
            async for chunk in self.client.call_stream(
                    "image", ctx.to_json(), final_out=final_out):
                offset += len(chunk)
                yield chunk
            provenance.merge_wire(ctx, final_out.get("prov"))
            if final_out.get("quality_capped"):
                ctx._pressure_quality_capped = True
            return
        except (ConnectionError, OverloadedError):
            if offset == 0 and self.fallback is not None:
                # Same landing as the unary path's unreachable case —
                # call_stream already exhausted the retry policy, so
                # re-running it through call_full would only double
                # the backoff ladder in front of the CPU render.
                telemetry.RESILIENCE.count_degraded_render()
                from ..utils import provenance
                provenance.mark(ctx, tier="degraded")
                yield await self.fallback.render_image_region(ctx)
                return
        if offset == 0:
            # No CPU fallback: ONE unary pass — a stream-layer failure
            # (chunk-framing corruption the read loop refused) must
            # not surface when the v2 unary wire still serves.
            yield await self.render_image_region(ctx)
            return
        # Mid-stream death with bytes already surfaced: RESUME instead
        # of truncating.  The render is deterministic and byte-exact
        # across every serving path (device re-render, sidecar byte
        # cache, degraded CPU — all pinned to the same golden in
        # tier-1), so re-fetching through the unary path (its own
        # retries + fallback behind it) and slicing off what already
        # left yields the identical remainder.  Under chaos this turns
        # "sidecar crashed between my chunk frames" from a truncated
        # HTTP body into a served tile.
        body = await self.render_image_region(ctx)
        if len(body) < offset:
            raise ConnectionError(
                "stream resume mismatch: re-rendered body shorter "
                "than the bytes already sent")
        yield bytes(body[offset:])


class SidecarMaskHandler:
    def __init__(self, client: SidecarClient, fallback=None):
        self.client = client
        self.fallback = fallback

    async def render_shape_mask(self, ctx: ShapeMaskCtx) -> bytes:
        from ..utils import provenance
        from .errors import OverloadedError
        try:
            resp_header, payload = await self.client.call_full(
                "mask", ctx.to_json())
        except (ConnectionError, OverloadedError):
            if self.fallback is None:
                raise
            telemetry.RESILIENCE.count_degraded_render()
            provenance.mark(ctx, tier="degraded")
            return await self.fallback.render_shape_mask(ctx)
        provenance.merge_wire(ctx, resp_header.get("prov"))
        return _map_response(resp_header, payload)


def _map_response(resp_header: dict, payload):
    status = resp_header["status"]
    return _map_status(
        status, payload if status == 200
        else resp_header.get("error", ""),
        retry_after_s=resp_header.get("retry_after"))


def _map_status(status: int, payload, retry_after_s=None):
    """Wire status -> the one exception contract ``server.errors``
    documents (the app's ``_status_of`` completes the round trip)."""
    from .errors import OverloadedError
    from ..utils.transient import DeadlineExceededError
    if status == 200:
        return payload
    if status == 400:
        raise BadRequestError(str(payload))
    if status == 404:
        raise NotFoundError()
    if status == 503:
        raise OverloadedError(
            str(payload) or "sidecar overloaded",
            retry_after_s=(retry_after_s if retry_after_s is not None
                           else 1.0))
    if status == 504:
        raise DeadlineExceededError(str(payload)
                                    or "sidecar deadline exceeded")
    raise RuntimeError(f"sidecar render failed ({status})")


# --------------------------------------------------------------- launch

def sidecar_main(config) -> None:
    """Blocking entry for ``--role sidecar`` (the device process).
    SIGTERM (systemd stop) triggers the same orderly teardown as
    cancellation: handlers drained, services closed; the ordered
    shutdown hook chain (warm-state snapshot first, black-box flight
    dump last, each guarded) runs before the teardown finishes."""
    import signal

    import threading

    holder: dict = {}

    def _start_chain() -> None:
        """Signal time: run the ordered chain (warm-state snapshot
        first, flight dump last, each guarded) on its OWN thread —
        it must capture state NOW, while services are live, and must
        not wait behind the orderly drain (a wedged teardown +
        supervisor SIGKILL must not cost the black box)."""
        from .shutdown import build_shutdown_chain
        telemetry.FLIGHT.record("signal", sig="SIGTERM")
        chain = build_shutdown_chain(config, holder.get("services"))
        t = threading.Thread(target=chain.run, args=("sigterm",),
                             name="shutdown-chain", daemon=True)
        holder["chain_thread"] = t
        t.start()

    async def main():
        task = asyncio.current_task()
        loop = asyncio.get_running_loop()

        def on_signal():
            _start_chain()
            task.cancel()

        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                loop.add_signal_handler(sig, on_signal)
            except (NotImplementedError, RuntimeError):
                pass
        try:
            await run_sidecar(config, services_out=holder)
        except asyncio.CancelledError:
            logger.info("render sidecar stopped")

    try:
        asyncio.run(main())
    except (KeyboardInterrupt, asyncio.CancelledError):
        pass
    finally:
        chain_thread = holder.get("chain_thread")
        if chain_thread is not None:
            # Bounded join: the snapshot/dump land before exit, but a
            # wedged hook cannot hold the process hostage.
            chain_thread.join(timeout=15.0)


def wait_sidecar_socket(proc, socket_path: str,
                        timeout_s: float = 180.0) -> None:
    """Block until the child accepts on ``socket_path``.

    Distinguishes "socket not yet bound" (keep polling) from "sidecar
    crashed during boot" (raise with the child's EXIT CODE immediately
    — a config typo must not masquerade as a 3-minute startup timeout).
    The child is re-polled AFTER each failed probe, so a crash landing
    between the liveness check and the connect can never slip through
    to the timeout either."""
    import socket as pysocket
    import time

    deadline = time.monotonic() + timeout_s
    kind, host, port = parse_address(socket_path)
    while True:
        code = proc.poll()
        if code is not None:
            raise RuntimeError(
                f"sidecar exited with {code} during startup")
        try:
            if kind == "tcp":
                s = pysocket.create_connection((host, port), timeout=1.0)
            else:
                s = pysocket.socket(pysocket.AF_UNIX)
                s.settimeout(1.0)
                s.connect(socket_path)
            s.close()
            return
        except OSError:
            pass
        code = proc.poll()
        if code is not None:
            raise RuntimeError(
                f"sidecar exited with {code} during startup")
        if time.monotonic() >= deadline:
            raise RuntimeError(
                "sidecar did not open its socket in time")
        time.sleep(0.2)


def spawn_sidecar(config_path: Optional[str], socket_path: str,
                  extra_args: Optional[list] = None):
    """``--role split``: start the device process as a child and wait
    for its socket to accept.  Returns the Popen handle."""
    import subprocess
    import sys

    from ..utils.jaxenv import require_chip_free
    require_chip_free("spawn_sidecar")
    argv = [sys.executable, "-m", "omero_ms_image_region_tpu.server",
            "--role", "sidecar", "--sidecar-socket", socket_path]
    if config_path:
        argv += ["--config", config_path]
    argv += list(extra_args or ())
    proc = subprocess.Popen(argv)
    try:
        wait_sidecar_socket(proc, socket_path)
    except Exception:
        if proc.poll() is None:
            proc.terminate()
        raise
    return proc


class SidecarSupervisor:
    """Keep the device process alive (the reference leaned on Vert.x
    supervisor restarts; this is the TPU build's equivalent for
    ``--role split``): spawn the sidecar, watch it from a daemon
    thread, respawn with capped exponential backoff when it dies.

    The readmission gate is built into the spawn itself:
    ``spawn_sidecar`` returns only once the socket ACCEPTS — and
    ``run_sidecar`` binds the socket strictly after ``build_services``,
    so an accepting socket means the device stack is up — while the
    frontends' ``/readyz`` (sidecar ping, ``prewarm_pending``) holds
    external traffic until the restarted process has re-run its
    prewarm gate.  ``spawn_fn`` is injectable so tests can supervise a
    cheap child instead of a full device process."""

    def __init__(self, spawn_fn, base_backoff_s: float = 0.5,
                 max_backoff_s: float = 30.0):
        import threading
        self._spawn_fn = spawn_fn
        self.base_backoff_s = base_backoff_s
        self.max_backoff_s = max_backoff_s
        self.proc = None
        self.restarts = 0
        self._stop = threading.Event()
        self._thread: Optional[object] = None

    @classmethod
    def for_config(cls, config_path: Optional[str], socket_path: str,
                   extra_args: Optional[list] = None,
                   max_backoff_s: float = 30.0) -> "SidecarSupervisor":
        return cls(lambda: spawn_sidecar(config_path, socket_path,
                                         extra_args),
                   max_backoff_s=max_backoff_s)

    def start(self):
        """Spawn the first child (blocking until its socket accepts,
        exactly like a bare ``spawn_sidecar``) and begin supervising."""
        import threading

        from ..utils.jaxenv import require_chip_free
        require_chip_free("SidecarSupervisor")
        self.proc = self._spawn_fn()
        self._thread = threading.Thread(
            target=self._monitor, name="sidecar-supervisor",
            daemon=True)
        self._thread.start()
        return self.proc

    def _monitor(self) -> None:
        import subprocess
        import time

        backoff = self.base_backoff_s
        spawned_at = time.monotonic()
        while not self._stop.is_set():
            proc = self.proc
            try:
                proc.wait(timeout=0.5)
            except subprocess.TimeoutExpired:
                if time.monotonic() - spawned_at > 30.0:
                    # A child that held for a while earns a reset: the
                    # backoff ladder punishes crash LOOPS, not isolated
                    # crashes an hour apart.
                    backoff = self.base_backoff_s
                continue
            if self._stop.is_set():
                break
            logger.warning(
                "render sidecar exited with %s; restarting in %.1f s",
                proc.returncode, backoff)
            if self._stop.wait(backoff):
                break
            backoff = min(backoff * 2.0, self.max_backoff_s)
            try:
                self.proc = self._spawn_fn()
            except Exception:
                # Spawn (or its startup probe) failed; the loop sees
                # the dead child again and ladders the backoff.
                logger.exception("sidecar respawn failed; will retry")
                continue
            if self._stop.is_set():
                # stop() raced this respawn (it can only terminate the
                # child it saw); the fresh child must not leak as an
                # orphan holding the socket.
                try:
                    self.proc.terminate()
                except Exception:
                    pass
                break
            spawned_at = time.monotonic()
            self.restarts += 1
            telemetry.RESILIENCE.count_supervisor_restart()
            telemetry.FLIGHT.record("supervisor.restart",
                                    n=self.restarts)
            logger.info("render sidecar restarted (restart #%d)",
                        self.restarts)

    def stop(self, timeout_s: float = 15.0) -> None:
        """Stop supervising and terminate the child (the deliberate
        shutdown path — no restart)."""
        self._stop.set()
        thread = self._thread
        if thread is not None:
            thread.join(timeout=timeout_s)
        proc = self.proc
        if proc is not None and proc.poll() is None:
            proc.terminate()
            try:
                proc.wait(timeout=timeout_s)
            except Exception:
                proc.kill()


class SidecarUnit:
    """One fleet member's sidecar PROCESS as a start/stoppable unit
    (the autoscaler's process-lifecycle seam, PR 13 follow-on): where
    the pre-provisioned posture parks a warm process, a unit-managed
    member's scale-down terminates it — releasing its devices and
    memory — and scale-up respawns it, blocking until the socket
    accepts (the same readmission gate as the supervisor).

    ``spawn_fn`` is injectable (the supervisor idiom) so the drill
    supervises a cheap fake instead of a full device process.  Both
    transitions are idempotent: stopping a stopped unit and starting
    a live one are no-ops, so a retried scale op never double-spawns.
    """

    def __init__(self, name: str, spawn_fn):
        self.name = name
        self._spawn_fn = spawn_fn
        self.proc = None
        self.starts = 0
        self.stops = 0

    def alive(self) -> bool:
        return self.proc is not None and self.proc.poll() is None

    def start(self) -> None:
        """Spawn the unit (blocking until its socket accepts — the
        spawn_fn's contract); no-op while the process lives."""
        if self.alive():
            return
        self.proc = self._spawn_fn()
        self.starts += 1
        telemetry.FLIGHT.record("autoscale.unit-start",
                                member=self.name)
        logger.info("sidecar unit %s started (pid %s)", self.name,
                    getattr(self.proc, "pid", None))

    def stop(self, timeout_s: float = 15.0) -> None:
        """Terminate the unit's process (SIGTERM — the sidecar's
        shutdown chain snapshots warm state — escalating to kill past
        ``timeout_s``); no-op when already stopped."""
        proc = self.proc
        if proc is None or proc.poll() is not None:
            return
        proc.terminate()
        try:
            proc.wait(timeout=timeout_s)
        except Exception:
            proc.kill()
        self.stops += 1
        telemetry.FLIGHT.record("autoscale.unit-stop",
                                member=self.name)
        logger.info("sidecar unit %s stopped", self.name)


class SidecarUnitLifecycle:
    """The autoscaler's member-name -> :class:`SidecarUnit` map.

    ``start(name)`` / ``stop(name)`` are the duck-typed hooks
    ``server.autoscaler.Autoscaler(lifecycle=...)`` drives: stop runs
    strictly AFTER the member's drain settled (its shard handoff needs
    the live process), start runs strictly BEFORE the undrain (routes
    must never land on a dead socket).  Unknown member names are
    no-ops — operators may unit-manage only part of a fleet."""

    def __init__(self, units: Dict[str, SidecarUnit]):
        self.units = dict(units)

    @classmethod
    def for_config(cls, config_path: str,
                   sockets_by_member: Dict[str, str]
                   ) -> "SidecarUnitLifecycle":
        """One unit per fleet member, all spawned from one sidecar
        config (``autoscaler.unit-config``) with the member's socket
        as ``--sidecar-socket`` — the frontend owns the unit
        processes instead of an operator pre-provisioning them."""
        return cls({
            name: SidecarUnit(
                name, lambda sock=sock: spawn_sidecar(config_path,
                                                      sock))
            for name, sock in sockets_by_member.items()})

    def start(self, name: str) -> None:
        unit = self.units.get(name)
        if unit is not None:
            unit.start()

    def stop(self, name: str) -> None:
        unit = self.units.get(name)
        if unit is not None:
            unit.stop()

    def start_all(self) -> None:
        """Spawn every unit CONCURRENTLY: each start() blocks until
        its socket accepts (device init is tens of seconds), and the
        units are independent processes — serially an 8-member fleet
        would pay 8x one boot before /readyz could pass."""
        import concurrent.futures as cf
        units = list(self.units.values())
        if len(units) <= 1:
            for unit in units:
                unit.start()
            return
        with cf.ThreadPoolExecutor(
                max_workers=len(units),
                thread_name_prefix="unit-start") as pool:
            for fut in [pool.submit(u.start) for u in units]:
                fut.result()

    def stop_all(self) -> None:
        for unit in self.units.values():
            unit.stop()

    def alive(self, name: str) -> bool:
        unit = self.units.get(name)
        return unit is not None and unit.alive()
