"""Micro-batching renderer: coalesce concurrent tile requests into
fixed-shape device dispatches.

This is the TPU-native replacement for the reference's worker-verticle data
parallelism (N=2x cores blocking render threads,
``ImageRegionMicroserviceVerticle.java:83-85,148-165``): instead of N CPU
threads each rendering one tile, concurrent requests are stacked into one
``vmap``-batched kernel call (SURVEY.md §2c, §7 step 5).

Fixed shapes are everything on TPU — each distinct (B, C, H, W) costs an
XLA compile — so two quantizations bound the executable set:

  * spatial buckets: a tile pads up (zeros) to the smallest configured
    bucket that fits, and the result is cropped back;
  * batch sizes: the collected group pads up (repeating the last tile) to
    the next entry of ``_BATCH_SHAPES`` <= the bucket's cap
    (``group_cap``: ``max_batch`` renders of 1024^2, more of a smaller
    bucket).

Requests with differing per-channel settings still share a batch: window,
family, reverse and the folded color tables are per-tile *data*, not
compile-time constants.  Only channel count, bucket shape and the codomain
scalars key the group.
"""

from __future__ import annotations

import asyncio
import collections
import contextlib
import itertools
import logging
from dataclasses import dataclass, field
from typing import Deque, Dict, List, Optional, Tuple

import time

import numpy as np

from ..ops.render import render_tile_batch_packed
from ..utils import entropypool, telemetry
from ..utils.stopwatch import REGISTRY, record_since, stopwatch

logger = logging.getLogger(__name__)

DEFAULT_BUCKETS = ((256, 256), (512, 512), (1024, 1024), (2048, 2048))


def pick_bucket(h: int, w: int,
                buckets=DEFAULT_BUCKETS) -> Tuple[int, int]:
    """Smallest bucket covering (h, w); oversize falls through to the exact
    shape (a one-off compile beats failing the request)."""
    for bh, bw in buckets:
        if h <= bh and w <= bw:
            return bh, bw
    return h, w


def mcu_grid(h: int, w: int) -> Tuple[int, int]:
    """``(h, w)`` rounded up to whole 16 x 16 MCUs: the least array a
    4:2:0 JPEG of that size is coded from."""
    return h + (-h) % 16, w + (-w) % 16


def bucket_lattice(ladder=DEFAULT_BUCKETS, planes=()) -> tuple:
    """The buckets a renderer picks from: the fixed ``ladder`` plus the
    MCU grid of every plane shape ``(h, w)`` the site states
    (``renderer.prewarm``: ``server.prewarm.stated_planes``), smallest
    first.  A stated 1080^2 field gets a 1088^2 bucket where the ladder
    alone would render, pack and fetch it as 2048^2, 3.5 x its pixels.
    A client's ``region=`` adds nothing here, so the compile set stays
    what the site wrote down.  Nothing stated: the ladder as given."""
    grids = {mcu_grid(h, w) for h, w in planes} - set(ladder)
    if not grids:
        return tuple(ladder)
    return tuple(sorted(set(ladder) | grids,
                        key=lambda b: (b[0] * b[1], b)))


# Allowed padded batch shapes: powers of two plus 3 and 6, so a group
# of 5-6 real tiles does not pay 8-shape execution.  Every entry is one
# compile per bucket key (cached persistently); pad tiles are excluded
# from the wire by compaction.
_BATCH_SHAPES = (1, 2, 3, 4, 6, 8, 16, 32, 64)


# ``max_batch`` counts renders of a bucket this large (or larger).
_CAP_BUCKET_PX = 1024 * 1024


def group_cap(max_batch: int, bucket_px: int) -> int:
    """The most renders one group of a ``bucket_px`` bucket takes
    (``_Pending.bucket_px``, set where a request's bucket is picked; 0
    = not bucketed).  ``max_batch`` keeps its meaning at buckets of
    1024^2 and above; a smaller bucket's cap is ``max_batch x (1024^2 /
    its pixels)``, held to the shape ladder's top: the device's cost of
    a group follows its pixels (on a v5e a 256^2 tile costs the chip
    ~1.4 ms and a 1024^2 tile ~19, PERF.md PR 28), so counting renders
    alone left a 256^2 group a sixteenth of a 1024^2 one.  Never below
    ``max_batch``."""
    if bucket_px <= 0 or bucket_px >= _CAP_BUCKET_PX:
        return max_batch
    return max(max_batch, min(_BATCH_SHAPES[-1],
                              max_batch * (_CAP_BUCKET_PX // bucket_px)))


def _pad_batch_size(n: int, cap: int) -> int:
    for size in _BATCH_SHAPES:
        if size >= n:
            return min(size, cap)
    return cap


def _raw_form(raw) -> tuple:
    """``(C, h, w, dtype)`` of a request's raw input in either of its
    forms (``_Pending.raw``)."""
    if isinstance(raw, tuple):
        return (len(raw),) + tuple(raw[0].shape) + (raw[0].dtype,)
    return tuple(raw.shape) + (raw.dtype,)


def _request_stack(raw, pad_to=None):
    """A request's ``[C, h, w]`` array, stacked from its planes where
    it carries them and edge-replicated to ``pad_to`` where its group's
    program would have done that (the fallback of a group that cannot
    take the planes as they are)."""
    if isinstance(raw, tuple):
        from ..ops.render import stack_channel_planes
        raw = stack_channel_planes(*raw)
        if pad_to is not None:
            from ..ops.jpegenc import pad_planes_to_mcu
            raw = pad_planes_to_mcu(raw, *pad_to)
    return raw


def _plane_shape(raw):
    """``(h, w)`` of the planes a request carries, None where it
    carries its own stack."""
    return raw[0].shape if isinstance(raw, tuple) else None


def _key_label(key: tuple) -> str:
    """Compact group-key label for flight-recorder events: the shape
    prefix only (channels x bucket), never the settings scalars."""
    if key and key[0] == "jpeg":
        return "jpeg:" + "x".join(str(v) for v in key[1:4])
    if key and key[0] == "mask":
        return "mask:" + "x".join(str(v) for v in key[1:3])
    return "x".join(str(v) for v in key[:3])


def _bucket_label(key: tuple) -> str:
    """The spatial bucket of a group key ("1088x1088"), as the
    ``batcher.group`` span carries it; a mask's shape is its own."""
    at = 2 if key and key[0] == "jpeg" else 1
    return "x".join(str(v) for v in key[at:at + 2])


def _shape_label(raw_shape, jpeg: bool = False) -> str:
    """Ladder-shape label for the estimated-vs-observed device cost
    model ("B8x4x1024x1024"); cardinality is bounded by the bucket and
    batch ladders."""
    label = "B" + "x".join(str(int(s)) for s in raw_shape)
    return ("jpeg:" + label) if jpeg else label


# How long a shape's cost-estimate capture waits before running: the
# AOT re-compile it may trigger is multi-core CPU churn, and the burst
# that minted the new shape deserves the machine first.
_ESTIMATE_DELAY_S = 5.0


def _capture_shape_estimate(shape: str, jitted_fn, args) -> None:
    """One-time XLA ``cost_analysis()`` capture for a compiled render
    shape (the /metrics estimated-vs-observed pair), spawned on a
    BACKGROUND daemon thread after a grace delay:
    ``lower().compile()`` re-traces and may re-compile on backends
    without a persistent compilation cache (seconds of multi-core
    work), and neither the first group of a new shape nor the traffic
    burst right behind it should pay for a diagnostic.  Any failure
    records a zero estimate; the per-shape claim in SHAPE_COSTS
    guarantees one capture per shape."""
    def capture():
        time.sleep(_ESTIMATE_DELAY_S)
        flops = nbytes = None
        try:
            cost = jitted_fn.lower(*args).compile().cost_analysis()
            flops = float(cost.get("flops", 0.0) or 0.0)
            nbytes = float(cost.get("bytes accessed", 0.0) or 0.0)
        except Exception:
            pass
        telemetry.SHAPE_COSTS.set_estimate(shape, flops, nbytes)

    import threading
    threading.Thread(target=capture, name=f"cost-est-{shape}",
                     daemon=True).start()


@dataclass
class _Pending:
    # What the group stacks: ``[C, bh, bw]`` padded to the bucket, in
    # the storage dtype (host numpy or device-resident), or a tuple of
    # ``C`` device-resident planes of the HBM raw cache that needed
    # nothing done to them per request (``takes_planes``): ``[bh, bw]``,
    # or a stated plane shape whose MCU grid the bucket is (``pad_to``).
    raw: object
    settings: dict
    h: int
    w: int
    quality: int = 0              # JPEG groups only
    # Pixels of the spatial bucket ``raw`` was padded to: what the
    # group's cap follows (``group_cap``).  0 = not bucketed (a mask,
    # shape-keyed): the cap stays ``max_batch``.
    bucket_px: int = 0
    # The bucket ``(bh, bw)`` the group's one program edge-replicates
    # this request's planes to (a JPEG request that carries planes
    # smaller than their bucket); None = ``raw`` has the bucket's shape.
    pad_to: Optional[Tuple[int, int]] = None
    future: asyncio.Future = None  # type: ignore[assignment]
    t_enqueue: float = 0.0        # queue-wait waterfall span
    t_popped: float = 0.0         # popped into a group: inGroup begins
    trace_id: str = None          # type: ignore[assignment]  # requester
    # Absolute time.monotonic() budget (utils.transient); queued work
    # whose budget is spent is cancelled at dispatch pop, never
    # rendered for a caller that already gave up.
    deadline: float = None        # type: ignore[assignment]
    # Times the watchdog has requeued this pending out of a stuck
    # group; at watchdog_escalate_after the next fire escalates
    # instead of healing again.
    requeues: int = 0

    def traces(self) -> Tuple[str, ...]:
        """The requester's trace, as a span's ``trace_ids``."""
        return (self.trace_id,) if self.trace_id else ()


class _LiveGroup:
    """One dispatched group render as the watchdog sees it: which
    pendings, which bucket queue to requeue into, and when the worker
    thread started.  ``fires``/``t_fire`` keep a healed-but-still-live
    group under scan: if its requeued pendings never reach a healthy
    slot (every slot wedged — e.g. pipeline_depth 1), the next
    threshold interval escalates instead of leaving the waiters
    parked forever."""

    __slots__ = ("key", "group", "t_start", "fires", "t_fire")

    def __init__(self, key: tuple, group: List["_Pending"],
                 t_start: float):
        self.key = key
        self.group = group
        self.t_start = t_start
        self.fires = 0
        self.t_fire = 0.0


class BatchingRenderer:
    """Drop-in for ``handler.Renderer`` with request coalescing.

    One dispatcher task per group key drains its queue: it waits up to
    ``linger_ms`` for co-arrivals, stacks up to ``max_batch`` tiles, runs
    the batched kernel in a worker thread (keeping the event loop free),
    and resolves each request's future with its cropped result.
    """

    # Consecutive full-batch dispatches that leave a backlog before the
    # batch size doubles (larger groups amortize dispatch + wire
    # round-trips under sustained load; each step compiles once).
    GROW_STREAK = 4

    def __init__(self, max_batch: int = 8, linger_ms: float = 2.0,
                 buckets=DEFAULT_BUCKETS, jpeg_engine: str = "sparse",
                 pipeline_depth: int = 4, max_batch_limit: int = None,
                 device_lanes: int = 2, planes=()):
        if jpeg_engine not in ("sparse", "huffman"):
            raise ValueError(
                f"batched jpeg engine must be 'sparse' or 'huffman', "
                f"got {jpeg_engine!r}")
        if pipeline_depth < 1:
            raise ValueError("pipeline_depth must be >= 1")
        if device_lanes < 1:
            raise ValueError("device_lanes must be >= 1")
        self.max_batch = max_batch
        # Queue-pressure growth ceiling: default 2x the configured
        # size (not measured on the current chip).
        self.max_batch_limit = max(max_batch, max_batch_limit
                                   or max_batch * 2)
        # Per-bucket-key backlog streaks: one saturated key must not be
        # reset by trickle traffic on another.
        self._full_streaks: Dict[tuple, int] = {}
        # Multi-host meshes must NOT grow from host-local timing: a
        # host doubling alone would launch a sharded program shape the
        # others never compile and hang the pod (MeshRenderer clears
        # this when process_count > 1).
        self._growth_enabled = True
        # One host-local retry of a group whose dispatch died on a
        # transient transport error (utils.transient).  Also cleared
        # on multi-host meshes: a lone host re-launching would diverge
        # the pod's SPMD launch sequence.
        self._transient_retry_enabled = True
        # Deadline-expired pendings are failed at dispatch pop instead
        # of rendered.  Safe on multi-host meshes too — the drop
        # happens on the LEADER before the group is announced, so every
        # process replays the identical post-drop group.
        self._deadline_drop_enabled = True
        self.linger_ms = linger_ms
        self.jpeg_engine = jpeg_engine
        self.pipeline_depth = pipeline_depth
        # The group threads code their groups' tiles too: the coding
        # pool leaves cores for them.
        entropypool.expect_group_threads(pipeline_depth)
        # ``planes``: the plane shapes (h, w) the site states
        # (``renderer.prewarm``).  Each gets a bucket no larger than
        # its MCU grid (``bucket_lattice``), and resident planes of a
        # stated shape ride to their group as they are.
        self.planes = frozenset((int(h), int(w)) for h, w in planes)
        self.buckets = bucket_lattice(buckets, self.planes)
        # (channels, h, w, dtype) of the one-request stacks and pads
        # handed to ``warm_edges`` so far.
        self._warmed_edges: set = set()
        self._queues: Dict[tuple, Deque[_Pending]] = {}
        self._dispatchers: Dict[tuple, asyncio.Task] = {}
        self._wakeups: Dict[tuple, asyncio.Event] = {}
        # When set (MeshRenderer in a multi-host pod), ONE launch slot
        # is shared across every bucket key, so concurrent per-key
        # dispatchers cannot interleave device launches.
        self._shared_slots: asyncio.Semaphore | None = None
        self._inflight: set = set()
        import threading
        self._stats_lock = threading.Lock()
        self.batches_dispatched = 0
        self.tiles_rendered = 0
        # Slots of the padded shapes launched, and how many of them
        # held a repeat of the group's last tile instead of a render
        # (/metrics imageregion_batcher_{shape,padded}_slots_total):
        # what the shape ladder wastes of the device.
        self.shape_slots = 0
        self.padded_slots = 0
        # Groups staged, by how their ``[B, C, bh, bw]`` array came to
        # be (/metrics imageregion_batcher_group_stacks_total{path=}):
        # "planes" = one jitted program over the members' resident
        # planes, "arrays" = a stack of the members' own stacks.
        self.group_stacks = {"planes": 0, "arrays": 0}
        # Pixels of the groups launched (/metrics
        # imageregion_batcher_bucket_px_total{part=}): "image" = the
        # members' own h x w, "pad" = what their buckets hold beyond
        # them.  Padded batch slots are ``padded_slots``', not these.
        self.bucket_px = {"image": 0, "pad": 0}
        # Two-stage group pipeline: each group render splits into a
        # fetch/stage half (stacking + host->device upload, run by any
        # of the pipeline_depth worker threads) and a device-execute
        # half gated by this bounded semaphore — the bounded queue
        # between the stages.  Default 2 (double-buffered): group N+1's
        # upload overlaps group N's execute, while at most two groups
        # contend for the device itself.
        self.device_lanes = device_lanes
        self._device_gate = threading.BoundedSemaphore(device_lanes)
        # Identifier of a dispatched group: on its ``batcher.group``
        # span, beside the members' trace ids (``group_trace``).
        self._group_ids = itertools.count(1)
        # High-water queue wait (ms) for the /metrics gauge — the
        # stragglers a mean hides and a p50 cannot see.
        self.queue_wait_max_ms = 0.0
        # Serialized-executable cache (server.execcache), wired by
        # build_services when persistence is on: packed group renders
        # call a deserialized compiled program when one matches the
        # call signature, and first-compiles are captured to disk for
        # the next process life.  None = today's jit-only path.
        # MeshRenderer never sets it: sharded programs are
        # mesh-topology-bound and must stay on the pod's lockstep
        # compile path.
        self.exec_cache = None
        # Per-member device pin (cross-host federation): group renders
        # dispatch on this device when set (io.staging.pin_scope);
        # None = the process default device.
        self.device = None
        # Brownout ladder "cap_lanes" (server.pressure): while nonzero,
        # at most this many group renders run concurrently regardless
        # of pipeline_depth — the governor's bound on device-side
        # concurrency under resource pressure.  0 = uncapped.
        self._lane_cap = 0
        # Watchdog state (server.watchdog): live group renders by
        # their inner future, and a ring of recent group durations
        # whose p99 anchors the stuck threshold.  Knobs are attributes
        # (not ctor args) so wiring stays config-driven and tests can
        # tighten them directly.
        self._live_groups: Dict[object, _LiveGroup] = {}
        self._group_durations: Deque[float] = collections.deque(
            maxlen=64)
        self.watchdog_stall_factor = 8.0
        self.watchdog_stall_min_s = 30.0
        self.watchdog_escalate_after = 2
        # First-tile-out settlement (wire.streaming): JPEG pendings
        # resolve the moment THEIR tile's entropy-encode slice lands,
        # instead of at the whole group's barrier — the first tile of
        # a B-tile group answers up to a batch-tail earlier, and the
        # sidecar's chunk frames forward it while siblings still
        # encode.  Byte-identical either way (the bytes ARE the
        # returned list's entries); settlement is loop-threadsafe.
        self.first_tile_out = True

    def _count_batch(self, tiles: int, shape: int,
                     device: Optional[int] = None) -> None:
        """Metrics update (``shape``: the padded batch shape launched;
        ``device``: the id of the device the group's output lies on,
        where it has one); group renders run concurrently on worker
        threads, so the increments need the lock."""
        with self._stats_lock:
            self.batches_dispatched += 1
            self.tiles_rendered += tiles
            self.shape_slots += shape
            self.padded_slots += shape - tiles
        if device is not None:
            telemetry.DEVICE_RENDERS.count(device, tiles)

    def group_cap(self, bucket_px: int) -> int:
        """This renderer's cap for a bucket, at its current (possibly
        grown) ``max_batch``."""
        return group_cap(self.max_batch, bucket_px)

    @contextlib.contextmanager
    def _lane(self):
        """Hold one of the ``device_lanes``.  The wait for it is a span
        of its own: with ``pipeline_depth`` > ``device_lanes`` it is a
        queue, and no other span sees it.  So is the hold, acquire to
        release: lanes x tiles a group / hold is the rate the gate
        allows."""
        with stopwatch("batcher.laneWait"):
            self._device_gate.acquire()
        try:
            with stopwatch("batcher.laneHold"):
                yield
        finally:
            self._device_gate.release()

    def queue_depth(self) -> int:
        """Requests waiting across every bucket key (the /metrics
        backlog gauge and the /readyz pressure check)."""
        return sum(len(q) for q in self._queues.values())

    def set_lane_cap(self, cap: int) -> None:
        """Brownout ladder "cap_lanes" actuator: bound concurrent
        group renders to ``cap`` (0 restores the configured
        pipeline_depth).  Takes effect at the next dispatch — running
        groups are never interrupted."""
        self._lane_cap = max(0, int(cap))

    def inflight(self) -> int:
        """Group renders currently occupying pipeline slots."""
        return len(self._inflight)

    # ----------------------------------------------------------- watchdog

    def group_p99_s(self) -> float:
        """Observed p99 of recent group-render durations (healed
        wedges excluded); 0 with no history — the stall floor rules
        alone then."""
        if not self._group_durations:
            return 0.0
        ordered = sorted(self._group_durations)
        return ordered[int(0.99 * (len(ordered) - 1))]

    def watchdog_scan(self, now: Optional[float] = None) -> List[dict]:
        """Scan-and-heal for stuck group renders (``server.watchdog``
        target contract): a live group older than
        ``max(stall_min_s, stall_factor x observed p99)`` is STUCK —
        its worker thread cannot be interrupted, but its waiters can
        be rescued.  The smallest heal that works: requeue the group's
        unsettled pendings at the head of their bucket queue, so a
        healthy pipeline slot re-renders them while the wedged thread
        settles into already-done futures (the existing skip-done
        contract).  A group whose pendings were already requeued
        ``watchdog_escalate_after - 1`` times escalates instead: its
        waiters fail with the transport-error class (503, client
        retries through) and the event carries ``escalate=True`` for
        the supervisor hook.  A healed group whose pendings are STILL
        unsettled a full threshold later re-fires toward the same
        escalation count — the requeue found no healthy slot (every
        lane wedged), so waiting for a re-dispatch that cannot happen
        would park the waiters forever.  Returns the fire events."""
        now = time.monotonic() if now is None else now
        threshold = max(self.watchdog_stall_min_s,
                        self.watchdog_stall_factor * self.group_p99_s())
        events: List[dict] = []
        for live in list(self._live_groups.values()):
            anchor = live.t_fire if live.fires else live.t_start
            if now - anchor < threshold:
                continue
            pending = [p for p in live.group if not p.future.done()]
            if not pending:
                continue          # everyone already settled or left
            live.fires += 1
            live.t_fire = now
            age = round(now - live.t_start, 3)
            if (live.fires >= self.watchdog_escalate_after
                    or max(p.requeues for p in pending)
                    >= self.watchdog_escalate_after - 1):
                for p in pending:
                    if not p.future.done():
                        p.future.set_exception(ConnectionError(
                            "watchdog: device lane stuck after "
                            "requeue; escalating"))
                events.append({"action": "escalate",
                               "target": f"lane:{_key_label(live.key)}",
                               "escalate": True, "age_s": age,
                               "tiles": len(pending)})
                continue
            queue = self._queues.get(live.key)
            if queue is None:
                continue
            for p in reversed(pending):
                # A re-fire (escalate_after > 2) finds the pendings
                # still queued from the last heal — never enqueue a
                # second copy.
                if any(q is p for q in queue):
                    continue
                p.requeues += 1
                queue.appendleft(p)
            wakeup = self._wakeups.get(live.key)
            if wakeup is not None:
                wakeup.set()
            events.append({"action": "requeue-group",
                           "target": f"lane:{_key_label(live.key)}",
                           "escalate": False, "age_s": age,
                           "tiles": len(pending)})
        return events

    def _record_queue_waits(self, group: List[_Pending], now: float,
                            cancelled: bool = False) -> None:
        """Per-request queue-wait spans, recorded ONCE per pending at
        the moment its group is popped for dispatch — never re-sampled
        later in the group's life, so the aggregate mean is exactly
        "how long did requests wait to be dispatched" and a few
        stragglers cannot re-enter the series.  The high-water mark
        feeds the imageregion_batcher_queue_wait_max_ms gauge
        (stragglers invisible at p50 — and diluted in a mean — stay
        visible there).

        ``cancelled`` pendings — budgets that died in the queue, or
        futures a disconnect/fault already settled — record under the
        SEPARATE ``batcher.queueWait.cancelled`` series: a request
        nobody rendered for must not skew the dispatched-wait mean
        (the BENCH_r05 "mean 2276 ms vs p50 2.2 ms" anomaly was
        exactly these corpses re-entering the aggregate) or the
        high-water gauge."""
        series = ("batcher.queueWait.cancelled" if cancelled
                  else "batcher.queueWait")
        for p in group:
            p.t_popped = now
            wait_ms = (now - p.t_enqueue) * 1000.0
            if not cancelled and wait_ms > self.queue_wait_max_ms:
                self.queue_wait_max_ms = wait_ms
            record_since(series, p.t_enqueue, now, p.traces())

    def _answer(self, p: _Pending, tiles: int, out=None,
                exc: Optional[BaseException] = None) -> None:
        """Settle one popped request's future, on the event loop, and
        close its span ``batcher.inGroup``: popped into a group of
        ``tiles`` (where ``batcher.queueWait`` ended) -> this request's
        own answer, stamped immediately before the future gets it.  A
        request's, recorded once like its queue wait: a first-tile-out
        settle ends it before the group's last tile's.  The stamp goes
        on the request's trace, where ``handler.respond`` begins."""
        if p.future.done():
            return
        now = record_since("batcher.inGroup", p.t_popped,
                           trace_ids=p.traces(), tiles=tiles)
        if p.trace_id:
            telemetry.mark_answered(now, p.trace_id)
        if exc is None:
            p.future.set_result(out)
        else:
            p.future.set_exception(exc)

    @staticmethod
    def _record_slot(t_slot: float, t_run: Optional[float],
                     t_ran: Optional[float], t_settle: float) -> None:
        """A pipeline slot's turn, from four stamps (every part crosses
        a thread, so none is a ``stopwatch``): ``batcher.slot``, the
        dispatcher's ``slots.acquire()`` returned -> ``settle`` about to
        release it; ``batcher.slotStart``, the same acquire -> the first
        line of ``run`` on the worker thread (the pop, bookkeeping,
        ``create_task``, the executor's queue, the thread's start);
        ``batcher.settleLag``, ``run``'s last line -> ``settle``'s first
        on the loop (``call_soon_threadsafe`` and the loop's own queue).
        ``slot`` = ``slotStart`` + ``batcher.group`` + ``settleLag``.
        The series alone, on no trace: the turn is the group's, a
        member's trace has its own ``batcher.inGroup`` and the group's
        span, and on the JPEG route every member has its answer (and a
        closed trace) before the group settles."""
        record_since("batcher.slot", t_slot, t_settle, ())
        if t_run is not None and t_ran is not None:
            record_since("batcher.slotStart", t_slot, t_run, ())
            record_since("batcher.settleLag", t_ran, t_settle, ())

    # ------------------------------------------------------------- public

    def takes_planes(self, h: int, w: int, jpeg: bool) -> bool:
        """Whether a request's resident planes ``[h, w]`` can ride to
        their group as they are (``_Pending.raw`` as a tuple): they fill
        their bucket, so ``render`` / ``render_jpeg`` would pad nothing;
        or, for a JPEG, they have a shape the site states, whose MCU
        grid is a bucket, and the group's one program pads them to it.
        What the handler asks before it stacks a request.  A shape
        nobody stated (a client's ``region=``, a WSI edge tile) is
        stacked and padded by itself: one program a shape there would
        leave the compile set to the clients."""
        if jpeg:
            return ((h, w) in self.planes
                    or pick_bucket(*mcu_grid(h, w),
                                   self.buckets) == (h, w))
        return pick_bucket(h, w, self.buckets) == (h, w)

    def warm_edges(self, channels: int, dtype, shapes) -> None:
        """Compile, on a thread of its own, the one-request stack and pad
        (``stack_channel_planes`` + ``pad_planes_to_mcu``) that a JPEG
        request of ``channels`` resident planes of each ``(h, w)`` in
        ``shapes`` takes where its group cannot take the planes: a
        pyramid's edge tiles, whose shapes the open image's levels fix
        (``region.edge_tile_shapes``), so that the rarest of them is
        not compiled on the event loop when a viewer first meets it.
        Each key once a process; a request that comes before the thread
        has compiled its shape compiles it itself, as it always did."""
        dtype = np.dtype(dtype)
        todo = []
        with self._stats_lock:
            for h, w in shapes:
                key = (channels, h, w, dtype.name)
                if key in self._warmed_edges \
                        or self.takes_planes(h, w, jpeg=True):
                    continue
                self._warmed_edges.add(key)
                todo.append((h, w))
        if not todo:
            return

        def warm():
            import jax
            from ..io.staging import pin_scope
            from ..ops.jpegenc import pad_planes_to_mcu
            from ..ops.render import stack_channel_planes
            try:
                with pin_scope(self.device):
                    for h, w in todo:
                        # As prewarm makes its planes: uncommitted.
                        plane = jax.device_put(np.zeros((h, w), dtype))
                        bh, bw = pick_bucket(*mcu_grid(h, w), self.buckets)
                        pad_planes_to_mcu(
                            stack_channel_planes(*[plane] * channels),
                            bh, bw).block_until_ready()
            except Exception:
                # The request path compiles what this did not.
                logger.exception("warming %d edge shapes failed",
                                 len(todo))

        import threading
        threading.Thread(target=warm, name="edge-warm",
                         daemon=True).start()

    async def render(self, raw, settings: dict) -> np.ndarray:
        """[C, H, W] raw (or its C resident planes) + packed settings
        -> u32[H, W] packed RGBA."""
        C, h, w, dtype = _raw_form(raw)
        bh, bw = pick_bucket(h, w, self.buckets)
        if (h, w) != (bh, bw):
            # A shape its bucket's group cannot take: stacked and
            # padded here, one request at a time.
            with stopwatch("batcher.requestPad"):
                raw = _request_stack(raw)
                if isinstance(raw, np.ndarray):
                    padded = np.zeros((C, bh, bw), raw.dtype)
                    padded[:, :h, :w] = raw
                    raw = padded
                else:
                    # Device-resident raw (HBM tile cache): pad on
                    # device.
                    import jax.numpy as jnp
                    raw = jnp.pad(raw,
                                  ((0, 0), (0, bh - h), (0, bw - w)))
        # tables is either [C, 3] ramp weights or [C, 256, 3] LUT tables
        # (ops.render.pack_settings); the two shapes cannot co-batch, nor
        # can raw dtypes (uint16 storage vs float32) mix in one stack.
        key = (C, bh, bw, int(settings["cd_start"]),
               int(settings["cd_end"]), settings["tables"].ndim,
               str(dtype))

        from ..utils.transient import deadline as _deadline
        pending = _Pending(raw=raw, settings=settings, h=h, w=w,
                           bucket_px=bh * bw,
                           future=asyncio.get_running_loop().create_future(),
                           trace_id=telemetry.current_trace_id(),
                           deadline=_deadline())
        return await self._enqueue(key, pending)

    async def render_jpeg(self, raw, settings: dict,
                          quality: int, width: int, height: int) -> bytes:
        """Batched fused render + device JPEG front end -> JFIF bytes
        (``raw``: ``[C, h, w]``, or its C resident planes).

        JPEG groups use the same spatial buckets as the packed path (all
        16-aligned), bounding the compile set against client-controlled
        region sizes; the per-tile SOF0 dimensions make decoders crop the
        padding, and tiles whose own MCU grid is smaller than the bucket
        are entropy-coded from the top-left block subgrid host-side
        (``ops.jpegenc.render_batch_to_jpeg``).  Padding is
        edge-replicated to keep it out of the boundary blocks' DCT energy.
        """
        from ..ops.jpegenc import pad_planes_to_mcu

        C, h, w, dtype = _raw_form(raw)
        bh, bw = pick_bucket(*mcu_grid(h, w), self.buckets)
        pad_to = None
        if (bh, bw) != (h, w):
            if isinstance(raw, tuple) and (h, w) in self.planes:
                pad_to = (bh, bw)      # the group's program pads them
            else:
                # A shape nobody stated (an edge tile, a client's
                # region): stacked and padded here, one request at a
                # time.
                with stopwatch("batcher.requestPad"):
                    raw = pad_planes_to_mcu(_request_stack(raw), bh, bw)
        key = ("jpeg", C, bh, bw, int(settings["cd_start"]),
               int(settings["cd_end"]), settings["tables"].ndim, quality,
               str(dtype))
        from ..utils.transient import deadline as _deadline
        pending = _Pending(raw=raw, settings=settings, h=height, w=width,
                           quality=quality, bucket_px=bh * bw,
                           pad_to=pad_to,
                           future=asyncio.get_running_loop().create_future(),
                           trace_id=telemetry.current_trace_id(),
                           deadline=_deadline())
        return await self._enqueue(key, pending)

    async def rasterize_mask(self, packed: np.ndarray, width: int,
                             height: int, flip_horizontal: bool,
                             flip_vertical: bool) -> np.ndarray:
        """Batched device mask rasterization (PR 20 leg 1): u8[nbytes]
        packed mask bits -> u8[H, W] 0/1 grid, byte-identical to the
        host ``ops.maskops`` unpack+flip (the PNG tail is shared, so
        the served bytes cannot diverge).

        Same-shape masks coalesce into one device dispatch through the
        ordinary group path — the (shape, flips) key bounds the compile
        set exactly like the spatial buckets bound the tile kernels.
        ``packed`` must be normalized to ``maskops.packed_nbytes``
        (``maskops.pack_mask_payload``) so group members stack."""
        key = ("mask", width, height,
               bool(flip_horizontal), bool(flip_vertical))
        from ..utils.transient import deadline as _deadline
        pending = _Pending(raw=packed,
                           settings={"fh": bool(flip_horizontal),
                                     "fv": bool(flip_vertical)},
                           h=height, w=width,
                           future=asyncio.get_running_loop().create_future(),
                           trace_id=telemetry.current_trace_id(),
                           deadline=_deadline())
        return await self._enqueue(key, pending)

    async def _enqueue(self, key: tuple, pending: _Pending):
        pending.t_enqueue = time.perf_counter()
        queue = self._queues.get(key)
        if queue is None:
            queue = self._queues[key] = collections.deque()
            self._wakeups[key] = asyncio.Event()
            self._dispatchers[key] = asyncio.create_task(
                self._dispatch_loop(key, pending.bucket_px))
        queue.append(pending)
        self._wakeups[key].set()
        return await pending.future

    async def close(self) -> None:
        for task in self._dispatchers.values():
            task.cancel()
        await asyncio.gather(*self._dispatchers.values(),
                             return_exceptions=True)
        # In-flight group renders run on worker threads and cannot be
        # interrupted; await them so their futures resolve (results or
        # errors) rather than cancelling out from under the waiters.
        if self._inflight:
            await asyncio.gather(*tuple(self._inflight),
                                 return_exceptions=True)
        # Fail any requests still queued so their awaiters don't hang
        # across shutdown.
        for queue in self._queues.values():
            while queue:
                pending = queue.popleft()
                if not pending.future.done():
                    # RuntimeError, not CancelledError: waiters sit in
                    # HTTP handlers whose ``except Exception`` must map
                    # this to a 500 instead of dropping the connection.
                    pending.future.set_exception(
                        RuntimeError("renderer shut down"))
        self._dispatchers.clear()
        self._queues.clear()
        self._wakeups.clear()

    # --------------------------------------------------------- dispatcher

    async def _dispatch_loop(self, key: tuple, bucket_px: int) -> None:
        """Drain the key's queue into group renders.

        Up to ``pipeline_depth`` group renders run concurrently (each on
        its own worker thread), and each render is three stages:
        fetch/stage (stack + host->device upload), device-execute
        (dispatch, wait, the wire rows' copy to the host) and, for a
        JPEG group, the host's entropy coding.  Only the middle one
        holds one of the ``device_lanes``: group k+1's upload and group
        k-1's entropy coding (native code, off the GIL) overlap group
        k's device execute.  A slot, unlike a lane, is kept through the
        group's serial entropy tail.
        """
        # The loop task was created from some request's context; detach
        # so dispatcher-side spans never attach to that one waterfall.
        telemetry.clear_context()
        queue = self._queues[key]
        wakeup = self._wakeups[key]
        slots = self._shared_slots or asyncio.Semaphore(self.pipeline_depth)
        while True:
            if not queue:
                wakeup.clear()
                await wakeup.wait()
            # Linger briefly so co-arriving tiles share the dispatch —
            # but never linger when a full batch is already waiting,
            # and never for a lone request on an otherwise idle
            # renderer (no queue behind it, nothing in flight): lingering
            # there buys no coalescing and only taxes single-tile p50.
            lone_idle = len(queue) == 1 and not self._inflight
            if (len(queue) < self.group_cap(bucket_px)
                    and self.linger_ms > 0 and not lone_idle):
                await asyncio.sleep(self.linger_ms / 1000.0)
            t_want = time.perf_counter()
            await slots.acquire()
            t_slot = time.perf_counter()
            if self._lane_cap and len(self._inflight) >= self._lane_cap:
                # Brownout: the governor capped concurrent groups
                # below pipeline_depth; park briefly and re-check
                # (only ever under an engaged cap_lanes step).
                slots.release()
                await asyncio.sleep(
                    max(self.linger_ms, 10.0) / 1000.0)
                continue
            # No awaits between popping the group and handing it to its
            # task, so a close() cancellation (delivered only at the
            # loop's await points) can never orphan a popped group.
            group: List[_Pending] = []
            cap = self.group_cap(bucket_px)
            now_mono = time.monotonic()
            expired: List[_Pending] = []
            dead: List[_Pending] = []
            while queue and len(group) < cap:
                p = queue.popleft()
                if p.future.done():
                    # Already settled while queued — the waiter
                    # disconnected (its await cancelled the future) or
                    # a fault path failed it.  Never rendered, and
                    # never counted as a dispatched queue wait.
                    dead.append(p)
                    continue
                if (self._deadline_drop_enabled
                        and p.deadline is not None
                        and now_mono >= p.deadline):
                    # Budget died in the queue: cancel cooperatively
                    # instead of rendering for a caller that already
                    # gave up — the slot goes to work that can still
                    # make its deadline.
                    expired.append(p)
                    continue
                group.append(p)
            if expired:
                from ..utils.transient import DeadlineExceededError
                telemetry.RESILIENCE.count_deadline_cancelled(
                    len(expired))
                telemetry.FLIGHT.record(
                    "batch.deadline-cancelled", n=len(expired),
                    key=_key_label(key))
                for p in expired:
                    if not p.future.done():
                        p.future.set_exception(DeadlineExceededError(
                            "deadline exceeded in batch queue"))
            if expired or dead:
                # Labelled separately — see _record_queue_waits.
                self._record_queue_waits(expired + dead,
                                         time.perf_counter(),
                                         cancelled=True)
            if not group:
                slots.release()
                continue
            # Sustained backlog: full groups that still leave a queue
            # mean the batch is the bottleneck — grow it (bounded).
            # Only where growing would enlarge THIS bucket's cap: a
            # backlog of 256^2 groups already at the ladder's top must
            # not double what a 1024^2 group takes.
            if self._growth_enabled:
                grown = min(self.max_batch * 2, self.max_batch_limit)
                if (len(group) == cap and queue
                        and group_cap(grown, bucket_px) > cap):
                    streak = self._full_streaks.get(key, 0) + 1
                    if streak >= self.GROW_STREAK:
                        self.max_batch = grown
                        streak = 0
                    self._full_streaks[key] = streak
                else:
                    self._full_streaks[key] = 0
            # Dispatch time IS the end of the queue wait: record here,
            # synchronously at pop (not when the group task happens to
            # run), once per pending.
            self._record_queue_waits(group, time.perf_counter())
            # Span ``batcher.slotWait``: how long the formed queue
            # waited for a free slot, which its queue waits contain.
            # The series alone: each member's trace has its queue wait.
            record_since("batcher.slotWait", t_want, t_slot, ())
            telemetry.FLIGHT.record(
                "batch.formed", key=_key_label(key), tiles=len(group),
                queued=len(queue), inflight=len(self._inflight))
            if key[0] == "jpeg":
                render = self._render_group_jpeg
            elif key[0] == "mask":
                render = self._render_group_mask
            else:
                render = self._render_group
            task = asyncio.create_task(
                self._run_group(render, group, slots, key, t_slot))
            self._inflight.add(task)
            task.add_done_callback(self._inflight.discard)

    async def _run_group(self, render, group: List[_Pending],
                         slots: asyncio.Semaphore, key: tuple,
                         t_slot: float) -> None:
        """Render one popped group on a worker thread.

        Settlement (slot release + waiter resolution) happens in the
        inner task's done callback, i.e. only when the worker THREAD has
        actually finished: cancelling this task must not free the launch
        slot while the render is still executing (on a multi-host mesh
        the shared slot is what keeps sharded launches serialized), and
        waiters must never see a raw CancelledError — it would bypass
        the HTTP layer's ``except Exception`` mapping and drop the
        connection without a response.
        """
        from ..utils import faultinject

        def render_hooked():
            # Chaos hook: a seeded injector raises a transient device
            # error here, so the retry path under test is the
            # production retry_transient, not a double.
            inj = faultinject.active()
            if inj is not None:
                inj.maybe_device_error()
            return render(group)

        if self._transient_retry_enabled:
            from ..utils.transient import retry_transient
            # Short backoff: the slot (and every request in the group)
            # waits it out, so a serving retry must not stall the
            # pipeline the way the bench's section-level retry may.
            run_inner = lambda: retry_transient(  # noqa: E731
                render_hooked, "group render", backoff_s=0.25)
        else:
            run_inner = render_hooked
        trace_ids = tuple(p.trace_id for p in group if p.trace_id)
        # The worker thread's first and last lines (``_record_slot``).
        t_run = t_ran = None

        def run():
            nonlocal t_run, t_ran
            t_run = time.perf_counter()
            # Worker-thread trace target: the group's device render,
            # wire fetch and encode spans land on EVERY member's
            # waterfall (each request really did wait on them).  The
            # group's own span is the parent of them all, by nesting
            # on this thread.
            try:
                with telemetry.group_trace(trace_ids), stopwatch(
                        "batcher.group", group_id=next(self._group_ids),
                        tiles=len(group),
                        padded=_pad_batch_size(
                            len(group),
                            self.group_cap(group[0].bucket_px)),
                        key=_key_label(key), bucket=_bucket_label(key)):
                    return run_inner()
            finally:
                t_ran = time.perf_counter()

        inner = asyncio.ensure_future(asyncio.to_thread(run))
        live = _LiveGroup(key, group, time.monotonic())
        self._live_groups[inner] = live

        def settle(fut: asyncio.Future) -> None:
            t_settle = time.perf_counter()
            slots.release()
            self._record_slot(t_slot, t_run, t_ran, t_settle)
            self._live_groups.pop(fut, None)
            if not live.fires:
                # Healed (stuck) groups stay out of the duration
                # history: one wedge must not stretch the p99 the
                # stuck threshold anchors on.
                self._group_durations.append(
                    time.monotonic() - live.t_start)
            if fut.cancelled():
                exc: BaseException = RuntimeError("render cancelled")
            else:
                exc = fut.exception()
            if exc is not None:
                for p in group:
                    self._answer(p, len(group), exc=exc)
                return
            for p, out in zip(group, fut.result()):
                self._answer(p, len(group), out)

        inner.add_done_callback(settle)
        try:
            await asyncio.shield(inner)
        except asyncio.CancelledError:
            raise  # settle() still fires when the thread finishes
        except Exception:
            pass   # waiters already failed by settle()

    def _group_arrays(self, group: List[_Pending]):
        """Pad the batch to the next ladder shape within the bucket's
        cap (repeating the last tile; extras are discarded) and build
        the stacked kernel inputs.  Where every member carries its
        resident planes, ONE jitted program stacks the group's B x C
        planes (``ops.render.stack_group_planes``); a group with any
        member of the other form stacks the members' own ``[C, bh,
        bw]`` arrays, on the device when any is resident there (the HBM
        raw tile cache)."""
        B = _pad_batch_size(len(group),
                            self.group_cap(group[0].bucket_px))
        padded = group + [group[-1]] * (B - len(group))
        shape = _plane_shape(group[0].raw)
        planes = shape is not None and all(
            _plane_shape(p.raw) == shape for p in group)
        if planes:
            from ..ops.render import stack_group_planes
            raw = stack_group_planes(tuple(p.raw for p in padded),
                                     pad=group[0].pad_to)
        elif all(isinstance(p.raw, np.ndarray) for p in group):
            raw = np.stack([p.raw for p in padded])
        else:
            import jax.numpy as jnp
            stacks = [_request_stack(p.raw, p.pad_to) for p in group]
            raw = jnp.stack(stacks + stacks[-1:] * (B - len(group)))
        image_px = sum(p.h * p.w for p in group)
        with self._stats_lock:
            self.group_stacks["planes" if planes else "arrays"] += 1
            if group[0].bucket_px:
                self.bucket_px["image"] += image_px
                self.bucket_px["pad"] += (
                    len(group) * group[0].bucket_px - image_px)

        def stack(name):
            return np.stack([p.settings[name] for p in padded])

        return raw, stack

    def _stage_group(self, group: List[_Pending]):
        """Fetch/stage half of a group render: stack the batch and ship
        it to the device BEFORE a device lane is taken, so group N+1's
        wire upload overlaps group N's device execute instead of
        running serially behind it.  Host stacks go up as they are
        (one asynchronous transfer, storage dtype); batches with
        device-resident members are already staged."""
        from ..utils import faultinject
        inj = faultinject.active()
        if inj is not None:
            freeze = inj.freeze_s()
            if freeze > 0:
                # Chaos hook: a wedged device lane.  Requests queued
                # behind it either shed at admission or cancel at
                # dispatch pop when their budgets die — the stall must
                # never back traffic up unboundedly.
                time.sleep(freeze)
        t0 = time.perf_counter()
        from ..io.staging import pin_scope
        with stopwatch("batcher.stage"), pin_scope(self.device):
            raw, stack = self._group_arrays(group)
            staged_bytes = (raw.nbytes
                            if isinstance(raw, np.ndarray) else 0)
            if isinstance(raw, np.ndarray):
                import jax
                raw = jax.device_put(raw)
        # Cost ledger, pro-rata: the group's one stack+upload spread
        # over its members (runs under group_trace, so each member's
        # ledger receives its share).  Device-resident stacks staged
        # zero host->HBM bytes.  One batched flush per group — not a
        # lock round-trip per field per member.
        n = max(1, len(group))
        fields = {"stage_ms": (time.perf_counter() - t0) * 1000.0 / n}
        if staged_bytes:
            fields["staged_bytes"] = staged_bytes / n
        telemetry.add_costs(fields)
        return raw, stack

    def _render_group_mask(self, group: List[_Pending]
                           ) -> List[np.ndarray]:
        """One batched device dispatch for a (shape, flips) mask group.

        The batch pads to a power of two (repeating the last member)
        exactly like the tile groups, so the compile set stays bounded
        by (shape, flips, pow2-batch) — and the kernel output is the
        identical 0/1 grid the host rasterizer produces, member for
        member."""
        from ..ops.maskops import rasterize_packed_batch
        n = len(group)
        B = _pad_batch_size(n, self.group_cap(group[0].bucket_px))
        padded = group + [group[-1]] * (B - n)
        packed = np.stack([p.raw for p in padded])
        _, width, height, fh, fv = self._mask_key_of(group)
        from ..io.staging import pin_scope
        with self._lane(), pin_scope(self.device):
            t0 = time.perf_counter()
            with stopwatch("Renderer.rasterizeMask.batch"):
                grids = rasterize_packed_batch(packed, width, height,
                                               fh, fv)
            exec_ms = (time.perf_counter() - t0) * 1000.0
        telemetry.add_cost("device_ms", exec_ms / max(1, n))
        self._count_batch(n, B)
        return [grids[i] for i in range(n)]

    def _mask_key_of(self, group: List[_Pending]) -> tuple:
        p = group[0]
        # h/w carry the mask shape; flips are re-derived from nothing —
        # the dispatcher hands the key to the render fn only via the
        # group, so stash flips on settings at enqueue instead.
        return ("mask", p.w, p.h, bool(p.settings.get("fh")),
                bool(p.settings.get("fv")))

    def _render_group(self, group: List[_Pending]) -> List[np.ndarray]:
        n = len(group)
        raw, stack = self._stage_group(group)
        s0 = group[0].settings
        args = (raw, stack("window_start"), stack("window_end"),
                stack("family"), stack("coefficient"),
                stack("reverse"),
                s0["cd_start"], s0["cd_end"], stack("tables"))
        shape = _shape_label(raw.shape)
        estimate = telemetry.SHAPE_COSTS.claim_estimate(shape)
        # Warm-restart path: a serialized executable matching this call
        # signature (deserialized at rehydrate, or captured in a prior
        # life) runs with NO trace/lower/compile.  Any failure falls
        # back to the jitted entry point — the executable cache can
        # only ever remove work.
        loaded_fn = (self.exec_cache.lookup("render_tile_batch_packed",
                                            args)
                     if self.exec_cache is not None else None)
        from ..io.staging import device_id, pin_scope
        with self._lane(), pin_scope(self.device):
            t0 = time.perf_counter()
            with stopwatch("Renderer.renderAsPackedInt.batch"):
                with stopwatch("device.dispatch"):
                    if loaded_fn is not None:
                        try:
                            out = loaded_fn(*args)
                        except Exception:
                            # Runtime drift the fingerprint cannot see:
                            # evict so only THIS group pays the failed
                            # attempt — every later group goes straight
                            # to the jit path.
                            self.exec_cache.invalidate(
                                "render_tile_batch_packed", args)
                            out = render_tile_batch_packed(*args)
                    else:
                        out = render_tile_batch_packed(*args)
                with stopwatch("device.wait", tiles=n):
                    out.block_until_ready()
                with stopwatch("wire.d2h", tiles=n):
                    host = np.asarray(out)
            device = device_id(out)
            exec_ms = (time.perf_counter() - t0) * 1000.0
        if loaded_fn is None and self.exec_cache is not None:
            # First group of this signature in this life: capture the
            # compiled program to disk (one-shot, delayed, background)
            # so the NEXT life skips the compile entirely.
            self.exec_cache.capture_async(
                "render_tile_batch_packed", render_tile_batch_packed,
                args)
        telemetry.add_cost("device_ms", exec_ms / n)
        telemetry.SHAPE_COSTS.observe(shape, exec_ms)
        if estimate:
            _capture_shape_estimate(shape, render_tile_batch_packed,
                                    args)
        self._count_batch(n, raw.shape[0], device)
        return [host[i, :p.h, :p.w] for i, p in enumerate(group[:n])]

    def _early_settle_cb(self, group: List[_Pending]):
        """First-tile-out hook for a JPEG group: resolve pending ``i``
        from the encode worker thread the moment its bytes exist.  The
        final group settle skips already-done futures, so this only
        ever MOVES a resolution earlier — same bytes, same error paths
        (a group failure after some tiles settled fails only the
        still-pending members, exactly like a partial disconnect)."""
        if not self.first_tile_out:
            return None
        n = len(group)

        def on_tile(i: int, data: bytes) -> None:
            if i >= n:
                return                     # batch-shape pad entries
            fut = group[i].future
            if fut is None:
                return    # harness-driven group (no waiter to settle)
            try:
                fut.get_loop().call_soon_threadsafe(
                    self._answer, group[i], n, data)
            except RuntimeError:
                pass                       # loop already closed
        return on_tile

    def _render_group_jpeg(self, group: List[_Pending]) -> List[bytes]:
        """``ops.jpegenc.render_batch_to_jpeg`` in its two halves: the
        lane is held while the device works for the group (dispatch,
        wait, the rows' copy to the host) and let go before the host
        codes them, so the next group's program starts under this
        group's entropy tail."""
        from ..ops.jpegenc import finish_wire_to_jpegs, render_batch_to_wire

        n = len(group)
        REGISTRY.record("batcher.groupTiles", float(n))
        raw, stack = self._stage_group(group)
        s0 = group[0].settings
        shape = _shape_label(raw.shape, jpeg=True)
        from ..io.staging import pin_scope
        timings: Dict[str, float] = {}
        # The pin covers the host half too: a tile that overflowed its
        # cap twice dispatches a one-tile program from there, with no
        # lane (as the mesh path's ``_dense_coefficients``).
        with pin_scope(self.device), contextlib.ExitStack() as lane:
            lane.enter_context(self._lane())
            with stopwatch("Renderer.renderAsPackedInt.batch"):
                wire = render_batch_to_wire(
                    raw, stack("window_start"), stack("window_end"),
                    stack("family"), stack("coefficient"),
                    stack("reverse"),
                    s0["cd_start"], s0["cd_end"], stack("tables"),
                    quality=group[0].quality,
                    dims=[(p.w, p.h) for p in group],  # pads skip encode
                    engine=self.jpeg_engine,
                    timings=timings,
                )
                lane.close()        # the device's work is over
                jpegs = finish_wire_to_jpegs(
                    wire, on_tile=self._early_settle_cb(group))
        # Observed-only for JPEG groups (the host wrapper has no single
        # compiled program to cost-analyze): the dispatch and the wait
        # for the program, without the copy out under the same lane or
        # the host entropy coding after it.
        exec_ms = timings.get("device_ms", 0.0)
        telemetry.add_cost("device_ms", exec_ms / n)
        telemetry.SHAPE_COSTS.observe(shape, exec_ms)
        self._count_batch(n, raw.shape[0], timings.get("device"))
        return jpegs


def batcher_knobs(config) -> dict:
    """The batcher's settings in ``config`` (``AppConfig``), as keyword
    arguments of ``BatchingRenderer`` and of the mesh's subclass."""
    from .prewarm import stated_planes

    batcher = config.batcher
    return dict(max_batch=batcher.max_batch,
                max_batch_limit=batcher.max_batch_limit,
                linger_ms=batcher.linger_ms,
                jpeg_engine=config.renderer.jpeg_engine,
                pipeline_depth=batcher.pipeline_depth,
                planes=stated_planes(config.renderer.prewarm))


def pin_to_device(renderer, raw_cache, device) -> None:
    """Dispatch ``renderer``'s groups and ``raw_cache``'s uploads (None:
    no cache) on ``device``."""
    if hasattr(renderer, "device"):
        renderer.device = device
    if raw_cache is not None:
        raw_cache.device = device


def build_renderer(config, device=None, renderer=None):
    """A chip's renderer and raw cache, as ``config`` (``AppConfig``)
    sets them: the one place the batcher's and the raw cache's settings
    reach their constructors.  Returns ``(renderer, raw_cache)``;
    ``raw_cache`` is None with ``raw-cache.enabled`` off.

    ``renderer``, when given, is one the caller built from
    :func:`batcher_knobs` (the server's ``MeshRenderer`` under
    ``parallel.enabled``).  Otherwise the renderer is a
    ``BatchingRenderer``, or the direct ``handler.Renderer`` with the
    batcher off and on a ``parallel`` deployment's extra fleet members.
    ``device`` pins the renderer and the cache to that device; None
    keeps the process default."""
    from ..io.devicecache import DeviceRawCache
    from .handler import Renderer

    if renderer is None:
        if config.batcher.enabled and not config.parallel.enabled:
            renderer = BatchingRenderer(**batcher_knobs(config))
        else:
            renderer = Renderer(jpeg_engine=config.renderer.jpeg_engine)
    if isinstance(renderer, BatchingRenderer):
        # With wire.streaming off a JPEG group answers its members at
        # its end, so each request's trace holds the group's render and
        # encode spans when it is finished.
        renderer.first_tile_out = config.wire.streaming
    raw_cache = (DeviceRawCache(config.raw_cache.max_bytes,
                                digest_index=config.raw_cache.digest_dedup)
                 if config.raw_cache.enabled else None)
    if device is not None:
        pin_to_device(renderer, raw_cache, device)
    return renderer, raw_cache
