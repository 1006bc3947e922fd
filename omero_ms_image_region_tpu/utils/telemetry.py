"""Request tracing, bucketed histograms and health state.

The reference treats its perf4j stopwatch spans and Graphite metric
beans as first-class plumbing (``beanRefContext.xml:36-46``); this
module is the grown-up form of that layer for the TPU service:

* **Traces** — every HTTP request gets a trace id; spans recorded
  anywhere in the pipeline (frontend handler, sidecar dispatch, batcher
  group, device render, wire fetch) attach to the requesting trace(s)
  through a ``contextvars`` context, so one request yields a
  parent/child span waterfall even when its render rode a coalesced
  group with seven other requests.  The sidecar wire carries the trace
  id, so device-process spans join the frontend's trace.
* **Histograms** — fixed log-scale bucket latency distributions
  (Prometheus ``_bucket``/``_sum``/``_count`` semantics), replacing the
  p50-only ring that could not distinguish a tail regression from link
  weather.
* **Gauges** — XLA compile events (count + cumulative ms — a lazily
  compiled batch shape shows up here mechanically), queue depth and
  pipeline occupancy are read live from the batcher at scrape time.
* **Slow-request dumps** — requests over a configured threshold write
  their full waterfall JSON to a spool directory
  (``scripts/trace_report.py`` renders them).
* **Readiness** — process-wide degradation state behind ``/readyz``.

Device-free on import: nothing here pulls in JAX (frontends import this
module), and the compile listener only touches ``jax.monitoring`` when
a device-owning process installs it.
"""

from __future__ import annotations

import contextvars
import json
import logging
import os
import threading
import time
import uuid
from bisect import bisect_left
from contextlib import contextmanager
from typing import Dict, List, Mapping, Optional, Tuple

from . import entropypool

log = logging.getLogger("omero_ms_image_region_tpu.telemetry")

# --------------------------------------------------------------- histograms

# Fixed log-scale bucket bounds (ms): 0.25 ms .. ~32.8 s, ratio 2.
# Fixed — not adaptive — so series from different processes, restarts
# and dashboards always align bucket-for-bucket.
BUCKET_BOUNDS_MS: Tuple[float, ...] = tuple(0.25 * 2 ** i
                                            for i in range(18))


def _fmt(v: float) -> str:
    """Prometheus-friendly number formatting (no trailing zeros)."""
    return ("%g" % v)


class Histogram:
    """Cumulative log-bucket histogram (not thread-safe on its own;
    callers hold their registry lock around ``add``).

    With ``exemplars=True`` each bucket also keeps its most recent
    observation's exemplar — ``(trace_id, tier)`` from the caller —
    written as ONE list-slot assignment (GIL-atomic, lock-light: the
    hot path pays a tuple build and an index store), exposed in
    OpenMetrics exemplar syntax by :meth:`series`.  The p99 bucket
    then NAMES a trace id an operator can pull a waterfall for."""

    __slots__ = ("bounds", "counts", "sum", "count", "exemplars")

    def __init__(self, bounds: Tuple[float, ...] = BUCKET_BOUNDS_MS,
                 exemplars: bool = False):
        self.bounds = bounds
        self.counts = [0] * (len(bounds) + 1)     # last = +Inf overflow
        self.sum = 0.0
        self.count = 0
        # bucket index -> (trace_id, tier, value, wall_ts) or None.
        self.exemplars = ([None] * (len(bounds) + 1) if exemplars
                          else None)

    def add(self, value: float,
            exemplar: Optional[Tuple[str, str]] = None) -> None:
        self.sum += value
        self.count += 1
        # bisect, not a linear bucket scan: add() sits on the span hot
        # path (every stage of every request lands here), and the scan
        # walked up to 18 bounds per observation.
        idx = bisect_left(self.bounds, value)
        self.counts[idx] += 1
        if self.exemplars is not None and exemplar is not None:
            # Slot write is a single GIL-atomic list assignment:
            # last-writer-wins is exactly the "most recent trace in
            # this bucket" semantics, so no lock is needed.
            self.exemplars[idx] = (exemplar[0], exemplar[1], value,
                                   time.time())

    def cumulative(self) -> List[int]:
        out, acc = [], 0
        for c in self.counts:
            acc += c
            out.append(acc)
        return out

    def quantile(self, q: float) -> float:
        """Bucket-resolution quantile estimate (upper bound of the
        bucket holding the q-th sample) — keeps the old ring-p50 API
        alive for profiling scripts."""
        if not self.count:
            return 0.0
        target = max(1, int(q * self.count + 0.5))
        acc = 0
        for i, c in enumerate(self.counts):
            acc += c
            if acc >= target:
                return (self.bounds[i] if i < len(self.bounds)
                        else self.bounds[-1] * 2)
        return self.bounds[-1] * 2

    def _exemplar_suffix(self, idx: int, enabled: bool) -> str:
        """OpenMetrics exemplar tail for one bucket line (empty when
        the bucket has none or the caller did not negotiate the
        OpenMetrics exposition): ``# {trace_id=..,tier=..} v ts``."""
        if not enabled or self.exemplars is None:
            return ""
        ex = self.exemplars[idx]
        if ex is None:
            return ""
        trace_id, tier, value, ts = ex
        return (f' # {{trace_id="{trace_id}",tier="{tier}"}} '
                f"{round(value, 3)} {round(ts, 3)}")

    def series(self, name: str, labels: str = "",
               exemplars: bool = False) -> List[str]:
        """Exposition lines.  ``labels`` is the inner label body without
        braces (e.g. ``route="x"``); ``le`` composes after it.
        ``exemplars`` opts the bucket lines into OpenMetrics exemplar
        tails — callers must pass True ONLY on a scrape that
        negotiated ``application/openmetrics-text`` (the classic
        text/plain parser rejects exemplar syntax, and one tail would
        fail the whole scrape)."""
        sep = "," if labels else ""
        lines = []
        cum = self.cumulative()
        for i, (b, c) in enumerate(zip(self.bounds, cum)):
            lines.append(f'{name}_bucket{{{labels}{sep}le="{_fmt(b)}"}}'
                         f" {c}{self._exemplar_suffix(i, exemplars)}")
        lines.append(f'{name}_bucket{{{labels}{sep}le="+Inf"}} '
                     f"{cum[-1]}"
                     f"{self._exemplar_suffix(len(self.bounds), exemplars)}")
        suffix = f"{{{labels}}}" if labels else ""
        lines.append(f"{name}_sum{suffix} {round(self.sum, 3)}")
        lines.append(f"{name}_count{suffix} {self.count}")
        return lines

    def exemplar_docs(self) -> List[dict]:
        """The live exemplars as JSON-able docs (the /debug/exemplars
        view: bucket upper bound -> most recent trace + tier)."""
        if self.exemplars is None:
            return []
        docs = []
        for i, ex in enumerate(list(self.exemplars)):
            if ex is None:
                continue
            le = (_fmt(self.bounds[i]) if i < len(self.bounds)
                  else "+Inf")
            docs.append({"le": le, "trace": ex[0], "tier": ex[1],
                         "value_ms": round(ex[2], 3),
                         "ts": round(ex[3], 3)})
        return docs


class HistogramVec:
    """Thread-safe histogram family keyed by one label value."""

    def __init__(self, label: str, exemplars: bool = False):
        self.label = label
        self.exemplars = exemplars
        self._lock = threading.Lock()
        self._hists: Dict[str, Histogram] = {}

    def observe(self, label_value: str, value: float,
                exemplar: Optional[Tuple[str, str]] = None) -> None:
        with self._lock:
            h = self._hists.get(label_value)
            if h is None:
                h = self._hists[label_value] = Histogram(
                    exemplars=self.exemplars)
            h.add(value, exemplar=exemplar)

    def series(self, name: str,
               exemplars: bool = False) -> List[str]:
        with self._lock:
            items = sorted(self._hists.items())
            lines = []
            for lv, h in items:
                lines += h.series(name, f'{self.label}="{lv}"',
                                  exemplars=exemplars)
            return lines

    def exemplar_docs(self) -> Dict[str, List[dict]]:
        """{label_value: [bucket exemplar docs]} — /debug/exemplars."""
        with self._lock:
            items = sorted(self._hists.items())
        return {lv: docs for lv, h in items
                if (docs := h.exemplar_docs())}

    def reset(self) -> None:
        with self._lock:
            self._hists.clear()


# End-to-end request latency by route — the acceptance-criteria series.
# Exemplared: each bucket names the most recent trace id + provenance
# tier that landed in it, so the p99 bucket points at a pullable
# waterfall (the metrics -> trace loop).
REQUEST_HIST = HistogramVec("route", exemplars=True)
_REQ_LOCK = threading.Lock()
_REQ_TOTALS: Dict[tuple, int] = {}


def count_request(route: str, status: int) -> None:
    with _REQ_LOCK:
        key = (route, int(status))
        _REQ_TOTALS[key] = _REQ_TOTALS.get(key, 0) + 1


# ------------------------------------------------------------------- traces

def new_trace_id() -> str:
    return uuid.uuid4().hex[:16]


class Trace:
    __slots__ = ("trace_id", "route", "t0", "wall_ts", "spans", "lock",
                 "costs", "t_answered")

    def __init__(self, trace_id: str, route: str = ""):
        self.trace_id = trace_id
        self.route = route
        self.t0 = time.perf_counter()
        self.wall_ts = time.time()
        self.spans: List[dict] = []
        # ``time.perf_counter`` stamp of the instant the request's
        # answer existed (``mark_answered``): where ``batcher.inGroup``
        # ends and ``handler.respond`` begins.  None: nothing rendered.
        self.t_answered: Optional[float] = None
        # Per-request cost ledger: numeric accumulators attributed to
        # this request (device-execute ms pro-rata from its batch
        # group, staged vs dedup-skipped HBM bytes, ...).  Written by
        # whatever layer did the work — batcher worker threads, the
        # device cache, the sidecar wire graft — under ``lock``.
        self.costs: Dict[str, float] = {}
        self.lock = threading.Lock()

    def add_cost(self, key: str, value: float) -> None:
        with self.lock:
            self.costs[key] = self.costs.get(key, 0.0) + float(value)

    def add_costs(self, items: Mapping[str, float]) -> None:
        """Batched ledger update: one lock acquisition for the whole
        mapping (the batcher flushes several fields per group; a lock
        round-trip per field was pure hot-path tax)."""
        with self.lock:
            costs = self.costs
            for key, value in items.items():
                costs[key] = costs.get(key, 0.0) + float(value)

    def export_costs(self) -> Dict[str, float]:
        """Wire-safe copy of the ledger (the sidecar response carries
        it so device-side costs land on the frontend's ledger)."""
        with self.lock:
            return dict(self.costs)

    def add_span(self, name: str, t_start: float, dur_ms: float,
                 **meta) -> None:
        span = {"name": name,
                "start_ms": round((t_start - self.t0) * 1000.0, 3),
                "dur_ms": round(dur_ms, 3)}
        if meta:
            span.update(meta)
        # Lock-free: list.append is atomic under the GIL, and every
        # reader below snapshots via list(self.spans) (also atomic)
        # before iterating — spans are recorded on the request path,
        # so the per-span lock round-trip was the single hottest
        # telemetry cost in the PR 4/5 profile.
        self.spans.append(span)

    def export_spans(self) -> List[dict]:
        """Copied span list (wire-safe: plain JSON dicts whose
        ``start_ms`` offsets are relative to this trace's t0)."""
        return [dict(s) for s in list(self.spans)]

    def span_ms(self, *names: str) -> Optional[float]:
        """Total duration of spans with one of the EXACT ``names``
        (None when the request never touched those stages).  Exact, not
        prefix: "Renderer.renderAsPackedInt" must not also sum its
        nested ".batch" child or totals exceed the request wall time."""
        total, seen = 0.0, False
        for s in list(self.spans):
            if s["name"] in names:
                total += s["dur_ms"]
                seen = True
        return total if seen else None

    def to_json(self, total_ms: Optional[float] = None,
                status: Optional[int] = None) -> dict:
        spans = sorted(list(self.spans), key=lambda s: s["start_ms"])
        with self.lock:
            costs = dict(self.costs)
        doc = {"trace_id": self.trace_id, "route": self.route,
               "ts": self.wall_ts, "spans": spans}
        if costs:
            doc["cost"] = {k: round(v, 3) for k, v in costs.items()}
        if total_ms is not None:
            doc["total_ms"] = round(total_ms, 3)
        if status is not None:
            doc["status"] = status
        return doc


class TraceRegistry:
    """Active traces by id, bounded; finished traces keep a short ring
    for tests and ad-hoc inspection.

    A sidecar process records spans for trace ids it never started (the
    frontend owns the request); those auto-created entries are evicted
    oldest-first once ``max_active`` is exceeded, so an orphaned trace
    can never leak memory."""

    def __init__(self, max_active: int = 4096, recent: int = 64):
        self._lock = threading.Lock()
        self._active: Dict[str, Trace] = {}
        self._max_active = max_active
        from collections import deque
        self.recent = deque(maxlen=recent)

    def start(self, trace_id: str, route: str = "") -> Trace:
        trace = Trace(trace_id, route)
        with self._lock:
            self._active[trace_id] = trace
            while len(self._active) > self._max_active:
                self._active.pop(next(iter(self._active)))
        return trace

    def get_or_create(self, trace_id: str) -> Trace:
        # Lock-free fast path: dict.get is GIL-atomic, and this lookup
        # runs once per span per trace (the hottest telemetry call in
        # the serving profile) — only the create takes the lock.  A
        # concurrent eviction racing the get just falls through to the
        # locked path.
        trace = self._active.get(trace_id)
        if trace is not None:
            return trace
        with self._lock:
            trace = self._active.get(trace_id)
            if trace is None:
                trace = self._active[trace_id] = Trace(trace_id)
                while len(self._active) > self._max_active:
                    self._active.pop(next(iter(self._active)))
            return trace

    def is_active(self, trace_id: str) -> bool:
        with self._lock:
            return trace_id in self._active

    def finish(self, trace_id: str) -> Optional[Trace]:
        with self._lock:
            trace = self._active.pop(trace_id, None)
        if trace is not None:
            self.recent.append(trace)
        return trace

    def reset(self) -> None:
        with self._lock:
            self._active.clear()
        self.recent.clear()


TRACES = TraceRegistry()

# The trace ids the CURRENT execution context is working for.  A plain
# request context carries one id; a batcher worker thread rendering a
# coalesced group carries every member's id, so the one group-render
# span lands on all of their waterfalls.
_TRACE_IDS: contextvars.ContextVar[Tuple[str, ...]] = \
    contextvars.ContextVar("imageregion_trace_ids", default=())

# Registry values recorded through the stopwatch registry that are NOT
# durations (counts etc.) — excluded from trace waterfalls.
_NON_SPAN_NAMES = frozenset({"batcher.groupTiles"})


def current_trace_ids() -> Tuple[str, ...]:
    return _TRACE_IDS.get()


def current_trace_id() -> Optional[str]:
    ids = _TRACE_IDS.get()
    return ids[0] if ids else None


def clear_context() -> None:
    """Detach the current execution context from any trace.  Long-lived
    tasks spawned from inside a request (the batcher's per-key
    dispatcher loops) MUST call this: contextvars copy at task creation,
    and without it every span the task ever records would attach to the
    spawning request's waterfall."""
    _TRACE_IDS.set(())


@contextmanager
def trace_scope(trace_id: str, route: str = ""):
    """Root scope for one request: registers the trace, makes it the
    context's recording target, yields the Trace (the caller finishes
    it — the finish policy lives with the HTTP layer)."""
    trace = TRACES.start(trace_id, route)
    token = _TRACE_IDS.set((trace_id,))
    try:
        yield trace
    finally:
        _TRACE_IDS.reset(token)


@contextmanager
def adopt_trace(trace_id: Optional[str]):
    """Join an existing trace (sidecar side of the wire): spans recorded
    inside attach to ``trace_id``'s waterfall.  No-op for None."""
    if not trace_id:
        yield None
        return
    trace = TRACES.get_or_create(trace_id)
    token = _TRACE_IDS.set((trace_id,))
    try:
        yield trace
    finally:
        _TRACE_IDS.reset(token)


@contextmanager
def group_trace(trace_ids: Tuple[str, ...]):
    """Recording target for a batcher worker thread rendering a
    coalesced group: spans land on EVERY member's waterfall."""
    token = _TRACE_IDS.set(tuple(trace_ids))
    try:
        yield
    finally:
        _TRACE_IDS.reset(token)


def record_span(name: str, t_start: float, dur_ms: float,
                trace_ids: Optional[Tuple[str, ...]] = None,
                **meta) -> None:
    """Attach a span to the given traces (default: the context's)."""
    ids = trace_ids if trace_ids is not None else _TRACE_IDS.get()
    for tid in ids:
        trace = TRACES.get_or_create(tid)
        trace.add_span(name, t_start, dur_ms, **meta)


def mark_answered(t: float, trace_id: Optional[str] = None) -> None:
    """Stamp the instant a request's answer existed on its trace (the
    context's when ``trace_id`` is None).  The first stamp stands: the
    batcher's, taken as it settles the request's future, comes before
    the handler's, taken when the renderer has returned (the only one
    where no batcher answered).  Never creates a trace."""
    trace = TRACES._active.get(trace_id or current_trace_id())
    if trace is not None and trace.t_answered is None:
        trace.t_answered = t


def observe_span(name: str, dur_ms: float, **meta) -> None:
    """Hook for the stopwatch registry: every recorded stage duration
    becomes a child span on whatever traces the context carries."""
    if name in _NON_SPAN_NAMES:
        return
    ids = _TRACE_IDS.get()
    if not ids:
        return
    record_span(name, time.perf_counter() - dur_ms / 1000.0, dur_ms,
                trace_ids=ids, **meta)


def add_cost(key: str, value: float,
             trace_ids: Optional[Tuple[str, ...]] = None) -> None:
    """Accumulate a cost onto the context's trace ledger(s).

    Pro-rata attribution is the CALLER's job: a batcher group render
    running under ``group_trace`` passes ``exec_ms / len(group)`` and
    every member's ledger receives its fair share of the one device
    dispatch.  No-op outside any trace context (prefetchers, prewarm)."""
    ids = trace_ids if trace_ids is not None else _TRACE_IDS.get()
    for tid in ids:
        TRACES.get_or_create(tid).add_cost(key, value)


def add_costs(items: Mapping[str, float],
              trace_ids: Optional[Tuple[str, ...]] = None) -> None:
    """Batched :func:`add_cost`: the whole mapping lands under ONE lock
    per trace (pay-for-what-you-use: a group render flushes its ledger
    fields in one shot instead of a lock round-trip per field)."""
    ids = trace_ids if trace_ids is not None else _TRACE_IDS.get()
    if not ids or not items:
        return
    for tid in ids:
        TRACES.get_or_create(tid).add_costs(items)


def merge_costs(trace_id: str, costs: Dict[str, float]) -> None:
    """Graft a wire-exported ledger (sidecar response) onto a trace."""
    trace = TRACES.get_or_create(trace_id)
    for key, value in costs.items():
        try:
            trace.add_cost(str(key), float(value))
        except (TypeError, ValueError):
            pass    # malformed wire field: drop it, keep serving


# ---------------------------------------------------------- compile events

class CompileStats:
    """XLA compile activity: count + cumulative ms of backend compiles.

    A serving-path program shape that was missed by prewarm shows up
    here as a count increment with a seconds-scale duration — the
    mechanical detector for first-touch compile stalls."""

    def __init__(self):
        self._lock = threading.Lock()
        self.events = 0
        self.total_ms = 0.0
        # Of those events, how many were answered by JAX's persistent
        # compilation cache (a short event, not a compile): the proof
        # that the cache directory is one this process can read.
        self.cache_hits = 0

    def observe(self, duration_s: float) -> None:
        with self._lock:
            self.events += 1
            self.total_ms += duration_s * 1000.0
        # Compile stalls are exactly the "what was it doing before it
        # fell over" class the black box exists for.
        FLIGHT.record("xla.compile", ms=round(duration_s * 1000.0, 1))

    def observe_cache_hit(self) -> None:
        with self._lock:
            self.cache_hits += 1

    def reset(self) -> None:
        with self._lock:
            self.events = 0
            self.total_ms = 0.0
            self.cache_hits = 0


COMPILE = CompileStats()
_COMPILE_LISTENER = threading.Lock()
_compile_listener_installed = False


# --------------------------------------------------------- cost ledger

# Per-route histograms over the request cost ledger — which requests
# are expensive, and WHERE the expense sits (device, queue, staging,
# encode, wire).  Keys are the ledger fields; byte fields convert to
# KB so the fixed ms-scale log buckets still resolve them.
_COST_HIST_FIELDS = {
    "device_ms": "imageregion_request_cost_device_ms",
    "read_ms": "imageregion_request_cost_read_ms",
    "stage_ms": "imageregion_request_cost_stage_ms",
    "queue_ms": "imageregion_request_cost_queue_ms",
    "encode_ms": "imageregion_request_cost_encode_ms",
    "staged_kb": "imageregion_request_cost_staged_kb",
    "wire_kb": "imageregion_request_cost_wire_kb",
}

COST_HISTS: Dict[str, HistogramVec] = {
    field: HistogramVec("route") for field in _COST_HIST_FIELDS
}


class CostTopK:
    """Bounded ledger of the most expensive recent requests (by wall
    total_ms) — the ``/debug/costs`` answer to "which requests are
    expensive".  Thread-safe; eviction is cheapest-first."""

    def __init__(self, k: int = 16):
        self.k = k
        self._lock = threading.Lock()
        self._entries: List[dict] = []   # sorted descending by score
        self.observed = 0

    def offer(self, doc: dict) -> None:
        score = float(doc.get("total_ms") or 0.0)
        with self._lock:
            self.observed += 1
            if (len(self._entries) >= self.k
                    and score <= float(
                        self._entries[-1].get("total_ms") or 0.0)):
                return
            self._entries.append(doc)
            self._entries.sort(key=lambda d: -(d.get("total_ms") or 0.0))
            del self._entries[self.k:]

    def snapshot(self) -> List[dict]:
        with self._lock:
            return [dict(d) for d in self._entries]

    def reset(self) -> None:
        with self._lock:
            self._entries.clear()
            self.observed = 0


COST_TOPK = CostTopK()


def assemble_ledger(trace: Trace, total_ms: float,
                    nbytes: int) -> Tuple[Dict[str, float], str]:
    """(ledger, cache_class) for a finished request.

    Accumulated costs (device/stage ms, staged bytes — written by the
    layers that did the work) merge with span-derived fields (queue
    wait, encode) and the response size.  ``cache_class`` is where the
    bytes came from: ``byte-cache`` (no pipeline ran), ``coalesced``
    (single-flight follower), else ``render``."""
    ledger = trace.export_costs()
    queue_ms = trace.span_ms("batcher.queueWait")
    if queue_ms is not None:
        ledger["queue_ms"] = round(queue_ms, 3)
    read_ms = trace.span_ms("PixelsService.readRegion")
    if read_ms is not None:
        ledger["read_ms"] = round(read_ms, 3)
    encode_ms = trace.span_ms("encodeImage", "jfif.encodeBatch")
    if encode_ms is not None:
        ledger["encode_ms"] = round(encode_ms, 3)
    ledger["wire_bytes"] = int(nbytes)
    ledger["total_ms"] = round(total_ms, 3)
    if trace.span_ms("cache.hit") is not None:
        cache_class = "byte-cache"
    elif trace.span_ms("dedup.coalesced") is not None:
        cache_class = "coalesced"
    else:
        cache_class = "render"
    return ledger, cache_class


def observe_request_cost(route: str, ledger: Dict[str, float]) -> None:
    """Feed the per-route cost histograms from a finished ledger."""
    for field, hist in COST_HISTS.items():
        if field == "staged_kb":
            value = ledger.get("staged_bytes")
        elif field == "wire_kb":
            value = ledger.get("wire_bytes")
        else:
            value = ledger.get(field)
        if value is None:
            continue
        if field.endswith("_kb"):
            value = float(value) / 1024.0
        hist.observe(route, float(value))


def cost_metric_lines() -> List[str]:
    lines: List[str] = []
    for field, hist in COST_HISTS.items():
        lines += hist.series(_COST_HIST_FIELDS[field])
    return lines


# ------------------------------------------------------ flight recorder

# Monotone artifact sequence shared by flight dumps and profile
# captures: two artifacts in the same wall-clock second must get two
# names, never silently overwrite one (next() is atomic on CPython).
import itertools as _itertools          # noqa: E402

_ARTIFACT_SEQ = _itertools.count(1)


class FlightRecorder:
    """Black-box ring of structured events: what the system was doing
    in the seconds before it fell over.

    Lock-free on the hot path — ``deque.append`` with a ``maxlen`` is
    atomic under the GIL, so recording from batcher worker threads,
    the admission path and (best-effort) signal handlers never blocks
    and never deadlocks.  ``dump`` snapshots via ``list(ring)`` (also
    atomic) and NEVER raises: a full disk must not turn a crash dump
    into a second crash."""

    def __init__(self, maxlen: int = 512):
        from collections import deque
        self._ring = deque(maxlen=maxlen)
        self.events_total = 0
        self.dumps_written = 0
        # Fleet identity stamp: when set (a process that knows which
        # member it is), every recorded event carries it, so merged
        # fleet rings stay attributable (events that already name a
        # member — drain phases, steals — keep their own).
        self.member: Optional[str] = None

    def configure(self, maxlen: int,
                  member: Optional[str] = None) -> None:
        from collections import deque
        if maxlen != self._ring.maxlen:
            self._ring = deque(self._ring, maxlen=max(16, maxlen))
        if member is not None:
            self.member = member

    def set_member(self, member: Optional[str]) -> None:
        self.member = member

    def record(self, kind: str, **fields) -> None:
        event = {"ts": round(time.time(), 3), "kind": kind}
        if self.member is not None and "member" not in fields:
            event["member"] = self.member
        if fields:
            event.update(fields)
        self._ring.append(event)
        self.events_total += 1    # benign race: a count, not a key

    def snapshot(self) -> List[dict]:
        return list(self._ring)

    def __len__(self) -> int:
        return len(self._ring)

    # Spool retention: dumps past this many are pruned oldest-first on
    # each write, so a breach-flapping (or curl-looping) deployment
    # cannot fill the disk with black-box snapshots.
    MAX_DUMPS = 64

    def dump(self, directory: str, reason: str) -> Optional[str]:
        """Write the ring as one JSON document; returns the path or
        None (never raises — see class docstring).  Names carry a
        monotone sequence so same-second dumps never collide."""
        try:
            events = self.snapshot()
            os.makedirs(directory, exist_ok=True)
            seq = next(_ARTIFACT_SEQ)
            path = os.path.join(
                directory,
                time.strftime(f"flight-%Y%m%d-%H%M%S-{os.getpid()}"
                              f"-{seq:04d}-{reason}.json"))
            doc = {"flight_recorder": True, "reason": reason,
                   "ts": round(time.time(), 3), "pid": os.getpid(),
                   "events": events}
            with open(path, "w") as f:
                json.dump(doc, f, indent=1)
            self.dumps_written += 1
            self._prune(directory)
            return path
        except Exception:
            try:
                log.warning("flight-recorder dump to %s failed",
                            directory, exc_info=True)
            except Exception:
                pass
            return None

    def _prune(self, directory: str) -> None:
        dumps = sorted(
            os.path.join(directory, name)
            for name in os.listdir(directory)
            if name.startswith("flight-") and name.endswith(".json"))
        for stale in dumps[:-self.MAX_DUMPS]:
            try:
                os.unlink(stale)
            except OSError:
                pass

    def reset(self) -> None:
        self._ring.clear()
        self.events_total = 0
        self.dumps_written = 0
        self.member = None


FLIGHT = FlightRecorder()


# ----------------------------------------------------------- SLO engine

class SloEngine:
    """Config-declared service objectives evaluated as multi-window
    burn rates (the Google SRE alerting form: error_rate /
    error_budget over a fast AND a slow window — both over threshold
    means the budget is burning fast enough, for long enough, to
    matter).

    Objectives:

    * ``availability`` — fraction of requests answering below 500
      (deliberate sheds and deadline 504s spend the budget: the user
      still did not get a tile);
    * ``latency`` — fraction of SUCCESSFUL requests under
      ``latency_ms`` (the p-target latency objective; errors are the
      availability objective's problem, not this one's).

    Time is bucketed (``BUCKET_S``) so the windows are O(window /
    bucket) memory and record() is a dict increment.  Disabled (the
    default — no targets configured) it costs one boolean check.
    A breach TRANSITION fires ``on_breach`` once per episode — the
    flight-recorder dump hook."""

    BUCKET_S = 5.0

    def __init__(self):
        self._lock = threading.Lock()
        self._clock = time.monotonic
        self.enabled = False
        self.availability_target = 0.0
        self.latency_ms = 0.0
        self.latency_target = 0.99
        self.fast_window_s = 60.0
        self.slow_window_s = 600.0
        self.breach_burn_rate = 14.4
        self.on_breach = None
        self.breached: Dict[str, bool] = {}
        self.breaches_total = 0
        # bucket index -> {"good": n, "bad": n, "fast": n, "slow": n}
        self._buckets: Dict[int, Dict[str, int]] = {}

    def configure(self, availability_target: float = 0.0,
                  latency_ms: float = 0.0,
                  latency_target: float = 0.99,
                  fast_window_s: float = 60.0,
                  slow_window_s: float = 600.0,
                  breach_burn_rate: float = 14.4,
                  on_breach=None, clock=time.monotonic) -> None:
        with self._lock:
            self.availability_target = availability_target
            self.latency_ms = latency_ms
            self.latency_target = latency_target
            self.fast_window_s = fast_window_s
            self.slow_window_s = max(slow_window_s, fast_window_s)
            self.breach_burn_rate = breach_burn_rate
            self.on_breach = on_breach
            self._clock = clock
            self.enabled = bool(availability_target or latency_ms)
            self._buckets.clear()
            self.breached = {}

    def _bucket(self, now: float) -> Dict[str, int]:
        idx = int(now // self.BUCKET_S)
        b = self._buckets.get(idx)
        if b is None:
            b = self._buckets[idx] = {"ok": 0, "err": 0,
                                      "fast": 0, "slow": 0}
            # Prune everything older than the slow window.
            floor = idx - int(self.slow_window_s // self.BUCKET_S) - 1
            for old in [i for i in self._buckets if i < floor]:
                del self._buckets[old]
        return b

    def record(self, status: int, dur_ms: float) -> None:
        if not self.enabled:
            return
        breach_cbs = []
        with self._lock:
            b = self._bucket(self._clock())
            if status >= 500:
                b["err"] += 1
            else:
                b["ok"] += 1
                if self.latency_ms:
                    if dur_ms <= self.latency_ms:
                        b["fast"] += 1
                    else:
                        b["slow"] += 1
            rates = self._burn_rates_locked()
            for objective, (fast, slow) in rates.items():
                now_breached = (fast >= self.breach_burn_rate
                                and slow >= self.breach_burn_rate)
                was = self.breached.get(objective, False)
                self.breached[objective] = now_breached
                if now_breached and not was:
                    self.breaches_total += 1
                    # Appended, not assigned: both objectives may
                    # transition on ONE record, and each breach owns
                    # its dump.
                    breach_cbs.append((objective, fast, slow))
        if self.on_breach is not None:
            for cb in breach_cbs:
                try:
                    self.on_breach(*cb)
                except Exception:  # forensics must never fail requests
                    log.warning("SLO on_breach hook failed",
                                exc_info=True)

    def _window_counts(self, window_s: float) -> Dict[str, int]:
        floor = int((self._clock() - window_s) // self.BUCKET_S)
        out = {"ok": 0, "err": 0, "fast": 0, "slow": 0}
        for idx, b in self._buckets.items():
            if idx >= floor:
                for k in out:
                    out[k] += b[k]
        return out

    def _burn_rates_locked(self) -> Dict[str, Tuple[float, float]]:
        rates: Dict[str, Tuple[float, float]] = {}

        def burn(bad: int, total: int, target: float) -> float:
            if total == 0:
                return 0.0
            budget = max(1e-9, 1.0 - target)
            return (bad / total) / budget

        pair = []
        for window_s in (self.fast_window_s, self.slow_window_s):
            pair.append(self._window_counts(window_s))
        if self.availability_target:
            rates["availability"] = tuple(
                burn(c["err"], c["ok"] + c["err"],
                     self.availability_target) for c in pair)
        if self.latency_ms:
            rates["latency"] = tuple(
                burn(c["slow"], c["fast"] + c["slow"],
                     self.latency_target) for c in pair)
        return rates

    def burn_rates(self) -> Dict[str, Tuple[float, float]]:
        """{objective: (fast_burn, slow_burn)} over the two windows."""
        with self._lock:
            return self._burn_rates_locked()

    def any_breached(self) -> bool:
        with self._lock:
            return any(self.breached.values())

    def summary(self) -> str:
        """One-line state for the /readyz annotation."""
        with self._lock:
            rates = self._burn_rates_locked()
            breached = [o for o, v in self.breached.items() if v]
        if not rates:
            return "disabled"
        parts = [f"{o} burn {fast:.1f}/{slow:.1f}"
                 for o, (fast, slow) in sorted(rates.items())]
        state = "BREACH " if breached else "ok "
        return state + ", ".join(parts)

    def metric_lines(self) -> List[str]:
        if not self.enabled:
            return []
        lines = []
        with self._lock:
            rates = self._burn_rates_locked()
            breached = dict(self.breached)
            breaches = self.breaches_total
        for objective, (fast, slow) in sorted(rates.items()):
            for window, rate in (("fast", fast), ("slow", slow)):
                lines.append(
                    f'imageregion_slo_burn_rate{{slo="{objective}",'
                    f'window="{window}"}} {round(rate, 4)}')
            lines.append(
                f'imageregion_slo_breach{{slo="{objective}"}} '
                f'{1 if breached.get(objective) else 0}')
        lines.append(f"imageregion_slo_breaches_total {breaches}")
        return lines

    def export_buckets(self) -> dict:
        """Wire-portable window state for fleet-level aggregation
        (``FleetSloStats``).  Bucket indices key off this process's
        monotonic clock, which means nothing on another host — so
        buckets cross the wire as AGES (seconds before this export),
        and the ingesting side re-anchors them against its own clock
        at ingest time.  Disabled engines export ``{}`` (the
        emit-when-live posture: a host with no objectives contributes
        nothing to the fleet burn)."""
        with self._lock:
            if not self.enabled:
                return {}
            now = self._clock()
            buckets = [
                [round(now - idx * self.BUCKET_S, 3),
                 b["ok"], b["err"], b["fast"], b["slow"]]
                for idx, b in sorted(self._buckets.items())
            ]
            return {
                "bucket_s": self.BUCKET_S,
                "availability_target": self.availability_target,
                "latency_ms": self.latency_ms,
                "latency_target": self.latency_target,
                "fast_window_s": self.fast_window_s,
                "slow_window_s": self.slow_window_s,
                "buckets": buckets,
            }

    def reset(self) -> None:
        with self._lock:
            self.enabled = False
            self.availability_target = 0.0
            self.latency_ms = 0.0
            self.on_breach = None
            self._clock = time.monotonic
            self._buckets.clear()
            self.breached = {}
            self.breaches_total = 0


SLO = SloEngine()


# ------------------------------------------------------ shape cost model

class ShapeCostModel:
    """Estimated vs observed device cost per compiled render shape.

    The batcher records every group's device-execute wall ms under its
    ladder-shape label, and (once per shape, best-effort) the XLA
    ``cost_analysis()`` flops/bytes estimate of the compiled program —
    so /metrics answers "is this shape running at the speed its
    program says it should" without a profiler attached.  Label
    cardinality is bounded by the bucket/batch ladder."""

    def __init__(self):
        self._lock = threading.Lock()
        self._shapes: Dict[str, dict] = {}
        self._claimed: set = set()

    def observe(self, shape: str, ms: float) -> None:
        with self._lock:
            s = self._shapes.get(shape)
            if s is None:
                s = self._shapes[shape] = {
                    "dispatches": 0, "ms_total": 0.0,
                    "est_flops": None, "est_bytes": None}
            s["dispatches"] += 1
            s["ms_total"] += ms

    def claim_estimate(self, shape: str) -> bool:
        """One-shot claim of the estimate capture for ``shape`` — True
        exactly once, so concurrent first groups of one shape spawn
        one capture, not one per lane."""
        with self._lock:
            if shape in self._claimed:
                return False
            self._claimed.add(shape)
            return True

    def set_estimate(self, shape: str, flops: Optional[float],
                     nbytes: Optional[float]) -> None:
        with self._lock:
            s = self._shapes.setdefault(shape, {
                "dispatches": 0, "ms_total": 0.0,
                "est_flops": None, "est_bytes": None})
            # 0.0 marks "capture attempted, nothing learned" so the
            # one-time hook never re-fires for the shape.
            s["est_flops"] = float(flops or 0.0)
            s["est_bytes"] = float(nbytes or 0.0)

    def snapshot(self) -> Dict[str, dict]:
        with self._lock:
            return {k: dict(v) for k, v in self._shapes.items()}

    def metric_lines(self, extra_labels: str = "") -> List[str]:
        lines = []
        extra = extra_labels.lstrip(",")
        with self._lock:
            items = sorted(self._shapes.items())
        for shape, s in items:
            lb = f'{{shape="{shape}"' + (f",{extra}" if extra
                                         else "") + "}"
            lines += [
                f"imageregion_shape_dispatches_total{lb} "
                f"{s['dispatches']}",
                f"imageregion_shape_device_ms_total{lb} "
                f"{round(s['ms_total'], 3)}",
            ]
            if s["dispatches"]:
                lines.append(
                    f"imageregion_shape_device_ms_mean{lb} "
                    f"{round(s['ms_total'] / s['dispatches'], 3)}")
            if s["est_flops"] is not None:
                lines += [
                    f"imageregion_shape_estimated_flops{lb} "
                    f"{_fmt(s['est_flops'])}",
                    f"imageregion_shape_estimated_bytes{lb} "
                    f"{_fmt(s['est_bytes'])}",
                ]
        return lines

    def reset(self) -> None:
        with self._lock:
            self._shapes.clear()
            self._claimed.clear()


SHAPE_COSTS = ShapeCostModel()


# ------------------------------------------------------ device profiling

class ProfileInProgressError(Exception):
    """A capture is already running (the endpoint answers 409)."""


_PROFILE_LOCK = threading.Lock()


class ProfileStats:
    """What the captures taken so far add up to (``/metrics``
    ``imageregion_profile_*``): device milliseconds by named stage,
    busy and traced milliseconds, idle milliseconds by what the host
    was doing, renders counted.  They move only when a capture is
    taken; one with no device plane (the CPU backend) moves
    ``captures`` alone.  Rules: ``utils.profile_summary``."""

    def __init__(self):
        self._lock = threading.Lock()
        self.reset()

    def reset(self) -> None:
        with self._lock:
            self.captures = 0
            self.busy_ms = self.traced_ms = 0.0
            self.renders = 0
            self.device_ms: Dict[str, float] = {}
            self.idle_ms: Dict[str, float] = {}

    def observe(self, summary: Optional[dict]) -> None:
        with self._lock:
            self.captures += 1
            if not summary:
                return
            self.busy_ms += summary["busy_ms"]
            self.traced_ms += summary["traced_ms"]
            self.renders += summary["renders"]
            for mine, theirs in ((self.device_ms, summary["device_ms"]),
                                 (self.idle_ms, summary["idle_ms"])):
                for key, ms in theirs.items():
                    mine[key] = mine.get(key, 0.0) + ms

    def metric_lines(self, extra_labels: str = "") -> List[str]:
        extra = extra_labels.lstrip(",")
        plain = f"{{{extra}}}" if extra else ""
        with self._lock:
            lines = [
                f"imageregion_profile_captures_total{plain} "
                f"{self.captures}",
                f"imageregion_profile_busy_ms_total{plain} "
                f"{round(self.busy_ms, 3)}",
                f"imageregion_profile_traced_ms_total{plain} "
                f"{round(self.traced_ms, 3)}",
                f"imageregion_profile_renders_total{plain} "
                f"{self.renders}",
            ]
            for family, label, values in (
                    ("device_ms", "stage", self.device_ms),
                    ("idle_ms", "during", self.idle_ms)):
                for key, ms in sorted(values.items()):
                    body = f'{label}="{key}"' + (f",{extra}" if extra
                                                 else "")
                    lines.append(f"imageregion_profile_{family}_total"
                                 f"{{{body}}} {round(ms, 3)}")
        return lines


PROFILE = ProfileStats()


class RouteStats:
    """Which way ``ImageRegionHandler`` sent each render it prepared:
    ``device`` (batcher -> chip) or ``host`` (``refimpl`` on a thread:
    regions of at most ``renderer.cpu-fallback-max-px`` pixels).
    ``/metrics imageregion_renders_routed_total{route=...}``; both
    series always present, so a share of them is never read from a
    missing one."""

    ROUTES = ("device", "host")

    def __init__(self):
        self._lock = threading.Lock()
        self.reset()

    def reset(self) -> None:
        with self._lock:
            self.counts = dict.fromkeys(self.ROUTES, 0)

    def count(self, route: str) -> None:
        with self._lock:
            self.counts[route] += 1

    def metric_lines(self, extra_labels: str = "") -> List[str]:
        extra = extra_labels.lstrip(",")
        with self._lock:
            return [
                f"imageregion_renders_routed_total{{route=\"{route}\""
                + (f",{extra}" if extra else "") + f"}} {n}"
                for route, n in self.counts.items()]


ROUTES = RouteStats()


class DeviceRenderStats:
    """Renders by the device that ran them, read off each group's
    output array when its render returns (``BatchingRenderer``): the
    chip a fleet member is pinned to, as the program found it, not as
    the code asked.  ``/metrics imageregion_device_renders_total
    {device="<id>"}``; a device appears once it has rendered."""

    def __init__(self):
        self._lock = threading.Lock()
        self.reset()

    def reset(self) -> None:
        with self._lock:
            self.counts: Dict[int, int] = {}

    def count(self, device: int, renders: int) -> None:
        with self._lock:
            self.counts[device] = self.counts.get(device, 0) + renders

    def metric_lines(self, extra_labels: str = "") -> List[str]:
        extra = extra_labels.lstrip(",")
        with self._lock:
            return [
                f"imageregion_device_renders_total{{device=\"{device}\""
                + (f",{extra}" if extra else "") + f"}} {n}"
                for device, n in sorted(self.counts.items())]


DEVICE_RENDERS = DeviceRenderStats()


class DuplicateLoadStats:
    """Channel planes that ``DeviceRawCache.get_or_load`` read and
    uploaded although another thread had loaded the same key during
    this read (the cache has no single flight: both loads ran, the
    later insert replaced the earlier one), by who lost the race:
    ``prefetch`` (``services.prefetch``'s pool) or ``request`` (every
    other caller).  ``/metrics
    imageregion_rawcache_duplicate_loads_total{by=...}``; both series
    always present."""

    BY = ("prefetch", "request")

    def __init__(self):
        self._lock = threading.Lock()
        self.reset()

    def reset(self) -> None:
        with self._lock:
            self.counts = dict.fromkeys(self.BY, 0)

    def count(self, by: str) -> None:
        with self._lock:
            self.counts[by] += 1

    def metric_lines(self, extra_labels: str = "") -> List[str]:
        extra = extra_labels.lstrip(",")
        with self._lock:
            return [
                "imageregion_rawcache_duplicate_loads_total"
                f"{{by=\"{by}\"" + (f",{extra}" if extra else "")
                + f"}} {n}" for by, n in self.counts.items()]


DUPLICATE_LOADS = DuplicateLoadStats()


class TileRenderStats:
    """What the handler prepared, counted where it resolves the region:
    tile requests by the pyramid level read and by whether the region,
    truncated at the level's edge, is the whole tile asked for
    (``full``) or smaller (``edge``), whatever bucket it is padded to
    later; and the unpadded pixels of every region rendered, tile or
    not.  ``/metrics imageregion_tile_renders_total{level, edge}`` (a
    series appears once counted; levels are bounded by the budget's
    16) and ``imageregion_render_pixels_total``."""

    EDGES = ("full", "edge")
    MAX_LEVELS = 16

    def __init__(self):
        self._lock = threading.Lock()
        self.reset()

    def reset(self) -> None:
        with self._lock:
            self.tiles: Dict[tuple, int] = {}
            self.pixels = 0

    def count(self, pixels: int, level: Optional[int] = None,
              edge: Optional[bool] = None) -> None:
        """One render of ``pixels``; a tile's ``level`` and ``edge``
        where it is one (levels past the bound share the last label)."""
        with self._lock:
            self.pixels += pixels
            if level is not None:
                key = (min(level, self.MAX_LEVELS - 1),
                       self.EDGES[bool(edge)])
                self.tiles[key] = self.tiles.get(key, 0) + 1

    def metric_lines(self, extra_labels: str = "") -> List[str]:
        extra = extra_labels.lstrip(",")
        more = f",{extra}" if extra else ""
        with self._lock:
            lines = [
                f"imageregion_tile_renders_total{{level=\"{level}\","
                f"edge=\"{edge}\"{more}}} {n}"
                for (level, edge), n in sorted(self.tiles.items())]
            lines.append("imageregion_render_pixels_total"
                         + (f"{{{extra}}}" if extra else "")
                         + f" {self.pixels}")
        return lines


TILE_RENDERS = TileRenderStats()


def capture_profile(directory: str, ms: float) -> dict:
    """Wrap ``jax.profiler`` around whatever the device is doing for
    ``ms`` milliseconds; returns the artifact manifest with the
    capture's ``summary`` (``utils.profile_summary``), which also
    accumulates on ``PROFILE``.

    The session runs without the Python tracer (its events, by the
    hundred thousand, were most of a capture's bytes and of its
    stop time, and nothing read them) and with the host tracer at the
    level that keeps the program's own annotations.

    Single-flight (`ProfileInProgressError` when one is live —
    concurrent captures would interleave one trace file), blocking
    (call via a worker thread), and the ONE telemetry function besides
    the compile listener that imports JAX — only device-owning
    processes serve it (frontends forward over the sidecar wire)."""
    if not _PROFILE_LOCK.acquire(blocking=False):
        raise ProfileInProgressError("a profile capture is already "
                                     "running")
    try:
        import jax
        from . import profile_summary
        seq = next(_ARTIFACT_SEQ)
        path = os.path.join(
            directory,
            time.strftime(f"profile-%Y%m%d-%H%M%S-{seq:04d}"))
        os.makedirs(path, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        t0 = time.perf_counter()
        jax.profiler.start_trace(path, profiler_options=options)
        try:
            time.sleep(max(0.0, ms) / 1000.0)
        finally:
            jax.profiler.stop_trace()
        files = []
        total = 0
        for root, _dirs, names in os.walk(path):
            for name in names:
                full = os.path.join(root, name)
                files.append(os.path.relpath(full, path))
                try:
                    total += os.path.getsize(full)
                except OSError:
                    pass
        doc = {"dir": path, "requested_ms": ms, "files": sorted(files),
               "bytes": total}
        summary = None
        t_summary = time.perf_counter()
        try:
            xplane = profile_summary.find_xplane(path)
            if xplane is not None:
                summary = profile_summary.summarize(
                    *profile_summary.read_capture(xplane))
        except Exception as e:
            # The artifact stands without its reduction.
            log.warning("profile summary of %s failed", path,
                        exc_info=True)
            doc["summary_error"] = repr(e)
        PROFILE.observe(summary)
        doc["summary"] = summary
        doc["summary_ms"] = round(
            (time.perf_counter() - t_summary) * 1000.0, 1)
        FLIGHT.record("profile.captured", dir=path,
                      ms=round(ms, 1), files=len(files))
        doc["ms"] = round((time.perf_counter() - t0) * 1000.0, 1)
        return doc
    finally:
        _PROFILE_LOCK.release()


def install_compile_listener() -> bool:
    """Register the jax.monitoring listener (device processes only —
    this is the one function here that imports JAX).  Idempotent;
    returns whether the listener is active."""
    global _compile_listener_installed
    with _COMPILE_LISTENER:
        if _compile_listener_installed:
            return True
        try:
            from jax import monitoring
        except Exception:       # pragma: no cover - jax-free frontends
            return False

        def _on_event(event: str, duration: float, **kw) -> None:
            # backend_compile is the actual XLA compile; trace/lowering
            # events would double-count the same program.
            if "backend_compile" in event:
                COMPILE.observe(duration)

        def _on_plain_event(event: str, **kw) -> None:
            if event == "/jax/compilation_cache/cache_hits":
                COMPILE.observe_cache_hit()

        monitoring.register_event_duration_secs_listener(_on_event)
        monitoring.register_event_listener(_on_plain_event)
        _compile_listener_installed = True
        return True


# --------------------------------------------------------------- resilience

class Resilience:
    """Fault-tolerance accounting behind /metrics: sheds, deadline
    cancellations, sidecar retries, degraded-mode renders, supervisor
    restarts.  Thread-safe — the batcher's worker threads and the
    supervisor's monitor thread both count here."""

    def __init__(self):
        self._lock = threading.Lock()
        self.shed: Dict[str, int] = {}            # reason -> count
        self.retries: Dict[str, int] = {}         # op -> retry count
        self.deadline_cancelled = 0
        self.degraded_renders = 0
        self.supervisor_restarts = 0
        # Attempts actually used per sidecar call, by op (a histogram,
        # not a mean: "most calls take 1, a few take 3" is the signal).
        self.attempts_hist = HistogramVec("op")

    def count_shed(self, reason: str = "queue-full") -> None:
        with self._lock:
            self.shed[reason] = self.shed.get(reason, 0) + 1

    def count_retry(self, op: str) -> None:
        with self._lock:
            self.retries[op] = self.retries.get(op, 0) + 1

    def count_deadline_cancelled(self, n: int = 1) -> None:
        with self._lock:
            self.deadline_cancelled += n

    def count_degraded_render(self) -> None:
        with self._lock:
            self.degraded_renders += 1

    def count_supervisor_restart(self) -> None:
        with self._lock:
            self.supervisor_restarts += 1

    def observe_attempts(self, op: str, attempts: int) -> None:
        self.attempts_hist.observe(op, float(attempts))

    def reset(self) -> None:
        with self._lock:
            self.shed.clear()
            self.retries.clear()
            self.deadline_cancelled = 0
            self.degraded_renders = 0
            self.supervisor_restarts = 0
        self.attempts_hist.reset()


RESILIENCE = Resilience()


# ------------------------------------------------------ warm persistence

class Persistence:
    """Warm-state persistence accounting (services.diskcache +
    services.warmstate + server.execcache): disk byte-cache write/
    corruption counters, snapshot age/duration, and live rehydrate
    progress.  Thread-safe — the disk tier's write-behind worker, the
    snapshot timer thread and the boot rehydrator all count here; the
    scrape path only reads."""

    def __init__(self):
        self._lock = threading.Lock()
        # Disk byte-cache tier (services.diskcache.DiskByteCache).
        self.diskcache_writes = 0
        self.diskcache_write_errors = 0
        self.diskcache_write_dropped = 0
        self.diskcache_corrupt = 0
        self.diskcache_bytes = 0          # gauge (set by the cache)
        self.diskcache_entries = 0        # gauge
        # Snapshot engine (services.warmstate).
        self.snapshots = 0
        self.snapshot_errors = 0
        self.snapshot_last_ts = 0.0       # wall clock of the last write
        self.snapshot_duration_ms = 0.0
        # Boot rehydrator progress (the /readyz annotation + gauges).
        self.rehydrate_running = False
        self.rehydrate_items_total = 0
        self.rehydrate_items_done = 0
        self.rehydrate_errors = 0
        self.rehydrate_aborted = False
        self.rehydrate_duration_ms = 0.0
        self.rehydrate_bytes_promoted = 0
        self.rehydrate_planes_restaged = 0
        self.rehydrate_executables_loaded = 0

    # ------------------------------------------------------- disk tier

    def count_disk_write(self, error: bool = False,
                         dropped: bool = False) -> None:
        with self._lock:
            if dropped:
                self.diskcache_write_dropped += 1
            elif error:
                self.diskcache_write_errors += 1
            else:
                self.diskcache_writes += 1

    def count_disk_corrupt(self) -> None:
        with self._lock:
            self.diskcache_corrupt += 1
        FLIGHT.record("diskcache.corrupt")

    def set_disk_size(self, nbytes: int, entries: int) -> None:
        with self._lock:
            self.diskcache_bytes = int(nbytes)
            self.diskcache_entries = int(entries)

    # -------------------------------------------------------- snapshot

    def count_snapshot(self, duration_ms: float,
                       error: bool = False) -> None:
        with self._lock:
            if error:
                self.snapshot_errors += 1
                return
            self.snapshots += 1
            self.snapshot_last_ts = time.time()
            self.snapshot_duration_ms = float(duration_ms)

    # ------------------------------------------------------- rehydrate

    def rehydrate_begin(self, items_total: int) -> None:
        with self._lock:
            self.rehydrate_running = True
            self.rehydrate_aborted = False
            self.rehydrate_items_total = int(items_total)
            self.rehydrate_items_done = 0

    def rehydrate_step(self, kind: str = "", nbytes: int = 0,
                       error: bool = False) -> None:
        with self._lock:
            self.rehydrate_items_done += 1
            if error:
                self.rehydrate_errors += 1
                return
            if kind == "byte":
                self.rehydrate_bytes_promoted += int(nbytes)
            elif kind == "plane":
                self.rehydrate_planes_restaged += 1
            elif kind == "executable":
                self.rehydrate_executables_loaded += 1

    def rehydrate_end(self, duration_ms: float,
                      aborted: bool = False) -> None:
        with self._lock:
            self.rehydrate_running = False
            self.rehydrate_aborted = bool(aborted)
            self.rehydrate_duration_ms = float(duration_ms)

    def rehydrate_summary(self) -> str:
        """One-line state for the /readyz annotation (rehydrate is
        best-effort: never a readiness failure, always visible)."""
        with self._lock:
            if self.rehydrate_running:
                return (f"running {self.rehydrate_items_done}"
                        f"/{self.rehydrate_items_total}")
            if self.rehydrate_aborted:
                return (f"aborted {self.rehydrate_items_done}"
                        f"/{self.rehydrate_items_total}")
            if self.rehydrate_items_total:
                return (f"done {self.rehydrate_items_done}"
                        f"/{self.rehydrate_items_total}")
        return "idle"

    def metric_lines(self, extra_labels: str = "") -> List[str]:
        def label() -> str:
            inner = extra_labels.lstrip(",")
            return f"{{{inner}}}" if inner else ""

        lb = label()
        with self._lock:
            age_s = (time.time() - self.snapshot_last_ts
                     if self.snapshot_last_ts else 0.0)
            return [
                f"imageregion_diskcache_writes_total{lb} "
                f"{self.diskcache_writes}",
                f"imageregion_diskcache_write_errors_total{lb} "
                f"{self.diskcache_write_errors}",
                f"imageregion_diskcache_write_dropped_total{lb} "
                f"{self.diskcache_write_dropped}",
                f"imageregion_diskcache_corrupt_total{lb} "
                f"{self.diskcache_corrupt}",
                f"imageregion_diskcache_bytes{lb} "
                f"{self.diskcache_bytes}",
                f"imageregion_diskcache_entries{lb} "
                f"{self.diskcache_entries}",
                f"imageregion_warmstate_snapshots_total{lb} "
                f"{self.snapshots}",
                f"imageregion_warmstate_snapshot_errors_total{lb} "
                f"{self.snapshot_errors}",
                f"imageregion_warmstate_snapshot_age_seconds{lb} "
                f"{round(age_s, 3)}",
                f"imageregion_warmstate_snapshot_duration_ms{lb} "
                f"{round(self.snapshot_duration_ms, 3)}",
                f"imageregion_rehydrate_running{lb} "
                f"{1 if self.rehydrate_running else 0}",
                f"imageregion_rehydrate_items_total{lb} "
                f"{self.rehydrate_items_total}",
                f"imageregion_rehydrate_items_done{lb} "
                f"{self.rehydrate_items_done}",
                f"imageregion_rehydrate_errors_total{lb} "
                f"{self.rehydrate_errors}",
                f"imageregion_rehydrate_duration_ms{lb} "
                f"{round(self.rehydrate_duration_ms, 3)}",
                f"imageregion_rehydrate_bytes_promoted_total{lb} "
                f"{self.rehydrate_bytes_promoted}",
                f"imageregion_rehydrate_planes_restaged_total{lb} "
                f"{self.rehydrate_planes_restaged}",
                f"imageregion_rehydrate_executables_loaded_total{lb} "
                f"{self.rehydrate_executables_loaded}",
            ]

    def reset(self) -> None:
        with self._lock:
            self.diskcache_writes = 0
            self.diskcache_write_errors = 0
            self.diskcache_write_dropped = 0
            self.diskcache_corrupt = 0
            self.diskcache_bytes = 0
            self.diskcache_entries = 0
            self.snapshots = 0
            self.snapshot_errors = 0
            self.snapshot_last_ts = 0.0
            self.snapshot_duration_ms = 0.0
            self.rehydrate_running = False
            self.rehydrate_items_total = 0
            self.rehydrate_items_done = 0
            self.rehydrate_errors = 0
            self.rehydrate_aborted = False
            self.rehydrate_duration_ms = 0.0
            self.rehydrate_bytes_promoted = 0
            self.rehydrate_planes_restaged = 0
            self.rehydrate_executables_loaded = 0


PERSIST = Persistence()


def resilience_metric_lines(breaker=None,
                            extra_labels: str = "") -> List[str]:
    """The fault-tolerance series.  ``breaker`` is the sidecar client's
    CircuitBreaker (frontend processes only; None omits the gauge)."""
    def label(body: str = "") -> str:
        inner = body + (("," if body else "")
                        + extra_labels.lstrip(",") if extra_labels
                        else "")
        return f"{{{inner}}}" if inner else ""

    lines: List[str] = []
    if breaker is not None:
        # 0 closed / 1 half-open / 2 open (utils.transient enum order).
        lines += [
            f"imageregion_breaker_state{label()} {breaker.state}",
            f"imageregion_breaker_opens_total{label()} {breaker.opens}",
        ]
    with RESILIENCE._lock:
        shed = sorted(RESILIENCE.shed.items())
        retries = sorted(RESILIENCE.retries.items())
        deadline_cancelled = RESILIENCE.deadline_cancelled
        degraded = RESILIENCE.degraded_renders
        restarts = RESILIENCE.supervisor_restarts
    for reason, n in shed:
        body = f'reason="{reason}"'
        lines.append(f"imageregion_shed_total{label(body)} {n}")
    for op, n in retries:
        body = f'op="{op}"'
        lines.append(f"imageregion_retries_total{label(body)} {n}")
    lines += [
        f"imageregion_deadline_cancelled_total{label()} "
        f"{deadline_cancelled}",
        f"imageregion_degraded_renders_total{label()} {degraded}",
        f"imageregion_supervisor_restarts_total{label()} {restarts}",
    ]
    if not extra_labels:
        # The per-op attempts histogram composes its own labels; the
        # sidecar merge path (extra_labels) skips it rather than emit
        # label-mangled series.
        lines += RESILIENCE.attempts_hist.series(
            "imageregion_retry_attempts")
    return lines


# ------------------------------------------------------------- wire stats

class WireStats:
    """Sidecar wire transport accounting (protocol v3): vectored-flush
    coalescing, the same-host shared-memory ring, and progressive chunk
    streaming.  Thread-safe — the client and server frame writers run
    on event loops, but smoke benches read concurrently."""

    def __init__(self):
        self._lock = threading.Lock()
        # Scatter-gather flushes: one writelines + one drain each.
        self.flushes = 0
        self.frames_flushed = 0
        self.flush_bytes = 0
        # Same-host ring: bodies that rode it vs fell back to the
        # socket (exhaustion / no negotiated ring for that size class).
        self.ring_hits = 0
        self.ring_fallbacks = 0
        self.ring_bytes = 0
        # Handshakes: connections that negotiated a ring vs degraded.
        self.ring_negotiated = 0
        self.ring_declined = 0
        # Progressive streaming: responses sent as chunk frames.
        self.streams = 0
        self.chunks = 0

    def observe_flush(self, frames: int, nbytes: int) -> None:
        with self._lock:
            self.flushes += 1
            self.frames_flushed += int(frames)
            self.flush_bytes += int(nbytes)

    def count_ring(self, nbytes: int, hit: bool) -> None:
        with self._lock:
            if hit:
                self.ring_hits += 1
                self.ring_bytes += int(nbytes)
            else:
                self.ring_fallbacks += 1

    def count_negotiation(self, ring: bool) -> None:
        with self._lock:
            if ring:
                self.ring_negotiated += 1
            else:
                self.ring_declined += 1

    def count_stream(self, chunks: int) -> None:
        with self._lock:
            self.streams += 1
            self.chunks += int(chunks)

    def frames_per_flush(self) -> Optional[float]:
        """Mean frames per vectored flush — >1 under concurrent load
        means the coalescer is actually amortizing syscalls/RTTs."""
        with self._lock:
            if not self.flushes:
                return None
            return self.frames_flushed / self.flushes

    def ring_hit_rate(self) -> Optional[float]:
        """Of the bodies eligible for the ring, the fraction that rode
        it (None until anything was eligible)."""
        with self._lock:
            total = self.ring_hits + self.ring_fallbacks
            if not total:
                return None
            return self.ring_hits / total

    def metric_lines(self, extra_labels: str = "") -> List[str]:
        def label() -> str:
            inner = extra_labels.lstrip(",")
            return f"{{{inner}}}" if inner else ""

        lb = label()
        with self._lock:
            fpf = (self.frames_flushed / self.flushes
                   if self.flushes else 0.0)
            return [
                f"imageregion_wire_flushes_total{lb} {self.flushes}",
                f"imageregion_wire_frames_total{lb} "
                f"{self.frames_flushed}",
                f"imageregion_wire_flush_bytes_total{lb} "
                f"{self.flush_bytes}",
                f"imageregion_wire_frames_per_flush{lb} "
                f"{round(fpf, 3)}",
                f"imageregion_wire_ring_hits_total{lb} "
                f"{self.ring_hits}",
                f"imageregion_wire_ring_fallbacks_total{lb} "
                f"{self.ring_fallbacks}",
                f"imageregion_wire_ring_bytes_total{lb} "
                f"{self.ring_bytes}",
                f"imageregion_wire_ring_negotiated_total{lb} "
                f"{self.ring_negotiated}",
                f"imageregion_wire_ring_declined_total{lb} "
                f"{self.ring_declined}",
                f"imageregion_wire_streams_total{lb} {self.streams}",
                f"imageregion_wire_chunks_total{lb} {self.chunks}",
            ]

    def reset(self) -> None:
        with self._lock:
            self.flushes = 0
            self.frames_flushed = 0
            self.flush_bytes = 0
            self.ring_hits = 0
            self.ring_fallbacks = 0
            self.ring_bytes = 0
            self.ring_negotiated = 0
            self.ring_declined = 0
            self.streams = 0
            self.chunks = 0


WIRE = WireStats()


def wire_metric_lines(extra_labels: str = "") -> List[str]:
    """The wire transport series; both sides of the socket emit a copy
    (the sidecar's merges with ``process="sidecar"`` labels)."""
    return WIRE.metric_lines(extra_labels)


# -------------------------------------------------------------------- fleet

class FleetStats:
    """Fleet-routing accounting (``parallel.fleet``): per-member
    routed/stolen/failed-over counters.  The ``member`` label set is
    closed by construction — member names come from config, bounded by
    ``_MAX_MEMBERS`` as a hard cardinality guard against a buggy
    caller minting names per request."""

    _MAX_MEMBERS = 64

    def __init__(self):
        self._lock = threading.Lock()
        self.routed: Dict[str, int] = {}
        self.stolen: Dict[str, int] = {}
        self.failed_over: Dict[str, int] = {}

    def _bump(self, table: Dict[str, int], member: str) -> None:
        with self._lock:
            if member not in table and len(table) >= self._MAX_MEMBERS:
                member = "_overflow"
            table[member] = table.get(member, 0) + 1

    def count_routed(self, member: str) -> None:
        self._bump(self.routed, member)

    def count_stolen(self, member: str) -> None:
        """``member`` is the STEALER: the lane that rendered skewed
        work from source bytes without adopting cache ownership."""
        self._bump(self.stolen, member)

    def count_failed_over(self, member: str) -> None:
        """``member`` is the hash-ring-next target that ADOPTED a dead
        member's shard work."""
        self._bump(self.failed_over, member)

    def totals(self) -> Dict[str, int]:
        with self._lock:
            return {
                "routed": sum(self.routed.values()),
                "stolen": sum(self.stolen.values()),
                "failed_over": sum(self.failed_over.values()),
            }

    def metric_lines(self, extra_labels: str = "") -> List[str]:
        extra = extra_labels.lstrip(",")

        def label(member: str) -> str:
            inner = f'member="{member}"' + (("," + extra) if extra
                                            else "")
            return "{" + inner + "}"

        lines: List[str] = []
        with self._lock:
            for fam, table in (
                    ("imageregion_fleet_routed_total", self.routed),
                    ("imageregion_fleet_stolen_total", self.stolen),
                    ("imageregion_fleet_failed_over_total",
                     self.failed_over)):
                for member in sorted(table):
                    lines.append(
                        f"{fam}{label(member)} {table[member]}")
        return lines

    def reset(self) -> None:
        with self._lock:
            self.routed.clear()
            self.stolen.clear()
            self.failed_over.clear()


FLEET = FleetStats()


class HotkeyStats:
    """Hot-plane replication accounting (``parallel.fleet``'s
    popularity tier): promotion/demotion lifecycle counters, replica
    staging volume, the never-double-stage violation counter (held at
    0 by the bench gate), per-member balanced-read counters (closed
    label set like :class:`FleetStats`), and the hot-route /
    replica-pressure gauges the autoscaler and runbook read."""

    _MAX_MEMBERS = 64

    def __init__(self):
        self._lock = threading.Lock()
        self.promoted = 0
        self.demoted = 0
        self.staged = 0
        self.duplicate_staged = 0
        self.balanced: Dict[str, int] = {}
        self.hot_routes = 0
        self.replica_pressure = 0.0

    def count_promoted(self) -> None:
        with self._lock:
            self.promoted += 1

    def count_demoted(self) -> None:
        with self._lock:
            self.demoted += 1

    def count_staged(self, n: int = 1) -> None:
        with self._lock:
            self.staged += int(n)

    def count_duplicate_staged(self) -> None:
        with self._lock:
            self.duplicate_staged += 1

    def count_balanced(self, member: str) -> None:
        """``member`` is a NON-OWNER replica that served a balanced
        read (owner-served reads are plain routed traffic)."""
        with self._lock:
            if member not in self.balanced \
                    and len(self.balanced) >= self._MAX_MEMBERS:
                member = "_overflow"
            self.balanced[member] = self.balanced.get(member, 0) + 1

    def set_hot_routes(self, n: int) -> None:
        with self._lock:
            self.hot_routes = int(n)

    def set_pressure(self, value: float) -> None:
        with self._lock:
            self.replica_pressure = float(value)

    def totals(self) -> Dict[str, float]:
        with self._lock:
            return {
                "promoted": self.promoted,
                "demoted": self.demoted,
                "staged": self.staged,
                "duplicate_staged": self.duplicate_staged,
                "balanced": sum(self.balanced.values()),
                "hot_routes": self.hot_routes,
                "replica_pressure": self.replica_pressure,
            }

    def metric_lines(self, extra_labels: str = "") -> List[str]:
        extra = extra_labels.lstrip(",")
        suffix = ("{" + extra + "}") if extra else ""

        def label(member: str) -> str:
            inner = f'member="{member}"' + (("," + extra) if extra
                                            else "")
            return "{" + inner + "}"

        lines: List[str] = []
        with self._lock:
            if not (self.promoted or self.demoted or self.staged
                    or self.duplicate_staged or self.balanced
                    or self.hot_routes or self.replica_pressure):
                return lines       # tier never engaged: no series
            lines.append("imageregion_hotkey_promotions_total"
                         f"{suffix} {self.promoted}")
            lines.append("imageregion_hotkey_demotions_total"
                         f"{suffix} {self.demoted}")
            lines.append("imageregion_hotkey_replica_staged_total"
                         f"{suffix} {self.staged}")
            lines.append("imageregion_hotkey_duplicate_staged_total"
                         f"{suffix} {self.duplicate_staged}")
            lines.append(f"imageregion_hotkey_hot_routes{suffix} "
                         f"{self.hot_routes}")
            lines.append("imageregion_hotkey_replica_pressure"
                         f"{suffix} {self.replica_pressure:.3f}")
            for member in sorted(self.balanced):
                lines.append("imageregion_hotkey_balanced_total"
                             f"{label(member)} "
                             f"{self.balanced[member]}")
        return lines

    def reset(self) -> None:
        with self._lock:
            self.promoted = 0
            self.demoted = 0
            self.staged = 0
            self.duplicate_staged = 0
            self.balanced.clear()
            self.hot_routes = 0
            self.replica_pressure = 0.0


HOTKEY = HotkeyStats()


# -------------------------------------------------- self-preservation

class PressureStats:
    """Resource-pressure governor accounting (``server.pressure``):
    the folded pressure level, the raw per-signal readings, and the
    brownout ladder's engaged set + transition counters.  Label sets
    are closed by construction — signal names come from the sampler's
    fixed set, step names from the config-validated ladder."""

    LEVELS = ("ok", "elevated", "critical")

    def __init__(self):
        self._lock = threading.Lock()
        self.level = 0                       # index into LEVELS
        self.signals: Dict[str, float] = {}
        self.steps_engaged: Dict[str, int] = {}    # step -> 0/1
        self.step_transitions: Dict[Tuple[str, str], int] = {}
        self.level_transitions = 0

    def set_level(self, level: int) -> None:
        with self._lock:
            if level != self.level:
                self.level_transitions += 1
            self.level = level

    def set_signal(self, name: str, value: float) -> None:
        with self._lock:
            self.signals[name] = float(value)

    def set_step(self, step: str, engaged: bool) -> None:
        with self._lock:
            self.steps_engaged[step] = 1 if engaged else 0
            key = (step, "engage" if engaged else "release")
            self.step_transitions[key] = \
                self.step_transitions.get(key, 0) + 1

    def declare_steps(self, steps) -> None:
        """Pre-register the ladder so every step's gauge exists from
        scrape one (a step that never engaged must read 0, not be
        absent)."""
        with self._lock:
            for step in steps:
                self.steps_engaged.setdefault(step, 0)

    def metric_lines(self, extra_labels: str = "") -> List[str]:
        extra = extra_labels.lstrip(",")

        def label(body: str = "") -> str:
            inner = ",".join(p for p in (body, extra) if p)
            return ("{" + inner + "}") if inner else ""

        with self._lock:
            lines = [
                f"imageregion_pressure_level{label()} {self.level}",
                f"imageregion_pressure_level_transitions_total"
                f"{label()} {self.level_transitions}",
                f"imageregion_pressure_steps_engaged{label()} "
                f"{sum(self.steps_engaged.values())}",
            ]
            for name in sorted(self.signals):
                body = 'signal="%s"' % name
                lines.append(
                    f"imageregion_pressure_signal{label(body)} "
                    f"{_fmt(self.signals[name])}")
            for step in sorted(self.steps_engaged):
                body = 'step="%s"' % step
                lines.append(
                    f"imageregion_pressure_step_engaged{label(body)} "
                    f"{self.steps_engaged[step]}")
            for (step, action) in sorted(self.step_transitions):
                body = 'step="%s",action="%s"' % (step, action)
                lines.append(
                    f"imageregion_pressure_step_transitions_total"
                    f"{label(body)} "
                    f"{self.step_transitions[(step, action)]}")
        return lines

    def reset(self) -> None:
        with self._lock:
            self.level = 0
            self.signals.clear()
            self.steps_engaged.clear()
            self.step_transitions.clear()
            self.level_transitions = 0


PRESSURE = PressureStats()


class WatchdogStats:
    """Watchdog accounting (``server.watchdog``): fires by healing
    action.  The ``action`` label set is closed — actions are the
    watchdog's own fixed vocabulary (requeue-group, drop-connection,
    escalate), never caller-minted."""

    def __init__(self):
        self._lock = threading.Lock()
        self.fires: Dict[str, int] = {}

    def count_fire(self, action: str) -> None:
        with self._lock:
            self.fires[action] = self.fires.get(action, 0) + 1

    def totals(self) -> Dict[str, int]:
        with self._lock:
            return dict(self.fires)

    def metric_lines(self, extra_labels: str = "") -> List[str]:
        extra = extra_labels.lstrip(",")
        lines: List[str] = []
        with self._lock:
            for action in sorted(self.fires):
                inner = f'action="{action}"' + (("," + extra) if extra
                                                else "")
                lines.append(
                    f"imageregion_watchdog_fires_total{{{inner}}} "
                    f"{self.fires[action]}")
        return lines

    def reset(self) -> None:
        with self._lock:
            self.fires.clear()


WATCHDOG = WatchdogStats()


class DrainStats:
    """Rolling-drain accounting (``parallel.fleet`` drains): per-member
    drain state and the handoff pre-stage counter.  Member names come
    from config (same closed set as FleetStats), bounded by the same
    hard cardinality guard."""

    _MAX_MEMBERS = 64
    STATES = ("active", "draining", "drained")

    def __init__(self):
        self._lock = threading.Lock()
        self.state: Dict[str, int] = {}      # member -> STATES index
        self.transitions: Dict[str, int] = {}
        self.prestaged_planes = 0
        self.drains_total = 0

    def set_state(self, member: str, state: str) -> None:
        idx = self.STATES.index(state)
        with self._lock:
            if member not in self.state \
                    and len(self.state) >= self._MAX_MEMBERS:
                member = "_overflow"
            if self.state.get(member) != idx:
                self.transitions[member] = \
                    self.transitions.get(member, 0) + 1
            self.state[member] = idx
            if state == "drained":
                self.drains_total += 1

    def count_prestaged(self, n: int) -> None:
        with self._lock:
            self.prestaged_planes += n

    def metric_lines(self, extra_labels: str = "") -> List[str]:
        extra = extra_labels.lstrip(",")

        def label(body: str = "") -> str:
            inner = ",".join(p for p in (body, extra) if p)
            return ("{" + inner + "}") if inner else ""

        with self._lock:
            lines = [
                f"imageregion_drain_prestaged_planes_total{label()} "
                f"{self.prestaged_planes}",
                f"imageregion_drains_total{label()} "
                f"{self.drains_total}",
            ]
            for member in sorted(self.state):
                body = 'member="%s"' % member
                lines.append(
                    f"imageregion_drain_state{label(body)} "
                    f"{self.state[member]}")
            for member in sorted(self.transitions):
                body = 'member="%s"' % member
                lines.append(
                    f"imageregion_drain_transitions_total{label(body)} "
                    f"{self.transitions[member]}")
        return lines

    def reset(self) -> None:
        with self._lock:
            self.state.clear()
            self.transitions.clear()
            self.prestaged_planes = 0
            self.drains_total = 0


DRAIN = DrainStats()


class AutoscalerStats:
    """Elastic-autoscaler accounting (``server.autoscaler``): the
    active-member gauge and floor/ceiling bounds, transitions by
    direction, and refused decisions by reason.  Both label sets are
    closed by construction — ``action`` is up/down, ``reason`` is
    ``autoscaler.BLOCKED_REASONS`` verbatim."""

    def __init__(self):
        self._lock = threading.Lock()
        self.active = 0
        self.floor = 0
        self.ceiling = 0
        self.transitions: Dict[str, int] = {}
        self.blocked: Dict[str, int] = {}

    def set_active(self, n: int) -> None:
        with self._lock:
            self.active = int(n)

    def set_bounds(self, floor: int, ceiling: int) -> None:
        with self._lock:
            self.floor = int(floor)
            self.ceiling = int(ceiling)

    def count_transition(self, action: str) -> None:
        with self._lock:
            self.transitions[action] = \
                self.transitions.get(action, 0) + 1

    def count_blocked(self, reason: str) -> None:
        with self._lock:
            self.blocked[reason] = self.blocked.get(reason, 0) + 1

    def metric_lines(self, extra_labels: str = "") -> List[str]:
        extra = extra_labels.lstrip(",")

        def label(body: str = "") -> str:
            inner = ",".join(p for p in (body, extra) if p)
            return ("{" + inner + "}") if inner else ""

        with self._lock:
            if not (self.active or self.transitions or self.blocked):
                # Quiet until an autoscaler is live (emit-when-live,
                # the httpcache posture — keeps non-fleet expositions
                # and the reset() contract exact).
                return []
            lines = [
                f"imageregion_autoscaler_active_members{label()} "
                f"{self.active}",
                f"imageregion_autoscaler_floor{label()} {self.floor}",
                f"imageregion_autoscaler_ceiling{label()} "
                f"{self.ceiling}",
            ]
            for action in sorted(self.transitions):
                body = 'action="%s"' % action
                lines.append(
                    f"imageregion_autoscaler_transitions_total"
                    f"{label(body)} {self.transitions[action]}")
            for reason in sorted(self.blocked):
                body = 'reason="%s"' % reason
                lines.append(
                    f"imageregion_autoscaler_blocked_total"
                    f"{label(body)} {self.blocked[reason]}")
        return lines

    def reset(self) -> None:
        with self._lock:
            self.active = 0
            self.floor = 0
            self.ceiling = 0
            self.transitions.clear()
            self.blocked.clear()


AUTOSCALER = AutoscalerStats()


class LoadModelStats:
    """Open-loop load-model accounting (``services.loadmodel``): how
    many arrivals the generator offered/completed per request class,
    sheds observed, and arrivals that fired behind schedule (the
    open-loop integrity counter — a generator that cannot keep its
    own schedule is measuring itself, not the service).  ``class`` is
    the closed ``loadmodel.CLASSES`` vocabulary."""

    def __init__(self):
        self._lock = threading.Lock()
        self.offered: Dict[str, int] = {}
        self.completed: Dict[str, int] = {}
        self.sheds = 0
        self.late = 0

    def count_offered(self, cls: str) -> None:
        with self._lock:
            self.offered[cls] = self.offered.get(cls, 0) + 1

    def count_completed(self, cls: str) -> None:
        with self._lock:
            self.completed[cls] = self.completed.get(cls, 0) + 1

    def count_shed(self) -> None:
        with self._lock:
            self.sheds += 1

    def count_late(self) -> None:
        with self._lock:
            self.late += 1

    def metric_lines(self, extra_labels: str = "") -> List[str]:
        extra = extra_labels.lstrip(",")

        def label(body: str = "") -> str:
            inner = ",".join(p for p in (body, extra) if p)
            return ("{" + inner + "}") if inner else ""

        with self._lock:
            if not (self.offered or self.sheds or self.late):
                return []        # emit-when-live (bench-side family)
            lines = [
                f"imageregion_loadmodel_shed_total{label()} "
                f"{self.sheds}",
                f"imageregion_loadmodel_late_fires_total{label()} "
                f"{self.late}",
            ]
            for cls in sorted(self.offered):
                body = 'class="%s"' % cls
                lines.append(
                    f"imageregion_loadmodel_offered_total"
                    f"{label(body)} {self.offered[cls]}")
            for cls in sorted(self.completed):
                body = 'class="%s"' % cls
                lines.append(
                    f"imageregion_loadmodel_completed_total"
                    f"{label(body)} {self.completed[cls]}")
        return lines

    def reset(self) -> None:
        with self._lock:
            self.offered.clear()
            self.completed.clear()
            self.sheds = 0
            self.late = 0


LOADMODEL = LoadModelStats()


class WorkloadStats:
    """Device-workloads plane accounting (PR 20): the batched
    mask/overlay rasterizer (``kind`` is the closed request vocabulary
    — which path served it), the crash-safe pyramid job subsystem
    (``action`` is the closed lifecycle vocabulary), and the z/t
    animation streamer (streams/frames/cancels plus the last stream's
    first-frame latency — the bounded-latency contract's live gauge)."""

    REQUEST_KINDS = ("mask_device", "mask_host", "overlay", "animation")
    JOB_ACTIONS = ("submitted", "resumed", "completed", "failed",
                   "cancelled", "deferred")

    def __init__(self):
        self._lock = threading.Lock()
        self.requests: Dict[str, int] = {}
        self.jobs: Dict[str, int] = {}
        self.jobs_active = 0
        self.levels_committed = 0
        self.streams = 0
        self.frames = 0
        self.stream_cancels = 0
        self.first_frame_ms: Optional[float] = None

    def count_request(self, kind: str) -> None:
        with self._lock:
            self.requests[kind] = self.requests.get(kind, 0) + 1

    def count_job(self, action: str) -> None:
        with self._lock:
            self.jobs[action] = self.jobs.get(action, 0) + 1

    def job_started(self) -> None:
        with self._lock:
            self.jobs_active += 1

    def job_finished(self) -> None:
        with self._lock:
            self.jobs_active = max(0, self.jobs_active - 1)

    def count_level_committed(self) -> None:
        with self._lock:
            self.levels_committed += 1

    def count_stream(self) -> None:
        with self._lock:
            self.streams += 1

    def count_frames(self, n: int = 1) -> None:
        with self._lock:
            self.frames += n

    def count_stream_cancelled(self) -> None:
        with self._lock:
            self.stream_cancels += 1

    def observe_first_frame_ms(self, ms: float) -> None:
        with self._lock:
            self.first_frame_ms = ms

    def metric_lines(self, extra_labels: str = "") -> List[str]:
        extra = extra_labels.lstrip(",")

        def label(body: str = "") -> str:
            inner = ",".join(p for p in (body, extra) if p)
            return ("{" + inner + "}") if inner else ""

        with self._lock:
            if not (self.requests or self.jobs or self.jobs_active
                    or self.levels_committed or self.streams):
                return []        # emit-when-live (workloads-plane only)
            lines = []
            for kind in sorted(self.requests):
                body = 'kind="%s"' % kind
                lines.append(
                    f"imageregion_workload_requests_total"
                    f"{label(body)} {self.requests[kind]}")
            for action in sorted(self.jobs):
                body = 'action="%s"' % action
                lines.append(
                    f"imageregion_pyramid_jobs_total"
                    f"{label(body)} {self.jobs[action]}")
            lines += [
                f"imageregion_pyramid_jobs_active{label()} "
                f"{self.jobs_active}",
                f"imageregion_pyramid_levels_committed_total{label()} "
                f"{self.levels_committed}",
                f"imageregion_animation_streams_total{label()} "
                f"{self.streams}",
                f"imageregion_animation_frames_total{label()} "
                f"{self.frames}",
                f"imageregion_animation_cancelled_total{label()} "
                f"{self.stream_cancels}",
            ]
            if self.first_frame_ms is not None:
                lines.append(
                    f"imageregion_animation_first_frame_ms{label()} "
                    f"{_fmt(self.first_frame_ms)}")
        return lines

    def reset(self) -> None:
        with self._lock:
            self.requests.clear()
            self.jobs.clear()
            self.jobs_active = 0
            self.levels_committed = 0
            self.streams = 0
            self.frames = 0
            self.stream_cancels = 0
            self.first_frame_ms = None


WORKLOADS = WorkloadStats()


class FederationStats:
    """Cross-host federation accounting (``parallel.federation``): the
    agreed manifest's version + member count, join-time agreement
    outcomes, gossip-round outcomes, cross-host warm shard transfers
    (the ``shard_transfer`` wire op, both directions counted where
    they ship) and remote prestage hints fired by the shard-aware
    prefetcher.  Both label sets reuse the closed ``reason``
    vocabulary — :data:`AGREEMENT_REASONS` / :data:`GOSSIP_REASONS`
    here, never caller-minted strings."""

    AGREEMENT_REASONS = ("agreed", "pending", "stale", "split-brain",
                         "unreachable", "legacy")
    GOSSIP_REASONS = ("ok", "mismatch", "unreachable")

    def __init__(self):
        self._lock = threading.Lock()
        self.manifest_version = 0
        self.members = 0
        self.agreements: Dict[str, int] = {}
        self.gossip: Dict[str, int] = {}
        self.shard_transfers = 0
        self.transfer_bytes = 0
        self.remote_prestage = 0

    def set_manifest(self, version: int, members: int) -> None:
        with self._lock:
            self.manifest_version = int(version)
            self.members = int(members)

    def count_agreement(self, reason: str) -> None:
        if reason not in self.AGREEMENT_REASONS:
            reason = "unreachable"
        with self._lock:
            self.agreements[reason] = self.agreements.get(reason, 0) + 1

    def count_gossip(self, reason: str) -> None:
        if reason not in self.GOSSIP_REASONS:
            reason = "unreachable"
        with self._lock:
            self.gossip[reason] = self.gossip.get(reason, 0) + 1

    def count_transfer(self, nbytes: int) -> None:
        with self._lock:
            self.shard_transfers += 1
            self.transfer_bytes += int(nbytes)

    def count_remote_prestage(self, n: int = 1) -> None:
        with self._lock:
            self.remote_prestage += n

    def metric_lines(self, extra_labels: str = "") -> List[str]:
        extra = extra_labels.lstrip(",")

        def label(body: str = "") -> str:
            inner = ",".join(p for p in (body, extra) if p)
            return ("{" + inner + "}") if inner else ""

        with self._lock:
            if not (self.manifest_version or self.agreements
                    or self.gossip or self.shard_transfers
                    or self.remote_prestage):
                # Emit-when-live (the autoscaler posture): non-federated
                # deployments keep their expositions — and the reset()
                # contract — exact.
                return []
            lines = [
                f"imageregion_federation_manifest_version{label()} "
                f"{self.manifest_version}",
                f"imageregion_federation_members{label()} "
                f"{self.members}",
                f"imageregion_federation_shard_transfers_total"
                f"{label()} {self.shard_transfers}",
                f"imageregion_federation_transfer_bytes_total"
                f"{label()} {self.transfer_bytes}",
                f"imageregion_federation_remote_prestage_total"
                f"{label()} {self.remote_prestage}",
            ]
            for reason in sorted(self.agreements):
                body = 'reason="%s"' % reason
                lines.append(
                    f"imageregion_federation_agreements_total"
                    f"{label(body)} {self.agreements[reason]}")
            for reason in sorted(self.gossip):
                body = 'reason="%s"' % reason
                lines.append(
                    f"imageregion_federation_gossip_total"
                    f"{label(body)} {self.gossip[reason]}")
        return lines

    def reset(self) -> None:
        with self._lock:
            self.manifest_version = 0
            self.members = 0
            self.agreements.clear()
            self.gossip.clear()
            self.shard_transfers = 0
            self.transfer_bytes = 0
            self.remote_prestage = 0


FEDERATION = FederationStats()


class DecisionStats:
    """Exposition half of the control-plane decision ledger
    (``utils.decisions`` owns the ring + spool): counts per
    (kind, verdict) as ``imageregion_decision_total``.  BOTH label
    vocabularies are closed and owned HERE so the cardinality budget
    can bound them mechanically — the ledger imports them, callers
    never mint either string."""

    KINDS = ("autoscaler", "epoch", "manifest", "gossip",
             "drain", "undrain", "handoff", "hotkey", "quorum",
             "sentinel")
    VERDICTS = ("up", "down", "blocked", "steady",
                "installed", "pending", "promoted", "demoted",
                "agreed", "stale", "split-brain", "unreachable",
                "legacy", "ok", "mismatch", "done", "failed",
                "fenced", "restored", "drift", "recovered")

    def __init__(self):
        self._lock = threading.Lock()
        self.counts: Dict[Tuple[str, str], int] = {}

    def count(self, kind: str, verdict: str) -> None:
        if kind not in self.KINDS or verdict not in self.VERDICTS:
            return                       # ledger already warned
        with self._lock:
            key = (kind, verdict)
            self.counts[key] = self.counts.get(key, 0) + 1

    def metric_lines(self, extra_labels: str = "") -> List[str]:
        extra = extra_labels.lstrip(",")

        def label(body: str = "") -> str:
            inner = ",".join(p for p in (body, extra) if p)
            return ("{" + inner + "}") if inner else ""

        with self._lock:
            if not self.counts:
                return []                # emit-when-live
            return [
                f"imageregion_decision_total"
                f"{label('kind=%s,verdict=%s' % (json.dumps(k), json.dumps(v)))}"
                f" {n}"
                for (k, v), n in sorted(self.counts.items())
            ]

    def reset(self) -> None:
        with self._lock:
            self.counts.clear()


DECISIONS = DecisionStats()


class FleetSloStats:
    """Fleet-level SLO burn: every federated host exports its
    ``SloEngine`` window buckets over the gossip wire
    (``SloEngine.export_buckets`` — age-keyed, since bucket indices
    are process-local monotonic) and the frontend re-anchors them here
    against its own clock, so one host's error budget burning is
    visible on the aggregating host's exposition as
    ``imageregion_fleet_slo_*`` even while the fleet-wide mean looks
    healthy.  The ``host`` label is bounded by ``_MAX_HOSTS``:
    ingests for new hosts beyond the bound are dropped (and counted)
    rather than growing the exposition — the overflow guard the
    cardinality budget relies on.  Objectives are assumed homogeneous
    across the fleet (one config rolled everywhere); the strictest
    target seen wins when they drift."""

    _MAX_HOSTS = 16

    def __init__(self):
        self._lock = threading.Lock()
        self._clock = time.monotonic
        # host -> {"t": ingest instant, "export": SloEngine export doc}
        self.hosts: Dict[str, dict] = {}
        self.dropped_hosts = 0

    def configure(self, clock=time.monotonic) -> None:
        with self._lock:
            self._clock = clock

    def ingest(self, host: str, export) -> bool:
        if not host or not isinstance(export, dict) \
                or not export.get("buckets"):
            return False
        with self._lock:
            if host not in self.hosts \
                    and len(self.hosts) >= self._MAX_HOSTS:
                self.dropped_hosts += 1
                return False
            self.hosts[host] = {"t": self._clock(),
                                "export": dict(export)}
        return True

    @staticmethod
    def _window_counts(export: dict, elapsed: float,
                       window_s: float) -> Dict[str, int]:
        out = {"ok": 0, "err": 0, "fast": 0, "slow": 0}
        bucket_s = float(export.get("bucket_s", 5.0))
        for row in export.get("buckets", ()):
            try:
                age, ok, err, fast, slow = row
            except (TypeError, ValueError):
                continue
            # ``age`` dates the bucket START at export; a bucket still
            # counts while any part of it overlaps the window.
            if float(age) + elapsed - bucket_s <= window_s:
                out["ok"] += int(ok)
                out["err"] += int(err)
                out["fast"] += int(fast)
                out["slow"] += int(slow)
        return out

    def _burns_locked(self) -> dict:
        """{"hosts": {host: {objective: {window: burn}}},
        "fleet": {objective: {window: burn}}} over live exports."""
        now = self._clock()

        def burn(bad: int, total: int, target: float) -> float:
            if total == 0 or not target:
                return 0.0
            return (bad / total) / max(1e-9, 1.0 - target)

        per_host: Dict[str, dict] = {}
        fleet_counts: Dict[Tuple[str, str], Dict[str, int]] = {}
        targets = {"availability": 0.0, "latency": 0.0}
        for host, entry in self.hosts.items():
            export = entry["export"]
            elapsed = max(0.0, now - entry["t"])
            targets["availability"] = max(
                targets["availability"],
                float(export.get("availability_target", 0.0)))
            targets["latency"] = max(
                targets["latency"],
                float(export.get("latency_target", 0.0))
                if export.get("latency_ms") else 0.0)
            host_doc: Dict[str, dict] = {}
            for window, window_s in (
                    ("fast", float(export.get("fast_window_s", 60.0))),
                    ("slow", float(export.get("slow_window_s",
                                              600.0)))):
                c = self._window_counts(export, elapsed, window_s)
                agg = fleet_counts.setdefault(
                    (window, ""), {"ok": 0, "err": 0,
                                   "fast": 0, "slow": 0})
                for k in c:
                    agg[k] += c[k]
                if export.get("availability_target"):
                    host_doc.setdefault("availability", {})[window] = \
                        burn(c["err"], c["ok"] + c["err"],
                             float(export["availability_target"]))
                if export.get("latency_ms"):
                    host_doc.setdefault("latency", {})[window] = \
                        burn(c["slow"], c["fast"] + c["slow"],
                             float(export.get("latency_target", 0.99)))
            per_host[host] = host_doc
        fleet: Dict[str, dict] = {}
        for (window, _), c in fleet_counts.items():
            if targets["availability"]:
                fleet.setdefault("availability", {})[window] = burn(
                    c["err"], c["ok"] + c["err"],
                    targets["availability"])
            if targets["latency"]:
                fleet.setdefault("latency", {})[window] = burn(
                    c["slow"], c["fast"] + c["slow"],
                    targets["latency"])
        return {"hosts": per_host, "fleet": fleet}

    def burns(self) -> dict:
        with self._lock:
            return self._burns_locked()

    def metric_lines(self, extra_labels: str = "") -> List[str]:
        extra = extra_labels.lstrip(",")

        def label(body: str = "") -> str:
            inner = ",".join(p for p in (body, extra) if p)
            return ("{" + inner + "}") if inner else ""

        with self._lock:
            if not self.hosts and not self.dropped_hosts:
                return []                # emit-when-live
            doc = self._burns_locked()
            lines = [f"imageregion_fleet_slo_hosts{label()} "
                     f"{len(self.hosts)}"]
            if self.dropped_hosts:
                lines.append(
                    f"imageregion_fleet_slo_dropped_hosts_total"
                    f"{label()} {self.dropped_hosts}")
            for objective in sorted(doc["fleet"]):
                for window in sorted(doc["fleet"][objective]):
                    body = ('slo="%s",window="%s"'
                            % (objective, window))
                    lines.append(
                        f"imageregion_fleet_slo_burn_rate"
                        f"{label(body)} "
                        f"{round(doc['fleet'][objective][window], 4)}")
            for host in sorted(doc["hosts"]):
                for objective in sorted(doc["hosts"][host]):
                    for window in sorted(doc["hosts"][host][objective]):
                        body = ('host="%s",slo="%s",window="%s"'
                                % (host, objective, window))
                        rate = doc["hosts"][host][objective][window]
                        lines.append(
                            f"imageregion_fleet_slo_host_burn_rate"
                            f"{label(body)} {round(rate, 4)}")
        return lines

    def reset(self) -> None:
        with self._lock:
            self._clock = time.monotonic
            self.hosts.clear()
            self.dropped_hosts = 0


FED_SLO = FleetSloStats()


class SentinelStats:
    """Exposition + fleet-merge half of the live perf-regression
    sentinel (``server.sentinel`` owns the sketches and the drift
    engine; this accumulator stays importable without the server
    stack).  Each member's engine pushes its per-tick summary here
    (``set_local``), gossip carries peer summaries in (``ingest`` —
    the ``FleetSloStats`` idiom, same ``_MAX_MEMBERS`` overflow guard
    the cardinality budget relies on), and ``merged`` answers
    ``GET /debug/sentinel`` with ONE fleet view instead of N
    incomparable ones."""

    _MAX_MEMBERS = 16

    def __init__(self):
        self._lock = threading.Lock()
        self._clock = time.monotonic
        # Freshness bound for the merged verdict: a member whose last
        # summary predates this is reported but not counted drifting
        # (a dead member must not pin the fleet red forever).
        self.stale_after_s = 120.0
        self.local: Optional[dict] = None
        # member -> {"t": ingest instant, "summary": tick summary doc}
        self.members: Dict[str, dict] = {}
        self.dropped_members = 0
        self.drifts = 0
        self.recoveries = 0
        self.bundles = 0
        self.bundle_errors = 0

    def configure(self, clock=time.monotonic) -> None:
        with self._lock:
            self._clock = clock

    # ------------------------------------------------- engine inputs

    def set_local(self, summary) -> None:
        """The local engine's latest tick summary (the doc gossip
        exports and ``merged`` folds in as this process's row)."""
        if isinstance(summary, dict):
            with self._lock:
                self.local = dict(summary)

    def count_drift(self) -> None:
        with self._lock:
            self.drifts += 1

    def count_recovery(self) -> None:
        with self._lock:
            self.recoveries += 1

    def count_bundle(self, error: bool = False) -> None:
        with self._lock:
            if error:
                self.bundle_errors += 1
            else:
                self.bundles += 1

    # --------------------------------------------------- fleet merge

    def export(self) -> Optional[dict]:
        """The local summary for the gossip wire (None while the
        engine has not ticked — peers skip on null)."""
        with self._lock:
            return dict(self.local) if self.local else None

    def ingest(self, member: str, summary) -> bool:
        if not member or not isinstance(summary, dict) \
                or not summary.get("verdict"):
            return False
        with self._lock:
            if member not in self.members \
                    and len(self.members) >= self._MAX_MEMBERS:
                self.dropped_members += 1
                return False
            self.members[member] = {"t": self._clock(),
                                    "summary": dict(summary)}
        return True

    def merged(self) -> dict:
        """Per-member rows + one fleet verdict: ``drifting`` while any
        FRESH member reports a confirmed drift."""
        with self._lock:
            now = self._clock()
            rows: Dict[str, dict] = {}
            if self.local:
                name = str(self.local.get("member") or "local")
                rows[name] = {"age_s": 0.0,
                              "summary": dict(self.local)}
            for member, entry in self.members.items():
                if member in rows:
                    continue
                rows[member] = {
                    "age_s": round(max(0.0, now - entry["t"]), 1),
                    "summary": dict(entry["summary"])}
            drifting = sorted(
                name for name, row in rows.items()
                if row["summary"].get("verdict") == "drifting"
                and row["age_s"] <= self.stale_after_s)
            return {
                "verdict": "drifting" if drifting else "ok",
                "drifting_members": drifting,
                "members": rows,
                "dropped_members": self.dropped_members,
                "drifts": self.drifts,
                "recoveries": self.recoveries,
                "bundles": self.bundles,
                "bundle_errors": self.bundle_errors,
            }

    # ----------------------------------------------------- exposition

    def metric_lines(self, extra_labels: str = "") -> List[str]:
        extra = extra_labels.lstrip(",")

        def label(body: str = "") -> str:
            inner = ",".join(p for p in (body, extra) if p)
            return ("{" + inner + "}") if inner else ""

        with self._lock:
            if self.local is None and not self.members:
                return []                # emit-when-live
            local = self.local or {}
            drifting = 1 if local.get("verdict") == "drifting" else 0
            lines = [
                f"imageregion_sentinel_drift{label()} {drifting}",
                f"imageregion_sentinel_keys{label()} "
                f"{len(local.get('routes') or {})}",
                f"imageregion_sentinel_ticks_total{label()} "
                f"{int(local.get('ticks') or 0)}",
                f"imageregion_sentinel_observations_total{label()} "
                f"{int(local.get('observations') or 0)}",
                f"imageregion_sentinel_drifts_total{label()} "
                f"{self.drifts}",
                f"imageregion_sentinel_recoveries_total{label()} "
                f"{self.recoveries}",
                f"imageregion_sentinel_bundles_total{label()} "
                f"{self.bundles}",
                f"imageregion_sentinel_bundle_errors_total{label()} "
                f"{self.bundle_errors}",
            ]
            for route in sorted(local.get("routes") or {}):
                doc = local["routes"][route] or {}
                body = 'route="%s"' % route
                for key, family in (
                        ("p99_ms", "imageregion_sentinel_live_p99_ms"),
                        ("baseline_p99_ms",
                         "imageregion_sentinel_baseline_p99_ms")):
                    v = doc.get(key)
                    if isinstance(v, (int, float)):
                        lines.append(f"{family}{label(body)} "
                                     f"{round(float(v), 3)}")
            now = self._clock()
            for member in sorted(self.members):
                entry = self.members[member]
                if now - entry["t"] > self.stale_after_s:
                    continue
                v = (1 if entry["summary"].get("verdict") == "drifting"
                     else 0)
                lines.append(
                    f"imageregion_sentinel_member_drift"
                    f"{label('member=%s' % json.dumps(member))} {v}")
        return lines

    def reset(self) -> None:
        with self._lock:
            self._clock = time.monotonic
            self.stale_after_s = 120.0
            self.local = None
            self.members.clear()
            self.dropped_members = 0
            self.drifts = 0
            self.recoveries = 0
            self.bundles = 0
            self.bundle_errors = 0


SENTINEL = SentinelStats()


class QuorumStats:
    """Partition-tolerance accounting: the quorum tracker's verdict
    (``parallel.federation.QuorumTracker``) and the link-partition
    fault injector (``utils.faultinject``).  Two families —
    ``imageregion_federation_quorum_*`` (am I in the majority, what
    have I refused while fenced) and ``imageregion_partition_*`` (the
    netsplit drill's injected link rules and the calls they blocked).
    Labels are closed vocabularies owned HERE: fence/restore
    transitions reuse the decision ledger's verdict strings, refusal
    actions are :data:`ACTIONS`, partition modes :data:`MODES`."""

    ACTIONS = ("adoption", "write_authority", "promotion",
               "autoscaler", "transfer", "roll")
    MODES = ("drop", "deny")

    def __init__(self):
        self._lock = threading.Lock()
        # None = no tracker installed (un-federated / quorum off):
        # emit-when-live keeps those expositions exact.
        self.quorate: Optional[bool] = None
        self.reachable_hosts = 0
        self.total_hosts = 0
        self.transitions: Dict[str, int] = {}
        self.refusals: Dict[str, int] = {}
        self.partition_rules = 0
        self.partition_blocked: Dict[str, int] = {}

    def set_quorum(self, quorate: bool, reachable: int,
                   total: int) -> None:
        with self._lock:
            self.quorate = bool(quorate)
            self.reachable_hosts = int(reachable)
            self.total_hosts = int(total)

    def count_transition(self, verdict: str) -> None:
        if verdict not in ("fenced", "restored"):
            return
        with self._lock:
            self.transitions[verdict] = \
                self.transitions.get(verdict, 0) + 1

    def count_refusal(self, action: str) -> None:
        if action not in self.ACTIONS:
            return
        with self._lock:
            self.refusals[action] = self.refusals.get(action, 0) + 1

    def set_partition_rules(self, n: int) -> None:
        with self._lock:
            self.partition_rules = int(n)

    def count_partition_blocked(self, mode: str) -> None:
        if mode not in self.MODES:
            mode = "drop"
        with self._lock:
            self.partition_blocked[mode] = \
                self.partition_blocked.get(mode, 0) + 1

    def metric_lines(self, extra_labels: str = "") -> List[str]:
        extra = extra_labels.lstrip(",")

        def label(body: str = "") -> str:
            inner = ",".join(p for p in (body, extra) if p)
            return ("{" + inner + "}") if inner else ""

        with self._lock:
            lines: List[str] = []
            if self.quorate is not None:
                lines += [
                    f"imageregion_federation_quorum_quorate{label()} "
                    f"{int(self.quorate)}",
                    f"imageregion_federation_quorum_reachable_hosts"
                    f"{label()} {self.reachable_hosts}",
                    f"imageregion_federation_quorum_hosts{label()} "
                    f"{self.total_hosts}",
                ]
            for verdict in sorted(self.transitions):
                body = 'verdict="%s"' % verdict
                lines.append(
                    f"imageregion_federation_quorum_transitions_total"
                    f"{label(body)} {self.transitions[verdict]}")
            for action in sorted(self.refusals):
                body = 'action="%s"' % action
                lines.append(
                    f"imageregion_federation_quorum_refusals_total"
                    f"{label(body)} {self.refusals[action]}")
            if self.partition_rules or self.partition_blocked:
                lines.append(f"imageregion_partition_rules{label()} "
                             f"{self.partition_rules}")
            for mode in sorted(self.partition_blocked):
                body = 'mode="%s"' % mode
                lines.append(
                    f"imageregion_partition_blocked_total"
                    f"{label(body)} {self.partition_blocked[mode]}")
        return lines

    def reset(self) -> None:
        with self._lock:
            self.quorate = None
            self.reachable_hosts = 0
            self.total_hosts = 0
            self.transitions.clear()
            self.refusals.clear()
            self.partition_rules = 0
            self.partition_blocked.clear()


QUORUM = QuorumStats()


class SessionStats:
    """Session-model accounting (``services.viewport`` +
    ``server.admission.SessionTokenBuckets``): how many distinct
    sessions the viewport tracker currently models, how many tile
    observations fed it, and LRU evictions (the bound working).  No
    per-session labels, ever — sessions are unbounded-cardinality by
    definition, so only aggregates reach the exposition."""

    def __init__(self):
        self._lock = threading.Lock()
        self.tracked = 0
        self.observations = 0
        self.evicted = 0

    def set_tracked(self, n: int) -> None:
        with self._lock:
            self.tracked = int(n)

    def count_observation(self) -> None:
        with self._lock:
            self.observations += 1

    def count_evicted(self) -> None:
        with self._lock:
            self.evicted += 1

    def metric_lines(self, extra_labels: str = "") -> List[str]:
        extra = extra_labels.lstrip(",")
        lb = ("{" + extra + "}") if extra else ""
        with self._lock:
            return [
                f"imageregion_session_tracked{lb} {self.tracked}",
                f"imageregion_session_observations_total{lb} "
                f"{self.observations}",
                f"imageregion_session_evictions_total{lb} "
                f"{self.evicted}",
            ]

    def reset(self) -> None:
        with self._lock:
            self.tracked = 0
            self.observations = 0
            self.evicted = 0


SESSIONS = SessionStats()


class PrefetchStats:
    """Predictive-prefetch accounting (``services.prefetch``):
    predictions made, loads scheduled/staged, foreground hits on
    prefetched planes, skips by reason, and the live budget scale.
    The ``reason`` label set is closed — this module's own vocabulary
    (budget, paused), never caller-minted."""

    def __init__(self):
        self._lock = threading.Lock()
        self.predicted = 0
        self.scheduled = 0
        self.staged = 0
        self.hits = 0
        self.skipped: Dict[str, int] = {}
        self.budget_scale = 1.0

    def count_predicted(self, n: int = 1) -> None:
        with self._lock:
            self.predicted += n

    def count_scheduled(self) -> None:
        with self._lock:
            self.scheduled += 1

    def count_staged(self) -> None:
        with self._lock:
            self.staged += 1

    def count_hit(self) -> None:
        with self._lock:
            self.hits += 1

    def count_skipped(self, reason: str) -> None:
        with self._lock:
            self.skipped[reason] = self.skipped.get(reason, 0) + 1

    def set_budget(self, scale: float) -> None:
        with self._lock:
            self.budget_scale = float(scale)

    def hit_rate(self) -> Optional[float]:
        with self._lock:
            if not self.staged:
                return None
            return self.hits / self.staged

    def metric_lines(self, extra_labels: str = "") -> List[str]:
        extra = extra_labels.lstrip(",")

        def label(body: str = "") -> str:
            inner = ",".join(p for p in (body, extra) if p)
            return ("{" + inner + "}") if inner else ""

        with self._lock:
            lines = [
                f"imageregion_prefetch_predicted_total{label()} "
                f"{self.predicted}",
                f"imageregion_prefetch_scheduled_total{label()} "
                f"{self.scheduled}",
                f"imageregion_prefetch_staged_total{label()} "
                f"{self.staged}",
                f"imageregion_prefetch_hits_total{label()} "
                f"{self.hits}",
                f"imageregion_prefetch_budget_scale{label()} "
                f"{_fmt(self.budget_scale)}",
            ]
            for reason in sorted(self.skipped):
                body = 'reason="%s"' % reason
                lines.append(
                    f"imageregion_prefetch_skipped_total{label(body)} "
                    f"{self.skipped[reason]}")
        return lines

    def reset(self) -> None:
        with self._lock:
            self.predicted = 0
            self.scheduled = 0
            self.staged = 0
            self.hits = 0
            self.skipped.clear()
            self.budget_scale = 1.0


PREFETCH = PrefetchStats()


class QosStats:
    """Tiered-QoS accounting (``server.admission`` fairness sheds +
    the fleet router's weighted dequeue): sheds and dequeues by QoS
    class, and how often interactive work jumped a bulk backlog.  The
    ``class`` label is closed by construction — the two-value
    interactive/bulk vocabulary of ``pressure.is_bulk``."""

    CLASSES = ("interactive", "bulk")

    def __init__(self):
        self._lock = threading.Lock()
        self.shed: Dict[str, int] = {}
        self.dequeued: Dict[str, int] = {}
        self.jumps = 0

    def count_shed(self, cls: str) -> None:
        with self._lock:
            self.shed[cls] = self.shed.get(cls, 0) + 1

    def count_dequeued(self, cls: str) -> None:
        with self._lock:
            self.dequeued[cls] = self.dequeued.get(cls, 0) + 1

    def count_jump(self) -> None:
        with self._lock:
            self.jumps += 1

    def metric_lines(self, extra_labels: str = "") -> List[str]:
        extra = extra_labels.lstrip(",")

        def label(body: str = "") -> str:
            inner = ",".join(p for p in (body, extra) if p)
            return ("{" + inner + "}") if inner else ""

        with self._lock:
            lines = [
                f"imageregion_qos_interactive_jumps_total{label()} "
                f"{self.jumps}",
            ]
            for cls in sorted(self.shed):
                body = 'class="%s"' % cls
                lines.append(
                    f"imageregion_qos_shed_total{label(body)} "
                    f"{self.shed[cls]}")
            for cls in sorted(self.dequeued):
                body = 'class="%s"' % cls
                lines.append(
                    f"imageregion_qos_dequeued_total{label(body)} "
                    f"{self.dequeued[cls]}")
        return lines

    def reset(self) -> None:
        with self._lock:
            self.shed.clear()
            self.dequeued.clear()
            self.jumps = 0


QOS = QosStats()


class HttpCacheStats:
    """Conditional-HTTP + peer-byte-tier accounting
    (``server.httpcache`` / ``parallel.fleet`` peer fetch): how much
    repeat-viewer traffic the edge ladder answered WITHOUT a render —
    If-None-Match arrivals, 304s and renderless HEADs at L5; probe /
    hit / fetch / fallback / put-back counters for the fleet-global
    byte tier.  No labels — the families are closed scalars."""

    def __init__(self):
        self._lock = threading.Lock()
        self.etag_requests = 0     # requests arriving with If-None-Match
        self.ims_requests = 0      # If-Modified-Since-only arrivals
        self.not_modified = 0      # 304s served (zero-work revalidation)
        self.head = 0              # HEADs served renderless
        self.peer_probes = 0       # authority byte-probe round-trips
        self.peer_hits = 0         # probes answered resident=true
        self.peer_fetches = 0      # peer bodies actually served
        self.peer_fallbacks = 0    # probe/fetch failed -> render path
        self.peer_putbacks = 0     # stolen-render write-backs shipped

    def count_etag_request(self) -> None:
        with self._lock:
            self.etag_requests += 1

    def count_ims_request(self) -> None:
        with self._lock:
            self.ims_requests += 1

    def count_not_modified(self) -> None:
        with self._lock:
            self.not_modified += 1

    def count_head(self) -> None:
        with self._lock:
            self.head += 1

    def count_peer_probe(self) -> None:
        with self._lock:
            self.peer_probes += 1

    def count_peer_hit(self) -> None:
        with self._lock:
            self.peer_hits += 1

    def count_peer_fetch(self) -> None:
        with self._lock:
            self.peer_fetches += 1

    def count_peer_fallback(self) -> None:
        with self._lock:
            self.peer_fallbacks += 1

    def count_peer_putback(self) -> None:
        with self._lock:
            self.peer_putbacks += 1

    def metric_lines(self, extra_labels: str = "") -> List[str]:
        extra = extra_labels.lstrip(",")
        lb = ("{" + extra + "}") if extra else ""
        with self._lock:
            if not (self.etag_requests or self.ims_requests
                    or self.not_modified
                    or self.head or self.peer_probes
                    or self.peer_fetches or self.peer_fallbacks
                    or self.peer_putbacks):
                # Quiet until the ladder has seen traffic (the same
                # emit-when-live posture as the fleet totals, and what
                # keeps the reset()-contract exposition exact).
                return []
            return [
                f"imageregion_httpcache_etag_requests_total{lb} "
                f"{self.etag_requests}",
                f"imageregion_httpcache_ims_requests_total{lb} "
                f"{self.ims_requests}",
                f"imageregion_httpcache_304_total{lb} "
                f"{self.not_modified}",
                f"imageregion_httpcache_head_total{lb} {self.head}",
                f"imageregion_httpcache_peer_probes_total{lb} "
                f"{self.peer_probes}",
                f"imageregion_httpcache_peer_hits_total{lb} "
                f"{self.peer_hits}",
                f"imageregion_httpcache_peer_fetches_total{lb} "
                f"{self.peer_fetches}",
                f"imageregion_httpcache_peer_fallbacks_total{lb} "
                f"{self.peer_fallbacks}",
                f"imageregion_httpcache_peer_putbacks_total{lb} "
                f"{self.peer_putbacks}",
            ]

    def reset(self) -> None:
        with self._lock:
            self.etag_requests = 0
            self.ims_requests = 0
            self.not_modified = 0
            self.head = 0
            self.peer_probes = 0
            self.peer_hits = 0
            self.peer_fetches = 0
            self.peer_fallbacks = 0
            self.peer_putbacks = 0


HTTPCACHE = HttpCacheStats()


class ProvenanceStats:
    """Response-provenance accounting (``utils.provenance``): how many
    responses each byte-source tier answered, per serving member, plus
    the routing-flag counters.  BOTH label sets are closed: ``tier``
    is ``provenance.TIERS`` verbatim (a drifted tier string is dropped
    to ``render_cold`` before it gets here), ``member`` is the
    config-named fleet set bounded like FleetStats, and ``flag`` is
    ``provenance.FLAGS``.  Thread-safe (the access-log finisher runs
    on the event loop, smoke benches read concurrently)."""

    _MAX_MEMBERS = 64

    def __init__(self):
        self._lock = threading.Lock()
        self.by_tier_member: Dict[Tuple[str, str], int] = {}
        self.flags: Dict[str, int] = {}
        # Maintained member set: count() runs in the per-request
        # finisher, so the overflow guard must be a set hit, not a
        # key-walk per response.
        self._members: set = set()

    def count(self, record: Mapping) -> None:
        from .provenance import FLAGS, TIERS
        tier = record.get("tier")
        if tier not in TIERS:
            tier = "render_cold"
        member = str(record.get("member") or "-")
        with self._lock:
            if member not in self._members:
                if len(self._members) >= self._MAX_MEMBERS:
                    member = "_overflow"
                self._members.add(member)
            key = (tier, member)
            self.by_tier_member[key] = \
                self.by_tier_member.get(key, 0) + 1
            for flag in FLAGS:
                if record.get(flag):
                    self.flags[flag] = self.flags.get(flag, 0) + 1

    def totals(self) -> Dict[str, int]:
        with self._lock:
            out: Dict[str, int] = {}
            for (tier, _member), n in self.by_tier_member.items():
                out[tier] = out.get(tier, 0) + n
            return out

    def metric_lines(self, extra_labels: str = "") -> List[str]:
        extra = extra_labels.lstrip(",")

        def label(body: str) -> str:
            inner = ",".join(p for p in (body, extra) if p)
            return "{" + inner + "}"

        with self._lock:
            lines = []
            for (tier, member) in sorted(self.by_tier_member):
                body = f'tier="{tier}",member="{member}"'
                lines.append(
                    f"imageregion_provenance_total{label(body)} "
                    f"{self.by_tier_member[(tier, member)]}")
            for flag in sorted(self.flags):
                body = f'flag="{flag}"'
                lines.append(
                    f"imageregion_provenance_flags_total{label(body)} "
                    f"{self.flags[flag]}")
        return lines

    def reset(self) -> None:
        with self._lock:
            self.by_tier_member.clear()
            self.flags.clear()
            self._members.clear()


PROVENANCE = ProvenanceStats()


def exemplars_snapshot() -> Dict[str, List[dict]]:
    """The request-duration histogram's live exemplars, per route —
    the /debug/exemplars JSON view (each entry names the most recent
    trace id + provenance tier to land in that latency bucket)."""
    return REQUEST_HIST.exemplar_docs()


def session_metric_lines(extra_labels: str = "") -> List[str]:
    """The session-serving families — ``imageregion_session_*``,
    ``imageregion_prefetch_*``, ``imageregion_qos_*`` — plus the
    open-loop load model's counters (emit-when-live: only a process
    actually replaying arrivals carries them)."""
    return (SESSIONS.metric_lines(extra_labels)
            + PREFETCH.metric_lines(extra_labels)
            + QOS.metric_lines(extra_labels)
            + LOADMODEL.metric_lines(extra_labels)
            + WORKLOADS.metric_lines(extra_labels))


def robustness_metric_lines(extra_labels: str = "") -> List[str]:
    """The self-preservation families — ``imageregion_pressure_*``,
    ``imageregion_watchdog_*``, ``imageregion_drain_*`` — plus the
    session-serving families (``imageregion_session_*`` /
    ``imageregion_prefetch_*`` / ``imageregion_qos_*``) — emitted from
    BOTH roles (the governor/watchdog run wherever they are wired;
    drains live with the fleet router; sessions/QoS at the admission
    edge)."""
    return (PRESSURE.metric_lines(extra_labels)
            + WATCHDOG.metric_lines(extra_labels)
            + DRAIN.metric_lines(extra_labels)
            + AUTOSCALER.metric_lines(extra_labels)
            + FEDERATION.metric_lines(extra_labels)
            + QUORUM.metric_lines(extra_labels)
            + DECISIONS.metric_lines(extra_labels)
            + FED_SLO.metric_lines(extra_labels)
            + session_metric_lines(extra_labels))


def pinned_device_id(services):
    """The id of the device an in-process fleet member is pinned to
    (``services.pin_device``); None where it uses the process
    default."""
    pin = getattr(services, "pin_device", None)
    return None if pin is None else getattr(pin, "id", pin)


def fleet_metric_lines(router=None, extra_labels: str = "",
                       single_flight=None) -> List[str]:
    """The ``imageregion_fleet_*`` families: the process-global
    routed/stolen/failed-over counters plus, when a live router is
    passed, per-member depth/inflight/health gauges and the HBM
    shard-ownership count (resident planes per local member).
    ``router`` is duck-typed (``parallel.fleet.FleetRouter``) so this
    module stays importable without the fleet stack.

    ``single_flight`` is the FLEET-WIDE coalescing table (it moved
    above the router, off ``services.single_flight`` — whose emitter
    would otherwise carry these families): passing it here keeps the
    ``imageregion_singleflight_*`` series alive in fleet postures."""
    extra = extra_labels.lstrip(",")
    lines = FLEET.metric_lines(extra_labels)
    lines += HOTKEY.metric_lines(extra_labels)
    if single_flight is not None:
        lb = ("{" + extra + "}") if extra else ""
        lines += [
            f"imageregion_singleflight_hits{lb} {single_flight.hits}",
            f"imageregion_singleflight_misses{lb} "
            f"{single_flight.misses}",
            f"imageregion_singleflight_inflight{lb} "
            f"{single_flight.inflight()}",
        ]
    if router is None:
        return lines

    def label(member: str = "") -> str:
        parts = [p for p in
                 ((f'member="{member}"' if member else ""), extra) if p]
        return ("{" + ",".join(parts) + "}") if parts else ""

    lines += [
        f"imageregion_fleet_members{label()} {len(router.order)}",
        f"imageregion_fleet_members_healthy{label()} "
        f"{len(router.healthy_members())}",
    ]
    for name in router.order:
        member = router.members[name]
        lines += [
            f"imageregion_fleet_member_depth{label(name)} "
            f"{router.member_depth(name)}",
            f"imageregion_fleet_member_inflight{label(name)} "
            f"{router.member_inflight(name)}",
            f"imageregion_fleet_member_healthy{label(name)} "
            f"{1 if member.healthy else 0}",
            f"imageregion_fleet_member_planes{label(name)} "
            f"{member.resident_planes()}",
        ]
        device = pinned_device_id(getattr(member, "services", None))
        if device is not None:
            # The chip this member holds, which its renders, uploads
            # and prewarm run on.
            body = f'member="{name}",device="{device}"'
            lines.append("imageregion_fleet_member_device{"
                         + body + (f",{extra}" if extra else "")
                         + "} 1")
    return lines


# ---------------------------------------------------------------- readiness

class Readiness:
    """Process-wide degradation state behind ``/readyz``."""

    def __init__(self):
        self.prewarm_pending = False

    def reset(self) -> None:
        self.prewarm_pending = False


READINESS = Readiness()


# -------------------------------------------------------------- slow dumps

def dump_slow_trace(trace: Trace, total_ms: float, status: int,
                    directory: str,
                    extra: Optional[dict] = None) -> Optional[str]:
    """Write the waterfall JSON for a slow request; never raises (a
    full disk must not fail the request that just succeeded).
    ``extra`` merges top-level fields into the document (the app
    attaches the provenance record so a dumped waterfall carries its
    where-did-the-bytes-come-from verdict)."""
    try:
        os.makedirs(directory, exist_ok=True)
        path = os.path.join(directory, f"{trace.trace_id}.json")
        doc = trace.to_json(total_ms=total_ms, status=status)
        if extra:
            doc.update(extra)
        with open(path, "w") as f:
            json.dump(doc, f, indent=1)
        return path
    except OSError:
        log.warning("slow-trace dump to %s failed", directory,
                    exc_info=True)
        return None


# -------------------------------------------------------------- exposition

# Metric family -> Prometheus type, for every family this service can
# emit (frontend, sidecar and combined posture).  finalize_exposition
# derives each line's family and emits the # TYPE header once.
METRIC_TYPES: Dict[str, str] = {
    "imageregion_span_count": "counter",
    "imageregion_span_ms": "histogram",
    "imageregion_request_duration_ms": "histogram",
    "imageregion_requests_total": "counter",
    "imageregion_cache_hits": "counter",
    "imageregion_cache_misses": "counter",
    "imageregion_cache_evictions": "counter",
    "imageregion_rawcache_hits": "counter",
    "imageregion_rawcache_misses": "counter",
    "imageregion_rawcache_evictions": "counter",
    "imageregion_rawcache_bytes": "gauge",
    "imageregion_rawcache_channel_loads_total": "counter",
    "imageregion_rawcache_duplicate_loads_total": "counter",
    "imageregion_tile_renders_total": "counter",
    "imageregion_render_pixels_total": "counter",
    "imageregion_pixel_sources_opened_total": "counter",
    "imageregion_pixel_sources_open": "gauge",
    "imageregion_planecache_hits": "counter",
    "imageregion_planecache_misses": "counter",
    "imageregion_singleflight_hits": "counter",
    "imageregion_singleflight_misses": "counter",
    "imageregion_singleflight_inflight": "gauge",
    "imageregion_batches_dispatched": "counter",
    "imageregion_tiles_rendered": "counter",
    "imageregion_batcher_queue_depth": "gauge",
    "imageregion_pipeline_inflight": "gauge",
    "imageregion_batcher_max_batch": "gauge",
    "imageregion_batcher_shape_slots_total": "counter",
    "imageregion_batcher_padded_slots_total": "counter",
    "imageregion_batcher_group_stacks_total": "counter",
    "imageregion_batcher_bucket_px_total": "counter",
    "imageregion_entropy_tiles_total": "counter",
    "imageregion_entropy_split_tiles_total": "counter",
    "imageregion_renders_routed_total": "counter",
    "imageregion_device_renders_total": "counter",
    "imageregion_batcher_queue_wait_max_ms": "gauge",
    "imageregion_compile_events_total": "counter",
    "imageregion_compile_ms_total": "counter",
    "imageregion_profile_captures_total": "counter",
    "imageregion_profile_busy_ms_total": "counter",
    "imageregion_profile_traced_ms_total": "counter",
    "imageregion_profile_renders_total": "counter",
    "imageregion_profile_device_ms_total": "counter",
    "imageregion_profile_idle_ms_total": "counter",
    "imageregion_compile_cache_hits_total": "counter",
    "imageregion_device_peak_bytes": "gauge",
    "imageregion_ready": "gauge",
    "imageregion_breaker_state": "gauge",
    "imageregion_breaker_opens_total": "counter",
    "imageregion_shed_total": "counter",
    "imageregion_retries_total": "counter",
    "imageregion_retry_attempts": "histogram",
    "imageregion_deadline_cancelled_total": "counter",
    "imageregion_degraded_renders_total": "counter",
    "imageregion_supervisor_restarts_total": "counter",
    # Cost-ledger histograms (per-route attribution of where each
    # request's time and bytes went).
    "imageregion_request_cost_device_ms": "histogram",
    "imageregion_request_cost_read_ms": "histogram",
    "imageregion_request_cost_stage_ms": "histogram",
    "imageregion_request_cost_queue_ms": "histogram",
    "imageregion_request_cost_encode_ms": "histogram",
    "imageregion_request_cost_staged_kb": "histogram",
    "imageregion_request_cost_wire_kb": "histogram",
    # SLO burn rates + breach bits.
    "imageregion_slo_burn_rate": "gauge",
    "imageregion_slo_breach": "gauge",
    "imageregion_slo_breaches_total": "counter",
    # Flight-recorder ring state.
    "imageregion_flight_events": "gauge",
    "imageregion_flight_events_total": "counter",
    "imageregion_flight_dumps_total": "counter",
    # Per-ladder-shape device cost (estimated vs observed).
    "imageregion_shape_dispatches_total": "counter",
    "imageregion_shape_device_ms_total": "counter",
    "imageregion_shape_device_ms_mean": "gauge",
    "imageregion_shape_estimated_flops": "gauge",
    "imageregion_shape_estimated_bytes": "gauge",
    # Warm-state persistence tier: disk byte cache, snapshot engine,
    # boot rehydrator, serialized render executables.
    "imageregion_diskcache_writes_total": "counter",
    "imageregion_diskcache_write_errors_total": "counter",
    "imageregion_diskcache_write_dropped_total": "counter",
    "imageregion_diskcache_corrupt_total": "counter",
    "imageregion_diskcache_bytes": "gauge",
    "imageregion_diskcache_entries": "gauge",
    "imageregion_warmstate_snapshots_total": "counter",
    "imageregion_warmstate_snapshot_errors_total": "counter",
    "imageregion_warmstate_snapshot_age_seconds": "gauge",
    "imageregion_warmstate_snapshot_duration_ms": "gauge",
    "imageregion_rehydrate_running": "gauge",
    "imageregion_rehydrate_items_total": "gauge",
    "imageregion_rehydrate_items_done": "gauge",
    "imageregion_rehydrate_errors_total": "counter",
    "imageregion_rehydrate_duration_ms": "gauge",
    "imageregion_rehydrate_bytes_promoted_total": "counter",
    "imageregion_rehydrate_planes_restaged_total": "counter",
    "imageregion_rehydrate_executables_loaded_total": "counter",
    "imageregion_execcache_hits": "counter",
    "imageregion_execcache_misses": "counter",
    "imageregion_execcache_loaded_total": "counter",
    "imageregion_execcache_saved_total": "counter",
    # Data-parallel device fleet (parallel.fleet): consistent-hash
    # routing, per-member batch lanes, bounded work stealing,
    # hash-ring-next failover, HBM shard ownership.
    "imageregion_fleet_members": "gauge",
    "imageregion_fleet_members_healthy": "gauge",
    "imageregion_fleet_member_depth": "gauge",
    "imageregion_fleet_member_inflight": "gauge",
    "imageregion_fleet_member_healthy": "gauge",
    "imageregion_fleet_member_planes": "gauge",
    "imageregion_fleet_member_device": "gauge",
    "imageregion_fleet_routed_total": "counter",
    "imageregion_fleet_stolen_total": "counter",
    "imageregion_fleet_failed_over_total": "counter",
    # Hot-plane replication (parallel.fleet popularity tier):
    # promotion lifecycle, replica staging, balanced reads, and the
    # replica-pressure gauge the autoscaler consumes.
    "imageregion_hotkey_promotions_total": "counter",
    "imageregion_hotkey_demotions_total": "counter",
    "imageregion_hotkey_replica_staged_total": "counter",
    "imageregion_hotkey_duplicate_staged_total": "counter",
    "imageregion_hotkey_balanced_total": "counter",
    "imageregion_hotkey_hot_routes": "gauge",
    "imageregion_hotkey_replica_pressure": "gauge",
    # Self-preservation layer (server.pressure / server.watchdog /
    # fleet drains): brownout ladder state, watchdog fires, rolling
    # drain phases.
    "imageregion_pressure_level": "gauge",
    "imageregion_pressure_level_transitions_total": "counter",
    "imageregion_pressure_signal": "gauge",
    "imageregion_pressure_steps_engaged": "gauge",
    "imageregion_pressure_step_engaged": "gauge",
    "imageregion_pressure_step_transitions_total": "counter",
    "imageregion_watchdog_fires_total": "counter",
    "imageregion_drain_state": "gauge",
    "imageregion_drain_transitions_total": "counter",
    "imageregion_drain_prestaged_planes_total": "counter",
    "imageregion_drains_total": "counter",
    # Elastic autoscaler (server.autoscaler): fleet-size controller
    # over the drain/undrain machinery.
    "imageregion_autoscaler_active_members": "gauge",
    "imageregion_autoscaler_floor": "gauge",
    "imageregion_autoscaler_ceiling": "gauge",
    "imageregion_autoscaler_transitions_total": "counter",
    "imageregion_autoscaler_blocked_total": "counter",
    # Open-loop load model (services.loadmodel): the bench-side
    # arrival generator's integrity counters (offered vs completed vs
    # shed, behind-schedule fires).
    "imageregion_loadmodel_offered_total": "counter",
    "imageregion_loadmodel_completed_total": "counter",
    "imageregion_loadmodel_shed_total": "counter",
    "imageregion_loadmodel_late_fires_total": "counter",
    # Device workloads plane (PR 20): batched mask/overlay
    # rasterization path counters, crash-safe pyramid build jobs,
    # z/t animation streams.
    "imageregion_workload_requests_total": "counter",
    "imageregion_pyramid_jobs_total": "counter",
    "imageregion_pyramid_jobs_active": "gauge",
    "imageregion_pyramid_levels_committed_total": "counter",
    "imageregion_animation_streams_total": "counter",
    "imageregion_animation_frames_total": "counter",
    "imageregion_animation_cancelled_total": "counter",
    "imageregion_animation_first_frame_ms": "gauge",
    # Cross-host fleet federation (parallel.federation): agreed
    # manifest state, join-time agreement outcomes, gossip rounds,
    # warm shard transfers over the wire, remote prestage hints.
    "imageregion_federation_manifest_version": "gauge",
    "imageregion_federation_members": "gauge",
    "imageregion_federation_agreements_total": "counter",
    "imageregion_federation_gossip_total": "counter",
    "imageregion_federation_shard_transfers_total": "counter",
    "imageregion_federation_transfer_bytes_total": "counter",
    "imageregion_federation_remote_prestage_total": "counter",
    # Partition tolerance (QuorumStats): quorum membership verdicts,
    # fence refusals, and the netsplit drill's injected link rules.
    "imageregion_federation_quorum_quorate": "gauge",
    "imageregion_federation_quorum_reachable_hosts": "gauge",
    "imageregion_federation_quorum_hosts": "gauge",
    "imageregion_federation_quorum_transitions_total": "counter",
    "imageregion_federation_quorum_refusals_total": "counter",
    "imageregion_partition_rules": "gauge",
    "imageregion_partition_blocked_total": "counter",
    # Control-plane decision ledger (utils.decisions): every
    # autoscaler / epoch / gossip / drain action as a closed
    # (kind, verdict) pair.
    "imageregion_decision_total": "counter",
    # Fleet-level SLO burn (FleetSloStats): per-host SloEngine window
    # buckets aggregated on the federation frontend.
    "imageregion_fleet_slo_hosts": "gauge",
    "imageregion_fleet_slo_dropped_hosts_total": "counter",
    "imageregion_fleet_slo_burn_rate": "gauge",
    "imageregion_fleet_slo_host_burn_rate": "gauge",
    # Live perf-regression sentinel (server.sentinel / SentinelStats):
    # drift verdicts, per-route live-vs-baseline p99, incident-bundle
    # captures, per-member fleet verdicts off the gossip merge.
    "imageregion_sentinel_drift": "gauge",
    "imageregion_sentinel_keys": "gauge",
    "imageregion_sentinel_ticks_total": "counter",
    "imageregion_sentinel_observations_total": "counter",
    "imageregion_sentinel_drifts_total": "counter",
    "imageregion_sentinel_recoveries_total": "counter",
    "imageregion_sentinel_bundles_total": "counter",
    "imageregion_sentinel_bundle_errors_total": "counter",
    "imageregion_sentinel_live_p99_ms": "gauge",
    "imageregion_sentinel_baseline_p99_ms": "gauge",
    "imageregion_sentinel_member_drift": "gauge",
    # Session-aware serving (services.viewport / services.prefetch /
    # server.admission token buckets / fleet QoS dequeue).
    "imageregion_session_tracked": "gauge",
    "imageregion_session_observations_total": "counter",
    "imageregion_session_evictions_total": "counter",
    "imageregion_prefetch_predicted_total": "counter",
    "imageregion_prefetch_scheduled_total": "counter",
    "imageregion_prefetch_staged_total": "counter",
    "imageregion_prefetch_hits_total": "counter",
    "imageregion_prefetch_skipped_total": "counter",
    "imageregion_prefetch_budget_scale": "gauge",
    "imageregion_qos_shed_total": "counter",
    "imageregion_qos_dequeued_total": "counter",
    "imageregion_qos_interactive_jumps_total": "counter",
    # Wire transport (protocol v3, WireStats): vectored-flush
    # coalescing, shm-ring traffic, chunk streaming.  Registered here
    # so the families carry real TYPE headers and pass the committed
    # cardinality budget (scripts/metrics_lint.py) — they were
    # exposition-only ("untyped") before the budget existed.
    "imageregion_wire_flushes_total": "counter",
    "imageregion_wire_frames_total": "counter",
    "imageregion_wire_flush_bytes_total": "counter",
    "imageregion_wire_frames_per_flush": "gauge",
    "imageregion_wire_ring_hits_total": "counter",
    "imageregion_wire_ring_fallbacks_total": "counter",
    "imageregion_wire_ring_bytes_total": "counter",
    "imageregion_wire_ring_negotiated_total": "counter",
    "imageregion_wire_ring_declined_total": "counter",
    "imageregion_wire_streams_total": "counter",
    "imageregion_wire_chunks_total": "counter",
    # Conditional HTTP + fleet-global byte tier (server.httpcache /
    # parallel.fleet peer fetch): the edge offload ladder's counters.
    "imageregion_httpcache_etag_requests_total": "counter",
    "imageregion_httpcache_304_total": "counter",
    "imageregion_httpcache_head_total": "counter",
    "imageregion_httpcache_peer_probes_total": "counter",
    "imageregion_httpcache_peer_hits_total": "counter",
    "imageregion_httpcache_peer_fetches_total": "counter",
    "imageregion_httpcache_peer_fallbacks_total": "counter",
    "imageregion_httpcache_peer_putbacks_total": "counter",
    # Response provenance (utils.provenance): which byte-source tier
    # answered, per serving member, plus routing flags.
    "imageregion_provenance_total": "counter",
    "imageregion_provenance_flags_total": "counter",
    # Conditional HTTP, Last-Modified leg: If-Modified-Since-only
    # revalidations (the ETag path keeps its own counters).
    "imageregion_httpcache_ims_requests_total": "counter",
}

# Terse HELP strings for the families whose meaning is not obvious
# from the name; every family gets a HELP line (fallback text) so the
# exposition lint can hold "HELP exactly once per family" everywhere.
METRIC_HELP: Dict[str, str] = {
    "imageregion_batcher_group_stacks_total":
        "Groups staged, by path: one program over the members' "
        "resident planes, or a stack of the members' own arrays",
    "imageregion_batcher_bucket_px_total":
        "Pixels of the groups launched, by part: the members' own "
        "image, or the pad their buckets hold beyond it",
    "imageregion_entropy_tiles_total":
        "JPEG tiles entropy-coded, by path: pooled in a group's tail "
        "that several threads coded, inline in one its own thread "
        "coded alone",
    "imageregion_entropy_split_tiles_total":
        "JPEG tiles entropy-coded in more than one segment of MCU rows, "
        "the segments side by side",
    "imageregion_rawcache_channel_loads_total":
        "Channel planes read (or handed over) and uploaded to the HBM "
        "raw cache",
    "imageregion_rawcache_duplicate_loads_total":
        "Channel planes read and uploaded to the HBM raw cache while "
        "another thread loaded the same key, by who lost the race",
    "imageregion_tile_renders_total":
        "Tile renders prepared, by pyramid level and by whether the "
        "region truncated at the level's edge is the whole tile",
    "imageregion_render_pixels_total":
        "Unpadded pixels of every region the handler prepared a render "
        "of",
    "imageregion_pixel_sources_opened_total":
        "Pixel sources constructed: lookups the LRU of open sources "
        "missed",
    "imageregion_pixel_sources_open":
        "Pixel sources the LRU holds open now",
    "imageregion_federation_manifest_version":
        "Shard epoch of the agreed fleet manifest",
    "imageregion_federation_agreements_total":
        "Join-time manifest agreement outcomes by reason",
    "imageregion_federation_gossip_total":
        "Membership gossip round outcomes by reason",
    "imageregion_federation_shard_transfers_total":
        "Warm HBM planes shipped cross-host over shard_transfer",
    "imageregion_federation_remote_prestage_total":
        "Predicted-plane prestage hints sent to remote owners",
    "imageregion_federation_quorum_quorate":
        "1 while this host can gossip with a strict majority of "
        "manifest hosts, 0 while fenced",
    "imageregion_federation_quorum_reachable_hosts":
        "Manifest hosts (self included) heard from within "
        "suspect-after-s",
    "imageregion_federation_quorum_transitions_total":
        "Quorum fence/restore transitions by verdict",
    "imageregion_federation_quorum_refusals_total":
        "State-changing actions refused while fenced, by action",
    "imageregion_profile_captures_total":
        "/debug/profile captures taken by this process",
    "imageregion_profile_device_ms_total":
        "Device milliseconds inside the captures, by named stage of "
        "the device programs (innermost named scope; unnamed = none)",
    "imageregion_profile_busy_ms_total":
        "Milliseconds inside the captures in which an operation ran "
        "on the chip",
    "imageregion_profile_traced_ms_total":
        "Milliseconds inside the captures from the first operation's "
        "start to the last one's end",
    "imageregion_profile_idle_ms_total":
        "Idle device milliseconds inside the captures, by the host "
        "span they overlap (fixed precedence; utils.profile_summary)",
    "imageregion_profile_renders_total":
        "Tiles of the groups whose wire.d2h began (their device.wait "
        "ended) inside a capture's traced interval",
    "imageregion_partition_rules":
        "Injected link-partition rules active in this process",
    "imageregion_partition_blocked_total":
        "Sidecar calls blocked by an injected link partition, by mode",
    "imageregion_decision_total":
        "Control-plane decision-ledger records by kind and verdict",
    "imageregion_fleet_slo_hosts":
        "Hosts currently contributing SLO window buckets to the "
        "fleet burn",
    "imageregion_fleet_slo_dropped_hosts_total":
        "SLO bucket ingests dropped by the host-cardinality bound",
    "imageregion_fleet_slo_burn_rate":
        "Fleet-aggregated error-budget burn per objective and window",
    "imageregion_fleet_slo_host_burn_rate":
        "Per-host error-budget burn per objective and window",
    "imageregion_sentinel_drift":
        "1 while the local perf sentinel holds a confirmed drift "
        "verdict",
    "imageregion_sentinel_keys":
        "Route classes the sentinel currently tracks quantiles for",
    "imageregion_sentinel_ticks_total":
        "Drift-evaluation windows the local sentinel has closed",
    "imageregion_sentinel_observations_total":
        "Requests the local sentinel has sketched",
    "imageregion_sentinel_drifts_total":
        "Per-key drift confirmations (confirm-ticks consecutive "
        "breaching windows)",
    "imageregion_sentinel_recoveries_total":
        "Per-key drift recoveries (recover-ticks consecutive clean "
        "windows)",
    "imageregion_sentinel_live_p99_ms":
        "Live windowed p99 latency per route class (sketch estimate)",
    "imageregion_sentinel_baseline_p99_ms":
        "Self-learned rolling-baseline p99 per route class",
    "imageregion_sentinel_member_drift":
        "Per-member drift verdict off the gossip merge (1 = drifting)",
    "imageregion_sentinel_bundles_total":
        "Forensic incident bundles written on confirmed drift",
    "imageregion_sentinel_bundle_errors_total":
        "Incident-bundle captures that failed (drift verdict stands)",
    "imageregion_request_cost_device_ms":
        "Per-request device-execute ms (pro-rata from batch group)",
    "imageregion_request_cost_read_ms":
        "Per-request cold pixel-store read + staging ms",
    "imageregion_request_cost_stage_ms":
        "Per-request host->HBM staging ms (pro-rata)",
    "imageregion_request_cost_queue_ms":
        "Per-request batcher queue wait ms",
    "imageregion_request_cost_encode_ms":
        "Per-request host encode ms",
    "imageregion_request_cost_staged_kb":
        "Per-request HBM bytes staged (KB, pro-rata)",
    "imageregion_request_cost_wire_kb":
        "Per-request response bytes (KB)",
    "imageregion_slo_burn_rate":
        "Error-budget burn rate per objective and window",
    "imageregion_slo_breach":
        "1 while the objective is in multi-window breach",
    "imageregion_flight_events":
        "Events currently held in the flight-recorder ring",
    "imageregion_shape_estimated_flops":
        "XLA cost_analysis flops estimate of the shape's program",
    "imageregion_batcher_queue_wait_max_ms":
        "High-water dispatched queue wait (cancelled waits excluded)",
    "imageregion_diskcache_corrupt_total":
        "Disk byte-cache entries rejected by checksum/format checks",
    "imageregion_warmstate_snapshot_age_seconds":
        "Seconds since the last warm-state manifest write (0 = never)",
    "imageregion_rehydrate_running":
        "1 while the boot rehydrator is replaying the warm-state "
        "manifest",
    "imageregion_rehydrate_bytes_promoted_total":
        "Disk byte-cache bytes promoted to the memory tier at boot",
    "imageregion_execcache_loaded_total":
        "Serialized render executables deserialized from disk",
    "imageregion_fleet_member_planes":
        "HBM-resident plane entries owned by the member (shard size)",
    "imageregion_fleet_member_device":
        "1 for the device an in-process member is pinned to (its "
        "renders, uploads and prewarm run there)",
    "imageregion_device_renders_total":
        "Renders by the device that ran them, read off each group's "
        "output array",
    "imageregion_fleet_stolen_total":
        "Renders the member stole from a backlogged peer (no cache "
        "adoption)",
    "imageregion_fleet_failed_over_total":
        "Dead-member shard work adopted hash-ring-next by the member",
    "imageregion_pressure_level":
        "Folded resource-pressure level (0 ok, 1 elevated, 2 critical)",
    "imageregion_pressure_signal":
        "Raw pressure-signal reading (fraction of budget, or raw "
        "depth/ms)",
    "imageregion_pressure_steps_engaged":
        "Brownout ladder steps currently engaged (prefix of the "
        "configured ladder)",
    "imageregion_pressure_step_engaged":
        "1 while the named ladder step is engaged",
    "imageregion_pressure_step_transitions_total":
        "Ladder step engage/release transitions",
    "imageregion_watchdog_fires_total":
        "Watchdog healings by action (requeue-group, drop-connection, "
        "escalate)",
    "imageregion_drain_state":
        "Fleet-member drain state (0 active, 1 draining, 2 drained)",
    "imageregion_drain_prestaged_planes_total":
        "Handoff planes pre-staged WARM onto ring successors by drains",
    "imageregion_session_tracked":
        "Distinct sessions currently modeled by the viewport tracker",
    "imageregion_session_evictions_total":
        "Session states evicted by the viewport tracker's LRU bound",
    "imageregion_prefetch_predicted_total":
        "Tiles predicted from session pan/zoom trajectories",
    "imageregion_prefetch_staged_total":
        "Predicted planes actually staged into an HBM tier",
    "imageregion_prefetch_hits_total":
        "Foreground requests that found their plane prefetched",
    "imageregion_prefetch_skipped_total":
        "Prefetch candidates skipped (budget exhausted or paused)",
    "imageregion_prefetch_budget_scale":
        "Live prefetch budget scale (1 full, 0 paused by the ladder)",
    "imageregion_qos_shed_total":
        "Per-session fairness sheds by QoS class (503 + Retry-After)",
    "imageregion_qos_dequeued_total":
        "Fleet-router dequeues by QoS class (weighted two-class queue)",
    "imageregion_qos_interactive_jumps_total":
        "Interactive dequeues that jumped a waiting bulk backlog",
    "imageregion_httpcache_304_total":
        "If-None-Match revalidations answered 304 with zero render/"
        "admission/token work",
    "imageregion_httpcache_head_total":
        "HEAD requests answered headers-only without a render",
    "imageregion_httpcache_peer_hits_total":
        "Authority byte-probes answered resident (peer has the bytes)",
    "imageregion_httpcache_peer_fetches_total":
        "Renders avoided by fetching bytes from a fleet peer's tier",
    "imageregion_httpcache_peer_fallbacks_total":
        "Peer probe/fetch failures that fell back to the render path",
    "imageregion_httpcache_peer_putbacks_total":
        "Stolen-render bytes written back to the shard authority",
    "imageregion_provenance_total":
        "Responses by byte-source tier and serving member "
        "(utils.provenance closed vocabulary)",
    "imageregion_provenance_flags_total":
        "Responses carrying a routing flag (stolen / failed_over / "
        "drain_rehomed / coalesced / quality_capped)",
    "imageregion_httpcache_ims_requests_total":
        "If-Modified-Since-only revalidation arrivals (ETag absent)",
    "imageregion_autoscaler_active_members":
        "Fleet members currently accepting routes (not draining)",
    "imageregion_autoscaler_floor":
        "Autoscaler hard minimum of non-draining members",
    "imageregion_autoscaler_ceiling":
        "Autoscaler maximum of active members (pre-provisioned set)",
    "imageregion_autoscaler_transitions_total":
        "Autoscaler scale transitions by direction (up = undrain "
        "with pre-stage-back, down = drain with warm handoff)",
    "imageregion_autoscaler_blocked_total":
        "Autoscaler decisions refused by reason (cooldown, floor, "
        "ceiling, busy, no-member)",
    "imageregion_loadmodel_offered_total":
        "Open-loop arrivals fired on schedule, by request class",
    "imageregion_loadmodel_completed_total":
        "Open-loop arrivals served, by request class",
    "imageregion_loadmodel_shed_total":
        "Open-loop arrivals refused with 503 + Retry-After",
    "imageregion_loadmodel_late_fires_total":
        "Arrivals fired behind schedule (open-loop integrity: the "
        "generator, not the service, fell behind)",
    "imageregion_workload_requests_total":
        "Device-workloads requests by kind (mask_device/mask_host = "
        "which rasterizer served the mask; overlay; animation)",
    "imageregion_pyramid_jobs_total":
        "Pyramid build job lifecycle transitions by action "
        "(submitted, resumed, completed, failed, cancelled, deferred)",
    "imageregion_pyramid_jobs_active":
        "Pyramid build jobs currently running or deferred",
    "imageregion_pyramid_levels_committed_total":
        "Pyramid levels atomically committed (tmp-dir os.replace)",
    "imageregion_animation_streams_total":
        "z/t animation streams started",
    "imageregion_animation_frames_total":
        "Animation frames written to clients",
    "imageregion_animation_cancelled_total":
        "Animation streams cancelled mid-flight (client disconnect "
        "or deadline) with remaining device work cancelled",
    "imageregion_animation_first_frame_ms":
        "Last animation stream's first-frame latency (the bounded "
        "first-frame-out contract's live gauge)",
    "imageregion_hotkey_promotions_total":
        "Routes promoted to an R>1 replica set (heat past threshold)",
    "imageregion_hotkey_demotions_total":
        "Promoted routes demoted back to R=1 (heat decayed)",
    "imageregion_hotkey_replica_staged_total":
        "Plane entries staged onto replicas at promotion "
        "(digest-deduped; residency probe hits count too)",
    "imageregion_hotkey_duplicate_staged_total":
        "Replica stagings that would have double-staged one "
        "(route, replica) pair in one epoch — a bug counter, held 0",
    "imageregion_hotkey_balanced_total":
        "Reads served by a NON-OWNER replica via least-queued "
        "balancing, by member",
    "imageregion_hotkey_hot_routes":
        "Routes currently holding an R>1 replica set",
    "imageregion_hotkey_replica_pressure":
        "Hottest promoted route's heat over the promotion threshold "
        "(>= 1: one plane is outrunning one member — scale-up signal)",
}

_HIST_SUFFIXES = ("_bucket", "_sum", "_count")


def _family_of(line: str) -> str:
    name = line.split("{", 1)[0].split(" ", 1)[0]
    for suffix in _HIST_SUFFIXES:
        if name.endswith(suffix):
            base = name[: -len(suffix)]
            if METRIC_TYPES.get(base) == "histogram":
                return base
    return name


def finalize_exposition(lines: List[str],
                        openmetrics: bool = False) -> str:
    """Order series by family (first-seen), emit one ``# TYPE`` header
    per family, pass comments through.  The single formatter shared by
    the app's ``/metrics`` and the sidecar merge path, so TYPE headers
    can never duplicate across the process boundary.

    ``openmetrics=True`` produces a body a STRICT OpenMetrics parser
    accepts (the negotiated exposition that carries exemplars — one
    illegal line would fail the whole scrape): free-form comments are
    dropped (only HELP/TYPE/UNIT/EOF may follow ``#``), ``untyped``
    maps to OM's ``unknown``, and counter metadata follows the OM
    naming rule — families ending ``_total`` declare HELP/TYPE under
    the suffix-less name, counters NOT ending ``_total`` (legacy
    names) degrade to ``unknown`` rather than violate the grammar.
    The caller appends the ``# EOF`` terminator."""
    families: Dict[str, List[str]] = {}
    order: List[str] = []
    comments: List[str] = []
    for line in lines:
        line = line.rstrip("\n")
        if not line:
            continue
        if line.startswith("#"):
            # TYPE and HELP are the finalizer's to emit (exactly once
            # per family); merged inputs must not smuggle duplicates.
            if not line.startswith(("# TYPE", "# HELP")):
                comments.append(line)
            continue
        fam = _family_of(line)
        if fam not in families:
            families[fam] = []
            order.append(fam)
        families[fam].append(line)
    present = set(order)
    out: List[str] = []
    for fam in order:
        mtype = METRIC_TYPES.get(fam, "untyped")
        help_text = METRIC_HELP.get(fam, fam.replace("_", " "))
        meta_name = fam
        if openmetrics:
            if mtype == "counter":
                base = fam[: -len("_total")] \
                    if fam.endswith("_total") else None
                if base and base not in present:
                    meta_name = base
                else:
                    # Legacy counter name (no _total suffix), or the
                    # suffix-less name is ITSELF a present family
                    # (imageregion_flight_events_total vs the
                    # ..._events gauge): duplicate metadata would
                    # fail the strict parser — degrade to unknown.
                    mtype = "unknown"
            elif mtype == "untyped":
                mtype = "unknown"
        out.append(f"# HELP {meta_name} {help_text}")
        out.append(f"# TYPE {meta_name} {mtype}")
        out += families[fam]
    if not openmetrics:
        out += comments
    return "\n".join(out) + "\n"


def request_metric_lines(exemplars: bool = False) -> List[str]:
    """The frontend-local request series (histogram + totals), the
    cost-ledger histograms, the SLO burn gauges and the local
    flight-recorder ring state.  ``exemplars=True`` adds the
    OpenMetrics exemplar tails to the request-duration buckets — ONLY
    for scrapes that negotiated ``application/openmetrics-text`` (the
    classic text parser rejects the syntax)."""
    lines = REQUEST_HIST.series("imageregion_request_duration_ms",
                                exemplars=exemplars)
    with _REQ_LOCK:
        totals = sorted(_REQ_TOTALS.items())
    for (route, status), n in totals:
        lines.append(f'imageregion_requests_total{{route="{route}",'
                     f'status="{status}"}} {n}')
    lines += cost_metric_lines()
    lines += HTTPCACHE.metric_lines()
    lines += PROVENANCE.metric_lines()
    lines += SLO.metric_lines()
    lines += SENTINEL.metric_lines()
    lines += [
        f"imageregion_flight_events {len(FLIGHT)}",
        f"imageregion_flight_events_total {FLIGHT.events_total}",
        f"imageregion_flight_dumps_total {FLIGHT.dumps_written}",
    ]
    return lines


def _device_peak_bytes() -> Optional[int]:
    """High-water device memory of this process (the largest over its
    local devices), where the backend reports one — the CPU backend
    does not.  Never the call that initialises a backend."""
    from .jaxenv import initialised_jax
    jax = initialised_jax()
    if jax is None:
        return None
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [int(p) for p in peaks if p is not None]
    return max(peaks) if peaks else None


def _shard_cache_lines(raw_cache, label) -> List[str]:
    """The series of one HBM raw-cache shard (a process's, or an
    in-process fleet member's: :func:`fleet_shard_metric_lines`)."""
    lines: List[str] = []
    if raw_cache is not None:
        lb = label()
        lines += [
            f"imageregion_rawcache_hits{lb} {raw_cache.hits}",
            f"imageregion_rawcache_misses{lb} {raw_cache.misses}",
            f"imageregion_rawcache_bytes{lb} {raw_cache.size_bytes}",
        ]
        if hasattr(raw_cache, "evictions"):
            lines.append(f"imageregion_rawcache_evictions{lb} "
                         f"{raw_cache.evictions}")
        if hasattr(raw_cache, "channel_loads"):
            # Channel planes read (or handed over) and uploaded: what
            # a change of the shown channels costs the store and the
            # link, beside the hits and misses of the planes' lookups.
            lines.append(f"imageregion_rawcache_channel_loads_total{lb} "
                         f"{raw_cache.channel_loads}")
        if hasattr(raw_cache, "plane_hits"):
            # Content-digest staging skips: uploads the plane cache
            # saved (hits) vs paid (misses) — wire probes included.
            lines += [
                f"imageregion_planecache_hits{lb} "
                f"{raw_cache.plane_hits}",
                f"imageregion_planecache_misses{lb} "
                f"{raw_cache.plane_misses}",
            ]
    return lines


def _shard_renderer_lines(renderer, label) -> List[str]:
    """The counters of one batcher (a process's, or an in-process fleet
    member's)."""
    lines: List[str] = []
    if hasattr(renderer, "batches_dispatched"):
        lb = label()
        lines += [
            f"imageregion_batches_dispatched{lb} "
            f"{renderer.batches_dispatched}",
            f"imageregion_tiles_rendered{lb} "
            f"{renderer.tiles_rendered}",
            # Slots of the padded shapes launched, and those of them
            # that held a repeat instead of a render.
            f"imageregion_batcher_shape_slots_total{lb} "
            f"{renderer.shape_slots}",
            f"imageregion_batcher_padded_slots_total{lb} "
            f"{renderer.padded_slots}",
        ]
        # Groups staged by one program over their members' resident
        # planes ("planes"), or from the members' own stacks ("arrays").
        for path, n in getattr(renderer, "group_stacks", {}).items():
            body = f'path="{path}"'
            lines.append("imageregion_batcher_group_stacks_total"
                         f"{label(body)} {n}")
        # Pixels launched: the members' own, and what their buckets
        # hold beyond them.
        for part, n in getattr(renderer, "bucket_px", {}).items():
            body = f'part="{part}"'
            lines.append("imageregion_batcher_bucket_px_total"
                         f"{label(body)} {n}")
    return lines


def fleet_shard_metric_lines(members, base_services) -> List[str]:
    """Each in-process fleet member's raw-cache shard and batcher
    counters under ``member="<name>"``, but the one over
    ``base_services``, whose are the process's own series
    (:func:`device_metric_lines`): each family's sum is then the whole
    fleet's."""
    lines: List[str] = []
    for member in members:
        services = getattr(member, "services", None)
        if services is None or services is base_services:
            continue            # a remote member exports its own

        def label(body: str = "", name=member.name) -> str:
            inner = f'member="{name}"'
            return "{" + (f"{body},{inner}" if body else inner) + "}"

        lines += _shard_cache_lines(services.raw_cache, label)
        lines += _shard_renderer_lines(services.renderer, label)
    return lines


def device_metric_lines(services, extra_labels: str = "") -> List[str]:
    """Series owned by a device-side process (combined app or sidecar):
    caches, raw cache, batcher gauges, compile events.

    ``services`` is duck-typed (``server.handler.ImageRegionServices``)
    so this module stays importable without the server stack;
    ``extra_labels`` is appended inside every label brace (the
    sidecar's ``process="sidecar"``).
    """
    def label(body: str = "") -> str:
        inner = body + (("," if body else "")
                        + extra_labels.lstrip(",") if extra_labels
                        else "")
        return f"{{{inner}}}" if inner else ""

    lines: List[str] = []
    for cache_name in ("image_region", "pixels_metadata", "shape_mask"):
        stack = getattr(getattr(services, "caches", None), cache_name,
                        None)
        for i, tier in enumerate(getattr(stack, "tiers", ())):
            hits = getattr(tier, "hits", None)
            misses = getattr(tier, "misses", None)
            if hits is None:
                continue
            lb = label(f'cache="{cache_name}",tier="{i}"')
            lines += [
                f"imageregion_cache_hits{lb} {hits}",
                f"imageregion_cache_misses{lb} {misses}",
            ]
            evictions = getattr(tier, "evictions", None)
            if evictions is not None:
                lines.append(
                    f"imageregion_cache_evictions{lb} {evictions}")
    lines += _shard_cache_lines(getattr(services, "raw_cache", None),
                                label)
    pixels_service = getattr(services, "pixels_service", None)
    if hasattr(pixels_service, "opened"):
        # The LRU of open pixel sources: a plate of more images than
        # it holds re-opens one a request.
        lb = label()
        lines += [
            f"imageregion_pixel_sources_opened_total{lb} "
            f"{pixels_service.opened}",
            f"imageregion_pixel_sources_open{lb} "
            f"{pixels_service.open_count()}",
        ]
    single_flight = getattr(services, "single_flight", None)
    if single_flight is not None:
        lb = label()
        lines += [
            f"imageregion_singleflight_hits{lb} {single_flight.hits}",
            f"imageregion_singleflight_misses{lb} "
            f"{single_flight.misses}",
            f"imageregion_singleflight_inflight{lb} "
            f"{single_flight.inflight()}",
        ]
    renderer = getattr(services, "renderer", None)
    lines += _shard_renderer_lines(renderer, label)
    if hasattr(renderer, "queue_depth"):
        lb = label()
        lines += [
            f"imageregion_batcher_queue_depth{lb} "
            f"{renderer.queue_depth()}",
            f"imageregion_pipeline_inflight{lb} "
            f"{renderer.inflight()}",
            f"imageregion_batcher_max_batch{lb} {renderer.max_batch}",
        ]
        if hasattr(renderer, "queue_wait_max_ms"):
            # High-water queue wait: the stragglers a mean hides and a
            # p50 cannot see at all.
            lines.append(f"imageregion_batcher_queue_wait_max_ms{lb} "
                         f"{round(renderer.queue_wait_max_ms, 3)}")
    # JPEG tiles coded, by whether their group's tail ran on more than
    # one thread.  The process's, as its one coding pool is.
    for path, n in entropypool.TILES.items():
        body = f'path="{path}"'
        lines.append("imageregion_entropy_tiles_total"
                     f"{label(body)} {n}")
    lb = label()
    lines += [
        f"imageregion_entropy_split_tiles_total{lb} "
        f"{entropypool.SPLIT_TILES}",
        f"imageregion_compile_events_total{lb} {COMPILE.events}",
        f"imageregion_compile_ms_total{lb} "
        f"{round(COMPILE.total_ms, 3)}",
        f"imageregion_compile_cache_hits_total{lb} "
        f"{COMPILE.cache_hits}",
    ]
    peak = _device_peak_bytes()
    if peak is not None:
        lines.append(f"imageregion_device_peak_bytes{lb} {peak}")
    # Per-ladder-shape estimated vs observed device cost (the batcher
    # records both; cardinality is bounded by the bucket/batch ladder).
    lines += SHAPE_COSTS.metric_lines(extra_labels)
    # What the /debug/profile captures taken so far add up to.
    lines += PROFILE.metric_lines(extra_labels)
    # The handler's choice between the device and the host render.
    lines += ROUTES.metric_lines(extra_labels)
    # Which chip ran them.
    lines += DEVICE_RENDERS.metric_lines(extra_labels)
    # Loads of a raw-cache key that another thread loaded meanwhile.
    lines += DUPLICATE_LOADS.metric_lines(extra_labels)
    # Tile renders by level and edge, and the pixels of every render.
    lines += TILE_RENDERS.metric_lines(extra_labels)
    # Warm-state persistence tier (disk byte cache, snapshot engine,
    # boot rehydrator) — device-side state, merged like the rest.
    lines += PERSIST.metric_lines(extra_labels)
    exec_cache = getattr(getattr(services, "renderer", None),
                         "exec_cache", None)
    if exec_cache is not None:
        lines += [
            f"imageregion_execcache_hits{lb} {exec_cache.hits}",
            f"imageregion_execcache_misses{lb} {exec_cache.misses}",
            f"imageregion_execcache_loaded_total{lb} "
            f"{exec_cache.loaded}",
            f"imageregion_execcache_saved_total{lb} "
            f"{exec_cache.saved}",
        ]
    if extra_labels:
        # The sidecar's flight-recorder ring, labelled so the
        # frontend's merged exposition keeps both processes' series
        # distinct.  Combined/frontend processes emit their own copy
        # unlabelled via request_metric_lines.
        lines += [
            f"imageregion_flight_events{lb} {len(FLIGHT)}",
            f"imageregion_flight_events_total{lb} "
            f"{FLIGHT.events_total}",
            f"imageregion_flight_dumps_total{lb} "
            f"{FLIGHT.dumps_written}",
        ]
    return lines


def reset() -> None:
    """Test isolation: clear every process-global accumulator —
    repeated in-process test apps must not leak counts (or SLO breach
    state, or flight events) across tests."""
    TRACES.reset()
    REQUEST_HIST.reset()
    with _REQ_LOCK:
        _REQ_TOTALS.clear()
    COMPILE.reset()
    READINESS.reset()
    RESILIENCE.reset()
    for hist in COST_HISTS.values():
        hist.reset()
    COST_TOPK.reset()
    FLIGHT.reset()
    SLO.reset()
    SHAPE_COSTS.reset()
    PROFILE.reset()
    ROUTES.reset()
    DEVICE_RENDERS.reset()
    DUPLICATE_LOADS.reset()
    TILE_RENDERS.reset()
    entropypool.reset_counts()
    PERSIST.reset()
    WIRE.reset()
    FLEET.reset()
    HOTKEY.reset()
    PRESSURE.reset()
    WATCHDOG.reset()
    DRAIN.reset()
    AUTOSCALER.reset()
    LOADMODEL.reset()
    WORKLOADS.reset()
    FEDERATION.reset()
    QUORUM.reset()
    DECISIONS.reset()
    FED_SLO.reset()
    SENTINEL.reset()
    SESSIONS.reset()
    PREFETCH.reset()
    QOS.reset()
    HTTPCACHE.reset()
    PROVENANCE.reset()
    # The decision ledger lives in utils.decisions (which imports this
    # module); reset it from here so ONE reset() call keeps the whole
    # forensics plane test-isolated.  Lazy import breaks the cycle.
    from . import decisions as _decisions
    _decisions.LEDGER.reset()
