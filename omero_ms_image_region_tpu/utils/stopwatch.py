"""Stage timing spans (≙ perf4j ``Slf4JStopWatch``).

The reference wraps every pipeline stage in a named stopwatch whose
start/elapsed pairs double as latency metrics in the logs (SURVEY.md §5:
``ImageRegionVerticle.java:148``, ``ImageRegionRequestHandler.java:189,303,
343,502,522``).  The span names are kept verbatim so dashboards built on the
Java service's logs keep working against this one.

Spans log at debug level and feed an in-process aggregator exposed on
``/metrics``; in a device-owning process each is also an annotation in
the profiler's capture (``install_annotations``).  Each span keeps a
fixed log-scale bucketed histogram
(``utils.telemetry.Histogram``) — proper Prometheus
``_bucket``/``_sum``/``_count`` series, replacing the old 256-sample
ring whose p50 hid tail regressions.  Every recorded duration is also
offered to the active request trace(s) (``telemetry.observe_span``), so
stage timings double as waterfall child spans.
"""

from __future__ import annotations

import asyncio
import logging
import threading
import time
from contextlib import contextmanager, nullcontext
from typing import Dict

from .telemetry import Histogram, observe_span, record_span

log = logging.getLogger("omero_ms_image_region_tpu.perf")


class SpanStats:
    __slots__ = ("count", "total_ms", "max_ms", "hist")

    def __init__(self):
        self.count = 0
        self.total_ms = 0.0
        self.max_ms = 0.0
        self.hist = Histogram()

    def add(self, ms: float) -> None:
        self.count += 1
        self.total_ms += ms
        if ms > self.max_ms:
            self.max_ms = ms
        self.hist.add(ms)

    def as_dict(self) -> dict:
        return {
            "count": self.count,
            "total_ms": round(self.total_ms, 3),
            "mean_ms": round(self.total_ms / self.count, 3)
            if self.count else 0.0,
            # Bucket-resolution estimate (upper bucket bound), kept for
            # the profiling scripts that read the old ring p50.
            "p50_ms": round(self.hist.quantile(0.5), 3),
            # Tail breakdown: BENCH_r05's batcher.queueWait showed mean
            # 2276 ms against p50 2.2 ms — a heavy tail a mean conflates
            # and a p50 cannot see.  p95/p99 are bucket-resolution
            # estimates like p50; max is exact.
            "p95_ms": round(self.hist.quantile(0.95), 3),
            "p99_ms": round(self.hist.quantile(0.99), 3),
            "max_ms": round(self.max_ms, 3),
        }


class StopWatchRegistry:
    def __init__(self):
        self._lock = threading.Lock()
        self._spans: Dict[str, SpanStats] = {}

    def add(self, name: str, ms: float) -> None:
        """The span's series alone, no trace's child span."""
        with self._lock:
            stats = self._spans.get(name)
            if stats is None:
                stats = self._spans[name] = SpanStats()
            stats.add(ms)

    def record(self, name: str, ms: float, **meta) -> None:
        """``meta`` goes with the span on the request's trace only."""
        self.add(name, ms)
        # Outside the lock: trace recording takes the trace's own lock.
        observe_span(name, ms, **meta)

    def snapshot(self) -> Dict[str, dict]:
        with self._lock:
            return {name: s.as_dict() for name, s in self._spans.items()}

    def histograms(self) -> Dict[str, Histogram]:
        """Shallow snapshot of the live histograms (read-only use)."""
        with self._lock:
            return dict((name, s.hist)
                        for name, s in self._spans.items())

    def reset(self) -> None:
        with self._lock:
            self._spans.clear()


REGISTRY = StopWatchRegistry()


def span_lines(extra_labels: str = "",
               registry: StopWatchRegistry = REGISTRY) -> list:
    """Prometheus exposition lines for every span — the one formatter
    shared by the app's /metrics and the sidecar's metrics op.

    Per span: ``imageregion_span_count`` plus the full
    ``imageregion_span_ms`` histogram (``_bucket``/``_sum``/``_count``;
    a mean over a window is the growth of ``_sum`` over the growth of
    the count).  ``extra_labels`` is appended inside the label braces
    (e.g. ``,process="sidecar"``)."""
    extra = extra_labels.lstrip(",")
    lines = []
    with registry._lock:
        items = sorted((name, s.count, s.hist)
                       for name, s in registry._spans.items())
        for name, count, hist in items:
            body = f'span="{name}"' + (f",{extra}" if extra else "")
            lines.append(f"imageregion_span_count{{{body}}} {count}")
            lines += hist.series("imageregion_span_ms", body)
    return lines


# The profiler's host-side annotation, or None.  This module (like
# utils.telemetry) must import without JAX, so the device-owning
# process installs it, the way it installs the compile listener.
_ANNOTATION = None


def install_annotations() -> bool:
    """Make every span also a ``jax.profiler.TraceAnnotation``: with a
    profiler session live (``/debug/profile``) it lands on the thread's
    host line on the clock of the device planes; with none it is one
    flag test.  Device-owning processes only; returns whether the
    annotation is active."""
    global _ANNOTATION
    try:
        from jax.profiler import TraceAnnotation
    except Exception:       # pragma: no cover - jax-free frontends
        return False
    _ANNOTATION = TraceAnnotation
    return True


class Span:
    """What ``stopwatch`` yields: ``ms`` is the span's duration once it
    has closed (0 before)."""
    __slots__ = ("ms",)

    def __init__(self):
        self.ms = 0.0


@contextmanager
def stopwatch(name: str, registry: StopWatchRegistry = REGISTRY, **meta):
    """Time a stage under a reference span name, e.g.
    ``Renderer.renderAsPackedInt`` or ``ProjectionService.projectStack``.
    ``meta`` (numbers and short strings: ``group_id``, ``tiles``,
    ``channels``) goes with the profiler annotation and with the span
    on the request's trace."""
    span = Span()
    with (_ANNOTATION(name, **meta) if _ANNOTATION is not None
          else nullcontext()):
        t0 = time.perf_counter()
        try:
            yield span
        finally:
            span.ms = ms = (time.perf_counter() - t0) * 1000.0
            registry.record(name, ms, **meta)
            log.debug("time[%s] = %.3f ms", name, ms)


def record_since(name: str, t0: float, t1: float = None,
                 trace_ids: tuple = None, **meta) -> float:
    """Record span ``name`` from the ``time.perf_counter`` stamp ``t0``
    to the stamp ``t1`` (now, when None), and return that end.  For a
    span that crosses an ``await`` or a thread: ``stopwatch`` there
    would put a profiler annotation on the event loop's line that
    interleaves with every other request's, or on the wrong thread.
    Same series on ``/metrics``; the child span, with ``meta``, goes on
    the traces ``trace_ids`` names (the context's, when None; the
    batcher's callers run under none and name their requests')."""
    end = time.perf_counter() if t1 is None else t1
    ms = (end - t0) * 1000.0
    REGISTRY.add(name, ms)
    record_span(name, t0, ms, trace_ids=trace_ids, **meta)
    return end


class LoopLagSampler:
    """How late the event loop runs what is due: sleep ``INTERVAL_S``,
    record the lateness as span ``loop.lag``.  One task a serving loop,
    always on.  ``ewma_ms`` is what the pressure governor reads as its
    ``loop_lag_ms`` signal: smoothed over about three seconds, the
    memory the governor's own timing had (0.3 of each one-second
    tick's lateness), so that one GC pause does not read as sustained
    lag: a single 300 ms stall moves it by 10 ms."""

    INTERVAL_S = 0.1
    # 1 - 0.7 ** INTERVAL_S: ten samples weigh what one tick's 0.3 did.
    ALPHA = 0.035

    def __init__(self):
        self.ewma_ms = 0.0

    def observe(self, lag_ms: float) -> None:
        REGISTRY.add("loop.lag", lag_ms)
        self.ewma_ms += self.ALPHA * (lag_ms - self.ewma_ms)

    async def run(self) -> None:
        while True:
            due = time.perf_counter() + self.INTERVAL_S
            await asyncio.sleep(self.INTERVAL_S)
            self.observe(max(0.0, (time.perf_counter() - due) * 1000.0))
