"""Adaptive JPEG wire-engine selection.

``renderer.jpeg-engine: auto`` used to probe the device->host link once
at startup (``utils.linkprobe``) and freeze the choice — but a link's
rate can change under a running service, and the wrong engine costs
throughput (the sparse wire stalls on a congested link; the huffman
engine wastes a fast one).  This controller keeps the choice live:

- every sparse wire fetch big enough to be bandwidth-dominated feeds an
  EWMA of the observed link rate (``observe_fetch`` — wired into the
  fetchers by ``ops.jpegenc.set_fetch_observer``);
- the engine flips when the EWMA crosses the sparse/huffman crossover
  with hysteresis (a band, so link noise cannot thrash engines — each
  flip costs a one-time compile of the other engine's program);
- while in huffman (whose small fetches are latency-dominated and say
  nothing useful about bandwidth) — and after any idle gap — the link
  is re-probed with a real transfer, so recovery back to sparse is
  observed rather than assumed.

Pod-safe on multi-host meshes by construction: the engines build
different SPMD programs, so per-HOST flips would diverge the pod —
instead ONLY the leader consults the controller, at group boundaries,
and the chosen engine rides the existing per-group pod announcement
(``parallel/serve.py``), so every process launches the identical
sharded program for each group.

Reference analogue: the compression level/codec applied per render in
``ImageRegionRequestHandler.java:559,580-582`` — here the *wire format*
adapts per group instead.
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Callable, Optional

from .linkprobe import AUTO_SPARSE_MIN_MB_S, measure_fetch_mb_s

logger = logging.getLogger(__name__)

# Fetches below this are latency-dominated and carry no bandwidth
# signal (256 KB at the 12 MB/s crossover is ~21 ms — anything
# smaller mostly measures the round trip).
MIN_OBSERVATION_BYTES = 256 * 1024


class AdaptiveEngine:
    """EWMA link-rate tracker choosing "sparse" or "huffman" live."""

    def __init__(self,
                 initial_engine: Optional[str] = None,
                 initial_rate_mb_s: Optional[float] = None,
                 crossover_mb_s: float = AUTO_SPARSE_MIN_MB_S,
                 hysteresis: float = 0.25,
                 alpha: float = 0.3,
                 reprobe_interval_s: float = 20.0,
                 idle_reprobe_s: float = 30.0,
                 probe: Callable[[], float] = None,
                 clock: Callable[[], float] = time.monotonic):
        self.crossover = crossover_mb_s
        self.hysteresis = hysteresis
        self.alpha = alpha
        self.reprobe_interval_s = reprobe_interval_s
        self.idle_reprobe_s = idle_reprobe_s
        # Re-probes run mid-serving: keep them lighter than the startup
        # probe (1 MB x 2 vs 4 MB x 3).
        self._probe = probe or (
            lambda: measure_fetch_mb_s(nbytes=1 << 20, repeats=2))
        self._clock = clock
        self._lock = threading.Lock()
        self.rate_mb_s = initial_rate_mb_s
        if initial_engine is None:
            initial_engine = self._pick(initial_rate_mb_s, "sparse")
        self.engine = initial_engine
        self.switches = 0            # metrics / tests
        self._suspect = 0
        self._probe_due = False
        now = clock()
        self._last_observation = now
        self._last_probe = now

    # Consecutive low conflated (compute-synced) readings before a real
    # probe is forced; see observe_fetch.
    SUSPECT_STREAK = 4

    # ------------------------------------------------------------ policy

    def _pick(self, rate: Optional[float], current: str) -> str:
        """Hysteresis band around the crossover: flip only on a clear
        signal, hold inside the band."""
        if rate is None:
            return current
        hi = self.crossover * (1.0 + self.hysteresis)
        lo = self.crossover * (1.0 - self.hysteresis)
        if rate >= hi:
            return "sparse"
        if rate <= lo:
            return "huffman"
        return current

    def _update(self, rate_sample: float, replace: bool = False) -> None:
        """Caller holds the lock.  ``replace`` skips the EWMA blend —
        used for explicit probes, which are direct link measurements
        that must not be damped by a stale estimate (an idle gap means
        the EWMA describes a link that may no longer exist)."""
        if replace or self.rate_mb_s is None:
            self.rate_mb_s = rate_sample
        else:
            self.rate_mb_s = (self.alpha * rate_sample
                              + (1.0 - self.alpha) * self.rate_mb_s)
        new = self._pick(self.rate_mb_s, self.engine)
        if new != self.engine:
            self.switches += 1
            logger.info(
                "adaptive wire engine: %s -> %s (link EWMA %.1f MB/s, "
                "crossover %.1f MB/s)", self.engine, new,
                self.rate_mb_s, self.crossover)
            self.engine = new

    # ------------------------------------------------------------ inputs

    def observe_fetch(self, nbytes: int, seconds: float,
                      conflated: bool = False) -> None:
        """Feed one device->host wire fetch (called from the fetchers).

        Small fetches are ignored (latency-dominated); the timestamp
        still counts as activity so idle detection stays honest.

        ``conflated`` samples timed device execution along with the
        transfer, so their rate is only a LOWER BOUND on the link: a
        high reading is real evidence (the link carried at least that),
        but a low one cannot distinguish slow-link from slow-compute.
        Low conflated readings therefore never feed the EWMA directly —
        they accumulate suspicion that triggers a real probe on the
        next :meth:`current` call instead.
        """
        now = self._clock()
        with self._lock:
            self._last_observation = now
            if nbytes < MIN_OBSERVATION_BYTES or seconds <= 0:
                return
            rate = nbytes / 1e6 / seconds
            if conflated:
                if rate >= self.crossover * (1.0 + self.hysteresis):
                    # Lower bound already above the sparse band: safe
                    # to count (the true rate is even higher).
                    self._suspect = 0
                    self._update(rate)
                elif self.engine == "sparse":
                    self._suspect += 1
                    if self._suspect >= self.SUSPECT_STREAK:
                        # Persistently low lower-bounds: force a real
                        # probe at the next engine query.
                        self._last_probe = (
                            now - self.reprobe_interval_s)
                        self._probe_due = True
                return
            self._suspect = 0
            self._update(rate)

    def current(self) -> str:
        """The engine to use for the next group.

        Runs on the render worker thread, so a due re-probe (huffman
        steady state, or an idle gap) may block briefly on a real
        transfer — that is the price of *observing* link recovery
        instead of assuming it.
        """
        now = self._clock()
        with self._lock:
            idle = (now - self._last_observation) >= self.idle_reprobe_s
            stale = (self.engine == "huffman"
                     and (now - self._last_probe)
                     >= self.reprobe_interval_s)
            suspect = self._probe_due
            if not (idle or stale or suspect):
                return self.engine
            self._probe_due = False
            self._suspect = 0
            self._last_probe = now
            self._last_observation = now
        try:
            rate = self._probe()
        except Exception:
            logger.warning("adaptive engine re-probe failed",
                           exc_info=True)
            return self.engine
        with self._lock:
            self._update(rate, replace=True)
            return self.engine
