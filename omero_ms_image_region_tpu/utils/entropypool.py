"""The process's coding threads: a group's tiles entropy-coded side by
side instead of one after the other.

The host tail of a JPEG group (``ops.jpegenc.finish_sparse_to_jpegs``)
is native code that gives the GIL up, on a machine of several cores,
and was a serial loop on the group's one thread.  Here the tail's tiles
are cut into runs, and the runs are taken by the group's own thread and
by the threads of ONE pool that every group of the process shares: four
groups coding at once share its threads instead of starting 4 x B of
their own.  A tail of one run, coded while no other tail is, has its
large tiles cut into segments of whole MCU rows, coded side by side in
the same way (:meth:`EntropyPool.code_segments`); otherwise a tail of
one run never touches the pool.

JAX-free (the frontend's ``/metrics`` reads :data:`TILES`).
"""

from __future__ import annotations

import collections
import contextvars
import os
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Optional

# Tiles coded, by path: "pooled" in a tail that more than one thread
# coded, "inline" in a tail its group's thread coded alone
# (/metrics imageregion_entropy_tiles_total{path=}).
TILES = {"pooled": 0, "inline": 0}
_TILES_LOCK = threading.Lock()
# Tiles coded in more than one segment
# (/metrics imageregion_entropy_split_tiles_total).
SPLIT_TILES = 0

# What a thread codes between two takes of the GIL: a run holds at most
# this many pixels of tiles (16 stock 256^2 tiles, one 1024^2 tile), so
# that small tiles do not pay the interpreter once each and a large
# one's answer does not wait for its neighbour's coding.
RUN_PX = 1024 * 1024

# The fewest MCUs a segment of a split tile holds: a split's fixed cost
# (a helper's hand-off, tens of microseconds a thread) stays a few per
# cent of a segment's coding (~0.3 ms at 256 MCUs), and a 256^2 stock
# tile (256 MCUs) is never cut.
SEGMENT_MCUS = 256


class EntropyPool:
    """``threads`` coding threads (started as they are first needed)
    beside whichever thread brings a tail."""

    def __init__(self, threads: int):
        self.threads = max(0, threads)
        self._executor = (
            ThreadPoolExecutor(self.threads,
                               thread_name_prefix="entropy-coder")
            if self.threads else None)
        # Tails being coded (:meth:`code` calls that have not returned).
        self._tails = 0
        self._tails_lock = threading.Lock()

    def runs(self, tiles: int, tile_px: int) -> list:
        """``range(tiles)`` cut into the runs a tail is coded in: as
        many as the threads that may code it, more where a run would
        pass :data:`RUN_PX`."""
        width = min(tiles, self.threads + 1)
        length = max(1, min(-(-tiles // max(1, width)),
                            RUN_PX // max(1, tile_px)))
        return [range(a, min(a + length, tiles))
                for a in range(0, tiles, length)]

    def segments(self, tiles: int, h16: int, w16: int) -> int:
        """How many segments each tile of a tail of ``tiles`` tiles of
        ``h16`` x ``w16`` MCUs is cut into, in whole MCU rows of at
        least :data:`SEGMENT_MCUS` MCUs a segment: one for this thread
        and one for each of the pool's, where the tail is one run and
        no other tail is being coded (every coder is free); else 1, the
        tile is not cut.  Where other tails are coded, the cores a split
        would take are busy with them, or with the reads and the serving
        loop beside them, and a split costs more than it saves."""
        if self._tails or len(self.runs(tiles, h16 * w16 * 256)) > 1:
            return 1
        rows = -(-SEGMENT_MCUS // max(1, w16))
        return max(1, min(self.threads + 1, h16 // rows))

    def code(self, tiles: int, tile_px: int,
             code_run: Callable[[range], Optional[set]]) -> None:
        """``code_run(run)`` for every run of ``range(tiles)``, on this
        thread and on as many of the pool's as there are further runs
        and free threads.  Returns when every run has ended; the first
        exception of any run is raised then, and runs not yet taken are
        dropped.  ``code_run`` may return the threads that coded
        segments of its tiles (:meth:`code_segments`): the tail's tiles
        count as ``pooled`` where more than one thread coded some part
        of it."""
        pending = collections.deque(self.runs(tiles, tile_px))
        coders = set()
        failed = []

        def drain() -> None:
            while not failed:
                try:
                    run = pending.popleft()
                except IndexError:
                    return
                coders.add(threading.get_ident())
                try:
                    coders.update(code_run(run) or ())
                except BaseException as e:
                    failed.append(e)

        self._count_tail(1)
        try:
            self._with_helpers(len(pending) - 1, drain)
        finally:
            self._count_tail(-1)
        if failed:
            raise failed[0]
        with _TILES_LOCK:
            TILES["pooled" if len(coders) > 1 else "inline"] += tiles

    def code_segments(self, n: int, work: Callable[[], int]) -> set:
        """One tile in ``n`` segments: ``work()`` once on this thread and
        once on each of up to ``n - 1`` of the pool's threads.  A
        ``work()`` call claims segments until none is left and returns
        how many it coded; it may wait for calls that are coding, never
        for one that has not started.  Returns when every call has
        returned, with the threads that coded a segment; the first
        exception is raised then.  May be called from a pool thread that
        codes a run."""
        global SPLIT_TILES
        coders = set()
        failed = []

        def coder() -> None:
            try:
                if work():
                    coders.add(threading.get_ident())
            except BaseException as e:
                failed.append(e)

        self._with_helpers(n - 1, coder)
        if failed:
            raise failed[0]
        if n > 1:
            with _TILES_LOCK:
                SPLIT_TILES += 1
        return coders

    def _with_helpers(self, count: int, fn: Callable[[], None]) -> None:
        """``fn()`` on this thread and on up to ``count`` of the pool's
        threads at once (``fn`` raises nothing).  A helper that finds
        the pool busy with other tails starts late, or never: once this
        thread's call has returned, the helpers still queued are
        cancelled and the others waited for.  Each runs under a copy of
        this thread's context (the group's traces, for a tile that
        takes the dense path)."""
        helpers = ([self._executor.submit(contextvars.copy_context().run,
                                          fn)
                    for _ in range(min(self.threads, count))]
                   if self._executor is not None else [])
        fn()
        for helper in helpers:
            if not helper.cancel():
                helper.result()

    def _count_tail(self, step: int) -> None:
        with self._tails_lock:
            self._tails += step


# The threads the process keeps busy beside the coders, as far as the
# code can see them: the serving loop's, and the group threads of the
# deepest pipeline a batcher was built with (one, the caller's own,
# where none was).
_GROUP_THREADS = 1
_POOL: Optional[EntropyPool] = None
_POOL_LOCK = threading.Lock()


def expect_group_threads(depth: int) -> None:
    """A batcher says how many groups it runs at once (its
    ``pipeline_depth``): threads that code too, and that the pool's
    size leaves cores for."""
    global _GROUP_THREADS
    _GROUP_THREADS = max(_GROUP_THREADS, int(depth))


def pool_threads() -> int:
    """The pool's size: the cores the process may use, less the serving
    loop's thread and the group threads."""
    return max(0, len(os.sched_getaffinity(0)) - 1 - _GROUP_THREADS)


def reset_counts() -> None:
    """Test isolation (``telemetry.reset``): the split tiles from zero."""
    global SPLIT_TILES
    with _TILES_LOCK:
        SPLIT_TILES = 0


def pool() -> EntropyPool:
    """The process's one pool, sized when the first tail needs it."""
    global _POOL
    if _POOL is None:
        with _POOL_LOCK:
            if _POOL is None:
                _POOL = EntropyPool(pool_threads())
    return _POOL
