"""The process's coding threads: a group's tiles entropy-coded side by
side instead of one after the other.

The host tail of a JPEG group (``ops.jpegenc.finish_sparse_to_jpegs``)
is native code that gives the GIL up, on a machine of several cores,
and was a serial loop on the group's one thread.  Here the tail's tiles
are cut into runs, and the runs are taken by the group's own thread and
by the threads of ONE pool that every group of the process shares: four
groups coding at once share its threads instead of starting 4 x B of
their own.  A tail of one run never touches the pool.

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

# What a thread codes between two takes of the GIL: a run holds at most
# this many pixels of tiles (16 stock 256^2 tiles, one 1024^2 tile), so
# that small tiles do not pay the interpreter once each and a large
# one's answer does not wait for its neighbour's coding.
RUN_PX = 1024 * 1024


class EntropyPool:
    """``threads`` coding threads (started as they are first needed)
    beside whichever thread brings a tail."""

    def __init__(self, threads: int):
        self.threads = max(0, threads)
        self._executor = (
            ThreadPoolExecutor(self.threads,
                               thread_name_prefix="entropy-coder")
            if self.threads else None)

    def runs(self, tiles: int, tile_px: int) -> list:
        """``range(tiles)`` cut into the runs a tail is coded in: as
        many as the threads that may code it, more where a run would
        pass :data:`RUN_PX`."""
        width = min(tiles, self.threads + 1)
        length = max(1, min(-(-tiles // max(1, width)),
                            RUN_PX // max(1, tile_px)))
        return [range(a, min(a + length, tiles))
                for a in range(0, tiles, length)]

    def code(self, tiles: int, tile_px: int,
             code_run: Callable[[range], None]) -> None:
        """``code_run(run)`` for every run of ``range(tiles)``, on this
        thread and on as many of the pool's as there are further runs
        and free threads.  Returns when every run has ended; the first
        exception of any run is raised then, and runs not yet taken are
        dropped."""
        runs = self.runs(tiles, tile_px)
        coders = 1
        if len(runs) > 1 and self._executor is not None:
            coders = self._fan_out(runs, code_run)
        else:
            for run in runs:
                code_run(run)
        with _TILES_LOCK:
            TILES["pooled" if coders > 1 else "inline"] += tiles

    def _fan_out(self, runs: list, code_run) -> int:
        """:meth:`code` over the pool; returns how many threads took a
        run."""
        pending = collections.deque(runs)
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
                    code_run(run)
                except BaseException as e:
                    failed.append(e)

        # A helper that finds the pool busy with other groups' tiles
        # starts late, or never: this thread takes the runs itself
        # meanwhile, and cancels the helpers still queued when none is
        # left.  Each runs under a copy of this thread's context (the
        # group's traces, for a tile that takes the dense path).
        helpers = [self._executor.submit(contextvars.copy_context().run,
                                         drain)
                   for _ in range(min(self.threads, len(runs) - 1))]
        drain()
        for helper in helpers:
            if not helper.cancel():
                helper.result()
        if failed:
            raise failed[0]
        return len(coders)


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


def pool() -> EntropyPool:
    """The process's one pool, sized when the first tail needs it."""
    global _POOL
    if _POOL is None:
        with _POOL_LOCK:
            if _POOL is None:
                _POOL = EntropyPool(pool_threads())
    return _POOL
