"""PixelsService: image id -> PixelSource (≙ ``ome.io.nio.PixelsService``,
consumed at ``ImageRegionRequestHandler.java:302-309``).

The reference resolves an image through the OMERO DB + binary repository;
here a data directory holds one chunked pyramid per image
(``<data_dir>/<image_id>/meta.json``), mirroring the reference's
``omero.data.dir`` layout role (``config.yaml:19-20``).
"""

from __future__ import annotations

import gc
import logging
import os
import resource
import sys
import threading
from collections import OrderedDict
from typing import List, Optional

from ..utils.stopwatch import stopwatch
from .ngff import NgffZarrSource, find_ngff
from .ometiff import OmeTiffSource, find_tiff
from .pixelsource import PixelSource
from .store import ChunkedPyramidStore

log = logging.getLogger(__name__)

# The bound on open sources follows what the process can hold
# (``derive_max_open``), and is never below this.  It is also the
# constructor's default, and the most OME-TIFF sources kept open.
MAX_OPEN_FLOOR = 128
# What one open source is reckoned to hold.  A ChunkedPyramidStore holds
# one descriptor and one mapping for each level it has mapped (the
# ``mmap`` under ``np.memmap`` keeps a duplicate of the file's
# descriptor); a ROMIO buffer one descriptor; an NGFF source none
# between reads.  Eight is a store read at eight levels (a 32k² slide
# down to 256²); a screening plate's field maps one.  OME-TIFF sources
# are held to the floor instead (``PixelsService._bound_tiffs_locked``).
SOURCE_FDS = 8
SOURCE_MAPS = 8
# Left to the rest of the process.  Descriptors: sockets, logs, the
# device runtime's files, the compile cache.  Mappings: shared
# libraries, thread stacks, malloc's arenas and large buffers (each a
# mapping of its own), the device runtime.
FD_RESERVE = 4096
MAP_RESERVE = 16384
MAX_MAP_COUNT_PATH = "/proc/sys/vm/max_map_count"


def raise_fd_limit() -> int:
    """Raise this process's soft ``RLIMIT_NOFILE`` to its hard limit,
    never lowering it; return the soft limit as it stands after.  A
    refused raise is logged and leaves the limit as it was.  The serving
    loop waits with epoll, so descriptors above 1024 are safe."""
    soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
    if hard <= soft:
        return soft
    try:
        resource.setrlimit(resource.RLIMIT_NOFILE, (hard, hard))
    except (ValueError, OSError) as e:
        log.warning("could not raise RLIMIT_NOFILE from %d to %d: %s",
                    soft, hard, e)
        return soft
    return hard


def read_max_map_count(path: str = MAX_MAP_COUNT_PATH) -> Optional[int]:
    """The kernel's limit on one process's mappings, or None where the
    host does not say (no ``/proc``)."""
    try:
        with open(path) as f:
            return int(f.read())
    except (OSError, ValueError):
        return None


def open_source_bound(fds: Optional[int], maps: Optional[int]) -> int:
    """How many sources the process can keep open: each limit less its
    reserve, divided by what one source holds, the smaller of the two,
    and never below ``MAX_OPEN_FLOOR``.  None is a limit the process
    does not have; with neither, the floor."""
    terms = [(limit - reserve) // per
             for limit, reserve, per in ((fds, FD_RESERVE, SOURCE_FDS),
                                         (maps, MAP_RESERVE, SOURCE_MAPS))
             if limit is not None]
    return max(MAX_OPEN_FLOOR, min(terms, default=MAX_OPEN_FLOOR))


def derive_max_open() -> int:
    """Raise the descriptor limit, read the mapping limit, and bound the
    open sources by both.  A server calls this once, in the process that
    builds its ``PixelsService``; the service itself changes no limit."""
    fds = raise_fd_limit()
    maps = read_max_map_count()
    bound = open_source_bound(fds, maps)
    log.info("pixel sources: up to %d open (RLIMIT_NOFILE %s, "
             "vm.max_map_count %s)", bound, fds, maps)
    return bound


class PixelsService:
    """Opens pixel sources from a data directory, with a bounded LRU handle
    cache (each open store holds live memmaps, so the bound caps fds and
    mappings on long-running servers).  A server passes the bound its
    process can hold (``derive_max_open``); OME-TIFF sources are held to
    ``MAX_OPEN_FLOOR`` of them whatever the bound.

    Backend is sniffed per image directory: a ``meta.json`` selects the
    chunked pyramid store; ``.zattrs``/``.zarray`` markers (directly or
    in a ``*.zarr`` child) select the OME-NGFF reader; otherwise an
    ``*.ome.tif(f)`` / ``*.tif(f)`` file selects the OME-TIFF reader —
    the role Bio-Formats format dispatch plays behind
    ``PixelsService.getPixelBuffer``
    (``ImageRegionRequestHandler.java:302-309``)."""

    # Evicted-set size past which a gc.collect() is forced: a reference
    # cycle (e.g. a captured exception traceback) can keep an evicted
    # source's refcount high until a cycle collection runs.
    _GC_THRESHOLD = 8

    def __init__(self, data_dir: str, max_open: int = MAX_OPEN_FLOOR,
                 repo_root: Optional[str] = None):
        self.data_dir = data_dir
        self.max_open = max_open
        # OMERO binary-repository mount (``omero.data.dir``,
        # ``config.yaml:19-20``): when set, images absent from the
        # per-image data_dir layout resolve through DB-provided
        # repo-relative paths (ManagedRepository filesets, legacy
        # Pixels/<id> ROMIO files) with zero re-arrangement — the role
        # of the reference's file-path resolver bean
        # (``beanRefContext.xml:13-16``).
        self.repo_root = repo_root
        self._lock = threading.Lock()
        self._open: "OrderedDict[int, PixelSource]" = OrderedDict()
        # Sources dropped from the LRU while possibly still mid-read;
        # closed deterministically once no outside reference remains
        # (see _drain_evicted) so fds/memmaps cannot outgrow max_open
        # under heavy image churn.
        self._evicted: List[PixelSource] = []
        # /metrics imageregion_pixel_sources_opened_total: sources
        # constructed (lookups the LRU missed).  Counted under ``_lock``.
        self.opened = 0

    def open_count(self) -> int:
        """Sources the LRU holds open now (at most ``max_open``)."""
        return len(self._open)

    def _drain_evicted_locked(self) -> int:
        """Close evicted sources no longer referenced anywhere else;
        returns how many stragglers remain.

        Caller holds ``self._lock``.  Refcount 3 = the list slot, the
        loop variable, and getrefcount's argument — i.e. no reader still
        holds the source.
        """
        still: List[PixelSource] = []
        for src in self._evicted:
            if sys.getrefcount(src) <= 3:
                try:
                    src.close()
                except Exception:
                    pass
            else:
                still.append(src)
        self._evicted = still
        return len(still)

    def _gc_and_drain(self) -> None:
        """Straggler pressure relief: a reference cycle (e.g. a captured
        exception traceback) can pin an evicted source until a cycle
        collection runs.  The collection happens OUTSIDE the lock so
        concurrent lookups are never stalled behind a full gc pass."""
        with stopwatch("PixelsService.gcDrain"):
            gc.collect()
            with self._lock:
                self._drain_evicted_locked()

    def image_dir(self, image_id: int) -> str:
        return os.path.join(self.data_dir, str(image_id))

    def _sniff(self, image_id: int) -> Optional[tuple]:
        """("chunked"|"ngff"|"tiff", path) | None."""
        d = self.image_dir(image_id)
        if os.path.exists(os.path.join(d, "meta.json")):
            return ("chunked", d)
        ngff = find_ngff(d)
        if ngff is not None:
            return ("ngff", ngff)
        tiff = find_tiff(d)
        if tiff is not None:
            return ("tiff", tiff)
        return None

    def exists(self, image_id: int) -> bool:
        return self._sniff(image_id) is not None

    def is_open(self, image_id: int) -> bool:
        """LRU probe without disk or DB I/O: a repo-resolved image that
        is already open needs no re-resolution on the hot tile path."""
        with self._lock:
            return image_id in self._open

    def get_open_source(self, image_id: int) -> Optional[PixelSource]:
        """The already-open source, or None — NEVER sniffs or opens,
        so it is safe to call on an event loop (the serving fast path;
        a concurrent eviction just returns None and the caller takes
        the off-loop open)."""
        with self._lock:
            src = self._open.get(image_id)
            if src is not None:
                self._open.move_to_end(image_id)
            return src

    def _open_from_repo(self, image_id: int, candidates, pixels):
        """Open the first usable repo-relative candidate path.

        TIFF-suffixed entries (``.ome.tif(f)`` preferred) open through
        the OME-TIFF reader; ``*.zarr`` directories open as OME-NGFF;
        a ``Pixels/<id>`` entry opens as a legacy ROMIO buffer, which
        needs the DB geometry (``pixels``).
        """
        from .romio import RomioPixelSource

        def rank(rel: str) -> int:
            low = rel.lower()
            if low.endswith((".ome.tif", ".ome.tiff")):
                return 0
            if low.endswith((".tif", ".tiff", ".svs", ".ndpi")):
                return 1       # TIFF-based vendor formats included
            return 2

        tried = []
        for rel in sorted(candidates, key=rank):
            path = os.path.join(self.repo_root, rel)
            if os.path.isdir(path):
                ngff = find_ngff(path)
                if ngff is not None:
                    return NgffZarrSource(ngff)
                tried.append(rel)
                continue
            if not os.path.isfile(path):
                tried.append(rel)
                continue
            if rank(rel) < 2:
                return OmeTiffSource(path)
            if rel.startswith("Pixels/"):
                if pixels is None:
                    raise ValueError(
                        f"image {image_id}: ROMIO path {rel} needs "
                        f"pixels geometry to open")
                return RomioPixelSource(path, pixels)
            # Unknown extension: vendor WSI files are very often plain
            # TIFF containers under another name — sniff the magic
            # rather than trusting the suffix.
            with open(path, "rb") as f:
                magic = f.read(4)
            if magic[:2] in (b"II", b"MM"):
                return OmeTiffSource(path)
            tried.append(rel)   # present but not a format we serve
        raise FileNotFoundError(
            f"image {image_id}: no usable pixel file under "
            f"{self.repo_root} (candidates: {tried or candidates})")

    def get_pixel_source(self, image_id: int, candidates=None,
                         pixels=None) -> PixelSource:
        """≙ ``PixelsService.getPixelBuffer(pixels, false)``.

        ``candidates`` are repo-root-relative paths from the metadata
        DB (``DbMetadataService.resolve_image_paths``); they apply only
        when the per-image ``data_dir`` layout has no entry, so a local
        override always wins.
        """
        with self._lock:
            src = self._open.get(image_id)
            if src is not None:
                self._open.move_to_end(image_id)
                if self._evicted:
                    # Steady-state hit traffic must still release
                    # finished readers' handles (no gc here: a plain
                    # refcount scan, trivial when the list is empty).
                    self._drain_evicted_locked()
                return src
        # The miss, on the thread that pays for it (the handler's
        # ``to_thread`` worker): sniff, constructor, insert / evict /
        # drain.  ``PixelsService.getPixelBuffer`` is the request's
        # span, hit or miss; this one fires on misses only.
        with stopwatch("PixelsService.openSource"):
            backend = self._sniff(image_id)
            if backend is None and candidates and self.repo_root:
                src = self._open_from_repo(image_id, candidates, pixels)
            elif backend is None:
                raise FileNotFoundError(
                    f"no pixel data for image {image_id} under "
                    f"{self.data_dir}"
                )
            elif backend[0] == "chunked":
                src = ChunkedPyramidStore(backend[1])
            elif backend[0] == "ngff":
                src = NgffZarrSource(backend[1])
            else:
                src = OmeTiffSource(backend[1])
            with self._lock:
                self.opened += 1
                # Double-check: a concurrent opener may have won the
                # race; keep theirs and drop ours so no store leaks its
                # memmaps.
                existing = self._open.get(image_id)
                if existing is not None:
                    self._open.move_to_end(image_id)
                    src.close()
                    return existing
                self._open[image_id] = src
                while len(self._open) > self.max_open:
                    # Do not close() here: a concurrent request may
                    # still be mid-read on the evicted source (close
                    # would yank the TIFF file handle out from under
                    # it).  Park it on the deferred-close list instead;
                    # it is closed on a later drain once its refcount
                    # shows no reader remains.
                    self._evicted.append(
                        self._open.popitem(last=False)[1])
                if isinstance(src, OmeTiffSource):
                    self._bound_tiffs_locked()
                stragglers = self._drain_evicted_locked()
        if stragglers > self._GC_THRESHOLD:
            self._gc_and_drain()
        return src

    def _bound_tiffs_locked(self) -> None:
        """Park all but the ``MAX_OPEN_FLOOR`` most recently used
        OME-TIFF sources.  Each keeps a segment memo of up to 64 MiB on
        the heap and a descriptor for each file of its fileset, which
        the per-source figures of the derived bound do not reckon; so
        they stay within what 128 open sources held before the bound
        followed the process's limits.  Caller holds ``self._lock``."""
        tiffs = [image_id for image_id, src in self._open.items()
                 if isinstance(src, OmeTiffSource)]
        for image_id in tiffs[:-MAX_OPEN_FLOOR]:
            self._evicted.append(self._open.pop(image_id))

    def invalidate(self, image_id: int) -> None:
        """Drop a cached open handle so the next request re-sniffs the
        image directory.  The pyramid job calls this after committing
        an NGFF group: the sniff order prefers it, but an LRU-resident
        pre-build source would otherwise keep serving unpyramided."""
        with self._lock:
            src = self._open.pop(image_id, None)
            if src is not None:
                # Deferred close — a concurrent reader may be mid-read.
                self._evicted.append(src)
            self._drain_evicted_locked()

    def close(self) -> None:
        with self._lock:
            for src in self._open.values():
                src.close()
            self._open.clear()
            for src in self._evicted:
                try:
                    src.close()
                except Exception:
                    pass
            self._evicted.clear()
