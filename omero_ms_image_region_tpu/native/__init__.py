"""Native runtime pieces: sharded LRU tile cache + pixel bit ops.

C++ with a plain C ABI, loaded through ctypes (no pybind11 in this image).
The shared library is compiled on first import with g++ into
``_build/libtilecache.so`` next to this file; if no toolchain is available
the import raises ImportError and callers fall back to pure Python
(``services.cache.make_cache`` does exactly that).

ctypes calls release the GIL, so cache traffic from render worker threads
runs concurrently across shards — the point of having this tier in C++.
"""

from __future__ import annotations

import collections
import contextlib
import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional

_HERE = os.path.dirname(os.path.abspath(__file__))
_BUILD_DIR = os.path.join(_HERE, "_build")
_SOURCE = os.path.join(_HERE, "tilecache.cpp")
_LIB_PATH = os.path.join(_BUILD_DIR, "libtilecache.so")
_JPEG_SOURCE = os.path.join(_HERE, "jpegenc.cpp")
_JPEG_LIB_PATH = os.path.join(_BUILD_DIR, "libjpegenc.so")
_JPEGDEC_SOURCE = os.path.join(_HERE, "jpegdec.cpp")
_JPEGDEC_LIB_PATH = os.path.join(_BUILD_DIR, "libjpegdec.so")
_JP2KT1_SOURCE = os.path.join(_HERE, "jp2kt1.cpp")
_JP2KT1_LIB_PATH = os.path.join(_BUILD_DIR, "libjp2kt1.so")
_BUILD_LOCK = threading.Lock()


_CXX_FLAGS = ("-O3", "-std=c++17", "-shared", "-fPIC", "-fopenmp")


def _source_stamp(source: str) -> str:
    """What a built library is valid for: the source bytes and the
    flags.  (Not mtimes: a copy of the tree can invert them either
    way, and ``_build`` is git-ignored so it travels with copies.)"""
    h = hashlib.sha256(" ".join(_CXX_FLAGS).encode())
    with open(source, "rb") as f:
        h.update(f.read())
    return h.hexdigest()


def _compile_lib(source: str, lib_path: str) -> None:
    os.makedirs(_BUILD_DIR, exist_ok=True)
    # Per-process temp names: several processes of one checkout (test
    # workers, a fleet's sidecars) may build at the same moment.
    tmp = f"{lib_path}.{os.getpid()}.tmp"
    cmd = ["g++", *_CXX_FLAGS, "-o", tmp, source]
    subprocess.run(cmd, check=True, capture_output=True)
    os.replace(tmp, lib_path)
    with open(tmp, "w") as f:
        f.write(_source_stamp(source))
    os.replace(tmp, lib_path + ".stamp")


def _is_stale(source: str, lib_path: str) -> bool:
    try:
        with open(lib_path + ".stamp") as f:
            built_from = f.read().strip()
    except OSError:
        return True
    return (not os.path.exists(lib_path)
            or built_from != _source_stamp(source))


class _NativeLib:
    """Build-on-first-use loader for one shared library: double-checked
    lock, source-hash staleness rebuild, cached first failure (so hot
    paths probing availability per batch don't re-spawn a doomed g++
    attempt every call), and per-lib ctypes prototype setup."""

    def __init__(self, source: str, lib_path: str, what: str,
                 configure) -> None:
        self.source = source
        self.lib_path = lib_path
        self.what = what
        self.configure = configure
        self.lib: Optional[ctypes.CDLL] = None
        self.error: Optional[str] = None

    def load(self) -> ctypes.CDLL:
        if self.lib is not None:
            return self.lib
        if self.error is not None:
            raise ImportError(self.error)
        with _BUILD_LOCK:
            if self.lib is not None:
                return self.lib
            if self.error is not None:
                raise ImportError(self.error)
            if _is_stale(self.source, self.lib_path):
                try:
                    _compile_lib(self.source, self.lib_path)
                except (OSError, subprocess.CalledProcessError) as e:
                    self.error = f"{self.what} unavailable: {e}"
                    raise ImportError(self.error)
            lib = ctypes.CDLL(self.lib_path)
            self.configure(lib)
            self.lib = lib
            return lib


def _configure_tilecache(lib: ctypes.CDLL) -> None:
    lib.tc_create.restype = ctypes.c_void_p
    lib.tc_create.argtypes = [ctypes.c_size_t, ctypes.c_uint]
    lib.tc_destroy.argtypes = [ctypes.c_void_p]
    lib.tc_put.restype = ctypes.c_int
    lib.tc_put.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                           ctypes.c_size_t, ctypes.c_char_p,
                           ctypes.c_size_t]
    lib.tc_get.restype = ctypes.c_longlong
    lib.tc_get.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                           ctypes.c_size_t,
                           ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8))]
    lib.tc_free.argtypes = [ctypes.POINTER(ctypes.c_uint8)]
    for fn in ("tc_hits", "tc_misses", "tc_size_bytes"):
        getattr(lib, fn).restype = ctypes.c_uint64
        getattr(lib, fn).argtypes = [ctypes.c_void_p]
    lib.bits_unpack_msb.argtypes = [ctypes.c_char_p, ctypes.c_size_t,
                                    ctypes.c_char_p]
    lib.flip_u32.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                             ctypes.c_int, ctypes.c_int,
                             ctypes.c_int, ctypes.c_int]
    lib.mask_overlay_u8.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                    ctypes.c_void_p, ctypes.c_void_p,
                                    ctypes.c_int, ctypes.c_int,
                                    ctypes.c_int]
    lib.tiff_lzw_decode.restype = ctypes.c_longlong
    lib.tiff_lzw_decode.argtypes = [ctypes.c_char_p, ctypes.c_size_t,
                                    ctypes.c_void_p, ctypes.c_size_t]


def _configure_jpegenc(lib: ctypes.CDLL) -> None:
    lib.jpeg_scratch_new.restype = ctypes.c_void_p
    lib.jpeg_scratch_new.argtypes = []
    lib.jpeg_scratch_free.restype = None
    lib.jpeg_scratch_free.argtypes = [ctypes.c_void_p]
    lib.jpeg_scratch_bytes.restype = ctypes.c_size_t
    lib.jpeg_scratch_bytes.argtypes = [ctypes.c_void_p]
    lib.jpeg_scratch_growths.restype = ctypes.c_longlong
    lib.jpeg_scratch_growths.argtypes = []
    lib.jpeg_encode.restype = ctypes.c_void_p
    lib.jpeg_encode.argtypes = [
        ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_int, ctypes.c_int, ctypes.c_int,
        ctypes.POINTER(ctypes.c_longlong),
    ]
    lib.jpeg_encode_sparse_run.restype = ctypes.c_void_p
    lib.jpeg_encode_sparse_run.argtypes = [
        ctypes.c_void_p, ctypes.c_int,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
        ctypes.c_int, ctypes.c_int,
        ctypes.c_void_p, ctypes.c_void_p,
    ]


def _configure_jpegdec(lib: ctypes.CDLL) -> None:
    lib.jpeg_decode_baseline.restype = ctypes.c_longlong
    lib.jpeg_decode_baseline.argtypes = [
        ctypes.c_char_p, ctypes.c_size_t,
        ctypes.c_char_p, ctypes.c_size_t,
        ctypes.c_void_p, ctypes.c_size_t,
        ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int),
        ctypes.POINTER(ctypes.c_int),
    ]


def _configure_jp2kt1(lib: ctypes.CDLL) -> None:
    lib.jp2k_t1_decode.restype = ctypes.c_longlong
    lib.jp2k_t1_decode.argtypes = [
        ctypes.c_char_p, ctypes.c_size_t,
        ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
        ctypes.c_int, ctypes.c_int, ctypes.c_int,
        ctypes.c_void_p,
    ]


_TILECACHE = _NativeLib(_SOURCE, _LIB_PATH, "native tilecache",
                        _configure_tilecache)
_JPEGENC = _NativeLib(_JPEG_SOURCE, _JPEG_LIB_PATH,
                      "native jpeg encoder", _configure_jpegenc)
_JPEGDEC = _NativeLib(_JPEGDEC_SOURCE, _JPEGDEC_LIB_PATH,
                      "native jpeg decoder", _configure_jpegdec)
_JP2KT1 = _NativeLib(_JP2KT1_SOURCE, _JP2KT1_LIB_PATH,
                     "native jpeg2000 tier-1", _configure_jp2kt1)


def _load() -> ctypes.CDLL:
    return _TILECACHE.load()


def _load_jpeg() -> ctypes.CDLL:
    return _JPEGENC.load()


def _load_jpegdec() -> ctypes.CDLL:
    return _JPEGDEC.load()


def _load_jp2kt1() -> ctypes.CDLL:
    return _JP2KT1.load()


def jp2k_t1_decode(data: bytes, w: int, h: int, npasses: int,
                   msbs: int, orient: int, segsym: bool,
                   half_at_zero: bool):
    """EBCOT Tier-1 decode of one code-block (native mirror of
    ``io.jp2k._t1_decode``; GIL released for the whole block)."""
    import numpy as np
    lib = _load_jp2kt1()
    out = np.zeros((h, w), np.float64)
    rc = lib.jp2k_t1_decode(data, len(data), w, h, npasses, msbs,
                            orient, int(segsym), int(half_at_zero),
                            out.ctypes.data_as(ctypes.c_void_p))
    if rc != 0:
        raise ValueError("jp2k_t1_decode: invalid arguments")
    return out


class NativeLRUCache:
    """CacheTier over the C++ sharded LRU (drop-in for MemoryLRUCache)."""

    def __init__(self, max_bytes: int = 256 * 1024 * 1024,
                 shards: int = 16):
        lib = _load()
        self._lib = lib
        self._handle = lib.tc_create(max_bytes, shards)
        if not self._handle:
            raise MemoryError("tc_create failed")
        self.max_bytes = max_bytes

    def __del__(self):
        handle = getattr(self, "_handle", None)
        if handle:
            self._lib.tc_destroy(handle)
            self._handle = None

    # -- sync face (executor threads; GIL released inside the C calls) ----

    def get_sync(self, key: str) -> Optional[bytes]:
        kb = key.encode()
        out = ctypes.POINTER(ctypes.c_uint8)()
        n = self._lib.tc_get(self._handle, kb, len(kb), ctypes.byref(out))
        if n < 0:
            return None
        try:
            return ctypes.string_at(out, n)
        finally:
            self._lib.tc_free(out)

    def set_sync(self, key: str, value: bytes) -> None:
        kb = key.encode()
        self._lib.tc_put(self._handle, kb, len(kb), value, len(value))

    # -- async face (CacheTier protocol) ----------------------------------

    async def get(self, key: str) -> Optional[bytes]:
        return self.get_sync(key)

    async def set(self, key: str, value: bytes) -> None:
        self.set_sync(key, value)

    # -- stats ------------------------------------------------------------

    @property
    def hits(self) -> int:
        return int(self._lib.tc_hits(self._handle))

    @property
    def misses(self) -> int:
        return int(self._lib.tc_misses(self._handle))

    @property
    def size_bytes(self) -> int:
        return int(self._lib.tc_size_bytes(self._handle))


def unpack_bits_msb(data: bytes, n_bits: int):
    """MSB-first 1-bit unpack to a u8 0/1 array (native fast path)."""
    import numpy as np
    lib = _load()
    out = np.empty(n_bits, dtype=np.uint8)
    lib.bits_unpack_msb(data, n_bits,
                        out.ctypes.data_as(ctypes.c_char_p))
    return out


def tiff_lzw_decode(data: bytes, dst_cap: int) -> bytes:
    """TIFF-variant LZW decode (native; GIL released for the whole
    stream).  Raises ValueError on malformed input or cap overflow."""
    lib = _load()
    out = ctypes.create_string_buffer(dst_cap)
    n = lib.tiff_lzw_decode(data, len(data), out, dst_cap)
    if n < 0:
        raise ValueError("malformed TIFF LZW stream (or output cap "
                         "exceeded)")
    return ctypes.string_at(out, n)   # single copy (raw[:n] would do two)


def mask_overlay_u8(base_rgba, mask_grids, fills):
    """Batched integer alpha-composite, OpenMP across the batch
    (GIL released for the whole blend)."""
    import numpy as np
    lib = _load()
    base = np.ascontiguousarray(base_rgba, dtype=np.uint8)
    grids = np.ascontiguousarray(mask_grids, dtype=np.uint8)
    f = np.ascontiguousarray(fills, dtype=np.uint8)
    if base.ndim != 4 or base.shape[-1] != 4:
        raise ValueError(f"base_rgba must be [B, H, W, 4], "
                         f"got {base.shape}")
    B, H, W, _ = base.shape
    # The C kernel trusts these shapes; mismatches would read/write out
    # of bounds where the numpy path raised a broadcast error.
    if grids.shape != (B, H, W):
        raise ValueError(f"mask_grids must be {(B, H, W)}, "
                         f"got {grids.shape}")
    if f.shape != (B, 4):
        raise ValueError(f"fills must be {(B, 4)}, got {f.shape}")
    out = np.empty_like(base)
    lib.mask_overlay_u8(
        base.ctypes.data_as(ctypes.c_void_p),
        grids.ctypes.data_as(ctypes.c_void_p),
        f.ctypes.data_as(ctypes.c_void_p),
        out.ctypes.data_as(ctypes.c_void_p),
        B, H, W)
    return out


class SparseOverflowError(ValueError):
    """The device wire buffer dropped entries (content denser than cap)."""


def status() -> dict:
    """Which implementation of each native piece this process got
    (building what is missing).  The device-owning server logs this at
    start-up: a failed g++ is an ImportError here and a pure-Python
    coder there, and an operator should not have to infer which from
    throughput."""
    def got(lib: _NativeLib) -> str:
        try:
            lib.load()
            return "native"
        except ImportError:
            return "python"
    return {"entropy_coder": got(_JPEGENC),
            "tile_cache": got(_TILECACHE)}


def jpeg_native_available() -> bool:
    """Eagerly probe (and build) the native encoder.

    The module-level symbols exist whether or not a toolchain does —
    compilation is deferred to first use — so ``import`` success is NOT a
    native-availability signal.  Fallback decisions must call this.
    """
    try:
        _load_jpeg()
        return True
    except ImportError:
        return False


# The coder's scratches (jpegenc.cpp ``Scratch``: symbol records, block
# offsets, output stream), kept from one call to the next so that a
# call allocates nothing.  A call takes one and gives it back; last in,
# first out, so as many exist as threads have ever coded at the same
# moment, and the ones in use stay warm.  No more are KEPT than the
# cores the process may use (more threads than that cannot code at
# once to any purpose): what the process retains is at most that many
# times the largest tile's 272 bytes a block (26.7 MB at 2048^2).
_SCRATCHES: collections.deque = collections.deque()
_SCRATCHES_KEPT = len(os.sched_getaffinity(0))


@contextlib.contextmanager
def _scratch(lib: ctypes.CDLL):
    """A scratch for one coding call: the one given back last, a new
    one where every scratch is in use."""
    try:
        scratch = _SCRATCHES.pop()
    except IndexError:
        scratch = lib.jpeg_scratch_new()
        if not scratch:
            raise MemoryError("jpeg_scratch_new failed")
    try:
        yield scratch
    finally:
        if len(_SCRATCHES) < _SCRATCHES_KEPT:
            _SCRATCHES.append(scratch)
        else:
            lib.jpeg_scratch_free(scratch)


def jpeg_scratch_stats() -> dict:
    """``growths``: times any scratch of the process took a larger
    block than it had (it stands still while tiles of sizes already
    seen are coded: the contract check that the scratch is kept);
    ``idle`` / ``idle_bytes``: the scratches no call holds right now,
    and what they retain."""
    lib = _load_jpeg()
    idle = list(_SCRATCHES)
    return {"growths": int(lib.jpeg_scratch_growths()),
            "idle": len(idle),
            "idle_bytes": sum(int(lib.jpeg_scratch_bytes(s))
                              for s in idle)}


def jpeg_encode_native(y, cb, cr, width: int, height: int,
                       quality: int) -> bytes:
    """Entropy-encode device JPEG coefficients to a JFIF stream (C++).

    ``y``/``cb``/``cr`` are the int16 zigzagged raster-order block arrays of
    :func:`..ops.jpegenc.packed_to_jpeg_coefficients` for ONE image.  The
    GIL is released inside the call, so a thread pool encodes a whole tile
    batch concurrently.
    """
    import numpy as np
    lib = _load_jpeg()
    y = np.ascontiguousarray(y, dtype=np.int16)
    cb = np.ascontiguousarray(cb, dtype=np.int16)
    cr = np.ascontiguousarray(cr, dtype=np.int16)
    h16, w16 = (height + 15) // 16, (width + 15) // 16
    if (y.size != h16 * w16 * 4 * 64 or cb.size != h16 * w16 * 64
            or cr.size != cb.size):
        raise ValueError(
            f"coefficient sizes {y.size}/{cb.size}/{cr.size} do not match "
            f"a {w16}x{h16}-MCU frame"
        )
    n = ctypes.c_longlong()
    with _scratch(lib) as scratch:
        out = lib.jpeg_encode(
            scratch, y.ctypes.data, cb.ctypes.data, cr.ctypes.data,
            width, height, quality, ctypes.byref(n))
        if not out:
            raise ValueError("jpeg_encode: invalid arguments")
        return ctypes.string_at(out, n.value)


def jpeg_encode_sparse_run(rows, dims, quality: int, cap: int) -> list:
    """JFIF-encode a run of tiles straight from their sparse wire rows,
    in ONE native call: the thread gives the GIL up once a run and not
    once a tile.

    ``rows`` are the u8[...] rows from ``ops.jpegenc.render_to_jpeg_sparse``
    (read where they lie: a contiguous row is not copied), ``dims`` each
    tile's ``(width, height)``.  One entry a row comes back: its JFIF
    ``bytes``, or the coder's return code where it gave none: ``-2``, the
    tile's coefficient density exceeded ``cap`` and the dense path must
    be taken; ``-1``, the row is malformed.
    """
    import numpy as np
    lib = _load_jpeg()
    n = len(rows)
    rows = [np.ascontiguousarray(r, dtype=np.uint8) for r in rows]
    bufs = (ctypes.c_void_p * n)(*[r.ctypes.data for r in rows])
    # The TRUE lengths are what the decoder validates against: a
    # truncated row errors instead of decoding its last entry from
    # what lies behind it.
    lens = (ctypes.c_size_t * n)(*[r.size for r in rows])
    widths = (ctypes.c_int * n)(*[w for w, _ in dims])
    heights = (ctypes.c_int * n)(*[h for _, h in dims])
    rc = (ctypes.c_longlong * n)()
    off = (ctypes.c_size_t * n)()
    with _scratch(lib) as scratch:
        out = lib.jpeg_encode_sparse_run(
            scratch, n, bufs, lens, widths, heights, quality, cap, rc, off)
        return [ctypes.string_at(out + off[i], rc[i]) if rc[i] >= 0
                else int(rc[i]) for i in range(n)]


def jpeg_encode_sparse_native(buf, width: int, height: int, quality: int,
                              cap: int) -> bytes:
    """JFIF-encode one tile straight from the device sparse wire buffer.

    ``buf`` is the u8[...] row from ``ops.jpegenc.render_to_jpeg_sparse``.
    Raises :class:`SparseOverflowError` when the tile's coefficient density
    exceeded ``cap`` and the dense path must be taken instead.
    """
    coded, = jpeg_encode_sparse_run([buf], [(width, height)], quality, cap)
    if coded == -2:
        raise SparseOverflowError(f"sparse buffer overflow (cap={cap})")
    if coded == -1:
        raise ValueError("jpeg_encode_sparse: invalid arguments")
    return coded


def jpeg_decode_baseline(data: bytes, tables: "bytes | None"):
    """Decode one JPEG (optionally abbreviated, with a TIFF JPEGTables
    stream) to ``u8[h, w, ncomp]`` raw components.

    Native mirror of ``io.jpegdec.decode_baseline_jpeg`` — same scope
    (SOF0/1 baseline AND SOF2 progressive, sampling 1-2, DRI/RST,
    inter-scan table updates), GIL released for the whole decode.
    Raises ImportError when no toolchain built the library and
    ValueError on malformed/unsupported streams.
    """
    import numpy as np
    lib = _load_jpegdec()
    w = ctypes.c_int()
    h = ctypes.c_int()
    nc = ctypes.c_int()
    tb = tables or b""
    # First call with zero cap: the decoder sizes the frame from the
    # headers (before entropy decode), fills out_w/h/ncomp and returns
    # the cap-too-small code (-2; -1 = malformed).
    n = lib.jpeg_decode_baseline(data, len(data), tb, len(tb), None, 0,
                                 ctypes.byref(w), ctypes.byref(h),
                                 ctypes.byref(nc))
    if n != -2:
        raise ValueError("malformed or unsupported JPEG stream")
    need = w.value * h.value * nc.value
    out = np.empty(need, np.uint8)
    n2 = lib.jpeg_decode_baseline(data, len(data), tb, len(tb),
                                  out.ctypes.data_as(ctypes.c_void_p),
                                  out.size, ctypes.byref(w),
                                  ctypes.byref(h), ctypes.byref(nc))
    if n2 != need:
        raise ValueError("malformed or unsupported JPEG stream")
    return out.reshape(h.value, w.value, nc.value)


def flip_u32(packed, flip_horizontal: bool, flip_vertical: bool):
    """Native single-pass flip of a u32[H, W] packed image."""
    import numpy as np
    lib = _load()
    src = np.ascontiguousarray(packed, dtype=np.uint32)
    h, w = src.shape
    dst = np.empty_like(src)
    lib.flip_u32(src.ctypes.data, dst.ctypes.data, h, w,
                 int(flip_horizontal), int(flip_vertical))
    return dst
