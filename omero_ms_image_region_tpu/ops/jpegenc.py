"""TPU-side JPEG front end: color transform + 8x8 DCT + quantization.

The reference encodes JPEG on the CPU from the packed-int render output
(``LocalCompress.compressToStream``, call site
``ImageRegionRequestHandler.java:580-582``).  On TPU the economics invert:
the rendered tile lives in HBM and the host link is the bottleneck, while
the 8x8 block DCT is a pair of small matmuls — exactly what the MXU does
best.  So the lossy half of baseline JPEG (BT.601 YCbCr conversion, 4:2:0
chroma subsampling, blockwise DCT-II, quantization, zigzag) runs on device
as one fused jitted kernel over the whole tile batch, and only the
quantized coefficients — far smaller and far more wire-compressible than
raw RGBA — cross to the host, where the serial entropy coding (Huffman,
byte stuffing, JFIF framing) runs in native code (``native/jpegenc.cpp``)
with a pure-Python fallback (:mod:`.jfif`).

Coefficient layout contract with the entropy coder:
  * ``y``  i16[B, (H/8)*(W/8),   64]  — luma blocks, raster order, zigzagged
  * ``cb`` i16[B, (H/16)*(W/16), 64]  — subsampled chroma, raster, zigzagged
  * ``cr`` i16[B, (H/16)*(W/16), 64]
H and W must be multiples of 16 (one 4:2:0 MCU); callers pad odd tiles by
edge replication before encode and patch the true size into the SOF0 header
dimensions (the JPEG spec decodes only the declared WxH).
"""

from __future__ import annotations

import functools
import threading
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.stopwatch import stopwatch

# ---------------------------------------------------------------- tables

# Annex K base quantization tables (natural 8x8 order).
BASE_LUMA_QUANT = np.array([
    [16, 11, 10, 16, 24, 40, 51, 61],
    [12, 12, 14, 19, 26, 58, 60, 55],
    [14, 13, 16, 24, 40, 57, 69, 56],
    [14, 17, 22, 29, 51, 87, 80, 62],
    [18, 22, 37, 56, 68, 109, 103, 77],
    [24, 35, 55, 64, 81, 104, 113, 92],
    [49, 64, 78, 87, 103, 121, 120, 101],
    [72, 92, 95, 98, 112, 100, 103, 99],
], dtype=np.int32)

BASE_CHROMA_QUANT = np.array([
    [17, 18, 24, 47, 99, 99, 99, 99],
    [18, 21, 26, 66, 99, 99, 99, 99],
    [24, 26, 56, 99, 99, 99, 99, 99],
    [47, 66, 99, 99, 99, 99, 99, 99],
    [99, 99, 99, 99, 99, 99, 99, 99],
    [99, 99, 99, 99, 99, 99, 99, 99],
    [99, 99, 99, 99, 99, 99, 99, 99],
    [99, 99, 99, 99, 99, 99, 99, 99],
], dtype=np.int32)


def quant_tables(quality: int) -> tuple[np.ndarray, np.ndarray]:
    """IJG quality scaling of the Annex K tables -> (luma, chroma) u8[8,8]."""
    quality = int(max(1, min(100, quality)))
    scale = 5000 // quality if quality < 50 else 200 - 2 * quality
    def scaled(base):
        t = (base * scale + 50) // 100
        return np.clip(t, 1, 255).astype(np.uint8)
    return scaled(BASE_LUMA_QUANT), scaled(BASE_CHROMA_QUANT)


@functools.lru_cache(maxsize=1)
def zigzag_order() -> np.ndarray:
    """Flat indices (into a row-major 8x8 block) in JPEG zigzag order."""
    order = sorted(
        ((r, c) for r in range(8) for c in range(8)),
        key=lambda rc: (rc[0] + rc[1],
                        rc[1] if (rc[0] + rc[1]) % 2 == 0 else rc[0]),
    )
    return np.array([r * 8 + c for r, c in order], dtype=np.int32)


@functools.lru_cache(maxsize=1)
def dct_matrix() -> np.ndarray:
    """Orthonormal 8-point DCT-II matrix == the JPEG FDCT normalization."""
    k = np.arange(8)
    D = np.cos((2 * k[None, :] + 1) * k[:, None] * np.pi / 16) * 0.5
    D[0] *= 1.0 / np.sqrt(2.0)
    return D.astype(np.float32)


# ---------------------------------------------------------------- kernel

def _blockify(x):
    """[B, H, W] -> [B, (H/8)*(W/8), 8, 8] in raster block order."""
    Bq, H, W = x.shape
    x = x.reshape(Bq, H // 8, 8, W // 8, 8)
    return x.transpose(0, 1, 3, 2, 4).reshape(Bq, -1, 8, 8)


@jax.named_scope("jpeg.dct_quant")
def _dct_quant_zigzag(planes, qtable, zig, D):
    """[B, H, W] level-shifted samples -> i16[B, nb, 64] zigzag coeffs."""
    blocks = _blockify(planes)
    coeffs = jnp.einsum("ux,bnxy,vy->bnuv", D, blocks, D,
                        preferred_element_type=jnp.float32)
    q = jnp.round(coeffs / qtable[None, None].astype(jnp.float32))
    q = jnp.clip(q, -2047.0, 2047.0).astype(jnp.int16)
    flat = q.reshape(q.shape[0], q.shape[1], 64)
    return jnp.take(flat, zig, axis=-1)


def _chroma_mean_2x2(x):
    """f32[B, H, W] -> f32[B, H/2, W/2]: the 4:2:0 subsample, an f32
    mean of each 2 x 2 block of samples.

    A pooling window and not ``reshape(B, H/2, 2, W/2, 2).mean((2, 4))``:
    the TPU lays an array out in (8, 128) tiles of its two minor
    dimensions, so the reshape's ``[..., W/2, 2]`` holds data in 2 of
    every 128 lanes and costs ten times this at 2048^2.  Here the
    minor dimension stays ``W``, then ``W/2``.
    """
    return jax.lax.reduce_window(
        x, 0.0, jax.lax.add, (1, 2, 2), (1, 2, 2), "VALID") * 0.25


@jax.jit
def packed_to_jpeg_coefficients(packed, qy, qc):
    """Packed RGBA render output -> quantized zigzag JPEG coefficients.

    Args:
      packed: u32[B, H, W] little-endian R,G,B,A packed pixels (the render
              kernel's native output; H, W multiples of 16).
      qy:     i32[8, 8] luma quantization table (natural order).
      qc:     i32[8, 8] chroma quantization table.

    Returns:
      (y, cb, cr) int16 coefficient arrays in the module-docstring layout.
    """
    with jax.named_scope("jpeg.ycbcr420"):
        r = (packed & 0xFF).astype(jnp.float32)
        g = ((packed >> 8) & 0xFF).astype(jnp.float32)
        b = ((packed >> 16) & 0xFF).astype(jnp.float32)

        # BT.601 full-range YCbCr; the +128 chroma bias and the JPEG -128
        # level shift cancel, so only luma is shifted.
        y = 0.299 * r + 0.587 * g + 0.114 * b - 128.0
        cb = -0.168736 * r - 0.331264 * g + 0.5 * b
        cr = 0.5 * r - 0.418688 * g - 0.081312 * b

        cb, cr = _chroma_mean_2x2(cb), _chroma_mean_2x2(cr)

    zig = jnp.asarray(zigzag_order())
    D = jnp.asarray(dct_matrix())
    return (
        _dct_quant_zigzag(y, qy, zig, D),
        _dct_quant_zigzag(cb, qc, zig, D),
        _dct_quant_zigzag(cr, qc, zig, D),
    )


@jax.jit
def rgb_to_jpeg_coefficients(rgb, qy, qc):
    """u8/f32[B, H, W, 3] RGB -> coefficients (CPU-reference-path variant)."""
    rgb = rgb.astype(jnp.uint32)
    packed = (rgb[..., 0] | (rgb[..., 1] << 8) | (rgb[..., 2] << 16))
    return packed_to_jpeg_coefficients(packed, qy, qc)


@jax.jit
def render_to_jpeg_coefficients(raw, window_start, window_end, family,
                                coefficient, reverse, cd_start, cd_end,
                                tables, qy, qc):
    """Fused batched render + JPEG front end, one device dispatch.

    The packed-RGBA intermediate stays in HBM; only the quantized
    coefficients cross the host link.  Argument order matches
    :func:`..ops.render.render_tile_batch_packed` plus the two quant tables.
    """
    from .render import _render_packed_impl

    packed = _render_packed_impl(raw, window_start, window_end, family,
                                 coefficient, reverse, cd_start, cd_end,
                                 tables)
    return packed_to_jpeg_coefficients(packed, qy, qc)


ENTRY_BITS = 18      # 6-bit zigzag position + 12-bit value (two's compl.)


def sparse_wire_width(H: int, W: int, cap: int) -> int:
    """Total device wire-buffer bytes per tile (the static shape)."""
    h16, w16 = (H + 15) // 16, (W + 15) // 16
    nb = h16 * w16 * 6
    return 4 + nb + (ENTRY_BITS * cap + 7) // 8


def sparse_prefix_bytes(total: int, H: int, W: int) -> int:
    """Bytes of a tile's wire buffer actually carrying data: the header,
    the per-block counts, and ``total`` 18-bit entries."""
    h16, w16 = (H + 15) // 16, (W + 15) // 16
    nb = h16 * w16 * 6
    return 4 + nb + (ENTRY_BITS * int(total) + 7) // 8


def _shift_left(x, s: int):
    """``x`` moved left along its last axis by a static ``s``, zeros
    coming in at the end."""
    n = x.shape[-1]
    if s >= n:
        return jnp.zeros_like(x)
    return jnp.pad(x[..., s:], [(0, 0)] * (x.ndim - 1) + [(0, s)])


_ENTRY_MASK = (1 << ENTRY_BITS) - 1
# What a 32-bit word holds of an entry's distance beside its 18 bits.
_DIST_BITS = 32 - ENTRY_BITS


def _shift_select(w, first: int, nbits: int):
    """``nbits`` passes of the log-step compaction over words
    ``entry | dist << 18`` (0 = a hole): in pass ``j`` every element
    whose distance bit ``j`` is set moves left by ``2**(first + j)``:
    one static shift and one select over the whole array."""
    for j in range(nbits):
        arriving = _shift_left(w, 1 << (first + j))
        comes = ((arriving >> (ENTRY_BITS + j)) & 1) == 1
        leaves = ((w >> (ENTRY_BITS + j)) & 1) == 1
        w = jnp.where(comes, arriving, jnp.where(leaves, 0, w))
    return w


def _compact_entries(field, keep, wi, cap: int):
    """Order-preserving left compaction: i32[B, cap] holding row b's
    kept ``field`` values in order, zeros after them; what does not
    fit in ``cap`` is dropped.  ``wi`` is the inclusive prefix sum of
    ``keep`` minus one (a kept element's target); a kept ``field`` is
    nonzero and under ``2**18``.

    A kept element at ``i`` must move left by ``d = i - wi[i]``, the
    zeros before it.  Taking the bits of ``d`` least significant
    first, an element whose bit ``k`` is set moves left by ``2**k``;
    two kept elements never meet (their distances differ by less than
    their separation, modulo any ``2**k``: the compress network of
    Hacker's Delight 7-4), so after ``ceil(log2 N)`` passes the
    entries stand compacted.  Each pass is a static shift and a select
    over the dense array: no index, no scatter, no gather.  The low 14
    bits of ``d`` ride in the word above the entry's 18, so a pass
    moves one array; for the passes past them the distance left is
    found again as position minus rank (a second prefix sum) and rides
    in the same bits.
    """
    N = field.shape[-1]
    nbits = max(N - 1, 1).bit_length()
    low = min(nbits, _DIST_BITS)
    idx = jnp.arange(N, dtype=jnp.int32)
    dist = idx - wi
    w = jnp.where(
        keep, field | ((dist & ((1 << low) - 1)) << ENTRY_BITS), 0)
    w = _shift_select(w, 0, low) & _ENTRY_MASK
    if nbits > low:
        held = w != 0
        rank = jnp.cumsum(held, axis=-1, dtype=jnp.int32) - 1
        w = jnp.where(held, w | (((idx - rank) >> low) << ENTRY_BITS), 0)
        w = _shift_select(w, low, nbits - low) & _ENTRY_MASK
    return jnp.pad(
        w, [(0, 0)] * (w.ndim - 1) + [(0, max(cap - N, 0))])[..., :cap]


# Entries a row of the stream-assembly matmul: 512 entries are exactly
# 1,152 = 9 x 128 bytes.
_PACK_ROW = 512


@functools.lru_cache(maxsize=1)
def _pack_tables():
    """How 18-bit entries become bytes, for a row of ``_PACK_ROW``
    entries: entry ``e`` starts at bit ``18 e`` and so falls into three
    consecutive bytes; piece ``p`` of it is ``(entry >> shift[p, e]) &
    mask[p, e]`` and lands in byte ``byte[p, e]`` times ``scale[p, e]``
    (a power of two)."""
    shape = (3, _PACK_ROW)
    shift, mask, byte, scale = (np.zeros(shape, np.int32)
                                for _ in range(4))
    for e in range(_PACK_ROW):
        bit, left = ENTRY_BITS * e, ENTRY_BITS
        for p in range(3):
            byte[p, e], used = divmod(bit, 8)
            take = min(8 - used, left)
            left -= take
            shift[p, e] = left
            mask[p, e] = (1 << take) - 1
            scale[p, e] = 1 << (8 - used - take)
            bit += take
        assert left == 0
    return shift, mask, byte.reshape(-1), scale.reshape(-1)


def _pack_entries(comp, nbytes: int):
    """i32[B, cap] 18-bit entries -> the MSB-first stream u8[B, nbytes].

    Four entries are exactly nine bytes, so the stream is a fixed
    linear map of the entries' pieces: every entry is cut (shifts and
    masks, no index array) into the three pieces that fall into
    different bytes, and one matmul with a 0 / power-of-two matrix
    adds each byte's at most two pieces at their places.  Pieces are
    under 256 and the scales powers of two, so bf16 holds both exactly
    and the f32 sums (under 256) are exact.
    """
    B, cap = comp.shape
    rows = -(-cap // _PACK_ROW)
    e = jnp.pad(comp, ((0, 0), (0, rows * _PACK_ROW - cap)))
    e = e.reshape(B, rows, _PACK_ROW)
    shift, mask, byte, scale = _pack_tables()
    pieces = jnp.concatenate(
        [((e >> shift[p]) & mask[p]).astype(jnp.bfloat16)
         for p in range(3)], axis=-1)                 # [B, rows, 3*512]
    out_bytes = ENTRY_BITS * _PACK_ROW // 8
    place = jnp.where(
        byte[:, None] == jnp.arange(out_bytes, dtype=jnp.int32)[None, :],
        scale[:, None], 0).astype(jnp.bfloat16)       # [3*512, 1152]
    stream = jnp.einsum("brk,kn->brn", pieces, place,
                        preferred_element_type=jnp.float32)
    return stream.astype(jnp.uint8).reshape(B, -1)[:, :nbytes]


@jax.named_scope("wire.sparse_pack")
def sparse_pack(y, cb, cr, cap: int):
    """Compact nonzero coefficients into one u8 wire buffer per tile.

    The device ships only the entropy-bearing bytes, so the
    device-to-host fetch scales with content, not pixels: for each tile
    a buffer

        [ total_entries i32 LE | per-block nonzero counts u8[nb] |
          packed 18-bit entries u8[ceil(18*cap/8)] ]

    where entry j (MSB-first at bit ``18*j``) is ``pos << 12 | val``:
    the 6-bit zigzag position and the 12-bit two's-complement value (the
    quantizer clips to ±2047, so 12 bits are exact) of the j-th nonzero
    in (block, zigzag) scan order — exactly the run-length stream
    baseline JPEG entropy-codes, so the host encoder
    (``jpeg_encode_sparse_run``) reads it directly.  Block order is luma
    raster, then Cb raster, then Cr raster.  Entries beyond ``cap`` are
    dropped (detected host-side via total_entries > cap; the caller then
    falls back to the dense path).

    At 2.25 bytes/entry the used bytes are one contiguous prefix
    (``sparse_prefix_bytes``), so the host fetches only that prefix —
    comparable in size to the final JPEG itself — instead of the full
    ``cap``-sized buffer (``SparseWireFetcher``).

    Neither stage holds a scatter or a data-dependent gather.  On a
    v5e the set-scatter that stood here ran at ~5 ns a source element
    and the per-byte gather pass at ~1.6 ns a byte, together half of a
    saturated chip (the traces of PR 25-28; 59 and 8 ms of a B = 8
    group at 4 x 1024^2, the stages timed alone in PR 29).  The
    compaction is :func:`_compact_entries`' shift-and-select passes
    (scope ``wire.sparse_pack.scatter``, 4.4 ms for the same group),
    the stream :func:`_pack_entries`' matmul
    (``wire.sparse_pack.bits``, 0.9 ms); the bytes are the same.
    """
    B = y.shape[0]
    flat = jnp.concatenate(
        [y.reshape(B, -1), cb.reshape(B, -1), cr.reshape(B, -1)], axis=1
    ).astype(jnp.int32)
    N = flat.shape[1]
    nb = N // 64
    mask = flat != 0
    counts = mask.reshape(B, nb, 64).sum(-1).astype(jnp.uint8)
    wi = jnp.cumsum(mask, axis=1) - 1                      # [B, N]
    total = (wi[:, -1] + 1).astype(jnp.int32)
    pos = jnp.arange(N, dtype=jnp.int32) % 64
    field = (pos << 12) | (flat & 0xFFF)                   # 18-bit entries

    with jax.named_scope("wire.sparse_pack.scatter"):
        comp = _compact_entries(field, mask, wi, cap)      # [B, cap]
    with jax.named_scope("wire.sparse_pack.bits"):
        stream = _pack_entries(comp, (ENTRY_BITS * cap + 7) // 8)
    tot_u8 = jax.lax.bitcast_convert_type(
        total[:, None], jnp.uint8).reshape(B, -1)
    return jnp.concatenate([tot_u8, counts, stream], axis=1)


@functools.partial(jax.jit, static_argnames=("cap",))
def render_to_jpeg_sparse(raw, window_start, window_end, family,
                          coefficient, reverse, cd_start, cd_end, tables,
                          qy, qc, cap: int):
    """Fused render + JPEG front end + sparse wire packing, one dispatch."""
    y, cb, cr = render_to_jpeg_coefficients(
        raw, window_start, window_end, family, coefficient, reverse,
        cd_start, cd_end, tables, qy, qc)
    return sparse_pack(y, cb, cr, cap)


class SparseWireFetcher:
    """Predictive prefix fetch of sparse wire buffers.

    The wire buffer's used bytes are one contiguous prefix
    (``sparse_prefix_bytes``), so on a slow host link only that prefix
    need cross.  The fetcher predicts the next batch's prefix from the
    largest tile seen so far (with headroom), rounds to a granule so the
    device slice comes from a small, cached set of compiled shapes, and
    completes any under-predicted row with a follow-up fetch.
    """

    GRANULE = 16 * 1024

    def __init__(self, H: int, W: int, cap: int, headroom: float = 1.06):
        h16, w16 = (H + 15) // 16, (W + 15) // 16
        self.nb = h16 * w16 * 6
        self.cap = cap
        self.width = sparse_wire_width(H, W, cap)
        self.headroom = headroom
        # First fetch: a third of the worst case, floor one granule.
        self._k = self._round(max(self.GRANULE, self.width // 3))

    def _round(self, n: int) -> int:
        g = self.GRANULE
        return min(self.width, ((n + g - 1) // g) * g)

    def start(self, buf):
        """Slice the predicted prefix and start its async host copy.

        ``buf`` is the device u8[B, width] array from
        :func:`render_to_jpeg_sparse`.  Returns an opaque handle for
        :meth:`finish`.
        """
        k = self._k
        pre = buf if k >= self.width else buf[:, :k]
        if hasattr(pre, "copy_to_host_async"):
            pre.copy_to_host_async()
        return pre, buf, k

    def _needed(self, host: np.ndarray) -> np.ndarray:
        """Per-row used-prefix bytes, from the fetched headers.
        Overflowed tiles (total > cap) need only the header to be
        detected; clamp so prediction tracks real prefixes."""
        totals = host[:, :4].copy().view(np.int32).ravel()
        return (4 + self.nb
                + (ENTRY_BITS * np.clip(totals, 0, self.cap) + 7) // 8)

    def finish(self, handle, tiles: int = None,
               timings: dict = None) -> np.ndarray:
        """Complete a fetch: host u8[B, >=prefix] rows, decodable by
        the matching decoder.  ``tiles`` / ``timings``: see
        :func:`_fetch_first`."""
        pre, buf, k = handle
        host = _fetch_first(pre, tiles, timings)
        needed = self._needed(host)
        mx = int(needed.max(initial=0))
        self._k = self._round(int(mx * self.headroom))
        if mx <= k:
            return host
        # Under-predicted: complete ALL rows with one batched slice (a
        # per-row fetch would pay the link's latency floor B times).
        rest = _fetch_rest(buf[:, k:self._round(mx)])
        return np.concatenate([host, rest], axis=1)

    def fetch(self, buf, tiles: int = None,
              timings: dict = None) -> np.ndarray:
        return self.finish(self.start(buf), tiles, timings)


_FETCHERS: dict = {}
_FETCHERS_LOCK = threading.Lock()


def _observe_fetch(nbytes: int, seconds: float,
                   conflated: bool = False) -> None:
    """``conflated``: the timed window synchronized on device EXECUTION
    as well as the transfer (the first fetch of a dispatched program),
    so bytes/seconds is a LOWER BOUND on the link rate, not a
    measurement of it."""
    # The link-health EWMA gauge (/metrics imageregion_link_mb_s) rides
    # every fetch — it is what settles "weather or regression?" when a
    # bench headline moves.
    from ..utils.telemetry import LINK
    try:
        LINK.observe(nbytes, seconds, conflated)
    except Exception:       # pragma: no cover - telemetry must never
        pass                # break the serving path


def _fetch_first(pre, tiles: int = None,
                 timings: dict = None) -> np.ndarray:
    """The first host copy of a dispatched program's output, as the
    span ``wire.fetch`` and its two parts: ``device.wait`` (the program
    and the slice program running to their end) and ``wire.d2h`` (the
    copy alone; it begins when ``tiles`` real tiles are rendered: how a
    profiler capture counts renders).  The wait's milliseconds go into
    ``timings["device_ms"]`` for the caller's cost ledger."""
    with stopwatch("wire.fetch") as fetch:
        with stopwatch("device.wait", tiles=tiles or 0) as wait:
            if hasattr(pre, "block_until_ready"):
                pre.block_until_ready()
        with stopwatch("wire.d2h", tiles=tiles or 0):
            host = np.asarray(pre)
    if timings is not None:
        timings["device_ms"] = timings.get("device_ms", 0.0) + wait.ms
    # Conflated: this wait covers the device render completing, not
    # just the wire, so its rate is only a lower bound on the link.
    _observe_fetch(host.nbytes, fetch.ms / 1000.0, conflated=True)
    return host


def _fetch_rest(rest) -> np.ndarray:
    """The follow-up copy of an under-predicted prefix
    (``wire.fetch2``): a slice program and its copy, nothing else."""
    with stopwatch("wire.fetch2") as fetch:
        host = np.asarray(rest)
    _observe_fetch(host.nbytes, fetch.ms / 1000.0)
    return host


def wire_fetcher(H: int, W: int, cap: int) -> SparseWireFetcher:
    """Process-wide fetcher per (tile shape, cap): prediction state is
    shared across requests so the serving path warms up once."""
    key = (H, W, cap)
    with _FETCHERS_LOCK:
        f = _FETCHERS.get(key)
        if f is None:
            f = _FETCHERS[key] = SparseWireFetcher(H, W, cap)
        return f


@jax.named_scope("wire.compact_rows")
def _compact_rows(bufs, lengths):
    """Device-side wire compaction: pack each row's used prefix
    contiguously so the host fetch carries exactly the needed bytes.

    ``bufs`` is u8[B, width] (either engine's wire layout), ``lengths``
    i32[B] gives each row's used-byte count (0 for rows the caller wants
    excluded, e.g. batch padding).  Returns u8[4*B + B*width]:

        [ lengths i32 LE x B | row0[:len0] | row1[:len1] | ... ]

    The prefix-fetch economics this enables: the old per-batch fetch
    sliced a COMMON prefix ``bufs[:, :k]`` with k predicted from the
    largest row — under per-request settings variance that over-fetches
    every smaller row (measured 1.8x wire waste at service load) and
    pads rows cost full freight.  Compacted, prediction tracks the SUM
    of row sizes (far lower relative variance), pad rows cost zero, and
    a group's wire bytes equal its entropy bytes.

    Rows move whole: row ``b`` goes left by ``b*width - start[b]``,
    the same distance for all its bytes.  Each row is zeroed past its
    length and the rows are written in ascending order at
    ``start[b]`` (``dynamic_update_slice``, one block move a row): a
    later row overwrites the zero tail of the one before it, and what
    the last one leaves is zeros to the end.  The unique-index
    set-scatter that stood here cost a v5e ~7 ns a byte (61 ms for
    8 rows of 909 KB, two fifths of a saturated chip in the traces of
    PR 25-28); the moves take 1.1 ms for the same rows (both timed
    alone in PR 29).
    """
    B, width = bufs.shape
    lengths = lengths.astype(jnp.int32)
    start = jnp.cumsum(lengths) - lengths
    col = jnp.arange(width, dtype=jnp.int32)
    rows = jnp.where(col[None, :] < lengths[:, None], bufs, 0)
    data = jax.lax.fori_loop(
        0, B,
        lambda b, out: jax.lax.dynamic_update_slice(
            out, rows[b], (start[b],)),
        jnp.zeros(B * width, jnp.uint8))
    header = jax.lax.bitcast_convert_type(lengths, jnp.uint8).reshape(-1)
    return jnp.concatenate([header, data])


@functools.partial(jax.jit, static_argnames=("cap",))
def render_to_jpeg_sparse_compact(raw, window_start, window_end, family,
                                  coefficient, reverse, cd_start, cd_end,
                                  tables, qy, qc, n_valid, *, cap: int):
    """Fused render + sparse wire + device compaction, one dispatch.

    ``n_valid`` (traced i32) masks trailing batch-padding rows to zero
    wire bytes.  Overflowed rows (total > cap) compact to just their
    header + counts — enough for the host to detect the overflow and
    take the dense path without shipping a dropped-entry stream.
    """
    bufs = render_to_jpeg_sparse(
        raw, window_start, window_end, family, coefficient, reverse,
        cd_start, cd_end, tables, qy, qc, cap=cap)
    B = bufs.shape[0]
    H, W = raw.shape[-2:]
    nb = ((H + 15) // 16) * ((W + 15) // 16) * 6
    total = jax.lax.bitcast_convert_type(
        bufs[:, :4].reshape(B, 1, 4), jnp.int32).reshape(B)
    used = 4 + nb + (ENTRY_BITS * jnp.minimum(total, cap) + 7) // 8
    lengths = jnp.where(total <= cap, used, 4 + nb)
    lengths = jnp.where(jnp.arange(B) < n_valid, lengths, 0)
    return _compact_rows(bufs, lengths)


@functools.partial(jax.jit,
                   static_argnames=("cap", "cap_words", "h16", "w16"))
def render_to_jpeg_huffman_compact(raw, window_start, window_end, family,
                                   coefficient, reverse, cd_start, cd_end,
                                   tables, qy, qc, dc_code, dc_len,
                                   ac_code, ac_len, n_valid, *,
                                   h16: int, w16: int,
                                   cap: int, cap_words: int):
    """Fused render + device Huffman + device compaction, one dispatch.

    Overflowed rows (entries > cap or bits > word budget) compact to
    their 8-byte header only; the host detects and dense-falls-back.
    """
    bufs = render_to_jpeg_huffman(
        raw, window_start, window_end, family, coefficient, reverse,
        cd_start, cd_end, tables, qy, qc, dc_code, dc_len, ac_code,
        ac_len, h16=h16, w16=w16, cap=cap, cap_words=cap_words)
    B = bufs.shape[0]
    hdr = jax.lax.bitcast_convert_type(
        bufs[:, :8].reshape(B, 2, 4), jnp.int32)
    total, bits = hdr[:, 0], hdr[:, 1]
    ok = (total <= cap) & (bits <= cap_words * 32)
    words = jnp.where(ok, (bits + 31) // 32, 0)
    lengths = (8 + 4 * words).astype(jnp.int32)
    lengths = jnp.where(jnp.arange(B) < n_valid, lengths, 0)
    return _compact_rows(bufs, lengths)


class CompactWireFetcher:
    """Predictive prefix fetch of a COMPACTED wire buffer.

    The buffer is ``[lengths i32 x B | concatenated used prefixes]``
    (:func:`_compact_rows`), so prediction tracks the batch's total
    used bytes — much lower relative variance than the per-row max the
    uncompacted fetchers must bound.  Under-prediction costs a second
    fetch (``wire.fetch2``), so the
    headroom adapts asymmetrically: a miss raises it sharply, on-target
    batches decay it slowly back toward the floor.
    """

    GRANULE = 32 * 1024
    HEADROOM_FLOOR = 1.06
    HEADROOM_CEIL = 1.6
    # Fetch sizes snap UP to a geometric ladder (ratio 2^(1/4), <=19%
    # over-fetch) instead of a fine arithmetic granule: every distinct
    # device slice shape costs an XLA compile, so the shape set must
    # be small and stable while predictions drift with content.
    LADDER_RATIO = 2.0 ** 0.25

    def __init__(self, B: int, width: int, prior_row_bytes: int = None):
        self.B = B
        self.hdr = 4 * B
        self.width = self.hdr + B * width     # full device buffer bytes
        self.headroom = self.HEADROOM_FLOOR
        # The fetcher is shared process-wide per (engine, shape, caps,
        # batch) while up to pipeline_depth workers render groups of
        # the same bucket concurrently; the _k/headroom read-modify-
        # write must not interleave or the prefix prediction mis-trains
        # (each mis-prediction costs ~1 link RTT).
        self._lock = threading.Lock()
        ladder = []
        step = float(self.GRANULE)
        while step < self.width:
            ladder.append(int(step))
            step *= self.LADDER_RATIO
        ladder.append(self.width)
        self._ladder = ladder
        # First fetch: the caller's content prior (e.g. measured
        # bytes/px for the engine's stream class) with generous slack —
        # a first-touch miss pays a link RTT AND a one-time slice-shape
        # compile, both far dearer than a fat first fetch.
        prior = (int(prior_row_bytes * B * 1.5) if prior_row_bytes
                 else self.width // 8)
        self._k = self._round(max(self.GRANULE, prior))

    def _round(self, n: int) -> int:
        n = max(n, self.hdr)
        for step in self._ladder:
            if step >= n:
                return step
        return self.width

    def start(self, buf):
        with self._lock:
            k = self._k
        pre = buf if k >= self.width else buf[:k]
        if hasattr(pre, "copy_to_host_async"):
            pre.copy_to_host_async()
        return pre, buf, k

    def finish(self, handle, tiles: int = None,
               timings: dict = None) -> list:
        """Complete a fetch -> per-row u8 arrays (length B; excluded
        rows come back empty).  ``tiles`` / ``timings``: see
        :func:`_fetch_first`."""
        pre, buf, k = handle
        host = _fetch_first(pre, tiles, timings)
        lengths = host[:self.hdr].view(np.int32)
        total = self.hdr + int(lengths.sum())
        missed = total > k
        if missed:
            host = np.concatenate(
                [host, _fetch_rest(buf[k:self._round(total)])])
        # Atomic prediction update: the fetches themselves run
        # unlocked (concurrent groups overlap on the wire by design);
        # only the read-modify-write of the shared training state is
        # serialized.
        with self._lock:
            if missed:
                self.headroom = min(self.HEADROOM_CEIL,
                                    self.headroom * 1.2)
            else:
                self.headroom = max(self.HEADROOM_FLOOR,
                                    self.headroom * 0.995)
            self._k = self._round(int(total * self.headroom))
        offs = self.hdr + np.concatenate(
            [[0], np.cumsum(lengths, dtype=np.int64)])
        return [host[offs[i]:offs[i + 1]] for i in range(self.B)]

    def fetch(self, buf, tiles: int = None,
              timings: dict = None) -> list:
        return self.finish(self.start(buf), tiles, timings)


def compact_fetcher(engine: str, H: int, W: int, cap: int,
                    cap_words: int, B: int) -> CompactWireFetcher:
    """Process-wide prediction state per (engine, shape, caps, batch)."""
    if engine == "huffman":
        width = 8 + 4 * cap_words
        # Measured q85 fixed-table streams on WSI-class content run
        # ~0.10-0.12 B/px; 0.14 as the first-touch prior.
        prior = 8 + int(H * W * 0.14)
    else:
        width = sparse_wire_width(H, W, cap)
        # Sparse wire: counts (6 B per 16x16 MCU region... nb bytes)
        # plus ~3.6x the huffman stream's entropy bytes.
        prior = 4 + ((H + 15) // 16) * ((W + 15) // 16) * 6 \
            + int(H * W * 0.5)
    key = ("compact", engine, H, W, cap, cap_words, B)
    with _FETCHERS_LOCK:
        f = _FETCHERS.get(key)
        if f is None:
            f = _FETCHERS[key] = CompactWireFetcher(B, width, prior)
        return f


def _quality_widen(quality: "int | None") -> int:
    """Cap multiplier for high-quality quant tables: measured WSI
    content runs ~5% coefficient density at q80 but ~12% at q90 — past
    the 1/8 default budgets, which would silently drop every tile to
    the per-tile host dense path (~170 ms each).  One shared rule so
    the direct, batched and mesh renderers all stay on the device
    path at high quality."""
    return 2 if quality is not None and quality >= 88 else 1


def wire_header_i32(bufs: np.ndarray, word: int) -> np.ndarray:
    """The per-row i32 header field ``word`` of fetched wire buffers
    (one place for the layout; both engines lead with LE i32 words)."""
    return bufs[:, 4 * word:4 * word + 4].copy().view(np.int32).ravel()


def row_header_i32(row: np.ndarray, word: int) -> int:
    """Header field of ONE wire row (compacted rows may sit at
    unaligned offsets, so go through bytes, not a view)."""
    return int.from_bytes(row[4 * word:4 * word + 4].tobytes(),
                          "little", signed=True)


# Process-wide overflow memo: once a (shape, quality, engine) workload
# overflows its default cap, later groups start at the doubled cap
# instead of paying a wasted base dispatch per group.
_CAP_MEMO: dict = {}

# Per-workload TUNED Huffman tables for the device wire: the packer's
# code/length tables are runtime arrays, so swapping in tables built
# from the workload's own symbol statistics costs nothing on device and
# shrinks every stream ~4-8% (wire time AND payload).  Keyed
# (H, W, quality); value = ((dc_code, dc_len, ac_code, ac_len) i32
# kernel arrays, jfif 8-tuple spec for framing), or None when tuning
# failed (never retried).  Computed ONCE per workload on a background
# thread from a sample tile's dense coefficients; groups serve the
# fixed profile until the tuned tables are ready.  Single-process
# serving only — the mesh path keeps the fixed pod-agreed tables.
_TUNED_TABLES: dict = {}
_TUNED_PENDING: set = set()
_TUNED_LOCK = threading.Lock()


def spec_kernel_arrays(spec8) -> tuple:
    """A jfif 8-tuple spec -> the (dc_code, dc_len, ac_code, ac_len)
    i32 arrays the device packer takes — ONE projection shared by the
    serving tuner and the bench (a drifted duplicate would silently
    decouple what the bench measures from what serving runs)."""
    return (spec8[2].astype(np.int32), spec8[3].astype(np.int32),
            spec8[6].astype(np.int32), spec8[7].astype(np.int32))


def _compute_tuned_tables(key, dense_coefficients) -> None:
    """Build and publish the tuned spec for ``key``; any failure
    (device error, odd content) publishes None so serving never
    retries or blocks on tuning."""
    from ..jfif import symbol_frequencies, tuned_huffman_spec
    try:
        y, cb, cr = dense_coefficients(0)
        spec8 = tuned_huffman_spec(*symbol_frequencies(y, cb, cr))
        result = (spec_kernel_arrays(spec8), spec8)
    except Exception:       # pragma: no cover - tuning must never break
        result = None       # serving; the fixed profile keeps working
    with _TUNED_LOCK:
        _TUNED_TABLES[key] = result
        _TUNED_PENDING.discard(key)


def _maybe_start_tuning(key, dense_coefficients) -> None:
    with _TUNED_LOCK:
        if key in _TUNED_TABLES or key in _TUNED_PENDING:
            return
        _TUNED_PENDING.add(key)
    threading.Thread(
        target=_compute_tuned_tables, args=(key, dense_coefficients),
        name=f"hufftune-{key[0]}x{key[1]}", daemon=True).start()


def default_sparse_cap(H: int, W: int, quality: "int | None" = None
                       ) -> int:
    """Wire-buffer entry budget per tile: 1/8 of all coefficient slots
    (1/4 for quality >= 88, see :func:`_quality_widen`).

    Measured densities: synthetic WSI content ~3%, worst-case uniform
    noise ~45% (which overflows and takes the dense fallback — by design).
    """
    return max_sparse_cap(H, W) // 8 * _quality_widen(quality)


def max_sparse_cap(H: int, W: int) -> int:
    """Every coefficient slot of the (16-aligned) frame — the cap at which
    no tile can overflow (tests and noise workloads)."""
    nb = (H // 8) * (W // 8) + 2 * (H // 16) * (W // 16)
    return nb * 64


def sparse_to_dense(buf: np.ndarray, H: int, W: int, cap: int):
    """Rebuild (y, cb, cr) dense coefficient blocks from one wire buffer.

    Returns None if the buffer overflowed ``cap`` (entries were dropped).
    Pure-numpy; used by tests and the Python fallback encoder.  ``buf``
    may be a prefix fetch: any length >= ``sparse_prefix_bytes(total)``
    decodes.
    """
    # The wire buffer is packed for the 16-aligned (MCU-padded) grid, so
    # block counts use ceil — H/W may be the tile's true, unaligned size
    # (the native encoder does the same, jpegenc.cpp encode_sparse_row).
    h16, w16 = (H + 15) // 16, (W + 15) // 16
    nb_y = h16 * w16 * 4
    nb_c = h16 * w16
    nb = nb_y + 2 * nb_c
    total = int(buf[:4].view(np.int32)[0])
    if total > cap:
        return None
    need = 4 + nb + (ENTRY_BITS * total + 7) // 8
    if len(buf) < need:
        raise ValueError(
            f"sparse buffer too short: {len(buf)} bytes < {need} needed")
    counts = buf[4:4 + nb].astype(np.int64)
    if int(counts.sum()) != total:
        raise ValueError("sparse buffer malformed: counts do not sum to "
                         "total")
    # Vectorized 18-bit field extraction: entry j lives MSB-first at bit
    # 18j; read a 32-bit big-endian window at its byte and shift.
    stream = np.pad(buf[4 + nb:], (0, 4)).astype(np.uint32)
    j = np.arange(total)
    bit = j * ENTRY_BITS
    byte0 = bit >> 3
    shift = bit & 7
    window = ((stream[byte0] << 24) | (stream[byte0 + 1] << 16)
              | (stream[byte0 + 2] << 8) | stream[byte0 + 3])
    field = (window >> (32 - 18 - shift)) & 0x3FFFF
    ps = (field >> 12).astype(np.int64)
    vs = (field & 0xFFF).astype(np.int16)
    vs = np.where(vs >= 2048, vs - 4096, vs).astype(np.int16)
    dense = np.zeros((nb, 64), np.int16)
    block_ids = np.repeat(np.arange(nb), counts)
    dense[block_ids, ps] = vs
    return (dense[:nb_y].reshape(nb_y, 64),
            dense[nb_y:nb_y + nb_c].reshape(nb_c, 64),
            dense[nb_y + nb_c:].reshape(nb_c, 64))


def encode_tiles_jpeg(packed, quality: int = 85, width: int | None = None,
                      height: int | None = None, executor=None) -> list:
    """Full TPU JPEG pipeline for a batch: packed RGBA -> JFIF bytes.

    Device: color transform + DCT + quantize + zigzag.  Host: entropy code
    each tile (native C++ when available, Python fallback), fanned out over
    ``executor`` threads when given (the ctypes call releases the GIL).

    ``packed`` is u32[B, H, W] with H, W multiples of 16; ``width``/
    ``height`` override the SOF0 dimensions for MCU-padded tiles.
    """
    B, H, W = packed.shape
    width = W if width is None else width
    height = H if height is None else height
    qy, qc = quant_tables(quality)
    y, cb, cr = packed_to_jpeg_coefficients(
        jnp.asarray(packed), qy.astype(np.int32), qc.astype(np.int32)
    )
    for a in (y, cb, cr):
        a.copy_to_host_async()
    y, cb, cr = np.asarray(y), np.asarray(cb), np.asarray(cr)

    from ..native import jpeg_native_available
    if jpeg_native_available():
        from ..native import jpeg_encode_native as _encode
    else:
        from ..jfif import encode_jfif as _encode

    def one(i):
        return _encode(y[i], cb[i], cr[i], width, height, quality)

    if executor is None:
        return [one(i) for i in range(B)]
    return list(executor.map(one, range(B)))


# ------------------------------------ compacted-entry device Huffman

@functools.lru_cache(maxsize=16)
def _mcu_scan_index(h16: int, w16: int) -> np.ndarray:
    """[n_mcu, 6] flat block indices (into [Y|Cb|Cr] raster blocks) in
    interleaved MCU scan order: 2x2 Y, then Cb, then Cr (T.81 A.2.3)."""
    nb_y = h16 * w16 * 4
    yw = w16 * 2
    my, mx = np.divmod(np.arange(h16 * w16), w16)
    idx = np.stack([
        (2 * my) * yw + 2 * mx, (2 * my) * yw + 2 * mx + 1,
        (2 * my + 1) * yw + 2 * mx, (2 * my + 1) * yw + 2 * mx + 1,
        nb_y + my * w16 + mx,
        nb_y + h16 * w16 + my * w16 + mx,
    ], axis=1)
    return idx.astype(np.int32)


def _category(x):
    """JPEG magnitude category of an i32 array, branchlessly (<= 11)."""
    a = jnp.abs(x)
    return sum((a >= (1 << b)).astype(jnp.int32) for b in range(11))


def _amplitude(x, s):
    """Amplitude bits: value as-is if positive, ones'-complement if not."""
    return jnp.where(x >= 0, x, x + jnp.left_shift(1, s) - 1)


def default_words_cap(H: int, W: int, quality: "int | None" = None
                      ) -> int:
    """Stream-word budget per tile for the compacted Huffman packer:
    H*W/8 bytes (~1.6x the measured fixed-table stream at benchmark
    density, doubled for quality >= 88; overflow falls back to the
    dense host path)."""
    return (H * W) // 8 // 4 * _quality_widen(quality)


def _scan_order_flat(h16: int, w16: int) -> np.ndarray:
    """[nb] flat indices mapping raster [Y|Cb|Cr] blocks into the JPEG
    interleaved MCU scan order (2x2 Y, Cb, Cr per MCU)."""
    return _mcu_scan_index(h16, w16).reshape(-1)


@functools.partial(jax.jit,
                   static_argnames=("cap", "cap_words", "h16", "w16"))
@jax.named_scope("wire.huffman_pack")
def huffman_pack(y, cb, cr, cap: int, cap_words: int,
                 dc_code, dc_len, ac_code, ac_len, *, h16: int, w16: int):
    """Entropy-code quantized coefficients on device with fixed tables.

    The wire-optimal sibling of :func:`sparse_pack`: instead of 18-bit
    (pos, val) entries the device emits the actual Huffman bitstream
    (``jfif.fixed_huffman_spec`` tables — one DC + one AC table for all
    components), so only ~Huffman-entropy bytes cross the link and the
    host merely 0xFF-stuffs and frames (``jfif.finish_fixed_stream``).

    A deposit scatter for EVERY coefficient slot would be ~15M
    updates/tile.  Here all per-entry work runs on the ``cap``-sized
    COMPACTED stream (one unique-index set-scatter,
    the same trick as ``sparse_pack``), and the bit deposits touch
    ~1.3M update slots/tile across TWO coalesced scatter passes: the
    dense per-block fields (DC diff + EOB, over ``2*nb``) ride one and
    the per-entry fields (folded ZRLs + main code+amplitude, over
    ``2*cap``) the other — non-unique scatter-adds serialize on TPU,
    so halving the pass count matters as much as the slot count.

    Per tile the output is ``[total_entries i32 | total_bits i32 |
    stream words u32[cap_words]]`` as LE bytes; the used prefix is
    ``8 + 4*ceil(total_bits/32)``.  Overflow (entries > cap or bits >
    32*cap_words) is detected host-side from the header.
    """
    B = y.shape[0]
    nb = y.shape[1] + cb.shape[1] + cr.shape[1]
    N = nb * 64
    # Interleaved MCU scan order: everything downstream — DC chains,
    # entry order, bit offsets — follows the JPEG scan.  The reorder is
    # a static permutation with MCU structure, so it lowers to reshapes
    # + one transpose (HBM block copies) rather than a 1.5M-element
    # gather: raster Y block (2my+dy, 2mx+dx) -> scan slot (my, mx, dy,
    # dx); Cb/Cr raster order already matches the MCU scan.
    yi = (y.astype(jnp.int32)
          .reshape(B, h16, 2, w16, 2, 64)
          .transpose(0, 1, 3, 2, 4, 5)
          .reshape(B, h16 * w16, 4, 64))
    blocks = jnp.concatenate(
        [yi, cb.astype(jnp.int32)[:, :, None],
         cr.astype(jnp.int32)[:, :, None]], axis=2,
    ).reshape(B, nb, 64)                                 # [B, nb, 64]
    mask = blocks != 0
    counts = mask.sum(-1)                                # [B, nb]
    total = counts.sum(-1).astype(jnp.int32)             # [B]

    # Dense per-block DC fields: diff against the previous block of the
    # same component in scan order.  The predecessor pattern is
    # structural per MCU slot (Y1..Y3 <- the Y before them in the same
    # MCU; Y0/Cb/Cr <- the same slot's value one MCU back), so it is
    # shifted slices, not a gather — TPU gathers cost ~100ns/element.
    dc = blocks[..., 0]
    n_mcu = nb // 6
    d6 = dc.reshape(B, n_mcu, 6)
    prev_mcu = jnp.pad(d6[:, :-1], ((0, 0), (1, 0), (0, 0)))
    pred = jnp.concatenate([
        prev_mcu[:, :, 3:4],        # Y0 <- previous MCU's Y3
        d6[:, :, 0:3],              # Y1..Y3 <- Y0..Y2
        prev_mcu[:, :, 4:6],        # Cb/Cr <- previous MCU's Cb/Cr
    ], axis=2).reshape(B, nb)
    dcdiff = dc - pred
    s_dc = _category(dcdiff)
    # One fused (len << 16 | code) table -> one gather instead of two.
    dc_cl = (jnp.left_shift(dc_len, 16) | dc_code)[s_dc]
    dc_fval = (jnp.left_shift(dc_cl & 0xFFFF, s_dc)
               | _amplitude(dcdiff, s_dc))
    dc_flen = jnp.right_shift(dc_cl, 16) + s_dc
    has_eob = ~mask[..., 63]
    eob_val = jnp.where(has_eob, ac_code[0x00], 0)
    eob_len = jnp.where(has_eob, ac_len[0x00], 0)

    # Compacted (pos, val) entry stream, scan-ordered.
    flat_scan = blocks.reshape(B, N)
    m = flat_scan != 0
    wi = jnp.cumsum(m, axis=1) - 1
    pos64 = jnp.arange(N, dtype=jnp.int32) % 64
    fieldc = (pos64 << 12) | (flat_scan & 0xFFF)

    def compact_one(m_row, w_row, f_row):
        tgt = jnp.where(m_row & (w_row < cap), w_row, jnp.int32(1) << 30)
        return jnp.zeros(cap, jnp.int32).at[tgt].set(
            f_row, mode="drop", unique_indices=True)

    comp = jax.vmap(compact_one)(m, wi, fieldc)          # [B, cap]
    epos = comp >> 12
    ev = comp & 0xFFF
    evals = jnp.where(ev >= 2048, ev - 4096, ev)
    jidx = jnp.arange(cap, dtype=jnp.int32)
    evalid = jidx[None, :] < total[:, None]

    # First-of-block flags (scattered at each nonempty block's first
    # entry slot).
    nonempty = counts > 0
    S = jnp.cumsum(counts, axis=1) - counts              # exclusive

    def flag_one(S_row, ne_row):
        tgt = jnp.where(ne_row & (S_row < cap), S_row, jnp.int32(1) << 30)
        return jnp.zeros(cap, jnp.int32).at[tgt].set(
            1, mode="drop", unique_indices=True)

    first = jax.vmap(flag_one)(S, nonempty)

    # AC fields per entry (DC entries — pos 0, always a block's first
    # entry — carry no AC field; the dense pass above covers them).
    prevpos = jnp.pad(epos[:, :-1], ((0, 0), (1, 0)))
    prev = jnp.where(first == 1, 0, prevpos)
    run = epos - prev - 1
    ac_live = evalid & (epos != 0)
    s_ac = _category(evals)
    z = jnp.clip(run >> 4, 0, 3)
    rem = jnp.where(ac_live, run & 15, 0)
    sym = jnp.left_shift(rem, 4) | s_ac
    # One fused (len << 16 | code) gather over the [B, cap] stream.
    ac_cl = (jnp.left_shift(ac_len, 16) | ac_code)[sym]
    main_val = (jnp.left_shift(ac_cl & 0xFFFF, s_ac)
                | _amplitude(evals, s_ac))
    main_len = jnp.where(ac_live, jnp.right_shift(ac_cl, 16) + s_ac, 0)
    main_val = jnp.where(ac_live, main_val, 0)
    # Up to three folded ZRL codes as ONE field: the fixed spec's ZRL is
    # 10 bits, so 3 x 10 = 30 fits an i32 deposit (one pass, not two).
    zc, zl = ac_code[0xF0], ac_len[0xF0]
    nz_ = jnp.where(ac_live, z, 0)
    zrl_len = nz_ * zl
    one = zc
    two = jnp.left_shift(zc, zl) | zc
    three = jnp.left_shift(two, zl) | zc
    zrl_val = jnp.where(nz_ == 3, three,
                        jnp.where(nz_ == 2, two,
                                  jnp.where(nz_ == 1, one, 0)))
    ent_len = zrl_len + main_len

    # Bit offsets, all arithmetic: entry cumsum + per-block bases.
    ac_excl = jnp.cumsum(ent_len, axis=1) - ent_len      # [B, cap]
    ac_tot = (ac_excl[:, -1] + ent_len[:, -1])[:, None]
    acX = jnp.concatenate([ac_excl, ac_tot], axis=1)     # [B, cap+1]
    e0 = jnp.minimum(S, cap)
    e1 = jnp.minimum(S + counts, cap)
    block_ac = (jnp.take_along_axis(acX, e1, 1)
                - jnp.take_along_axis(acX, e0, 1))
    block_bits = dc_flen + block_ac + eob_len
    block_start = jnp.cumsum(block_bits, axis=1) - block_bits
    total_bits = (block_start[:, -1] + block_bits[:, -1]).astype(jnp.int32)

    # Per-entry bit base: scatter each nonempty block's base into its
    # first entry slot, then carry it across the block's entries with a
    # running max — NOT a [B, cap] gather.  Valid because the bases are
    # provably non-decreasing across nonempty blocks: for consecutive
    # nonempty b < b', base_{b'} - base_b = (sum of block_bits over
    # [b, b')) + dc_flen_{b'} - dc_flen_b - block_ac_b
    # >= eob_b + dc_flen_{b'} >= 0 (empty blocks between them only add
    # their dc+eob bits), and base_0 = dc_flen_0 >= 0, so zero-filled
    # gaps never win the max.
    base_b = block_start + dc_flen - jnp.take_along_axis(acX, e0, 1)

    def base_first_one(S_row, ne_row, vals):
        tgt = jnp.where(ne_row & (S_row < cap), S_row, jnp.int32(1) << 30)
        return jnp.zeros(cap, jnp.int32).at[tgt].set(
            vals, mode="drop", unique_indices=True)

    base_at_first = jax.vmap(base_first_one)(S, nonempty, base_b)
    carried = jax.lax.cummax(base_at_first, axis=1)
    estart = jnp.where(ac_live, carried + ac_excl, 0)

    oob = jnp.int32(1) << 30

    def deposit(words, val, length, start):
        w = start >> 5
        rb = start & 31
        sh0 = 32 - rb - length
        c0 = jnp.where(
            sh0 >= 0,
            jnp.left_shift(val, jnp.minimum(sh0, 31)),
            jnp.right_shift(val, jnp.minimum(-sh0, 31)),
        )
        sh1 = 64 - rb - length
        c1 = jnp.where(
            sh1 < 32, jnp.left_shift(val, jnp.maximum(sh1, 0) & 31), 0)
        # Route dead lanes (zero-length fields; second words the field
        # never crosses into) out of bounds: drop-mode scatters skip
        # them, and most fields are < 32 bits so this halves the
        # effective update stream.
        live = length > 0
        w0 = jnp.where(live, w, oob)
        w1 = jnp.where(live & (rb + length > 32), w + 1, oob)
        words = words.at[w0].add(c0, mode="drop")
        words = words.at[w1].add(c1, mode="drop")
        return words

    def pack_one(dcv, dcl, bst, bac, ev_, el_, zv, zlen, mv, ml, est):
        words = jnp.zeros(cap_words + 1, jnp.int32)
        # Coalesced deposits: the two dense per-block fields (DC diff,
        # EOB) ride one scatter pass and the two per-entry fields
        # (folded ZRLs, main code+amplitude) ride another — 2 deposit
        # passes (4 scatter-adds) instead of 4 (8).  Scatter-adds over
        # disjoint bits commute, so the stream is bit-identical; the
        # win is fewer serialized non-unique scatter ops per tile.
        words = deposit(words,
                        jnp.concatenate([dcv, ev_]),
                        jnp.concatenate([dcl, el_]),
                        jnp.concatenate([bst, bst + dcl + bac]))
        words = deposit(words,
                        jnp.concatenate([zv, mv]),
                        jnp.concatenate([zlen, ml]),
                        jnp.concatenate([est, est + zlen]))
        return words[:cap_words]

    words = jax.vmap(pack_one)(
        dc_fval, dc_flen, block_start, block_ac, eob_val, eob_len,
        zrl_val, zrl_len, main_val, main_len, estart)

    words_u8 = jax.lax.bitcast_convert_type(
        jax.lax.bitcast_convert_type(words, jnp.uint32), jnp.uint8
    ).reshape(B, -1)
    hdr = jax.lax.bitcast_convert_type(
        jnp.stack([total, total_bits], axis=1), jnp.uint8).reshape(B, -1)
    return jnp.concatenate([hdr, words_u8], axis=1)


@functools.partial(jax.jit,
                   static_argnames=("cap", "cap_words", "h16", "w16"))
def render_to_jpeg_huffman(raw, window_start, window_end, family,
                           coefficient, reverse, cd_start, cd_end, tables,
                           qy, qc, dc_code, dc_len, ac_code, ac_len,
                           *, h16: int, w16: int,
                           cap: int, cap_words: int):
    """Fused render + JPEG front end + device Huffman, one dispatch."""
    y, cb, cr = render_to_jpeg_coefficients(
        raw, window_start, window_end, family, coefficient, reverse,
        cd_start, cd_end, tables, qy, qc)
    return huffman_pack(y, cb, cr, cap, cap_words,
                        dc_code, dc_len, ac_code, ac_len,
                        h16=h16, w16=w16)


class HuffmanWireFetcher(SparseWireFetcher):
    """Prefix fetch for the Huffman wire: needed = 8 + stream bytes."""

    def __init__(self, H: int, W: int, cap: int, cap_words: int,
                 headroom: float = 1.06):
        self.cap = cap
        self.cap_words = cap_words
        self.width = 8 + 4 * cap_words
        self.headroom = headroom
        self._k = self._round(max(self.GRANULE, self.width // 3))

    def _needed(self, host: np.ndarray) -> np.ndarray:
        bits = host[:, 4:8].copy().view(np.int32).ravel()
        bits = np.clip(bits, 0, self.cap_words * 32)
        return 8 + 4 * ((bits + 31) // 32)


def huffman_spec_arrays():
    """(dc_code, dc_len, ac_code, ac_len) i32 arrays for the packer."""
    from ..jfif import fixed_huffman_spec
    _, _, dc_code, dc_len, _, _, ac_code, ac_len = fixed_huffman_spec()
    return (dc_code.astype(np.int32), dc_len.astype(np.int32),
            ac_code.astype(np.int32), ac_len.astype(np.int32))


def finish_huffman_batch(bufs, dims, H: int, W: int,
                         quality: int, cap: int, cap_words: int,
                         dense_fallback=None, spec=None,
                         on_tile=None) -> list:
    """Fetched Huffman wire rows -> JFIF bytes per tile.

    ``bufs`` indexes per-row u8 buffers: a 2D [B, >=prefix] array (the
    uncompacted wire) or a list of per-row arrays (the compacted wire,
    where rows carry exactly their used bytes).  Host work is O(stream
    bytes): byte-swap + 0xFF-stuff + frame (``jfif.finish_fixed_stream``).
    Overflowed tiles (entries > cap or bits > capacity) — and tiles whose
    ``dims`` entry is None (callers mark tiles the packed stream cannot
    serve, e.g. bucket-padded ones) — go through
    ``dense_fallback(i) -> bytes``.

    ``spec`` (jfif 8-tuple) frames with TUNED shared tables when the
    device packed the stream with them; None = the fixed profile.
    """
    from ..jfif import finish_fixed_stream, finish_stream_with_spec

    out = []
    for i, dim in enumerate(dims):
        if dim is None:
            if dense_fallback is None:
                raise ValueError("tile %d needs the dense path but no "
                                 "fallback was given" % i)
            out.append(dense_fallback(i))
            if on_tile is not None:
                on_tile(i, out[-1])
            continue
        w_, h_ = dim
        row = bufs[i]
        total = row_header_i32(row, 0)
        bits = row_header_i32(row, 1)
        if total > cap or bits > cap_words * 32:
            if dense_fallback is None:
                raise ValueError(
                    f"huffman wire overflow (entries={total}, bits={bits})")
            out.append(dense_fallback(i))
            if on_tile is not None:
                on_tile(i, out[-1])
            continue
        nwords = (bits + 31) // 32
        # Compacted rows can sit at unaligned offsets in the fetched
        # stream; ascontiguousarray re-bases so the u32 view is legal.
        words = np.ascontiguousarray(
            row[8:8 + 4 * nwords]).view("<u4")
        out.append(finish_fixed_stream(words, bits, w_, h_, quality)
                   if spec is None else
                   finish_stream_with_spec(words, bits, w_, h_,
                                           quality, spec))
        if on_tile is not None:
            on_tile(i, out[-1])
    return out


def dense_encoder():
    """The per-tile dense-coefficient entropy coder: native if available,
    else Python.  Returns ``encode(y, cb, cr, width, height, quality) ->
    bytes``."""
    from ..native import jpeg_native_available
    if jpeg_native_available():
        from ..native import jpeg_encode_native
        return jpeg_encode_native
    from ..jfif import encode_jfif
    return encode_jfif


def sparse_encoder():
    """The per-tile sparse entropy coder: native if available, else Python.

    Returns ``encode(buf, width, height, quality, cap) -> bytes``, raising
    ``native.SparseOverflowError`` when the buffer dropped entries.
    """
    from ..native import SparseOverflowError, jpeg_native_available
    if jpeg_native_available():
        from ..native import jpeg_encode_sparse_native
        return jpeg_encode_sparse_native

    from ..jfif import encode_jfif

    def _encode(buf, w, h, q, cap_):
        dense = sparse_to_dense(buf, h, w, cap_)
        if dense is None:
            raise SparseOverflowError(f"overflow (cap={cap_})")
        y, cb, cr = dense
        return encode_jfif(y, cb, cr, w, h, q)

    return _encode


def sparse_run_encoder():
    """The sparse entropy coder over a run of tiles: native (one call a
    run, the GIL given up once) if available, else Python.

    Returns ``encode(rows, dims, quality, cap) -> list``, one entry a
    row: its ``bytes``, or ``-2`` where the row dropped entries (the
    dense path must be taken) and ``-1`` where it is malformed, as
    ``native.jpeg_encode_sparse_run``.
    """
    from ..native import SparseOverflowError, jpeg_native_available
    if jpeg_native_available():
        from ..native import jpeg_encode_sparse_run
        return jpeg_encode_sparse_run

    _encode = sparse_encoder()

    def _encode_run(rows, dims, q, cap_):
        out = []
        for buf, (w, h) in zip(rows, dims):
            try:
                out.append(_encode(buf, w, h, q, cap_))
            except SparseOverflowError:
                out.append(-2)
        return out

    return _encode_run


def encode_sparse_buffers(bufs: np.ndarray, width: int, height: int,
                          quality: int, cap: int, executor=None,
                          dense_fallback=None) -> list:
    """Entropy-encode a batch of fetched sparse wire buffers to JFIF.

    ``bufs`` indexes per-row u8 buffers: the host u8[B, ...] array from
    :func:`render_to_jpeg_sparse`, or a list of per-row arrays (the
    compacted wire).  Tiles whose coefficient density overflowed ``cap``
    are re-encoded via ``dense_fallback(i) -> bytes`` when given (else
    ValueError propagates).
    """
    from ..native import SparseOverflowError
    _encode = sparse_encoder()

    def one(i):
        try:
            return _encode(bufs[i], width, height, quality, cap)
        except SparseOverflowError:
            if dense_fallback is None:
                raise
            return dense_fallback(i)

    if executor is None:
        return [one(i) for i in range(len(bufs))]
    return list(executor.map(one, range(len(bufs))))


_HUFF_FETCHERS: dict = {}


def huffman_wire_fetcher(H: int, W: int, cap: int,
                         cap_words: int) -> "HuffmanWireFetcher":
    key = (H, W, cap, cap_words)
    with _FETCHERS_LOCK:
        f = _HUFF_FETCHERS.get(key)
        if f is None:
            f = _HUFF_FETCHERS[key] = HuffmanWireFetcher(H, W, cap,
                                                         cap_words)
        return f


class GroupWire(NamedTuple):
    """A group's wire rows in host memory, with what the host half of
    :func:`render_batch_to_jpeg` needs to frame them: the engine that
    coded them (a ``huffman`` group with a bucket-padded tile comes back
    ``sparse``), the caps the rows were packed under (after the one-shot
    widening), the tuned tables' frame spec (``huffman``; None = the
    fixed profile) and ``dense_coefficients(i) -> (y, cb, cr)``, the
    one-tile program a tile that overflowed twice falls back to."""
    engine: str
    rows: list
    cap: int
    cap_words: int
    frame_spec: object
    dims: list
    H: int
    W: int
    quality: int
    dense_coefficients: object


def render_batch_to_wire(raw, window_start, window_end, family,
                         coefficient, reverse, cd_start, cd_end, tables,
                         quality: int, dims, cap: int | None = None,
                         engine: str = "sparse",
                         timings: dict = None) -> GroupWire:
    """The device half of :func:`render_batch_to_jpeg` (its arguments):
    one batched dispatch and the fetch of its wire rows, and a second of
    both where a tile overflowed its cap.  When it returns the device
    has nothing left to do for the group, which is where the batcher
    lets go of its device lane."""
    B, C, H, W = raw.shape

    from ..io.staging import device_id

    def dispatched(span, out) -> None:
        if timings is not None:
            timings["device_ms"] = timings.get("device_ms", 0.0) + span.ms
            # Where the group ran, read off its output: a fleet
            # member's pin as it held (``BatchingRenderer._count_batch``).
            timings["device"] = device_id(out)

    if cap is None:
        cap = default_sparse_cap(H, W, quality)
    qy, qc = (np.asarray(t, np.int32) for t in quant_tables(quality))

    def dense_coefficients(i):
        y, cb, cr = render_to_jpeg_coefficients(
            raw[i:i + 1],
            *(a[i:i + 1] if getattr(a, "ndim", 0) else a
              for a in (window_start, window_end, family, coefficient,
                        reverse)),
            cd_start, cd_end,
            tables[i:i + 1], qy, qc)
        return np.asarray(y)[0], np.asarray(cb)[0], np.asarray(cr)[0]

    n = len(dims)
    all_exact = all((h_ + 15) // 16 * 16 == H
                    and (w_ + 15) // 16 * 16 == W for (w_, h_) in dims)
    if engine == "huffman" and all_exact:
        # Tuned per-workload tables when ready (fixed profile until
        # then, and forever if tuning failed); the host half must
        # declare whichever tables coded the stream.
        tuned = _TUNED_TABLES.get((H, W, quality))
        if tuned is not None:
            spec_arrays, frame_spec = tuned
        else:
            spec_arrays, frame_spec = huffman_spec_arrays(), None

        def dispatch_huffman(c, cw):
            fetcher = compact_fetcher("huffman", H, W, c, cw, B)
            with stopwatch("device.dispatch") as span:
                bufs = render_to_jpeg_huffman_compact(
                    raw, window_start, window_end, family, coefficient,
                    reverse, cd_start, cd_end, tables, qy, qc,
                    *spec_arrays, np.int32(n),
                    h16=H // 16, w16=W // 16, cap=c, cap_words=cw)
                handle = fetcher.start(bufs)
            dispatched(span, bufs)
            return fetcher.finish(handle, n, timings)[:n]

        cap_words = default_words_cap(H, W, quality)
        memo_key = ("huffman", H, W, quality)
        if _CAP_MEMO.get(memo_key):
            cap, cap_words = cap * 2, cap_words * 2
        rows = dispatch_huffman(cap, cap_words)
        totals = np.array([row_header_i32(r, 0) for r in rows])
        bits = np.array([row_header_i32(r, 1) for r in rows])
        over = (totals > cap) | (bits > cap_words * 32)
        rescuable = ((totals <= 2 * cap)
                     & (bits <= 2 * cap_words * 32))
        if memo_key not in _CAP_MEMO and (over & rescuable).any():
            # Cap overflow (dense content, narrow windows): ONE retry of
            # the whole batch at doubled caps instead of per-tile dense
            # re-renders, whose full-coefficient fetches (~6 MB/tile)
            # can cost seconds each on a congested link.  Skipped when
            # every overflowing tile exceeds even the doubled caps (the
            # retry could rescue nothing).  First retry per (shape,
            # quality) compiles the 2x variant — a one-time stall the
            # memo (and the persistent compilation cache) then avoids by
            # starting such workloads at 2x.
            _CAP_MEMO[memo_key] = True
            cap, cap_words = cap * 2, cap_words * 2
            rows = dispatch_huffman(cap, cap_words)
        return GroupWire("huffman", rows, cap, cap_words, frame_spec,
                         dims, H, W, quality, dense_coefficients)

    def dispatch_sparse(c):
        fetcher = compact_fetcher("sparse", H, W, c, 0, B)
        with stopwatch("device.dispatch") as span:
            bufs = render_to_jpeg_sparse_compact(
                raw, window_start, window_end, family, coefficient,
                reverse, cd_start, cd_end, tables, qy, qc, np.int32(n),
                cap=c)
            handle = fetcher.start(bufs)
        dispatched(span, bufs)
        return fetcher.finish(handle, n, timings)[:n]

    memo_key = ("sparse", H, W, quality)
    if _CAP_MEMO.get(memo_key):
        cap = cap * 2
    rows = dispatch_sparse(cap)
    totals = np.array([row_header_i32(r, 0) for r in rows])
    if (memo_key not in _CAP_MEMO
            and ((totals > cap) & (totals <= 2 * cap)).any()):
        # Same one-shot widening + memo as the huffman engine above.
        _CAP_MEMO[memo_key] = True
        cap = cap * 2
        rows = dispatch_sparse(cap)
    return GroupWire("sparse", rows, cap, 0, None, dims, H, W, quality,
                     dense_coefficients)


def finish_wire_to_jpegs(wire: GroupWire, tune: bool = True,
                         on_tile=None) -> list:
    """The host half of :func:`render_batch_to_jpeg`: the fetched rows
    of :func:`render_batch_to_wire` -> JFIF per tile
    (``jfif.encodeBatch``).  Nothing here runs on the device but the
    rare ``dense_coefficients(i)`` of a tile that overflowed twice."""
    rows, cap, dims, H, W, quality = (wire.rows, wire.cap, wire.dims,
                                      wire.H, wire.W, wire.quality)
    if wire.engine == "huffman":
        _dense_encode = dense_encoder()

        def dense_tile(i):
            # Still overflowing at 2x: re-encode from dense coefficients.
            w_, h_ = dims[i]
            return _dense_encode(*wire.dense_coefficients(i), w_, h_,
                                 quality)

        if wire.frame_spec is None and tune:
            # One-time background tuning from this workload's first
            # group (a single dense-coefficient sample).  ``tune=False``
            # callers (prewarm's all-zero compile probes) must never
            # seed the tables real traffic will be served with.
            _maybe_start_tuning((H, W, quality), wire.dense_coefficients)
        with stopwatch("jfif.encodeBatch"):
            return finish_huffman_batch(
                rows, dims, H, W, quality, cap, wire.cap_words,
                dense_fallback=dense_tile, spec=wire.frame_spec,
                on_tile=on_tile)
    with stopwatch("jfif.encodeBatch"):
        return finish_sparse_to_jpegs(rows, dims, H, W, quality, cap,
                                      wire.dense_coefficients,
                                      on_tile=on_tile)


def render_batch_to_jpeg(raw, window_start, window_end, family, coefficient,
                         reverse, cd_start, cd_end, tables, quality: int,
                         dims, cap: int | None = None,
                         engine: str = "sparse",
                         tune: bool = True, on_tile=None,
                         timings: dict = None) -> list:
    """Serving-path helper: one batched device dispatch -> JFIF per tile.

    ``raw`` is [B, C, H, W] with H, W multiples of 16 (callers edge-pad;
    render is pointwise so padding commutes with it) and per-tile settings
    stacked along B as in :func:`render_to_jpeg_sparse`.  ``dims`` gives
    each tile's true ``(width, height)`` written into its SOF0 header —
    the decoder crops the MCU padding away.  A tile whose own ceil-16
    grid is smaller than (H, W) (spatial bucketing bounding the compile
    set) is entropy-coded from the top-left block subgrid on the host.
    Overflowing tiles re-run through the dense coefficient path.

    ``engine`` selects the device wire format: ``"sparse"`` (18-bit
    coefficient entries + host entropy coding — wins on fast links) or
    ``"huffman"`` (device fixed-table Huffman, ~3x fewer wire bytes —
    wins on slow/congested links).  The packed Huffman stream covers the
    full (H, W) grid, so a group containing bucket-padded tiles (true
    grid smaller than (H, W)) falls back to the sparse engine as a
    whole — one dispatch either way, never per-tile re-renders.

    ``on_tile(i, jpeg_bytes)`` (optional) fires the moment tile ``i``'s
    encode slice lands — the batcher's first-tile-out settlement hook:
    tile 0's waiter can be answered while tile N-1 is still entropy
    coding, instead of every waiter parking behind the batch tail.  The
    bytes passed are EXACTLY the returned list's entry (byte-identity is
    the streaming contract); callback exceptions are the caller's.

    ``timings`` (optional dict): ``device_ms`` gains the milliseconds of
    the spans ``device.dispatch`` (the jitted call until it returns,
    and the wire fetcher's slice after it: Python dispatch, argument
    upload, a trace or compile that falls into them) and
    ``device.wait`` (the program running to its end), for the batcher's
    cost ledger.

    It is its two halves called in turn: :func:`render_batch_to_wire`
    (everything the device does) and :func:`finish_wire_to_jpegs` (the
    host's entropy coding).  The batcher calls them apart, with a device
    lane around the first only.
    """
    return finish_wire_to_jpegs(
        render_batch_to_wire(
            raw, window_start, window_end, family, coefficient, reverse,
            cd_start, cd_end, tables, quality, dims, cap=cap,
            engine=engine, timings=timings),
        tune=tune, on_tile=on_tile)


def finish_sparse_to_jpegs(bufs, dims, H: int, W: int, quality: int,
                           cap: int, dense_coefficients,
                           on_tile=None) -> list:
    """Host tail of the sparse serving path: fetched wire rows -> JFIF.

    ``dims`` gives each tile's true ``(width, height)``; tiles whose own
    ceil-16 grid is smaller than the bucketed (H, W) are entropy-coded
    from the top-left block subgrid, and tiles that overflowed ``cap``
    re-render through ``dense_coefficients(i) -> (y, cb, cr)``.

    The tiles are coded in runs, side by side: by this thread and by
    the process's coding threads (``utils.entropypool``), a tail of one
    run by this thread alone.  ``on_tile(i, bytes)`` fires once a tile,
    the moment its run has returned, FROM THE THREAD THAT CODED IT and
    in no promised order.  A tile that needs the dense path takes it on
    the thread that found it.  The first exception of any tile is raised
    from here once every thread has stopped; tiles not yet coded then
    stay uncoded and fire nothing.
    """
    from ..utils import entropypool

    _encode_run = sparse_run_encoder()
    _dense_encode = dense_encoder()
    n = len(dims)
    exact = [(h_ + 15) // 16 * 16 == H and (w_ + 15) // 16 * 16 == W
             for (w_, h_) in dims]
    out = [None] * n

    def dense_tile(i: int) -> bytes:
        w_, h_ = dims[i]
        dense = None if exact[i] else sparse_to_dense(bufs[i], H, W, cap)
        if dense is None:                   # the row overflowed its cap
            dense = dense_coefficients(i)
        y, cb, cr = dense if exact[i] else \
            slice_block_subgrid(*dense, H, W, w_, h_)
        return _dense_encode(y, cb, cr, w_, h_, quality)

    def code_run(run: range) -> None:
        rows = [i for i in run if exact[i]]
        coded = dict(zip(rows, _encode_run(
            [bufs[i] for i in rows], [dims[i] for i in rows],
            quality, cap)))
        for i in run:
            body = coded.get(i, -2)
            if body == -2:
                body = dense_tile(i)
            elif body == -1:
                raise ValueError("jpeg_encode_sparse: invalid arguments")
            out[i] = body
            if on_tile is not None:
                on_tile(i, body)

    entropypool.pool().code(n, H * W, code_run)
    return out


def pad_to_mcu(rgba: np.ndarray) -> np.ndarray:
    """Edge-replicate u8[H, W, ...] so H and W are multiples of 16."""
    H, W = rgba.shape[:2]
    ph, pw = (-H) % 16, (-W) % 16
    if ph == 0 and pw == 0:
        return rgba
    pad = [(0, ph), (0, pw)] + [(0, 0)] * (rgba.ndim - 2)
    return np.pad(rgba, pad, mode="edge")


def pad_planes_to_mcu(raw, target_h: int | None = None,
                      target_w: int | None = None):
    """Edge-replicate [C, h, w] planes to a 16-aligned grid.

    Render is pointwise, so padding raw and rendering equals rendering and
    edge-replicating the image; replication (not zeros) keeps the padding
    out of the edge blocks' DCT energy.  ``target_h``/``target_w`` pad to
    a larger (bucketed) grid; default is the tile's own ceil-16 grid.
    Device-resident input (the HBM raw-tile cache) pads on device.
    """
    h, w = raw.shape[-2:]
    th = target_h if target_h is not None else h + (-h) % 16
    tw = target_w if target_w is not None else w + (-w) % 16
    if th % 16 or tw % 16 or th < h or tw < w:
        raise ValueError(f"bad MCU pad target ({th}, {tw}) for ({h}, {w})")
    if (th, tw) == (h, w):
        return raw
    xp = np if isinstance(raw, np.ndarray) else jnp
    return xp.pad(raw, ((0, 0), (0, th - h), (0, tw - w)), mode="edge")


def slice_block_subgrid(y, cb, cr, grid_h: int, grid_w: int,
                        width: int, height: int):
    """Take the top-left ceil-16 subgrid of dense coefficient blocks.

    The wire buffer may cover a bucketed (grid_h, grid_w) frame larger
    than the tile; baseline JPEG decodes exactly ceil(h/16) x ceil(w/16)
    MCUs from the SOF0 dims, so the surplus blocks must be dropped before
    entropy coding.
    """
    gh16, gw16 = grid_h // 16, grid_w // 16
    th16, tw16 = (height + 15) // 16, (width + 15) // 16
    y = y.reshape(gh16 * 2, gw16 * 2, 64)[:th16 * 2, :tw16 * 2]
    cb = cb.reshape(gh16, gw16, 64)[:th16, :tw16]
    cr = cr.reshape(gh16, gw16, 64)[:th16, :tw16]
    return (np.ascontiguousarray(y).reshape(-1, 64),
            np.ascontiguousarray(cb).reshape(-1, 64),
            np.ascontiguousarray(cr).reshape(-1, 64))
