"""Packed host->device staging for raw uint16 pixel data.

The cold first-touch path moves 8 MB per raw 16-bit WSI tile from host
to HBM.  Pixel
content is smooth signal + sensor noise, so block bit-packed zigzag row
deltas (``native/wirepack.cpp``) carry the same planes in ~1.4x fewer
bytes — and, unlike general entropy coding, the fixed-width-per-block
layout decodes VECTORIZED on the device: a gather + shift per sample
and one row cumsum, no sequential bitstream walk (which a TPU cannot
express).  This is the H2D mirror of the D2H JPEG wire: ship transforms
of the pixels sized to the link, compute the inverse where the data
lands.

``stage(arr)`` is the drop-in for ``jax.device_put`` on storage-dtype
raw planes: it packs when the packer is available and the content
actually compresses, and falls back to a plain transfer otherwise
(including non-uint16 dtypes).  Whether the saved bytes pay for the
on-device decode on the current chip's host link is not measured
(ROADMAP S4).

Reference context: the reference's Bio-Formats path materializes raw
planes host-side and hands byte[] buffers to the renderer in-process
(``ImageRegionRequestHandler.java:302-309,559``); it never pays a
device link, so this stage has no Java counterpart.
"""

from __future__ import annotations

import contextlib
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

# Words arrays pad up to one of these lengths so the unpack kernel
# compiles once per (shape, padded-length) instead of once per
# data-dependent length (each distinct shape costs an XLA compile).
# Ratio 2^(1/4) = <=19% padding.
_LADDER_RATIO = 2.0 ** 0.25
_LADDER_FLOOR = 4096          # words


def _pad_words(n: int) -> int:
    if n <= _LADDER_FLOOR:
        return _LADDER_FLOOR
    steps = math.ceil(math.log(n / _LADDER_FLOOR, _LADDER_RATIO))
    return int(math.ceil(_LADDER_FLOOR * _LADDER_RATIO ** steps))


@functools.partial(jax.jit, static_argnames=("shape",))
@jax.named_scope("stage.unpack16")   # utils.profile_summary.STAGES
def unpack16_device(words, widths, shape) -> jax.Array:
    """Inverse of ``native.wirepack_pack16`` on device.

    ``words`` u32[>=n_words] (zero-padded), ``widths``
    u8[n_rows * ceil(W/32)], ``shape`` the original array shape.
    Fully vectorized: per-sample gather + shifts, then a per-row
    cumsum undoes the delta coding.
    """
    W = shape[-1]
    n_rows = 1
    for s in shape[:-1]:
        n_rows *= s
    bpr = (W + 31) // 32
    w32 = widths.astype(jnp.int32)                      # [n_rows*bpr]
    block_bits = w32 * 32
    off = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(block_bits)])[:-1]
    col = jnp.broadcast_to(jnp.arange(W, dtype=jnp.int32), (n_rows, W))
    b = (jnp.arange(n_rows, dtype=jnp.int32)[:, None] * bpr
         + col // 32)                                   # [n_rows, W]
    j = col % 32
    w = w32[b]
    pos = off[b] + j * w
    wi = pos >> 5
    sh = (pos & 31).astype(jnp.uint32)
    words = words.astype(jnp.uint32)
    lo = words[wi] >> sh
    hi_shift = (jnp.uint32(32) - sh) & jnp.uint32(31)
    hi = jnp.where(sh > 0,
                   words[jnp.minimum(wi + 1, words.shape[0] - 1)]
                   << hi_shift,
                   jnp.uint32(0))
    mask = (jnp.uint32(1) << w.astype(jnp.uint32)) - jnp.uint32(1)
    z = ((lo | hi) & mask).astype(jnp.int32)
    d = (z >> 1) ^ -(z & 1)                             # un-zigzag
    x = jnp.cumsum(d, axis=1)                           # undo row delta
    return x.astype(jnp.uint16).reshape(shape)


def pack16_host(arr: np.ndarray):
    """Host-side packing via the native packer; raises ImportError when
    the toolchain is unavailable (callers fall back to raw staging)."""
    from ..native import wirepack_pack16
    return wirepack_pack16(arr)


# Skip packing below this size: dispatch + decode overhead beats the
# saved bytes on small transfers.
_MIN_STAGE_BYTES = 1 << 20
# Bit offsets are computed with int32 arithmetic on device (TPUs run
# x32); past this many samples the packed bit count could exceed 2^31
# and silently wrap, so bigger arrays take the plain transfer.
_MAX_STAGE_SAMPLES = (1 << 31) // 18


def _regular_shape(shape) -> bool:
    """Shapes worth compiling an unpack executable for.

    ``unpack16_device`` is shape-jitted and a novel shape costs a
    seconds-scale compile — far more than the
    packed bytes save once.  Serving traffic is dominated by bucketed
    tiles and tile-snapped bands, so packing is restricted to that
    lattice (rows % 64 == 0, width % 256 == 0); arbitrary client
    region shapes fall back to the un-compiled plain transfer.
    """
    h, w = shape[-2], shape[-1]
    lead = 1
    for s in shape[:-2]:
        lead *= s
    return h % 64 == 0 and w % 256 == 0 and lead <= 64


@contextlib.contextmanager
def pin_scope(device):
    """Run the enclosed dispatches on ``device`` — per-member device
    pinning for the combined federated role (``parallel.federation``
    partitions ``jax.local_devices()`` across a host's members, so
    each member's staging and render executes on ITS device set).
    ``None`` yields straight through: the process default device, the
    pre-federation behavior, at zero cost."""
    if device is None:
        yield
        return
    import jax
    with jax.default_device(device):
        yield


def stage(arr: np.ndarray, min_ratio: float = 1.1):
    """Packed ``device_put`` for uint16 raw planes.

    Packs on host, ships words + widths, decodes on device; returns the
    device uint16 array.  Falls back to a plain ``device_put`` when the
    packer is unavailable, the dtype is not uint16, the array is small,
    huge (int32 bit-offset budget), off the regular tile/band shape
    lattice (compile economics), or the content does not compress by at
    least ``min_ratio`` (noise floors exist: packed-but-incompressible
    data would ship 17/16 of raw).
    """
    if (not isinstance(arr, np.ndarray) or arr.dtype != np.uint16
            or arr.nbytes < _MIN_STAGE_BYTES or arr.ndim < 2
            or arr.size > _MAX_STAGE_SAMPLES
            or not _regular_shape(arr.shape)):
        return jax.device_put(arr)
    try:
        words, widths = pack16_host(arr)
    except ImportError:
        return jax.device_put(arr)
    # Judge the bytes that actually cross the link: the words buffer
    # ships at its ladder-padded length (up to ~19% over), so a pack
    # accepted at ~0.91x raw could ship ~1.08x raw after padding.
    packed_bytes = _pad_words(len(words)) * 4 + widths.nbytes
    if packed_bytes * min_ratio > arr.nbytes:
        return jax.device_put(arr)
    padded = np.zeros(_pad_words(len(words)), np.uint32)
    padded[:len(words)] = words
    return unpack16_device(jax.device_put(padded),
                           jax.device_put(widths), arr.shape)


def stage_deduped(arr: np.ndarray, cache, digest: str = None):
    """Digest-first staging: skip the upload when the content is already
    device-resident.

    ``cache`` is an ``io.devicecache.DeviceRawCache`` with its digest
    index on.  Returns ``(device_array, digest, was_resident)``:
    ``was_resident`` True means zero bytes crossed the host->device link
    (the plane was found under some key — a prior wire push, or the same
    content staged for another region identity).  On a miss the plane
    stages through :func:`stage` (packed when it pays) and is recorded
    under its content key, so the NEXT identical push — from any
    frontend, for any region identity — skips the wire.

    This is the server half of the sidecar's digest-first plane
    protocol (``server.sidecar``: ``plane_probe`` then ``plane_put``
    only on miss), and the in-process staging skip for everything else.
    """
    from .devicecache import plane_digest, plane_key

    digest = digest or plane_digest(arr)
    resident = cache.get_by_digest(digest)
    if resident is not None:
        cache.count_plane(hit=True)
        from ..utils import telemetry
        telemetry.add_cost("staged_bytes_skipped", arr.nbytes)
        return resident, digest, True
    staged = cache.get_or_load(plane_key(digest), lambda: arr,
                               digest=digest)
    return staged, digest, False


def stage_ratio(arr: np.ndarray) -> float:
    """Diagnostic: packed/raw byte ratio for ``arr`` (1.0 = raw)."""
    words, widths = pack16_host(arr)
    return (words.nbytes + widths.nbytes) / arr.nbytes
