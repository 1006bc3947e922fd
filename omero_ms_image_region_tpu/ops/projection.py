"""Z-stack intensity projection.

TPU-native replacement for ``ProjectionService.java`` (reference: CPU
per-pixel loops at ``:176-199`` (max) and ``:259-291`` (mean/sum)).  Instead
of slicing the stack per request (which would recompile per Z-range), the
kernel always reduces over the full Z axis with a dynamic 0/1 weight vector
derived from (start, end, stepping) — one compiled executable per stack
shape, Z-range fully dynamic.

Reference semantics preserved exactly, including its quirks:
  * max:  z runs ``start..end`` INCLUSIVE (``:184``), and the accumulator
          starts at 0 (``:183``) — an all-negative column projects to 0.
  * mean/sum: z runs ``start..end`` EXCLUSIVE of end (``:271``), result is
          clamped above by the pixel type's max (``:280-282``), never below.
  * mean divides by the number of planes actually used (``:277-279``).

Bounds validation mirrors ``projectStack`` (``ProjectionService.java:52-64``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..models.rendering import Projection


def check_projection_bounds(start: int, end: int, stepping: int,
                            channel: int, timepoint: int,
                            size_z: int, size_c: int, size_t: int) -> None:
    """Host-side validation (= zIntervalBoundsCheck / outOfBounds* checks)."""
    if start < 0 or end < 0:
        raise ValueError("Z interval value cannot be negative.")
    if start >= size_z or end >= size_z:
        raise ValueError(f"Z interval value cannot be >= {size_z}")
    if stepping is not None and stepping <= 0:
        raise ValueError(f"stepping: {stepping} <= 0")
    if channel is not None:
        if channel < 0:
            raise ValueError(f"channel: {channel} < 0")
        if channel >= size_c:
            raise ValueError(f"channel index must be <{size_c}")
    if timepoint is not None:
        if timepoint < 0:
            raise ValueError(f"timepoint: {timepoint} < 0")
        if timepoint >= size_t:
            raise ValueError(f"timepoint must be <{size_t}")


@functools.partial(jax.jit, static_argnames=("algorithm",))
def _project(stack, start, end, stepping, type_max, algorithm: int):
    Z = stack.shape[0]
    idx = jnp.arange(Z)
    on_step = ((idx - start) % stepping) == 0
    x = stack.astype(jnp.float32)

    if algorithm == Projection.MAXIMUM_INTENSITY:
        w = (idx >= start) & (idx <= end) & on_step          # inclusive end
        masked = jnp.where(w[:, None, None], x, -jnp.inf)
        # Accumulator starts at 0 in the reference (:183): clamp from below.
        return jnp.maximum(jnp.max(masked, axis=0), 0.0)

    # mean / sum: exclusive end (:271)
    w = ((idx >= start) & (idx < end) & on_step).astype(jnp.float32)
    total = jnp.sum(x * w[:, None, None], axis=0)
    if algorithm == Projection.MEAN_INTENSITY:
        count = jnp.maximum(jnp.sum(w), 1.0)
        total = total / count
    # Clamp to the destination type maximum (:280-282); no lower clamp.
    return jnp.minimum(total, type_max)


@jax.jit
def _fold_max(acc, plane):
    return jnp.maximum(acc, plane.astype(jnp.float32))


@jax.jit
def _fold_sum(acc, plane):
    return acc + plane.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("algorithm",))
def _finalize(acc, count, type_max, algorithm: int):
    if algorithm == Projection.MAXIMUM_INTENSITY:
        return jnp.maximum(acc, 0.0)     # 0-floor accumulator (:183)
    if algorithm == Projection.MEAN_INTENSITY:
        acc = acc / jnp.maximum(count, 1.0)
    return jnp.minimum(acc, type_max)    # type-max clamp (:280-282)


def _finalize_host(acc: np.ndarray, count: int, type_max: float,
                   algorithm) -> np.ndarray:
    """Numpy mirror of :func:`_finalize` (identical reference
    semantics: 0-floor max accumulator, mean divide, type-max clamp)."""
    if algorithm == Projection.MAXIMUM_INTENSITY:
        return np.maximum(acc, 0.0)
    if algorithm == Projection.MEAN_INTENSITY:
        acc = acc / max(float(count), 1.0)
    return np.minimum(acc, np.float32(type_max))


def _resolve_placement(placement: str, sample) -> str:
    """``auto`` folds where the data lives: a host-resident source
    (numpy reads) folds on host and ships ONE projected plane across
    the link — a projection is a reduction, so uploading Z planes to
    reduce them device-side pays Z plane transfers to save host work
    that is memory-bound anyway.  Device-resident sources keep the
    device fold (zero transfers either way).  Which side wins on the
    current chip is not measured (ROADMAP S5); callers can force
    ``device``."""
    if placement == "auto":
        return "host" if isinstance(sample, np.ndarray) else "device"
    if placement not in ("host", "device"):
        raise ValueError(f"unknown placement {placement!r}")
    return placement


def project_planes(get_plane, algorithm, size_z: int, start: int,
                   end: int, stepping: int = 1,
                   type_max: float = 255.0, shape=None,
                   placement: str = "auto"):
    """Stream a Z-projection plane by plane — WSI-scale memory bound.

    Where :func:`project_stack` needs the whole ``[Z, H, W]`` stack
    resident (matching ``PixelBuffer.getStack`` at
    ``ProjectionService.java:72``, which stalls and swaps on real WSI
    stacks), this reads ONLY the planes inside the Z window via
    ``get_plane(z) -> [H, W]`` and folds each into an accumulator:
    peak memory is one plane + the accumulator, independent of Z.

    ``placement`` picks where the fold runs (see
    :func:`_resolve_placement`: ``auto`` folds where the data lives, so
    host sources never upload the stack just to reduce it).

    Reference semantics are identical to :func:`project_stack`
    (inclusive max / exclusive mean-sum windows, stepping, 0-floor max
    accumulator, type-max clamp).

    Returns f32[H, W] on device.
    """
    algorithm, zs, inclusive = _validate_and_window(
        algorithm, size_z, start, end, stepping)
    acc = None
    first = get_plane(zs[0]) if zs else None
    if zs:
        placement = _resolve_placement(placement, first)
    if placement == "host" and zs:
        acc = np.asarray(first, np.float32)
        for z in zs[1:]:
            plane = np.asarray(get_plane(z), np.float32)
            acc = np.maximum(acc, plane) if inclusive else acc + plane
        return jnp.asarray(_finalize_host(acc, len(zs), type_max,
                                          algorithm))
    fold = _fold_max if inclusive else _fold_sum
    for i, z in enumerate(zs):
        plane = jnp.asarray(first if i == 0 else get_plane(z))
        acc = (plane.astype(jnp.float32) if acc is None
               else fold(acc, plane))
    if acc is None:
        # Empty mean/sum window (start == end): all-zero plane, the
        # full-stack kernel's result for a zero weight vector.  With
        # ``shape`` provided (the serving path knows the plane geometry)
        # no plane is read at all — a WSI-scale probe read just for its
        # shape would defeat the bounded-reads contract.
        if shape is None:
            shape = np.asarray(get_plane(start)).shape
        acc = jnp.zeros(shape, jnp.float32)
    return _finalize(acc, jnp.asarray(float(len(zs)), jnp.float32),
                     jnp.asarray(type_max, jnp.float32), int(algorithm))


@functools.partial(jax.jit, static_argnames=("alg",))
def _fold_chunk(acc, chunk, alg: int):
    """Fold a [zc, h, W] chunk into a [h, W] band accumulator in ONE
    dispatch (vs one dispatch per plane in the plain stream)."""
    x = chunk.astype(jnp.float32)
    if alg == Projection.MAXIMUM_INTENSITY:
        return jnp.maximum(acc, jnp.max(x, axis=0))
    return acc + jnp.sum(x, axis=0)


@jax.jit
def _stitch(out, band, y0):
    return jax.lax.dynamic_update_slice(out, band, (y0, 0))


def _validate_and_window(algorithm, size_z: int, start: int, end: int,
                         stepping: int):
    """Shared validation + Z-window derivation for the streaming
    projections (one copy of the reference's window semantics: max is
    end-INclusive, mean/sum end-EXclusive, ``ProjectionService.java
    :184,:271``).  Returns (algorithm, zs, inclusive)."""
    algorithm = Projection(algorithm)
    if algorithm not in (
        Projection.MAXIMUM_INTENSITY,
        Projection.MEAN_INTENSITY,
        Projection.SUM_INTENSITY,
    ):
        raise ValueError(f"Unknown algorithm: {algorithm}")
    if start < 0 or end < 0:
        raise ValueError("Z interval value cannot be negative.")
    if start >= size_z or end >= size_z:
        raise ValueError(f"Z interval value cannot be >= {size_z}")
    if stepping <= 0:
        raise ValueError(f"stepping: {stepping} <= 0")
    inclusive = algorithm == Projection.MAXIMUM_INTENSITY
    stop = end + 1 if inclusive else end
    zs = [z for z in range(start, stop) if (z - start) % stepping == 0]
    return algorithm, zs, inclusive


def project_region_banded(get_band, algorithm, size_z: int, start: int,
                          end: int, stepping: int = 1,
                          type_max: float = 255.0, plane_shape=None,
                          band_rows: int = 256, z_chunk: int = 8,
                          get_chunk=None, placement: str = "auto"):
    """Spatially-banded streamed Z-projection — peak HOST footprint is
    chunk-sized, not plane-sized.

    :func:`project_planes` bounds memory in Z but still reads (and
    uploads) FULL planes; at real WSI scale (80k x 80k u16 => 12.8 GB
    per host plane) that breaks the host long before the device.  Here
    the plane is processed in horizontal bands of ``band_rows`` rows:
    ``get_band(z, y0, h) -> [h, W]`` reads only a band, ``z_chunk``
    bands stack into one device fold dispatch, and finished band
    accumulators stitch into the output plane on device.  Peak host
    memory is one ``[z_chunk, band_rows, W]`` chunk.  Peak DEVICE
    memory is still the f32 output plane (plus one band accumulator
    and one chunk) — the projected plane feeds the render, which needs
    it whole, so the largest projectable plane is bounded by HBM
    exactly as any renderable plane is (the reference materializes
    full byte[] planes at the same point, ``ProjectionService.java
    :72``).

    The last band is aligned to ``H - band_rows`` (fixed shapes keep
    one compiled executable); its overlap rows recompute identical
    values, so the stitch is idempotent.  Reference semantics match
    :func:`project_stack` exactly (inclusive max / exclusive mean-sum
    windows, stepping, 0-floor max accumulator, type-max clamp —
    ``ProjectionService.java:176-291``).

    ``placement`` picks where the folds run (``auto`` = where the data
    lives, :func:`_resolve_placement`): a host source folds each band
    in numpy and only the finished [H, W] plane crosses the link.

    Returns f32[H, W] on device.
    """
    algorithm, zs, inclusive = _validate_and_window(
        algorithm, size_z, start, end, stepping)
    if plane_shape is None:
        raise ValueError("plane_shape is required")
    H, W = plane_shape
    band_h = min(band_rows, H)
    alg = int(algorithm)

    # Auto-placement probes are REUSED as the first loop read, so auto
    # costs no extra I/O: the band probe is (band 0, z0); the chunk
    # probe reads the full first [z_chunk, band, W] block.
    probe = probe_chunk = None
    first_chunk_zs = tuple(zs[:z_chunk])
    if zs and placement == "auto":
        if get_chunk is not None:
            sample = probe_chunk = get_chunk(list(first_chunk_zs), 0,
                                             band_h)
        else:
            sample = probe = get_band(zs[0], 0, band_h)
        placement = _resolve_placement(placement, sample)

    def read_band(z, y0, h):
        nonlocal probe
        if probe is not None and z == zs[0] and y0 == 0:
            band, probe = probe, None
            return band
        return get_band(z, y0, h)

    def read_chunk(chunk_zs, y0, h):
        nonlocal probe_chunk
        if (probe_chunk is not None and y0 == 0
                and tuple(chunk_zs) == first_chunk_zs):
            chunk, probe_chunk = probe_chunk, None
            return chunk
        return get_chunk(chunk_zs, y0, h)

    if placement == "host" and zs:
        out = np.zeros((H, W), np.float32)
        for bi in range(-(-H // band_h)):
            y0 = min(bi * band_h, H - band_h)
            acc = (np.full((band_h, W), -np.inf, np.float32)
                   if inclusive else np.zeros((band_h, W), np.float32))
            for ci in range(0, len(zs), z_chunk):
                chunk_zs = zs[ci:ci + z_chunk]
                if get_chunk is not None:
                    chunk = np.asarray(
                        read_chunk(chunk_zs, y0, band_h), np.float32)
                else:
                    chunk = np.stack([
                        np.asarray(read_band(z, y0, band_h), np.float32)
                        for z in chunk_zs])
                if inclusive:
                    acc = np.maximum(acc, chunk.max(axis=0))
                else:
                    acc += chunk.sum(axis=0)
            out[y0:y0 + band_h] = acc
        return jnp.asarray(_finalize_host(out, len(zs), type_max,
                                          algorithm))

    out = jnp.zeros((H, W), jnp.float32)
    n_bands = -(-H // band_h)
    for bi in range(n_bands):
        y0 = min(bi * band_h, H - band_h)
        if not zs:
            # Empty mean/sum window: the zero output plane stands.
            break
        acc = (jnp.full((band_h, W), -jnp.inf, jnp.float32) if inclusive
               else jnp.zeros((band_h, W), jnp.float32))
        for ci in range(0, len(zs), z_chunk):
            chunk_zs = zs[ci:ci + z_chunk]
            if get_chunk is not None:
                # Sources that can serve a [z, band, W] block in one
                # read (device-resident stacks especially: per-plane
                # slicing costs a dispatch each).
                chunk = get_chunk(chunk_zs, y0, band_h)
                if len(chunk_zs) < z_chunk:
                    xp = np if isinstance(chunk, np.ndarray) else jnp
                    pad = (chunk[:1] if inclusive
                           else xp.zeros_like(chunk[:1]))
                    chunk = xp.concatenate(
                        [chunk] + [pad] * (z_chunk - len(chunk_zs)))
            else:
                bands = [read_band(z, y0, band_h) for z in chunk_zs]
                if len(bands) < z_chunk:
                    # Fixed chunk shape = one compiled fold.  Max pads
                    # by repeating a real band (idempotent); sum pads
                    # zeros.
                    pad = (bands[0] if inclusive
                           else np.zeros_like(np.asarray(bands[0])))
                    bands = bands + [pad] * (z_chunk - len(bands))
                xp = jnp if any(not isinstance(b, np.ndarray)
                                for b in bands) else np
                chunk = xp.stack(bands)
            acc = _fold_chunk(acc, chunk, alg)
        out = _stitch(out, acc, jnp.asarray(y0, jnp.int32))
    return _finalize(out, jnp.asarray(float(len(zs)), jnp.float32),
                     jnp.asarray(type_max, jnp.float32), alg)


def project_stack(stack, algorithm, start: int, end: int,
                  stepping: int = 1, type_max: float = 255.0):
    """Project a Z-stack.

    Args:
      stack:     f32[Z, H, W] one channel/timepoint stack
                 (= PixelBuffer.getStack slice, ``ProjectionService.java:72``).
      algorithm: models.rendering.Projection
      start/end: Z interval (see module docstring for in/exclusivity).
      stepping:  use every ``stepping``-th section (``:166-170``).
      type_max:  pixel type maximum for the mean/sum clamp.

    Returns:
      f32[H, W] projected plane.
    """
    algorithm = Projection(algorithm)
    if algorithm not in (
        Projection.MAXIMUM_INTENSITY,
        Projection.MEAN_INTENSITY,
        Projection.SUM_INTENSITY,
    ):
        raise ValueError(f"Unknown algorithm: {algorithm}")
    # Z-interval validation (= zIntervalBoundsCheck at the projectStack
    # entry, ProjectionService.java:52-54); channel/timepoint bounds are the
    # caller's (check_projection_bounds) since only it knows those sizes.
    if start < 0 or end < 0:
        raise ValueError("Z interval value cannot be negative.")
    if start >= stack.shape[0] or end >= stack.shape[0]:
        raise ValueError(f"Z interval value cannot be >= {stack.shape[0]}")
    if stepping <= 0:
        raise ValueError(f"stepping: {stepping} <= 0")
    return _project(
        stack,
        jnp.asarray(start, jnp.int32),
        jnp.asarray(end, jnp.int32),
        jnp.asarray(stepping, jnp.int32),
        jnp.asarray(type_max, jnp.float32),
        int(algorithm),
    )
