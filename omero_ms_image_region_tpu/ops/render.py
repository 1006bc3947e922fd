"""The fused tile render kernel.

TPU-native replacement for ``omeis.providers.re.Renderer.renderAsPackedInt``
(reference call site ``ImageRegionRequestHandler.java:559``) and the settings
application in ``updateSettings`` (``:689-741``).

Design (deliberately different from the reference's per-pixel Java pipeline):
the entire post-quantization chain — codomain maps (reverse intensity), LUT
vs RGBA color, alpha weighting, greyscale-vs-rgb model, channel activity — is
folded on the host into one ``(C, 256, 3)`` float32 table per render
(:func:`build_channel_tables`).  The device kernel is then just

    quantize (window + family curve)  ->  per-channel table gather
    ->  additive composite (sum over C)  ->  clip  ->  u8 RGBA

which XLA fuses into a single pass over HBM, and which is identical work for
every (C, H, W) shape — so one compiled executable serves every request of a
given tile bucket, and ``vmap`` batches concurrent requests for free.

Semantics preserved from the reference renderer:
  * quantum over codomain [cd_start, cd_end], default [0,255]
    (``ImageRegionRequestHandler.java:273-276``)
  * reverse-intensity codomain op q -> cd_start + cd_end - q, applied to the
    quantized value before color mapping (``:717-730``)
  * LUT color = table gather; RGBA color = linear ramp * color * alpha
    (``:705-715``)
  * greyscale model renders only the first active channel as grey
    (Renderer.MODEL_GREYSCALE; ``:735-740``)
  * rgb model composites active channels additively with clamp
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..models.rendering import RenderingDef, RenderingModel
from .quantum import quantize


def build_channel_tables(
    rdef: RenderingDef, lut_provider=None
) -> np.ndarray:
    """Fold color/LUT/alpha/model/codomain chain into (C, 256, 3) tables.

    Row semantics: ``rgb_contribution = table[channel][quantized_value]``.
    Inactive channels are all-zero rows, so the composite sum can run over
    every channel unconditionally (no ragged/active-set shapes on device).
    """
    C = len(rdef.channel_bindings)
    tables = np.zeros((C, 256, 3), dtype=np.float32)
    ramp = np.arange(256, dtype=np.float32)

    greyscale = rdef.model == RenderingModel.GREYSCALE
    first_active = next(
        (i for i, cb in enumerate(rdef.channel_bindings) if cb.active), None
    )

    for c, cb in enumerate(rdef.channel_bindings):
        if not cb.active:
            continue
        if greyscale:
            if c != first_active:
                continue
            # Grey ramp: quantized value becomes the grey level directly.
            table = np.stack([ramp, ramp, ramp], axis=-1)
        else:
            lut_table = None
            if cb.lut is not None and lut_provider is not None:
                lut_table = lut_provider.get(cb.lut)
            if lut_table is not None:
                table = lut_table.astype(np.float32) * (cb.alpha / 255.0)
            else:
                color = np.array(
                    [cb.red, cb.green, cb.blue], dtype=np.float32
                )
                table = (ramp[:, None] / 255.0) * color[None, :] * (
                    cb.alpha / 255.0
                )
        tables[c] = table
    return tables


def build_ramp_weights(rdef: RenderingDef, lut_provider=None):
    """Fold the color chain into per-channel linear weights, if possible.

    Every non-LUT channel's (C, 256, 3) table is a ramp — ``table[q] =
    q * w`` with ``w = color * alpha / 255**2`` (grey model: ``w = 1``) —
    so the composite collapses to one multiply-add contraction over
    channels, with no per-pixel table gather at all.  TPU has no per-lane
    gather; the measured gap on a 8x4x1024^2 batch is ~9x (0.89 s table
    gathers vs 0.10 s arithmetic).  Returns f32[C, 3] weights, or None
    when any active channel resolves an actual LUT file (the gather path
    must run; :func:`build_channel_tables`).
    """
    C = len(rdef.channel_bindings)
    w = np.zeros((C, 3), dtype=np.float32)
    greyscale = rdef.model == RenderingModel.GREYSCALE
    first_active = next(
        (i for i, cb in enumerate(rdef.channel_bindings) if cb.active), None
    )
    for c, cb in enumerate(rdef.channel_bindings):
        if not cb.active:
            continue
        if greyscale:
            if c == first_active:
                w[c] = 1.0
            continue
        if (cb.lut is not None and lut_provider is not None
                and lut_provider.get(cb.lut) is not None):
            return None
        color = np.array([cb.red, cb.green, cb.blue], dtype=np.float32)
        w[c] = (color / 255.0) * (cb.alpha / 255.0)
    return w


def composite_ramp_packed(q, weights):
    """Arithmetic composite for ramp-only renders (no table gather).

    ``q`` [..., C, H, W] quantized values, ``weights`` [..., C, 3] from
    :func:`build_ramp_weights` sharing the same leading dims.  Same packed
    u32 output as :func:`composite_packed`.
    """
    qf = q.astype(jnp.float32)
    out = []
    for comp in range(3):
        v = jnp.einsum("...chw,...c->...hw", qf, weights[..., comp])
        v = jnp.clip(jnp.round(v), 0.0, 255.0).astype(jnp.uint32)
        out.append(v)
    r, g, b = out
    return r | (g << 8) | (b << 16) | jnp.uint32(0xFF000000)


def composite_packed(q, tables):
    """Table lookup + additive composite + ABGR pack, TPU-layout-native.

    ``q`` [..., C, H, W] quantized values, ``tables`` [..., C, 256, 3]
    folded color tables sharing the same leading dims.

    Two deliberate layout decisions (both forced by the TPU memory tiling,
    where the minor-most dim is padded to 128 lanes):

      * The lookup runs as three flat shared-operand gathers — one per color
        component — over a ``[prod(lead)*256]`` vector, with each plane's
        indices offset into its own 256-entry block.  A vmapped per-plane
        ``table[q]`` becomes a batched gather that XLA expands into a
        one-hot contraction (OOM), and any big ``[..., 3]`` intermediate
        pads 3 -> 128 lanes (observed: 42.7x HBM expansion, 20 GB for an
        8x4x1024x1024 batch).

      * The result is the reference's packed-int form
        (``Renderer.renderAsPackedInt``, ``ImageRegionRequestHandler.java:559``):
        u32[..., H, W] with bytes R|G<<8|B<<16|A<<24, i.e. little-endian
        memory order R,G,B,A — so the host gets RGBA by ``.view(uint8)``
        with zero copies and the device never materializes a
        4-wide minor axis.
    """
    lead = q.shape[:-2]          # (..., C)
    n_planes = 1
    for d in lead:
        n_planes *= d
    flat = tables.reshape(n_planes * 256, 3)
    idx = q + (jnp.arange(n_planes, dtype=q.dtype) * 256).reshape(
        lead + (1, 1)
    )
    out = []
    for comp in range(3):
        v = jnp.take(flat[:, comp], idx, axis=0)     # f32 [..., C, H, W]
        v = jnp.sum(v, axis=-3)                      # composite over C
        v = jnp.clip(jnp.round(v), 0.0, 255.0).astype(jnp.uint32)
        out.append(v)
    r, g, b = out
    return r | (g << 8) | (b << 16) | jnp.uint32(0xFF000000)


@jax.named_scope("render")      # a stage of utils.profile_summary.STAGES
def _render_packed_impl(raw, window_start, window_end, family, coefficient,
                        reverse, cd_start, cd_end, tables):
    """Shared impl over arbitrary leading dims: raw [..., C, H, W]."""
    shape = raw.shape
    H, W = shape[-2:]
    n_planes = 1
    for d in shape[:-2]:
        n_planes *= d
    q = quantize(
        raw.reshape(n_planes, H, W),
        window_start.reshape(n_planes),
        window_end.reshape(n_planes),
        family.reshape(n_planes),
        coefficient.reshape(n_planes),
        cd_start,
        cd_end,
    )
    # Reverse-intensity codomain op (ReverseIntensityContext,
    # ImageRegionRequestHandler.java:717-730): mirror within the codomain.
    q = jnp.where(
        reverse.reshape(n_planes)[:, None, None] != 0,
        cd_start + cd_end - q, q,
    ).reshape(shape)
    # Shape-dispatch: ramp weights [..., C, 3] (one dim fewer than the
    # [..., C, 256, 3] gather tables) take the arithmetic path.
    if tables.ndim == raw.ndim - 1:
        return composite_ramp_packed(q, tables)
    return composite_packed(q, tables)


@jax.jit
def render_tile_packed(raw, window_start, window_end, family, coefficient,
                       reverse, cd_start, cd_end, tables):
    """Render one raw multi-channel tile to packed RGBA ints.

    Args:
      raw:          f32[C, H, W] raw channel planes.
      window_start: f32[C]
      window_end:   f32[C]
      family:       i32[C] quantum family ids
      coefficient:  f32[C] family curve coefficients
      reverse:      i32[C] 1 to apply reverse-intensity, else 0
      cd_start:     i32[] codomain start (QuantumDef)
      cd_end:       i32[] codomain end (QuantumDef)
      tables:       f32[C, 256, 3] channel tables from
                    :func:`build_channel_tables`.

    Returns:
      u32[H, W] packed pixels, little-endian byte order R,G,B,A with alpha
      fully opaque (the reference's packed ARGB analogue).
    """
    return _render_packed_impl(raw, window_start, window_end, family,
                               coefficient, reverse, cd_start, cd_end,
                               tables)


@jax.jit
def render_tile_batch_packed(raw, window_start, window_end, family,
                             coefficient, reverse, cd_start, cd_end, tables):
    """Batched render to packed ints: per-tile args gain a leading dim B.

    This is the micro-batched hot path (SURVEY.md section 7 step 5): the
    worker coalesces concurrent tile requests of one bucket shape into a
    single device dispatch.

    Args:
      raw:    f32[B, C, H, W]
      cd_start/cd_end: scalars, shared across the batch.
      others: as :func:`render_tile_packed` with a leading B axis.
    Returns:
      u32[B, H, W]
    """
    return _render_packed_impl(raw, window_start, window_end, family,
                               coefficient, reverse, cd_start, cd_end,
                               tables)


def unpack_rgba(packed: np.ndarray) -> np.ndarray:
    """u32[..., H, W] packed pixels -> u8[..., H, W, 4] RGBA, zero-copy."""
    packed = np.ascontiguousarray(np.asarray(packed))
    le = packed.astype("<u4", copy=False)
    return le.view(np.uint8).reshape(packed.shape + (4,))


def render_tile(raw, window_start, window_end, family, coefficient,
                reverse, cd_start, cd_end, tables):
    """Host-convenience single-tile render -> u8[H, W, 4] RGBA numpy."""
    return unpack_rgba(render_tile_packed(
        raw, window_start, window_end, family, coefficient, reverse,
        cd_start, cd_end, tables,
    ))


def render_tile_batch(raw, window_start, window_end, family, coefficient,
                      reverse, cd_start, cd_end, tables):
    """Host-convenience batched render -> u8[B, H, W, 4] RGBA numpy."""
    return unpack_rgba(render_tile_batch_packed(
        raw, window_start, window_end, family, coefficient, reverse,
        cd_start, cd_end, tables,
    ))


def pack_settings(rdef: RenderingDef, lut_provider=None):
    """Host-side packing of a RenderingDef into kernel arguments.

    Returns a dict of numpy arrays ready to splat into :func:`render_tile`.
    ``tables`` is f32[C, 3] ramp weights when no active channel uses a LUT
    (the kernels' fast arithmetic path), else the full f32[C, 256, 3]
    gather tables.
    """
    cbs = rdef.channel_bindings
    weights = build_ramp_weights(rdef, lut_provider)
    return {
        "window_start": np.array([cb.input_start for cb in cbs], np.float32),
        "window_end": np.array([cb.input_end for cb in cbs], np.float32),
        "family": np.array([cb.family.index for cb in cbs], np.int32),
        "coefficient": np.array([cb.coefficient for cb in cbs], np.float32),
        "reverse": np.array(
            [1 if cb.reverse_intensity else 0 for cb in cbs], np.int32
        ),
        "cd_start": np.int32(rdef.quantum.cd_start),
        "cd_end": np.int32(rdef.quantum.cd_end),
        "tables": (weights if weights is not None
                   else build_channel_tables(rdef, lut_provider)),
    }


@jax.jit
def stack_channel_planes(*planes):
    """The ``[C, h, w]`` stack of ONE request, from ``C`` device-resident
    channel planes ``[h, w]`` of the HBM raw cache (one entry a channel:
    ``io.devicecache.region_key``).  One program a (count, shape,
    dtype), a copy of the planes on the device; the stack is the
    request's own and nothing keeps it.  The fallback of
    :func:`stack_group_planes`: a request whose planes need a flip or a
    pad of their own, a projection's planes, a renderer that does not
    batch."""
    # A stage of utils.profile_summary.STAGES.
    with jax.named_scope("stage.channel_stack"):
        return jnp.stack(planes)


@functools.partial(jax.jit, static_argnames=("pad",))
def stack_group_planes(members, pad=None):
    """The ``[B, C, h, w]`` array a group's render takes, from its
    members' planes: ``members`` is a tuple of ``B`` tuples of ``C``
    device-resident planes ``[h, w]`` (a padded slot repeats the last
    member's).  One program a (B, C, shape, pad, dtype) and ONE dispatch
    a group, where a stack a request and an eager ``jnp.stack`` of the
    stacks were B + B + 1.  Bit for bit
    ``jnp.stack([stack_channel_planes(*m) for m in members])``.  The
    planes are the cache's: nothing is donated.

    ``pad``: the bucket ``(bh, bw)`` of a JPEG group whose planes are
    smaller than it (a 1080^2 field in its 1088^2 MCU grid).  The same
    program then edge-replicates the stack to ``[B, C, bh, bw]``, bit
    for bit ``ops.jpegenc.pad_planes_to_mcu`` of each member's stack;
    the cache keeps the planes as the store holds them."""
    with jax.named_scope("stage.channel_stack"):
        raw = jnp.stack([jnp.stack(planes) for planes in members])
    h, w = raw.shape[-2:]
    if pad is None or tuple(pad) == (h, w):
        return raw
    # A stage of its own: what a bucket larger than the plane costs.
    with jax.named_scope("stage.pad_mcu"):
        return jnp.pad(raw, ((0, 0), (0, 0), (0, pad[0] - h),
                             (0, pad[1] - w)), mode="edge")
