"""Pallas TPU kernels for the fused tile render.

Two kernels, matching :mod:`..ops.render`'s own shape dispatch:

* **Ramp kernel** (``tables`` = f32[C, 3] weights) — the serving-path
  formulation, an option of the direct renderer
  (``renderer.kernel: pallas``; a compile or runtime failure of the
  kernel fails the request in ``server.handler.Renderer`` — there is
  no quiet switch to XLA).  ``pack_settings`` emits ramp weights
  whenever no active channel resolves an actual LUT file — the
  overwhelmingly common case — and the ramp composite is pure
  elementwise arithmetic: window clamp, family curve, round, per-channel
  multiply-accumulate, clip, u32 pack.  No gather, no one-hot, no
  reshape — nothing in the Mosaic-unsupported layout classes.  This is
  the same reformulation the XLA path itself made
  (``ops.render.composite_ramp_packed``: arithmetic beats table gathers
  ~9x on TPU), applied to the Pallas formulation: the round-3 blocker —
  a ``(bh, W) -> (bh*W, 1)`` flatten Mosaic rejects
  (``infer-vector-layout: unsupported shape cast``, minor dim cast to
  1) — existed only to feed the one-hot MXU contraction, and the ramp
  path needs neither.

* **One-hot LUT kernel** (``tables`` = f32[C, 256, 3]) — the original
  round-3 experiment, kept for real-LUT renders and as the
  one-hot-as-MXU-contraction reference:

      onehot(q)[N, 256] @ table[256, 3]  ==  table[q]

  The pixel flatten feeding the MXU is a leading-dim collapse
  ``(bh, bw, 256) -> (bh*bw, 256)`` (minor dim preserved — the
  shape-cast class Mosaic supports), and rows AND lanes are blocked
  (8 x 512 at 1024^2) so the one-hot fits VMEM under a tiling-legal
  row block.  Parity is proven in interpret mode
  (tests/test_pallas.py); the serving option never routes LUT renders
  here.

Both forms compile for a v5e at 4 x 1024^2 (tests/test_chip_compile.py
keeps them compiling; interpret mode alone let a scalar ``powf`` and a
4-row block through for as long as nothing else looked).  Neither has
been timed on the chip: ``ops.render`` remains the default and the
portable reference.

Replaces the same reference surface (``Renderer.renderAsPackedInt``,
``ImageRegionRequestHandler.java:559``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..ops.quantum import _ratio as _quantum_ratio

# Row-block height per grid step (upper bound; see _ramp_block_h).
_BLOCK_H = 256
# Ramp kernel budget for one raw input block f32[C, bh, W].  The
# pipeline double-buffers it and the kernel holds a few (bh, W)
# temporaries beside it; 2 MB keeps the whole step under the 16 MB of
# scoped VMEM a v5e core grants (4 MB blocks measured 17.2 MB there).
_RAMP_BLOCK_BYTES = 2 << 20
# LUT (one-hot) kernel budget: the materialized one-hot is
# f32[bh*bw, 256] (1 KB per pixel) and its MXU product another half of
# that, so one grid step covers at most this many pixels (~4 MB + 2 MB).
_ONEHOT_MAX_PIXELS = 4096


def pick_block_h(H: int, max_block: int = _BLOCK_H) -> int:
    """Row-block height: the largest divisor of H that is a multiple of
    8 and at most ``max_block``; H itself when there is none.

    The grid covers H in equal row blocks, so bh must divide H exactly,
    and the chip's tiling wants the block's row count to be a multiple
    of 8 or the whole array.  The production buckets (256/512/1024/
    2048) all take ``max_block``; odd heights fall back to one
    whole-height block (correct, never fast — bucket such shapes
    upstream).
    """
    bh = min(max_block, H) // 8 * 8
    while bh >= 8 and H % bh:
        bh -= 8
    return bh if bh >= 8 else H


def _ramp_block_h(C: int, H: int, W: int) -> int:
    rows = _RAMP_BLOCK_BYTES // (C * W * 4)
    return pick_block_h(H, max_block=max(8, min(_BLOCK_H, rows)))


def _lut_block(H: int, W: int) -> tuple:
    """(bh, bw) for the one-hot kernel: lanes are blocked too (in
    multiples of 128, the chip's lane tile) so that a legal 8-row block
    still keeps the one-hot inside ``_ONEHOT_MAX_PIXELS``."""
    bw = W
    if W % 128 == 0:
        bw = min(W, _ONEHOT_MAX_PIXELS // 8) // 128 * 128
        while W % bw:
            bw -= 128
    return pick_block_h(H, max_block=max(8, _ONEHOT_MAX_PIXELS // bw)), bw


def _quantize_channel(x, ws, we, fam, k, cd_start, cd_end, rev):
    """One channel's window clamp + family curve + reverse, in f32.

    The exact closed forms the XLA kernel uses (ops.quantum._ratio),
    evaluated on VMEM blocks, so the two paths agree bit-for-bit for
    every family.
    """
    # The window/curve scalars arrive from SMEM.  Lift them to one-row
    # vectors first: the family transform takes pow/log/exp of them,
    # and Mosaic legalizes those on vectors only (a scalar ``powf`` is
    # refused by the chip's compiler; interpret mode never noticed).
    def row(s):
        return jnp.full((1, x.shape[-1]), s, jnp.float32)

    ws, we, k = row(ws), row(we), row(k)
    k_max = (cd_end - cd_start).astype(jnp.float32)
    x_clamped = jnp.clip(x, jnp.minimum(ws, we), jnp.maximum(ws, we))
    ratio = jnp.clip(
        _quantum_ratio(x_clamped, x, ws, we, fam, k), 0.0, 1.0)
    q = jnp.round(cd_start.astype(jnp.float32) + k_max * ratio)
    q = jnp.where(rev != 0,
                  (cd_start + cd_end).astype(jnp.float32) - q, q)
    return jnp.clip(q, 0.0, 255.0)


def _pack_u32(acc_r, acc_g, acc_b):
    """Clip/round the composites and pack to the u32 RGBA layout.

    Mosaic has no direct f32->u32 cast; go through i32 (values <= 255).
    """
    r = jnp.clip(jnp.round(acc_r), 0.0, 255.0).astype(jnp.int32)
    g = jnp.clip(jnp.round(acc_g), 0.0, 255.0).astype(jnp.int32)
    b = jnp.clip(jnp.round(acc_b), 0.0, 255.0).astype(jnp.int32)
    packed = r | (g << 8) | (b << 16) | jnp.int32(-0x1000000)  # A=0xFF
    return jax.lax.bitcast_convert_type(packed, jnp.uint32)


def _render_kernel_ramp(ws_ref, we_ref, fam_ref, coef_ref, rev_ref,
                        cd_ref, w_ref, raw_ref, out_ref):
    """One (batch, row-block) grid step of the RAMP composite.

    raw_ref: f32[C, bh, W] (VMEM; already loaded block)
    out_ref: u32[1, bh, W] (VMEM ref; leading block dim)
    scalars (SMEM, prefetched): ws/we/coef f32[C], fam/rev i32[C],
    cd i32[2], w f32[C*3] flattened ramp weights.

    Entirely elementwise — the serving formulation with no layout
    hazards (see module docstring).
    """
    C, bh, W = raw_ref.shape
    cd_start = cd_ref[0]
    cd_end = cd_ref[1]

    acc_r = jnp.zeros((bh, W), jnp.float32)
    acc_g = jnp.zeros((bh, W), jnp.float32)
    acc_b = jnp.zeros((bh, W), jnp.float32)

    for c in range(C):  # C is a static block dim: unrolled at trace time
        q = _quantize_channel(raw_ref[c], ws_ref[c], we_ref[c],
                              fam_ref[c], coef_ref[c], cd_start,
                              cd_end, rev_ref[c])
        acc_r += q * w_ref[3 * c]
        acc_g += q * w_ref[3 * c + 1]
        acc_b += q * w_ref[3 * c + 2]

    out_ref[0] = _pack_u32(acc_r, acc_g, acc_b)


def _render_kernel_lut(ws_ref, we_ref, fam_ref, coef_ref, rev_ref,
                       cd_ref, raw_ref, tables_ref, out_ref):
    """One (batch, row-block) grid step of the one-hot LUT composite.

    raw_ref:    f32[C, bh, W]       (VMEM; already loaded block)
    tables_ref: f32[C, 256, 128]    (VMEM; only cols 0..2 are live)
    out_ref:    u32[1, bh, W]       (VMEM ref; leading block dim)
    scalars (SMEM, prefetched): ws/we/fam/coef/rev f32|i32[C], cd i32[2]
    """
    C, bh, W = raw_ref.shape
    cd_start = cd_ref[0]
    cd_end = cd_ref[1]

    acc_r = jnp.zeros((bh, W), jnp.float32)
    acc_g = jnp.zeros((bh, W), jnp.float32)
    acc_b = jnp.zeros((bh, W), jnp.float32)

    for c in range(C):
        q = _quantize_channel(raw_ref[c], ws_ref[c], we_ref[c],
                              fam_ref[c], coef_ref[c], cd_start,
                              cd_end, rev_ref[c])
        # One-hot contraction on the MXU: [bh*W, 256] @ [256, 128].
        # The one-hot is built 3-D with the class axis MINOR and the
        # pixel flatten expressed as a leading-dim collapse (minor dim
        # preserved) — the shape-cast class Mosaic supports, unlike the
        # round-3 (bh, W) -> (bh*W, 1) minor-dim cast it rejected.
        # (Integer compare: Mosaic rejects float iota.)
        qi = q.astype(jnp.int32)
        classes = jax.lax.broadcasted_iota(jnp.int32, (bh, W, 256), 2)
        qb = jax.lax.broadcast_in_dim(qi, (bh, W, 256), (0, 1))
        onehot = (qb == classes).astype(jnp.float32).reshape(
            bh * W, 256)
        rgb = jnp.dot(onehot, tables_ref[c],
                      preferred_element_type=jnp.float32)
        acc_r += rgb[:, 0].reshape(bh, W)
        acc_g += rgb[:, 1].reshape(bh, W)
        acc_b += rgb[:, 2].reshape(bh, W)

    out_ref[0] = _pack_u32(acc_r, acc_g, acc_b)


@functools.partial(jax.jit, static_argnames=("interpret",))
def render_tile_batch_packed_pallas(raw, window_start, window_end, family,
                                    coefficient, reverse, cd_start, cd_end,
                                    tables, *, interpret=False):
    """Pallas fused batched render: f32[B, C, H, W] -> u32[B, H, W].

    Same contract as ``ops.render.render_tile_batch_packed`` except the
    per-channel settings are shared across the batch (the direct
    renderer's case; the batcher keys groups by settings when using
    this path), so they arrive unbatched: window_start/window_end/
    coefficient f32[C], family/reverse i32[C], and ``tables`` either
    f32[C, 3] ramp weights (the serving ramp kernel) or f32[C, 256, 3]
    LUT tables (the experimental one-hot kernel) — the same shape
    dispatch as ``ops.render._render_packed_impl``.
    """
    B, C, H, W = raw.shape
    cd = jnp.stack([jnp.asarray(cd_start, jnp.int32),
                    jnp.asarray(cd_end, jnp.int32)])
    scalars = (window_start.astype(jnp.float32),
               window_end.astype(jnp.float32),
               family.astype(jnp.int32),
               coefficient.astype(jnp.float32),
               reverse.astype(jnp.int32), cd)

    if tables.ndim == 2:
        # Ramp weights [C, 3]: the elementwise serving kernel.  The
        # weights ride SMEM with the other per-channel scalars.
        bh = _ramp_block_h(C, H, W)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=7,
            grid=(B, H // bh),
            in_specs=[
                pl.BlockSpec((1, C, bh, W), lambda b, h, *_: (b, 0, h, 0)),
            ],
            out_specs=pl.BlockSpec((1, bh, W), lambda b, h, *_: (b, h, 0)),
        )

        def kernel(ws, we, fam, coef, rev, cdv, w, raw_blk, out_blk):
            _render_kernel_ramp(ws, we, fam, coef, rev, cdv, w,
                                raw_blk[0], out_blk)

        return pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((B, H, W), jnp.uint32),
            interpret=interpret,
        )(*scalars, tables.astype(jnp.float32).reshape(C * 3),
          raw.astype(jnp.float32))

    # LUT tables [C, 256, 3]: pad the color axis 3 -> 128 so the MXU
    # contraction output is lane-aligned; dead columns contract to
    # zeros.  Rows AND lanes are blocked so the materialized one-hot
    # fits VMEM under a tiling-legal (multiple-of-8) row block.
    bh, bw = _lut_block(H, W)
    tables_padded = jnp.zeros((C, 256, 128), jnp.float32)
    tables_padded = tables_padded.at[:, :, :3].set(
        tables.astype(jnp.float32))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(B, H // bh, W // bw),
        in_specs=[
            pl.BlockSpec((1, C, bh, bw),
                         lambda b, h, w, *_: (b, 0, h, w)),
            pl.BlockSpec((C, 256, 128), lambda b, h, w, *_: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bh, bw),
                               lambda b, h, w, *_: (b, h, w)),
    )

    def kernel(ws, we, fam, coef, rev, cdv, raw_blk, tab_blk, out_blk):
        _render_kernel_lut(ws, we, fam, coef, rev, cdv,
                           raw_blk[0], tab_blk, out_blk)

    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, W), jnp.uint32),
        interpret=interpret,
    )(*scalars, raw.astype(jnp.float32), tables_padded)


def render_tile_packed_pallas(raw, window_start, window_end, family,
                              coefficient, reverse, cd_start, cd_end,
                              tables, *, interpret=False):
    """Single-tile convenience: f32[C, H, W] -> u32[H, W] (the direct
    renderer's call shape)."""
    return render_tile_batch_packed_pallas(
        raw[None], window_start, window_end, family, coefficient,
        reverse, cd_start, cd_end, tables, interpret=interpret)[0]
