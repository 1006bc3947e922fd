"""Pallas render kernels: parity with the XLA kernel.

The RAMP kernel (elementwise, no one-hot) is a serving option
(renderer.kernel: pallas) whose failures are loud; the one-hot LUT
kernel is parity-tested here and compiled for the chip in
tests/test_chip_compile.py.  Interpret mode is steered from these
tests, never from the product.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from omero_ms_image_region_tpu.models.pixels import Pixels
from omero_ms_image_region_tpu.models.rendering import (
    RenderingModel, default_rendering_def,
)
from omero_ms_image_region_tpu.experimental.pallas_render import (
    render_tile_batch_packed_pallas,
)
from omero_ms_image_region_tpu.ops.render import (
    build_channel_tables, pack_settings, render_tile_batch_packed,
)


def _rdef(C=3):
    pixels = Pixels(image_id=1, size_x=64, size_y=64, size_c=C,
                    pixels_type="uint16")
    rdef = default_rendering_def(pixels)
    rdef.model = RenderingModel.RGB
    colors = [(255, 0, 0), (0, 255, 0), (0, 0, 255), (255, 255, 0)]
    for i, cb in enumerate(rdef.channel_bindings):
        cb.active = True
        cb.red, cb.green, cb.blue = colors[i % 4]
        cb.input_start, cb.input_end = 200.0, 50000.0
        cb.reverse_intensity = i == 2
    return rdef


def _parity(B, C, H, W, family="linear", lut=False, seed=0,
            ramp=False):
    from omero_ms_image_region_tpu.models.rendering import Family
    rng = np.random.default_rng(seed)
    rdef = _rdef(C)
    for cb in rdef.channel_bindings:
        cb.family = Family(family)
        cb.coefficient = 1.3 if family in ("polynomial",
                                           "exponential") else 1.0
    lut_provider = None
    if lut:
        from omero_ms_image_region_tpu.ops.lut import LutProvider
        lut_provider = LutProvider()  # no files: colors fold to ramps
    s = pack_settings(rdef, lut_provider)
    if ramp:
        # The serving ramp path: pack_settings already folded the
        # colors to f32[C, 3] weights (no LUT files resolve).
        tables = s["tables"]
        assert tables.ndim == 2
    else:
        tables = build_channel_tables(rdef, lut_provider)
    raw = rng.integers(0, 65535, size=(B, C, H, W)).astype(np.float32)

    got = np.asarray(render_tile_batch_packed_pallas(
        raw, s["window_start"], s["window_end"], s["family"],
        s["coefficient"], s["reverse"], s["cd_start"], s["cd_end"],
        tables, interpret=True))

    tiled = lambda a: np.tile(a[None], (B,) + (1,) * a.ndim)
    want = np.asarray(render_tile_batch_packed(
        raw, tiled(s["window_start"]), tiled(s["window_end"]),
        tiled(s["family"]), tiled(s["coefficient"]), tiled(s["reverse"]),
        s["cd_start"], s["cd_end"], tiled(tables)))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("C", [1, 3])
@pytest.mark.parametrize("family", ["linear", "polynomial", "logarithmic",
                                    "exponential"])
def test_pallas_matches_xla_kernel(C, family):
    _parity(2, C, 16, 64, family=family, seed=C)


@pytest.mark.parametrize("B", [1, 2, 5])
@pytest.mark.parametrize("H,W", [
    (16, 64),     # small block
    (40, 32),     # H with no pow2 block: bh=40
    (96, 128),    # bh=96
    (272, 64),    # H > _BLOCK_H with H % 256 != 0: bh=136
])
def test_pallas_shapes_and_batches(B, H, W):
    """Shapes off the 256-divisible grid must render, not assert."""
    _parity(B, 2, H, W, seed=B * H)


def test_pallas_full_lut_tables():
    _parity(1, 2, 16, 64, lut=True, seed=9)


def test_pick_block_h_covers_buckets_and_odd_heights():
    from omero_ms_image_region_tpu.experimental.pallas_render import (
        _lut_block, _ramp_block_h, pick_block_h)

    # Production buckets take the full block.
    for H in (256, 512, 1024, 2048):
        assert pick_block_h(H) == 256
    # Odd heights pick their largest multiple-of-8 divisor <= 256 ...
    assert pick_block_h(16) == 16
    assert pick_block_h(272) == 136
    assert pick_block_h(384) == 192
    assert pick_block_h(520) == 104
    # ... and one whole-height block when there is none (a full-dim
    # block is always tiling-legal: correct, never fast).
    assert pick_block_h(509) == 509
    assert pick_block_h(100) == 100
    for H in (16, 272, 384, 520, 509, 100):
        bh = pick_block_h(H)
        assert H % bh == 0 and (bh % 8 == 0 or bh == H)
    # The serving shape: 4 x 1024^2 keeps the raw block at 2 MB, and
    # the one-hot block is 8 rows x 512 lanes (the chip refuses the
    # 4-row block a rows-only cap used to pick).
    assert _ramp_block_h(4, 1024, 1024) == 128
    assert _ramp_block_h(1, 1024, 1024) == 256
    assert _lut_block(1024, 1024) == (8, 512)
    assert _lut_block(16, 64) == (16, 64)


@pytest.mark.parametrize("family", ["linear", "polynomial",
                                    "logarithmic", "exponential"])
def test_pallas_ramp_kernel_matches_xla(family):
    """The serving RAMP kernel (elementwise, no one-hot) is bit-exact
    against the XLA arithmetic composite for every family."""
    _parity(2, 3, 16, 64, family=family, seed=11, ramp=True)


@pytest.mark.parametrize("B,H,W", [(1, 16, 64), (3, 96, 128)])
def test_pallas_ramp_kernel_shapes(B, H, W):
    _parity(B, 2, H, W, seed=B + H, ramp=True)


def _interpret_on_cpu(monkeypatch):
    """Steer the product's kernel call into interpret mode FROM THE
    TEST (Mosaic only compiles for a TPU; the Renderer has no hook for
    this and must not grow one)."""
    import omero_ms_image_region_tpu.experimental.pallas_render as pr
    import functools
    monkeypatch.setattr(
        pr, "render_tile_packed_pallas",
        functools.partial(pr.render_tile_packed_pallas, interpret=True))


def test_pallas_is_a_serving_option(monkeypatch):
    """renderer.kernel: pallas is accepted and the direct Renderer
    serves ramp renders through the kernel bit-identically to XLA."""
    from omero_ms_image_region_tpu.server.config import AppConfig
    from omero_ms_image_region_tpu.server.handler import Renderer
    from omero_ms_image_region_tpu.ops.render import render_tile_packed

    cfg = AppConfig.from_dict({"renderer": {"kernel": "pallas"}})
    assert cfg.renderer.kernel == "pallas"

    rdef = _rdef(2)
    s = pack_settings(rdef)
    assert s["tables"].ndim == 2          # ramp weights: eligible
    rng = np.random.default_rng(5)
    raw = rng.integers(0, 65535, size=(2, 16, 64)).astype(np.float32)

    _interpret_on_cpu(monkeypatch)
    got = Renderer(kernel="pallas")._render_sync(raw, s)
    want = np.asarray(render_tile_packed(
        raw, s["window_start"], s["window_end"], s["family"],
        s["coefficient"], s["reverse"], s["cd_start"], s["cd_end"],
        s["tables"]))
    np.testing.assert_array_equal(got, want)


def test_pallas_option_failure_is_loud(monkeypatch):
    """A kernel the backend refuses fails the request, every time —
    the option never quietly turns itself into the XLA kernel."""
    from omero_ms_image_region_tpu.server.handler import Renderer

    rdef = _rdef(2)
    s = pack_settings(rdef)
    rng = np.random.default_rng(6)
    raw = rng.integers(0, 65535, size=(2, 16, 64)).astype(np.float32)

    r = Renderer(kernel="pallas")
    import omero_ms_image_region_tpu.experimental.pallas_render as pr

    def refuse(*a, **k):
        raise RuntimeError("mosaic")
    monkeypatch.setattr(pr, "render_tile_packed_pallas", refuse)
    for _ in range(2):                    # no latch: still loud later
        with pytest.raises(RuntimeError, match="mosaic"):
            r._render_sync(raw, s)
    # And on this CPU backend the real kernel (no interpret steering)
    # is refused by JAX itself rather than served by XLA.
    monkeypatch.undo()
    with pytest.raises(Exception):
        r._render_sync(raw, s)


def test_pallas_lut_renders_stay_on_xla(monkeypatch):
    """LUT-table renders (tables.ndim == 3) never route to pallas —
    the serving option covers the ramp kernel only."""
    from omero_ms_image_region_tpu.server.handler import Renderer
    import omero_ms_image_region_tpu.experimental.pallas_render as pr

    rdef = _rdef(2)
    s = dict(pack_settings(rdef))
    s["tables"] = build_channel_tables(rdef)    # force the 3-D tables
    rng = np.random.default_rng(7)
    raw = rng.integers(0, 65535, size=(2, 16, 64)).astype(np.float32)

    def never(*a, **k):
        raise AssertionError("LUT render routed to the pallas kernel")
    monkeypatch.setattr(pr, "render_tile_packed_pallas", never)
    out = Renderer(kernel="pallas")._render_sync(raw, s)
    assert out.shape == (16, 64)
