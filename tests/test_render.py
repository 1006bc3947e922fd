"""Fused render kernel vs CPU reference: models, LUTs, reverse intensity,
composition, batching."""

import numpy as np
import pytest

from omero_ms_image_region_tpu.models.pixels import Pixels
from omero_ms_image_region_tpu.models.rendering import (
    ChannelBinding,
    Family,
    QuantumDef,
    RenderingDef,
    RenderingModel,
    default_rendering_def,
)
from omero_ms_image_region_tpu.ops.lut import LutProvider
from omero_ms_image_region_tpu.ops.render import (
    build_channel_tables,
    pack_settings,
    render_tile,
    render_tile_batch,
    render_tile_batch_packed,
    unpack_rgba,
)
from omero_ms_image_region_tpu.refimpl import render_ref


def _pixels(C=3, H=8, W=8, ptype="uint16"):
    return Pixels(image_id=1, pixels_type=ptype, size_x=W, size_y=H,
                  size_c=C)


def _rdef(C=3, model=RenderingModel.RGB, ptype="uint16"):
    rdef = default_rendering_def(_pixels(C=C, ptype=ptype))
    rdef.model = model
    colors = [(255, 0, 0, 255), (0, 255, 0, 255), (0, 0, 255, 255),
              (255, 255, 0, 255)]
    for c, cb in enumerate(rdef.channel_bindings):
        cb.red, cb.green, cb.blue, cb.alpha = colors[c % 4]
    return rdef


def _render_jax(raw, rdef, lut_provider=None):
    s = pack_settings(rdef, lut_provider)
    return np.asarray(render_tile(raw.astype(np.float32), **s))


def test_rgb_composite_matches_reference():
    rng = np.random.default_rng(0)
    raw = rng.uniform(0, 65535, size=(3, 8, 8)).astype(np.float32)
    rdef = _rdef()
    got = _render_jax(raw, rdef)
    want = render_ref(raw, rdef)
    assert np.abs(got.astype(int) - want.astype(int)).max() <= 1


def test_greyscale_first_active_channel_only():
    raw = np.stack(
        [
            np.full((4, 4), 0, np.float32),
            np.full((4, 4), 65535, np.float32),
            np.full((4, 4), 30000, np.float32),
        ]
    )
    rdef = _rdef(model=RenderingModel.GREYSCALE)
    rdef.channel_bindings[0].active = False  # first ACTIVE is channel 1
    got = _render_jax(raw, rdef)
    want = render_ref(raw, rdef)
    np.testing.assert_array_equal(got, want)
    # channel 1 is saturated -> grey 255
    assert got[0, 0].tolist() == [255, 255, 255, 255]


def test_inactive_channels_do_not_contribute():
    raw = np.stack(
        [np.zeros((4, 4), np.float32), np.full((4, 4), 65535, np.float32)]
    )
    rdef = _rdef(C=2)
    rdef.channel_bindings[1].active = False
    got = _render_jax(raw, rdef)
    assert got[..., :3].max() == 0


def test_lut_channel():
    lp = LutProvider()
    table = np.zeros((256, 3), np.uint8)
    table[:, 1] = np.arange(256)  # green ramp
    lp.add("green_ramp.lut", table)

    rdef = _rdef(C=1)
    rdef.channel_bindings[0].lut = "green_ramp.lut"
    raw = np.full((1, 4, 4), 65535, np.float32)
    got = _render_jax(raw, rdef, lp)
    want = render_ref(raw, rdef, lp)
    np.testing.assert_array_equal(got, want)
    assert got[0, 0].tolist() == [0, 255, 0, 255]


def test_reverse_intensity():
    rdef = _rdef(C=1)
    rdef.channel_bindings[0].reverse_intensity = True
    raw = np.zeros((1, 4, 4), np.float32)  # min value -> reversed = max
    got = _render_jax(raw, rdef)
    want = render_ref(raw, rdef)
    np.testing.assert_array_equal(got, want)
    assert got[0, 0, 0] == 255  # red channel at full after reversal


def test_alpha_scales_contribution():
    rdef = _rdef(C=1)
    rdef.channel_bindings[0].alpha = 128
    raw = np.full((1, 4, 4), 65535, np.float32)
    got = _render_jax(raw, rdef)
    want = render_ref(raw, rdef)
    assert np.abs(got.astype(int) - want.astype(int)).max() <= 1
    assert abs(int(got[0, 0, 0]) - 128) <= 1


def test_additive_composite_clamps():
    rdef = _rdef(C=2)
    for cb in rdef.channel_bindings:
        cb.red, cb.green, cb.blue = 255, 255, 255
    raw = np.full((2, 4, 4), 65535, np.float32)
    got = _render_jax(raw, rdef)
    assert got[..., :3].max() == 255


def test_families_per_channel_against_reference():
    rng = np.random.default_rng(7)
    raw = rng.uniform(0, 65535, size=(4, 6, 6)).astype(np.float32)
    rdef = _rdef(C=4)
    fams = [Family.LINEAR, Family.POLYNOMIAL, Family.LOGARITHMIC,
            Family.EXPONENTIAL]
    for cb, fam in zip(rdef.channel_bindings, fams):
        cb.family = fam
        cb.coefficient = 1.5 if fam == Family.POLYNOMIAL else 1.0
        cb.active = True
    got = _render_jax(raw, rdef)
    want = render_ref(raw, rdef)
    assert np.abs(got.astype(int) - want.astype(int)).max() <= 2


def test_batch_render_matches_single():
    rng = np.random.default_rng(3)
    B, C, H, W = 4, 3, 8, 8
    raw = rng.uniform(0, 65535, size=(B, C, H, W)).astype(np.float32)
    rdef = _rdef()
    s = pack_settings(rdef)
    batched = np.asarray(
        render_tile_batch(
            raw,
            np.tile(s["window_start"], (B, 1)),
            np.tile(s["window_end"], (B, 1)),
            np.tile(s["family"], (B, 1)),
            np.tile(s["coefficient"], (B, 1)),
            np.tile(s["reverse"], (B, 1)),
            s["cd_start"],
            s["cd_end"],
            np.tile(s["tables"], (B,) + (1,) * s["tables"].ndim),
        )
    )
    for b in range(B):
        single = np.asarray(render_tile(raw[b], **s))
        np.testing.assert_array_equal(batched[b], single)


def test_custom_codomain_interval():
    # QuantumDef with a narrowed codomain must cap quantized output —
    # and the reverse-intensity mirror must respect it too.
    rdef = _rdef(C=1)
    rdef.quantum = QuantumDef(cd_start=0, cd_end=127)
    raw = np.full((1, 4, 4), 65535, np.float32)
    got = _render_jax(raw, rdef)
    want = render_ref(raw, rdef)
    assert np.abs(got.astype(int) - want.astype(int)).max() <= 1
    assert abs(int(got[0, 0, 0]) - 127) <= 1  # red capped at cd_end

    rdef.channel_bindings[0].reverse_intensity = True
    zero = np.zeros((1, 4, 4), np.float32)
    got_rev = _render_jax(zero, rdef)
    want_rev = render_ref(zero, rdef)
    assert np.abs(got_rev.astype(int) - want_rev.astype(int)).max() <= 1
    assert abs(int(got_rev[0, 0, 0]) - 127) <= 1  # mirrored within [0,127]


def test_log_family_degenerate_unit_window():
    # log over [0, 1] collapses both endpoints to 0: step function, not NaN.
    rdef = _rdef(C=1, ptype="float")
    cb = rdef.channel_bindings[0]
    cb.family = Family.LOGARITHMIC
    cb.input_start, cb.input_end = 0.0, 1.0
    raw = np.array([[[0.0, 0.5, 1.0, 2.0]]], np.float32)
    got = _render_jax(raw, rdef)
    want = render_ref(raw, rdef)
    np.testing.assert_array_equal(got, want)
    assert got[0, 0, 0] == 0 and got[0, 2, 0] == 255


def test_default_rendering_def_matches_reference_defaults():
    rdef = default_rendering_def(_pixels(C=5))
    # First three channels active, linear family, type-range window, red.
    assert [cb.active for cb in rdef.channel_bindings] == [
        True, True, True, False, False,
    ]
    cb = rdef.channel_bindings[0]
    assert cb.family == Family.LINEAR
    assert (cb.input_start, cb.input_end) == (0.0, 65535.0)
    assert (cb.red, cb.green, cb.blue, cb.alpha) == (255, 0, 0, 255)
    assert rdef.model == RenderingModel.GREYSCALE
    assert rdef.quantum.cd_start == 0 and rdef.quantum.cd_end == 255


# ---- the served kernel (render_tile_batch_packed, what the batcher
# and both JPEG programs call) against the reference, with each tile
# of the batch under its own window as a coalesced group has them.

_FAMILIES = ["linear", "polynomial", "logarithmic", "exponential"]


def _tile_rdef(C, family, b):
    rdef = _rdef(C=C)
    for i, cb in enumerate(rdef.channel_bindings):
        cb.active = True
        cb.family = Family(family)
        cb.coefficient = 1.3 if family in ("polynomial",
                                           "exponential") else 1.0
        cb.input_start = 200.0 + 150.0 * b
        cb.input_end = 50000.0 - 4000.0 * b
        cb.reverse_intensity = i == 2
    return rdef


def _batched_vs_reference(B, C, H, W, family="linear", form="tables",
                          seed=0, lut_provider=None, lut=None):
    rng = np.random.default_rng(seed)
    raw = rng.integers(0, 65535, size=(B, C, H, W)).astype(np.float32)
    rdefs = [_tile_rdef(C, family, b) for b in range(B)]
    if lut is not None:
        for rdef in rdefs:
            rdef.channel_bindings[0].lut = lut
    packs = [pack_settings(r, lut_provider) for r in rdefs]
    if form == "ramp":
        assert all(s["tables"].ndim == 2 for s in packs)   # f32[C, 3]
        tables = [s["tables"] for s in packs]
    else:
        tables = [build_channel_tables(r, lut_provider) for r in rdefs]
        assert all(t.shape == (C, 256, 3) for t in tables)

    def stack(k):
        return np.stack([s[k] for s in packs])

    got = unpack_rgba(render_tile_batch_packed(
        raw, stack("window_start"), stack("window_end"), stack("family"),
        stack("coefficient"), stack("reverse"), packs[0]["cd_start"],
        packs[0]["cd_end"], np.stack(tables)))
    assert got.shape == (B, H, W, 4)
    for b in range(B):
        want = render_ref(raw[b], rdefs[b], lut_provider)
        assert np.abs(got[b].astype(int) - want.astype(int)).max() <= 2


@pytest.mark.parametrize("C", [1, 3, 4])
@pytest.mark.parametrize("family", _FAMILIES)
def test_batched_kernel_families_against_reference(C, family):
    _batched_vs_reference(2, C, 16, 64, family=family, seed=C)


@pytest.mark.parametrize("B", [1, 2, 5])
@pytest.mark.parametrize("H,W", [(16, 64), (40, 32), (96, 128), (272, 64)])
def test_batched_kernel_shapes_against_reference(B, H, W):
    _batched_vs_reference(B, 2, H, W, seed=B * H)


@pytest.mark.parametrize("C", [3, 4])
@pytest.mark.parametrize("family", _FAMILIES)
def test_batched_kernel_ramp_weights_against_reference(C, family):
    _batched_vs_reference(2, C, 16, 64, family=family, form="ramp",
                          seed=11 + C)


@pytest.mark.parametrize("B,H,W", [(1, 16, 64), (3, 96, 128)])
def test_batched_kernel_ramp_weights_shapes(B, H, W):
    _batched_vs_reference(B, 2, H, W, form="ramp", seed=B + H)


def test_batched_kernel_lut_file_tables_against_reference():
    lp = LutProvider()
    table = np.zeros((256, 3), np.uint8)
    table[:, 1] = np.arange(256)
    table[:, 2] = 255 - np.arange(256)
    lp.add("green_up_blue_down.lut", table)
    _batched_vs_reference(2, 2, 16, 64, seed=9, lut_provider=lp,
                          lut="green_up_blue_down.lut")
