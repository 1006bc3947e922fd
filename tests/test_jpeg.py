"""TPU JPEG front end + JFIF entropy coder.

Covers the replacement for the reference's CPU JPEG stage
(``LocalCompress.compressToStream``, ``ImageRegionRequestHandler.java:
457-460,580-582``): device DCT/quantization kernel, Python entropy coder,
native C++ entropy coder (byte-parity with Python), and decode validation
through an independent decoder (PIL).
"""

import io

import numpy as np
import pytest
from PIL import Image

from omero_ms_image_region_tpu.jfif import build_huffman_table, encode_jfif
from omero_ms_image_region_tpu.ops.jpegenc import (
    dct_matrix, encode_tiles_jpeg, max_sparse_cap,
    packed_to_jpeg_coefficients, pad_to_mcu, quant_tables, sparse_pack,
    sparse_to_dense, zigzag_order,
)

from omero_ms_image_region_tpu.native import (
    SparseOverflowError, jpeg_encode_native, jpeg_encode_sparse_native,
    jpeg_encode_sparse_run, jpeg_native_available,
)

HAVE_NATIVE = jpeg_native_available()


def blob_image(H, W, seed=0, noise=0.0):
    rng = np.random.default_rng(seed)
    yy, xx = np.mgrid[0:H, 0:W]
    img = np.zeros((H, W, 3), np.float32)
    for _ in range(8):
        cy, cx = rng.integers(0, H), rng.integers(0, W)
        s = rng.uniform(4, max(5, min(H, W) / 4))
        img += np.exp(-((yy - cy) ** 2 + (xx - cx) ** 2) / (2 * s * s))[
            ..., None] * rng.uniform(0, 255, 3)
    if noise:
        img += rng.normal(0, noise, img.shape)
    return np.clip(img, 0, 255).astype(np.uint8)


def pack(img):
    return (img[..., 0].astype(np.uint32)
            | (img[..., 1].astype(np.uint32) << 8)
            | (img[..., 2].astype(np.uint32) << 16))


def coeffs_for(img, quality):
    qy, qc = quant_tables(quality)
    y, cb, cr = packed_to_jpeg_coefficients(
        pack(img)[None], qy.astype(np.int32), qc.astype(np.int32))
    return np.asarray(y)[0], np.asarray(cb)[0], np.asarray(cr)[0]


# ------------------------------------------------------------- tables

def test_quant_tables_quality_scaling():
    qy50, qc50 = quant_tables(50)
    assert qy50[0, 0] == 16 and qc50[0, 0] == 17  # Annex K at q=50
    qy100, _ = quant_tables(100)
    assert (qy100 == 1).all()
    qy10, _ = quant_tables(10)
    assert (qy10.astype(int) >= qy50.astype(int)).all()


def test_zigzag_is_the_jpeg_order():
    z = zigzag_order()
    assert sorted(z.tolist()) == list(range(64))
    assert z[:10].tolist() == [0, 1, 8, 16, 9, 2, 3, 10, 17, 24]
    assert z[-4:].tolist() == [47, 55, 62, 63]


def test_dct_matrix_is_orthonormal():
    D = dct_matrix()
    np.testing.assert_allclose(D @ D.T, np.eye(8), atol=1e-6)


def test_huffman_table_is_valid_and_optimalish():
    freq = np.zeros(256, dtype=np.int64)
    freq[0] = 1000
    freq[1] = 500
    freq[5] = 100
    freq[0xF0] = 1
    bits, huffval = build_huffman_table(freq)
    assert bits[1:].sum() == 4 and len(huffval) == 4
    assert huffval[0] == 0  # most frequent symbol gets the shortest code
    assert (np.cumsum([0] + [int(b) for b in bits[1:]]) <= 2 ** np.arange(
        17)).all()  # Kraft inequality at every length


# ------------------------------------------------------------- encoder

@pytest.mark.parametrize("H,W", [(64, 64), (32, 48), (16, 16)])
def test_decode_matches_pil_quality(H, W):
    img = blob_image(H, W, seed=H + W)
    y, cb, cr = coeffs_for(img, 85)
    data = encode_jfif(y, cb, cr, W, H, 85)
    dec = np.asarray(
        Image.open(io.BytesIO(data)).convert("RGB")).astype(np.float32)
    assert dec.shape == (H, W, 3)
    buf = io.BytesIO()
    Image.fromarray(img).save(buf, format="JPEG", quality=85)
    dec_pil = np.asarray(
        Image.open(buf).convert("RGB")).astype(np.float32)
    ours = np.abs(dec - img).mean()
    pils = np.abs(dec_pil - img).mean()
    assert ours <= pils * 1.3 + 0.5


def test_uniform_image_is_tiny():
    img = np.full((64, 64, 3), 130, np.uint8)
    y, cb, cr = coeffs_for(img, 85)
    data = encode_jfif(y, cb, cr, 64, 64, 85)
    assert len(data) < 900
    dec = np.asarray(Image.open(io.BytesIO(data)).convert("RGB"))
    assert np.abs(dec.astype(int) - 130).max() <= 2


def test_non_mcu_aligned_size_via_padding():
    img = blob_image(24, 40, seed=3)
    padded = pad_to_mcu(img)
    assert padded.shape == (32, 48, 3)
    y, cb, cr = coeffs_for(padded, 85)
    data = encode_jfif(y, cb, cr, 40, 24, 85)
    dec = np.asarray(Image.open(io.BytesIO(data)).convert("RGB"))
    assert dec.shape == (24, 40, 3)
    assert np.abs(dec.astype(np.float32) - img).mean() < 12.0


@pytest.mark.skipif(not HAVE_NATIVE, reason="no native toolchain")
@pytest.mark.parametrize("seed,H,W,q", [(0, 64, 64, 85), (1, 32, 48, 50),
                                        (2, 16, 32, 95)])
def test_native_matches_python_bytes(seed, H, W, q):
    img = blob_image(H, W, seed=seed, noise=4.0)
    y, cb, cr = coeffs_for(img, q)
    assert (jpeg_encode_native(y, cb, cr, W, H, q)
            == encode_jfif(y, cb, cr, W, H, q))


# ------------------------------------------------------------- sparse wire

def test_sparse_pack_roundtrips_to_dense():
    img = blob_image(32, 48, seed=9, noise=3.0)
    y, cb, cr = coeffs_for(img, 85)
    cap = 512
    buf = np.asarray(sparse_pack(y[None], cb[None], cr[None], cap))[0]
    got = sparse_to_dense(buf, 32, 48, cap)
    assert got is not None
    np.testing.assert_array_equal(got[0], y)
    np.testing.assert_array_equal(got[1], cb)
    np.testing.assert_array_equal(got[2], cr)


def test_sparse_to_dense_accepts_unaligned_true_dims():
    """The wire buffer covers the 16-aligned grid; callers may pass the
    tile's true (unaligned) dims — counts must use ceil, like the native
    encoder."""
    img = pad_to_mcu(blob_image(20, 28, seed=12))
    assert img.shape == (32, 32, 3)
    y, cb, cr = coeffs_for(img, 85)
    cap = 1024
    buf = np.asarray(sparse_pack(y[None], cb[None], cr[None], cap))[0]
    got = sparse_to_dense(buf, 20, 28, cap)     # true dims, not padded
    assert got is not None
    np.testing.assert_array_equal(got[0], y)
    data = encode_jfif(got[0], got[1], got[2], 28, 20, 85)
    dec = np.asarray(Image.open(io.BytesIO(data)).convert("RGB"))
    assert dec.shape == (20, 28, 3)


def test_sparse_prefix_decodes_and_short_prefix_raises():
    from omero_ms_image_region_tpu.ops.jpegenc import sparse_prefix_bytes

    img = blob_image(32, 48, seed=9, noise=3.0)
    y, cb, cr = coeffs_for(img, 85)
    cap = 512
    buf = np.asarray(sparse_pack(y[None], cb[None], cr[None], cap))[0]
    total = int(buf[:4].view(np.int32)[0])
    need = sparse_prefix_bytes(total, 32, 48)
    assert need < buf.size
    got = sparse_to_dense(buf[:need], 32, 48, cap)
    np.testing.assert_array_equal(got[0], y)
    with pytest.raises(ValueError):
        sparse_to_dense(buf[:need - 1], 32, 48, cap)
    if HAVE_NATIVE:
        assert (jpeg_encode_sparse_native(buf[:need], 48, 32, 85, cap)
                == jpeg_encode_sparse_native(buf, 48, 32, 85, cap))
        # A truncated buffer must error, not decode its tail from zeros.
        with pytest.raises(ValueError):
            jpeg_encode_sparse_native(buf[:need - 1], 48, 32, 85, cap)


def test_wire_fetcher_prefix_and_completion():
    from omero_ms_image_region_tpu.ops.jpegenc import (
        SparseWireFetcher, sparse_prefix_bytes)

    img = blob_image(32, 32, seed=3, noise=2.0)
    y, cb, cr = coeffs_for(img, 85)
    cap = max_sparse_cap(32, 32)
    buf = np.asarray(sparse_pack(y[None], cb[None], cr[None], cap))
    total = int(buf[0, :4].view(np.int32)[0])

    f = SparseWireFetcher(32, 32, cap)
    f.GRANULE = 16            # tiny granule so prediction is exercised
    f._k = 8 + 16             # deliberately under-predict
    rows = f.fetch(buf)
    assert rows.shape[0] == 1
    got = sparse_to_dense(rows[0], 32, 32, cap)
    np.testing.assert_array_equal(got[0], y)
    # prediction updated to cover the observed prefix (+headroom, rounded)
    assert f._k >= sparse_prefix_bytes(total, 32, 32)
    # a second fetch is single-pass (no completion path)
    got2 = sparse_to_dense(f.fetch(buf)[0], 32, 32, cap)
    np.testing.assert_array_equal(got2[0], y)


def test_sparse_pack_overflow_detected():
    rng = np.random.default_rng(0)
    img = rng.integers(0, 255, (16, 16, 3)).astype(np.uint8)  # dense noise
    y, cb, cr = coeffs_for(img, 95)
    cap = 8
    buf = np.asarray(sparse_pack(y[None], cb[None], cr[None], cap))[0]
    assert sparse_to_dense(buf, 16, 16, cap) is None
    if HAVE_NATIVE:
        with pytest.raises(SparseOverflowError):
            jpeg_encode_sparse_native(buf, 16, 16, 95, cap)


@pytest.mark.skipif(not HAVE_NATIVE, reason="no native toolchain")
@pytest.mark.parametrize("seed,H,W,q", [(4, 64, 64, 85), (5, 32, 48, 75)])
def test_sparse_native_matches_dense_native(seed, H, W, q):
    img = blob_image(H, W, seed=seed, noise=2.0)
    y, cb, cr = coeffs_for(img, q)
    cap = (H // 8) * (W // 8) * 16
    buf = np.asarray(sparse_pack(y[None], cb[None], cr[None], cap))[0]
    assert (jpeg_encode_sparse_native(buf, W, H, q, cap)
            == jpeg_encode_native(y, cb, cr, W, H, q))


@pytest.mark.skipif(not HAVE_NATIVE, reason="no native toolchain")
def test_sparse_native_rejects_malformed_buffer():
    img = blob_image(16, 16, seed=6, noise=5.0)
    y, cb, cr = coeffs_for(img, 85)
    cap = 512
    buf = np.array(sparse_pack(y[None], cb[None], cr[None], cap))[0].copy()
    nb = 4 + 2  # 16x16 tile: 4 luma + 2 chroma blocks
    counts = buf[4:4 + nb]
    assert int(counts[0]) >= 2
    # counts no longer sum to the header total -> must be rejected, not
    # trusted into fixed-size block arrays
    counts[0] -= 1
    with pytest.raises(ValueError):
        jpeg_encode_sparse_native(buf, 16, 16, 85, cap)


# ------------------------------- compacted-entry device Huffman packer

def _huffman_wire(y, cb, cr, H, W, cap=None, cap_words=None):
    from omero_ms_image_region_tpu.ops.jpegenc import (
        default_words_cap, huffman_pack, huffman_spec_arrays,
        max_sparse_cap)

    cap = cap if cap is not None else max_sparse_cap(H, W)
    cap_words = (cap_words if cap_words is not None
                 else max(64, default_words_cap(H, W) * 4))
    bufs = np.asarray(huffman_pack(
        y[None], cb[None], cr[None], cap, cap_words,
        *huffman_spec_arrays(),
        h16=(H + 15) // 16, w16=(W + 15) // 16))
    return bufs, cap, cap_words


@pytest.mark.parametrize("seed,H,W,noise", [
    (1, 16, 16, 2.0), (2, 32, 48, 3.0), (3, 64, 64, 6.0),
])
def test_huffman_pack_matches_host_fixed_coder(seed, H, W, noise):
    """Device Huffman stream == the host fixed-table coder, byte for
    byte, through the full JFIF framing."""
    from omero_ms_image_region_tpu.jfif import encode_jfif
    from omero_ms_image_region_tpu.ops.jpegenc import finish_huffman_batch

    img = blob_image(H, W, seed=seed, noise=noise)
    y, cb, cr = coeffs_for(img, 85)
    bufs, cap, cap_words = _huffman_wire(y, cb, cr, H, W)
    got = finish_huffman_batch(bufs, [(W, H)], H, W, 85, cap, cap_words)[0]
    want = encode_jfif(y, cb, cr, W, H, 85, huffman="fixed")
    assert got == want


def test_huffman_pack_empty_blocks_and_dc_only():
    """All-zero coefficients (EOBs everywhere) and DC-only blocks."""
    from omero_ms_image_region_tpu.jfif import encode_jfif
    from omero_ms_image_region_tpu.ops.jpegenc import finish_huffman_batch

    H = W = 16
    nb_y, nb_c = 4, 1
    y = np.zeros((nb_y, 64), np.int16)
    cb = np.zeros((nb_c, 64), np.int16)
    cr = np.zeros((nb_c, 64), np.int16)
    y[1, 0] = -37    # one DC-only block
    y[2, 63] = 5     # last-position AC: no EOB for this block
    bufs, cap, cap_words = _huffman_wire(y, cb, cr, H, W)
    got = finish_huffman_batch(bufs, [(W, H)], H, W, 85, cap, cap_words)[0]
    assert got == encode_jfif(y, cb, cr, W, H, 85, huffman="fixed")


def test_huffman_long_zero_runs_fold_zrls():
    """Runs of 16+, 32+ and 48+ zeros exercise the 1+2 ZRL split."""
    from omero_ms_image_region_tpu.jfif import encode_jfif
    from omero_ms_image_region_tpu.ops.jpegenc import finish_huffman_batch

    H = W = 16
    y = np.zeros((4, 64), np.int16)
    y[0, 0], y[0, 20], y[0, 40] = 100, 7, -3      # run 19, run 19
    y[1, 1], y[1, 35] = 2, 9                      # run 33 -> 2 ZRLs
    y[2, 63] = 1                                  # run 62 -> 3 ZRLs
    cb = np.zeros((1, 64), np.int16)
    cr = np.zeros((1, 64), np.int16)
    cb[0, 5] = -1
    bufs, cap, cap_words = _huffman_wire(y, cb, cr, H, W)
    got = finish_huffman_batch(bufs, [(W, H)], H, W, 85, cap, cap_words)[0]
    assert got == encode_jfif(y, cb, cr, W, H, 85, huffman="fixed")


def test_huffman_overflow_detected_and_falls_back():
    from omero_ms_image_region_tpu.ops.jpegenc import finish_huffman_batch

    img = blob_image(16, 16, seed=6, noise=8.0)
    y, cb, cr = coeffs_for(img, 95)
    bufs, cap, cap_words = _huffman_wire(y, cb, cr, 16, 16, cap=4)
    with pytest.raises(ValueError):
        finish_huffman_batch(bufs, [(16, 16)], 16, 16, 95, 4, cap_words)
    out = finish_huffman_batch(bufs, [(16, 16)], 16, 16, 95, 4, cap_words,
                               dense_fallback=lambda i: b"FALLBACK")
    assert out == [b"FALLBACK"]


def test_huffman_fetcher_prefix_roundtrip():
    from omero_ms_image_region_tpu.jfif import encode_jfif
    from omero_ms_image_region_tpu.ops.jpegenc import (
        HuffmanWireFetcher, finish_huffman_batch)

    img = blob_image(32, 32, seed=8, noise=4.0)
    y, cb, cr = coeffs_for(img, 85)
    bufs, cap, cap_words = _huffman_wire(y, cb, cr, 32, 32)
    f = HuffmanWireFetcher(32, 32, cap, cap_words)
    f.GRANULE = 16
    f._k = 24                       # force the completion path
    rows = f.fetch(bufs)
    got = finish_huffman_batch(rows, [(32, 32)], 32, 32, 85, cap,
                               cap_words)[0]
    assert got == encode_jfif(y, cb, cr, 32, 32, 85, huffman="fixed")


def test_render_batch_to_jpeg_huffman_engine_mixed_dims():
    """The serving helper's huffman engine: exact tiles via the device
    stream, bucket-padded ones via the dense path — every JPEG decodes
    at its own size and matches the sparse engine's pixels."""
    import io

    from PIL import Image

    from omero_ms_image_region_tpu.flagship import (
        batched_args, flagship_settings, synthetic_wsi_tiles)
    from omero_ms_image_region_tpu.ops.jpegenc import render_batch_to_jpeg

    rng = np.random.default_rng(3)
    B, C, H, W = 3, 2, 32, 32
    _, settings = flagship_settings(C)
    raw = synthetic_wsi_tiles(rng, B, C, H, W).astype(np.float32)
    args = batched_args(settings, raw)
    dims = [(32, 32), (20, 12), (32, 16)]   # exact, padded, padded
    got = render_batch_to_jpeg(*args, quality=85, dims=dims,
                               engine="huffman")
    want = render_batch_to_jpeg(*args, quality=85, dims=dims,
                                engine="sparse")
    for (w_, h_), g, s in zip(dims, got, want):
        gi = np.asarray(Image.open(io.BytesIO(g)).convert("RGB"),
                        np.int16)
        si = np.asarray(Image.open(io.BytesIO(s)).convert("RGB"),
                        np.int16)
        assert gi.shape == (h_, w_, 3) == si.shape
        # Same quantized coefficients, different entropy tables: pixels
        # decode identically.
        np.testing.assert_array_equal(gi, si)


# ------------------------------------------- device Huffman bit-packing

def test_fixed_huffman_spec_is_complete_and_valid():
    from omero_ms_image_region_tpu.jfif import fixed_huffman_spec
    dc_bits, dc_vals, dc_code, dc_len, ac_bits, ac_vals, ac_code, ac_len = \
        fixed_huffman_spec()
    assert set(dc_vals.tolist()) == set(range(12))
    legal_ac = {0x00, 0xF0} | {(r << 4) | s
                               for r in range(16) for s in range(1, 11)}
    assert set(ac_vals.tolist()) == legal_ac
    assert all(dc_len[s] > 0 for s in range(12))
    assert max(dc_len.max(), ac_len.max()) <= 16


def test_encode_tiles_jpeg_batch():
    imgs = np.stack([blob_image(32, 32, seed=s) for s in range(3)])
    packed = pack(imgs)
    outs = encode_tiles_jpeg(packed, quality=85)
    assert len(outs) == 3
    for img, data in zip(imgs, outs):
        dec = np.asarray(Image.open(io.BytesIO(data)).convert("RGB"))
        assert np.abs(dec.astype(np.float32) - img).mean() < 8.0


def test_high_quality_widens_wire_caps():
    """q >= 88 doubles the wire caps up front; a RESCUABLE overflow
    (fits at 2x) retries once at doubled caps and memoizes, while an
    unrescuable one goes straight to the per-tile dense path."""
    import omero_ms_image_region_tpu.ops.jpegenc as je

    rng = np.random.default_rng(40)
    B, C, H, W = 2, 1, 64, 64
    flat = np.zeros((B, C, H, W), np.float32)          # ~zero density
    noisy = rng.integers(0, 65535, size=(B, C, H, W)).astype(np.float32)
    ws = np.zeros((B, C), np.float32)
    we = np.full((B, C), 65535.0, np.float32)
    fam = np.zeros((B, C), np.int32)
    coef = np.ones((B, C), np.float32)
    rev = np.zeros((B, C), np.int32)
    tables = np.tile(np.array([[1.0, 1.0, 1.0]], np.float32),
                     (B, C, 1)).reshape(B, C, 3)
    base = je.default_sparse_cap(H, W)

    def probe_totals(raw):
        bufs = np.asarray(je.render_to_jpeg_sparse(
            raw, ws, we, fam, coef, rev, 0, 255, tables,
            *(np.asarray(t, np.int32) for t in je.quant_tables(80)),
            cap=je.max_sparse_cap(H, W)))
        return je.wire_header_i32(bufs, 0)

    # Mid-density content whose totals land in (cap, 2*cap]: a noise
    # band over a zero background, width found by probing.
    mid = None
    for band in range(6, W + 1, 2):
        cand = flat.copy()
        cand[:, :, :, :band] = noisy[:, :, :, :band]
        totals = probe_totals(cand)
        if (totals > base).all() and (totals <= 2 * base).all():
            mid = cand
            break
    assert mid is not None, "no mid-density band found"

    caps_seen = []
    dense_calls = []
    # The serving path dispatches through the compacted-wire wrapper
    # (render_batch_to_jpeg), so that is where per-group caps surface.
    orig = je.render_to_jpeg_sparse_compact
    orig_coeff = je.render_to_jpeg_coefficients

    def spy(*args, **kwargs):
        caps_seen.append(kwargs.get("cap"))
        return orig(*args, **kwargs)

    def spy_coeff(*args, **kwargs):
        # Count only HOST (dense-fallback) calls: jit tracing invokes
        # this with tracers, not ndarrays.
        if isinstance(args[0], np.ndarray):
            dense_calls.append(1)
        return orig_coeff(*args, **kwargs)

    je.render_to_jpeg_sparse_compact = spy
    je.render_to_jpeg_coefficients = spy_coeff
    try:
        def run(raw, q):
            caps_seen.clear()
            dense_calls.clear()
            jpegs = je.render_batch_to_jpeg(
                raw, ws, we, fam, coef, rev, 0, 255, tables,
                quality=q, dims=[(W, H)] * B, engine="sparse")
            assert all(j[:2] == b"\xff\xd8" for j in jpegs)
            return list(caps_seen), len(dense_calls)

        je._CAP_MEMO.clear()
        # Low density: one dispatch at the quality-appropriate cap.
        assert run(flat, 80) == ([base], 0)
        assert run(flat, 92) == ([2 * base], 0)
        # Unrescuable overflow (uniform noise >> 2x cap): no wasted
        # retry; tiles take the dense path.
        caps, dense = run(noisy, 80)
        assert caps == [base] and dense == B
        # Rescuable overflow: one retry at 2x, NO dense re-renders...
        je._CAP_MEMO.clear()
        assert run(mid, 80) == ([base, 2 * base], 0)
        # ...and the memo starts subsequent groups at 2x directly.
        assert run(mid, 80) == ([2 * base], 0)
    finally:
        je.render_to_jpeg_sparse_compact = orig
        je.render_to_jpeg_coefficients = orig_coeff
        je._CAP_MEMO.clear()


def test_huffman_rescuable_overflow_widens_once_and_memoizes():
    """The huffman engine's half of the one-shot widening: a group
    whose entries or bits land in (cap, 2 x cap] is dispatched once
    more with BOTH caps doubled and no dense re-render, and the memo
    starts the next group of that shape and quality at the doubled
    caps."""
    import omero_ms_image_region_tpu.ops.jpegenc as je

    rng = np.random.default_rng(40)
    B, C, H, W, Q = 2, 1, 64, 64, 80
    noisy = rng.integers(0, 65535, size=(B, C, H, W)).astype(np.float32)
    ws = np.zeros((B, C), np.float32)
    we = np.full((B, C), 65535.0, np.float32)
    fam = np.zeros((B, C), np.int32)
    coef = np.ones((B, C), np.float32)
    rev = np.zeros((B, C), np.int32)
    tables = np.ones((B, C, 3), np.float32)
    cap, words = je.default_sparse_cap(H, W, Q), je.default_words_cap(
        H, W, Q)
    spec = je.huffman_spec_arrays()
    qy, qc = (np.asarray(t, np.int32) for t in je.quant_tables(Q))

    def probe(raw):
        bufs = np.asarray(je.render_to_jpeg_huffman(
            raw, ws, we, fam, coef, rev, 0, 255, tables, qy, qc, *spec,
            h16=H // 16, w16=W // 16, cap=je.max_sparse_cap(H, W),
            cap_words=H * W))
        return je.wire_header_i32(bufs, 0), je.wire_header_i32(bufs, 1)

    mid = None
    for band in range(2, W + 1, 2):
        cand = np.zeros((B, C, H, W), np.float32)
        cand[:, :, :, :band] = noisy[:, :, :, :band]
        totals, bits = probe(cand)
        over = (totals > cap) | (bits > words * 32)
        fits_doubled = (totals <= 2 * cap) & (bits <= 2 * words * 32)
        if (over & fits_doubled).all():
            mid = cand
            break
    assert mid is not None, "no mid-density band found"

    launches, dense_calls = [], []
    orig = je.render_to_jpeg_huffman_compact
    orig_coeff = je.render_to_jpeg_coefficients

    def spy(*args, **kwargs):
        launches.append((kwargs["cap"], kwargs["cap_words"]))
        return orig(*args, **kwargs)

    def spy_coeff(*args, **kwargs):
        if isinstance(args[0], np.ndarray):     # host calls, not traces
            dense_calls.append(1)
        return orig_coeff(*args, **kwargs)

    def run():
        launches.clear()
        jpegs = je.render_batch_to_jpeg(
            mid, ws, we, fam, coef, rev, 0, 255, tables, quality=Q,
            dims=[(W, H)] * B, engine="huffman", tune=False)
        for j in jpegs:
            assert Image.open(io.BytesIO(j)).size == (W, H)
        return list(launches)

    je.render_to_jpeg_huffman_compact = spy
    je.render_to_jpeg_coefficients = spy_coeff
    je._CAP_MEMO.clear()
    try:
        assert run() == [(cap, words), (2 * cap, 2 * words)]
        assert run() == [(2 * cap, 2 * words)]
        assert not dense_calls
        # The sparse engine's memo is its own key.
        assert ("sparse", H, W, Q) not in je._CAP_MEMO
    finally:
        je.render_to_jpeg_huffman_compact = orig
        je.render_to_jpeg_coefficients = orig_coeff
        je._CAP_MEMO.clear()


# ---------------------------------------------------- compacted wire

class TestCompactWire:
    """Device-side wire compaction: the fetch carries exactly each
    row's used bytes, pad rows cost zero, and the compacted rows are
    byte-identical to the uncompacted wire's used prefixes."""

    def _args(self, B, C, H, W, seed=0, window=255.0):
        rng = np.random.default_rng(seed)
        # Smooth gradients (per-tile phase): small streams that stay
        # well under the tiny-tile default caps, sized differently per
        # row so compaction has real variance to pack.
        yy, xx = np.mgrid[0:H, 0:W].astype(np.float32)
        phase = rng.uniform(0, np.pi, size=(B, C, 1, 1)).astype(
            np.float32)
        freq = rng.uniform(1.0, 3.0, size=(B, C, 1, 1)).astype(
            np.float32)
        raw = 120.0 + 60.0 * np.sin(
            freq * (yy + xx)[None, None] / max(H, W) + phase)
        ws = np.zeros((B, C), np.float32)
        we = np.full((B, C), window, np.float32)
        fam = np.zeros((B, C), np.int32)
        coef = np.ones((B, C), np.float32)
        rev = np.zeros((B, C), np.bool_)
        tables = np.tile(np.array([[1.0, 0.8, 0.5]], np.float32),
                         (B, C, 1)).reshape(B, C, 3)
        return raw, ws, we, fam, coef, rev, tables

    def test_sparse_rows_match_uncompacted(self):
        from omero_ms_image_region_tpu.ops import jpegenc as je
        B, C, H, W = 4, 2, 32, 32
        raw, ws, we, fam, coef, rev, tables = self._args(B, C, H, W)
        qy, qc = (np.asarray(t, np.int32) for t in quant_tables(85))
        # Generous cap: parity is about layout, not overflow policy
        # (tiny-tile default caps are a 128-byte stream budget).
        cap = je.max_sparse_cap(H, W)
        full = np.asarray(je.render_to_jpeg_sparse(
            raw, ws, we, fam, coef, rev, 0, 255, tables, qy, qc,
            cap=cap))
        compact = np.asarray(je.render_to_jpeg_sparse_compact(
            raw, ws, we, fam, coef, rev, 0, 255, tables, qy, qc,
            np.int32(B), cap=cap))
        lengths = compact[:4 * B].view(np.int32)
        nb = (H // 16) * (W // 16) * 6
        offs = 4 * B + np.concatenate([[0], np.cumsum(lengths)])
        for i in range(B):
            total = int(full[i, :4].view(np.int32)[0])
            assert total <= cap
            need = 4 + nb + (je.ENTRY_BITS * total + 7) // 8
            assert lengths[i] == need
            row = compact[offs[i]:offs[i + 1]]
            np.testing.assert_array_equal(row, full[i, :need])

    @pytest.mark.parametrize("B", [1, 2, 3, 4, 6, 8])
    def test_huffman_rows_match_uncompacted(self, B):
        from omero_ms_image_region_tpu.ops import jpegenc as je
        C, H, W = 1, 32, 32
        raw, ws, we, fam, coef, rev, tables = self._args(B, C, H, W, 1)
        qy, qc = (np.asarray(t, np.int32) for t in quant_tables(85))
        cap = je.max_sparse_cap(H, W)
        cap_words = H * W           # generous: parity, not overflow
        spec = je.huffman_spec_arrays()
        full = np.asarray(je.render_to_jpeg_huffman(
            raw, ws, we, fam, coef, rev, 0, 255, tables, qy, qc, *spec,
            h16=H // 16, w16=W // 16, cap=cap, cap_words=cap_words))
        compact = np.asarray(je.render_to_jpeg_huffman_compact(
            raw, ws, we, fam, coef, rev, 0, 255, tables, qy, qc, *spec,
            np.int32(B), h16=H // 16, w16=W // 16, cap=cap,
            cap_words=cap_words))
        lengths = compact[:4 * B].view(np.int32)
        offs = 4 * B + np.concatenate([[0], np.cumsum(lengths)])
        for i in range(B):
            bits = int(full[i, 4:8].view(np.int32)[0])
            need = 8 + 4 * ((bits + 31) // 32)
            assert lengths[i] == need
            np.testing.assert_array_equal(
                compact[offs[i]:offs[i + 1]], full[i, :need])

    def test_pad_rows_cost_zero_wire_bytes(self):
        from omero_ms_image_region_tpu.ops import jpegenc as je
        B, C, H, W = 4, 1, 32, 32
        raw, ws, we, fam, coef, rev, tables = self._args(B, C, H, W, 2)
        qy, qc = (np.asarray(t, np.int32) for t in quant_tables(85))
        cap = je.max_sparse_cap(H, W)
        compact = np.asarray(je.render_to_jpeg_sparse_compact(
            raw, ws, we, fam, coef, rev, 0, 255, tables, qy, qc,
            np.int32(2), cap=cap))
        lengths = compact[:4 * B].view(np.int32)
        assert (lengths[:2] > 0).all()
        assert (lengths[2:] == 0).all()

    def test_overflow_row_compacts_to_header(self):
        from omero_ms_image_region_tpu.ops import jpegenc as je
        B, C, H, W = 2, 1, 32, 32
        rng = np.random.default_rng(3)
        # Uniform noise: dense coefficients, guaranteed cap overflow.
        raw = rng.uniform(0, 255, size=(B, C, H, W)).astype(np.float32)
        ws = np.zeros((B, C), np.float32)
        we = np.full((B, C), 255.0, np.float32)
        fam = np.zeros((B, C), np.int32)
        coef = np.ones((B, C), np.float32)
        rev = np.zeros((B, C), np.bool_)
        tables = np.ones((B, C, 3), np.float32)
        qy, qc = (np.asarray(t, np.int32) for t in quant_tables(85))
        cap = 8   # tiny: force overflow
        nb = (H // 16) * (W // 16) * 6
        compact = np.asarray(je.render_to_jpeg_sparse_compact(
            raw, ws, we, fam, coef, rev, 0, 255, tables, qy, qc,
            np.int32(B), cap=cap))
        lengths = compact[:4 * B].view(np.int32)
        # Overflowed rows ship header + counts only (detectable, small).
        assert (lengths == 4 + nb).all()
        row0 = compact[4 * B:4 * B + lengths[0]]
        assert je.row_header_i32(row0, 0) > cap

    def test_fetcher_roundtrip_and_prediction(self):
        from omero_ms_image_region_tpu.ops import jpegenc as je
        B, C, H, W = 4, 2, 32, 32
        raw, ws, we, fam, coef, rev, tables = self._args(B, C, H, W, 4)
        qy, qc = (np.asarray(t, np.int32) for t in quant_tables(85))
        cap = je.max_sparse_cap(H, W)
        buf = je.render_to_jpeg_sparse_compact(
            raw, ws, we, fam, coef, rev, 0, 255, tables, qy, qc,
            np.int32(B), cap=cap)
        width = je.sparse_wire_width(H, W, cap)
        f = je.CompactWireFetcher(B, width)
        f._k = f.hdr            # force an under-prediction second fetch
        rows = f.fetch(buf)
        full = np.asarray(buf)
        lengths = full[:4 * B].view(np.int32)
        offs = 4 * B + np.concatenate([[0], np.cumsum(lengths)])
        assert len(rows) == B
        for i in range(B):
            np.testing.assert_array_equal(rows[i],
                                          full[offs[i]:offs[i + 1]])
        # Miss raised the headroom; an on-target fetch decays it.
        assert f.headroom > f.HEADROOM_FLOOR
        hr = f.headroom
        f.fetch(buf)
        assert f.headroom <= hr

    @pytest.mark.parametrize("engine", ["sparse", "huffman"])
    def test_under_predicted_fetch_serves_the_same_bytes(self, engine):
        """A group whose prefix prediction fell short pays one
        ``wire.fetch2`` and answers with the bytes a well-predicted
        fetch gives; the miss retrains the shared fetcher."""
        from omero_ms_image_region_tpu.ops import jpegenc as je
        from omero_ms_image_region_tpu.utils.stopwatch import REGISTRY
        # A quality no other test serves at this shape: the cap memo
        # and the fetcher of this key are this test's own.
        B, C, H, W, Q = 4, 2, 32, 32, 83
        raw, ws, we, fam, coef, rev, tables = self._args(B, C, H, W, 6)

        def second_fetches():
            return REGISTRY.snapshot().get("wire.fetch2",
                                           {}).get("count", 0)

        def serve():
            before = second_fetches()
            jpegs = je.render_batch_to_jpeg(
                raw, ws, we, fam, coef, rev, 0, 255, tables, quality=Q,
                dims=[(W, H)] * B, engine=engine, tune=False)
            return jpegs, second_fetches() - before

        f = je.compact_fetcher(
            engine, H, W, je.default_sparse_cap(H, W, Q),
            je.default_words_cap(H, W, Q) if engine == "huffman" else 0,
            B)
        f._k = f.hdr                 # the prefix holds the lengths only
        missed, n_missed = serve()
        assert n_missed == 1 and f.headroom > f.HEADROOM_FLOOR
        again, n_again = serve()
        assert n_again == 0
        assert missed == again
        for j in missed:
            assert Image.open(io.BytesIO(j)).size == (W, H)

    def test_batch_to_jpeg_end_to_end_decodable(self):
        from omero_ms_image_region_tpu.ops import jpegenc as je
        B, C, H, W = 3, 2, 32, 32
        raw, ws, we, fam, coef, rev, tables = self._args(B, C, H, W, 5)
        for engine in ("sparse", "huffman"):
            jpegs = je.render_batch_to_jpeg(
                raw, ws, we, fam, coef, rev, 0, 255, tables,
                quality=85, dims=[(W, H)] * B, engine=engine)
            assert len(jpegs) == B
            for j in jpegs:
                img = Image.open(io.BytesIO(j))
                assert img.size == (W, H)


# ------------------------------------------------- tuned huffman tables

class TestTunedHuffmanTables:
    """Per-workload tuned Huffman tables on the device wire: same
    coefficients, smaller streams, every legal symbol still encodable."""

    def _batch(self, seed=0, B=3, C=2, H=64, W=64):
        # Gentle content (sigma-2 noise): streams stay inside the wire
        # word budget, so every tile serves from the device stream and
        # the size comparison measures the TABLES, not the dense-
        # fallback policy (denser content is covered by the drift
        # test, where tuned tables RESCUE tiles from the fallback).
        rng = np.random.default_rng(seed)
        yy, xx = np.mgrid[0:H, 0:W].astype(np.float32)
        phase = rng.uniform(0, np.pi, size=(B, C, 1, 1)).astype(
            np.float32)
        raw = 120.0 + 60.0 * np.sin((yy + xx)[None, None] / 24 + phase)
        raw += rng.normal(0, 2.0, raw.shape).astype(np.float32)
        ws = np.zeros((B, C), np.float32)
        we = np.full((B, C), 255.0, np.float32)
        fam = np.zeros((B, C), np.int32)
        coef = np.ones((B, C), np.float32)
        rev = np.zeros((B, C), np.bool_)
        tables = np.tile(np.array([[1.0, 0.8, 0.5]], np.float32),
                         (B, C, 1)).reshape(B, C, 3)
        return raw, ws, we, fam, coef, rev, tables

    def _clear(self):
        from omero_ms_image_region_tpu.ops import jpegenc as je
        with je._TUNED_LOCK:
            je._TUNED_TABLES.clear()
            je._TUNED_PENDING.clear()

    def test_tuned_spec_every_legal_symbol_coded(self):
        from omero_ms_image_region_tpu.jfif import tuned_huffman_spec
        spec = tuned_huffman_spec(np.zeros(256, np.int64),
                                  np.zeros(256, np.int64))
        _, _, dc_code, dc_len, _, _, ac_code, ac_len = spec
        for s in range(12):
            assert dc_len[s] > 0
        for run in range(16):
            for size in range(1, 11):
                assert ac_len[(run << 4) | size] > 0
        assert ac_len[0x00] > 0 and ac_len[0xF0] > 0
        assert int(dc_len.max()) <= 16 and int(ac_len.max()) <= 16

    def test_tuned_batch_same_pixels_smaller_bytes(self):
        """render_batch_to_jpeg with tuned tables published: decoded
        pixels identical to the fixed-profile run (same coefficients),
        streams smaller on the measured content class."""
        from omero_ms_image_region_tpu.ops import jpegenc as je

        args = self._batch()
        B, C, H, W = args[0].shape
        full = args[:6] + (0, 255, args[6])
        dims = [(W, H)] * B
        self._clear()
        try:
            fixed = je.render_batch_to_jpeg(
                *full, quality=85, dims=dims, engine="huffman")
            # Publish tuned tables synchronously (the serving path
            # kicked off a background thread; tests want determinism).
            key = (H, W, 85)
            with je._TUNED_LOCK:
                je._TUNED_TABLES.pop(key, None)
                je._TUNED_PENDING.clear()
            qy, qc = (np.asarray(t, np.int32)
                      for t in je.quant_tables(85))

            def dense0(i):
                y, cb, cr = je.render_to_jpeg_coefficients(
                    args[0][i:i + 1], *(a[i:i + 1] for a in args[1:6]),
                    0, 255, args[6][i:i + 1], qy, qc)
                return (np.asarray(y)[0], np.asarray(cb)[0],
                        np.asarray(cr)[0])

            je._compute_tuned_tables(key, dense0)
            assert je._TUNED_TABLES[key] is not None
            tuned = je.render_batch_to_jpeg(
                *full, quality=85, dims=dims, engine="huffman")
        finally:
            self._clear()
        for f, t in zip(fixed, tuned):
            pf = np.asarray(Image.open(io.BytesIO(f)).convert("RGB"))
            pt = np.asarray(Image.open(io.BytesIO(t)).convert("RGB"))
            np.testing.assert_array_equal(pf, pt)
        assert sum(map(len, tuned)) < sum(map(len, fixed))

    def test_tuned_tables_survive_content_drift(self):
        """Tables tuned on smooth content must still encode NOISE
        (every legal symbol has a code); overflow falls back densely
        rather than failing."""
        from omero_ms_image_region_tpu.ops import jpegenc as je

        args = self._batch(seed=1)
        B, C, H, W = args[0].shape
        key = (H, W, 85)
        self._clear()
        try:
            qy, qc = (np.asarray(t, np.int32)
                      for t in je.quant_tables(85))

            def dense0(i):
                y, cb, cr = je.render_to_jpeg_coefficients(
                    args[0][i:i + 1], *(a[i:i + 1] for a in args[1:6]),
                    0, 255, args[6][i:i + 1], qy, qc)
                return (np.asarray(y)[0], np.asarray(cb)[0],
                        np.asarray(cr)[0])

            je._compute_tuned_tables(key, dense0)
            rng = np.random.default_rng(2)
            noise_raw = rng.uniform(0, 255, args[0].shape).astype(
                np.float32)
            jpegs = je.render_batch_to_jpeg(
                noise_raw, *args[1:6], 0, 255, args[6], quality=85,
                dims=[(W, H)] * B, engine="huffman")
        finally:
            self._clear()
        for j in jpegs:
            assert Image.open(io.BytesIO(j)).size == (W, H)

    def test_background_tuning_kicks_in(self):
        """The serving path publishes tuned tables after the first
        group and uses them for later groups."""
        import time

        from omero_ms_image_region_tpu.ops import jpegenc as je

        args = self._batch(seed=3)
        B, C, H, W = args[0].shape
        full = args[:6] + (0, 255, args[6])
        self._clear()
        try:
            je.render_batch_to_jpeg(*full, quality=85,
                                    dims=[(W, H)] * B, engine="huffman")
            for _ in range(100):            # background thread
                if (H, W, 85) in je._TUNED_TABLES:
                    break
                time.sleep(0.1)
            assert je._TUNED_TABLES.get((H, W, 85)) is not None
        finally:
            self._clear()

    def test_prewarm_never_seeds_tuning(self):
        """All-zero compile probes (tune=False) must not publish
        tables fitted to black content."""
        from omero_ms_image_region_tpu.ops import jpegenc as je

        args = self._batch(seed=4)
        B, C, H, W = args[0].shape
        full = (np.zeros_like(args[0]),) + args[1:6] + (0, 255, args[6])
        self._clear()
        try:
            je.render_batch_to_jpeg(*full, quality=85,
                                    dims=[(W, H)] * B, engine="huffman",
                                    tune=False)
            import time
            time.sleep(0.3)
            assert (H, W, 85) not in je._TUNED_TABLES
            assert not je._TUNED_PENDING
        finally:
            self._clear()

    def test_zrl_code_bounded_for_device_fold(self):
        """The device packer folds up to 3 ZRL codes into one 32-bit
        deposit: tuned tables must keep ZRL <= 10 bits even when the
        sample contains no runs at all (ZRL at the long-code end would
        silently corrupt the packed stream)."""
        from omero_ms_image_region_tpu.jfif import tuned_huffman_spec

        # Adversarial stats: heavy mass on many symbols, ZRL unseen.
        ac = np.zeros(256, np.int64)
        for run in range(16):
            for size in range(1, 11):
                ac[(run << 4) | size] = 1_000_000
        ac[0x00] = 50_000_000
        ac[0xF0] = 0                       # never observed
        dc = np.zeros(256, np.int64)
        dc[0] = 1_000_000
        spec = tuned_huffman_spec(dc, ac)
        assert int(spec[7][0xF0]) <= 10

    def test_tuned_run_content_with_zrl_runs(self):
        """Content with >=16-zero runs (sparse isolated spikes) must
        encode and decode correctly through tuned tables built from
        run-free content — the ZRL fold bound end to end."""
        from omero_ms_image_region_tpu.ops import jpegenc as je

        args = self._batch(seed=6)
        B, C, H, W = args[0].shape
        key = (H, W, 85)
        self._clear()
        try:
            qy, qc = (np.asarray(t, np.int32)
                      for t in je.quant_tables(85))

            def dense0(i):
                y, cb, cr = je.render_to_jpeg_coefficients(
                    args[0][i:i + 1], *(a[i:i + 1] for a in args[1:6]),
                    0, 255, args[6][i:i + 1], qy, qc)
                return (np.asarray(y)[0], np.asarray(cb)[0],
                        np.asarray(cr)[0])

            je._compute_tuned_tables(key, dense0)
            spikes = np.full(args[0].shape, 128.0, np.float32)
            spikes[:, :, ::16, ::24] = 255.0     # isolated spikes
            jpegs = je.render_batch_to_jpeg(
                spikes, *args[1:6], 0, 255, args[6], quality=85,
                dims=[(W, H)] * B, engine="huffman")
            ref = je.render_batch_to_jpeg(
                spikes, *args[1:6], 0, 255, args[6], quality=85,
                dims=[(W, H)] * B, engine="sparse")
        finally:
            self._clear()
        for jh, js in zip(jpegs, ref):
            ph = np.asarray(Image.open(io.BytesIO(jh)).convert("RGB"))
            ps = np.asarray(Image.open(io.BytesIO(js)).convert("RGB"))
            np.testing.assert_array_equal(ph, ps)


# ------------------------------------- refimpl golden bit-exactness

class TestFusedPathsMatchRefimplGolden:
    """Every fused/restructured render+encode variant produces bytes
    IDENTICAL to an encode of the refimpl golden render's pixels —
    the tier-1 contract that lets kernel surgery (the round-6 scatter
    restructures, deposit coalescing, compaction rewrite) land without
    any chance of silently changing served bytes.

    The golden: ``refimpl.render_ref`` (jax-free numpy, the reference
    Renderer semantics) renders the same raw planes; its RGBA feeds
    the SAME coefficient front end; the host entropy coders frame the
    result.  Any divergence — render, DCT/quant, wire packing,
    compaction, entropy coding — breaks byte equality.
    """

    B, C, H, W = 3, 2, 32, 32
    QUALITY = 85

    def _case(self):
        from omero_ms_image_region_tpu.flagship import (
            batched_args, flagship_settings, synthetic_wsi_tiles)
        from omero_ms_image_region_tpu.refimpl import render_ref

        rng = np.random.default_rng(42)
        rdef, settings = flagship_settings(self.C)
        # Soft content: scaled-down blobs over a mid-window pedestal,
        # so every tile's stream stays WITHIN the default wire caps —
        # this golden pins the DEVICE stream's bytes; the overflow
        # fallback path has its own coverage above, and a cap overflow
        # here would silently swap in the per-tile optimal encoder
        # (valid JPEG, different framing) and void the comparison.
        raw = (synthetic_wsi_tiles(
            rng, self.B, self.C, self.H, self.W).astype(np.float32)
            / 8.0 + 15000.0)
        args = batched_args(settings, raw)
        golden_rgba = [render_ref(raw[i], rdef) for i in range(self.B)]
        # Overflow guard: nonzero coefficients per tile must be under
        # the default sparse cap (see above).
        from omero_ms_image_region_tpu.ops.jpegenc import (
            default_sparse_cap)
        cap = default_sparse_cap(self.H, self.W, self.QUALITY)
        for i, rgba in enumerate(golden_rgba):
            y, cb, cr = self._golden_coeffs(rgba)
            nnz = sum(int(np.count_nonzero(a)) for a in (y, cb, cr))
            assert nnz <= cap, \
                f"tile {i} content too dense for the golden ({nnz})"
        return args, golden_rgba

    def _golden_coeffs(self, rgba):
        from omero_ms_image_region_tpu.ops.jpegenc import (
            rgb_to_jpeg_coefficients)
        qy, qc = (t.astype(np.int32)
                  for t in quant_tables(self.QUALITY))
        y, cb, cr = rgb_to_jpeg_coefficients(
            rgba[None, ..., :3].astype(np.float32), qy, qc)
        return np.asarray(y)[0], np.asarray(cb)[0], np.asarray(cr)[0]

    def test_sparse_engine_bytes_match_golden(self):
        from omero_ms_image_region_tpu.ops.jpegenc import (
            dense_encoder, render_batch_to_jpeg)

        args, golden_rgba = self._case()
        got = render_batch_to_jpeg(
            *args, quality=self.QUALITY,
            dims=[(self.W, self.H)] * self.B, engine="sparse")
        encode = dense_encoder()
        for i in range(self.B):
            want = encode(*self._golden_coeffs(golden_rgba[i]),
                          self.W, self.H, self.QUALITY)
            assert got[i] == want, f"tile {i}: sparse bytes diverged"

    def test_huffman_engine_bytes_match_golden(self):
        from omero_ms_image_region_tpu.ops import jpegenc as je
        from omero_ms_image_region_tpu.ops.jpegenc import (
            render_batch_to_jpeg)

        args, golden_rgba = self._case()
        # tune=False pins the fixed tables so the golden framing below
        # (huffman="fixed") states exactly what coded the stream — and
        # any tuned tables another test already published for this
        # (shape, quality) are stashed aside, or they would code the
        # stream instead.
        with je._TUNED_LOCK:
            stash = je._TUNED_TABLES.pop((self.H, self.W,
                                          self.QUALITY), None)
        try:
            got = render_batch_to_jpeg(
                *args, quality=self.QUALITY,
                dims=[(self.W, self.H)] * self.B, engine="huffman",
                tune=False)
        finally:
            if stash is not None:
                with je._TUNED_LOCK:
                    je._TUNED_TABLES[(self.H, self.W,
                                      self.QUALITY)] = stash
        for i in range(self.B):
            y, cb, cr = self._golden_coeffs(golden_rgba[i])
            want = encode_jfif(y, cb, cr, self.W, self.H,
                               self.QUALITY, huffman="fixed")
            assert got[i] == want, f"tile {i}: huffman bytes diverged"

    def test_fused_coefficients_match_golden_render(self):
        """The fused render->DCT front end sees EXACTLY the refimpl
        pixels: coefficients from the one-dispatch fused kernel equal
        coefficients computed from the golden RGBA."""
        from omero_ms_image_region_tpu.ops.jpegenc import (
            render_to_jpeg_coefficients)

        args, golden_rgba = self._case()
        qy, qc = (t.astype(np.int32)
                  for t in quant_tables(self.QUALITY))
        y, cb, cr = (np.asarray(a) for a in
                     render_to_jpeg_coefficients(*args, qy, qc))
        for i in range(self.B):
            gy, gcb, gcr = self._golden_coeffs(golden_rgba[i])
            np.testing.assert_array_equal(y[i], gy)
            np.testing.assert_array_equal(cb[i], gcb)
            np.testing.assert_array_equal(cr[i], gcr)

    def test_compacted_wire_restructure_is_byte_stable(self):
        """The unique-set-scatter _compact_rows rewrite reproduces the
        reference compaction byte-for-byte, including zero-length
        (pad) rows and ragged lengths."""
        import jax.numpy as jnp
        from omero_ms_image_region_tpu.ops import jpegenc as je

        rng = np.random.default_rng(9)
        bufs = rng.integers(0, 256, size=(5, 97), dtype=np.uint8)
        lengths = np.array([97, 0, 13, 96, 1], np.int32)
        got = np.asarray(je._compact_rows(jnp.asarray(bufs),
                                          jnp.asarray(lengths)))
        # Reference semantics, plain numpy.
        want = np.zeros(4 * 5 + 5 * 97, np.uint8)
        want[:20] = lengths.astype("<i4").view(np.uint8)
        off = 20
        for row, ln in zip(bufs, lengths):
            want[off:off + ln] = row[:ln]
            off += ln
        np.testing.assert_array_equal(got, want)


# ------------- the wire compactions against plain numpy (no scatter)

def _random_coeffs(rng, B, nby, density):
    """i16 coefficient arrays in the module's layout, nonzero at
    ``density`` (0 -> none, 1 -> every slot)."""
    def plane(nb):
        v = rng.integers(1, 2048, size=(B, nb, 64))
        v *= rng.choice([-1, 1], size=v.shape)
        keep = rng.random(v.shape) < density
        return np.where(keep, v, 0).astype(np.int16)
    return plane(nby), plane(nby // 4), plane(nby // 4)


def _numpy_sparse_wire(y, cb, cr, cap):
    """The wire layout of ``sparse_pack``'s docstring, written out with
    boolean indexing and ``np.packbits``: u8[B, 4 + nb + ceil(18 cap / 8)]."""
    B = y.shape[0]
    flat = np.concatenate([a.reshape(B, -1) for a in (y, cb, cr)],
                          axis=1).astype(np.int32)
    nb = flat.shape[1] // 64
    field = ((np.arange(flat.shape[1]) % 64) << 12) | (flat & 0xFFF)
    rows = []
    for b in range(B):
        mask = flat[b] != 0
        kept = field[b][mask][:cap]
        comp = np.zeros(cap, np.int64)
        comp[:kept.size] = kept
        bits = ((comp[:, None] >> np.arange(17, -1, -1)) & 1).astype(np.uint8)
        rows.append(np.concatenate([
            np.array([mask.sum()], "<i4").view(np.uint8),
            mask.reshape(nb, 64).sum(-1).astype(np.uint8),
            np.packbits(bits.reshape(-1))]))
    return np.stack(rows)


@pytest.mark.parametrize("cap_kind", ["under", "at", "over", "odd"])
@pytest.mark.parametrize("density", [0.0, 0.03, 0.12, 0.5, 1.0])
def test_entry_compaction_matches_boolean_indexing(density, cap_kind):
    """``_compact_entries`` is ``field[keep][:cap]`` zero-filled, whole
    rows compared: no entry lost, none out of order, zeros past the
    total, at every density and with the total under, at and over
    ``cap`` (and a ``cap`` that is no multiple of 4)."""
    import jax.numpy as jnp
    from omero_ms_image_region_tpu.ops import jpegenc as je

    rng = np.random.default_rng(int(density * 100) + len(cap_kind))
    B, N = 3, 6144 + 384                 # not a power of two
    keep = rng.random((B, N)) < density
    keep[1, :] &= np.arange(N) > N // 2  # a long leading run of zeros
    field = np.where(keep, rng.integers(1, 1 << 18, size=(B, N)), 0)
    total = int(keep.sum(axis=1).max())
    cap = {"under": max(total - 7, 1), "at": max(total, 1),
           "over": total + 64, "odd": 4 * (total // 8) + 3}[cap_kind]
    wi = np.cumsum(keep, axis=1) - 1
    got = np.asarray(je._compact_entries(
        jnp.asarray(field, jnp.int32), jnp.asarray(keep),
        jnp.asarray(wi, jnp.int32), cap))
    want = np.zeros((B, cap), np.int32)
    for b in range(B):
        kept = field[b][keep[b]][:cap]
        want[b, :kept.size] = kept
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("cap_kind", ["under", "at", "over", "odd"])
@pytest.mark.parametrize("density", [0.0, 0.03, 0.12, 0.5, 1.0])
def test_sparse_pack_whole_buffer_matches_numpy(density, cap_kind):
    """Header, counts and the 18-bit stream, zeros past the used total
    included, against the numpy wire: the stream assembly (four entries
    are nine bytes) at caps that are and are not multiples of 4."""
    rng = np.random.default_rng(7 + int(density * 100))
    y, cb, cr = _random_coeffs(rng, 2, 16, density)      # a 32x32 tile
    total = max(int(sum((a[b] != 0).sum() for a in (y, cb, cr)))
                for b in range(2))
    cap = {"under": max(total - 5, 1), "at": max(total, 1),
           "over": total + 32, "odd": 4 * (total // 8) + 1}[cap_kind]
    got = np.asarray(sparse_pack(y, cb, cr, cap))
    np.testing.assert_array_equal(got, _numpy_sparse_wire(y, cb, cr, cap))


@pytest.mark.parametrize("pattern", ["ragged", "pads", "full", "empty"])
@pytest.mark.parametrize("B", [1, 5, 8, 64])
def test_compact_rows_matches_ragged_concat(B, pattern):
    """``_compact_rows`` is the lengths header and the ragged concat of
    the rows' prefixes, zeros to the end: zero-length (pad) rows first,
    last and in the middle, every row whole, every row empty."""
    import jax.numpy as jnp
    from omero_ms_image_region_tpu.ops import jpegenc as je

    rng = np.random.default_rng(B * 10 + len(pattern))
    width = 1531                          # odd, not a multiple of 4
    bufs = rng.integers(1, 256, size=(B, width), dtype=np.uint8)
    lengths = rng.integers(0, width + 1, size=B).astype(np.int32)
    if pattern == "pads":
        lengths[[0, B // 2, B - 1]] = 0
    elif pattern == "full":
        lengths[:] = width
    elif pattern == "empty":
        lengths[:] = 0
    got = np.asarray(je._compact_rows(jnp.asarray(bufs),
                                      jnp.asarray(lengths)))
    want = np.zeros(4 * B + B * width, np.uint8)
    want[:4 * B] = lengths.astype("<i4").view(np.uint8)
    ragged = np.concatenate([row[:n] for row, n in zip(bufs, lengths)])
    want[4 * B:4 * B + ragged.size] = ragged
    np.testing.assert_array_equal(got, want)


# ------------------------------------ the two halves of the served path

_HALVES_SHAPE = (3, 2, 64, 64)
_HALVES_Q = 77          # no other test serves this shape at this quality


def _halves_batch():
    """Three tiles of rising density: a smooth field under noise of
    sigma 0.5, 2 and 4 grey levels (299, 379 and 595 entries, under
    the default cap of 768 and its 4,096 bits)."""
    B, C, H, W = _HALVES_SHAPE
    rng = np.random.default_rng(31)
    yy, xx = np.mgrid[0:H, 0:W].astype(np.float32)
    raw = np.broadcast_to(120.0 + 30.0 * np.sin((yy + xx) / 24.0),
                          (B, C, H, W)).copy()
    raw += rng.normal(0, 1.0, raw.shape).astype(np.float32) \
        * np.array([0.5, 2.0, 4.0], np.float32)[:, None, None, None]
    ws = np.zeros((B, C), np.float32)
    we = np.full((B, C), 255.0, np.float32)
    fam = np.zeros((B, C), np.int32)
    coef = np.ones((B, C), np.float32)
    rev = np.zeros((B, C), np.bool_)
    tables = np.tile(np.array([[1.0, 0.8, 0.5]], np.float32),
                     (B, C, 1)).reshape(B, C, 3)
    return raw.astype(np.float32), ws, we, fam, coef, rev, 0, 255, tables


@pytest.mark.parametrize("case", ["exact", "padded", "retry", "dense"])
@pytest.mark.parametrize("engine", ["sparse", "huffman"])
def test_the_two_halves_give_the_bytes_of_the_composed_call(
        engine, case, monkeypatch):
    """``render_batch_to_wire`` then ``finish_wire_to_jpegs``, called
    apart as the batcher calls them, return what
    ``render_batch_to_jpeg`` returns, byte for byte, and ``on_tile``
    fires for the same tiles with the same bytes: on
    grid-exact and bucket-padded ``dims``, through the one-shot cap
    widening, and with a tile that overflows the doubled cap too and
    is coded from its dense coefficients."""
    import omero_ms_image_region_tpu.ops.jpegenc as je

    B, C, H, W = _HALVES_SHAPE
    Q = _HALVES_Q
    args = _halves_batch()
    dims = [(W, H), (60, 50), (W, H)]       # 50 x 60 rounds up to 64^2
    if case == "padded":
        dims = [(W, H), (32, 32), (40, 56)]
    default_cap = je.default_sparse_cap(H, W, Q)

    def forget():
        for e in ("sparse", "huffman"):
            je._CAP_MEMO.pop((e, H, W, Q), None)

    dense_calls = []
    real_coeff = je.render_to_jpeg_coefficients

    def spy_coeff(*a, **kw):
        if isinstance(a[0], np.ndarray):        # host calls, not traces
            dense_calls.append(1)
        return real_coeff(*a, **kw)

    monkeypatch.setattr(je, "render_to_jpeg_coefficients", spy_coeff)
    forget()
    try:
        # Each tile's entries, from the headers of an uncapped dispatch.
        probe = je.render_batch_to_wire(
            *args, quality=Q, dims=dims, engine=engine,
            cap=je.max_sparse_cap(H, W))
        totals = sorted(je.row_header_i32(r, 0) for r in probe.rows)
        assert totals[0] < totals[1] < totals[2] - 1
        assert totals[2] <= default_cap
        # ``retry``: the densest tile lands in (cap, 2 x cap].
        # ``dense``: the middle one does, and the densest is over the
        # doubled cap as well.
        cap = {"retry": totals[2] * 2 // 3,
               "dense": (totals[1] + 1) // 2}.get(case)

        def composed():
            forget()
            fired = []
            jpegs = je.render_batch_to_jpeg(
                *args, quality=Q, dims=dims, engine=engine, cap=cap,
                tune=False, on_tile=lambda i, d: fired.append((i, d)))
            return jpegs, fired

        def apart():
            forget()
            fired = []
            wire = je.render_batch_to_wire(
                *args, quality=Q, dims=dims, engine=engine, cap=cap)
            jpegs = je.finish_wire_to_jpegs(
                wire, tune=False,
                on_tile=lambda i, d: fired.append((i, d)))
            return jpegs, fired, wire

        want, want_fired = composed()
        dense_calls.clear()
        got, got_fired, wire = apart()
    finally:
        forget()
    # Once a tile, in no promised order since PR 37 (the sparse tail's
    # tiles are coded side by side).
    got_fired, want_fired = sorted(got_fired), sorted(want_fired)
    assert got == want and got_fired == want_fired
    assert [i for i, _ in got_fired] == list(range(B))
    assert [d for _, d in got_fired] == got
    for j, (w_, h_) in zip(got, dims):
        assert Image.open(io.BytesIO(j)).size == (w_, h_)
    # Each case took the path it is named for.
    assert wire.engine == ("sparse" if case == "padded" else engine)
    assert wire.cap == (default_cap if cap is None else 2 * cap)
    assert bool(dense_calls) == (case == "dense")


# ------------------------------------------- the 4:2:0 chroma subsample

def _plain_chroma_mean(x):
    """The subsample as ``jpeg.ycbcr420`` wrote it until PR 35, kept
    here as the plain reference (f32; a numpy or a traced array)."""
    B, H, W = x.shape
    return x.reshape(B, H // 2, 2, W // 2, 2).mean((2, 4))


def _f32_sums_of_four(x):
    """Every f32 result that adding a 2 x 2 block's four samples can
    give: 12 running sums and 3 sums of two pairs."""
    import itertools
    s = [x[:, i::2, j::2] for i in (0, 1) for j in (0, 1)]
    sums = [((s[a] + s[b]) + s[c]) + s[d]
            for a, b, c, d in itertools.permutations(range(4)) if a < b]
    sums += [(s[0] + s[a]) + (s[b] + s[c])
             for a, b, c in ((1, 2, 3), (2, 1, 3), (3, 1, 2))]
    return sums


@pytest.mark.parametrize("content", ["seeded", "rails", "checkerboard"])
@pytest.mark.parametrize("shape", [(2, 16, 16), (2, 256, 256),
                                   (1, 1088, 1088), (3, 64, 2048)])
def test_chroma_mean_is_the_plain_f32_mean_of_four(shape, content):
    """The pooled subsample against the plain reshape-and-mean.  On the
    rails and on a checkerboard every partial sum is exact, so the two
    agree to the last bit whatever the order of the adds.  On seeded
    planes an f32 sum of four depends on that order, which the compiler
    chooses a program (numpy adds each row's pair, then the rows): each
    output has to be one of the 15 f32 sums of its own four samples,
    times 0.25 -- an f32 mean of the four, and nothing looser."""
    import jax

    from omero_ms_image_region_tpu.ops.jpegenc import _chroma_mean_2x2
    B, H, W = shape
    if content == "seeded":
        x = np.random.default_rng(H * W).uniform(
            -127.5, 127.5, shape).astype(np.float32)
    elif content == "rails":
        x = np.where(np.random.default_rng(H + W).random(shape) < 0.5,
                     np.float32(-127.5), np.float32(127.5))
        x[0, :2, :2] = 127.5
        x[0, 2:4, :2] = -127.5
    else:
        yy, xx = np.mgrid[0:H, 0:W]
        x = np.broadcast_to(np.where((yy + xx) % 2, -127.5, 127.5),
                            shape).astype(np.float32)
    got = np.asarray(jax.jit(_chroma_mean_2x2)(x))
    want = _plain_chroma_mean(x)
    assert got.dtype == np.float32 and got.shape == want.shape
    if content == "seeded":
        some_order = np.zeros(got.shape, bool)
        for total in _f32_sums_of_four(x):
            some_order |= got == total * np.float32(0.25)
        assert some_order.all()
    else:
        np.testing.assert_array_equal(got, want)
        if content == "rails":
            assert got[0, 0, 0] == 127.5 and got[0, 1, 0] == -127.5


@pytest.mark.parametrize("seed,B,H,W,noise", [
    (11, 3, 32, 32, 0.0), (12, 2, 64, 96, 6.0), (13, 1, 256, 256, 2.0)])
def test_front_end_gives_the_reshape_formulations_coefficients(
        seed, B, H, W, noise):
    """``packed_to_jpeg_coefficients`` against the front end as it was
    until PR 35 (the chroma mean a reshape to ``[..., W/2, 2]``), on
    seeded tiles: the same ``(y, cb, cr)``."""
    import jax
    import jax.numpy as jnp

    from omero_ms_image_region_tpu.ops.jpegenc import _dct_quant_zigzag

    @jax.jit
    def reshape_formulation(packed, qy, qc):
        r = (packed & 0xFF).astype(jnp.float32)
        g = ((packed >> 8) & 0xFF).astype(jnp.float32)
        b = ((packed >> 16) & 0xFF).astype(jnp.float32)
        y = 0.299 * r + 0.587 * g + 0.114 * b - 128.0
        cb = -0.168736 * r - 0.331264 * g + 0.5 * b
        cr = 0.5 * r - 0.418688 * g - 0.081312 * b
        zig = jnp.asarray(zigzag_order())
        D = jnp.asarray(dct_matrix())
        return (_dct_quant_zigzag(y, qy, zig, D),
                _dct_quant_zigzag(_plain_chroma_mean(cb), qc, zig, D),
                _dct_quant_zigzag(_plain_chroma_mean(cr), qc, zig, D))

    packed = np.stack([pack(blob_image(H, W, seed=seed + 100 * i,
                                       noise=noise)) for i in range(B)])
    qy, qc = (t.astype(np.int32) for t in quant_tables(90))
    got = packed_to_jpeg_coefficients(packed, qy, qc)
    want = reshape_formulation(packed, qy, qc)
    for g, w, blocks in zip(got, want, (H * W // 64, H * W // 256,
                                        H * W // 256)):
        assert g.shape == (B, blocks, 64) and g.dtype == jnp.int16
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        # more than the DC terms: the comparison is of real content
        assert np.count_nonzero(np.asarray(g)) > B * blocks


# ------------------------- the sparse tail on several threads (PR 37)

def _wire_row(H, W, seed, per_block=6.0):
    """A sparse wire row of an H x W tile, made on the host: ``[total
    i32 | counts u8[nb] | 18-bit (pos << 12 | val) entries]`` with
    about ``per_block`` non-zero coefficients a block at ascending
    zigzag positions.  Returns ``(row, total)``."""
    rng = np.random.default_rng(seed)
    nb = ((H + 15) // 16) * ((W + 15) // 16) * 6
    counts = np.minimum(rng.poisson(per_block, nb), 64).astype(np.uint8)
    total = int(counts.sum())
    taken = np.arange(64)[None, :] < counts[:, None]
    pos = np.where(taken, np.argsort(rng.random((nb, 64)), axis=1), 64)
    pos.sort(axis=1)
    pos = pos[pos < 64]                 # block by block, ascending
    mag = np.clip(rng.exponential(6.0, total).astype(np.int64), 1, 2047)
    val = np.where(rng.random(total) < 0.5, -mag, mag)
    return _pack_wire(counts, pos, val), total


def _pack_wire(counts, pos, val):
    """``[total i32 | counts u8[nb] | 18-bit (pos << 12 | val)
    entries]``: the row of blocks with ``counts`` entries each, entry by
    entry ``pos`` and ``val``."""
    field = ((np.asarray(pos).astype(np.uint32) << 12)
             | (np.asarray(val).astype(np.int64) & 0xFFF).astype(np.uint32))
    bits = ((field[:, None] >> np.arange(17, -1, -1)[None, :]) & 1)
    return np.concatenate([
        np.array([int(np.sum(counts))], "<i4").view(np.uint8),
        np.asarray(counts, np.uint8),
        np.packbits(bits.astype(np.uint8).ravel())])


def _parent_tail(bufs, dims, H, W, quality, cap, dense_coefficients,
                 on_tile=None):
    """``finish_sparse_to_jpegs`` as it was before PR 37, kept as the
    plain reference: one tile after the other on the calling thread."""
    from omero_ms_image_region_tpu.ops.jpegenc import (
        dense_encoder, slice_block_subgrid, sparse_encoder)

    _encode = sparse_encoder()
    _dense_encode = dense_encoder()
    out = []
    for i, (w_, h_) in enumerate(dims):
        exact = ((h_ + 15) // 16 * 16 == H and (w_ + 15) // 16 * 16 == W)
        try:
            if exact:
                out.append(_encode(bufs[i], w_, h_, quality, cap))
                if on_tile is not None:
                    on_tile(i, out[-1])
                continue
            dense = sparse_to_dense(bufs[i], H, W, cap)
            if dense is None:
                raise SparseOverflowError(f"overflow (cap={cap})")
        except SparseOverflowError:
            dense = dense_coefficients(i)
        y, cb, cr = slice_block_subgrid(*dense, H, W, w_, h_) \
            if not exact else dense
        out.append(_dense_encode(y, cb, cr, w_, h_, quality))
        if on_tile is not None:
            on_tile(i, out[-1])
    return out


def _tail_group(n, E):
    """``n`` rows of an E x E bucket and their ``dims``: where the group
    is large enough, row 1 is smaller than the bucket's grid
    (``slice_block_subgrid``) and row 3 overflows the cap (the dense
    path); two batch-shape pad rows follow the group's own."""
    made = [_wire_row(E, E, 37 * E + i, 20.0 if i == 3 else 6.0)
            for i in range(n)]
    rows = [r for r, _ in made]
    totals = [t for _, t in made]
    dims = [(E, E)] * n
    if n > 1:
        dims[1] = (E - 24, E - 8)
    cap = max(t for i, t in enumerate(totals) if i != 3)
    if n > 3:
        assert totals[3] > cap

    def dense_coefficients(i):
        assert i == 3
        return sparse_to_dense(rows[i], E, E, totals[i])

    pads = [np.zeros(0, np.uint8)] * 2
    return rows + pads, dims, cap, dense_coefficients


class _Meeting:
    """An ``on_tile`` that records what fired, and holds the first
    thread's first tile until a second thread has brought one: a tail
    whose threads meet here ran on more than one of them."""

    def __init__(self, wait=True):
        import threading
        self.fired = []
        self.threads = set()
        self._met = threading.Event()
        self._wait = wait
        self._ident = threading.get_ident

    def __call__(self, i, body):
        self.fired.append((i, body))
        self.threads.add(self._ident())
        if len(self.threads) > 1:
            self._met.set()
        if self._wait:
            self._met.wait(10.0)


@pytest.mark.parametrize("E", [64, 256])
@pytest.mark.parametrize("n", [1, 2, 6, 33])
def test_the_pooled_tail_returns_the_serial_loops_bytes(n, E, coding_pool):
    """Groups of 1, 2, 6 and 33 rows at two sizes: the tail coded on
    several threads returns, byte for byte, what the parent's serial
    loop returns (a row that overflows its cap, a member smaller than
    the bucket's grid and batch-shape pad rows among them), and
    ``on_tile`` fires exactly once a tile with the returned entry's
    content, from whichever thread coded it."""
    from omero_ms_image_region_tpu.ops.jpegenc import finish_sparse_to_jpegs

    rows, dims, cap, dense_coefficients = _tail_group(n, E)
    want_fired = []
    want = _parent_tail(rows, dims, E, E, 90, cap, dense_coefficients,
                        on_tile=lambda i, d: want_fired.append((i, d)))
    assert [i for i, _ in want_fired] == list(range(n))

    meeting = _Meeting(wait=n > 1)
    got = finish_sparse_to_jpegs(rows, dims, E, E, 90, cap,
                                 dense_coefficients, on_tile=meeting)
    assert got == want
    assert sorted(meeting.fired) == want_fired
    assert all(type(d) is bytes for _, d in meeting.fired)
    for (w_, h_), body in zip(dims, got):
        assert Image.open(io.BytesIO(body)).size == (w_, h_)
    if n == 1:
        # A group of one is coded in line: no hop, no pool thread.
        assert coding_pool.TILES == {"pooled": 0, "inline": 1}
        assert len(meeting.threads) == 1
    else:
        assert len(meeting.threads) > 1
        assert coding_pool.TILES == {"pooled": n, "inline": 0}
    # Without callbacks: the same list.
    assert finish_sparse_to_jpegs(rows, dims, E, E, 90, cap,
                                  dense_coefficients) == want


def test_a_group_of_one_never_touches_the_pool(coding_pool):
    from omero_ms_image_region_tpu.ops.jpegenc import finish_sparse_to_jpegs

    class Untouched:
        def submit(self, *a, **kw):
            raise AssertionError("a group of one reached the pool")

    real = coding_pool._POOL._executor
    coding_pool._POOL._executor = Untouched()
    try:
        row, total = _wire_row(64, 64, 5)
        meeting = _Meeting(wait=False)
        got = finish_sparse_to_jpegs([row], [(64, 64)], 64, 64, 90, total,
                                     None, on_tile=meeting)
    finally:
        coding_pool._POOL._executor = real
    assert meeting.fired == [(0, got[0])]
    assert coding_pool.TILES == {"pooled": 0, "inline": 1}


@pytest.mark.parametrize("bad", [0, 4, 8])
def test_one_malformed_row_fails_the_tail_and_nothing_fires_twice(
        bad, coding_pool):
    """A row whose counts do not sum to its total fails the tail as it
    did the serial loop (``ValueError``, once, from
    ``finish_sparse_to_jpegs``); the other tiles' callbacks have fired
    or not, each at most once and never for the malformed one."""
    from omero_ms_image_region_tpu.ops.jpegenc import finish_sparse_to_jpegs

    n, E = 9, 64
    made = [_wire_row(E, E, 100 + i) for i in range(n)]
    rows = [r.copy() for r, _ in made]
    cap = max(t for _, t in made)
    good = _parent_tail(rows, [(E, E)] * n, E, E, 90, cap, None)
    rows[bad][4] += 1                       # the first block's count
    with pytest.raises(ValueError):
        _parent_tail(rows, [(E, E)] * n, E, E, 90, cap, None)
    meeting = _Meeting(wait=False)
    with pytest.raises(ValueError):
        finish_sparse_to_jpegs(rows, [(E, E)] * n, E, E, 90, cap, None,
                               on_tile=meeting)
    fired = [i for i, _ in meeting.fired]
    assert len(fired) == len(set(fired)) and bad not in fired
    assert all(body == good[i] for i, body in meeting.fired)


def test_a_callbacks_exception_surfaces_once_from_the_tail(coding_pool):
    from omero_ms_image_region_tpu.ops.jpegenc import finish_sparse_to_jpegs

    n, E = 6, 64
    made = [_wire_row(E, E, 200 + i) for i in range(n)]
    fired = []

    def on_tile(i, body):
        fired.append(i)
        if i == 2:
            raise RuntimeError("the waiter is gone")

    with pytest.raises(RuntimeError, match="the waiter is gone"):
        finish_sparse_to_jpegs([r for r, _ in made], [(E, E)] * n, E, E,
                               90, max(t for _, t in made), None,
                               on_tile=on_tile)
    assert len(fired) == len(set(fired)) and 2 in fired


def test_a_busy_pool_leaves_the_tail_to_its_own_thread(coding_pool):
    """Helpers that never get a thread (the pool is coding other
    groups' tiles) are cancelled: the group's thread codes every run
    itself and does not wait for them."""
    import threading

    from omero_ms_image_region_tpu.ops.jpegenc import finish_sparse_to_jpegs

    release = threading.Event()
    busy = [coding_pool._POOL._executor.submit(release.wait, 30.0)
            for _ in range(3)]
    try:
        n, E = 6, 64
        made = [_wire_row(E, E, 300 + i) for i in range(n)]
        rows, cap = [r for r, _ in made], max(t for _, t in made)
        meeting = _Meeting(wait=False)
        got = finish_sparse_to_jpegs(rows, [(E, E)] * n, E, E, 90, cap,
                                     None, on_tile=meeting)
    finally:
        release.set()
    for b in busy:
        b.result()
    assert got == _parent_tail(rows, [(E, E)] * n, E, E, 90, cap, None)
    assert meeting.threads == {threading.get_ident()}
    assert coding_pool.TILES == {"pooled": 0, "inline": n}


def test_the_pools_runs_follow_the_tiles_and_the_threads():
    from omero_ms_image_region_tpu.utils.entropypool import (
        EntropyPool, RUN_PX)

    pool = EntropyPool(8)
    try:
        # Large tiles: one a run, whatever the threads.
        assert pool.runs(6, 1024 * 1024) == [range(i, i + 1)
                                             for i in range(6)]
        assert pool.runs(2, 2048 * 2048) == [range(0, 1), range(1, 2)]
        assert pool.runs(1, 256 * 256) == [range(0, 1)]
        # Small ones: a run a thread, and no more than RUN_PX of them.
        assert pool.runs(32, 256 * 256) == [range(a, a + 4)
                                            for a in range(0, 32, 4)]
        assert RUN_PX // (256 * 256) == 16
    finally:
        pool._executor.shutdown()
    lone = EntropyPool(0)
    assert lone.runs(40, 256 * 256) == [range(0, 16), range(16, 32),
                                        range(32, 40)]
    coded = []
    lone.code(3, 1024 * 1024, coded.append)
    assert coded == [range(0, 1), range(1, 2), range(2, 3)]


def test_the_pool_is_sized_from_the_cores_and_the_group_threads(
        monkeypatch):
    import os

    from omero_ms_image_region_tpu.utils import entropypool

    monkeypatch.setattr(entropypool, "_GROUP_THREADS", 1)
    monkeypatch.setattr(os, "sched_getaffinity",
                        lambda pid: set(range(13)))
    assert entropypool.pool_threads() == 11
    entropypool.expect_group_threads(4)
    assert entropypool.pool_threads() == 8      # 13 - the loop - 4
    entropypool.expect_group_threads(2)         # the deepest stands
    assert entropypool.pool_threads() == 8
    monkeypatch.setattr(os, "sched_getaffinity", lambda pid: {0, 1})
    assert entropypool.pool_threads() == 0


@pytest.mark.skipif(not HAVE_NATIVE, reason="no native toolchain")
def test_the_coders_scratch_is_kept_between_calls():
    """A count, not a speed: once a thread has coded a tile of a size,
    twenty more of that size (sparse runs and the dense coder) grow no
    scratch, and what is idle afterwards holds the size's records."""
    from omero_ms_image_region_tpu.native import (
        jpeg_encode_sparse_run, jpeg_scratch_stats)

    E = 256
    made = [_wire_row(E, E, 400 + i) for i in range(4)]
    rows, cap = [r for r, _ in made], max(t for _, t in made)
    first = jpeg_encode_sparse_run(rows, [(E, E)] * 4, 90, cap)
    dense = sparse_to_dense(rows[0], E, E, cap)
    first_dense = jpeg_encode_native(*dense, E, E, 90)
    grown = jpeg_scratch_stats()["growths"]
    for _ in range(5):
        assert jpeg_encode_sparse_run(rows, [(E, E)] * 4, 90, cap) == first
        assert jpeg_encode_native(*dense, E, E, 90) == first_dense
    stats = jpeg_scratch_stats()
    assert stats["growths"] == grown
    # 272 bytes a block, six blocks an MCU.
    assert stats["idle"] >= 1
    assert stats["idle_bytes"] >= (E // 16) ** 2 * 6 * 272
    # A larger tile grows it, once.
    big, total = _wire_row(2 * E, 2 * E, 7)
    one = jpeg_encode_sparse_native(big, 2 * E, 2 * E, 90, total)
    grown = jpeg_scratch_stats()["growths"]
    assert jpeg_encode_sparse_native(big, 2 * E, 2 * E, 90, total) == one
    assert jpeg_scratch_stats()["growths"] == grown


@pytest.mark.skipif(not HAVE_NATIVE, reason="no native toolchain")
def test_eight_threads_coding_at_once_give_the_lone_threads_bytes():
    import threading
    from concurrent.futures import ThreadPoolExecutor

    sizes = [64, 256, 128, 64, 256, 128, 512, 64]
    made = [_wire_row(E, E, 500 + i) for i, E in enumerate(sizes)]
    want = [jpeg_encode_sparse_native(row, E, E, 90, total)
            for (row, total), E in zip(made, sizes)]
    start = threading.Barrier(8)

    def code(k):
        (row, total), E = made[k], sizes[k]
        start.wait(10.0)
        return [jpeg_encode_sparse_native(row, E, E, 90, total)
                for _ in range(6)]

    with ThreadPoolExecutor(8) as ex:
        got = list(ex.map(code, range(8)))
    assert got == [[w] * 6 for w in want]


@pytest.mark.skipif(not HAVE_NATIVE, reason="no native toolchain")
def test_a_run_reads_its_rows_in_place_and_reports_each_rows_code():
    """No pad of the row: a row that ends with its last entry (nothing
    readable behind it in its own array) codes as the same row with
    bytes behind it; a malformed and an overflowing row of a run leave
    the others as they are."""
    from omero_ms_image_region_tpu.native import jpeg_encode_sparse_run

    E = 64
    made = [_wire_row(E, E, 600 + i, 3.0 + i) for i in range(5)]
    rows = [r for r, _ in made]
    cap = made[3][1]
    assert made[4][1] > cap
    lone = [jpeg_encode_sparse_native(r, E, E, 90, cap) for r in rows[:4]]
    tailed = [np.concatenate([r, np.full(9, 0xAB, np.uint8)])[:r.size]
              for r in rows]
    broken = rows[1].copy()
    broken[4] += 1
    got = jpeg_encode_sparse_run([tailed[0], broken, tailed[2], tailed[3],
                                  tailed[4]], [(E, E)] * 5, 90, cap)
    assert got == [lone[0], -1, lone[2], lone[3], -2]


# ------------------------------- one tile's tail on several threads

_SPLIT_ROWS = {}


def _split_case(E):
    """A row of an E x E tile, its serial coder's bytes, and the Python
    reference's (``jfif.encode_jfif`` of the row's dense coefficients),
    made once a size."""
    if E not in _SPLIT_ROWS:
        row, total = _wire_row(E, E, 4100 + E)
        serial, = jpeg_encode_sparse_run([row], [(E, E)], 90, total)
        reference = encode_jfif(*sparse_to_dense(row, E, E, total), E, E,
                                90)
        _SPLIT_ROWS[E] = (row, total, serial, reference)
    return _SPLIT_ROWS[E]


def _in_order(n, work):
    """The split coder's one coder: this thread (``_in_order.segments``
    keeps the number of segments the coder cut)."""
    _in_order.segments = n
    work()


def _threads(count):
    """A fan that runs the split coder's ``work()`` on ``count`` plain
    threads at once and returns what each coded."""
    import threading

    def fan(n, work):
        start = threading.Barrier(count)
        coded = [None] * count

        def run(i):
            start.wait(10.0)
            coded[i] = work()

        threads = [threading.Thread(target=run, args=(i,))
                   for i in range(count)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30.0)
        assert not any(t.is_alive() for t in threads)
        fan.coded = coded

    return fan


def _scan(jfif: bytes) -> bytes:
    """The entropy-coded scan of a stream, its 0x00 stuffing taken out."""
    at = jfif.index(b"\xff\xda")
    at += 2 + int.from_bytes(jfif[at + 2:at + 4], "big")
    return jfif[at:-2].replace(b"\xff\x00", b"\xff")


@pytest.mark.skipif(not HAVE_NATIVE, reason="no native toolchain")
@pytest.mark.parametrize("segments", [1, 2, 3, 8, 9])
@pytest.mark.parametrize("E", [1024, 1088, 2048])
def test_a_split_tile_is_the_serial_coders_bytes(E, segments):
    """Cut into 1, 2, 3, 8 or 9 segments of whole MCU rows, a tile
    codes to the serial coder's bytes and the Python reference's."""
    from omero_ms_image_region_tpu.native import jpeg_encode_sparse_split

    row, total, serial, reference = _split_case(E)
    assert serial == reference
    got = jpeg_encode_sparse_split(row, (E, E), 90, total, segments,
                                   _in_order)
    assert got == serial
    assert _in_order.segments == segments


@pytest.mark.skipif(not HAVE_NATIVE, reason="no native toolchain")
@pytest.mark.parametrize("count", [2, 9, 16])
@pytest.mark.parametrize("E", [1024, 2048])
def test_many_coders_at_once_give_the_serial_coders_bytes(E, count):
    """Two, nine or sixteen threads in the coder's ``work`` at once on
    nine segments: every segment coded once, by whichever claimed it,
    the tables built once between the passes: the serial bytes; and
    after the first tile of a size no scratch grows."""
    from omero_ms_image_region_tpu.native import (
        jpeg_encode_sparse_split, jpeg_scratch_stats)

    row, total, serial, _ = _split_case(E)
    fan = _threads(count)
    for i in range(3):
        assert jpeg_encode_sparse_split(row, (E, E), 90, total, 9,
                                        fan) == serial
        assert sum(fan.coded) == 2 * 9     # each segment's two passes
        if i == 0:
            grown = jpeg_scratch_stats()["growths"]
    assert jpeg_scratch_stats()["growths"] == grown
    malformed, bad_total = _malformed_in_last_rows(1024)
    assert jpeg_encode_sparse_split(malformed, (1024, 1024), 90, bad_total,
                                    9, fan) == -1


@pytest.mark.skipif(not HAVE_NATIVE, reason="no native toolchain")
def test_a_stock_tile_is_never_cut():
    """256^2 is 256 MCUs, one segment's least: every pool, however
    large, codes a stock tile whole, and the coder asked for more
    segments than rows gives one a row, the same bytes."""
    from omero_ms_image_region_tpu.native import jpeg_encode_sparse_split
    from omero_ms_image_region_tpu.utils.entropypool import (
        SEGMENT_MCUS, EntropyPool)

    assert SEGMENT_MCUS == 256
    for threads in (0, 3, 8, 64):
        assert EntropyPool(threads).segments(1, 16, 16) == 1
    row, total = _wire_row(256, 256, 4141)
    serial, = jpeg_encode_sparse_run([row], [(256, 256)], 90, total)
    assert jpeg_encode_sparse_split(row, (256, 256), 90, total, 40,
                                    _in_order) == serial
    assert _in_order.segments == 16


@pytest.mark.skipif(not HAVE_NATIVE, reason="no native toolchain")
@pytest.mark.parametrize("segments", [2, 3, 8, 9])
def test_the_dc_predictor_crosses_every_boundary(segments):
    """A tile whose only coefficients are DC: every symbol but EOB is a
    difference from the last block's DC, so a predictor lost at a
    boundary would change the bytes."""
    from omero_ms_image_region_tpu.native import jpeg_encode_sparse_split

    E = 1024
    nb = (E // 16) ** 2 * 6
    rng = np.random.default_rng(41)
    val = rng.integers(-1000, 1000, nb)
    val[val == 0] = 7
    counts = np.ones(nb, np.uint8)
    row = _pack_wire(counts, np.zeros(nb, np.int64), val)
    serial, = jpeg_encode_sparse_run([row], [(E, E)], 90, nb)
    assert serial == encode_jfif(*sparse_to_dense(row, E, E, nb), E, E, 90)
    assert jpeg_encode_sparse_split(row, (E, E), 90, nb, segments,
                                    _in_order) == serial
    # A block with no entries (DC 0) before a boundary: the predictor
    # there is 0, not the DC of the block before it.
    counts[(E // 16) * 4 * 32 - 1] = 0
    keep = np.ones(nb, bool)
    keep[(E // 16) * 4 * 32 - 1] = False
    holed = _pack_wire(counts, np.zeros(nb - 1, np.int64), val[keep])
    serial, = jpeg_encode_sparse_run([holed], [(E, E)], 90, nb - 1)
    assert jpeg_encode_sparse_split(holed, (E, E), 90, nb - 1, 2,
                                    _in_order) == serial


def _ones_at_row_ends(E, seed):
    """A row built to end every MCU row with 1-bits and start the next
    with a long code: the last Cr block of each MCU row ends at position
    63 with 1023 (ten 1-bits, no EOB), and the first luma block jumps to
    a rare DC category (a long code of leading 1-bits)."""
    rng = np.random.default_rng(seed)
    h16 = w16 = E // 16
    nb_y, nb_c = h16 * w16 * 4, h16 * w16
    nb = nb_y + 2 * nb_c
    dc = rng.integers(-40, 40, nb)
    dc[[4 * r * w16 for r in range(h16)]] = 1500
    entries = [[(0, int(v))] for v in dc]
    for b in range(nb):
        if rng.random() < 0.5:
            entries[b].append((int(rng.integers(1, 20)),
                               int(rng.integers(-30, 30)) or 1))
    for r in range(h16):
        entries[nb_y + nb_c + r * w16 + w16 - 1] = [(0, int(dc[0])),
                                                     (63, 1023)]
    counts = np.array([len(e) for e in entries], np.uint8)
    flat = [x for e in entries for x in e]
    return (_pack_wire(counts, [p for p, _ in flat], [v for _, v in flat]),
            len(flat))


def _mcu_row_starts(row, E, total):
    """Where each MCU row of an E x E tile's scan starts, in bits, and
    where the scan ends: each block's codes and amplitude bits under the
    Python reference's symbols and optimal tables."""
    from omero_ms_image_region_tpu import jfif

    y, cb, cr = sparse_to_dense(row, E, E, total)
    mcus = jfif._mcu_block_indices(E // 16, E // 16)
    (y_rec, y_dc, y_ac), (cb_rec, cb_dc, cb_ac), (cr_rec, cr_dc, cr_ac) = [
        jfif._component_symbols(blocks) for blocks in (
            [y[i] for m in mcus for i in m[0]], [cb[m[1]] for m in mcus],
            [cr[m[1]] for m in mcus])]

    def lengths(freq):
        return jfif._codes_from_table(*build_huffman_table(freq))[1]

    def bits(rec, dc_len, ac_len):
        dc_sym, _, acs = rec
        return int(dc_len[dc_sym]) + dc_sym + sum(
            int(ac_len[sym]) + (sym & 0x0F) for sym, _ in acs)

    luma = lengths(y_dc), lengths(y_ac)
    chroma = lengths(cb_dc + cr_dc), lengths(cb_ac + cr_ac)
    mcu_bits = [sum(bits(r, *luma) for r in y_rec[4 * m:4 * m + 4])
                + bits(cb_rec[m], *chroma) + bits(cr_rec[m], *chroma)
                for m in range(len(mcus))]
    ends = np.cumsum(mcu_bits)
    return [0] + [int(b) for b in ends[E // 16 - 1::E // 16]]


@pytest.mark.skipif(not HAVE_NATIVE, reason="no native toolchain")
def test_a_0xff_across_a_segment_boundary_is_stuffed_as_the_serial_coder():
    """Rows searched until one's spliced bits put a 0xFF byte across a
    boundary (part of it from each segment, one an MCU row): that byte
    is stuffed, and the stream is the serial coder's."""
    from omero_ms_image_region_tpu.native import jpeg_encode_sparse_split

    E = 256
    for seed in range(200):
        row, total = _ones_at_row_ends(E, 4300 + seed)
        serial, = jpeg_encode_sparse_run([row], [(E, E)], 90, total)
        scan = _scan(serial)
        got = jpeg_encode_sparse_split(row, (E, E), 90, total, 16,
                                       _in_order)
        assert got == serial and _in_order.segments == 16
        *offsets, end = _mcu_row_starts(row, E, total)
        assert len(scan) == -(-end // 8)
        across = [b for b in offsets[1:]
                  if b % 8 and scan[b // 8] == 0xFF]
        if across:
            break
    else:
        pytest.fail("no row put a 0xFF across a boundary")
    # The byte is stuffed in the stream: an 0xFF then 0x00 at its place.
    ffs = scan[:across[0] // 8].count(0xFF)
    at = serial.index(b"\xff\xda")
    at += 2 + int.from_bytes(serial[at + 2:at + 4], "big")
    at += across[0] // 8 + ffs
    assert serial[at:at + 2] == b"\xff\x00"


def _malformed_in_last_rows(E):
    """A row whose last block's entries are not ascending (its second
    entry at its first's position)."""
    rng = np.random.default_rng(4142)
    nb = (E // 16) ** 2 * 6
    counts = np.full(nb, 3, np.uint8)
    pos = np.tile([0, 5, 9], nb)
    pos[-2] = 0                         # the last Cr block's second entry
    val = rng.integers(1, 500, 3 * nb)
    return _pack_wire(counts, pos, val), 3 * nb


@pytest.mark.skipif(not HAVE_NATIVE, reason="no native toolchain")
@pytest.mark.parametrize("segments", [1, 2, 9])
def test_a_malformed_block_in_the_last_segment_gives_the_serial_code(
        segments, coding_pool):
    from omero_ms_image_region_tpu.native import jpeg_encode_sparse_split

    E = 1024
    row, total = _malformed_in_last_rows(E)
    assert jpeg_encode_sparse_run([row], [(E, E)], 90, total) == [-1]
    assert jpeg_encode_sparse_split(row, (E, E), 90, total, segments,
                                    _in_order) == -1
    assert jpeg_encode_sparse_split(
        row, (E, E), 90, total, segments,
        coding_pool._POOL.code_segments) == -1
    # Overflow and a short row: the run coder's codes, before a segment.
    assert jpeg_encode_sparse_split(row, (E, E), 90, total - 1,
                                    segments, _in_order) == -2
    assert jpeg_encode_sparse_split(row[:100], (E, E), 90, total,
                                    segments, _in_order) == -1


def test_a_pool_of_no_threads_codes_every_segment_on_its_caller():
    import threading

    from omero_ms_image_region_tpu.utils import entropypool

    pool = entropypool.EntropyPool(0)
    calls = []

    def work():
        calls.append(threading.get_ident())
        return 5

    assert pool.code_segments(5, work) == {threading.get_ident()}
    assert calls == [threading.get_ident()]


def test_segments_follow_the_grid_and_the_free_coders():
    from omero_ms_image_region_tpu.utils.entropypool import EntropyPool

    pool = EntropyPool(8)
    try:
        assert pool.segments(1, 64, 64) == 9        # a lone 1024^2 tile
        assert pool.segments(1, 128, 128) == 9      # a lone 2048^2 plane
        assert pool.segments(2, 128, 128) == 1      # two runs: not cut
        assert pool.segments(3, 68, 68) == 1
        assert pool.segments(9, 64, 64) == 1
        assert pool.segments(16, 16, 16) == 1       # 256^2: never
        assert pool.segments(1, 32, 32) == 4        # 512^2: 8 rows each
        assert pool.segments(1, 3, 200) == 1        # 2 rows a segment
    finally:
        pool._executor.shutdown()
    assert EntropyPool(0).segments(1, 64, 64) == 1


@pytest.mark.skipif(not HAVE_NATIVE, reason="no native toolchain")
def test_a_lone_large_tile_is_split_and_counted_once(coding_pool,
                                                     monkeypatch):
    """A group of one 1024^2 tile: cut into 1 + 3 segments (the pool's
    three threads free), its coder's ``work`` entered by more than one
    thread (the first waits for a second), counted once as split and as
    ``pooled`` when more than one of them coded a segment; ``on_tile``
    fires once, from the thread that spliced it, with the serial
    coder's bytes."""
    import threading

    from omero_ms_image_region_tpu.ops.jpegenc import finish_sparse_to_jpegs

    pool = coding_pool._POOL
    real = pool.code_segments
    seen, met, coders = set(), threading.Event(), []

    def meeting(n, work):
        assert n == 4

        def met_work():
            seen.add(threading.get_ident())
            if len(seen) > 1:
                met.set()
            met.wait(10.0)
            return work()

        coders.append(real(n, met_work))
        return coders[-1]

    monkeypatch.setattr(pool, "code_segments", meeting)
    row, total, serial, _ = _split_case(1024)
    fired = []
    got = finish_sparse_to_jpegs(
        [row], [(1024, 1024)], 1024, 1024, 90, total, None,
        on_tile=lambda i, b: fired.append((i, b, threading.get_ident())))
    assert got == [serial]
    assert fired == [(0, serial, threading.get_ident())]
    assert len(seen) > 1 and len(coders) == 1 and len(coders[0]) >= 1
    assert coding_pool.TILES == ({"pooled": 1, "inline": 0}
                                 if len(coders[0]) > 1 else
                                 {"pooled": 0, "inline": 1})
    assert coding_pool.SPLIT_TILES == 1


@pytest.mark.skipif(not HAVE_NATIVE, reason="no native toolchain")
@pytest.mark.parametrize("n", [1, 2, 3])
def test_a_split_tail_fires_each_tile_once_with_the_serial_bytes(
        n, coding_pool):
    """Groups of one to three 1024^2 tiles (a lone tile cut in four on
    three idle threads, two or three tiles not cut): the bytes and
    callbacks of the parent's loop, a cut tile counted once as split."""
    from omero_ms_image_region_tpu.ops.jpegenc import finish_sparse_to_jpegs

    rows, dims, cap, dense_coefficients = _tail_group(n, 1024)
    want = _parent_tail(rows, dims, 1024, 1024, 90, cap, dense_coefficients)
    fired = []
    got = finish_sparse_to_jpegs(rows, dims, 1024, 1024, 90, cap,
                                 dense_coefficients,
                                 on_tile=lambda i, b: fired.append((i, b)))
    assert got == want
    assert sorted(fired) == list(enumerate(want))
    assert coding_pool._POOL.segments(n, 64, 64) == [4, 1, 1][n - 1]
    assert coding_pool.SPLIT_TILES == (n == 1)
    assert sum(coding_pool.TILES.values()) == n


@pytest.mark.skipif(not HAVE_NATIVE, reason="no native toolchain")
def test_a_busy_pool_leaves_a_split_tile_to_its_own_thread(coding_pool):
    """Every pool thread held by another tail: the tile is still cut,
    its helpers never start, and its own thread codes every segment."""
    import threading

    from omero_ms_image_region_tpu.ops.jpegenc import finish_sparse_to_jpegs

    release = threading.Event()
    busy = [coding_pool._POOL._executor.submit(release.wait, 30.0)
            for _ in range(3)]
    try:
        row, total, serial, _ = _split_case(1024)
        fired = []
        got = finish_sparse_to_jpegs(
            [row], [(1024, 1024)], 1024, 1024, 90, total, None,
            on_tile=lambda i, b: fired.append(threading.get_ident()))
    finally:
        release.set()
    for b in busy:
        b.result()
    assert got == [serial]
    assert fired == [threading.get_ident()]
    assert coding_pool.TILES == {"pooled": 0, "inline": 1}
    assert coding_pool.SPLIT_TILES == 1


def test_a_tile_is_not_cut_while_another_tail_is_coded(coding_pool):
    """While another tail is being coded (here a lone tile on its own
    group's thread, the pool idle), a lone 1024^2 tile is not cut; once
    that tail has returned, or failed, it is cut in four again."""
    import threading

    pool = coding_pool._POOL
    assert pool.segments(1, 64, 64) == 4
    entered, release = threading.Event(), threading.Event()

    def code_run(run):
        entered.set()
        release.wait(30.0)
        raise RuntimeError("a coder failed")

    def other_tail():
        with pytest.raises(RuntimeError):
            pool.code(1, 1024 * 1024, code_run)

    holder = threading.Thread(target=other_tail)
    holder.start()
    try:
        assert entered.wait(30.0)
        assert pool.segments(1, 64, 64) == 1
        assert pool.segments(1, 128, 128) == 1
    finally:
        release.set()
        holder.join(30.0)
    assert not holder.is_alive()
    assert pool.segments(1, 64, 64) == 4


def test_a_coders_exception_is_raised_once_after_every_coder_stopped(
        coding_pool):
    import threading
    import time

    pool = coding_pool._POOL
    lock, active, calls = threading.Lock(), [0], []

    def work():
        with lock:
            active[0] += 1
            calls.append(threading.get_ident())
            first = len(calls) == 1
        try:
            time.sleep(0.05)
            if first:
                raise RuntimeError("a coder failed")
            return 1
        finally:
            with lock:
                active[0] -= 1

    with pytest.raises(RuntimeError, match="a coder failed"):
        pool.code_segments(4, work)
    assert active[0] == 0
    assert len(calls) >= 1
    assert coding_pool.SPLIT_TILES == 0


@pytest.mark.skipif(not HAVE_NATIVE, reason="no native toolchain")
def test_a_failing_coder_fails_the_tail_and_its_tile_fires_nothing(
        coding_pool, monkeypatch):
    import omero_ms_image_region_tpu.ops.jpegenc as jpegenc

    real = jpegenc.sparse_split_encoder()

    def failing(row, dims, quality, cap, segments, fan):
        def broken(n, work):
            def work_():
                raise RuntimeError("a coder failed")
            return fan(n, work_)
        return real(row, dims, quality, cap, segments, broken)

    monkeypatch.setattr(jpegenc, "sparse_split_encoder", lambda: failing)
    row, total, _, _ = _split_case(1024)
    fired = []
    with pytest.raises(RuntimeError, match="a coder failed"):
        jpegenc.finish_sparse_to_jpegs(
            [row], [(1024, 1024)], 1024, 1024, 90, total, None,
            on_tile=lambda i, b: fired.append(i))
    assert fired == []
    assert coding_pool.TILES == {"pooled": 0, "inline": 0}


@pytest.mark.skipif(not HAVE_NATIVE, reason="no native toolchain")
def test_segments_fanned_out_from_pool_threads_do_not_deadlock(coding_pool):
    """Every thread of the pool codes a run whose tile fans out its
    segments into the same pool (no thread left for a helper): each
    run's own thread codes what no helper took, and the tail ends with
    the serial bytes."""
    import threading

    from omero_ms_image_region_tpu.native import jpeg_encode_sparse_split

    pool = coding_pool._POOL
    E = 256
    made = [_wire_row(E, E, 4400 + i) for i in range(8)]
    want = [jpeg_encode_sparse_run([r], [(E, E)], 90, t)[0] for r, t in made]
    got = [None] * 8

    def code_run(run):
        for i in run:
            row, total = made[i]
            got[i] = jpeg_encode_sparse_split(row, (E, E), 90, total, 4,
                                              pool.code_segments)

    done = threading.Event()
    worker = threading.Thread(
        target=lambda: (pool.code(8, 1024 * 1024, code_run), done.set()),
        daemon=True)
    worker.start()
    worker.join(60.0)
    assert done.is_set()
    assert got == want
    assert coding_pool.SPLIT_TILES == 8
    assert sum(coding_pool.TILES.values()) == 8


@pytest.mark.skipif(not HAVE_NATIVE, reason="no native toolchain")
def test_many_tails_splitting_at_once_lose_no_segment_and_no_count(
        coding_pool, monkeypatch):
    """Twelve tails at once on a pool of sixteen threads (more than the
    cores), each run's tile cut into four segments, under a short
    switch interval: every tile is the serial coder's bytes and no
    count is lost."""
    import sys
    import threading

    from omero_ms_image_region_tpu.native import jpeg_encode_sparse_split
    from omero_ms_image_region_tpu.utils import entropypool

    pool = entropypool.EntropyPool(16)
    monkeypatch.setattr(entropypool, "_POOL", pool)
    E, tails, runs_a_tail = 256, 12, 3
    made = [_wire_row(E, E, 4500 + i) for i in range(runs_a_tail)]
    want = [jpeg_encode_sparse_run([r], [(E, E)], 90, t)[0] for r, t in made]
    got, lock = [], threading.Lock()

    def code_run(run):
        for i in run:
            row, total = made[i]
            body = jpeg_encode_sparse_split(row, (E, E), 90, total, 4,
                                            pool.code_segments)
            with lock:
                got.append((i, body))

    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        callers = [threading.Thread(
            target=lambda: pool.code(runs_a_tail, 1024 * 1024, code_run),
            daemon=True) for _ in range(tails)]
        for t in callers:
            t.start()
        for t in callers:
            t.join(60.0)
    finally:
        sys.setswitchinterval(switch)
        pool._executor.shutdown(wait=False)
    assert not any(t.is_alive() for t in callers)
    assert len(got) == tails * runs_a_tail
    assert all(body == want[i] for i, body in got)
    assert entropypool.SPLIT_TILES == tails * runs_a_tail
    assert sum(entropypool.TILES.values()) == tails * runs_a_tail
