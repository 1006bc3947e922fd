"""Native C++ tier: LRU cache semantics, bit ops, flip parity.

Skipped wholesale when no g++ toolchain can build the shared library.
"""

import numpy as np
import pytest

native = pytest.importorskip(
    "omero_ms_image_region_tpu.native",
    reason="native toolchain unavailable")


class TestNativeLRUCache:
    def test_round_trip(self):
        cache = native.NativeLRUCache(max_bytes=1 << 20, shards=4)
        assert cache.get_sync("missing") is None
        cache.set_sync("k", b"hello world")
        assert cache.get_sync("k") == b"hello world"
        assert cache.hits == 1
        assert cache.misses == 1

    def test_overwrite(self):
        cache = native.NativeLRUCache(max_bytes=1 << 20)
        cache.set_sync("k", b"a" * 100)
        cache.set_sync("k", b"b")
        assert cache.get_sync("k") == b"b"

    def test_eviction_under_budget(self):
        # Single shard so the LRU order is deterministic.
        cache = native.NativeLRUCache(max_bytes=1000, shards=1)
        for i in range(100):
            cache.set_sync(f"k{i}", b"x" * 100)
        assert cache.size_bytes <= 1000
        assert cache.get_sync("k99") == b"x" * 100
        assert cache.get_sync("k0") is None

    def test_lru_recency(self):
        cache = native.NativeLRUCache(max_bytes=300, shards=1)
        cache.set_sync("a", b"x" * 100)
        cache.set_sync("b", b"y" * 100)
        cache.get_sync("a")                   # a most-recent
        cache.set_sync("c", b"z" * 150)       # evicts b, not a
        assert cache.get_sync("a") is not None
        assert cache.get_sync("b") is None

    def test_empty_value(self):
        cache = native.NativeLRUCache()
        cache.set_sync("empty", b"")
        assert cache.get_sync("empty") == b""

    def test_many_shards_consistent(self):
        cache = native.NativeLRUCache(max_bytes=1 << 22, shards=16)
        blobs = {f"key-{i}": bytes([i % 256]) * (i + 1) for i in range(500)}
        for k, v in blobs.items():
            cache.set_sync(k, v)
        for k, v in blobs.items():
            assert cache.get_sync(k) == v

    def test_concurrent_access(self):
        import threading
        cache = native.NativeLRUCache(max_bytes=1 << 22, shards=8)
        errors = []

        def worker(tid):
            try:
                for i in range(200):
                    key = f"t{tid}-{i}"
                    cache.set_sync(key, key.encode() * 50)
                    got = cache.get_sync(key)
                    assert got == key.encode() * 50
            except Exception as e:
                errors.append(e)

        threads = [threading.Thread(target=worker, args=(t,))
                   for t in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors


class TestNativeBitOps:
    def test_unpack_matches_numpy(self):
        rng = np.random.default_rng(0)
        data = rng.integers(0, 256, size=100, dtype=np.uint8).tobytes()
        for n_bits in (1, 7, 8, 9, 640, 799):
            expected = np.unpackbits(
                np.frombuffer(data, np.uint8))[:n_bits]
            got = native.unpack_bits_msb(data, n_bits)
            np.testing.assert_array_equal(got, expected)

    def test_flip_u32_matches_numpy(self):
        rng = np.random.default_rng(1)
        img = rng.integers(0, 2**32, size=(33, 57), dtype=np.uint32)
        for fh in (False, True):
            for fv in (False, True):
                expected = img
                if fv:
                    expected = expected[::-1]
                if fh:
                    expected = expected[:, ::-1]
                np.testing.assert_array_equal(
                    native.flip_u32(img, fh, fv), expected)

    def test_mask_overlay_matches_numpy_fallback(self):
        """Native OpenMP blend is bit-identical to the integer numpy
        formula overlay_masks_batch falls back to."""
        rng = np.random.default_rng(2)
        B, H, W = 4, 37, 53
        base = rng.integers(0, 255, size=(B, H, W, 4)).astype(np.uint8)
        grids = rng.integers(0, 2, size=(B, H, W)).astype(np.uint8)
        fills = rng.integers(0, 255, size=(B, 4)).astype(np.uint8)
        got = native.mask_overlay_u8(base, grids, fills)
        a = (grids.astype(np.uint32)
             * fills[:, None, None, 3].astype(np.uint32))[..., None]
        fill_rgb = fills[:, None, None, :3].astype(np.uint32)
        expected = base.copy()
        expected[..., :3] = ((base[..., :3].astype(np.uint32) * (255 - a)
                              + fill_rgb * a + 127) // 255).astype(np.uint8)
        np.testing.assert_array_equal(got, expected)
        # Opaque fill fully replaces RGB under the mask; alpha preserved.
        fills[:, 3] = 255
        o = native.mask_overlay_u8(base, grids, fills)
        m = grids.astype(bool)
        for b in range(B):
            np.testing.assert_array_equal(
                o[b][m[b]][:, :3],
                np.broadcast_to(fills[b, :3], (int(m[b].sum()), 3)))
        np.testing.assert_array_equal(o[..., 3], base[..., 3])

    def test_mask_overlay_division_exactness(self):
        """Pin the exact (x + 127) / 255 rounding over the full input
        lattice.  The vectorized blend uses the identity
        q = (x + 1 + (x >> 8)) >> 8; the widespread variant WITHOUT the
        +1 is wrong exactly when x + 127 lands on 255 (e.g. alpha 1,
        base 0, fill 128) — enumerate every (base, fill) pair for the
        boundary-prone alphas so that class can never regress."""
        for alpha in (0, 1, 2, 127, 128, 253, 254, 255):
            b_all = np.repeat(np.arange(256, dtype=np.uint8), 256)
            f_all = np.tile(np.arange(256, dtype=np.uint8), 256)
            B = b_all.size
            base = np.zeros((1, 1, B, 4), np.uint8)
            base[0, 0, :, 0] = b_all
            grids = np.ones((1, 1, B), np.uint8)
            for fv in (0, 1, 128, 255):
                fills = np.array([[0, fv, fv, alpha]], np.uint8)
                fills[0, 0] = 0   # red channel swept via base instead
                got = native.mask_overlay_u8(base, grids, fills)
                a = np.uint32(alpha)
                exp_r = ((b_all.astype(np.uint32) * (255 - a) + 0 * a
                          + 127) // 255).astype(np.uint8)
                np.testing.assert_array_equal(got[0, 0, :, 0], exp_r)
                exp_g = ((0 * (255 - a) + np.uint32(fv) * a + 127)
                         // 255).astype(np.uint8)
                np.testing.assert_array_equal(
                    got[0, 0, :, 1], np.full(B, exp_g, np.uint8))

    def test_mask_overlay_validates_shapes(self):
        import pytest
        base = np.zeros((2, 8, 8, 4), np.uint8)
        with pytest.raises(ValueError, match="mask_grids"):
            native.mask_overlay_u8(base, np.zeros((2, 4, 4), np.uint8),
                                   np.zeros((2, 4), np.uint8))
        with pytest.raises(ValueError, match="fills"):
            native.mask_overlay_u8(base, np.zeros((2, 8, 8), np.uint8),
                                   np.zeros((1, 4), np.uint8))

    def test_mask_overlay_nonzero_means_on(self):
        """0/255-style masks blend identically to 0/1 masks in both the
        native and the numpy fallback paths."""
        from omero_ms_image_region_tpu.ops.maskops import (
            overlay_masks_batch)
        rng = np.random.default_rng(3)
        base = rng.integers(0, 255, size=(2, 16, 16, 4)).astype(np.uint8)
        g01 = rng.integers(0, 2, size=(2, 16, 16)).astype(np.uint8)
        fills = rng.integers(0, 255, size=(2, 4)).astype(np.uint8)
        np.testing.assert_array_equal(
            overlay_masks_batch(base, g01 * 255, fills),
            overlay_masks_batch(base, g01, fills))

    def test_tiff_lzw_matches_python_decoder(self):
        """Native LZW decode is byte-identical to the pure-Python
        reference on PIL-produced streams and rejects malformed input."""
        import io as _io
        import pytest
        from PIL import Image

        from omero_ms_image_region_tpu.io.tiff import (TiffFile,
                                                       _lzw_decode)

        rng = np.random.default_rng(5)
        # Mixed content: smooth + noisy (exercises table resets/KwKwK).
        a = (np.outer(np.arange(211), np.ones(333)).astype(np.uint16)
             + rng.integers(0, 300, size=(211, 333)).astype(np.uint16))
        buf = _io.BytesIO()
        Image.fromarray(a).save(buf, format="TIFF",
                                compression="tiff_lzw")
        import tempfile, os
        with tempfile.TemporaryDirectory() as d:
            p = os.path.join(d, "l.tif")
            open(p, "wb").write(buf.getvalue())
            tf = TiffFile(p)
            ifd = tf.ifds[0]
            offs = ifd.get(273)
            cnts = ifd.get(279)
            for i in range(len(offs)):
                raw = tf._pread(int(offs[i]), int(cnts[i]))
                expected = _lzw_decode(raw)
                got = native.tiff_lzw_decode(raw, len(expected))
                assert got == expected, f"strip {i} differs"
            tf.close()
        with pytest.raises(ValueError):
            native.tiff_lzw_decode(b"\xff\xff\xff\xff", 10)


class TestBuildStaleness:
    """A built library is valid for its SOURCE (a hash of it and the
    flags), not for an mtime order a copy of the tree can invert."""

    def test_keyed_on_source_hash_not_mtime(self, tmp_path, monkeypatch):
        import os
        monkeypatch.setattr(native, "_BUILD_DIR", str(tmp_path))
        src = tmp_path / "one.cpp"
        lib = str(tmp_path / "libone.so")
        src.write_text('extern "C" int one() { return 1; }\n')
        assert native._is_stale(str(src), lib)          # never built
        native._compile_lib(str(src), lib)
        assert not native._is_stale(str(src), lib)
        # mtimes either way round change nothing.
        os.utime(src, (1, 1))
        assert not native._is_stale(str(src), lib)
        os.utime(lib, (1, 1))
        os.utime(src, None)
        assert not native._is_stale(str(src), lib)
        # The source changing does, and so does a missing stamp or lib.
        src.write_text('extern "C" int one() { return 2; }\n')
        assert native._is_stale(str(src), lib)
        native._compile_lib(str(src), lib)
        os.remove(lib + ".stamp")
        assert native._is_stale(str(src), lib)
        native._compile_lib(str(src), lib)
        os.remove(lib)
        assert native._is_stale(str(src), lib)

    def test_status_names_what_was_built(self):
        assert native.status() == {"entropy_coder": "native",
                                   "tile_cache": "native"}
