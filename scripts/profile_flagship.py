"""Component-level timing of the flagship JPEG path on the real chip.

Breaks one batch of the config-3 workload into stages and times each:
dispatch+device compute, wire fetch (prefetched and cold), host entropy
encode — plus fetch probes (zeros vs noise payloads of the same shape).
Not part of the bench; a diagnostic for optimization work.
"""

import statistics
import time

import numpy as np

from omero_ms_image_region_tpu.flagship import (
    batched_args, flagship_settings, synthetic_wsi_tiles,
)
from omero_ms_image_region_tpu.ops.jpegenc import (
    default_sparse_cap, encode_sparse_buffers, quant_tables,
    render_to_jpeg_sparse,
)

import jax
import jax.numpy as jnp


def t(fn, n=5):
    fn()
    xs = []
    for _ in range(n):
        t0 = time.perf_counter()
        fn()
        xs.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(xs), min(xs)


def main():
    rng = np.random.default_rng(7)
    B, C, H, W = 8, 4, 1024, 1024
    quality = 85
    cap = default_sparse_cap(H, W)
    _, settings = flagship_settings()
    raw = synthetic_wsi_tiles(rng, B, C, H, W)
    args_suffix = batched_args(settings, raw)[1:]
    qy, qc = (tt.astype(np.int32) for tt in quant_tables(quality))
    dev_raw = jax.device_put(raw)
    jax.block_until_ready(dev_raw)

    buf = render_to_jpeg_sparse(dev_raw, *args_suffix, qy, qc, cap=cap)
    buf.block_until_ready()
    host = np.asarray(buf)
    print("wire buffer shape/bytes per batch:", buf.shape, buf.nbytes)
    nb = (H // 8) * (W // 8) + 2 * (H // 16) * (W // 16)
    totals = host[:, :4].copy().view(np.int32).ravel()
    print("per-tile nonzero entries:", totals.tolist(), "cap:", cap)

    # 1. dispatch + device compute + implicit sync via tiny fetch
    def dispatch_sync():
        b = render_to_jpeg_sparse(dev_raw, *args_suffix, qy, qc, cap=cap)
        np.asarray(b[0, :4])  # sync on 4 bytes
    print("dispatch+device (tiny fetch sync): %.1f / %.1f ms" % t(dispatch_sync))

    # 2. full fetch after async prefetch
    def fetch_prefetched():
        b = render_to_jpeg_sparse(dev_raw, *args_suffix, qy, qc, cap=cap)
        b.copy_to_host_async()
        return b
    b = fetch_prefetched()
    time.sleep(1.0)
    t0 = time.perf_counter()
    host = np.asarray(b)
    print("np.asarray after 1s-old prefetch: %.1f ms" % ((time.perf_counter() - t0) * 1e3))

    def fetch_cold():
        b = render_to_jpeg_sparse(dev_raw, *args_suffix, qy, qc, cap=cap)
        np.asarray(b)
    print("dispatch+full fetch (no prefetch gap): %.1f / %.1f ms" % t(fetch_cold))

    # 3. host entropy encode only
    def encode_only():
        encode_sparse_buffers(host, W, H, quality, cap)
    print("host encode (serial): %.1f / %.1f ms" % t(encode_only))
    import concurrent.futures as cf
    pool = cf.ThreadPoolExecutor(max_workers=8)
    def encode_pool():
        encode_sparse_buffers(host, W, H, quality, cap, executor=pool)
    print("host encode (8 threads): %.1f / %.1f ms" % t(encode_pool))

    # 4. wire compressibility probe: same nbytes, zeros vs random
    nbytes = buf.nbytes
    zeros = jnp.zeros((nbytes,), jnp.uint8)
    noise = jax.device_put(
        np.random.default_rng(0).integers(0, 255, nbytes, dtype=np.uint8))
    jax.block_until_ready([zeros, noise])
    def fz():
        np.asarray(zeros + jnp.uint8(0))
    def fn_():
        np.asarray(noise + jnp.uint8(0))
    print("fetch %d MB zeros: %.1f / %.1f ms" % ((nbytes // 1_000_000,) + t(fz)))
    print("fetch %d MB noise: %.1f / %.1f ms" % ((nbytes // 1_000_000,) + t(fn_)))

    # 5. fetch size sweep (latency floor + bandwidth)
    for mb in (0.01, 0.1, 1, 4, 16):
        n = int(mb * 1e6)
        a = jax.device_put(np.zeros(n, np.uint8))
        jax.block_until_ready(a)
        med, best = t(lambda a=a: np.asarray(a[:]), n=3)
        print("fetch %6.2f MB (device zeros): %.1f ms -> %.1f MB/s"
              % (mb, med, n / 1e6 / (med / 1e3)))


if __name__ == "__main__":
    main()
