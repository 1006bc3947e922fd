"""Device->host link probe: pick the JPEG wire engine for this link.

The two batched wire engines trade device time against wire bytes
(``ops/jpegenc.py``): "sparse" ships ~0.29 MB per 1024d tile and spends
almost no device time packing; "huffman" packs the full fixed-table
bitstream on device (~0.08 MB/tile, ~3.6x fewer bytes) but its deposit
scatters bound it to ~35-40 tiles/s of device throughput.  Sparse
therefore wins exactly when the link can carry its extra bytes faster
than huffman renders: rate > huffman_ceiling * sparse_bytes/tile
~= 38 * 0.29 ~= 11 MB/s.  ``renderer.jpeg-engine: auto`` measures the
link once at startup and picks accordingly.  (The crossover's inputs
were not measured on the current chip; ROADMAP S7.)
"""

from __future__ import annotations

import logging
import time

logger = logging.getLogger(__name__)

# Crossover (MB/s) above which the sparse wire out-runs the huffman
# engine's device-bound ceiling; see module docstring for the arithmetic.
AUTO_SPARSE_MIN_MB_S = 12.0


def measure_fetch_mb_s(nbytes: int = 4 << 20, repeats: int = 3) -> float:
    """Best-of-N device->host fetch bandwidth in MB/s.

    Each repeat fetches a fresh random buffer.
    """
    import jax
    import numpy as np

    rng = np.random.default_rng(0)
    best = float("inf")
    for _ in range(repeats):
        x = jax.device_put(rng.integers(0, 255, nbytes, dtype=np.uint8))
        jax.block_until_ready(x)
        t0 = time.perf_counter()
        np.asarray(x)
        best = min(best, time.perf_counter() - t0)
    return nbytes / 1e6 / best


def resolve_auto_engine() -> str:
    """Measure the link and return "sparse" or "huffman".

    In a multi-host pod every process MUST resolve to the same engine —
    the engines build different shard_map programs over the same global
    mesh, and divergence hangs the pod (SPMD).  Hosts can sit on opposite
    sides of the crossover (one fast NIC, one congested), so the local
    rate is all-gathered and the pod-wide MINIMUM decides: the slowest
    link is the one the sparse wire would actually stall on.
    """
    # A probe that cannot move 4 MB off the device raises: a backend
    # that broken must not start serving on a guessed engine.
    rate = measure_fetch_mb_s()
    import jax
    if jax.process_count() > 1:
        import numpy as np
        from jax.experimental import multihost_utils
        rates = np.asarray(
            multihost_utils.process_allgather(np.float32(rate)))
        pod_rate = float(rates.min())
        logger.info("link probe (pod): local %.1f MB/s, pod min %.1f MB/s "
                    "across %d hosts", rate, pod_rate, rates.size)
        rate = pod_rate
    engine = "sparse" if rate >= AUTO_SPARSE_MIN_MB_S else "huffman"
    logger.info("link probe: %.1f MB/s device->host -> jpeg engine %r "
                "(crossover %.0f MB/s)", rate, engine, AUTO_SPARSE_MIN_MB_S)
    return engine
