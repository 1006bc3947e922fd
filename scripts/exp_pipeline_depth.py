"""Experiment: service-level throughput vs batcher pipeline depth.

The batcher overlaps up to ``pipeline-depth`` group renders (dispatch /
wire fetch / host entropy encode).  Where each group's fetch pays a
long round trip, a shallow pipeline leaves the device idle between
groups; this measures the closed-loop service rate at several depths.

Usage: python scripts/exp_pipeline_depth.py [depth ...]
"""

import asyncio
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from omero_ms_image_region_tpu.flagship import synthetic_wsi_tiles
from omero_ms_image_region_tpu.io.store import build_pyramid
from omero_ms_image_region_tpu.server.config import (
    AppConfig, BatcherConfig, RawCacheConfig, RendererConfig)

import bench  # noqa: E402  (repo-root harness: reuse _service_run)


def main() -> None:
    # Args: colon-separated max_batch:depth pairs, e.g. 8:2 16:4; bare
    # ints are depths with max_batch 8.
    combos = []
    for a in sys.argv[1:]:
        mb, _, d = a.partition(":")
        combos.append((int(mb), int(d)) if d else (8, int(mb)))
    combos = combos or [(8, 2), (8, 4), (16, 2), (16, 4)]
    rng = np.random.default_rng(7)
    with tempfile.TemporaryDirectory() as tmp:
        planes = synthetic_wsi_tiles(rng, 4, 1, 4096, 4096).reshape(
            4, 1, 4096, 4096)
        build_pyramid(planes, os.path.join(tmp, "1"), n_levels=1)
        for engine in ("huffman", "sparse"):
            for max_batch, depth in combos:
                config = AppConfig(
                    data_dir=tmp,
                    batcher=BatcherConfig(enabled=True, linger_ms=3.0,
                                          max_batch=max_batch,
                                          pipeline_depth=depth),
                    raw_cache=RawCacheConfig(enabled=True, prefetch=False),
                    renderer=RendererConfig(cpu_fallback_max_px=0,
                                            jpeg_engine=engine))
                t0 = time.perf_counter()
                tps, _p50 = asyncio.run(bench._service_run(config))
                print(f"engine={engine} batch={max_batch} depth={depth}: "
                      f"{tps:.1f} tiles/s "
                      f"(window {time.perf_counter() - t0:.1f}s)", flush=True)


if __name__ == "__main__":
    main()
