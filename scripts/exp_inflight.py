"""A/B: batcher target_inflight split policy vs max_batch convoys.

Interleaved windows in one process so whatever else the host is doing
hits both arms alike; round 0 is compile warm-up and discounted.

Usage: python scripts/exp_inflight.py [rounds] [window_s] [engine]
"""

import asyncio
import os
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    rounds = int(sys.argv[1]) if len(sys.argv) > 1 else 3
    window = float(sys.argv[2]) if len(sys.argv) > 2 else 8.0
    engine = sys.argv[3] if len(sys.argv) > 3 else "huffman"

    from omero_ms_image_region_tpu.utils.jaxenv import (
        place_compilation_cache)
    place_compilation_cache()

    from omero_ms_image_region_tpu.flagship import synthetic_wsi_tiles
    from omero_ms_image_region_tpu.io.store import build_pyramid
    from omero_ms_image_region_tpu.server.config import (
        AppConfig, BatcherConfig, RawCacheConfig, RendererConfig)

    import bench

    rng = np.random.default_rng(0)
    results = {1: [], 3: []}
    with tempfile.TemporaryDirectory() as tmp:
        planes = synthetic_wsi_tiles(rng, 4, 1, 4096, 4096).reshape(
            4, 1, 4096, 4096)
        build_pyramid(planes, os.path.join(tmp, "1"), n_levels=1)
        for r in range(rounds):
            for ti in (1, 3):
                config = AppConfig(
                    data_dir=tmp,
                    batcher=BatcherConfig(enabled=True, linger_ms=3.0,
                                          target_inflight=ti),
                    raw_cache=RawCacheConfig(enabled=True,
                                             prefetch=False),
                    renderer=RendererConfig(cpu_fallback_max_px=0,
                                            jpeg_engine=engine))
                tps, p50 = asyncio.run(
                    bench._service_run(config, duration_s=window))
                results[ti].append(tps)
                print(f"round {r} target_inflight={ti}: "
                      f"{tps:.1f} tiles/s  p50={p50:.0f} ms",
                      flush=True)
    for ti, vals in results.items():
        steady = vals[1:] or vals
        print(f"target_inflight={ti}: best={max(steady):.1f} "
              f"mean_steady={sum(steady) / len(steady):.1f}")


if __name__ == "__main__":
    main()
