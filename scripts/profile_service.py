"""Service-level stage waterfall (diagnostic for the 10x close).

Runs the bench's exact closed-loop service workload (16 clients,
1024^2 4-ch tiles, k-varied windows) against the real app while
recording where each group's wall time goes:

  queue_wait   request enqueue -> group pop
  group_size   tiles per dispatched group (pad waste shows here)
  dispatch     group pop -> device dispatch returned
  fetch        wire fetch wall (start -> all prefix bytes on host)
  fetch2       under-predicted second fetch (each pays ~1 RTT)
  encode       host entropy/framing tail
  settle       encode done -> futures resolved

Usage: python scripts/profile_service.py [duration_s] [engine]
"""

import asyncio
import os
import statistics
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


class Recorder:
    def __init__(self):
        self.events = {}

    def add(self, name, value):
        self.events.setdefault(name, []).append(value)

    def summary(self):
        out = {}
        for name, vals in sorted(self.events.items()):
            vs = sorted(vals)
            out[name] = {
                "n": len(vs),
                "p50": vs[len(vs) // 2],
                "p90": vs[int(len(vs) * 0.9)],
                "sum": sum(vs),
            }
        return out


REC = Recorder()


def patch():
    """Per-group wall-time split; everything finer-grained (queue wait,
    wire fetch/fetch2, encode) is read from the production REGISTRY
    spans the serving path records itself."""
    from omero_ms_image_region_tpu.ops import jpegenc
    from omero_ms_image_region_tpu.server import batcher as batcher_mod

    orig_jpeg = batcher_mod.BatchingRenderer._render_group_jpeg

    def render_group_jpeg(self, group):
        t0 = time.perf_counter()
        REC.add("group_size", len(group))
        out = orig_jpeg(self, group)
        REC.add("group_total_ms", (time.perf_counter() - t0) * 1e3)
        return out

    batcher_mod.BatchingRenderer._render_group_jpeg = render_group_jpeg


def main():
    duration = float(sys.argv[1]) if len(sys.argv) > 1 else 8.0
    engine = sys.argv[2] if len(sys.argv) > 2 else "huffman"
    max_batch = int(sys.argv[3]) if len(sys.argv) > 3 else 8

    from omero_ms_image_region_tpu.utils.jaxenv import (
        place_compilation_cache)
    place_compilation_cache()

    patch()

    from omero_ms_image_region_tpu.ops import jpegenc as _je

    def observe(nbytes, seconds, conflated=False):
        REC.add("wire_bytes", nbytes)

    _je.set_fetch_observer(observe)

    from omero_ms_image_region_tpu.flagship import synthetic_wsi_tiles
    from omero_ms_image_region_tpu.io.store import build_pyramid
    from omero_ms_image_region_tpu.server.config import (
        AppConfig, BatcherConfig, RawCacheConfig, RendererConfig)

    import bench

    rng = np.random.default_rng(0)
    with tempfile.TemporaryDirectory() as tmp:
        planes = synthetic_wsi_tiles(rng, 4, 1, 4096, 4096).reshape(
            4, 1, 4096, 4096)
        build_pyramid(planes, os.path.join(tmp, "1"), n_levels=1)
        config = AppConfig(
            data_dir=tmp,
            batcher=BatcherConfig(enabled=True, linger_ms=3.0,
                                  max_batch=max_batch),
            raw_cache=RawCacheConfig(enabled=True, prefetch=False),
            renderer=RendererConfig(cpu_fallback_max_px=0,
                                    jpeg_engine=engine))
        t0 = time.perf_counter()
        tps, p50 = asyncio.run(
            bench._service_run(config, duration_s=duration))
        wall = time.perf_counter() - t0

    from omero_ms_image_region_tpu.utils.linkprobe import \
        measure_fetch_mb_s
    link = measure_fetch_mb_s(nbytes=2 << 20, repeats=2)
    tiles = sum(REC.events.get("group_size", []))
    wire_mb = sum(REC.events.get("wire_bytes", [])) / 1e6
    per_tile = wire_mb / max(tiles, 1)
    print(f"\nengine={engine} window={duration}s wall={wall:.1f}s "
          f"tiles/s={tps:.1f} p50={p50:.0f}ms")
    print(f"  link_adjacent={link:.1f} MB/s  wire={wire_mb:.1f} MB "
          f"({per_tile * 1000:.0f} KB/tile)  "
          f"wire_bound_ceiling={link / max(per_tile, 1e-9):.1f} tiles/s")
    for name, s in REC.summary().items():
        if name.endswith("_ms"):
            print(f"  {name:22s} n={s['n']:4d} p50={s['p50']:8.1f} "
                  f"p90={s['p90']:8.1f} sum={s['sum'] / 1e3:7.2f}s")
        else:
            print(f"  {name:22s} n={s['n']:4d} p50={s['p50']:8.0f} "
                  f"p90={s['p90']:8.0f} sum={s['sum']:.0f}")
    sizes = REC.events.get("group_size", [])
    if sizes:
        from collections import Counter
        print("  group size histogram:", dict(sorted(
            Counter(sizes).items())))
    from omero_ms_image_region_tpu.utils.stopwatch import REGISTRY
    print("  -- registry spans --")
    for name, s in sorted(REGISTRY.snapshot().items()):
        print(f"  {name:34s} n={s['count']:5d} mean={s['mean_ms']:8.1f} "
              f"p50={s['p50_ms']:8.1f}")


if __name__ == "__main__":
    main()
