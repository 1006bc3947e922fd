"""Experiment: service-level throughput sweep on the real device.

Sweeps (engine, max_batch, pipeline_depth, linger) through the full
HTTP stack on one synthetic WSI and prints tiles/s per combo plus the
span timings from /metrics, to find where wave time goes.
"""

import asyncio
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from aiohttp.test_utils import TestClient, TestServer

from omero_ms_image_region_tpu.flagship import synthetic_wsi_tiles
from omero_ms_image_region_tpu.io.store import build_pyramid
from omero_ms_image_region_tpu.server.app import create_app
from omero_ms_image_region_tpu.server.config import (
    AppConfig, BatcherConfig, RawCacheConfig, RendererConfig)


_SEQ = [0]


def run_combo(tmp, engine, max_batch, depth, linger, n_requests=16):
    config = AppConfig(
        data_dir=tmp,
        batcher=BatcherConfig(enabled=True, linger_ms=linger,
                              max_batch=max_batch,
                              pipeline_depth=depth),
        raw_cache=RawCacheConfig(enabled=True, prefetch=False),
        renderer=RendererConfig(cpu_fallback_max_px=0,
                                jpeg_engine=engine))

    async def run():
        app = create_app(config)
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            def url(i):
                # Every request gets a unique window, so each is a
                # distinct render (same discipline as
                # bench._service_run).
                _SEQ[0] += 1
                w = 20000 + (_SEQ[0] % 5000) * 9
                x, y = i % 4, (i // 4) % 4
                return (f"/webgateway/render_image_region/1/0/0"
                        f"?tile=0,{x},{y},1024,1024&format=jpeg&m=c"
                        f"&c=1|0:{w}$FF0000,2|0:{w - 1000}$00FF00,"
                        f"3|0:{w - 2000}$0000FF,4|0:{w - 3000}$FFFF00")
            await asyncio.gather(*(client.get(url(i))
                                   for i in range(n_requests)))
            best = None
            for _ in range(3):
                t0 = time.perf_counter()
                resps = await asyncio.gather(
                    *(client.get(url(i)) for i in range(n_requests)))
                assert all(r.status == 200 for r in resps)
                for r in resps:
                    await r.read()
                dt = time.perf_counter() - t0
                best = dt if best is None else min(best, dt)
            m = await (await client.get("/metrics")).text()
            return n_requests / best, m
        finally:
            await client.close()

    return asyncio.run(run())


def main():
    rng = np.random.default_rng(
        0)
    tmp = tempfile.mkdtemp()
    planes = synthetic_wsi_tiles(rng, 4, 1, 4096, 4096).reshape(
        4, 1, 4096, 4096)
    build_pyramid(planes, os.path.join(tmp, "1"), n_levels=1)

    combos = [
        ("sparse", 8, 2, 3.0),
        ("huffman", 8, 2, 3.0),
        ("huffman", 16, 2, 3.0),
        ("huffman", 16, 3, 3.0),
        ("sparse", 16, 2, 3.0),
        ("sparse", 16, 3, 3.0),
        ("sparse", 8, 3, 3.0),
    ]
    for engine, mb, depth, linger in combos:
        tps, metrics = run_combo(tmp, engine, mb, depth, linger)
        print(f"{engine:8s} mb={mb:3d} depth={depth} linger={linger}: "
              f"{tps:6.1f} tiles/s", flush=True)
        if os.environ.get("SHOW_SPANS"):
            for line in metrics.splitlines():
                if "span" in line and ("renderAsPackedInt" in line
                                       or "getPixelBuffer" in line):
                    print("   ", line)


if __name__ == "__main__":
    main()
