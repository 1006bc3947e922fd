"""HBM-resident raw tile cache: identity, eviction, handler integration."""

import asyncio

import numpy as np
import pytest

from omero_ms_image_region_tpu.io.devicecache import (
    DeviceRawCache, region_key,
)


def test_same_key_loads_once_and_counts():
    cache = DeviceRawCache(max_bytes=1 << 30)
    calls = []

    def loader():
        calls.append(1)
        return np.ones((8, 8), np.float32)

    # One channel plane an entry: the key's last part is one channel.
    key = region_key(1, 0, 0, 0, (0, 0, 8, 8), 1)
    a = cache.get_or_load(key, loader)
    b = cache.get_or_load(key, loader)
    assert len(calls) == 1
    assert a is b
    assert cache.hits == 1 and cache.misses == 1
    assert cache.channel_loads == 1
    assert cache.get_planes([key, region_key(1, 0, 0, 0, (0, 0, 8, 8),
                                             0)]) == [a, None]
    assert cache.hits == 2 and cache.misses == 1
    np.testing.assert_array_equal(np.asarray(a), 1.0)


def test_eviction_respects_byte_budget():
    # DISTINCT content per key: identical content would alias one
    # device buffer (content-digest dedup) and fit the budget forever.
    tile_bytes = 2 * 8 * 8 * 4
    cache = DeviceRawCache(max_bytes=tile_bytes * 2)
    for i in range(4):
        cache.get_or_load(("k", i),
                          lambda i=i: np.full((2, 8, 8), float(i),
                                              np.float32))
    assert len(cache) == 2                       # oldest two evicted
    assert cache.size_bytes == tile_bytes * 2
    assert cache.evictions == 2
    # Oldest keys are gone: reloading key 0 is a miss.
    misses = cache.misses
    cache.get_or_load(("k", 0),
                      lambda: np.full((2, 8, 8), 0.0, np.float32))
    assert cache.misses == misses + 1


def test_digest_aliases_share_buffer_and_bytes():
    """Identical content under many keys holds ONE device buffer and
    ONE byte-budget charge; the bytes leave only with the last alias."""
    tile_bytes = 2 * 8 * 8 * 4
    cache = DeviceRawCache(max_bytes=tile_bytes * 4)
    arrs = [cache.get_or_load(("k", i),
                              lambda: np.zeros((2, 8, 8), np.float32))
            for i in range(3)]
    assert arrs[0] is arrs[1] is arrs[2]     # one buffer, three keys
    assert len(cache) == 3
    assert cache.size_bytes == tile_bytes    # accounted once
    assert cache.plane_hits == 2 and cache.plane_misses == 1
    # Distinct content pushes the shared buffer's aliases out one by
    # one; the shared bytes leave the budget only with the LAST alias.
    for i in range(3):
        cache.get_or_load(("fresh", i),
                          lambda i=i: np.full((2, 8, 8), 1.0 + i,
                                              np.float32))
    assert cache.size_bytes <= tile_bytes * 4


def test_racing_identical_content_misses_share_one_buffer():
    """Two threads key-missing concurrently on identical content must
    converge on ONE device buffer (the in-lock digest re-probe): no
    unaccounted second HBM allocation survives in the cache."""
    import threading

    cache = DeviceRawCache()
    content = np.arange(2 * 8 * 8, dtype=np.uint16).reshape(2, 8, 8)
    barrier = threading.Barrier(2, timeout=10)

    def load():
        barrier.wait()      # both threads inside the miss path at once
        return content.copy()

    outs = [None, None]

    def worker(i):
        outs[i] = cache.get_or_load(("r", i), load)

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert outs[0] is outs[1]               # loser adopted the winner's
    assert cache.size_bytes == content.nbytes
    assert len(cache) == 2                  # both keys present, aliased


def test_wire_probe_counts_hits_only():
    """One actual upload = exactly one plane_misses increment: the
    probe counts only hits (uploads that never happen); the miss is
    recorded by the staging itself."""
    from omero_ms_image_region_tpu.io.staging import stage_deduped

    cache = DeviceRawCache()
    arr = np.arange(128, dtype=np.uint16).reshape(2, 8, 8)
    from omero_ms_image_region_tpu.io.devicecache import plane_digest
    digest = plane_digest(arr)
    assert cache.resident_digest(digest) is False     # probe: cold
    assert cache.plane_misses == 0                    # not yet an upload
    stage_deduped(arr, cache, digest=digest)          # the upload
    assert cache.plane_misses == 1
    assert cache.resident_digest(digest) is True      # probe: warm
    assert cache.plane_hits == 1


def test_prefetcher_stages_neighbor_tiles(tmp_path):
    """Serving one tile schedules its lattice neighbors into the device
    cache, so the next pan step's raw planes are already resident."""
    from omero_ms_image_region_tpu.io.service import PixelsService
    from omero_ms_image_region_tpu.io.store import build_pyramid
    from omero_ms_image_region_tpu.ops.lut import LutProvider
    from omero_ms_image_region_tpu.server.ctx import ImageRegionCtx
    from omero_ms_image_region_tpu.server.handler import (
        ImageRegionHandler, ImageRegionServices, Renderer,
    )
    from omero_ms_image_region_tpu.services.cache import (
        CacheConfig, Caches,
    )
    from omero_ms_image_region_tpu.services.metadata import (
        CanReadMemo, LocalMetadataService,
    )
    from omero_ms_image_region_tpu.services.prefetch import TilePrefetcher

    rng = np.random.default_rng(1)
    planes = rng.integers(0, 60000, size=(1, 1, 64, 64)).astype(np.uint16)
    build_pyramid(planes, str(tmp_path / "4"), chunk=(16, 16), n_levels=1)
    cache = DeviceRawCache()
    prefetcher = TilePrefetcher(cache)
    services = ImageRegionServices(
        pixels_service=PixelsService(str(tmp_path)),
        metadata=LocalMetadataService(str(tmp_path)),
        caches=Caches.from_config(CacheConfig.enabled_all()),
        can_read_memo=CanReadMemo(),
        renderer=Renderer(),
        lut_provider=LutProvider(),
        raw_cache=cache,
        prefetcher=prefetcher,
        cpu_fallback_max_px=0,   # small test tiles must use the device path
    )
    handler = ImageRegionHandler(services)
    ctx = ImageRegionCtx.from_params({
        "imageId": "4", "theZ": "0", "theT": "0", "m": "c",
        "tile": "0,1,1,16,16", "c": "1|0:60000$FF0000", "format": "png",
    })
    loop = asyncio.new_event_loop()
    try:
        loop.run_until_complete(handler.render_image_region(ctx))
    finally:
        loop.close()
    prefetcher.flush()
    # Interior tile: all four lattice neighbors staged + the tile itself.
    assert prefetcher.scheduled == 4
    assert len(cache) == 5
    # Warm viewport: resident neighbors schedule no new pool work.
    loop = asyncio.new_event_loop()
    try:
        loop.run_until_complete(handler.render_image_region(
            ImageRegionCtx.from_params({
                "imageId": "4", "theZ": "0", "theT": "0", "m": "c",
                "tile": "0,1,1,16,16", "c": "1|0:50000$FF0000",
                "format": "png",
            })))
    finally:
        loop.close()
    prefetcher.flush()
    assert prefetcher.scheduled == 4
    prefetcher.close()


def test_settings_change_rerenders_from_device(tmp_path):
    """Two requests for one tile with different windows: the raw read and
    the host->device transfer happen once."""
    from omero_ms_image_region_tpu.io.service import PixelsService
    from omero_ms_image_region_tpu.io.store import build_pyramid
    from omero_ms_image_region_tpu.ops.lut import LutProvider
    from omero_ms_image_region_tpu.server.ctx import ImageRegionCtx
    from omero_ms_image_region_tpu.server.handler import (
        ImageRegionHandler, ImageRegionServices, Renderer,
    )
    from omero_ms_image_region_tpu.services.cache import (
        CacheConfig, Caches,
    )
    from omero_ms_image_region_tpu.services.metadata import (
        CanReadMemo, LocalMetadataService,
    )

    rng = np.random.default_rng(0)
    planes = rng.integers(0, 60000, size=(2, 1, 32, 32)).astype(np.uint16)
    build_pyramid(planes, str(tmp_path / "3"), chunk=(16, 16), n_levels=1)
    cache = DeviceRawCache()
    services = ImageRegionServices(
        pixels_service=PixelsService(str(tmp_path)),
        metadata=LocalMetadataService(str(tmp_path)),
        caches=Caches.from_config(CacheConfig.enabled_all()),
        can_read_memo=CanReadMemo(),
        renderer=Renderer(),
        lut_provider=LutProvider(),
        raw_cache=cache,
        cpu_fallback_max_px=0,   # small test tiles must use the device path
    )
    handler = ImageRegionHandler(services)

    def ctx(window):
        return ImageRegionCtx.from_params({
            "imageId": "3", "theZ": "0", "theT": "0", "m": "c",
            "c": f"1|0:{window}$FF0000", "format": "jpeg",
        })

    loop = asyncio.new_event_loop()
    try:
        first = loop.run_until_complete(
            handler.render_image_region(ctx(60000)))
        second = loop.run_until_complete(
            handler.render_image_region(ctx(30000)))
    finally:
        loop.close()
    assert first[:2] == second[:2] == b"\xff\xd8"
    assert first != second                 # different windows, new render
    assert cache.misses == 1 and cache.hits == 1
