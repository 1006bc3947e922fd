"""A raw-cache entry is ONE channel plane (PR 32): a viewer that toggles
one of its shown channels over a resident view reads and uploads that
plane alone, every sample is resident at most once, every builder of a
cache key agrees with the handler's, and the bytes are a cold server's.
Seeded data, CPU backend."""

import asyncio
import json

import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from omero_ms_image_region_tpu.io.devicecache import (
    DeviceRawCache, entry_region_key, region_key,
)
from omero_ms_image_region_tpu.io.service import PixelsService
from omero_ms_image_region_tpu.io.store import build_pyramid
from omero_ms_image_region_tpu.ops.lut import LutProvider
from omero_ms_image_region_tpu.server.app import create_app
from omero_ms_image_region_tpu.server.batcher import BatchingRenderer
from omero_ms_image_region_tpu.server.config import AppConfig
from omero_ms_image_region_tpu.server.ctx import ImageRegionCtx
from omero_ms_image_region_tpu.server.handler import (
    ImageRegionHandler, ImageRegionServices, Renderer,
)
from omero_ms_image_region_tpu.server.region import RegionDef
from omero_ms_image_region_tpu.services.cache import CacheConfig, Caches
from omero_ms_image_region_tpu.services.metadata import (
    CanReadMemo, LocalMetadataService,
)
from omero_ms_image_region_tpu.utils import telemetry
from omero_ms_image_region_tpu.utils.stopwatch import REGISTRY

IMG = 40
C = 8                           # stored channels; a viewer shows 5-6
EDGE = 64
PLANE_BYTES = EDGE * EDGE * 2
COLORS = ("0000FF", "FF0000", "00FF00", "FFFF00", "FF00FF", "00FFFF",
          "FFFFFF", "FF8000")


def run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()


@pytest.fixture(scope="module")
def data_dir(tmp_path_factory):
    """A 2 x 2 grid of 64^2 tiles, 8 channels, uint16."""
    root = tmp_path_factory.mktemp("cycif")
    rng = np.random.default_rng(32)
    planes = rng.integers(0, 60000, size=(C, 1, 2 * EDGE, 2 * EDGE)
                          ).astype(np.uint16)
    build_pyramid(planes, str(root / str(IMG)), chunk=(EDGE, EDGE),
                  n_levels=1).close()
    return str(root)


def services_of(data_dir, cache=None, renderer=None, **kw):
    return ImageRegionServices(
        pixels_service=PixelsService(data_dir),
        metadata=LocalMetadataService(data_dir),
        caches=Caches.from_config(CacheConfig()),   # no bytes cache
        can_read_memo=CanReadMemo(),
        renderer=renderer or Renderer(),
        lut_provider=LutProvider(),
        raw_cache=DeviceRawCache() if cache is None else cache,
        cpu_fallback_max_px=0,        # 64^2 tiles take the device path
        **kw)


def params(shown, tile="0,0,0,64,64", fmt="jpeg", window=30000, **more):
    """Every stored channel in ``c=``, the hidden ones negative, as
    OMERO.web sends them; ``shown`` is 0-based."""
    c = ",".join(
        f"{'' if i in shown else '-'}{i + 1}|{100 * i}:{window + 500 * i}"
        f"${COLORS[i]}" for i in range(C))
    return {"imageId": str(IMG), "theZ": "0", "theT": "0", "tile": tile,
            "c": c, "m": "c", "format": fmt, "q": "0.9", **more}


def ctx_of(shown, **kw) -> ImageRegionCtx:
    return ImageRegionCtx.from_params(params(shown, **kw))


def render(handler, shown, **kw) -> bytes:
    return run(handler.render_image_region(ctx_of(shown, **kw)))


def stack_spans() -> int:
    return REGISTRY.snapshot().get("handler.channelStack",
                                   {}).get("count", 0)


# ------------------------------------------------- (a) one plane a channel

def test_toggling_one_of_six_loads_one_plane_and_hiding_one_loads_none(
        data_dir):
    cache = DeviceRawCache()
    handler = ImageRegionHandler(services_of(data_dir, cache))
    view = [0, 1, 2, 3, 4, 5]
    spans = stack_spans()
    render(handler, view)
    assert (cache.channel_loads, cache.misses, cache.hits) == (6, 6, 0)
    assert (len(cache), cache.size_bytes) == (6, 6 * PLANE_BYTES)
    # One of the six switched for another: exactly one plane is read.
    render(handler, [0, 1, 2, 3, 4, 7], window=31000)
    assert (cache.channel_loads, cache.misses, cache.hits) == (7, 7, 5)
    assert cache.size_bytes == 7 * PLANE_BYTES
    # One hidden: five resident planes, nothing read.
    render(handler, [0, 1, 2, 3, 7], window=32000)
    assert (cache.channel_loads, cache.misses, cache.hits) == (7, 7, 10)
    assert cache.size_bytes == 7 * PLANE_BYTES
    # Each request put its own stack together, and kept none.
    assert stack_spans() == spans + 3
    assert all(np.asarray(arr).shape == (EDGE, EDGE)
               for arr in cache._entries.values())


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 32])
def test_resident_bytes_are_the_distinct_planes_touched(data_dir, seed):
    """Any sequence of toggles over two tiles: every (region, channel)
    touched is resident exactly once."""
    rng = np.random.default_rng(seed)
    cache = DeviceRawCache()
    handler = ImageRegionHandler(services_of(data_dir, cache))
    shown = sorted(int(c) for c in rng.choice(C, size=5, replace=False))
    touched = set()
    for step in range(12):
        hidden = [c for c in range(C) if c not in shown]
        if len(shown) == 5:
            shown = sorted(shown + [hidden[int(rng.integers(len(hidden)))]])
        else:
            shown.remove(shown[int(rng.integers(len(shown)))])
        tile = ("0,0,0,64,64", "0,1,1,64,64")[step % 2]
        render(handler, shown, tile=tile, window=20000 + step)
        touched |= {(tile, c) for c in shown}
    assert len(cache) == len(touched) == cache.channel_loads
    assert cache.size_bytes == len(touched) * PLANE_BYTES


@pytest.mark.parametrize("fmt", ["jpeg", "png"])
@pytest.mark.parametrize("flips", [{}, {"flip": "h"}, {"flip": "v"},
                                   {"flip": "hv"}])
def test_bodies_are_a_cold_servers_for_the_same_request(data_dir, fmt,
                                                        flips):
    """After toggles have left some of a request's planes resident and
    some not, its answer is byte for byte a cold server's."""
    warm = ImageRegionHandler(services_of(data_dir))
    render(warm, [0, 2, 4, 5, 6], fmt=fmt)
    render(warm, [0, 1, 2, 3, 4, 5], fmt=fmt, window=25000)
    want = [0, 1, 3, 5, 6, 7]       # 0 1 3 5 6 resident, 7 not
    got = render(warm, want, fmt=fmt, window=28000, **flips)
    cold = ImageRegionHandler(services_of(data_dir))
    assert got == render(cold, want, fmt=fmt, window=28000, **flips)
    # And all resident: the event loop's fast path.
    assert got == render(warm, want, fmt=fmt, window=28000, **flips)
    if flips:
        assert got != render(cold, want, fmt=fmt, window=28000)


def test_an_evicted_channel_is_reread_alone(data_dir):
    """The LRU drops one channel plane of a view; the next request of
    the view reads that plane and no other."""
    cache = DeviceRawCache(max_bytes=7 * PLANE_BYTES)
    handler = ImageRegionHandler(services_of(data_dir, cache))
    view = [1, 2, 3, 4, 5, 6]
    render(handler, view)
    render(handler, [0], tile="0,1,0,64,64")
    assert (len(cache), cache.evictions) == (7, 0)
    render(handler, [0], tile="0,0,1,64,64")     # the 8th plane
    assert (len(cache), cache.evictions) == (7, 1)
    first = region_key(IMG, 0, 0, 0, (0, 0, EDGE, EDGE), view[0])
    assert first not in cache                    # the view's oldest
    loads = cache.channel_loads
    body = render(handler, view)
    assert cache.channel_loads == loads + 1 and first in cache
    assert body == render(ImageRegionHandler(services_of(data_dir)), view)


def test_the_span_and_the_counter_are_on_metrics_and_the_trace(data_dir):
    cfg = AppConfig(data_dir=data_dir)
    cfg.raw_cache.enabled = True
    cfg.raw_cache.prefetch = False      # the request's own loads only
    cfg.renderer.cpu_fallback_max_px = 0
    query = "&".join(f"{k}={v}" for k, v in params(
        [0, 3, 5]).items() if k not in ("imageId", "theZ", "theT"))
    url = f"/webgateway/render_image_region/{IMG}/0/0?{query}"

    async def main():
        client = TestClient(TestServer(create_app(cfg)))
        await client.start_server()
        try:
            for _ in range(2):
                resp = await client.get(url.replace("|0:", "|%d:" % _))
                assert resp.status == 200, await resp.text()
                await resp.read()
            return await (await client.get("/metrics")).text()
        finally:
            await client.close()

    telemetry.TRACES.recent.clear()
    spans = stack_spans()           # the registry is the process's
    text = asyncio.run(main())
    assert "imageregion_rawcache_channel_loads_total 3" in text
    assert "# TYPE imageregion_rawcache_channel_loads_total counter" \
        in text
    assert "imageregion_rawcache_misses 3" in text
    assert "imageregion_rawcache_hits 3" in text
    assert ('imageregion_span_count{span="handler.channelStack"} '
            f'{spans + 2}\n') in text
    stacks = [s for t in telemetry.TRACES.recent
              if t.route == "render_image_region"
              for s in t.spans if s["name"] == "handler.channelStack"]
    assert [(s["channels"], s["missing"]) for s in stacks] == [
        (3, 3), (3, 0)]


# --------------------------------------- (b) every builder of a key agrees

def _handler_keys(shown, tile=(0, 0)):
    x, y = tile
    ctx = ctx_of(shown, tile=f"0,{x},{y},{EDGE},{EDGE}")
    return ImageRegionHandler._plane_keys(
        ctx, RegionDef(x * EDGE, y * EDGE, EDGE, EDGE), 0, shown)


def _via_prefetch(data_dir, source, shown):
    """The prefetcher's keys for the tile right of (0, 0)."""
    from omero_ms_image_region_tpu.services.prefetch import TilePrefetcher
    cache = DeviceRawCache()
    prefetcher = TilePrefetcher(cache)
    services = services_of(data_dir, cache, prefetcher=prefetcher)
    try:
        render(ImageRegionHandler(services), shown)
        prefetcher.flush()
    finally:
        prefetcher.close()
    return cache, _handler_keys(shown, (1, 0))


def _via_warmstate(data_dir, source, shown):
    from omero_ms_image_region_tpu.services.warmstate import (
        restage_plane_entry)
    cache = DeviceRawCache()
    pixels = PixelsService(data_dir)
    # Through JSON, as a manifest on disk.
    for entry in json.loads(json.dumps(source.snapshot_entries())):
        assert restage_plane_entry(cache, pixels, entry) is True
    return cache, _handler_keys(shown)


def _via_sidecar(data_dir, source, shown):
    from omero_ms_image_region_tpu.server.sidecar import _shard_transfer
    cache = DeviceRawCache()
    receiver = ImageRegionHandler(services_of(data_dir, cache))
    for entry in source.snapshot_entries():
        host = np.asarray(source.get(entry_region_key(entry)))
        header = {"entry": json.loads(json.dumps(
            {**entry, "dtype": str(host.dtype),
             "shape": list(host.shape)}))}
        run(_shard_transfer(receiver, header, host.tobytes()))
    return cache, _handler_keys(shown)


def _via_fleet(data_dir, source, shown):
    from omero_ms_image_region_tpu.parallel.fleet import LocalMember
    cache = DeviceRawCache()
    giver = services_of(data_dir, source)
    taker = services_of(data_dir, cache)
    exported = run(LocalMember(
        "m0", ImageRegionHandler(giver), giver).shard_export())
    assert run(LocalMember(
        "m1", ImageRegionHandler(taker), taker).shard_transfer(
        exported)) == len(exported)
    return cache, _handler_keys(shown)


@pytest.mark.parametrize("site", ["handler", "prefetch", "warmstate",
                                  "sidecar", "fleet"])
def test_every_builder_of_a_key_agrees_with_the_handlers(data_dir, site):
    """The five construction sites: what each puts into a cache is
    found by the handler's probe, plane for plane (a foreground request
    then reads nothing)."""
    shown = [0, 2, 5]
    source = DeviceRawCache()
    render(ImageRegionHandler(services_of(data_dir, source)), shown)
    want = _handler_keys(shown)
    assert set(source._entries) == set(want) == {
        region_key(IMG, 0, 0, 0, (0, 0, EDGE, EDGE), c) for c in shown}
    if site == "handler":
        cache, keys = source, want
    else:
        cache, keys = {"prefetch": _via_prefetch,
                       "warmstate": _via_warmstate,
                       "sidecar": _via_sidecar,
                       "fleet": _via_fleet}[site](data_dir, source, shown)
    assert set(keys) <= set(cache._entries)
    assert all(np.asarray(p).shape == (EDGE, EDGE)
               for p in cache.get_planes(keys))
    # The foreground request of those planes loads nothing.
    tile = "0,1,0,64,64" if site == "prefetch" else "0,0,0,64,64"
    loads = cache.channel_loads
    body = render(ImageRegionHandler(services_of(data_dir, cache)), shown,
                  tile=tile, window=27000)
    assert cache.channel_loads == loads
    assert body == render(ImageRegionHandler(services_of(data_dir)),
                          shown, tile=tile, window=27000)


def test_an_entry_of_the_older_format_is_refused_cleanly(data_dir):
    """A manifest written before PR 32 names a LIST of channels where
    one channel stands now: skipped entry by entry (a cold miss later),
    never an exception out of a boot or a hand-off."""
    from omero_ms_image_region_tpu.parallel.fleet import LocalMember
    from omero_ms_image_region_tpu.services.warmstate import (
        restage_plane_entry)
    old = {"key": [IMG, 0, 0, 0, [0, 0, EDGE, EDGE], [0, 1]],
           "digest": None, "route": "r"}
    with pytest.raises(TypeError):
        entry_region_key(old)
    cache = DeviceRawCache()
    assert restage_plane_entry(cache, PixelsService(data_dir), old) is False
    services = services_of(data_dir, cache)
    member = LocalMember("m0", ImageRegionHandler(services), services)
    arr = np.zeros((2, EDGE, EDGE), np.uint16)
    assert run(member.shard_transfer([
        {**old, "dtype": "uint16", "shape": list(arr.shape),
         "bytes": arr.tobytes()}])) == 0
    assert len(cache) == 0
    new = {**old, "key": old["key"][:5] + [1]}
    assert restage_plane_entry(cache, PixelsService(data_dir), new) is True
    assert entry_region_key(new) in cache


# ------------------------------------- (c) a queue of mixed active counts

def test_a_mixed_five_and_six_channel_queue_answers_as_each_alone(
        data_dir):
    """Requests that show five channels and requests that show six,
    side by side in the batcher's queue (two group keys, two program
    sets: no count is padded): every answer is the answer of the same
    request rendered alone."""
    requests = [
        (shown, tile, 26000 + 300 * i)
        for i, (shown, tile) in enumerate(
            (shown, tile)
            for tile in ("0,0,0,64,64", "0,1,0,64,64", "0,0,1,64,64")
            for shown in ([0, 1, 2, 4, 6], [0, 1, 2, 4, 6, 7],
                          [0, 3, 4, 5, 7], [0, 2, 3, 4, 5, 7]))]

    async def together():
        renderer = BatchingRenderer(max_batch=8, linger_ms=20.0,
                                    buckets=((EDGE, EDGE),))
        handler = ImageRegionHandler(
            services_of(data_dir, renderer=renderer))
        try:
            bodies = await asyncio.gather(*(
                handler.render_image_region(
                    ctx_of(shown, tile=tile, window=window))
                for shown, tile, window in requests))
            return bodies, renderer.batches_dispatched, sorted(
                key[1] for key in renderer._queues)
        finally:
            await renderer.close()

    async def alone(shown, tile, window):
        renderer = BatchingRenderer(max_batch=8, buckets=((EDGE, EDGE),))
        handler = ImageRegionHandler(
            services_of(data_dir, renderer=renderer))
        try:
            return await handler.render_image_region(
                ctx_of(shown, tile=tile, window=window))
        finally:
            await renderer.close()

    bodies, groups, counts = run(together())
    assert counts == [5, 6]                 # one queue a shown count
    assert groups < len(requests)           # they did share groups
    for body, request in zip(bodies, requests):
        assert body[:2] == b"\xff\xd8"
        assert body == run(alone(*request)), request
