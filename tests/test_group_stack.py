"""A group's planes are stacked once, by one program (PR 33): a request
whose shown planes are all resident, unflipped and bucket-sized carries
them to the batcher as a tuple, and ``ops.render.stack_group_planes``
builds the group's ``[B, C, bh, bw]`` array in one dispatch.  Anything
else (a flip, an edge tile, a renderer that does not batch) stacks a
request as before, and every body is the parent's byte for byte.
Seeded data, CPU backend: counts and bytes, never a speed."""

import asyncio
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from omero_ms_image_region_tpu.io.devicecache import DeviceRawCache
from omero_ms_image_region_tpu.io.service import PixelsService
from omero_ms_image_region_tpu.io.store import build_pyramid
from omero_ms_image_region_tpu.ops import render as render_ops
from omero_ms_image_region_tpu.ops.lut import LutProvider
from omero_ms_image_region_tpu.server import handler as handler_mod
from omero_ms_image_region_tpu.server.app import create_app
from omero_ms_image_region_tpu.server.batcher import (
    BatchingRenderer, _Pending, _pad_batch_size,
)
from omero_ms_image_region_tpu.server.config import AppConfig
from omero_ms_image_region_tpu.server.ctx import ImageRegionCtx
from omero_ms_image_region_tpu.server.handler import (
    ImageRegionHandler, ImageRegionServices, Renderer,
)
from omero_ms_image_region_tpu.services.cache import CacheConfig, Caches
from omero_ms_image_region_tpu.services.metadata import (
    CanReadMemo, LocalMetadataService,
)
from omero_ms_image_region_tpu.utils import telemetry
from omero_ms_image_region_tpu.utils.stopwatch import REGISTRY

IMG = 33
C = 6
EDGE = 64
COLORS = ("0000FF", "FF0000", "00FF00", "FFFF00", "FF00FF", "00FFFF")


def run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()


@pytest.fixture(scope="module")
def data_dir(tmp_path_factory):
    """A 2 x 2 grid of 64^2 tiles, 6 channels, uint16."""
    root = tmp_path_factory.mktemp("group")
    rng = np.random.default_rng(33)
    planes = rng.integers(0, 60000, size=(C, 1, 2 * EDGE, 2 * EDGE)
                          ).astype(np.uint16)
    build_pyramid(planes, str(root / str(IMG)), chunk=(EDGE, EDGE),
                  n_levels=1).close()
    return str(root)


def ctx_of(shown, fmt="jpeg", window=30000, **where) -> ImageRegionCtx:
    c = ",".join(
        f"{'' if i in shown else '-'}{i + 1}|{100 * i}:{window + 500 * i}"
        f"${COLORS[i]}" for i in range(C))
    return ImageRegionCtx.from_params({
        "imageId": str(IMG), "theZ": "0", "theT": "0", "c": c, "m": "c",
        "format": fmt, "q": "0.9", **where})


def services_of(data_dir, renderer):
    return ImageRegionServices(
        pixels_service=PixelsService(data_dir),
        metadata=LocalMetadataService(data_dir),
        caches=Caches.from_config(CacheConfig()),   # no bytes cache
        can_read_memo=CanReadMemo(),
        renderer=renderer, lut_provider=LutProvider(),
        raw_cache=DeviceRawCache(),
        cpu_fallback_max_px=0)        # 64^2 tiles take the device path


def batcher(**kw) -> BatchingRenderer:
    return BatchingRenderer(max_batch=8, linger_ms=20.0,
                            buckets=((EDGE, EDGE),), **kw)


class _StacksARequest(BatchingRenderer):
    """The parent's flow: the handler stacks every request."""
    takes_planes = None


def serve(data_dir, renderer, rounds):
    """Each round's requests side by side through handler -> renderer;
    the bodies of every round."""
    async def main():
        handler = ImageRegionHandler(services_of(data_dir, renderer))
        try:
            return [await asyncio.gather(*(
                handler.render_image_region(ctx_of(**kw)) for kw in r))
                for r in rounds]
        finally:
            if hasattr(renderer, "close"):
                await renderer.close()
    return run(main())


def counted(monkeypatch):
    """Calls of the two stacking programs, wherever they are named."""
    calls = {"group": 0, "request": 0}

    def counting(name, fn):
        def wrapper(*args, **kw):
            calls[name] += 1
            return fn(*args, **kw)
        return wrapper

    monkeypatch.setattr(render_ops, "stack_group_planes", counting(
        "group", render_ops.stack_group_planes))
    request = counting("request", render_ops.stack_channel_planes)
    monkeypatch.setattr(render_ops, "stack_channel_planes", request)
    monkeypatch.setattr(handler_mod, "stack_channel_planes", request)
    return calls


def stack_spans() -> int:
    return REGISTRY.snapshot().get("handler.channelStack",
                                   {}).get("count", 0)


# --------------------------------------------------- the group's program

@pytest.mark.parametrize("n, B, chans, edge", [
    (40, 64, 4, 256), (7, 8, 4, 64), (3, 3, 6, 64), (5, 6, 6, 64)])
def test_group_program_is_the_stack_of_the_requests_stacks(n, B, chans,
                                                           edge):
    """Bit for bit, padded slots (repeats of the last member)
    included, and the same array a group of stacked members gives."""
    rng = np.random.default_rng(B * chans + edge)
    members = [tuple(jax.device_put(rng.integers(
        0, 65536, size=(edge, edge)).astype(np.uint16))
        for _ in range(chans)) for _ in range(n)]

    def group_of(raws):
        return [_Pending(raw=raw, settings={}, h=edge, w=edge,
                         bucket_px=edge * edge) for raw in raws]

    renderer = BatchingRenderer(max_batch=8)
    assert _pad_batch_size(n, renderer.group_cap(edge * edge)) == B
    raw, _ = renderer._group_arrays(group_of(members))
    stacks = [render_ops.stack_channel_planes(*m) for m in members]
    want = np.asarray(jnp.stack(stacks + stacks[-1:] * (B - n)))
    assert raw.shape == (B, chans, edge, edge) and raw.dtype == jnp.uint16
    assert np.array_equal(np.asarray(raw), want)
    assert renderer.group_stacks == {"planes": 1, "arrays": 0}
    # One member of the other form: the old path, whole.
    old, _ = renderer._group_arrays(group_of(stacks[:1] + members[1:]))
    assert np.array_equal(np.asarray(old), want)
    assert renderer.group_stacks == {"planes": 1, "arrays": 1}


def test_takes_planes_follows_the_bucket():
    renderer = BatchingRenderer(buckets=((64, 64), (256, 256)))
    assert renderer.takes_planes(64, 64, jpeg=True)
    assert renderer.takes_planes(256, 256, jpeg=False)
    assert not renderer.takes_planes(48, 64, jpeg=True)    # padded
    assert not renderer.takes_planes(64, 100, jpeg=False)
    assert not renderer.takes_planes(250, 256, jpeg=True)  # MCU grid
    # Oversize: its own exact shape is its bucket, 16-aligned or not
    # on the packed route, 16-aligned only on the JPEG route.
    assert renderer.takes_planes(300, 300, jpeg=False)
    assert renderer.takes_planes(320, 320, jpeg=True)
    assert not renderer.takes_planes(300, 300, jpeg=True)
    assert not hasattr(Renderer(), "takes_planes")


# -------------------------------------------- one dispatch a group, counted

TILES = ("0,0,0,64,64", "0,1,0,64,64", "0,0,1,64,64", "0,1,1,64,64")


def test_a_resident_group_is_one_program_and_the_hit_path_none(
        data_dir, monkeypatch):
    shown = [0, 1, 2, 4]
    fill = [dict(shown=shown, tile=t) for t in TILES]
    hits = [dict(shown=shown, tile=t, window=31000 + 100 * i)
            for i, t in enumerate(TILES + TILES[:2])]
    renderer = batcher()
    calls = counted(monkeypatch)
    spans = stack_spans()

    async def main():
        handler = ImageRegionHandler(services_of(data_dir, renderer))
        try:
            await asyncio.gather(*(handler.render_image_region(
                ctx_of(**kw)) for kw in fill))
            before = dict(calls), dict(renderer.group_stacks)
            bodies = await asyncio.gather(*(handler.render_image_region(
                ctx_of(**kw)) for kw in hits))
            return before, bodies
        finally:
            await renderer.close()

    (calls0, stacks0), bodies = run(main())
    # The misses too hand their planes on (the reading thread's tail).
    assert calls0["request"] == 0 and stacks0["arrays"] == 0
    # Six resident requests in one group (on a quiet machine; never
    # more groups than requests): one program a group in
    # batcher.stage, none on the hit path.  Groups are counted where
    # they are staged: a group's waiters are settled tile by tile
    # before its own count of batches moves.
    groups = renderer.group_stacks["planes"] - stacks0["planes"]
    assert 1 <= groups <= len(hits)
    assert calls["group"] == calls0["group"] + groups
    assert calls["request"] == 0
    assert renderer.group_stacks["arrays"] == 0
    # The span fires once a request all the same.
    assert stack_spans() == spans + len(fill) + len(hits)
    assert all(b[:2] == b"\xff\xd8" for b in bodies)


@pytest.mark.parametrize("odd", [
    dict(tile="0,0,1,64,64", flip="hv"),       # flipped: stacked, turned
    dict(region="96,80,32,48"),                # an edge tile: padded
])
def test_one_odd_member_sends_its_group_down_the_old_path(
        data_dir, monkeypatch, odd):
    shown = [0, 1, 2, 4]
    requests = [dict(shown=shown, tile=t, window=32000 + 100 * i)
                for i, t in enumerate(TILES[:3])]
    requests.append(dict(shown=shown, window=33000, **odd))
    renderer = batcher()
    calls = counted(monkeypatch)
    fill, bodies = serve(data_dir, renderer, [requests, requests])
    assert fill == bodies
    # The second round is one group of four resident requests, one of
    # them stacked by the handler: the group stacks its members' stacks.
    assert calls["request"] >= 1
    assert renderer.group_stacks["arrays"] >= 1
    assert bodies == serve(data_dir, _StacksARequest(
        max_batch=8, linger_ms=20.0, buckets=((EDGE, EDGE),)),
        [requests])[0]
    alone = [serve(data_dir, Renderer(), [[kw]])[0][0] for kw in requests]
    assert bodies == alone


# ------------------------------------------------- the parent's bytes

# SHA-256 of the bodies below from the parent commit (56ef8c9, PR 32;
# CPU backend, this container), handler -> BatchingRenderer, cold and
# again with every plane resident.
PARENT = {
    "tile": (dict(shown=[0, 1, 2, 4], tile="0,0,0,64,64"),
             "8c0c29ad53247efb4a6e95e56acee57fb563129a27f5414db26084141df3c6f1"),
    "tile2": (dict(shown=[0, 1, 2, 4], tile="0,1,0,64,64", window=31000),
              "0745499d02905f714975e13e053557d6eeaad71f61838af98d750ee192be9cde"),
    "tile3": (dict(shown=[0, 1, 2, 4], tile="0,1,1,64,64", window=32000),
              "7ba2967419659503972b771d53658088144abcfe4aa535d40435e5c804760099"),
    "flipped": (dict(shown=[0, 1, 2, 4], tile="0,0,1,64,64", flip="hv",
                     window=33000),
                "89253dc6df241fa363c751fc6b2ea41283ec5a6be5d5d82f6ec4abf98e067338"),
    "edge": (dict(shown=[0, 1, 2, 4], region="96,80,32,48", window=34000),
             "85c9e382fc1675a7bf3c7cb3e59796a5b177615954cd480ad545f1586a85d72e"),
    "png": (dict(shown=[0, 2, 5], tile="0,0,0,64,64", fmt="png"),
            "99baf3a2ae656e5cf94ead1083737c31b4517b15c7d16127aaa939b956a11eac"),
}


@pytest.fixture(scope="module")
def served(data_dir):
    requests = [kw for kw, _ in PARENT.values()]
    renderer = batcher()
    rounds = serve(data_dir, renderer, [requests, requests])
    return renderer, rounds, serve(data_dir, Renderer(), [requests])[0]


@pytest.mark.parametrize("name", list(PARENT))
def test_served_bodies_are_the_parents(served, name):
    """Through handler -> batcher, missed and resident, the body is the
    parent's; where a renderer that this PR leaves alone
    (``handler.Renderer``) gives other bytes than it gave the parent
    here, the floats are another machine's and the pin says nothing."""
    renderer, (cold, warm), unbatched = served
    i = list(PARENT).index(name)
    assert cold[i] == warm[i] == unbatched[i]
    assert renderer.group_stacks["planes"] >= 1
    digest = hashlib.sha256(warm[i]).hexdigest()
    if hashlib.sha256(unbatched[0]).hexdigest() != PARENT["tile"][1]:
        pytest.skip("another float platform than the pin's")
    assert digest == PARENT[name][1]


# ------------------------------------------------------ /metrics, prewarm

def test_group_stacks_are_counted_on_metrics(tmp_path):
    """The shipped buckets (a stock 256^2 tile fills the smallest)."""
    planes = np.random.default_rng(256).integers(
        0, 60000, size=(2, 1, 256, 256)).astype(np.uint16)
    build_pyramid(planes, str(tmp_path / str(IMG)), chunk=(256, 256),
                  n_levels=1).close()
    cfg = AppConfig(data_dir=str(tmp_path))
    cfg.raw_cache.enabled = True
    cfg.raw_cache.prefetch = False
    base = f"/webgateway/render_image_region/{IMG}/0/0?m=c&format=jpeg"
    plain = base + "&tile=0,0,0,256,256&c=1|0:30000$FF0000,2|0:%d$00FF00"
    flipped = plain + "&flip=h"

    async def main():
        client = TestClient(TestServer(create_app(cfg)))
        await client.start_server()
        try:
            for url in (plain % 40000, plain % 41000, flipped % 42000):
                resp = await client.get(url)
                assert resp.status == 200, await resp.text()
                await resp.read()
            return await (await client.get("/metrics")).text()
        finally:
            await client.close()

    text = asyncio.run(main())
    assert 'imageregion_batcher_group_stacks_total{path="planes"} 2' \
        in text
    assert 'imageregion_batcher_group_stacks_total{path="arrays"} 1' \
        in text
    assert "# TYPE imageregion_batcher_group_stacks_total counter" in text
    assert text.count("# HELP imageregion_batcher_group_stacks_total") == 1


def test_after_prewarm_no_warmed_batch_shape_compiles_its_stack():
    from omero_ms_image_region_tpu.server.prewarm import (
        prewarm_batch_sizes, prewarm_renderer)
    telemetry.install_compile_listener()
    renderer = BatchingRenderer(max_batch=2, buckets=((32, 32),))
    prewarm_renderer(["3x32"], "sparse", max_batch=2,
                     buckets=renderer.buckets)
    plane = jax.device_put(np.ones((32, 32), np.uint16))
    sizes = prewarm_batch_sizes(renderer.group_cap(32 * 32))
    assert sizes == (1, 2, 3, 4, 6, 8, 16, 32, 64)
    events = telemetry.COMPILE.events
    for B in sizes:
        group = [_Pending(raw=(plane,) * 3, settings={}, h=32, w=32,
                          bucket_px=32 * 32) for _ in range(B)]
        raw, _ = renderer._stage_group(group)
        assert raw.shape == (B, 3, 32, 32)
    # And the fallback of a request that is stacked by itself.
    render_ops.stack_channel_planes(*[plane] * 3)
    assert telemetry.COMPILE.events == events
    assert renderer.group_stacks == {"planes": len(sizes), "arrays": 0}
