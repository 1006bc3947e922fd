"""Plane sizes off the bucket ladder (PR 34): a site states its plane
sizes in ``renderer.prewarm``, each stated size gets a bucket of its
own (its MCU grid), resident planes of a stated size ride to their
group as they are and the group's ONE program stacks and
edge-replicates them to the bucket.  A size nobody stated falls to the
fixed ladder and adds no bucket.  Served fields of the deployment
``jump5-u16-p1080`` (5 x uint16, 1080^2) are held to the benchmark's
plain reference under the configuration's own limits.  Seeded data,
CPU backend: counts and bytes, never a speed."""

import asyncio
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from omero_ms_image_region_tpu.io.devicecache import DeviceRawCache
from omero_ms_image_region_tpu.io.service import PixelsService
from omero_ms_image_region_tpu.io.store import build_pyramid
from omero_ms_image_region_tpu.ops import render as render_ops
from omero_ms_image_region_tpu.ops.jpegenc import pad_planes_to_mcu
from omero_ms_image_region_tpu.ops.lut import LutProvider
from omero_ms_image_region_tpu.server.batcher import (
    DEFAULT_BUCKETS, BatchingRenderer, _Pending, bucket_lattice,
    mcu_grid, pick_bucket,
)
from omero_ms_image_region_tpu.server.ctx import ImageRegionCtx
from omero_ms_image_region_tpu.server.handler import (
    ImageRegionHandler, ImageRegionServices,
)
from omero_ms_image_region_tpu.server.prewarm import (
    parse_spec, prewarm_batch_sizes, prewarm_renderer, stated_planes,
)
from omero_ms_image_region_tpu.services.cache import CacheConfig, Caches
from omero_ms_image_region_tpu.services.metadata import (
    CanReadMemo, LocalMetadataService,
)
from omero_ms_image_region_tpu.utils import profile_summary as ps
from omero_ms_image_region_tpu.utils import telemetry
from omero_ms_image_region_tpu.utils.stopwatch import REGISTRY

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _json(*parts) -> dict:
    with open(os.path.join(REPO, *parts)) as f:
        return json.load(f)


CONFIG = _json("benchmark", "configs", "jump5-u16-p1080.json")
C = CONFIG["channels"]


def run(coro):
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    finally:
        loop.close()


# ------------------------------------------------------------ the lattice

def test_a_stated_plane_gets_the_bucket_of_its_mcu_grid():
    assert mcu_grid(1080, 1080) == (1088, 1088)
    assert mcu_grid(1024, 600) == (1024, 608)
    assert stated_planes(["5x1080@90"]) == ((1080, 1080),)
    lattice = bucket_lattice(DEFAULT_BUCKETS, stated_planes(["5x1080"]))
    assert lattice == ((256, 256), (512, 512), (1024, 1024),
                       (1088, 1088), (2048, 2048))
    assert pick_bucket(1088, 1088, lattice) == (1088, 1088)
    # The parent's ladder renders the same field as 2048^2: 3.54 x.
    assert pick_bucket(1088, 1088) == (2048, 2048)
    assert 2048 * 2048 / (1088 * 1088) == pytest.approx(3.54, abs=0.01)
    assert 1088 * 1088 / (1080 * 1080) < 1.05
    # A shape on the ladder, stated or not, changes nothing; nothing
    # stated leaves the ladder as it was given.
    assert bucket_lattice(DEFAULT_BUCKETS,
                          stated_planes(["4x1024", "4x256", "3x2048"])
                          ) == DEFAULT_BUCKETS
    assert bucket_lattice(((64, 64),)) == ((64, 64),)
    # Several stated sizes: smallest first, each once.
    assert bucket_lattice(DEFAULT_BUCKETS, stated_planes(
        ["5x1080", "3x1080@80", "2x2160", "4x600"])) == (
        (256, 256), (512, 512), (608, 608), (1024, 1024), (1088, 1088),
        (2048, 2048), (2160, 2160))


def test_an_unstated_region_falls_to_the_ladder_and_adds_no_bucket():
    """However many distinct sizes a client sends, the lattice is what
    the site wrote down: ``render_jpeg`` only reads it."""
    renderer = BatchingRenderer(max_batch=2, linger_ms=1.0,
                                planes=stated_planes(["5x1080@90"]))
    before = renderer.buckets
    rng = np.random.default_rng(34)
    sizes = {(int(h), int(w)) for h, w in rng.integers(17, 1100, (40, 2))}
    sizes.add((700, 1000))                  # region=...,1000,700
    for h, w in sizes:
        assert pick_bucket(*mcu_grid(h, w), renderer.buckets) in before
        # Planes of a size nobody stated ride only where they fill a
        # bucket as they are.
        assert renderer.takes_planes(h, w, jpeg=True) == (
            (h, w) in before)
    assert pick_bucket(*mcu_grid(700, 1000), renderer.buckets) == (
        1024, 1024)
    assert renderer.buckets == before
    assert renderer.planes == {(1080, 1080)}

    # And through the door itself: a 70 x 100 region under a renderer
    # whose site states 120^2 planes is padded, as a request of its
    # own, to the smallest bucket there is (the stated size's 128^2);
    # it adds none.
    small = BatchingRenderer(max_batch=2, linger_ms=1.0,
                             buckets=((256, 256),),
                             planes=((120, 120),))
    assert small.buckets == ((128, 128), (256, 256))

    async def main():
        try:
            raw = jnp.asarray(rng.integers(0, 60000, (2, 70, 100)
                                           ).astype(np.uint16))
            return await small.render_jpeg(raw, _settings(2), 90, 100,
                                           70)
        finally:
            await small.close()

    body = run(main())
    assert body[:2] == b"\xff\xd8"
    assert small.buckets == ((128, 128), (256, 256))
    assert small.group_stacks == {"planes": 0, "arrays": 1}
    assert small.bucket_px == {"image": 7000, "pad": 128 * 128 - 7000}


def test_takes_planes_for_a_stated_field_not_for_a_flip_or_an_edge_tile(
        plate):
    renderer = BatchingRenderer(planes=stated_planes(["5x1080@90"]))
    assert renderer.takes_planes(1080, 1080, jpeg=True)
    assert renderer.takes_planes(1088, 1088, jpeg=True)   # a bucket
    assert renderer.takes_planes(1024, 1024, jpeg=True)
    # A WSI edge tile, an unstated field whose grid happens to be the
    # stated bucket, a packed (PNG) render of the field: stacked and
    # padded by themselves.
    assert not renderer.takes_planes(1024, 600, jpeg=True)
    assert not renderer.takes_planes(1085, 1082, jpeg=True)
    assert not renderer.takes_planes(1080, 1080, jpeg=False)
    # What the parent's renderer said of the same field.
    assert not BatchingRenderer().takes_planes(1080, 1080, jpeg=True)
    # A flipped request never asks: the handler stacks it.
    data_dir, _ = plate[120, 120]
    renderer = _renderer(120, 120)
    bodies, calls = _serve(data_dir, renderer, [
        [dict(image=1, window=30000, flip="h")]])
    assert renderer.group_stacks == {"planes": 0, "arrays": 1}
    assert bodies[0][0][:2] == b"\xff\xd8"


PARENT_SPECS = {
    "4x1024": (4, 1024, 85, "uint16"), "3x512@90": (3, 512, 90, "uint16"),
    "2x1024:uint8": (2, 1024, 85, "uint8"),
    "4x256": (4, 256, 85, "uint16"), "3x2048@90": (3, 2048, 90, "uint16"),
    "5x1024@90": (5, 1024, 90, "uint16"),
    "6x1024@90": (6, 1024, 90, "uint16"),
    "2x256@70:float32": (2, 256, 70, "float32"),
    "4x64@90": (4, 64, 90, "uint16"), "3x128@90": (3, 128, 90, "uint16"),
    "1x16": (1, 16, 85, "uint16"), "64x8192@100": (64, 8192, 100, "uint16"),
}


@pytest.mark.parametrize("spec", list(PARENT_SPECS))
def test_every_spec_the_parent_took_parses_to_what_it_did(spec):
    c, edge, q, dt = PARENT_SPECS[spec]
    assert parse_spec(spec) == (c, edge, q, np.dtype(dt))
    # And means what it meant: an edge on the ladder adds no bucket, a
    # 16-aligned one is its own grid.
    lattice = bucket_lattice(DEFAULT_BUCKETS, stated_planes([spec]))
    if (edge, edge) in DEFAULT_BUCKETS:
        assert lattice == DEFAULT_BUCKETS
    else:
        assert set(lattice) == set(DEFAULT_BUCKETS) | {(edge, edge)}


# ----------------------------------------------- the group's one program

def _members(n, chans, h, w, seed):
    rng = np.random.default_rng(seed)
    return [tuple(jax.device_put(rng.integers(
        0, 65536, size=(h, w)).astype(np.uint16)) for _ in range(chans))
        for _ in range(n)]


@pytest.mark.parametrize("B", [1, 2, 8])
@pytest.mark.parametrize("h, w, bucket", [
    (120, 120, (128, 128)), (136, 200, (144, 208))])
def test_the_group_program_with_a_pad_is_the_padded_stack(B, h, w,
                                                          bucket):
    members = _members(B, 5, h, w, seed=B * h + w)
    got = render_ops.stack_group_planes(tuple(members), pad=bucket)
    want = jnp.stack([pad_planes_to_mcu(jnp.stack(m), *bucket)
                      for m in members])
    assert got.shape == (B, 5) + bucket and got.dtype == jnp.uint16
    assert np.array_equal(np.asarray(got), np.asarray(want))
    # The corner is the plane's last sample, the rim its last row and
    # column: edge replication, not zeros.
    host = np.asarray(got)
    assert np.array_equal(host[:, :, h:, :w],
                          np.repeat(host[:, :, h - 1:h, :w],
                                    bucket[0] - h, axis=2))
    assert (host[:, :, -1, -1] == host[:, :, h - 1, w - 1]).all()
    # A plane that fills its bucket: the program PR 33 wrote.
    assert np.array_equal(
        np.asarray(render_ops.stack_group_planes(tuple(members),
                                                 pad=(h, w))),
        np.asarray(render_ops.stack_group_planes(tuple(members))))


def test_the_batcher_hands_the_pad_to_the_group_program():
    """Through ``_group_arrays``: padded slots repeat the last member;
    a group of mixed plane shapes takes the older path and gives the
    same array."""
    renderer = BatchingRenderer(max_batch=8, buckets=((256, 256),),
                                planes=((120, 120), (128, 128)))
    assert renderer.buckets == ((128, 128), (256, 256))
    members = _members(5, 3, 120, 120, seed=5)

    def group_of(raws):
        return [_Pending(raw=raw, settings={}, h=120, w=120,
                         bucket_px=128 * 128,
                         pad_to=(None if raw[0].shape == (128, 128)
                                 else (128, 128))) for raw in raws]

    raw, _ = renderer._group_arrays(group_of(members))
    stacks = [pad_planes_to_mcu(jnp.stack(m), 128, 128) for m in members]
    want = np.asarray(jnp.stack(stacks + stacks[-1:]))
    assert raw.shape == (6, 3, 128, 128)
    assert np.array_equal(np.asarray(raw), want)
    assert renderer.group_stacks == {"planes": 1, "arrays": 0}
    full = tuple(jnp.asarray(p) for p in np.asarray(stacks[0]))
    mixed, _ = renderer._group_arrays(group_of([full] + members[1:]))
    assert np.array_equal(np.asarray(mixed), want)
    assert renderer.group_stacks == {"planes": 1, "arrays": 1}
    assert renderer.bucket_px == {
        "image": 10 * 120 * 120, "pad": 10 * (128 * 128 - 120 * 120)}


def test_after_prewarm_no_warmed_batch_shape_compiles_its_padded_stack():
    telemetry.install_compile_listener()
    renderer = BatchingRenderer(max_batch=2, buckets=((64, 64),),
                                planes=stated_planes(["3x24"]))
    assert renderer.buckets == ((32, 32), (64, 64))
    prewarm_renderer(["3x24"], "sparse", max_batch=2,
                     buckets=renderer.buckets)
    plane = jax.device_put(np.ones((24, 24), np.uint16))
    sizes = prewarm_batch_sizes(renderer.group_cap(32 * 32))
    assert sizes == (1, 2, 3, 4, 6, 8, 16, 32, 64)
    events = telemetry.COMPILE.events
    for B in sizes:
        group = [_Pending(raw=(plane,) * 3, settings={}, h=24, w=24,
                          bucket_px=32 * 32, pad_to=(32, 32))
                 for _ in range(B)]
        raw, _ = renderer._stage_group(group)
        assert raw.shape == (B, 3, 32, 32)
    assert telemetry.COMPILE.events == events
    assert renderer.group_stacks == {"planes": len(sizes), "arrays": 0}


# ---------------------------------------- served fields against the reference

def _settings(chans):
    from omero_ms_image_region_tpu.flagship import flagship_settings
    return flagship_settings(chans)[1]


@pytest.fixture(scope="module")
def plate(tmp_path_factory):
    """Two seeded 5 x uint16 fields of each size, written through the
    program's ingest in chunks that leave a ragged rim, as 1080 rows in
    1024-pixel chunks do; the content generator is the benchmark's."""
    from benchmark import datagen
    out = {}
    for h, w, chunk in ((120, 120, 64), (136, 200, 64),
                        (1080, 1080, 1024)):
        root = tmp_path_factory.mktemp(f"plate{h}x{w}")
        rng = np.random.default_rng(34 + h)
        tiles = datagen.synthetic_tiles(rng, 2, C, max(h, w), max(h, w))
        images = {i + 1: np.ascontiguousarray(tiles[i][:, :h, :w])
                  for i in range(2)}
        for i, planes in images.items():
            build_pyramid(planes[:, None], str(root / str(i)),
                          chunk=(chunk, chunk), n_levels=1).close()
        out[h, w] = str(root), images
    return out


def _ctx(image, window, fmt="jpeg", **where) -> ImageRegionCtx:
    c = ",".join(f"{i + 1}|{100 * i}:{window + 500 * i}"
                 f"${CONFIG['colors'][i]}" for i in range(C))
    return ImageRegionCtx.from_params({
        "imageId": str(image), "theZ": "0", "theT": "0", "c": c,
        "m": "c", "format": fmt, "q": str(CONFIG["quality"]), **where})


def _windows(window):
    return [[100 * i, window + 500 * i] for i in range(C)]


def _renderer(h, w) -> BatchingRenderer:
    return BatchingRenderer(max_batch=8, linger_ms=20.0,
                            planes=((h, w),))


def _serve(data_dir, renderer, rounds):
    calls = {"group_pads": []}
    real = render_ops.stack_group_planes

    def counting(members, pad=None):
        calls["group_pads"].append(pad)
        return real(members, pad=pad)

    async def main():
        services = ImageRegionServices(
            pixels_service=PixelsService(data_dir),
            metadata=LocalMetadataService(data_dir),
            caches=Caches.from_config(CacheConfig()),
            can_read_memo=CanReadMemo(), renderer=renderer,
            lut_provider=LutProvider(), raw_cache=DeviceRawCache(),
            cpu_fallback_max_px=0)
        handler = ImageRegionHandler(services)
        render_ops.stack_group_planes = counting
        try:
            return [await asyncio.gather(*(
                handler.render_image_region(_ctx(**kw)) for kw in r))
                for r in rounds]
        finally:
            render_ops.stack_group_planes = real
            await renderer.close()
    return run(main()), calls


@pytest.mark.parametrize("h, w", [(120, 120), (136, 200), (1080, 1080)])
def test_served_fields_hold_to_the_reference_on_a_miss_and_on_a_hit(
        plate, h, w):
    """HTTP's handler -> raw cache -> batcher -> the group's program ->
    the served program -> the entropy tail, at the field's own size,
    against ``benchmark/references/render_jpeg.py`` under the
    configuration's limits; missed (read, uploaded, handed on by the
    reading thread) and again resident under a fresh window."""
    from benchmark.references import render_jpeg
    data_dir, images = plate[h, w]
    renderer = _renderer(h, w)
    bucket = mcu_grid(h, w)
    assert bucket in renderer.buckets
    miss = [dict(image=1, window=30000), dict(image=2, window=41000)]
    hit = [dict(image=1, window=33000), dict(image=2, window=25000)]
    (cold, warm), calls = _serve(data_dir, renderer, [miss, hit])
    limits = CONFIG["limits"]
    config = dict(CONFIG, tile_edge=h)
    for bodies, requests in ((cold, miss), (warm, hit)):
        for body, kw in zip(bodies, requests):
            req = {"item": (kw["image"], None, None),
                   "windows": _windows(kw["window"])}
            numbers = render_jpeg.compare_request(body, images, req,
                                                  config)
            assert "error" not in numbers, numbers
            for key, limit in limits.items():
                assert numbers[key] <= limit, (key, numbers)
            # The client's JPEG is the field's own size: the pad never
            # reaches it.
            rgb, _ = render_jpeg.decode(body)
            assert rgb.shape == (h, w, 3)
    # Every group went down the one program, which padded it.
    assert renderer.group_stacks["arrays"] == 0
    assert renderer.group_stacks["planes"] >= 2
    assert set(calls["group_pads"]) == {bucket}
    assert renderer.bucket_px["image"] == 4 * h * w
    assert renderer.bucket_px["pad"] == 4 * (bucket[0] * bucket[1]
                                             - h * w)
    # The renderer that stacks and pads a request by itself gives the
    # same bytes: one algorithm, two ways in.
    older = BatchingRenderer(max_batch=8, linger_ms=20.0,
                             buckets=renderer.buckets)
    (again,), _ = _serve(data_dir, older, [hit])
    assert older.group_stacks["planes"] == 0
    assert older.group_stacks["arrays"] >= 1
    assert again == warm


def test_the_raw_cache_keeps_planes_as_the_store_holds_them(plate):
    data_dir, images = plate[120, 120]
    renderer = _renderer(120, 120)
    cache = DeviceRawCache()

    async def main():
        services = ImageRegionServices(
            pixels_service=PixelsService(data_dir),
            metadata=LocalMetadataService(data_dir),
            caches=Caches.from_config(CacheConfig()),
            can_read_memo=CanReadMemo(), renderer=renderer,
            lut_provider=LutProvider(), raw_cache=cache,
            cpu_fallback_max_px=0)
        try:
            await ImageRegionHandler(services).render_image_region(
                _ctx(image=1, window=30000))
        finally:
            await renderer.close()
    run(main())
    assert len(cache) == C
    assert cache.size_bytes == C * 120 * 120 * 2


# ------------------------------------------------- counter, scope, span

def test_bucket_px_is_counted_on_metrics_and_the_group_span_names_it(
        plate):
    from omero_ms_image_region_tpu.server.config import AppConfig
    from omero_ms_image_region_tpu.server.app import create_app
    from aiohttp.test_utils import TestClient, TestServer
    data_dir, _ = plate[120, 120]
    cfg = AppConfig(data_dir=data_dir)
    cfg.raw_cache.enabled = True
    cfg.raw_cache.prefetch = False
    cfg.renderer.prewarm = ()
    cfg.renderer.cpu_fallback_max_px = 0
    seen = []
    record = REGISTRY.record

    def recording(name, ms, **meta):
        if name == "batcher.group":
            seen.append(meta)
        return record(name, ms, **meta)

    async def main():
        app = create_app(cfg)
        client = TestClient(TestServer(app))
        await client.start_server()
        REGISTRY.record = recording
        try:
            for window in (30000, 31000):     # no bytes cache hit
                resp = await client.get(
                    "/webgateway/render_image/1/0/0?m=c&format=jpeg"
                    "&q=0.9&c=" + ",".join(
                        f"{i + 1}|0:{window + 100 * i}$FF0000"
                        for i in range(C)))
                assert resp.status == 200, await resp.text()
                await resp.read()
            # A request is settled when its tile is coded; its group's
            # span closes on the worker thread a moment later.
            for _ in range(200):
                if len(seen) == 2:
                    break
                await asyncio.sleep(0.01)
            return await (await client.get("/metrics")).text()
        finally:
            REGISTRY.record = record
            await client.close()

    text = asyncio.run(main())
    # Nothing stated: a 120^2 field rides the ladder's 256^2 bucket,
    # and the counter says what that costs.
    image, pad = 2 * 120 * 120, 2 * (256 * 256 - 120 * 120)
    assert f'imageregion_batcher_bucket_px_total{{part="image"}} {image}' \
        in text
    assert f'imageregion_batcher_bucket_px_total{{part="pad"}} {pad}' \
        in text
    assert "# TYPE imageregion_batcher_bucket_px_total counter" in text
    assert text.count("# HELP imageregion_batcher_bucket_px_total") == 1
    assert [m["bucket"] for m in seen] == ["256x256", "256x256"]
    assert all(m["key"].startswith("jpeg:5x256x256") for m in seen)


def test_the_benchmarks_metric_reads_the_counter():
    spec = _json("benchmark", "layer_metrics", "bucket_fill_share.json")
    from benchmark.readers import labelled_ratio
    fam = "imageregion_batcher_bucket_px_total"
    m0 = {f'{fam}{{part="image"}}': 1000.0, f'{fam}{{part="pad"}}': 50.0}
    m1 = {f'{fam}{{part="image"}}': 1000.0 + 1080 * 1080 * 7,
          f'{fam}{{part="pad"}}': 50.0 + (1088 * 1088 - 1080 * 1080) * 7}
    got = labelled_ratio.read({"m0": m0, "m1": m1}, **spec["args"])
    assert got == pytest.approx(100 * 1080 ** 2 / 1088 ** 2)
    assert got > 98.5
    # The parent exports no such family: left out, never 0.
    assert labelled_ratio.read({"m0": {}, "m1": {"x": 1.0}},
                               **spec["args"]) is None


def test_pad_mcu_is_a_stage_of_its_own_on_hand_made_rows():
    assert "stage.pad_mcu" in ps.STAGES
    assert ps.stage_of("jit(stack_group_planes)/stage.pad_mcu/pad") == \
        "stage.pad_mcu"
    assert ps.stage_of(
        "jit(stack_group_planes)/stage.channel_stack/concatenate") == \
        "stage.channel_stack"
    assert ps.stage_of("jit(stack_group_planes)/pad") == ps.UNNAMED
    spec = _json("benchmark", "layer_metrics", "stack_pad_device_ms.json")
    from benchmark.readers import labelled_ratio
    fam = "imageregion_profile_device_ms_total"
    m0 = {}
    m1 = {f'{fam}{{stage="stage.channel_stack"}}': 30.0,
          f'{fam}{{stage="stage.pad_mcu"}}': 12.0,
          f'{fam}{{stage="render"}}': 900.0,
          "imageregion_profile_renders_total": 300.0}
    assert labelled_ratio.read({"m0": m0, "m1": m1},
                               **spec["args"]) == pytest.approx(0.14)
    # A server without the pad's scope (the parent): the stack alone.
    del m1[f'{fam}{{stage="stage.pad_mcu"}}']
    assert labelled_ratio.read({"m0": m0, "m1": m1},
                               **spec["args"]) == pytest.approx(0.10)
    # No capture: nothing.
    assert labelled_ratio.read({"m0": m1, "m1": m1},
                               **spec["args"]) is None
