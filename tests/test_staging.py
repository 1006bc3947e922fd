"""Host->device staging of a raw plane that missed the HBM raw cache.

The plane goes up as it is: the same samples land in HBM bit for bit,
in the storage dtype, through one asynchronous ``device_put`` (or one a
band for a tall region), and no program is compiled or run for the
upload.  The bands' bounds are arithmetic and are checked as such.
"""

import numpy as np
import pytest

import jax

from omero_ms_image_region_tpu.io import staging
from omero_ms_image_region_tpu.io.devicecache import DeviceRawCache
from omero_ms_image_region_tpu.io.memory import InMemoryPixelSource
from omero_ms_image_region_tpu.server import handler as handler_mod
from omero_ms_image_region_tpu.server.ctx import ImageRegionCtx
from omero_ms_image_region_tpu.server.handler import (
    ImageRegionHandler, ImageRegionServices, Renderer,
)
from omero_ms_image_region_tpu.server.region import RegionDef
from omero_ms_image_region_tpu.utils import telemetry

BAND = handler_mod._STAGE_BAND_ROWS


# ------------------------------------------------------------ the bounds

@pytest.mark.parametrize("height,y,tile_h,expected", [
    # The plate cell's plane at store_chunk 1024: two bands where the
    # nudged bounds gave [0, 1024, 1025, 2047, 2048].
    (2048, 0, 1024, [0, 1024, 2048]),
    # The slide cell's tiles, first row and a later one: one band
    # where the nudged bounds gave [0, 1, 1023, 1024].
    (1024, 0, 1024, [0, 1024]),
    (1024, 3072, 1024, [0, 1024]),
    # Small store tiles: the four even bands stand.
    (2048, 0, 64, [0, 512, 1024, 1536, 2048]),
    (8192, 0, 1024, [0, 2048, 4096, 6144, 8192]),
    # An off-grid y: interior bounds sit on the store's rows, not the
    # region's.
    (2048, 100, 512, [0, 412, 924, 1436, 2048]),
    (2048, 37, 256, [0, 475, 987, 1499, 2048]),
    # Shorter than two bands: never banded.
    (2 * BAND - 1, 0, 64, [0, 2 * BAND - 1]),
    (BAND, 640, 64, [0, BAND]),
    # A last partial band, and one too short to stand alone.
    (1300, 0, 512, [0, 512, 1024, 1300]),
    (1100, 0, 512, [0, 512, 1100]),
    # One store tile row holds the whole region.
    (2048, 0, 4096, [0, 2048]),
    (600, 1, 1, [0, 300, 600]),
])
def test_band_bounds(height, y, tile_h, expected):
    bounds = handler_mod._stage_band_bounds(height, y, tile_h)
    assert bounds == expected
    assert bounds[0] == 0 and bounds[-1] == height
    assert all(a < b for a, b in zip(bounds, bounds[1:]))
    assert all((y + b) % tile_h == 0 for b in bounds[1:-1])
    assert len(bounds) <= 5
    if len(bounds) > 2:
        assert min(b - a for a, b in zip(bounds, bounds[1:])) >= BAND


def test_band_bounds_hold_over_a_sweep():
    """The four properties over every small combination, not only the
    listed ones."""
    for height in range(1, 2400, 37):
        for y in (0, 1, 255, 256, 1000):
            for tile_h in (1, 16, 256, 300, 1024):
                bounds = handler_mod._stage_band_bounds(height, y, tile_h)
                bands = [b - a for a, b in zip(bounds, bounds[1:])]
                assert bounds[0] == 0 and bounds[-1] == height
                assert all(n > 0 for n in bands)
                assert all((y + b) % tile_h == 0 for b in bounds[1:-1])
                assert len(bands) == 1 or min(bands) >= BAND


# --------------------------------------------------------- the exactness

def _planes(dtype, shape, seed):
    rng = np.random.default_rng(seed)
    if np.issubdtype(dtype, np.integer):
        return rng.integers(0, np.iinfo(dtype).max, size=shape,
                            endpoint=True).astype(dtype)
    return rng.standard_normal(shape).astype(dtype)


def _handler(raw_cache):
    return ImageRegionHandler(ImageRegionServices(
        pixels_service=None, metadata=None, caches=None,
        can_read_memo=None, renderer=Renderer(), raw_cache=raw_cache))


_CTX = {"imageId": "1", "theZ": "0", "theT": "0", "m": "c",
        "c": "1|0:60000$FF0000,2|0:60000$00FF00"}


@pytest.mark.parametrize("dtype", [np.uint8, np.uint16, np.float32])
def test_a_miss_through_the_cache_is_the_source_bit_for_bit(dtype):
    cache = DeviceRawCache()
    arr = _planes(dtype, (3, 200, 328), seed=1)
    got = cache.get_or_load(("k", np.dtype(dtype).name), lambda: arr)
    assert isinstance(got, jax.Array) and got.dtype == arr.dtype
    assert np.array_equal(np.asarray(got), arr)
    assert (cache.hits, cache.misses) == (0, 1)
    assert cache.get_or_load(("k", np.dtype(dtype).name), None) is got


@pytest.mark.parametrize("height,bands", [(1000, 3), (300, 1)],
                         ids=["banded", "single-shot"])
@pytest.mark.parametrize("dtype", [np.uint8, np.uint16, np.float32])
def test_a_miss_through_load_staged_is_the_source_bit_for_bit(
        dtype, height, bands):
    planes = _planes(dtype, (2, 1, 1100, 768), seed=2)
    src = InMemoryPixelSource(planes, tile=(128, 128))
    region = RegionDef(32, 16, 700, height)
    assert len(handler_mod._stage_band_bounds(height, 16, 128)) - 1 == bands
    reads = []
    get_region = src.get_region
    src.get_region = lambda z, c, t, r, level=0: (
        reads.append(r.height) or get_region(z, c, t, r, level))
    handler = _handler(DeviceRawCache())
    staged = handler._read_region(
        src, ImageRegionCtx.from_params(_CTX), region, 0, [0, 1])
    assert isinstance(staged, jax.Array) and staged.dtype == planes.dtype
    assert np.array_equal(np.asarray(staged),
                          planes[:, 0, 16:16 + height, 32:732])
    # Each row is read once: a band a channel, and the bands tile the
    # region.
    assert len(reads) == 2 * bands and sum(reads) == 2 * height


def test_without_a_raw_cache_the_read_stays_on_the_host():
    planes = _planes(np.uint16, (2, 1, 1100, 768), seed=3)
    got = _handler(None)._read_region(
        InMemoryPixelSource(planes), ImageRegionCtx.from_params(_CTX),
        RegionDef(0, 0, 768, 1100), 0, [0, 1])
    assert isinstance(got, np.ndarray)
    assert np.array_equal(got, planes[:, 0])


# ------------------------------------------------ no program for an upload

def test_a_cold_miss_compiles_and_dispatches_no_program():
    """A shape this process has never seen goes up through
    ``get_or_load`` and ``imageregion_compile_events_total`` does not
    move; through the handler's single-shot read it moves by one, the
    stack of the region's channel planes (no program an upload: a
    second region of the shape compiles nothing); a jitted call of the
    same new shape afterwards moves it again, so the listener was
    listening."""
    assert telemetry.install_compile_listener()
    shape = (3, 320, 1280)
    # Smooth content on the lattice of tile-snapped bands (rows % 64,
    # width % 256, over 1 MiB): what a transform of the upload would
    # choose to act on.
    arr = (np.arange(np.prod(shape)) % 4093).reshape(shape).astype(
        np.uint16)
    cache = DeviceRawCache()
    before = telemetry.COMPILE.events
    got = cache.get_or_load("cold", lambda: arr)
    jax.block_until_ready(got)
    planes = arr[:, None]
    staged = _handler(cache)._read_region(
        InMemoryPixelSource(planes), ImageRegionCtx.from_params(_CTX),
        RegionDef(0, 0, 1280, 320), 0, [0, 1, 2])
    jax.block_until_ready(staged)
    assert telemetry.COMPILE.events == before + 1
    assert np.array_equal(np.asarray(staged), arr)
    again = _handler(cache)._read_region(
        InMemoryPixelSource(planes + 1), ImageRegionCtx.from_params(_CTX),
        RegionDef(0, 0, 1280, 320), 0, [0, 1, 2])
    assert telemetry.COMPILE.events == before + 1
    assert np.array_equal(np.asarray(again), arr)   # resident: no read
    jax.block_until_ready(jax.jit(lambda a: a + 1)(got))
    assert telemetry.COMPILE.events == before + 2


def test_staging_defines_no_device_program():
    """Nothing in ``io/staging`` can be lowered: the module holds the
    device pin and the digest skip, and no jitted function."""
    jitted = [name for name, obj in vars(staging).items()
              if hasattr(obj, "lower") and callable(obj)]
    assert jitted == []


def test_prewarm_compiles_nothing_from_staging(caplog):
    """Prewarm's program list, as JAX logs it: a uint16 spec whose
    stacked group is on the lattice the packed stager used to warm
    (rows % 64, width % 256, >= 1 MiB at batch 2) compiles the JPEG
    program and no upload program.  (Edge and quality are ones no
    other test uses: a program this process already holds would not
    be logged.)"""
    import logging
    import re

    from omero_ms_image_region_tpu.server.prewarm import prewarm_renderer

    with jax.log_compiles(), caplog.at_level(logging.WARNING):
        prewarm_renderer(["1x768@37"], "sparse", max_batch=2,
                         buckets=((768, 768),))
    compiled = set(re.findall(r"Compiling (?:jit\()?(\w+)", caplog.text))
    assert "render_to_jpeg_sparse_compact" in compiled
    assert not [name for name in compiled if "unpack" in name
                or hasattr(staging, name)]
