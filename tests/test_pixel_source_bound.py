"""The bound on ``PixelsService``'s open sources: derived from the
process's descriptor and mapping limits, with a reserve, a per-source
cost and a floor; what one open source really holds; and a plate
larger than the old fixed bound of 128, walked cyclically, opening
each image once."""

import logging
import os
import resource
import weakref

import numpy as np
import pytest

from omero_ms_image_region_tpu.io import PixelsService, build_pyramid
from omero_ms_image_region_tpu.io import service
from omero_ms_image_region_tpu.io.ometiff import OmeTiffSource
from omero_ms_image_region_tpu.io.store import ChunkedPyramidStore
from omero_ms_image_region_tpu.io.tiffwrite import write_ome_tiff
from omero_ms_image_region_tpu.server.region import RegionDef

# ------------------------------------------------------- the derivation

@pytest.mark.parametrize("fds, maps, bound", [
    # A host with a raised descriptor limit: the mappings bind.
    (1 << 20, 65530, (65530 - service.MAP_RESERVE) // service.SOURCE_MAPS),
    # A low descriptor limit binds before the kernel's default mappings.
    (20000, 65530, (20000 - service.FD_RESERVE) // service.SOURCE_FDS),
    # No /proc: the descriptors alone.
    (65536, None, (65536 - service.FD_RESERVE) // service.SOURCE_FDS),
    # Limits that leave less than the floor, or nothing past the
    # reserve, still keep the floor.
    (1024, 65530, service.MAX_OPEN_FLOOR),
    (4096, 65530, service.MAX_OPEN_FLOOR),
    (1 << 20, 8192, service.MAX_OPEN_FLOOR),
    # Neither limit observable: the floor.
    (None, None, service.MAX_OPEN_FLOOR),
])
def test_the_bound_is_each_limit_less_its_reserve_over_a_sources_cost(
        fds, maps, bound):
    assert service.open_source_bound(fds, maps) == bound
    assert bound >= service.MAX_OPEN_FLOOR == 128


def test_the_numbers_behind_the_bound():
    """The reserve and the per-source cost as the docstrings state them;
    on the usual kernel default of 65,530 mappings and a raised
    descriptor limit, a server keeps 6,143 sources open."""
    assert (service.FD_RESERVE, service.MAP_RESERVE) == (4096, 16384)
    assert (service.SOURCE_FDS, service.SOURCE_MAPS) == (8, 8)
    assert service.open_source_bound(1 << 20, 65530) == 6143


def test_max_map_count_is_read_from_proc_and_missing_is_none(tmp_path):
    f = tmp_path / "max_map_count"
    f.write_text("65530\n")
    assert service.read_max_map_count(str(f)) == 65530
    assert service.read_max_map_count(str(tmp_path / "absent")) is None
    f.write_text("not a number\n")
    assert service.read_max_map_count(str(f)) is None


def _fake_limits(monkeypatch, calls):
    """A process with soft 1,024 and hard 2**20 descriptors and the
    kernel's default 65,530 mappings; ``setrlimit`` calls are recorded
    in ``calls``, not made."""
    monkeypatch.setattr(resource, "getrlimit", lambda which: (1024, 1 << 20))
    monkeypatch.setattr(resource, "setrlimit",
                        lambda which, lim: calls.append(lim))
    monkeypatch.setattr(service, "read_max_map_count", lambda: 65530)


def test_a_service_left_to_itself_changes_no_limit(monkeypatch):
    """``PixelsService`` without ``max_open`` keeps the floor and leaves
    the process's limits alone; a given ``max_open`` is kept as it is."""
    calls = []
    _fake_limits(monkeypatch, calls)
    assert PixelsService("/none").max_open == service.MAX_OPEN_FLOOR
    assert PixelsService("/none", max_open=2).max_open == 2
    assert calls == []


def test_the_server_derives_the_bound_where_it_builds_the_service(
        monkeypatch, tmp_path):
    """Both server call sites raise the descriptor limit once and build
    their service with the bound that follows: the device-owning stack
    (``build_services``) and the frontend's degraded CPU handler."""
    from omero_ms_image_region_tpu.server.app import build_services
    from omero_ms_image_region_tpu.server.config import AppConfig
    from omero_ms_image_region_tpu.server.degraded import (
        DegradedCpuHandler)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    calls = []
    _fake_limits(monkeypatch, calls)
    config = AppConfig(data_dir=str(tmp_path))
    services = build_services(config)
    try:
        assert services.pixels_service.max_open == 6143
    finally:
        services.pixels_service.close()
    assert calls == [(1 << 20, 1 << 20)]
    assert DegradedCpuHandler(config).pixels_service.max_open == 6143
    assert calls == [(1 << 20, 1 << 20)] * 2


# ---------------------------------------------------- the soft-limit raise

@pytest.mark.parametrize("soft, hard, asked, result", [
    (1024, 524288, (524288, 524288), 524288),   # raised to the hard limit
    (524288, 524288, None, 524288),              # already there: no call
])
def test_the_soft_limit_is_raised_to_the_hard_one_and_never_lowered(
        monkeypatch, soft, hard, asked, result):
    calls = []
    monkeypatch.setattr(resource, "getrlimit", lambda which: (soft, hard))
    monkeypatch.setattr(resource, "setrlimit",
                        lambda which, lim: calls.append((which, lim)))
    assert service.raise_fd_limit() == result
    assert calls == ([] if asked is None
                     else [(resource.RLIMIT_NOFILE, asked)])


@pytest.mark.parametrize("error", [ValueError("not allowed"),
                                   OSError(1, "Operation not permitted")])
def test_a_refused_raise_is_logged_and_leaves_the_limit(
        monkeypatch, caplog, error):
    def refuse(which, lim):
        raise error

    monkeypatch.setattr(resource, "getrlimit", lambda which: (1024, 4096))
    monkeypatch.setattr(resource, "setrlimit", refuse)
    with caplog.at_level(logging.WARNING, logger=service.__name__):
        assert service.raise_fd_limit() == 1024
    assert "could not raise RLIMIT_NOFILE" in caplog.text
    # The bound then follows the soft limit that stands: the floor here.
    monkeypatch.setattr(service, "read_max_map_count", lambda: 65530)
    assert service.derive_max_open() == service.MAX_OPEN_FLOOR


def test_the_real_raise_never_lowers_the_limit():
    soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
    service.raise_fd_limit()
    after, hard_after = resource.getrlimit(resource.RLIMIT_NOFILE)
    assert hard_after == hard
    assert after >= soft


# ------------------------------------------- what one open source holds

def _held(root):
    """(descriptors, mappings) of this process that name a file under
    ``root``."""
    root = os.path.realpath(root) + os.sep
    fds = 0
    for fd in os.listdir("/proc/self/fd"):
        try:
            target = os.readlink(f"/proc/self/fd/{fd}")
        except OSError:          # closed since the listing
            continue
        fds += target.startswith(root)
    with open("/proc/self/maps") as f:
        maps = sum(1 for line in f
                   if line.rstrip("\n").split(None, 5)[-1].startswith(root))
    return fds, maps


needs_proc = pytest.mark.skipif(
    not os.path.isdir("/proc/self/fd") or
    not os.path.exists("/proc/self/maps"),
    reason="no /proc to count a process's descriptors and mappings in")


@needs_proc
def test_a_chunked_store_holds_one_descriptor_and_mapping_a_level_read(
        tmp_path):
    """An open store holds nothing until it reads; each level it reads
    adds one descriptor and one mapping; read at ``SOURCE_FDS`` levels
    it holds exactly the per-source figure; closed, nothing."""
    levels = service.SOURCE_FDS
    assert service.SOURCE_MAPS == levels
    root = str(tmp_path / "img")
    planes = np.arange(256 * 256, dtype=np.uint16).reshape(1, 1, 256, 256)
    build_pyramid(planes, root, chunk=(32, 32), n_levels=levels)
    store = ChunkedPyramidStore(root)
    assert store.resolution_levels() == levels
    assert _held(root) == (0, 0)
    for level, (w, h) in enumerate(store.resolution_descriptions()):
        store.get_region(0, 0, 0, RegionDef(0, 0, w, h), level)
        store.get_region(0, 0, 0, RegionDef(0, 0, 1, 1), level)
        assert _held(root) == (level + 1, level + 1)
    assert _held(root) == (service.SOURCE_FDS, service.SOURCE_MAPS)
    store.close()
    assert _held(root) == (0, 0)


@needs_proc
def test_an_ome_tiff_source_holds_its_file_and_no_mapping(tmp_path):
    root = str(tmp_path / "img")
    os.makedirs(root)
    planes = np.arange(64 * 64, dtype=np.uint16).reshape(1, 1, 64, 64)
    write_ome_tiff(planes, os.path.join(root, "img.ome.tiff"),
                   tile=(32, 32), n_levels=1)
    src = OmeTiffSource(os.path.join(root, "img.ome.tiff"))
    src.get_region(0, 0, 0, RegionDef(0, 0, 64, 64), 0)
    assert _held(root) == (1, 0)
    src.close()
    assert _held(root) == (0, 0)


# ---------------------------------------- a plate larger than 128 images

def test_a_cyclic_walk_of_400_images_opens_each_once(tmp_path, monkeypatch):
    """400 one-field images, walked twice in plate order as a screening
    viewer does, under the bound a server derives (the kernel's default
    mappings, a raised descriptor limit): each image is opened once,
    nothing is evicted, and every source stays open."""
    _fake_limits(monkeypatch, [])
    images = 400
    rng = np.random.default_rng(42)
    for image in range(1, images + 1):
        build_pyramid(
            rng.integers(0, 60000, size=(1, 1, 8, 8)).astype(np.uint16),
            str(tmp_path / str(image)), chunk=(8, 8), n_levels=1)
    svc = PixelsService(str(tmp_path), max_open=service.derive_max_open())
    assert svc.max_open == 6143 > images > 128
    try:
        for _ in range(2):
            for image in range(1, images + 1):
                src = svc.get_pixel_source(image)
                src.get_region(0, 0, 0, RegionDef(0, 0, 8, 8), 0)
                assert svc.get_open_source(image) is src
        assert svc.opened == images
        assert svc.open_count() == images
        assert not svc._evicted
    finally:
        svc.close()


# ------------------------------------------- OME-TIFF sources stay at 128

def _tiff_image(root, seed):
    os.makedirs(root)
    planes = np.random.default_rng(seed).integers(
        0, 60000, size=(1, 1, 64, 64)).astype(np.uint16)
    write_ome_tiff(planes, os.path.join(root, "img.ome.tiff"),
                   tile=(32, 32), n_levels=1)


def test_many_tiff_sources_keep_their_memos_within_the_old_budget(
        tmp_path, monkeypatch):
    """Under a derived bound of 6,143, 300 OME-TIFF images walked in
    turn: at most ``MAX_OPEN_FLOOR`` of them stay open, each parked one
    is closed (its memo emptied, its file released), so the memos'
    bytes and the descriptors together never pass what 128 sources held;
    200 chunked stores opened beside them are not evicted."""
    from omero_ms_image_region_tpu.io import ometiff
    # A memo of two of a source's four 32² tiles, so each source is
    # full after its read.
    cap = 2 * 32 * 32 * 2
    monkeypatch.setattr(ometiff, "_SEG_CACHE_BYTES", cap)
    stores, tiffs = range(1, 201), range(201, 501)
    for image in stores:
        build_pyramid(np.full((1, 1, 8, 8), image, np.uint16),
                      str(tmp_path / str(image)), chunk=(8, 8), n_levels=1)
    for image in tiffs:
        _tiff_image(str(tmp_path / str(image)), image)
    svc = PixelsService(str(tmp_path), max_open=6143)
    sources = []
    try:
        for image in stores:
            svc.get_pixel_source(image).get_region(
                0, 0, 0, RegionDef(0, 0, 8, 8), 0)
        for image in tiffs:
            src = svc.get_pixel_source(image)
            src.get_region(0, 0, 0, RegionDef(0, 0, 64, 64), 0)
            assert src._seg_cache_bytes == cap
            sources.append(weakref.ref(src))
            del src
            live = [s() for s in sources if s() is not None]
            memo = sum(s._seg_cache_bytes for s in live)
            assert memo <= service.MAX_OPEN_FLOOR * cap
            del live
        open_tiffs = [i for i in tiffs if svc.is_open(i)]
        assert open_tiffs == list(tiffs[-service.MAX_OPEN_FLOOR:])
        assert all(svc.is_open(i) for i in stores)
        assert svc.open_count() == len(stores) + service.MAX_OPEN_FLOOR
        assert svc.opened == len(stores) + len(tiffs)
        if os.path.isdir("/proc/self/fd"):
            fds = sum(_held(str(tmp_path / str(i)))[0] for i in tiffs)
            assert fds == service.MAX_OPEN_FLOOR
    finally:
        svc.close()


def test_a_tiff_reused_stays_open_and_the_oldest_tiff_is_parked(tmp_path):
    """The OME-TIFF bound is least recently used: a source read again
    moves to the back, and the one parked is the TIFF used longest ago,
    never a chunked store older still."""
    build_pyramid(np.zeros((1, 1, 8, 8), np.uint16), str(tmp_path / "1"),
                  chunk=(8, 8), n_levels=1)
    tiffs = range(2, 2 + service.MAX_OPEN_FLOOR + 1)
    for image in tiffs:
        _tiff_image(str(tmp_path / str(image)), image)
    svc = PixelsService(str(tmp_path), max_open=1000)
    try:
        svc.get_pixel_source(1)
        for image in tiffs[:-1]:
            svc.get_pixel_source(image)
        svc.get_pixel_source(tiffs[0])          # used again: now newest
        svc.get_pixel_source(tiffs[-1])         # one TIFF too many
        assert svc.is_open(1) and svc.is_open(tiffs[0])
        assert not svc.is_open(tiffs[1])
        assert svc.open_count() == 1 + service.MAX_OPEN_FLOOR
    finally:
        svc.close()
