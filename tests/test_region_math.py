"""Region/pyramid geometry vs the reference's own test expectations
(ImageRegionRequestHandlerTest.java:202-618)."""

import pytest

from omero_ms_image_region_tpu.server.region import (
    RegionDef,
    clamp_region_to_plane,
    edge_tile_shapes,
    flip_region,
    get_region_def,
    truncate_region,
)

LEVELS_1024 = [[1024, 1024]]
MAX_TILE = 2048


def test_tile_default_size():
    # testGetRegionDefCtxTile: tile (2,2) with no w/h uses image tile size.
    rd = get_region_def(LEVELS_1024, None, RegionDef(2, 2, 0, 0), None,
                        (256, 256), MAX_TILE)
    assert rd.as_tuple() == (512, 512, 256, 256)


def test_tile_with_width_and_height():
    rd = get_region_def(LEVELS_1024, None, RegionDef(2, 2, 64, 128), None,
                        (64, 128), MAX_TILE)
    assert rd.as_tuple() == (128, 256, 64, 128)


def test_tile_clamped_to_max_tile_length():
    rd = get_region_def([[8192, 8192]], None, RegionDef(0, 0, 4096, 4096),
                        None, (256, 256), MAX_TILE)
    assert rd.width == MAX_TILE and rd.height == MAX_TILE


def test_region_passthrough():
    rd = get_region_def(LEVELS_1024, None, None, RegionDef(512, 512, 256, 256),
                        (256, 256), MAX_TILE)
    assert rd.as_tuple() == (512, 512, 256, 256)


def test_no_tile_or_region_full_plane():
    rd = get_region_def(LEVELS_1024, None, None, None, (256, 256), MAX_TILE)
    assert rd.as_tuple() == (0, 0, 1024, 1024)


def test_full_plane_uses_selected_resolution():
    rd = get_region_def([[256, 256], [1024, 1024]], 0, None, None,
                        (256, 256), MAX_TILE)
    assert rd.as_tuple() == (0, 0, 256, 256)


@pytest.mark.parametrize(
    "region,expect",
    [
        # testGetRegionDefCtxRegionTruncX/Y/XY at 1024^2
        (RegionDef(768, 0, 512, 512), (768, 0, 256, 512)),
        (RegionDef(0, 768, 512, 512), (0, 768, 512, 256)),
        (RegionDef(768, 768, 512, 512), (768, 768, 256, 256)),
    ],
)
def test_region_truncation(region, expect):
    rd = get_region_def(LEVELS_1024, None, None, region, (256, 256), MAX_TILE)
    assert rd.as_tuple() == expect


def test_tile_truncation():
    # Edge tile of a non-tile-aligned dimension.
    rd = get_region_def(LEVELS_1024, None, RegionDef(3, 0, 0, 0), None,
                        (300, 300), MAX_TILE)
    assert rd.as_tuple() == (900, 0, 124, 300)


def test_flip_region_h():
    rd = RegionDef(0, 0, 256, 256)
    flip_region(1024, 1024, rd, True, False)
    assert rd.as_tuple() == (768, 0, 256, 256)


def test_flip_region_v():
    rd = RegionDef(0, 0, 256, 256)
    flip_region(1024, 1024, rd, False, True)
    assert rd.as_tuple() == (0, 768, 256, 256)


def test_flip_region_hv():
    rd = RegionDef(128, 256, 256, 128)
    flip_region(1024, 1024, rd, True, True)
    assert rd.as_tuple() == (640, 640, 256, 128)


def test_flip_mirror_x_edge_non_aligned():
    """testFlipRegionDefMirorXEdge: 768^2 image, 512-tiles, flip H —
    truncation happens BEFORE mirroring, so edge tiles land at x=0."""
    levels = [[768, 768]]
    cases = [
        (RegionDef(0, 0, 1024, 1024), (0, 0, 768, 768)),
        (RegionDef(512, 0, 512, 512), (0, 0, 256, 512)),
        (RegionDef(0, 512, 512, 512), (256, 512, 512, 256)),
        (RegionDef(512, 512, 512, 512), (0, 512, 256, 256)),
    ]
    for region, expect in cases:
        rd = get_region_def(levels, None, None, region, (512, 512),
                            MAX_TILE, flip_horizontal=True)
        assert rd.as_tuple() == expect, (region, rd)


def test_flip_mirror_y_edge_non_aligned():
    levels = [[768, 768]]
    rd = get_region_def(levels, None, None, RegionDef(0, 512, 512, 512),
                        (512, 512), MAX_TILE, flip_vertical=True)
    assert rd.as_tuple() == (0, 0, 512, 256)


def test_region_def_indexes_levels_largest_first():
    # The reference's testSelectResolution: a largest-first level list is
    # indexed directly by the request resolution (its n-res-1 inversion is
    # buffer-order-specific and intentionally absent here; see
    # server.region NOTE).
    levels = [[1024, 1024], [256, 512]]
    rd = get_region_def(levels, 1, None, RegionDef(100, 200, 400, 500),
                        (800, 800), MAX_TILE)
    assert rd.as_tuple() == (100, 200, 256 - 100, 512 - 200)


def test_clamp_region_to_plane():
    rd = RegionDef(512, 0, 1024, 1024)
    clamp_region_to_plane([[1024, 768]], None, rd)
    assert rd.as_tuple() == (512, 0, 512, 768)
    assert clamp_region_to_plane([[64, 64]], None, None) is None


def test_truncate_region_noop_when_inside():
    rd = RegionDef(0, 0, 100, 100)
    truncate_region(1024, 1024, rd)
    assert rd.as_tuple() == (0, 0, 100, 100)


def test_edge_tile_shapes_are_the_truncated_tiles_of_every_level():
    """The shapes ``get_region_def`` cuts at each level's right column,
    bottom row and corner; a level smaller than the tile is one tile."""
    levels = [[24000, 16000], [12000, 8000], [6000, 4000], [3000, 2000],
              [1500, 1000], [750, 500]]
    shapes = edge_tile_shapes(levels, 1024, 1024)
    assert len(shapes) == 15
    assert {(1024, 448), (640, 1024), (640, 448), (1000, 476),
            (1000, 1024), (500, 750)} <= set(shapes)
    cut = set()
    for res, (sx, sy) in enumerate(levels):
        for col in range(-(-sx // 1024)):
            for row in range(-(-sy // 1024)):
                rd = get_region_def(levels, res, RegionDef(col, row, 1024,
                                                           1024),
                                    None, (1024, 1024), MAX_TILE)
                if (rd.height, rd.width) != (1024, 1024):
                    cut.add((rd.height, rd.width))
    assert cut == set(shapes)
    # A pyramid of whole tiles has none.
    assert edge_tile_shapes([[2048, 1024], [1024, 512]], 512, 512) == []
