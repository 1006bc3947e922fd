"""Region / pyramid geometry.

Pure-function re-expression of the region math in
``ImageRegionRequestHandler.java``: region selection (``getRegionDef``
``:789-832``), bounds truncation (``truncateRegionDef`` ``:751-758``),
pre-flip mirroring (``flipRegionDef`` ``:770-780``), plane-bounds clamping
(``checkPlaneDef`` ``:651-681``), and OMERO resolution-order inversion
(``setResolutionLevel`` ``:840-853``).

These are host-side and shape-producing: they decide exactly which raw
rectangle the IO layer reads and which padded bucket the device kernel
receives, so they stay in Python and stay pure (the reference's own tests
test them the same way; SURVEY.md section 4).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple


@dataclass
class RegionDef:
    """A rectangular region (= omeis.providers.re.data.RegionDef)."""

    x: int = 0
    y: int = 0
    width: int = 0
    height: int = 0

    def as_tuple(self) -> Tuple[int, int, int, int]:
        return (self.x, self.y, self.width, self.height)


def truncate_region(size_x: int, size_y: int, region: RegionDef) -> RegionDef:
    """Clamp width/height so the region fits the image
    (= truncateRegionDef, ``:751-758``)."""
    region.width = min(region.width, size_x - region.x)
    region.height = min(region.height, size_y - region.y)
    return region


def flip_region(size_x: int, size_y: int, region: RegionDef,
                flip_horizontal: bool, flip_vertical: bool) -> RegionDef:
    """Mirror the region origin for flipped rendering so the flipped output
    of the mirrored read equals the straight read of the requested region
    (= flipRegionDef, ``:770-780``)."""
    if flip_horizontal:
        region.x = size_x - region.width - region.x
    if flip_vertical:
        region.y = size_y - region.height - region.y
    return region


def clamp_region_to_plane(resolution_levels: Sequence[Sequence[int]],
                          resolution: Optional[int],
                          region: Optional[RegionDef]) -> Optional[RegionDef]:
    """Reset out-of-bounds width/height against the selected resolution's
    plane size (= checkPlaneDef, ``:651-681``)."""
    if region is None:
        return None
    res = resolution or 0
    size_x, size_y = resolution_levels[res][0], resolution_levels[res][1]
    if region.width + region.x > size_x:
        region.width = size_x - region.x
    if region.height + region.y > size_y:
        region.height = size_y - region.y
    return region


def tile_extent(tile: RegionDef, image_tile_size: Tuple[int, int],
                max_tile_length: int) -> Tuple[int, int]:
    """``(width, height)`` of a tile request before truncation: the
    tile's own, else the image's native tile size, each clamped to
    ``max_tile_length``.  A region truncated below it at the level's
    edge is an edge tile."""
    tile_w = tile.width or image_tile_size[0]
    tile_h = tile.height or image_tile_size[1]
    return min(tile_w, max_tile_length), min(tile_h, max_tile_length)


def edge_tile_shapes(resolution_levels: Sequence[Sequence[int]],
                     tile_w: int, tile_h: int) -> List[Tuple[int, int]]:
    """``(height, width)`` of every tile of ``tile_w x tile_h`` that its
    level's edge cuts below the tile: each level's right column, bottom
    row and corner (a level smaller than the tile is one such tile).
    Fixed by the image's levels, not by its clients."""
    shapes = set()
    for size_x, size_y in resolution_levels:
        widths = {min(tile_w, size_x), size_x % tile_w or tile_w}
        heights = {min(tile_h, size_y), size_y % tile_h or tile_h}
        shapes |= {(h, w) for h in heights for w in widths}
    shapes.discard((tile_h, tile_w))
    return sorted(shapes)


def get_region_def(
    resolution_levels: Sequence[Sequence[int]],
    resolution: Optional[int],
    tile: Optional[RegionDef],
    region: Optional[RegionDef],
    image_tile_size: Tuple[int, int],
    max_tile_length: int,
    flip_horizontal: bool = False,
    flip_vertical: bool = False,
) -> RegionDef:
    """Resolve the pixel region to read (= getRegionDef, ``:789-832``).

    Tile requests use the tile's own width/height if given, else the
    image's native tile size, clamped to ``max_tile_length``; the offset is
    in tile units.  Region requests are pixel-space.  Neither => the whole
    plane at the selected resolution (returned WITHOUT truncate/flip, as in
    the reference's early return ``:822-827``).
    """
    res = resolution or 0
    size_x, size_y = resolution_levels[res][0], resolution_levels[res][1]
    out = RegionDef()
    if tile is not None:
        tile_w, tile_h = tile_extent(tile, image_tile_size,
                                     max_tile_length)
        out.width = tile_w
        out.height = tile_h
        out.x = tile.x * tile_w
        out.y = tile.y * tile_h
    elif region is not None:
        out.x, out.y = region.x, region.y
        out.width, out.height = region.width, region.height
    else:
        out.x, out.y = 0, 0
        out.width, out.height = size_x, size_y
        return out
    truncate_region(size_x, size_y, out)
    flip_region(size_x, size_y, out, flip_horizontal, flip_vertical)
    return out


# NOTE: the reference's setResolutionLevel inversion (``level = n - res - 1``,
# ``:845-852``) is deliberately NOT reproduced here: it converts between the
# largest-first descriptions order and OMERO's smallest-first PixelBuffer
# level order.  Our PixelSource numbers levels largest-first like the
# descriptions, so the request resolution IS the read level (see
# ImageRegionHandler._get_region).
