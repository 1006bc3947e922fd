"""The plain reference of a tile request at ANY pyramid level, for
deployments whose viewers zoom (``"reference": "render_jpeg_levels"``).
A request's record carries ``item`` ``(image, level, col, row)``
(``traffic_kinds/zoom_pan.py``).

Level *n* is computed from level *n - 1* by the store's documented rule
(``io/store.py``: "mean-pool by 2"): the mean of each 2 x 2 block,
rounded half to even, the odd last row or column of a level left out,
so each side halves rounded down.  Here that is done in integer sums
(four uint16 samples sum below 2^18, exactly), in bands of rows, once a
run and level, from the level-0 arrays the harness made from the seed;
nothing of the program is imported.  The tile is then cut at ``col x
tile_edge``, ``row x tile_edge`` and truncated at the level's edge, and
rendered and compared exactly as ``render_jpeg`` does, by its own
functions (``compare``, ``control_body``): the numbers compared are that
module's, ``qtable_diff`` and ``excess_err``.

Where this departs from OMERO's own pyramids: Bio-Formats and the
converters that write pyramidal OME-TIFF or NGFF (bioformats2raw,
ASHLAR) may downsample otherwise (nearest neighbour, area averaging in
floating point, other rounding), and OMERO's own pyramid builder keeps
levels of other sizes.  The rule here is the one this system stores and
serves; a tile that agrees with it agrees with what the system ingested,
not with a level another tool would have written.
"""

from __future__ import annotations

import threading
import weakref

import numpy as np

from benchmark.references import render_jpeg

BAND = 1024                      # output rows summed at a time

_lock = threading.Lock()
# id(level-0 array) -> [level 1, level 2, ...]; an entry goes when its
# level-0 array does, before the id can name another.
_levels: dict = {}


def downsample(plane: np.ndarray) -> np.ndarray:
    """``[C, H, W]`` uint16 -> ``[C, H // 2, W // 2]``: each 2 x 2
    block's mean, rounded half to even, in integers."""
    C, H, W = plane.shape
    h, w = H // 2, W // 2
    out = np.empty((C, h, w), plane.dtype)
    for c in range(C):
        for y0 in range(0, h, BAND):
            y1 = min(h, y0 + BAND)
            v = plane[c, 2 * y0:2 * y1, :2 * w]
            s = (v[0::2, 0::2].astype(np.uint32) + v[0::2, 1::2]
                 + v[1::2, 0::2] + v[1::2, 1::2])
            q, r = s >> 2, s & 3
            # Half to even: up past the half, and at it when q is odd.
            q += (r > 2) | ((r == 2) & ((q & 1) == 1))
            out[c, y0:y1] = q
    return out


def level(images: dict, image: int, n: int) -> np.ndarray:
    """Level ``n`` of ``image`` ``[C, h, w]``, computed once a run."""
    base = images[image]
    if n == 0:
        return base
    with _lock:
        pyramid = _levels.get(id(base))
        if pyramid is None:
            pyramid = _levels[id(base)] = []
            weakref.finalize(base, _levels.pop, id(base), None)
        while len(pyramid) < n:
            pyramid.append(downsample(pyramid[-1] if pyramid else base))
        return pyramid[n - 1]


def region_of(size: tuple, tile: int, col: int, row: int) -> tuple:
    """``(x, y, w, h)`` of tile ``(col, row)`` at a level of ``size``
    ``(width, height)``, truncated at the level's edge."""
    x, y = col * tile, row * tile
    if not (0 <= x < size[0] and 0 <= y < size[1]):
        raise ValueError(f"tile {col},{row} outside a level of {size}")
    return x, y, min(tile, size[0] - x), min(tile, size[1] - y)


def _inputs(images: dict, req: dict, config: dict) -> tuple:
    """(raw region at its level, colours, quality) of one request."""
    image, n, col, row = req["item"]
    _, colors, quality = render_jpeg._inputs(
        images, {"item": (image, None, None)}, config)
    raw = level(images, image, int(n))
    x, y, w, h = region_of((raw.shape[2], raw.shape[1]),
                           int(config["tile_edge"]), int(col), int(row))
    return raw[:, y:y + h, x:x + w], colors, quality


# ------------------------------------------- what run.py and control.py call

def compare_request(body: bytes, images: dict, req: dict,
                    config: dict) -> dict:
    """The numbers of one answer; the configuration's ``limits`` name
    the ones that are compared."""
    raw, colors, quality = _inputs(images, req, config)
    return render_jpeg.compare(body, raw, req["windows"], colors, quality)


def control_request(images: dict, req: dict, config: dict,
                    **knobs) -> bytes:
    """The control's answer to the same request (``control.py``)."""
    raw, colors, _ = _inputs(images, req, config)
    return render_jpeg.control_body(raw, req["windows"], colors, **knobs)
