"""A closed loop of viewers who open a slide the way every tile viewer
does (OMERO.iviewer, OpenLayers, OpenSeadragon): the slide at a coarse
pyramid level, a zoom in by factors of two, one level a step, a pan at
full resolution, and a zoom back out.

A viewer is a browser tab as in ``closed_loop`` (whose window draws and
connection slots this kind reuses): it holds ``connections_per_viewer``
connections and asks for its next tile the moment one returns.  It runs
sessions back to back.  A session draws a focus point over the slide
(and, on a deployment of several images, the image) from the seed, then
asks, step by step:

- from the top level down to level 0, the tiles a ``viewport`` of
  ``[width, height]`` pixels centred on the focus covers at that level,
  the viewport clamped inside the level;
- at level 0, ``pan_steps`` pans of one tile in a direction the seed
  draws (right, left, down or up; a pan the slide's edge stops turns
  back), asking only the tiles each pan newly exposes;
- from level 1 up to the top level again, around the viewport's centre
  after the pan.

A step's tiles go in raster order; the next step follows as
connections free.  Every request is ``tile=<level>,<col>,<row>,<tile
edge>,<tile edge>`` with fresh per-channel windows, so the bytes cache
and ETags never answer.  The levels are the store's: the configuration's
extent (``level0_tiles`` blocks of ``content_edge``) halved, rounded
down, while the next level's short side is at least the least level
size the harness ingests with (``benchmark/datagen.py``: 256, or the
block edge where that is smaller).  A request's record carries ``item``
``(image, level, col, row)``, what ``references/render_jpeg_levels.py``
renders.

The mix's file beside the parameters above: ``window_start`` /
``window_end``, ``think_s``, ``warm_pass_s`` / ``warm_max_passes`` as in
``closed_loop``; no fill (``warm_fill`` false).  The warm-up's passes go
on with the viewers' sessions, and the window goes on from there.
"""

from __future__ import annotations

import urllib.parse

import numpy as np

from benchmark import loadgen
from benchmark.traffic_kinds import closed_loop
from benchmark.traffic_kinds.closed_loop import WARMUP

# (dx, dy) of a pan, in tiles.
DIRECTIONS = ((1, 0), (-1, 0), (0, 1), (0, -1))


def levels_of(config: dict) -> list:
    """``[(width, height)]`` of the deployment's pyramid levels,
    largest first, by the store's halving rule at the harness's least
    level size."""
    edge = int(config["content_edge"])
    tx, ty = (int(n) for n in config["level0_tiles"])
    w, h = tx * edge, ty * edge
    levels = [(w, h)]
    least = min(256, edge)
    while config["pyramid"] and min(w // 2, h // 2) >= least:
        w, h = w // 2, h // 2
        levels.append((w, h))
    return levels


def viewport_tiles(size: tuple, tile: int, viewport: tuple,
                   centre: tuple) -> tuple:
    """``((x0, y0), [(col, row)])``: the viewport's origin at a level of
    ``size`` pixels, centred on ``centre`` and clamped inside the level,
    and the tiles it covers in raster order."""
    origin = []
    spans = []
    for extent, view, c in zip(size, viewport, centre):
        view = min(view, extent)
        lo = int(min(max(round(c - view / 2), 0), extent - view))
        origin.append(lo)
        spans.append(range(lo // tile, (lo + view - 1) // tile + 1))
    cols, rows = spans
    return tuple(origin), [(c, r) for r in rows for c in cols]


def tile_region(size: tuple, tile: int, col: int, row: int) -> tuple:
    """``(x, y, w, h)`` of tile ``(col, row)`` at a level of ``size``,
    truncated at the level's edge."""
    x, y = col * tile, row * tile
    return x, y, min(tile, size[0] - x), min(tile, size[1] - y)


def session_items(config: dict, mix: dict, image: int, focus: tuple,
                  direction: tuple) -> list:
    """Every item ``(image, level, col, row)`` of one session, in the
    order it asks them."""
    levels = levels_of(config)
    tile = int(config["tile_edge"])
    viewport = tuple(int(n) for n in mix["viewport"])
    top = len(levels) - 1

    def at(level: int, centre0: tuple) -> tuple:
        scale = 2 ** level
        return viewport_tiles(levels[level], tile, viewport,
                              (centre0[0] / scale, centre0[1] / scale))

    items = []
    for level in range(top, -1, -1):
        origin, tiles = at(level, focus)
        items += [(image, level, c, r) for c, r in tiles]
    # The pan, at level 0, from the viewport the zoom ended on.
    size = levels[0]
    view = [min(v, e) for v, e in zip(viewport, size)]

    def pan(origin: tuple, direction: tuple) -> list:
        return [min(max(o + d * tile, 0), e - v)
                for o, d, e, v in zip(origin, direction, size, view)]

    shown = set(tiles)
    for _ in range(int(mix.get("pan_steps", 4))):
        moved = pan(origin, direction)
        if moved == list(origin):
            # The edge turns the pan back.
            direction = tuple(-d for d in direction)
            moved = pan(origin, direction)
        centre = (moved[0] + view[0] / 2, moved[1] + view[1] / 2)
        origin, tiles = at(0, centre)
        items += [(image, 0, c, r) for c, r in tiles
                  if (c, r) not in shown]
        shown = set(tiles)
    centre = (origin[0] + view[0] / 2, origin[1] + view[1] / 2)
    for level in range(1, top + 1):
        _, tiles = at(level, centre)
        items += [(image, level, c, r) for c, r in tiles]
    return items


def request_path(config: dict, item: tuple, windows: list) -> str:
    image, level, col, row = item
    edge = int(config["tile_edge"])
    params = {
        "tile": f"{level},{col},{row},{edge},{edge}",
        "c": ",".join(f"{c + 1}|{ws}:{we}${config['colors'][c]}"
                      for c, (ws, we) in enumerate(windows)),
        "m": "c", "format": config["format"],
        "q": str(config["quality"])}
    query = urllib.parse.urlencode(params, safe="|:$,")
    return f"/webgateway/{config['route']}/{image}/0/0?{query}"


class Viewer(closed_loop.Viewer):
    """One viewer running zoom sessions back to back: ``closed_loop``'s
    generator and window draws, with no share of items (every viewer
    roams the whole slide)."""

    def __init__(self, index: int, mix: dict, config: dict, seed: int,
                 stream: int):
        self.config, self.mix, self.index = config, mix, index
        self.rng = np.random.default_rng(
            np.random.SeedSequence([int(seed), index, stream]))
        self.channels = int(config["channels"])
        self.sessions = 0
        self.queue: list = []          # items left of this session

    def new_session(self) -> None:
        width, height = levels_of(self.config)[0]
        image = 1 + int(self.rng.integers(int(self.config["images"])))
        focus = (float(self.rng.uniform(0, width)),
                 float(self.rng.uniform(0, height)))
        direction = DIRECTIONS[int(self.rng.integers(len(DIRECTIONS)))]
        self.queue = session_items(self.config, self.mix, image, focus,
                                   direction)[::-1]
        self.sessions += 1

    def request(self, item: tuple) -> dict:
        windows = self.windows()
        return {"item": item, "windows": windows,
                "path": request_path(self.config, item, windows)}

    def next(self) -> dict:
        if not self.queue:
            self.new_session()
        return self.request(self.queue.pop())

    def __repr__(self) -> str:
        return (f"viewer {self.index}: session {self.sessions}, "
                f"{len(self.queue)} tiles left")


def viewers(env: dict, stream: int) -> list:
    mix, config = env["mix"], env["config"]
    return [Viewer(v, mix, config, env["seed"], stream)
            for v in range(int(mix["viewers"]))]


# ---------------------------------------------- what run.py calls by name

def warm_up(env: dict) -> dict:
    """Passes of the cell's own sessions until one whole pass adds no
    compile event, at most ``warm_max_passes``; no fill.  Every answer
    has to be 200.  The viewers, mid-session, are what :func:`window`
    goes on from."""
    port, mix = env["port"], env["mix"]
    if mix.get("warm_fill"):
        raise ValueError(f"zoom_pan has no fill, not {mix['warm_fill']!r}")
    conns, think = int(mix["connections_per_viewer"]), mix["think_s"]
    warm = viewers(env, WARMUP)
    passes, events = 0, env["compile_events"]()
    pass_s = float(mix.get("warm_pass_s", 3.0))
    while True:
        records, _, _ = loadgen.drive(port, [v.next for v in warm], conns,
                                      seconds=pass_s, think_s=think)
        loadgen.require_all_ok(records, "warm-up pass")
        passes += 1
        now = env["compile_events"]()
        quiet = now == events
        events = now
        if quiet or passes >= int(mix.get("warm_max_passes", 8)):
            return {"filled": 0, "passes": passes, "quiet": quiet,
                    "compile_events": events, "viewers": warm}


def window(env: dict, seconds: float, warm: dict, side_task=None) -> tuple:
    """The measured window: ``(records, t_start, t_stop)`` as
    ``loadgen.drive`` gives them, the warm-up's viewers going on with
    their sessions."""
    mix = env["mix"]
    return loadgen.drive(
        env["port"], [v.next for v in warm["viewers"]],
        int(mix["connections_per_viewer"]), seconds=seconds,
        think_s=mix["think_s"], side_task=side_task)
