"""The one general traffic generator: a closed loop of viewers.

A viewer is a browser tab: it holds ``connections_per_viewer``
connections to the one host and asks for its next render the moment one
returns (after ``think_s``).  Everything else is the mix's data file:

``viewers``, ``connections_per_viewer``, ``think_s``
``order``          ``seeded``: each request draws one item of the
                   viewer's share with the seed's generator;
                   ``sweep``: the viewer walks its share in raster /
                   plate order, cyclically, from an offset the seed
                   draws (every seed the same set, in another order).
``working_set``    how many items, from the first in raster order, the
                   viewers share out between them (default: all).  An
                   item is one level-0 tile on the region route and one
                   whole image on the plane route.
``window_start``, ``window_end``   [lo, hi] of the integers each
                   channel's window is drawn from, per request, so no
                   two requests of a run carry the same settings.
``warm_fill``      ``"working_set"``: touch every item of the working
                   set once before the warm-up passes (a mix that wants
                   the raw cache hot); ``"all"``: every item of the
                   deployment, the working set last (a cache as full as
                   a live site's, the working set its newest part);
                   ``false``: nothing.
``warm_pass_s``, ``warm_max_passes``   warm-up passes of that length
                   until one adds no compile event, at most that many.

The generator knows nothing of cells or configurations by name.  What
``run.py`` asks of a traffic kind is the two functions at the foot,
:func:`warm_up` and :func:`window`; an open loop would be another file
beside this one with the same two.
"""

from __future__ import annotations

import urllib.parse

import numpy as np

from benchmark import loadgen

WINDOW, WARMUP = 0, 1          # generator streams of one viewer


def items_of(config: dict) -> list:
    """Every renderable item of the deployment in raster / plate order:
    ``(image, tile_x, tile_y)`` or ``(image, None, None)``."""
    n_images = int(config["images"])
    tx, ty = (int(n) for n in config["level0_tiles"])
    if config["route"] == "render_image":
        return [(i + 1, None, None) for i in range(n_images)]
    per = int(config["content_edge"]) // int(config["tile_edge"])
    return [(i + 1, x, y) for i in range(n_images)
            for y in range(ty * per) for x in range(tx * per)]


def request_path(config: dict, item: tuple, windows: list) -> str:
    image, x, y = item
    params = {}
    if x is not None:
        edge = int(config["tile_edge"])
        params["tile"] = f"0,{x},{y},{edge},{edge}"
    params["c"] = ",".join(
        f"{c + 1}|{ws}:{we}${config['colors'][c]}"
        for c, (ws, we) in enumerate(windows))
    params.update({"m": "c", "format": config["format"],
                   "q": str(config["quality"])})
    query = urllib.parse.urlencode(params, safe="|:$,")
    return f"/webgateway/{config['route']}/{image}/0/0?{query}"


class Viewer:
    def __init__(self, index: int, mix: dict, config: dict, items: list,
                 seed: int, stream: int):
        n_viewers = int(mix["viewers"])
        n = int(mix.get("working_set", len(items)))
        if not 0 < n <= len(items):
            raise ValueError(f"working_set {n} of {len(items)} items")
        lo, hi = index * n // n_viewers, (index + 1) * n // n_viewers
        self.share = items[lo:hi]
        self.config, self.mix = config, mix
        self.rng = np.random.default_rng(
            np.random.SeedSequence([int(seed), index, stream]))
        self.cursor = int(self.rng.integers(len(self.share)))
        self.channels = int(config["channels"])

    def windows(self) -> list:
        s_lo, s_hi = self.mix["window_start"]
        e_lo, e_hi = self.mix["window_end"]
        starts = self.rng.integers(s_lo, s_hi + 1, self.channels)
        ends = self.rng.integers(e_lo, e_hi + 1, self.channels)
        return [(int(a), int(b)) for a, b in zip(starts, ends)]

    def request(self, item: tuple) -> dict:
        windows = self.windows()
        return {"item": item, "windows": windows,
                "path": request_path(self.config, item, windows)}

    def next(self) -> dict:
        if self.mix["order"] == "seeded":
            item = self.share[int(self.rng.integers(len(self.share)))]
        elif self.mix["order"] == "sweep":
            item = self.share[self.cursor]
            self.cursor = (self.cursor + 1) % len(self.share)
        else:
            raise ValueError(f"unknown order {self.mix['order']!r}")
        return self.request(item)


def viewers(mix: dict, config: dict, items: list, seed: int,
            stream: int) -> list:
    return [Viewer(v, mix, config, items, seed, stream)
            for v in range(int(mix["viewers"]))]


# ---------------------------------------------- what run.py calls by name

def warm_up(env: dict) -> dict:
    """The cell's own traffic, outside the window: the fill the mix asks
    for, then passes until one whole pass adds no compile event.
    ``env``: ``port``, ``mix``, ``config``, ``seed`` and
    ``compile_events()`` (the server's count so far).  Every answer has
    to be 200.  Returns what :func:`window` goes on from."""
    port, mix, config = env["port"], env["mix"], env["config"]
    conns, think = int(mix["connections_per_viewer"]), mix["think_s"]
    items = items_of(config)
    warm = viewers(mix, config, items, env["seed"], WARMUP)
    filled = 0
    fill = mix.get("warm_fill")
    if fill:
        if fill not in ("working_set", "all"):
            raise ValueError(f"unknown warm_fill {fill!r}")
        # Popped from the end: a viewer's own share comes last.
        queues = [[v.request(item) for item in v.share] for v in warm]
        if fill == "all":
            n = int(mix.get("working_set", len(items)))
            rest = items[n:]
            for i, (v, q) in enumerate(zip(warm, queues)):
                q.extend(v.request(item)
                         for item in rest[i::len(warm)])
        records, _, _ = loadgen.drive(
            port, [lambda q=q: q.pop() if q else None for q in queues],
            conns)
        loadgen.require_all_ok(records, "warm-up fill")
        filled = len(records)
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
            return {"filled": filled, "passes": passes, "quiet": quiet,
                    "compile_events": events,
                    "cursors": [v.cursor for v in warm]}


def window(env: dict, seconds: float, warm: dict, side_task=None) -> tuple:
    """The measured window: ``(records, t_start, t_stop)`` as
    ``loadgen.drive`` gives them.  A sweep goes on from where the
    warm-up's stopped, so the window's first requests are the items used
    longest ago, not the warm-up's last (which a cache still holds)."""
    mix, config = env["mix"], env["config"]
    vs = viewers(mix, config, items_of(config), env["seed"], WINDOW)
    for viewer, cursor in zip(vs, warm["cursors"]):
        viewer.cursor = cursor
    return loadgen.drive(
        env["port"], [v.next for v in vs],
        int(mix["connections_per_viewer"]), seconds=seconds,
        think_s=mix["think_s"], side_task=side_task)
