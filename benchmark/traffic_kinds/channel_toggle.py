"""A closed loop of viewers who switch channels on and off over a fixed
field: the traffic of a highly multiplexed slide (t-CyCIF, CODEX), where
an image stores tens of channels and a viewer shows a handful.

A viewer is a browser tab as in ``closed_loop`` (whose ``Viewer``,
window draws and connection slots this kind reuses): it holds
``connections_per_viewer`` connections and asks for its next render the
moment one returns.  What differs is what it asks for.  Each viewer owns
a viewport of ``viewport_tiles`` level-0 tiles (viewer ``i``'s is the
``i``-th such block of the slide in raster order) and shows the channels
``always_shown`` (1-based, as in ``c=``) and ``markers_shown`` of the
others, drawn by the seed.  A *step* asks the viewport's tiles in raster
order under one fresh draw of per-channel windows, with EVERY stored
channel in ``c=``, the hidden ones under a negative index, as OMERO.web
sends them.  Steps alternate: *off* (one of the shown markers, drawn by
the seed, is hidden) and *on* (a marker not shown, drawn by the seed, is
added).  The viewers' generators are independent, so requests of both
counts share the server's queue.  Every request's record carries
``shown``: the 0-based indexes of its active channels, ascending, which
is what the reference renders (``references/render_jpeg_shown.py``).

The mix's file beside the parameters above: ``window_start`` /
``window_end``, ``think_s``, ``warm_pass_s`` / ``warm_max_passes`` as in
``closed_loop``; ``warm_fill`` ``"viewport_channels"`` asks every tile
of every viewport once with ``always_shown`` and each block of
``warm_fill_block`` consecutive other channels, so that every (tile,
channel) of the viewports has been read once before the passes
(``false``: nothing).
"""

from __future__ import annotations

import urllib.parse

from benchmark import loadgen
from benchmark.traffic_kinds import closed_loop
from benchmark.traffic_kinds.closed_loop import WARMUP, WINDOW


def viewport_of(index: int, mix: dict, config: dict) -> list:
    """The items ``(image, tile_x, tile_y)`` of viewer ``index``'s
    viewport, in raster order."""
    per = int(config["content_edge"]) // int(config["tile_edge"])
    tiles_x, tiles_y = (int(n) * per for n in config["level0_tiles"])
    vx, vy = (int(n) for n in mix["viewport_tiles"])
    across, down = tiles_x // vx, tiles_y // vy
    if not 0 <= index < across * down * int(config["images"]):
        raise ValueError(f"no viewport {index}: {across} x {down} of "
                         f"{vx} x {vy} tiles an image")
    image, block = divmod(index, across * down)
    by, bx = divmod(block, across)
    return [(image + 1, bx * vx + x, by * vy + y)
            for y in range(vy) for x in range(vx)]


def request_path(config: dict, item: tuple, windows: list,
                 shown: list) -> str:
    """``closed_loop.request_path`` with the channels not in ``shown``
    (0-based) switched off: every stored channel is in ``c=``, a hidden
    one as ``-<n>|...``."""
    image, x, y = item
    edge = int(config["tile_edge"])
    on = set(shown)
    params = {
        "tile": f"0,{x},{y},{edge},{edge}",
        "c": ",".join(
            f"{'' if c in on else '-'}{c + 1}|{ws}:{we}"
            f"${config['colors'][c]}"
            for c, (ws, we) in enumerate(windows)),
        "m": "c", "format": config["format"],
        "q": str(config["quality"])}
    query = urllib.parse.urlencode(params, safe="|:$,-")
    return f"/webgateway/{config['route']}/{image}/0/0?{query}"


class Viewer(closed_loop.Viewer):
    """One viewer over its own viewport, toggling markers."""

    def __init__(self, index: int, mix: dict, config: dict, items: list,
                 seed: int, stream: int):
        super().__init__(index, mix, config, items, seed, stream)
        self.share = viewport_of(index, mix, config)
        self.always = sorted(int(n) - 1 for n in mix["always_shown"])
        self.markers = [c for c in range(self.channels)
                        if c not in self.always]
        self.on = sorted(int(c) for c in self.rng.choice(
            self.markers, size=int(mix["markers_shown"]), replace=False))
        self.full = len(self.on)
        self.step: list = []            # requests left of this step

    def toggle(self) -> None:
        """Off when every marker of the handful is shown, else on."""
        if len(self.on) == self.full:
            self.on.remove(self.on[int(self.rng.integers(len(self.on)))])
        else:
            off = [c for c in self.markers if c not in self.on]
            self.on = sorted(
                self.on + [off[int(self.rng.integers(len(off)))]])

    def request(self, item: tuple, windows: list = None,
                markers: list = None) -> dict:
        windows = self.windows() if windows is None else windows
        shown = sorted(self.always
                       + (self.on if markers is None else markers))
        return {"item": item, "windows": windows, "shown": shown,
                "path": request_path(self.config, item, windows, shown)}

    def next(self) -> dict:
        if not self.step:
            self.toggle()
            windows = self.windows()
            self.step = [self.request(item, windows)
                         for item in reversed(self.share)]
        return self.step.pop()

    def fill(self) -> list:
        """Every (tile, channel) of the viewport once: each tile with
        ``always_shown`` and each block of consecutive markers."""
        n = int(self.mix["warm_fill_block"])
        blocks = [self.markers[i:i + n]
                  for i in range(0, len(self.markers), n)]
        return [self.request(item, markers=block)
                for item in self.share for block in blocks]


def viewers(env: dict, stream: int) -> list:
    mix, config = env["mix"], env["config"]
    items = closed_loop.items_of(config)
    return [Viewer(v, mix, config, items, env["seed"], stream)
            for v in range(int(mix["viewers"]))]


# ---------------------------------------------- what run.py calls by name

def warm_up(env: dict) -> dict:
    """The fill the mix asks for, then passes of the cell's own traffic
    until one whole pass adds no compile event (``closed_loop.warm_up``
    with this kind's viewers).  Every answer has to be 200."""
    port, mix = env["port"], env["mix"]
    conns, think = int(mix["connections_per_viewer"]), mix["think_s"]
    warm = viewers(env, WARMUP)
    filled = 0
    fill = mix.get("warm_fill")
    if fill:
        if fill != "viewport_channels":
            raise ValueError(f"unknown warm_fill {fill!r}")
        # Popped from the end.
        queues = [v.fill()[::-1] for v in warm]
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
                    "compile_events": events}


def window(env: dict, seconds: float, warm: dict, side_task=None) -> tuple:
    """The measured window: ``(records, t_start, t_stop)`` as
    ``loadgen.drive`` gives them; the viewers start from the seed's own
    handfuls, whatever the warm-up showed last."""
    mix = env["mix"]
    return loadgen.drive(
        env["port"], [v.next for v in viewers(env, WINDOW)],
        int(mix["connections_per_viewer"]), seconds=seconds,
        think_s=mix["think_s"], side_task=side_task)
