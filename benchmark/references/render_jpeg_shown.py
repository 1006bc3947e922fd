"""The plain reference of a render request that shows a SUBSET of the
image's channels, for deployments whose viewers switch channels on and
off (``"reference": "render_jpeg_shown"``).  A request's record carries
``shown``: the 0-based indexes of the channels its ``c=`` left active,
in ascending order.  The reference cuts those planes out of the level-0
array the harness made from the seed, takes their colours and their
windows (the record's ``windows`` holds one a stored channel, hidden
ones included), and hands them to ``render_jpeg``'s render and
comparison: a hidden channel contributes nothing and is not read.

Imports ``benchmark.references.render_jpeg`` and nothing of the
program.  The numbers compared are that module's (``qtable_diff``,
``excess_err``); ``run.py`` and ``control.py`` call the two entries at
the foot.
"""

from __future__ import annotations

from benchmark.references import render_jpeg


def _inputs(images: dict, req: dict, config: dict) -> tuple:
    """(raw planes of the shown channels, their windows, their colours,
    quality) of one request."""
    raw, colors, quality = render_jpeg._inputs(images, req, config)
    shown = [int(c) for c in req["shown"]]
    if not shown or sorted(set(shown)) != shown \
            or not 0 <= shown[0] <= shown[-1] < raw.shape[0]:
        raise ValueError(f"shown {shown} of {raw.shape[0]} channels")
    return (raw[shown], [req["windows"][c] for c in shown],
            [colors[c] for c in shown], quality)


def compare_request(body: bytes, images: dict, req: dict,
                    config: dict) -> dict:
    """The numbers of one answer; the configuration's ``limits`` name
    the ones that are compared."""
    raw, windows, colors, quality = _inputs(images, req, config)
    return render_jpeg.compare(body, raw, windows, colors, quality)


def control_request(images: dict, req: dict, config: dict,
                    **knobs) -> bytes:
    """The control's answer to the same request (``control.py``)."""
    raw, windows, colors, _ = _inputs(images, req, config)
    return render_jpeg.control_body(raw, windows, colors, **knobs)
