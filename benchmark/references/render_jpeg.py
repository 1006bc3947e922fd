"""The plain reference of a render request, and the comparison that
decides ``correct``, for deployments that answer windowed, coloured
JPEG renders.  A configuration names its reference module
(``"reference": "render_jpeg"``) and ``run.py`` calls the two entries at
the foot of this file: :func:`compare_request` and
:func:`control_request`.  A deployment that answers something else
(PNG, a projection, a mask) brings another file beside this one.

Imports nothing of the program: numpy for the
render semantics (per channel: window -> 0..255 codomain, times the
channel's colour, additive composite; OMERO's ``Renderer`` for linear
families in RGB mode), libjpeg through PIL for the baseline-JPEG stage
(IJG tables at the configuration's quality, 4:2:0), and PIL's decoder
for both sides.

Numbers compared, per sampled response (the worst over the sample is
printed beside its limit):

``qtable_diff``   largest absolute difference between the quantisation
                  tables the body carries and the IJG tables at the
                  configuration's quality.  Exact: limit 0.
``excess_err``    how much further the decoded body lies from the
                  reference render than libjpeg's own encoding of that
                  render at that quality does: mean absolute RGB error
                  of the one over that of the other, less 1.  Content
                  and window move both errors alike, so the ratio is
                  steady from seed to seed where neither error is.
"""

from __future__ import annotations

import io

import numpy as np

# Annex K tables, natural (row-major) order.
_LUMA = np.array([
    16, 11, 10, 16, 24, 40, 51, 61, 12, 12, 14, 19, 26, 58, 60, 55,
    14, 13, 16, 24, 40, 57, 69, 56, 14, 17, 22, 29, 51, 87, 80, 62,
    18, 22, 37, 56, 68, 109, 103, 77, 24, 35, 55, 64, 81, 104, 113, 92,
    49, 64, 78, 87, 103, 121, 120, 101, 72, 92, 95, 98, 112, 100, 103, 99])
_CHROMA = np.array([
    17, 18, 24, 47, 99, 99, 99, 99, 18, 21, 26, 66, 99, 99, 99, 99,
    24, 26, 56, 99, 99, 99, 99, 99, 47, 66, 99, 99, 99, 99, 99, 99,
    99, 99, 99, 99, 99, 99, 99, 99, 99, 99, 99, 99, 99, 99, 99, 99,
    99, 99, 99, 99, 99, 99, 99, 99, 99, 99, 99, 99, 99, 99, 99, 99])


def ijg_tables(quality: int) -> tuple:
    """IJG quality scaling of the Annex K tables, natural order."""
    quality = int(max(1, min(100, quality)))
    scale = 5000 // quality if quality < 50 else 200 - 2 * quality
    return tuple(np.clip((t * scale + 50) // 100, 1, 255)
                 for t in (_LUMA, _CHROMA))


def render_rgb(raw: np.ndarray, windows: list, colors: list,
               data_bits: int | None = None) -> np.ndarray:
    """uint8[H, W, 3] of ``raw`` [C, H, W] under per-channel
    ``(start, end)`` windows and ``(r, g, b)`` colours, in float64.

    ``data_bits`` is the control's knob only: keep that many high bits
    of each 16-bit sample (what a later PR would be tempted to ship)."""
    accum = np.zeros(raw.shape[1:] + (3,), np.float64)
    for plane, (ws, we), color in zip(raw, windows, colors):
        if data_bits is not None:
            drop = 16 - data_bits
            plane = (plane >> drop) << drop
        x = plane.astype(np.float64)
        ratio = np.clip((np.clip(x, ws, we) - ws) / (we - ws), 0.0, 1.0)
        q = np.round(255.0 * ratio)
        accum += (q[..., None] / 255.0) * np.asarray(color, np.float64)
    return np.clip(np.round(accum), 0, 255).astype(np.uint8)


def libjpeg_bytes(rgb: np.ndarray, quality: int) -> bytes:
    from PIL import Image
    buf = io.BytesIO()
    Image.fromarray(np.ascontiguousarray(rgb)).save(
        buf, format="JPEG", quality=int(quality), subsampling="4:2:0")
    return buf.getvalue()


def decode(body: bytes) -> tuple:
    """(uint8[H, W, 3], {table id: natural-order int array})."""
    from PIL import Image
    img = Image.open(io.BytesIO(body))
    img.load()
    # PIL (12.x here) hands the tables over in natural order.
    tables = {k: np.asarray(v, np.int64)
              for k, v in getattr(img, "quantization", {}).items()}
    return np.asarray(img.convert("RGB")), tables


def _table_diff(got: np.ndarray, want: np.ndarray) -> int:
    if got.shape != want.shape:
        return 255
    return int(np.abs(got - want).max())


def compare(body: bytes, raw: np.ndarray, windows: list, colors: list,
            quality: int) -> dict:
    """The numbers of one response against the reference."""
    want_rgb = render_rgb(raw, windows, colors)
    ref_rgb, _ = decode(libjpeg_bytes(want_rgb, quality))
    try:
        got_rgb, tables = decode(body)
    except Exception as e:                     # not a JPEG at all
        return {"error": f"undecodable body: {e}"}
    if got_rgb.shape != want_rgb.shape:
        return {"error": f"decoded {got_rgb.shape}, "
                         f"expected {want_rgb.shape}"}
    qy, qc = ijg_tables(quality)
    if sorted(tables) != [0, 1]:
        qdiff = 255
    else:
        qdiff = max(_table_diff(tables[0], qy),
                    _table_diff(tables[1], qc))
    a = got_rgb.astype(np.float64)
    want = want_rgb.astype(np.float64)
    err = float(np.abs(a - want).mean())
    libjpeg_err = float(np.abs(ref_rgb - want).mean())
    return {
        "qtable_diff": float(qdiff),
        "excess_err": err / max(libjpeg_err, 1e-9) - 1.0,
        # Not compared, kept for the human lines: the two errors in
        # grey levels, and the two encodings' distance from each other.
        "err": err, "libjpeg_err": libjpeg_err,
        "libjpeg_gap": float(np.abs(a - ref_rgb).mean()),
    }


def control_body(raw: np.ndarray, windows: list, colors: list,
                 quality: int, data_bits: int | None = None) -> bytes:
    """The control: the reference put in the program's place, one step
    below what the configuration states (a lower JPEG quality and/or
    fewer data bits)."""
    return libjpeg_bytes(render_rgb(raw, windows, colors, data_bits),
                         quality)


# ------------------------------------------- what run.py and control.py call

def _inputs(images: dict, req: dict, config: dict) -> tuple:
    """(raw region, colours, quality) of one request of this deployment:
    the level-0 samples the request reads, cut from the arrays the
    harness made from the seed."""
    image, x, y = req["item"]
    raw = images[image]
    if x is not None:
        edge = int(config["tile_edge"])
        raw = raw[:, y * edge:(y + 1) * edge, x * edge:(x + 1) * edge]
    colors = [tuple(int(h[i:i + 2], 16) for i in (0, 2, 4))
              for h in config["colors"]]
    return raw, colors, round(float(config["quality"]) * 100)


def compare_request(body: bytes, images: dict, req: dict,
                    config: dict) -> dict:
    """The numbers of one answer; the configuration's ``limits`` name
    the ones that are compared."""
    raw, colors, quality = _inputs(images, req, config)
    return compare(body, raw, req["windows"], colors, quality)


def control_request(images: dict, req: dict, config: dict,
                    **knobs) -> bytes:
    """The control's answer to the same request (``control.py``)."""
    raw, colors, _ = _inputs(images, req, config)
    return control_body(raw, req["windows"], colors, **knobs)
