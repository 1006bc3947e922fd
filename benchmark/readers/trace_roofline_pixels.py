"""``trace_roofline`` for a cell whose renders differ in size (pyramid
edge tiles beside whole ones): the least time the chip could take for
the renders finished inside the traced window over the time it was busy
in it, with a render's bytes and operations counted at the MEAN
UNPADDED PIXELS A RENDER and not at ``tile_edge``^2, which would count
an edge tile's padding as work.  The mean is the growth of
``imageregion_render_pixels_total`` (every region the handler prepared,
unpadded) over that of ``imageregion_renders_routed_total`` (the same
renders, both routes) over the window; ``work.render_bytes`` and
``work.render_ops`` are linear in the pixels, so the mean render's work
is the mean of the renders' work.  Whichever way a later change pads the
edges, this reads the same.  Everything else, the counting of the
renders included, is ``trace_roofline``'s.  Nothing where that reads
nothing, or where the server exports no such counter (a program from
before it): never 0."""

from benchmark import work
from benchmark.prom import delta

PIXELS = "imageregion_render_pixels_total"
RENDERS = "imageregion_renders_routed_total"


def read(ctx: dict):
    trace, cap = ctx.get("trace"), ctx.get("capture")
    if not trace or not cap or trace["busy_s"] <= 0 or not cap.get(
            "renders"):
        return None
    if not any(key.partition("{")[0] == PIXELS for key in ctx["m1"]):
        return None
    renders = delta(ctx["m0"], ctx["m1"], RENDERS)
    if renders <= 0:
        return None
    mean_px = delta(ctx["m0"], ctx["m1"], PIXELS) / renders
    cfg = ctx["config"]
    n_bytes = work.render_bytes(cfg["channels"], mean_px, 1,
                                cfg["itemsize"], ctx["mean_body_bytes"])
    n_ops = work.render_ops(cfg["channels"], mean_px, 1)
    least, bound = work.least_seconds(ctx["peak"], n_bytes, n_ops)
    ctx.setdefault("notes", {})["roofline_bound"] = bound
    return 100.0 * least * cap["renders"] / trace["busy_s"]
