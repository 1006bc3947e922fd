"""Least time the chip could take for the renders finished inside the
traced window (``work.least_seconds``: the larger of bytes over peak
bytes/s and operations over peak op/s) over the time the chip was busy
in it.  Renders finished = the answers the client had back between the
first traced operation's start and the last one's end, on the machine's
wall clock, which the profiler stamps its session with: one interval,
counted, no rate from another span.  Nothing without a trace, without
that stamp, without a render in the interval, or without busy time:
never 0."""

from benchmark import work


def read(ctx: dict):
    trace, cap = ctx.get("trace"), ctx.get("capture")
    if not trace or not cap or trace["busy_s"] <= 0:
        return None
    renders = cap.get("renders")
    if not renders:
        return None
    cfg = ctx["config"]
    n_bytes = work.render_bytes(cfg["channels"], cfg["tile_edge"],
                                cfg["tile_edge"], cfg["itemsize"],
                                ctx["mean_body_bytes"])
    n_ops = work.render_ops(cfg["channels"], cfg["tile_edge"],
                            cfg["tile_edge"])
    least, bound = work.least_seconds(ctx["peak"], n_bytes, n_ops)
    ctx.setdefault("notes", {})["roofline_bound"] = bound
    return 100.0 * least * renders / trace["busy_s"]
