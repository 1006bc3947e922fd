"""``trace_roofline`` for a cell whose renders run on several chips:
the least time the chips could take for the renders finished inside the
traced window over the time ALL of them were busy in it.
``trace.reduce`` averages busy time over the device planes that ran an
operation (``chips``), while the renders counted are the whole fleet's,
so the plain reader would read up to ``chips`` times high; here the
busy time is the average times the chips.  On one chip this is
``trace_roofline``.  Nothing where that reads nothing: never 0."""

from benchmark.readers import trace_roofline


def read(ctx: dict):
    trace = ctx.get("trace")
    if not trace:
        return None
    ctx.setdefault("notes", {})         # shared with the copy below
    return trace_roofline.read(dict(ctx, trace=dict(
        trace, busy_s=trace["busy_s"] * trace["chips"])))
