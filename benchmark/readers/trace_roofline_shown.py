"""``trace_roofline`` for a deployment whose requests show a SUBSET of
the stored channels: the least time the chip could take for the renders
finished inside the traced window over the time it was busy in it, with
a render's bytes and operations counted at the shown channels and not
at the stored ones, which nobody reads.  The count is the mean of the
configuration's ``shown`` (the counts the cell's traffic alternates
between, step by step, so the mean over a window's requests is theirs
to within one step in hundreds).  Everything else, the counting of the
renders included, is ``trace_roofline``'s.  Nothing where that reads
nothing, or for a configuration that states no ``shown``: never 0."""

import statistics

from benchmark.readers import trace_roofline


def read(ctx: dict):
    cfg = ctx["config"]
    if not cfg.get("shown"):
        return None
    ctx.setdefault("notes", {})         # shared with the copy below
    return trace_roofline.read(dict(ctx, config=dict(
        cfg, channels=statistics.fmean(cfg["shown"]))))
