"""Growth of some counters over growth of others, over the window
(``percent``: times 100).  Nothing when the denominator did not move."""

from benchmark.prom import delta


def read(ctx: dict, numerator: list, denominator: list,
         percent: bool = False):
    num = sum(delta(ctx["m0"], ctx["m1"], f) for f in numerator)
    den = sum(delta(ctx["m0"], ctx["m1"], f) for f in denominator)
    if den <= 0:
        return None
    return (100.0 if percent else 1.0) * num / den
