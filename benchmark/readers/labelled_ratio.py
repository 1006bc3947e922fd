"""``counter_ratio`` whose terms carry labels: growth of some labelled
series over growth of others, over the window (``percent``: times 100).
A term is ``{"family": ..., "labels": {...}}``; one with no labels sums
every series of its family.  Nothing when the denominator did not move
(a server without the family, or a window in which it stood still)."""

from benchmark.prom import delta


def read(ctx: dict, numerator: list, denominator: list,
         percent: bool = False):
    def grown(terms: list) -> float:
        return sum(delta(ctx["m0"], ctx["m1"], t["family"],
                         **t.get("labels", {})) for t in terms)

    den = grown(denominator)
    if den <= 0:
        return None
    return (100.0 if percent else 1.0) * grown(numerator) / den
