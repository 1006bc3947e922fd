"""Growth of one counter family over the window.  Nothing when the
server does not export the family at all."""

from benchmark.prom import series


def read(ctx: dict, family: str):
    if not any(k.partition("{")[0] == family for k in ctx["m1"]):
        return None
    return series(ctx["m1"], family) - series(ctx["m0"], family)
