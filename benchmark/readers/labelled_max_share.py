"""The largest share one label value takes of a counter family's growth
over the window, in %: ``imageregion_device_renders_total{device=...}``
read as the busiest chip's share of the renders (25 on four chips that
split them evenly, 100 where one chip did everything).  Nothing when
the server does not export the family (a program from before it) or
when the family did not grow."""

import re

from benchmark.prom import delta


def read(ctx: dict, family: str, label: str):
    pattern = re.compile(r'(?:^|,)' + re.escape(label) + r'="([^"]*)"')
    values = set()
    for key in ctx["m1"]:
        name, _, rest = key.partition("{")
        found = pattern.search(rest) if name == family else None
        if found:
            values.add(found.group(1))
    grown = {v: delta(ctx["m0"], ctx["m1"], family, **{label: v})
             for v in values}
    total = sum(grown.values())
    if total <= 0:
        return None
    return 100.0 * max(grown.values()) / total
