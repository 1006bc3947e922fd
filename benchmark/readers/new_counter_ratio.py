"""``counter_ratio`` for a numerator this PR's program adds: growth of
some counters over growth of others, over the window.  Nothing when the
server exports none of the numerator's families at all (a program from
before the counter: a ratio of 0 would say that nothing was counted,
where nothing could be), and nothing when the denominator did not
move."""

from benchmark.readers import counter_ratio


def read(ctx: dict, numerator: list, denominator: list):
    exported = {key.partition("{")[0] for key in ctx["m1"]}
    if not exported & set(numerator):
        return None
    return counter_ratio.read(ctx, numerator, denominator)
