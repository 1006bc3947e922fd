"""Mean duration of one server span over the window: growth of its
``imageregion_span_ms_sum`` over growth of its ``imageregion_span_count``
between the window's first and last ``/metrics``.  Nothing when the
span did not fire in the window."""

from benchmark.prom import delta


def read(ctx: dict, span: str):
    count = delta(ctx["m0"], ctx["m1"], "imageregion_span_count", span=span)
    if count <= 0:
        return None
    return delta(ctx["m0"], ctx["m1"], "imageregion_span_ms_sum",
                 span=span) / count
