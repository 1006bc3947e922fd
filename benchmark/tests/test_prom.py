"""The Prometheus delta readers on two canned ``/metrics`` texts."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import prom                                 # noqa: E402
from benchmark.readers import (counter_delta, counter_ratio,  # noqa: E402
                               span_mean)

FIRST = """\
# HELP imageregion_span_count spans
# TYPE imageregion_span_count counter
imageregion_span_count{span="wire.fetch"} 10
imageregion_span_ms_sum{span="wire.fetch"} 500.0
imageregion_span_count{span="batcher.stage"} 4
imageregion_span_ms_sum{span="batcher.stage"} 8.0
imageregion_span_count{span="wire.fetch",process="sidecar"} 1
imageregion_span_ms_sum{span="wire.fetch",process="sidecar"} 10.0
imageregion_rawcache_hits 100
imageregion_rawcache_misses 20
imageregion_tiles_rendered 120
imageregion_batches_dispatched 30
imageregion_compile_events_total 45
imageregion_build_info{version="1"} NaN
"""
SECOND = """\
imageregion_span_count{span="wire.fetch"} 30
imageregion_span_ms_sum{span="wire.fetch"} 2500.0
imageregion_span_count{span="batcher.stage"} 4
imageregion_span_ms_sum{span="batcher.stage"} 8.0
imageregion_span_count{span="wire.fetch",process="sidecar"} 1
imageregion_span_ms_sum{span="wire.fetch",process="sidecar"} 10.0
imageregion_rawcache_hits 190
imageregion_rawcache_misses 30
imageregion_tiles_rendered 280
imageregion_batches_dispatched 50
imageregion_compile_events_total 47
"""


@pytest.fixture
def ctx():
    return {"m0": prom.parse_metrics(FIRST),
            "m1": prom.parse_metrics(SECOND)}


def test_parse_and_series(ctx):
    assert ctx["m0"]['imageregion_span_count{span="wire.fetch"}'] == 10
    assert prom.series(ctx["m0"], "imageregion_span_count",
                       span="wire.fetch") == 11          # both processes
    assert prom.delta(ctx["m0"], ctx["m1"],
                      "imageregion_tiles_rendered") == 160


def test_span_mean_is_delta_sum_over_delta_count(ctx):
    assert span_mean.read(ctx, span="wire.fetch") == pytest.approx(100.0)
    # A span that did not fire in the window is not reported.
    assert span_mean.read(ctx, span="batcher.stage") is None
    assert span_mean.read(ctx, span="no.such.span") is None


def test_counter_ratio_and_delta(ctx):
    assert counter_ratio.read(
        ctx, numerator=["imageregion_rawcache_hits"],
        denominator=["imageregion_rawcache_hits",
                     "imageregion_rawcache_misses"],
        percent=True) == pytest.approx(90.0)
    assert counter_ratio.read(
        ctx, numerator=["imageregion_tiles_rendered"],
        denominator=["imageregion_batches_dispatched"]) == 8.0
    assert counter_ratio.read(ctx, numerator=["imageregion_tiles_rendered"],
                              denominator=["nothing_moves"]) is None
    assert counter_delta.read(
        ctx, family="imageregion_compile_events_total") == 2.0
    assert counter_delta.read(ctx, family="not_exported") is None
