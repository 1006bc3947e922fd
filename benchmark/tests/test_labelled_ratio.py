"""``labelled_ratio`` on two canned ``/metrics`` texts, as the new
per-layer metrics' files call it."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
sys.path.insert(0, os.path.dirname(BENCH_DIR))

from benchmark import prom                                 # noqa: E402
from benchmark.readers import labelled_ratio               # noqa: E402

BEFORE = """\
imageregion_profile_captures_total 0
imageregion_profile_busy_ms_total 0.0
imageregion_profile_traced_ms_total 0.0
imageregion_profile_renders_total 0
"""
AFTER = """\
imageregion_profile_captures_total 1
imageregion_profile_busy_ms_total 2950.0
imageregion_profile_traced_ms_total 3000.0
imageregion_profile_renders_total 150
imageregion_profile_device_ms_total{stage="wire.sparse_pack"} 300.0
imageregion_profile_device_ms_total{stage="wire.sparse_pack.scatter"} 900.0
imageregion_profile_device_ms_total{stage="wire.sparse_pack.bits"} 150.0
imageregion_profile_device_ms_total{stage="wire.compact_rows"} 1050.0
imageregion_profile_device_ms_total{stage="render"} 200.0
imageregion_profile_device_ms_total{stage="unnamed"} 59.0
imageregion_profile_idle_ms_total{during="device.wait"} 30.0
imageregion_profile_idle_ms_total{during="unattributed"} 5.0
imageregion_profile_idle_ms_total{during="no_group"} 15.0
"""


def metric_args(name: str) -> dict:
    with open(os.path.join(BENCH_DIR, "layer_metrics",
                           name + ".json")) as f:
        spec = json.load(f)
    assert spec["reader"] == "labelled_ratio"
    return spec["args"]


@pytest.fixture
def ctx():
    return {"m0": prom.parse_metrics(BEFORE),
            "m1": prom.parse_metrics(AFTER)}


@pytest.mark.parametrize("name,value", [
    ("wire_pack_device_ms", (300.0 + 900.0 + 150.0) / 150),
    ("wire_compact_device_ms", 7.0),
    ("render_dct_device_ms", 200.0 / 150),
    ("unnamed_device_share", 2.0),
    ("idle_unattributed_share", 10.0),
])
def test_the_new_metrics_read_the_labelled_counters(ctx, name, value):
    assert labelled_ratio.read(ctx, **metric_args(name)) \
        == pytest.approx(value)


def test_a_stage_that_never_ran_reads_zero_not_nothing(ctx):
    # The denominator moved; the numerator's series is not there.
    assert labelled_ratio.read(ctx, **metric_args("unpack_device_ms")) \
        == 0.0


@pytest.mark.parametrize("name", [
    "wire_pack_device_ms", "unnamed_device_share",
    "idle_unattributed_share"])
def test_nothing_where_the_denominator_stood_still(ctx, name):
    """A server without the counters (the parent), a capture with no
    device plane (the CPU rehearsal), or no capture at all."""
    still = {"m0": ctx["m0"], "m1": ctx["m0"]}
    assert labelled_ratio.read(still, **metric_args(name)) is None
    none = {"m0": {}, "m1": {}}
    assert labelled_ratio.read(none, **metric_args(name)) is None
