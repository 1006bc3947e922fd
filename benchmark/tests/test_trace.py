"""The trace reduction's arithmetic on hand-made rows, the roofline on
known numbers, and (where it is kept beside this file) a short real TPU
capture read through ``jax.profiler.ProfileData``."""

import glob
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import trace, work                          # noqa: E402
from benchmark.readers import trace_idle, trace_roofline   # noqa: E402

TPU, OPS, MODS = "/device:TPU:0", trace.OPS_LINE, trace.MODULES_LINE
MS = 1_000_000


def rows():
    return [
        # two overlapping ops, a gap of 30 ms, one op, a gap of 10 ms, one op
        (TPU, OPS, "fusion.1", 0 * MS, 20 * MS),
        (TPU, OPS, "scatter.2", 10 * MS, 20 * MS),     # union: 0..30
        (TPU, OPS, "fusion.1", 60 * MS, 10 * MS),      # 60..70
        (TPU, OPS, "copy.3", 80 * MS, 20 * MS),        # 80..100
        (TPU, MODS, "jit_render(1)", 0 * MS, 30 * MS),
        (TPU, MODS, "jit_render(1)", 60 * MS, 40 * MS),
        (TPU, "Steps", "0", 0, 100 * MS),              # read by nothing
        ("/host:CPU", "python", "sleep", 0, 500 * MS),  # not a device
        ("/device:TPU:0 SparseCore", OPS, "x", 0, 900 * MS),  # not a chip
    ]


def test_union_and_gaps():
    assert trace.union_ns([(0, 20), (10, 30), (60, 70)]) == 40
    assert trace.union_ns([]) == 0
    assert trace.union_ns([(5, 6), (0, 10)]) == 10
    ev = trace.line_events(rows(), TPU, OPS)
    assert trace.gaps(ev) == [("before fusion.1", 0.03),
                              ("before copy.3", 0.01)]


def test_reduce_busy_window_breakdown():
    r = trace.reduce(rows())
    assert r["chips"] == 1
    assert r["window_s"] == pytest.approx(0.100)
    assert (r["first_ns"], r["last_ns"]) == (0, 100 * MS)
    assert r["busy_s"] == pytest.approx(0.060)
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(0.030)]
    assert [n for n, _ in r["device_ops"]] == ["fusion.1", "scatter.2",
                                               "copy.3"]
    assert r["device_modules"] == [["jit_render(1)", pytest.approx(0.07)]]
    assert r["idle_gaps"][0] == ["before fusion.1", pytest.approx(0.03)]
    assert trace_idle.read({"trace": r}) == pytest.approx(40.0)


def test_traced_window_on_the_callers_clock():
    """Rows count from the session's start, which the profiler stamps
    in wall-clock ns; one pair of readings maps it to the caller's."""
    r = trace.reduce([(TPU, OPS, "fusion.1", 40 * MS, 10 * MS),
                      (TPU, OPS, "fusion.1", 900 * MS, 100 * MS)])
    session = {trace.SESSION_START: 1_790_000_002_000_000_000}
    # The caller read wall clock ...000.5 s when its own clock said 70.
    lo, hi = trace.interval_on_clock(
        r, session, 1_790_000_000_500_000_000, 70.0)
    assert (lo, hi) == (pytest.approx(71.54), pytest.approx(72.5))
    assert trace.interval_on_clock(r, {}, 0, 0.0) is None


def test_two_chips_average_busy_and_share_the_window():
    two = rows() + [("/device:TPU:1", OPS, "fusion.1", 0, 100 * MS)]
    r = trace.reduce(two)
    assert r["chips"] == 2
    assert r["busy_s"] == pytest.approx((0.060 + 0.100) / 2)
    assert r["window_s"] == pytest.approx(0.100)


def test_nothing_on_a_device_reduces_to_nothing():
    host_only = [r for r in rows() if r[0] == "/host:CPU"]
    assert trace.reduce(host_only) is None
    assert trace_idle.read({"trace": None}) is None
    assert trace_roofline.read({"trace": None, "capture": None}) is None


def test_roofline_on_known_numbers():
    peak = {"hbm_bytes_per_s": 800e9, "flops_per_s": 200e12}
    # 4 x 1024^2 uint16 + 300 KB of JPEG: bytes-bound.
    n_bytes = work.render_bytes(4, 1024, 1024, 2, 300_000)
    assert n_bytes == 4 * 1024 * 1024 * 2 + 300_000
    n_ops = work.render_ops(4, 1024, 1024)
    assert n_ops == (4 * 12 + 18 + 2 + 1.5 * 32) * 1024 * 1024
    least, bound = work.least_seconds(peak, n_bytes, n_ops)
    assert bound == "bytes"
    assert least == pytest.approx(n_bytes / 800e9)
    assert work.least_seconds(peak, 1.0, 1e12)[1] == "ops"
    # 5 answers back inside a 0.1 s traced window with 0.06 s busy.
    ctx = {"trace": trace.reduce(rows()), "peak": peak,
           "capture": {"renders": 5},
           "mean_body_bytes": 300_000,
           "config": {"channels": 4, "tile_edge": 1024, "itemsize": 2}}
    want = 100.0 * least * 5 / 0.060
    assert trace_roofline.read(ctx) == pytest.approx(want)
    assert ctx["notes"]["roofline_bound"] == "bytes"
    # No render in the capture: nothing, never 0.
    ctx["capture"] = {"renders": 0}
    assert trace_roofline.read(ctx) is None
    # No session stamp in the capture: no count, nothing.
    ctx["capture"] = {"renders": None}
    assert trace_roofline.read(ctx) is None


REAL = sorted(glob.glob(os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "data", "*.xplane.pb")))


@pytest.mark.skipif(not REAL, reason="no recorded TPU capture kept here")
def test_recorded_tpu_capture_reduces():
    got = trace.reduce(trace.read_xplane(REAL[0])[0])
    assert got is not None and got["chips"] == 1
    assert 0 < got["busy_s"] <= got["window_s"]
    assert got["device_ops"] and got["device_modules"]
