"""One recorded TPU capture (``data/rewindow_1000ms.xplane.pb.gz``: one
second of ``wsi4-u16-t1024.rewindow`` on a v5e, taken by the server's
own ``/debug/profile`` without the Python tracer and gzipped: 2 MB as
written, two thirds of it the programs' ``HloProto``s that carry the
stage names; a second and not 300 ms, because a group lives 450 ms and
its span is written when it ends; PERF.md section 6, PR 26) read by
both reductions: the benchmark's (``benchmark/trace.py``, which
``device_idle_share`` and ``render_path_roofline`` come from) and the
program's (``utils/profile_summary.py``, which the
``imageregion_profile_*`` counters come from)."""

import gzip
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark import trace                                # noqa: E402
from omero_ms_image_region_tpu.utils import (              # noqa: E402
    profile_summary)

RECORDED = os.path.join(HERE, "data", "rewindow_1000ms.xplane.pb.gz")


@pytest.fixture(scope="module")
def capture(tmp_path_factory) -> str:
    path = str(tmp_path_factory.mktemp("capture") / "recorded.xplane.pb")
    with gzip.open(RECORDED, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return path


@pytest.fixture(scope="module")
def both(capture):
    rows, session = trace.read_xplane(capture)
    summary = profile_summary.summarize(
        *profile_summary.read_capture(capture))
    return trace.reduce(rows), session, summary


def test_the_capture_fits_and_has_no_python_tracer_event(capture):
    assert os.path.getsize(RECORDED) < 1_000_000
    rows, _ = trace.read_xplane(capture, plane_prefix="/host:")
    assert rows
    assert not [r for r in rows if r[2].startswith("$")]


def test_both_reductions_agree_on_busy_and_traced_time(both):
    reduced, session, summary = both
    assert reduced["chips"] == 1 and list(summary["planes"]) == [
        "/device:TPU:0"]
    assert summary["busy_ms"] == pytest.approx(
        reduced["busy_s"] * 1e3, rel=0.01)
    assert summary["traced_ms"] == pytest.approx(
        reduced["window_s"] * 1e3, rel=0.01)
    plane = summary["planes"]["/device:TPU:0"]
    assert (plane["first_ns"], plane["last_ns"]) == (
        reduced["first_ns"], reduced["last_ns"])
    assert trace.SESSION_START in session


def test_the_stages_sum_to_busy_and_most_of_it_has_a_name(both):
    _, _, summary = both
    assert sum(summary["device_ms"].values()) == pytest.approx(
        summary["busy_ms"], rel=1e-6)
    assert set(summary["device_ms"]) <= set(profile_summary.STAGES) | {
        profile_summary.UNNAMED}
    assert summary["device_ms"].get(profile_summary.UNNAMED, 0.0) \
        < 0.10 * summary["busy_ms"]
    # The served program's stages are all there.
    for stage in ("render", "jpeg.dct_quant", "wire.sparse_pack.scatter",
                  "wire.sparse_pack.bits", "wire.compact_rows"):
        assert summary["device_ms"][stage] > 0, stage


def test_idle_is_split_without_remainder_and_renders_are_counted(both):
    _, _, summary = both
    assert sum(summary["idle_ms"].values()) == pytest.approx(
        summary["traced_ms"] - summary["busy_ms"], abs=1e-6)
    # What the client counted in the same interval of that run.
    assert summary["renders"] == 46
    spans = summary["host_spans"]
    for name in ("batcher.group", "batcher.laneWait", "device.dispatch",
                 "wire.fetch", "device.wait", "wire.d2h",
                 "jfif.encodeBatch"):
        assert spans[name]["count"] > 0, name
