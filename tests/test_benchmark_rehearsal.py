"""``benchmark/tests``' users of the ``rehearsal_root`` fixture, run on
the cells the harness had before PR 28 (``tests/bench_rehearsal.py``
says why they run from here): ``BENCHMARK.json`` against its files,
``benchmark/run.py`` end to end and traced through each cell at 64^2 /
128^2 on the CPU, the two planted faults and the controls, which are
the proof that ``correct`` can come out false."""

import inspect
import importlib
import json
import os

import pytest

from bench_rehearsal import (FIRST_CELL, ONE_DEVICE, PER_CELL, REPO,
                             build_rehearsal, load)

rehearsal = load("test_rehearsal")
file_tests = load("test_benchmark_file")


@pytest.fixture(scope="module")
def rehearsal_root(tmp_path_factory):
    return build_rehearsal(tmp_path_factory)


@pytest.fixture(autouse=True)
def one_device(monkeypatch):
    monkeypatch.setenv("XLA_FLAGS", ONE_DEVICE)


def test_every_rehearsal_test_of_the_harness_is_run_from_here():
    """A test added to ``test_rehearsal.py`` has to be listed in
    ``bench_rehearsal`` too, or it would run nowhere."""
    theirs = {name for name, fn in inspect.getmembers(
        rehearsal, inspect.isfunction) if name.startswith("test_")}
    assert theirs == set(PER_CELL) | set(FIRST_CELL)
    assert {name for name, fn in inspect.getmembers(
        file_tests, inspect.isfunction) if name.startswith("test_")
        and "bench" in inspect.signature(fn).parameters} == {
        "test_keys_names_and_limits", "test_every_name_finds_its_files"}


@pytest.mark.parametrize("which", ["committed", "rehearsal"])
@pytest.mark.parametrize("check", ["test_keys_names_and_limits",
                                   "test_every_name_finds_its_files"])
def test_benchmark_file(rehearsal_root, which, check):
    root = REPO if which == "committed" else rehearsal_root
    traffic = os.path.join(
        REPO, "benchmark", "traffic") if root is REPO else os.path.join(
        root, "traffic")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    getattr(file_tests, check)((bench, root, traffic))


def test_lane_hold_ms_is_added_at_the_end_and_reads_the_lanes_span():
    """PR 31's per-layer metric: a list entry after everything the
    benchmark had then (PR 32 appended three, PR 33 one), in every
    cell, and a file that hands the span ``batcher.laneHold`` to the
    reader the other span means use."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = {m["name"]: m for m in bench["per_layer"]}["lane_hold_ms"]
    assert entry == {
        "name": "lane_hold_ms", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "batcher",
        "moves": "renders_per_s",
        "workloads": [w["name"] for w in bench["workloads"]]}
    with open(os.path.join(REPO, "benchmark", "layer_metrics",
                           "lane_hold_ms.json")) as f:
        spec = json.load(f)
    assert spec == {
        "name": "lane_hold_ms", "layer": "batcher", "unit": "ms",
        "moves": "renders_per_s", "source": "program_span",
        "reader": "span_mean", "args": {"span": "batcher.laneHold"}}
    # The span is the batcher's own, recorded by the gate itself.
    from omero_ms_image_region_tpu.server.batcher import BatchingRenderer
    from omero_ms_image_region_tpu.utils.stopwatch import REGISTRY

    def holds():
        return REGISTRY.snapshot().get("batcher.laneHold",
                                       {}).get("count", 0)

    before = holds()
    with BatchingRenderer()._lane():
        assert holds() == before        # recorded at release
    assert holds() == before + 1


def test_plane_stack_share_is_added_at_the_end_and_reads_the_counter():
    """PR 33's per-layer metric: the list's last entry when it came
    (later PRs append after it), in every cell; a
    file that hands ``imageregion_batcher_group_stacks_total`` to the
    reader ``host_route_share`` uses; nothing from a server without
    the family (the parent), never 0 and never a raise."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = [m["name"] for m in bench["per_layer"]]
    assert names.index("plane_stack_share") == names.index(
        "channel_stack_ms") + 1
    assert bench["per_layer"][names.index("plane_stack_share")] == {
        "name": "plane_stack_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "staging",
        "moves": "renders_per_s",
        # Every cell of its time; the four-chip cell came after and is
        # not on it, the cold pan after that and is.
        "workloads": [w["name"] for w in bench["workloads"]][:7]
        + ["wsi4-u16-t1024x24.coldpan"]}
    with open(os.path.join(REPO, "benchmark", "layer_metrics",
                           "plane_stack_share.json")) as f:
        spec = json.load(f)
    family = "imageregion_batcher_group_stacks_total"
    assert spec == {
        "name": "plane_stack_share", "layer": "staging", "unit": "%",
        "moves": "renders_per_s", "source": "program_counter",
        "reader": "labelled_ratio",
        "args": {"numerator": [{"family": family,
                                "labels": {"path": "planes"}}],
                 "denominator": [{"family": family}], "percent": True}}
    reader = importlib.import_module("benchmark.readers.labelled_ratio")

    def read(m0, m1):
        return reader.read({"m0": m0, "m1": m1}, **spec["args"])

    parent = {"imageregion_batches_dispatched": 10.0,
              'imageregion_span_count{span="batcher.stage"}': 10.0}
    assert read({}, parent) is None
    assert read(parent, {k: 2 * v for k, v in parent.items()}) is None
    m0 = {family + '{path="planes"}': 4.0, family + '{path="arrays"}': 1.0}
    m1 = {family + '{path="planes"}': 34.0,
          family + '{path="arrays"}': 11.0}
    assert read(m0, m1) == pytest.approx(75.0)
    assert read(m0, m0) is None          # no group in the window
    # The series are the batcher's own, on /metrics from its first
    # scrape on (both labels, so a share is never read from a missing
    # one).
    from omero_ms_image_region_tpu.server.batcher import BatchingRenderer
    from omero_ms_image_region_tpu.utils.telemetry import (
        device_metric_lines)

    class Services:
        renderer = BatchingRenderer()

    text = "\n".join(device_metric_lines(Services()))
    assert family + '{path="planes"} 0' in text
    assert family + '{path="arrays"} 0' in text


@pytest.mark.parametrize("cell", rehearsal.CELLS)
@pytest.mark.parametrize("name", PER_CELL)
def test_a_cell_the_harness_had(tmp_path, rehearsal_root, name, cell):
    getattr(rehearsal, name)(tmp_path, rehearsal_root, cell)


@pytest.mark.parametrize("name", FIRST_CELL)
def test_the_harness_first_cell(tmp_path, rehearsal_root, name):
    getattr(rehearsal, name)(tmp_path, rehearsal_root)
