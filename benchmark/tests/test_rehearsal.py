"""CPU rehearsal of ``run.py`` at 64^2 tiles through the same code, real
server child and real HTTP (as ``tests/test_chip_smoke.py`` rehearses
``chip_smoke.py``).  Sizes, the benchmark file and the expected platform
are patched HERE, in a wrapper process; the command has no option for
them.  A rehearsal is a contract check: it gives counts, never speeds.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
CELLS = ["tiny4-u16-t64.rewindow", "tinyplate3-u16-p128.scan"]

_WRAPPER = r"""
import os, sys
sys.path.insert(0, {repo!r})
import benchmark.run as br
br.BENCH_FILE = {rehearsal!r} + "/BENCHMARK.json"
br.BENCH_ROOT = {rehearsal!r}
br.TRAFFIC_DIR = {rehearsal!r} + "/traffic"
{patch}
controls = None
if {controls!r}:
    from benchmark.control import CONTROLS as controls
try:
    code = br.main({argv!r}, controls=controls)
finally:
    print("PARENT_IMPORTED_JAX=%s" % ("jax" in sys.modules),
          file=sys.stderr, flush=True)
sys.exit(code)
"""

_FAULTY_CHILD = r"""
_Child = br.Child
class FaultyChild(_Child):
    def __init__(self, name, argv, workdir, env=None):
        env = dict(env or {{}})
        env["PYTHONPATH"] = {fault!r} + os.pathsep + {repo!r}
        env["BENCH_FAULT_FLAG"] = os.path.join(workdir, "window_is_open")
        self.flag = env["BENCH_FAULT_FLAG"]
        FaultyChild.last = self
        super().__init__(name, argv, workdir, env)
br.Child = FaultyChild
# A fault that waits for the window is told when it opens.
import benchmark.traffic_kinds.closed_loop as _kind
_window = _kind.window
def _flagged_window(*a, **k):
    open(FaultyChild.last.flag, "w").close()
    return _window(*a, **k)
_kind.window = _flagged_window
"""


def _run(tmp_path, rehearsal, cell, patch='br.EXPECT_PLATFORM = "cpu"',
         trace=0, controls=False, seed=2500000123):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    env["TMPDIR"] = str(tmp_path)
    env["BENCH_RUN"] = "ignored"
    argv = ["--workload", cell, "--seed", str(seed), "--seconds", "2",
            "--trace", str(trace)]
    proc = subprocess.run(
        [sys.executable, "-c", _WRAPPER.format(
            repo=REPO, rehearsal=rehearsal, patch=patch, argv=argv,
            controls=controls)],
        env=env, cwd=str(tmp_path), capture_output=True, text=True,
        timeout=600)
    return proc, [ln for ln in proc.stdout.splitlines() if ln.strip()]


def _result(proc, lines):
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    assert "PARENT_IMPORTED_JAX" in proc.stderr
    result = json.loads(lines[-1])
    assert list(result)[:5] == ["correct", "attempted", "failed",
                                "metrics", "device"]
    assert list(result)[-1] == "compared"
    return result


def test_wrong_platform_fails_without_a_result_line(tmp_path,
                                                    rehearsal_root):
    """The sandbox's own case: the server is on the CPU, the benchmark
    expects a TPU.  Fails at the first /readyz, in seconds."""
    proc, lines = _run(tmp_path, rehearsal_root, CELLS[0], patch="")
    assert proc.returncode != 0
    assert "expected 'tpu'" in proc.stderr
    assert not [ln for ln in lines if '"correct"' in ln]
    assert "PARENT_IMPORTED_JAX=False" in proc.stderr
    assert not [p for p in os.listdir(tmp_path)
                if p.startswith("imageregion_bench_")]


@pytest.mark.parametrize("cell", CELLS)
def test_end_to_end_line(tmp_path, rehearsal_root, cell):
    bench = json.load(open(os.path.join(rehearsal_root, "BENCHMARK.json")))
    proc, lines = _run(tmp_path, rehearsal_root, cell)
    result = _result(proc, lines)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 10
    assert sorted(result["metrics"]) == sorted(
        m["name"] for m in bench["end_to_end"])
    for name, m in result["metrics"].items():
        assert m["value"] > 0 and m["unit"]
    assert result["device"]["platform"] == "cpu"
    assert "PARENT_IMPORTED_JAX=False" in proc.stderr
    # Each number compared, beside its limit, ends standard error.
    tail = proc.stderr.strip().splitlines()[-5:-1]
    assert [ln.split(":")[0] for ln in tail] == [
        "compared qtable_diff", "compared excess_err", "compared sampled",
        "compared unanswered"]
    assert result["compared"]["unanswered"] == {"value": 0, "limit": 0}
    assert not [p for p in os.listdir(tmp_path)
                if p.startswith("imageregion_bench_")]


@pytest.mark.parametrize("cell", CELLS)
def test_traced_line_reads_the_layer_metrics(tmp_path, rehearsal_root,
                                             cell):
    """Each span a cell's metric reads fires in that cell.  On the CPU
    the capture has a host plane and no device plane, so the two trace
    metrics read nothing and are left out (never 0)."""
    bench = json.load(open(os.path.join(rehearsal_root, "BENCHMARK.json")))
    proc, lines = _run(tmp_path, rehearsal_root, cell, trace=1)
    result = _result(proc, lines)
    assert result["correct"] is True
    want = {m["name"] for m in bench["per_layer"]
            if cell in m.get("workloads", [cell])
            and m["source"] != "device_trace"}
    assert set(result["metrics"]) == want
    assert "busy_s" not in result["device"]
    # The parent read the capture with JAX only after the child exited.
    assert "PARENT_IMPORTED_JAX=True" in proc.stderr
    assert "events on device planes []" in proc.stdout
    # The profiler's own stamp of the session's start, read through
    # this installation's ProfileData.
    assert "'profile_start_time': 1" in proc.stdout


def test_altered_answer_comes_out_not_correct(tmp_path, rehearsal_root):
    """The rest of a run with the timed path broken underneath: the
    server renders every request's first channel under another window
    than it was asked for (faults/altered_answer)."""
    fault = os.path.join(HERE, "faults", "altered_answer")
    proc, lines = _run(
        tmp_path, rehearsal_root, CELLS[0],
        patch='br.EXPECT_PLATFORM = "cpu"\n'
              + _FAULTY_CHILD.format(fault=fault, repo=REPO))
    result = _result(proc, lines)
    assert result["correct"] is False
    assert result["failed"] == result["compared"]["sampled"]["value"]
    assert result["compared"]["unanswered"]["value"] == 0
    assert result["compared"]["excess_err"]["value"] \
        > 10 * result["compared"]["excess_err"]["limit"]
    assert result["compared"]["qtable_diff"]["value"] == 0


@pytest.mark.parametrize("cell", CELLS)
def test_part_of_a_group_shed_comes_out_not_correct(tmp_path,
                                                    rehearsal_root, cell):
    """Half of the batch left out: from the window's first request the
    server sheds every other request with its own 503
    (faults/shed_part_of_group).  Every answer that does come is right,
    and the tail is better for it; ``correct`` has to be false."""
    fault = os.path.join(HERE, "faults", "shed_part_of_group")
    proc, lines = _run(
        tmp_path, rehearsal_root, cell,
        patch='br.EXPECT_PLATFORM = "cpu"\n'
              + _FAULTY_CHILD.format(fault=fault, repo=REPO))
    result = _result(proc, lines)
    assert result["correct"] is False
    unanswered = result["compared"]["unanswered"]
    assert unanswered["limit"] == 0
    assert unanswered["value"] >= result["attempted"] // 2 - 1
    assert result["failed"] == unanswered["value"]
    # Nothing else gives it away: the bodies that came are right.
    assert result["compared"]["excess_err"]["value"] \
        <= result["compared"]["excess_err"]["limit"]
    assert "UNANSWERED" in proc.stdout and "503" in proc.stdout


def test_controls_come_out_not_correct(tmp_path, rehearsal_root):
    proc, lines = _run(tmp_path, rehearsal_root, CELLS[0], controls=True)
    result = _result(proc, lines)
    assert result["correct"] is True
    assert sorted(result["controls"]) == ["bits8", "bits8_q80", "q80"]
    # The control of record, and the quality step alone (exact tables).
    # ``bits8`` alone is a diagnostic: at 64^2 its least reading can
    # fall under the limit, at the cells' sizes it does not (PERF.md).
    for name in ("bits8_q80", "q80"):
        assert result["controls"][name]["correct"] is False, name
