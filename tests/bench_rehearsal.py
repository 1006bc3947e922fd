"""The benchmark harness's own CPU checks (``benchmark/tests``), driven
from tier-1 over a rehearsal that holds EVERY configuration of
``BENCHMARK.json``.

``benchmark/tests/conftest.py`` builds the rehearsal from
``rehearsal/overrides.json``, which has no entry for the deployment
PR 28 added (``stock4-u16-t256``): a PR may add files under
``benchmark/`` and edit none, so the entries sit beside it in
``overrides_stock4-u16-t256.json`` and every user of the harness's
``rehearsal_root`` fixture errors when ``benchmark/tests`` is run by
itself, until a ``benchmark`` PR merges the two files.  Until then the
users run here: the harness's builder, its test functions and its
planted faults are loaded by path and called with the merged rehearsal,
so nothing of them is copied (``tests/test_benchmark_rehearsal.py``:
the cells the harness had; ``tests/test_benchmark_stock_cell.py``: the
new one).  A rehearsal gives counts, never speeds.
"""

import importlib.util
import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_TESTS = os.path.join(REPO, "benchmark", "tests")
STOCK_CELL, TINY_STOCK_CELL = "stock4-u16-t256.pan", "tinystock4-u16-t64.pan"

# The harness's rehearsal tests (benchmark/tests/test_rehearsal.py):
# those it runs once a cell, and those it runs on its first cell only
# (``CELLS[0]``, which a caller may point elsewhere).
PER_CELL = ("test_end_to_end_line",
            "test_traced_line_reads_the_layer_metrics",
            "test_part_of_a_group_shed_comes_out_not_correct")
FIRST_CELL = ("test_wrong_platform_fails_without_a_result_line",
              "test_altered_answer_comes_out_not_correct",
              "test_controls_come_out_not_correct")


def load(name: str):
    """A module of ``benchmark/tests`` by path (the directory is no
    package, and its ``conftest`` must not shadow this suite's)."""
    spec = importlib.util.spec_from_file_location(
        f"_bench_tests_{name}", os.path.join(BENCH_TESTS, name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def build_rehearsal(tmp_path_factory) -> str:
    """``conftest.build_rehearsal`` over a copy of ``rehearsal/`` whose
    ``overrides.json`` has the new deployment's entries merged in."""
    staged = tmp_path_factory.mktemp("staged")
    shutil.copytree(os.path.join(BENCH_TESTS, "rehearsal"),
                    str(staged / "rehearsal"))
    with open(staged / "rehearsal" / "overrides.json") as f:
        over = json.load(f)
    with open(staged / "rehearsal"
              / "overrides_stock4-u16-t256.json") as f:
        more = json.load(f)
    for key in ("configs", "traffic"):
        assert not set(over[key]) & set(more[key])
        over[key].update(more[key])
    with open(staged / "rehearsal" / "overrides.json", "w") as f:
        json.dump(over, f)
    builder = load("conftest")
    builder.HERE = str(staged)
    return builder.build_rehearsal(
        str(tmp_path_factory.mktemp("rehearsal")))


ONE_DEVICE = "--xla_force_host_platform_device_count=1"
"""``XLA_FLAGS`` for a rehearsal's server child: a cell asks for one
chip, this suite's conftest gives the CPU backend eight virtual
devices, and the child inherits the flag."""
