"""The benchmark harness's own CPU checks (``benchmark/tests``), driven
from tier-1 over a rehearsal that holds EVERY configuration of
``BENCHMARK.json``.

``benchmark/tests/conftest.py`` builds the rehearsal from
``rehearsal/overrides.json``, which has no entry for the deployments
added after it (``stock4-u16-t256``, ``cycif40-u16-t1024``,
``jump5-u16-p1080``, ``fleet4-wsi4-u16-t1024``,
``wsi4-u16-t1024x24``): a change that is not one of the harness itself
adds files under ``benchmark/`` and edits none, so the entries sit
beside it in ``overrides_<deployment>.json`` and every user
of the harness's ``rehearsal_root`` fixture errors when
``benchmark/tests`` is run by itself, until a ``benchmark`` PR merges
the files.  Until then the users run here: the harness's builder, its
test functions and its planted faults are loaded by path and called
with the merged rehearsal, so nothing of them is copied
(``tests/test_benchmark_rehearsal.py``: the cells the harness had;
``tests/test_benchmark_stock_cell.py``,
``tests/test_benchmark_toggle_cell.py``,
``tests/test_benchmark_jump_cell.py``,
``tests/test_benchmark_fleet_cell.py`` and
``tests/test_benchmark_coldpan_cell.py``: the new ones).  A rehearsal
gives counts, never speeds.
"""

import glob
import importlib.util
import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_TESTS = os.path.join(REPO, "benchmark", "tests")
STOCK_CELL, TINY_STOCK_CELL = "stock4-u16-t256.pan", "tinystock4-u16-t64.pan"
TOGGLE_CELL = "cycif40-u16-t1024.toggle"
TINY_TOGGLE_CELL = "tinycycif8-u16-t64.toggle"
SINGLE_CELL = "stock4-u16-t256.single"
TINY_SINGLE_CELL = "tinystock4-u16-t64.single"
JUMP_CELL, TINY_JUMP_CELL = "jump5-u16-p1080.scan", "tinyjump5-u16-p120.scan"
SINGLE1024_CELL = "wsi4-u16-t1024.single"
TINY_SINGLE1024_CELL = "tiny4-u16-t64.single"
COLDPAN_CELL = "wsi4-u16-t1024x24.coldpan"
TINY_COLDPAN_CELL = "tinywsi4x24-u16-t64.coldpan"

# The harness's rehearsal tests (benchmark/tests/test_rehearsal.py):
# those it runs once a cell, and those it runs on its first cell only
# (``CELLS[0]``, which a caller may point elsewhere).
PER_CELL = ("test_end_to_end_line",
            "test_traced_line_reads_the_layer_metrics",
            "test_part_of_a_group_shed_comes_out_not_correct")
FIRST_CELL = ("test_wrong_platform_fails_without_a_result_line",
              "test_altered_answer_comes_out_not_correct",
              "test_controls_come_out_not_correct")


# What tier-1 changes of the rehearsal's traffic beyond the override
# files, so that a run is steady on a CPU it shares with five other
# xdist workers.  The harness's window is 2 s (``test_rehearsal._run``)
# and ``run.py`` fails a run in which no request came back inside it.
# ``pan``'s 16 viewers x 6 connections put 96 requests in flight at
# once: alone they take 0.8 s each, under six concurrent rehearsals
# 2.7-2.9 s, and 8-9 of 104 came back in time (PR 32's reproduction of
# the driver's failure of ``test_rehearsal_end_to_end_line``); a little
# more load and none does.  Two connections a viewer (32 in flight:
# 39-60 of 71-92 came back under the same load, and groups still pass
# ``max-batch``, which 16 in flight did not always do: 3.68 a group
# in a whole run of the suite).
STEADY = {"pan": {"connections_per_viewer": 2}}


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
    ``overrides.json`` has the entries of every
    ``overrides_<deployment>.json`` beside it merged in."""
    staged = tmp_path_factory.mktemp("staged")
    shutil.copytree(os.path.join(BENCH_TESTS, "rehearsal"),
                    str(staged / "rehearsal"))
    with open(staged / "rehearsal" / "overrides.json") as f:
        over = json.load(f)
    for path in sorted(glob.glob(
            str(staged / "rehearsal" / "overrides_*.json"))):
        with open(path) as f:
            more = json.load(f)
        for key in ("configs", "traffic"):
            assert not set(over[key]) & set(more[key]), path
            over[key].update(more[key])
    # ``conftest`` renames configurations by substring, in the order it
    # meets them: a name that holds another (``wsi4-u16-t1024x24``,
    # ``fleet4-wsi4-u16-t1024``) has to go before the one it holds.
    over["configs"] = dict(sorted(over["configs"].items(),
                                  key=lambda item: -len(item[0])))
    for mix, sets in STEADY.items():
        over["traffic"][mix].update(sets)
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
