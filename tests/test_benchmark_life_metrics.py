"""PR 36's fifteen per-layer metrics (a request's four phases, the
source open, a slot's three parts, the loop's lag, two idle classes:
ISSUE 36's fourteen; and the wait for a slot, which its review asked
a reader for): the entries of ``BENCHMARK.json`` against the table,
each file under ``benchmark/layer_metrics/`` through the reader it
names on hand-made ``/metrics`` pairs, and what each reads from a
server that lacks this PR's series (the parent).  Their rehearsal through
``benchmark/run.py`` is the traced cells' (``tests/test_benchmark_*``:
every host-side metric that lists a cell has to read a number in it).
"""

import importlib
import json
import os

import pytest

from bench_rehearsal import REPO

RO, BATCHER, DEVICE = "request orchestration", "batcher", "device"
SCANS = ["plate3-u16-p2048.scan", "jump5-u16-p1080.scan"]
# name: (layer, source, moves, reader, cells; None = all seven)
METRICS = {
    "request_ms": (RO, "program_span", "p50_ms", "labelled_ratio", None),
    "metadata_ms": (RO, "program_span", "renders_per_s", "span_mean",
                    None),
    "source_open_ms": (RO, "program_span", "renders_per_s", "span_mean",
                       SCANS[::-1]),
    "source_opens_per_render": (RO, "program_counter", "renders_per_s",
                                "new_counter_ratio", None),
    "in_group_ms": (BATCHER, "program_span", "p50_ms", "span_mean", None),
    "respond_ms": (RO, "program_span", "p50_ms", "span_mean", None),
    "account_ms": (RO, "program_span", "renders_per_s", "span_mean",
                   ["stock4-u16-t256.pan", "stock4-u16-t256.single",
                    "wsi4-u16-t1024.single"]),
    "loop_lag_ms": (RO, "program_span", "p50_ms", "span_mean", None),
    "slot_hold_ms": (BATCHER, "program_span", "renders_per_s",
                     "span_mean", None),
    "slot_start_ms": (BATCHER, "program_span", "renders_per_s",
                      "span_mean", None),
    "settle_lag_ms": (BATCHER, "program_span", "renders_per_s",
                      "span_mean", None),
    "group_ms": (BATCHER, "program_span", "renders_per_s", "span_mean",
                 None),
    "idle_no_group_share": (DEVICE, "device_trace", "renders_per_s",
                            "labelled_ratio", None),
    "idle_read_share": (DEVICE, "device_trace", "renders_per_s",
                        "labelled_ratio", SCANS),
    # A part of ``queue_wait_ms``, and moves what it moves.
    "slot_wait_ms": (BATCHER, "program_span", "p95_ms", "span_mean",
                     None),
}
SPAN_OF = {
    "metadata_ms": "handler.metadata",
    "source_open_ms": "PixelsService.getPixelBuffer",
    "in_group_ms": "batcher.inGroup", "respond_ms": "handler.respond",
    "account_ms": "http.account", "loop_lag_ms": "loop.lag",
    "slot_hold_ms": "batcher.slot", "slot_start_ms": "batcher.slotStart",
    "settle_lag_ms": "batcher.settleLag", "group_ms": "batcher.group",
    "slot_wait_ms": "batcher.slotWait"}


def _bench() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _spec(name: str) -> dict:
    with open(os.path.join(REPO, "benchmark", "layer_metrics",
                           name + ".json")) as f:
        return json.load(f)


def _read(name: str, m0: dict, m1: dict):
    spec = _spec(name)
    reader = importlib.import_module(
        f"benchmark.readers.{spec['reader']}")
    return reader.read({"m0": m0, "m1": m1}, **spec["args"])


def _span(span: str, count: float, total_ms: float) -> dict:
    return {f'imageregion_span_count{{span="{span}"}}': count,
            f'imageregion_span_ms_sum{{span="{span}"}}': total_ms}


def test_the_fifteen_entries_are_the_tables_appended_in_its_order():
    bench = _bench()
    # The seven cells of its time; PR 38 appended a four-chip one and
    # put it on some of these lists.
    cells = [w["name"] for w in bench["workloads"]][:7]
    # Cells appended later, on some of these lists: the four-chip one
    # and the cold pan.
    later = set(w["name"] for w in bench["workloads"][7:])
    # PR 37 appended one more behind them (``entropy_pooled_share``),
    # the four-chip cell's four, then its queue wait; the cold pan's
    # four, then ``entropy_split_share``.
    mine = [dict(m, workloads=[w for w in m["workloads"]
                               if w not in later])
            for m in bench["per_layer"][31:31 + 15]]
    assert [m["name"] for m in mine] == list(METRICS)
    # The deep zoom's four came last.
    assert len(bench["per_layer"]) == 31 + 15 + 1 + 4 + 1 + 4 + 1 + 4
    layers = {m["layer"] for m in bench["per_layer"][:31]}
    for entry in mine:
        layer, source, moves, reader, listed = METRICS[entry["name"]]
        assert entry == {
            "name": entry["name"], "unit": entry["unit"],
            "better": "lower", "source": source, "layer": layer,
            "moves": moves, "workloads": listed or cells}
        assert layer in layers          # a layer the benchmark names
        spec = _spec(entry["name"])
        assert (spec["reader"], spec["layer"], spec["source"],
                spec["moves"], spec["unit"]) == (
            reader, layer, source, moves, entry["unit"])
        # No reader is new: the three the benchmark had express all.
        assert reader in ("span_mean", "labelled_ratio",
                          "new_counter_ratio")


@pytest.mark.parametrize("name", sorted(SPAN_OF))
def test_a_span_metric_is_the_mean_of_its_span_over_the_window(name):
    span = SPAN_OF[name]
    assert _spec(name)["args"] == {"span": span}
    m0, m1 = _span(span, 10.0, 50.0), _span(span, 110.0, 350.0)
    assert _read(name, m0, m1) == pytest.approx(3.0)
    # The span did not fire in the window, or the server has none such
    # (the parent, for all but ``group_ms``): left out, never 0.
    assert _read(name, m1, m1) is None
    assert _read(name, {}, _span("batcher.stage", 9.0, 9.0)) is None


def test_every_span_a_metric_reads_is_one_the_program_records():
    source = ""
    for where, _dirs, files in os.walk(
            os.path.join(REPO, "omero_ms_image_region_tpu")):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(where, name)) as f:
                    source += f.read()
    for span in SPAN_OF.values():
        assert f'"{span}"' in source, span


def test_request_ms_is_the_servers_mean_request_over_every_route():
    family = "imageregion_request_duration_ms"
    m0 = {family + '_sum{route="render_image_region"}': 1000.0,
          family + '_count{route="render_image_region"}': 10.0,
          family + '_bucket{route="render_image_region",le="+Inf"}': 10.0}
    m1 = {family + '_sum{route="render_image_region"}': 1000.0 + 2400.0,
          family + '_count{route="render_image_region"}': 10.0 + 20.0,
          family + '_sum{route="render_image"}': 600.0,
          family + '_count{route="render_image"}': 10.0,
          family + '_bucket{route="render_image_region",le="+Inf"}': 30.0}
    assert _read("request_ms", m0, m1) == pytest.approx(100.0)
    assert _read("request_ms", m1, m1) is None
    # The series are the program's own since before this PR: the parent
    # reads the metric too.
    from omero_ms_image_region_tpu.utils import telemetry
    telemetry.reset()
    telemetry.REQUEST_HIST.observe("render_image_region", 40.0)
    telemetry.REQUEST_HIST.observe("render_image_region", 60.0)
    from benchmark.prom import parse_metrics
    live = parse_metrics("\n".join(telemetry.request_metric_lines()))
    assert _read("request_ms", {}, live) == pytest.approx(50.0)
    telemetry.reset()


def test_source_opens_per_render_reads_zero_where_nothing_is_opened():
    opened = "imageregion_pixel_sources_opened_total"
    m0 = {opened: 128.0, "imageregion_tiles_rendered": 1000.0}
    m1 = {opened: 128.0 + 384.0, "imageregion_tiles_rendered": 1384.0}
    assert _read("source_opens_per_render", m0, m1) == pytest.approx(1.0)
    # Every image open already: 0, not nothing.
    still = dict(m1, **{opened: 128.0})
    assert _read("source_opens_per_render", m0, still) == 0.0
    # The parent exports no such counter: nothing, never 0.
    parent = {"imageregion_tiles_rendered": 1384.0}
    assert _read("source_opens_per_render",
                 {"imageregion_tiles_rendered": 1000.0}, parent) is None
    assert _read("source_opens_per_render", m1, m1) is None


def test_the_idle_shares_split_the_capture_s_idle_by_class():
    family = "imageregion_profile_idle_ms_total"

    m1 = {f'{family}{{during="no_group"}}': 300.0,
          f'{family}{{during="unattributed"}}': 20.0,
          f'{family}{{during="device.wait"}}': 80.0,
          f'{family}{{during="PixelsService.readRegion"}}': 500.0,
          f'{family}{{during="PixelsService.openSource"}}': 90.0,
          f'{family}{{during="PixelsService.gcDrain"}}': 10.0}
    assert _read("idle_no_group_share", {}, m1) == pytest.approx(30.0)
    assert _read("idle_read_share", {}, m1) == pytest.approx(60.0)
    # A capture of the parent's program: its ``no_group`` is a number,
    # the reading threads' classes are none of its idle.
    parent = {f'{family}{{during="no_group"}}': 900.0,
              f'{family}{{during="device.wait"}}': 100.0}
    assert _read("idle_no_group_share", {}, parent) == pytest.approx(90.0)
    assert _read("idle_read_share", {}, parent) == 0.0
    # No capture (an untraced run, the CPU backend): nothing.
    assert _read("idle_no_group_share", m1, m1) is None
    assert _read("idle_read_share", {}, {}) is None
    # The classes are the reduction's own names.
    from omero_ms_image_region_tpu.utils import profile_summary as ps
    for term in _spec("idle_read_share")["args"]["numerator"]:
        assert term["labels"]["during"] in ps.IDLE_ORDER
    assert _spec("idle_no_group_share")["args"]["numerator"][0][
        "labels"]["during"] == ps.NO_GROUP


def test_entropy_pooled_share_is_appended_and_reads_the_tails_counter():
    """PR 37's one metric: the last entry, every cell, a data file over
    the reader ``plane_stack_share`` uses; nothing from a server
    without the family (the parent), 0 where every group is of one."""
    bench = _bench()
    # The seven cells of its time, and the cold pan and the deep zoom
    # appended to them; later metrics came after it.
    cells = [w["name"] for w in bench["workloads"]][:7]
    assert bench["per_layer"][31 + 15] == {
        "name": "entropy_pooled_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "host entropy tail",
        "moves": "p50_ms",
        "workloads": cells + ["wsi4-u16-t1024x24.coldpan",
                              "wsi4-u16-t1024-24000x16000.zoompan"]}
    entropy_ms = {m["name"]: m for m in bench["per_layer"]}["entropy_ms"]
    assert (entropy_ms["layer"], entropy_ms["moves"]) == (
        "host entropy tail", "p50_ms")
    spec = _spec("entropy_pooled_share")
    family = "imageregion_entropy_tiles_total"
    assert spec == {
        "name": "entropy_pooled_share", "layer": "host entropy tail",
        "unit": "%", "moves": "p50_ms", "source": "program_counter",
        "reader": "labelled_ratio",
        "args": {"numerator": [{"family": family,
                                "labels": {"path": "pooled"}}],
                 "denominator": [{"family": family}], "percent": True}}
    m0 = {f'{family}{{path="pooled"}}': 60.0,
          f'{family}{{path="inline"}}': 40.0}
    m1 = {f'{family}{{path="pooled"}}': 60.0 + 570.0,
          f'{family}{{path="inline"}}': 40.0 + 30.0}
    assert _read("entropy_pooled_share", m0, m1) == pytest.approx(95.0)
    lone = {f'{family}{{path="pooled"}}': 60.0,
            f'{family}{{path="inline"}}': 4000.0}
    assert _read("entropy_pooled_share", m0, lone) == 0.0
    assert _read("entropy_pooled_share", m1, m1) is None
    assert _read("entropy_pooled_share", {},
                 {"imageregion_tiles_rendered": 9.0}) is None
    # The series are the program's own, from its one coding pool.
    from benchmark.prom import parse_metrics
    from omero_ms_image_region_tpu.utils import entropypool, telemetry
    live = parse_metrics(telemetry.finalize_exposition(
        telemetry.device_metric_lines(None)))
    for path, n in entropypool.TILES.items():
        assert live[f'{family}{{path="{path}"}}'] == float(n)


def test_entropy_split_share_is_appended_and_reads_the_split_counter():
    """The split tiles' metric: appended last, on the cells of
    ``entropy_pooled_share`` (its denominator's family), a data file
    over the reader ``rawcache_dup_load_share`` uses; nothing from a
    server without the counter (an older one), 0 where no tile was
    split."""
    bench = _bench()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    # Last of its time; the deep zoom's four came after it.
    split_entry = bench["per_layer"][31 + 15 + 1 + 4 + 1 + 4]
    assert split_entry == {
        "name": "entropy_split_share", "unit": "tiles/tile",
        "better": "higher", "source": "program_counter",
        "layer": "host entropy tail", "moves": "p50_ms",
        "workloads": by_name["entropy_pooled_share"]["workloads"]}
    for cell in ("wsi4-u16-t1024.single", "plate3-u16-p2048.scan",
                 "jump5-u16-p1080.scan", "wsi4-u16-t1024.rewindow",
                 "stock4-u16-t256.pan", "stock4-u16-t256.single"):
        assert cell in split_entry["workloads"]
    split = "imageregion_entropy_split_tiles_total"
    tiles = "imageregion_entropy_tiles_total"
    assert _spec("entropy_split_share") == {
        "name": "entropy_split_share", "layer": "host entropy tail",
        "unit": "tiles/tile", "moves": "p50_ms",
        "source": "program_counter", "reader": "new_counter_ratio",
        "args": {"numerator": [split], "denominator": [tiles]}}
    m0 = {split: 10.0, f'{tiles}{{path="pooled"}}': 10.0,
          f'{tiles}{{path="inline"}}': 5.0}
    m1 = {split: 10.0 + 180.0, f'{tiles}{{path="pooled"}}': 10.0 + 190.0,
          f'{tiles}{{path="inline"}}': 5.0 + 10.0}
    assert _read("entropy_split_share", m0, m1) == pytest.approx(0.9)
    stock = dict(m1, **{split: 10.0})
    assert _read("entropy_split_share", m0, stock) == 0.0
    assert _read("entropy_split_share", m1, m1) is None
    older = {k: v for k, v in m1.items() if k != split}
    assert _read("entropy_split_share", {}, older) is None
    # The series is the program's own.
    from benchmark.prom import parse_metrics
    from omero_ms_image_region_tpu.utils import entropypool, telemetry
    live = parse_metrics(telemetry.finalize_exposition(
        telemetry.device_metric_lines(None)))
    assert live[split] == float(entropypool.SPLIT_TILES)
