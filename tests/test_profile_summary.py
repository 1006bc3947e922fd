"""The profile summary (``utils.profile_summary``) on hand-made rows,
the names it counts by inside the compiled device programs, and a CPU
capture of one batched JPEG group: the program's spans nested on the
worker thread's line, with no Python-tracer event beside them.
"""

import asyncio
import glob
import os
import re
import subprocess
import sys
import threading

import numpy as np
import pytest

from omero_ms_image_region_tpu.utils import profile_summary as ps
from omero_ms_image_region_tpu.utils import telemetry

MS = 1_000_000
TPU0, TPU1 = "/device:TPU:0", "/device:TPU:1"


# ----------------------------------------------------------- intervals

def test_interval_arithmetic():
    a = ps.union([(5, 9), (0, 3), (2, 4), (9, 9), (20, 30)])
    assert a == [(0, 4), (5, 9), (20, 30)]
    b = [(1, 6), (8, 25)]
    assert ps.intersect(a, b) == [(1, 4), (5, 6), (8, 9), (20, 25)]
    assert ps.subtract(a, b) == [(0, 1), (6, 8), (25, 30)]
    assert ps.subtract(b, a) == [(4, 5), (9, 20)]
    assert ps.length(a) == 4 + 4 + 10
    assert ps.subtract(a, []) == a and ps.intersect(a, []) == []


@pytest.mark.parametrize("op_name,stage", [
    ("jit(f)/wire.sparse_pack/wire.sparse_pack.scatter/scatter",
     "wire.sparse_pack.scatter"),
    ("jit(f)/wire.sparse_pack/cumsum", "wire.sparse_pack"),
    ("jit(f)/jit(g)/render/jpeg.dct_quant/dot_general", "jpeg.dct_quant"),
    ("jit(f)/wire.compact_rows/gather", "wire.compact_rows"),
    ("jit(f)/add", "unnamed"),
    ("jit(f)/renderer/add", "unnamed"),       # a whole component only
    ("", "unnamed"),
])
def test_an_operation_belongs_to_its_innermost_listed_scope(op_name,
                                                            stage):
    assert ps.stage_of(op_name) == stage


def test_self_times_count_every_busy_instant_once():
    # A loop's event [0, 100) holds its body's [10, 30) and [40, 60);
    # an event that outlives its parent is counted once too.
    events = [("loop", 0, 100), ("body", 10, 30), ("body", 40, 60),
              ("late", 90, 120), ("alone", 200, 210)]
    got = ps.self_times(events)
    assert got == {"loop": 100 - 20 - 20 - 10, "body": 40, "late": 30,
                   "alone": 10}
    assert sum(got.values()) == ps.length(
        ps.union((s, e) for _, s, e in events))


# ------------------------------------------------------------- summary

def _dev(plane, op_name, start_ms, dur_ms):
    return (plane, op_name, int(start_ms * MS), int(dur_ms * MS))


def _host(thread, span, start_ms, dur_ms, **stats):
    return (thread, span, int(start_ms * MS), int(dur_ms * MS), stats)


def hand_made_capture():
    """One chip, busy [100, 140) + [150, 170) + [300, 320) ms."""
    device = [
        _dev(TPU0, "jit(p)/render/mul", 100, 10),
        _dev(TPU0, "jit(p)/wire.sparse_pack/cumsum", 110, 10),
        # A fusion inside the packer's scatter scope, and the loop it
        # is the body of.
        _dev(TPU0, "jit(p)/wire.sparse_pack/while", 120, 20),
        _dev(TPU0, "jit(p)/wire.sparse_pack/wire.sparse_pack.scatter/"
                   "scatter", 125, 10),
        _dev(TPU0, "jit(p)/wire.compact_rows/scatter", 150, 15),
        _dev(TPU0, "jit(p)/add", 165, 5),
        _dev(TPU0, "", 300, 20),
    ]
    host = [
        _host("w0", "batcher.group", 90, 120, group_id=7, tiles=5),
        # Gap [140, 150): 4 ms of it a dispatch, which also lies in a
        # lane wait of another thread (dispatch comes first), then 3 ms
        # of lane wait alone, then 3 ms inside the group and no span.
        _host("w1", "device.dispatch", 140, 4),
        _host("w0", "batcher.laneWait", 138, 9),
        _host("w0", "device.wait", 150, 25, tiles=5),
        # Gap [170, 300): the wait for a chip that ran nothing until
        # 175, a copy to 180, entropy coding to 200, the group's end at
        # 210, then no group alive.
        _host("w0", "wire.d2h", 175, 5, tiles=5),
        _host("w0", "jfif.encodeBatch", 180, 20),
        # A wait that ended after the last operation, with its copy: not
        # a render of this interval.
        _host("w1", "device.wait", 310, 30, tiles=8),
        _host("w1", "wire.d2h", 340, 2, tiles=8),
        # One that ended before the first: neither.
        _host("w1", "device.wait", 50, 40, tiles=3),
        _host("w1", "wire.d2h", 90, 1, tiles=3),
        # One begun before the session is not in the capture; its copy,
        # begun inside the interval, is, and counts.
        _host("w2", "wire.d2h", 160, 1, tiles=6),
    ]
    return device, host


def test_stages_sum_to_busy_and_the_innermost_scope_wins():
    s = ps.summarize(*hand_made_capture())
    assert s["busy_ms"] == pytest.approx(40 + 20 + 20)
    assert s["traced_ms"] == pytest.approx(220)
    assert s["device_ms"] == pytest.approx({
        "render": 10, "wire.sparse_pack": 10 + 10,
        "wire.sparse_pack.scatter": 10, "wire.compact_rows": 15,
        "unnamed": 5 + 20})
    assert sum(s["device_ms"].values()) == pytest.approx(s["busy_ms"])
    assert list(s["planes"]) == [TPU0]


def test_gaps_are_split_in_the_stated_order_and_sum_to_idle():
    s = ps.summarize(*hand_made_capture())
    assert s["idle_ms"] == pytest.approx({
        "device.dispatch": 4, "batcher.laneWait": 3, "unattributed": 3 + 10,
        "device.wait": 5, "wire.d2h": 5, "jfif.encodeBatch": 20,
        "no_group": 90})
    assert sum(s["idle_ms"].values()) == pytest.approx(
        s["traced_ms"] - s["busy_ms"])
    assert ps.IDLE_ORDER.index("device.dispatch") \
        < ps.IDLE_ORDER.index("batcher.laneWait") \
        < ps.IDLE_ORDER.index("device.wait")


def test_a_gap_goes_to_the_reading_threads_before_it_goes_to_nobody():
    """PR 36: the spans of the threads between groups come after every
    class the order had, so those read what they read; only ``no_group``
    and ``unattributed`` give way."""
    device, host = hand_made_capture()
    before = ps.summarize(device, host)["idle_ms"]
    host = host + [
        # A read under the entropy coding [195, 200) and the group's
        # tail [200, 205): the coding keeps its 5 ms, the read takes 5
        # of what had no span though a group was alive.
        _host("r0", "PixelsService.readRegion", 195, 10),
        # No group alive: a read [220, 260), an open that begins under
        # it [250, 270), its forced collection, a request's accounting.
        _host("r1", "PixelsService.readRegion", 220, 40),
        _host("r2", "PixelsService.openSource", 250, 20, backend="tiff"),
        _host("r2", "PixelsService.gcDrain", 270, 2),
        _host("loop", "http.account", 280, 1),
        # One under a busy stretch takes nothing.
        _host("r0", "PixelsService.readRegion", 100, 40),
    ]
    s = ps.summarize(device, host)
    old = set(ps.IDLE_ORDER[:7])
    assert old == {"xla.compile", "device.dispatch", "batcher.stage",
                   "batcher.laneWait", "wire.d2h", "jfif.encodeBatch",
                   "device.wait"}
    assert {k: v for k, v in s["idle_ms"].items() if k in old} \
        == pytest.approx({k: v for k, v in before.items() if k in old})
    assert s["idle_ms"] == pytest.approx({
        "device.dispatch": 4, "batcher.laneWait": 3, "device.wait": 5,
        "wire.d2h": 5, "jfif.encodeBatch": 20,
        "PixelsService.readRegion": 5 + 40,
        "PixelsService.openSource": 10, "PixelsService.gcDrain": 2,
        "http.account": 1,
        "unattributed": 3 + 10 - 5, "no_group": 90 - 40 - 10 - 2 - 1})
    assert sum(s["idle_ms"].values()) == pytest.approx(
        s["traced_ms"] - s["busy_ms"])
    assert ps.IDLE_ORDER[7:] == (
        "PixelsService.readRegion", "PixelsService.openSource",
        "PixelsService.gcDrain", "http.account", "prefetch.stage")
    assert set(ps.IDLE_ORDER) <= ps.HOST_SPANS
    assert s["host_spans"]["PixelsService.readRegion"]["count"] == 3


def test_a_gap_under_the_prefetcher_alone_is_put_down_to_it():
    """The prefetcher's staging, on its own thread, comes after every
    class the order had: alone under a gap it takes what went to
    ``no_group``; beside a request's read the read keeps the gap."""
    device, host = hand_made_capture()
    before = ps.summarize(device, host)["idle_ms"]
    host = host + [
        # No group alive: the prefetcher alone [220, 250), then beside
        # a read [255, 285) that holds its [260, 280).
        _host("p0", "prefetch.stage", 220, 30, tiles=1, planes=4),
        _host("p1", "prefetch.stage", 260, 20, tiles=1, planes=4),
        _host("r0", "PixelsService.readRegion", 255, 30),
        # One under a busy stretch takes nothing.
        _host("p0", "prefetch.stage", 100, 30, tiles=1, planes=4),
    ]
    s = ps.summarize(device, host)
    assert ps.IDLE_ORDER[-1] == "prefetch.stage"
    assert "prefetch.stage" in ps.HOST_SPANS
    moved = {"prefetch.stage", "PixelsService.readRegion", "no_group"}
    assert {k: v for k, v in s["idle_ms"].items() if k not in moved} \
        == pytest.approx({k: v for k, v in before.items()
                          if k not in moved})
    assert s["idle_ms"]["prefetch.stage"] == pytest.approx(30)
    assert s["idle_ms"]["PixelsService.readRegion"] == pytest.approx(30)
    assert s["idle_ms"]["no_group"] == pytest.approx(90 - 30 - 30)
    assert sum(s["idle_ms"].values()) == pytest.approx(
        s["traced_ms"] - s["busy_ms"])
    assert s["host_spans"]["prefetch.stage"]["count"] == 3


def test_a_compile_takes_a_gap_before_the_dispatch_it_lies_in():
    device = [_dev(TPU0, "jit(p)/render/mul", 0, 10),
              _dev(TPU0, "jit(p)/render/mul", 110, 10)]
    host = [_host("w0", "batcher.group", 0, 200, tiles=1),
            _host("w0", "device.dispatch", 20, 80),
            _host("w0", "xla.compile", 30, 60)]
    s = ps.summarize(device, host)
    assert s["idle_ms"] == pytest.approx({
        "xla.compile": 60, "device.dispatch": 20, "unattributed": 20})


def test_renders_are_the_tiles_of_the_copies_that_began_inside():
    s = ps.summarize(*hand_made_capture())
    assert s["renders"] == 5 + 6
    assert s["host_spans"]["device.wait"] == {"count": 3, "ms": 95.0}
    assert s["host_spans"]["wire.d2h"]["count"] == 4


def test_two_device_planes_are_summed_and_kept_apart():
    device, host = hand_made_capture()
    device += [_dev(TPU1, "jit(p)/render/mul", 100, 50),
               _dev(TPU1, "jit(p)/wire.compact_rows/gather", 160, 40)]
    s = ps.summarize(device, host)
    one = ps.summarize(*hand_made_capture())
    assert sorted(s["planes"]) == [TPU0, TPU1]
    assert s["planes"][TPU0] == one["planes"][TPU0]
    other = s["planes"][TPU1]
    assert other["busy_ms"] == pytest.approx(90)
    assert other["traced_ms"] == pytest.approx(100)
    assert other["device_ms"] == pytest.approx(
        {"render": 50, "wire.compact_rows": 40})
    assert sum(other["idle_ms"].values()) == pytest.approx(10)
    assert s["busy_ms"] == pytest.approx(80 + 90)
    assert s["traced_ms"] == pytest.approx(220 + 100)
    assert s["device_ms"]["render"] == pytest.approx(10 + 50)
    assert sum(s["idle_ms"].values()) == pytest.approx(
        s["traced_ms"] - s["busy_ms"])


def test_no_device_plane_gives_no_summary():
    _, host = hand_made_capture()
    assert ps.summarize([], host) is None


# ------------------------------------- names out of the capture's file

def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _msg(*fields) -> bytes:
    """Protobuf encoding of ``(number, int | bytes | str)`` fields."""
    out = bytearray()
    for number, value in fields:
        if isinstance(value, int):
            out += _varint(number << 3) + _varint(value)
        else:
            data = value.encode() if isinstance(value, str) else value
            out += _varint(number << 3 | 2) + _varint(len(data)) + data
    return bytes(out)


def _inst(inst_id, name, op_name="", operands=(), calls=()):
    fields = [(1, name), (35, inst_id)]
    if op_name:
        fields.append((7, _msg((2, op_name))))
    if operands:
        fields.append((36, b"".join(_varint(o) for o in operands)))
    if calls:
        fields.append((38, b"".join(_varint(c) for c in calls)))
    return (2, _msg(*fields))


def hand_made_xplane() -> bytes:
    """One program as a TPU capture carries it: an ``HloProto`` in the
    metadata plane, under the name its module events have."""
    fused = _msg((1, "fused_computation"), (5, 10), (6, 101),
                 _inst(100, "param.0"),
                 _inst(101, "scatter.1", "jit(p)/wire.compact_rows/scatter",
                       operands=[100]))
    unnamed = _msg((1, "fused_computation.1"), (5, 11), (6, 111),
                   _inst(110, "param.1"), _inst(111, "select.9"))
    body = _msg((1, "wide.body"), (5, 12), (6, 121),
                _inst(120, "param.2"),
                _inst(121, "dynamic-update-slice.3", operands=[120]))
    main = _msg(
        (1, "main"), (5, 1), (6, 9),
        _inst(1, "mul.1", "jit(p)/render/mul"),
        _inst(2, "cumsum.1", "jit(p)/wire.sparse_pack/cumsum"),
        _inst(3, "where.1", "jit(p)/wire.sparse_pack/"
                            "wire.sparse_pack.scatter/select_n"),
        # A fusion with no name of its own: its root's.
        _inst(4, "fusion.5", calls=[10]),
        # The compiler's batched scatter: a bare name, nothing inside;
        # its operands' most deeply nested scope.
        _inst(5, "fusion.6", "scatter", operands=[2, 3, 1], calls=[11]),
        # What is made from it follows it; a loop takes its operands',
        # and its body its own.
        _inst(6, "copy.7", operands=[5]),
        _inst(7, "while.8", operands=[6], calls=[12]),
        # Nothing to go by.
        _inst(8, "constant.1"),
        _inst(9, "tuple.1", "outfeed"))
    proto = _msg((1, _msg((1, "jit_p"), (3, fused), (3, unnamed),
                          (3, body), (3, main))))
    entry = _msg((1, 7), (2, _msg((1, 7), (2, "jit_p(123)"),
                                  (5, _msg((1, 1), (6, proto))))))
    plane = _msg((1, 3), (2, ps.METADATA_PLANE), (4, entry))
    other = _msg((1, 1), (2, "/device:TPU:0"),
                 (4, _msg((1, 1), (2, _msg((2, "%fusion.6 = ..."))))))
    return _msg((1, other), (1, plane))


def test_an_operation_finds_its_name_in_the_captures_own_programs():
    names = ps.hlo_op_names(hand_made_xplane())
    assert list(names) == ["jit_p(123)"]
    stage = {inst: ps.stage_of(op) for inst, op in names["jit_p(123)"].items()}
    assert stage["mul.1"] == "render"
    assert stage["fusion.5"] == "wire.compact_rows"
    assert stage["fusion.6"] == "wire.sparse_pack.scatter"
    assert stage["copy.7"] == "wire.sparse_pack.scatter"
    assert stage["while.8"] == "wire.sparse_pack.scatter"
    assert stage["dynamic-update-slice.3"] == "wire.sparse_pack.scatter"
    assert stage["select.9"] == "wire.sparse_pack.scatter"   # its caller's
    assert stage["constant.1"] == "unnamed"
    assert names["jit_p(123)"]["tuple.1"] == "outfeed"       # kept
    assert ps.instruction_of(
        "%fusion.6 = s32[3145728]{0:T(1024)} fusion(s32[8]{0} %p), "
        "kind=kLoop") == "fusion.6"
    assert ps.hlo_op_names(b"") == {}


# ---------------------------------------------------------- the counters

def test_counters_move_only_with_a_device_plane():
    telemetry.PROFILE.reset()
    telemetry.PROFILE.observe(None)          # a CPU capture
    lines = telemetry.PROFILE.metric_lines()
    assert "imageregion_profile_captures_total 1" in lines
    assert "imageregion_profile_busy_ms_total 0.0" in lines
    assert "imageregion_profile_renders_total 0" in lines
    assert not [ln for ln in lines if "{" in ln]
    summary = ps.summarize(*hand_made_capture())
    telemetry.PROFILE.observe(summary)
    telemetry.PROFILE.observe(summary)
    lines = telemetry.PROFILE.metric_lines(',process="sidecar"')
    assert 'imageregion_profile_captures_total{process="sidecar"} 3' \
        in lines
    assert 'imageregion_profile_renders_total{process="sidecar"} 22' \
        in lines
    assert ('imageregion_profile_device_ms_total{stage="wire.sparse_pack'
            '.scatter",process="sidecar"} 20.0') in lines
    assert ('imageregion_profile_idle_ms_total{during="no_group",'
            'process="sidecar"} 180.0') in lines
    telemetry.reset()
    assert telemetry.PROFILE.captures == 0
    assert not telemetry.PROFILE.device_ms


# ------------------------------------- names inside the device programs

def _render_args(B=2, C=3, H=16, W=16):
    from omero_ms_image_region_tpu.ops import jpegenc
    f32 = lambda *s: np.zeros(s, np.float32)           # noqa: E731
    i32 = lambda *s: np.zeros(s, np.int32)             # noqa: E731
    qy, qc = (np.asarray(t, np.int32)
              for t in jpegenc.quant_tables(90))
    return (np.zeros((B, C, H, W), np.uint16), f32(B, C), f32(B, C) + 1,
            i32(B, C), f32(B, C) + 1, i32(B, C), 0, 255, f32(B, C, 3),
            qy, qc)


def _scopes_in(text: str) -> set:
    parts = set()
    for op_name in re.findall(r'op_name="([^"]*)"', text):
        parts.update(op_name.split("/"))
    return parts & set(ps.STAGES)


def test_every_scope_is_in_the_compiled_text_of_the_served_programs():
    """The compiled text carries ``op_name``; the lowered text carries
    none, which is why JAX's cache key can leave the names out
    (``utils.jaxenv.place_compilation_cache`` puts them in)."""
    from omero_ms_image_region_tpu.ops import jpegenc
    args = _render_args()
    front = {"render", "jpeg.ycbcr420", "jpeg.dct_quant"}
    lowered = jpegenc.render_to_jpeg_sparse_compact.lower(
        *args, np.int32(2), cap=64)
    assert "wire.sparse_pack" not in lowered.as_text()
    assert _scopes_in(lowered.compile().as_text()) == front | {
        "wire.sparse_pack", "wire.sparse_pack.scatter",
        "wire.sparse_pack.bits", "wire.compact_rows"}
    spec = jpegenc.huffman_spec_arrays()
    text = jpegenc.render_to_jpeg_huffman_compact.lower(
        *args, *spec, np.int32(2), h16=1, w16=1, cap=64,
        cap_words=64).compile().as_text()
    assert _scopes_in(text) == front | {"wire.huffman_pack",
                                        "wire.compact_rows"}
    # The stack of a request's channel planes is a program of its own
    # (ops.render.stack_channel_planes) with a stage of its own.
    from omero_ms_image_region_tpu.ops.render import stack_channel_planes
    plane = np.zeros((16, 16), np.uint16)
    assert _scopes_in(stack_channel_planes.lower(
        plane, plane).compile().as_text()) == {"stage.channel_stack"}
    # So is the stack of a group's planes (PR 33), under the same stage.
    from omero_ms_image_region_tpu.ops.render import stack_group_planes
    assert _scopes_in(stack_group_planes.lower(
        ((plane, plane),) * 3).compile().as_text()) == {
        "stage.channel_stack"}
    # Where the planes are smaller than their bucket the same program
    # pads them (PR 34), under a stage of its own.
    field = np.zeros((24, 24), np.uint16)
    assert _scopes_in(stack_group_planes.lower(
        ((field, field),) * 3, pad=(32, 32)).compile().as_text()) == {
        "stage.channel_stack", "stage.pad_mcu"}
    assert set(ps.STAGES) == front | {
        "wire.sparse_pack", "wire.sparse_pack.scatter",
        "wire.sparse_pack.bits", "wire.compact_rows",
        "wire.huffman_pack", "stage.channel_stack", "stage.pad_mcu"}


def _opcodes(compiled_text: str) -> set:
    return set(re.findall(r"= \S+ ([a-z][a-z-]*)\(", compiled_text))


@pytest.mark.parametrize("program", ["render_to_jpeg_sparse_compact",
                                     "render_to_jpeg_sparse"])
def test_the_served_program_holds_no_scatter(program):
    """Both wire compactions are dense passes and block moves (PR 29):
    no ``scatter`` in the lowered text, none (nor the ``sort`` a
    scatter lowers to at some batch shapes) among the compiled
    operations, and the four wire scopes still name the stages.  The
    scope ``wire.sparse_pack.scatter`` keeps its name: the benchmark's
    metrics read that label, whatever implements the stage.  Gathers
    by a constant index (the zigzag ``take``) are not the target."""
    from omero_ms_image_region_tpu.ops import jpegenc
    args = _render_args()
    if program == "render_to_jpeg_sparse_compact":
        args += (np.int32(2),)
    lowered = getattr(jpegenc, program).lower(*args, cap=64)
    assert "scatter" not in lowered.as_text()
    compiled = lowered.compile().as_text()
    assert not _opcodes(compiled) & {"scatter", "sort"}
    wire = {"wire.sparse_pack", "wire.sparse_pack.scatter",
            "wire.sparse_pack.bits"}
    if program == "render_to_jpeg_sparse_compact":
        wire.add("wire.compact_rows")
    assert _scopes_in(compiled) >= wire


def test_the_cache_key_takes_the_names_in(monkeypatch):
    import jax

    from omero_ms_image_region_tpu.utils import jaxenv
    before = (jax.config.jax_compilation_cache_include_metadata_in_key,
              jax.config.jax_compilation_cache_dir)
    try:
        jax.config.update(
            "jax_compilation_cache_include_metadata_in_key", False)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        jaxenv.place_compilation_cache()
        assert jax.config.jax_compilation_cache_include_metadata_in_key
    finally:
        jax.config.update(
            "jax_compilation_cache_include_metadata_in_key", before[0])
        jax.config.update("jax_compilation_cache_dir", before[1])


# ------------------------------------------ a capture of one JPEG group

def _inside(child, parent) -> bool:
    return parent[0] <= child[0] and child[1] <= parent[1]


def test_a_capture_holds_the_group_and_its_spans_nested_on_one_line(
        tmp_path):
    from jax.profiler import ProfileData

    from omero_ms_image_region_tpu.server.batcher import BatchingRenderer
    from omero_ms_image_region_tpu.utils import stopwatch as sw
    assert sw.install_annotations() is True
    rng = np.random.default_rng(5)
    settings = {
        "window_start": np.zeros(3, np.float32),
        "window_end": np.full(3, 4000.0, np.float32),
        "family": np.zeros(3, np.int32),
        "coefficient": np.ones(3, np.float32),
        "reverse": np.zeros(3, np.int32),
        "cd_start": 0, "cd_end": 255,
        "tables": np.eye(3, dtype=np.float32)}

    async def three_tiles(renderer):
        tiles = [rng.integers(0, 4000, (3, 64, 64)).astype(np.uint16)
                 for _ in range(3)]
        return await asyncio.gather(*(
            renderer.render_jpeg(t, settings, 90, 64, 64)
            for t in tiles))

    async def main():
        renderer = BatchingRenderer(max_batch=4, linger_ms=20.0)
        try:
            await three_tiles(renderer)        # compiles, untraced
            doc = {}
            capture = threading.Thread(target=lambda: doc.update(
                telemetry.capture_profile(str(tmp_path), 1000.0)))
            capture.start()
            await asyncio.sleep(0.3)
            jpegs = await three_tiles(renderer)
            await asyncio.to_thread(capture.join)
            return doc, jpegs
        finally:
            await renderer.close()

    doc, jpegs = asyncio.run(main())
    assert all(j[:2] == b"\xff\xd8" for j in jpegs)
    # The CPU backend has no device plane: no summary, one capture.
    assert doc["summary"] is None and "summary_error" not in doc
    xplane = ps.find_xplane(doc["dir"])
    assert xplane and doc["bytes"] >= os.path.getsize(xplane)

    lines = [(line.name, list(line.events))
             for plane in ProfileData.from_file(xplane).planes
             for line in plane.lines]
    # No Python-tracer event ("$file.py:line function") anywhere.
    assert not [ev.name for _, events in lines for ev in events
                if ev.name.startswith("$")]
    # The line itself, not its name: a worker that has run other files
    # holds several threads of one name (``asyncio_0``), a line each.
    groups = [(events, ev) for _, events in lines for ev in events
              if ev.name == "batcher.group"]
    assert len(groups) == 1
    line_events, group = groups[0]
    stats = dict(group.stats)
    assert stats["tiles"] == 3 and stats["padded"] == 3
    assert stats["group_id"] >= 2 and stats["key"] == "jpeg:3x256x256"

    def at(ev):
        return (ev.start_ns, ev.start_ns + ev.duration_ns)

    mine = {}
    for ev in line_events:
        if ev.name in ps.HOST_SPANS and _inside(at(ev), at(group)):
            mine.setdefault(ev.name, []).append(ev)
    assert sorted(mine) == sorted([
        "batcher.group", "batcher.laneWait", "batcher.stage",
        "device.dispatch", "wire.fetch", "device.wait", "wire.d2h",
        "jfif.encodeBatch"])
    assert all(len(evs) == 1 for evs in mine.values())
    span = {name: at(evs[0]) for name, evs in mine.items()}
    assert _inside(span["device.wait"], span["wire.fetch"])
    assert _inside(span["wire.d2h"], span["wire.fetch"])
    order = ["batcher.stage", "batcher.laneWait", "device.dispatch",
             "device.wait", "wire.d2h", "jfif.encodeBatch"]
    assert [span[n][0] for n in order] == sorted(span[n][0]
                                                 for n in order)
    assert dict(mine["device.wait"][0].stats)["tiles"] == 3
    assert dict(mine["wire.d2h"][0].stats)["tiles"] == 3
    # The same rows through the program's own reader.
    device_rows, host_rows = ps.read_capture(xplane)
    assert device_rows == []
    assert {r[1] for r in host_rows} >= set(order)


def test_the_device_ledger_is_dispatch_plus_wait():
    """A JPEG group's ``device_ms`` no longer runs from the lane to the
    end of the host's entropy coding."""
    from omero_ms_image_region_tpu.ops import jpegenc
    from omero_ms_image_region_tpu.utils.stopwatch import REGISTRY
    args = _render_args()[:9]
    before = REGISTRY.snapshot()
    timings = {}
    jpegs = jpegenc.render_batch_to_jpeg(
        *args, quality=90, dims=[(16, 16), (16, 16)], timings=timings)
    assert len(jpegs) == 2
    after = REGISTRY.snapshot()

    def grown(name):
        return after[name]["total_ms"] - before.get(
            name, {"total_ms": 0.0})["total_ms"]

    assert timings["device_ms"] == pytest.approx(
        grown("device.dispatch") + grown("device.wait"), abs=0.01)
    assert grown("wire.fetch") >= grown("device.wait") + grown("wire.d2h")


# ----------------------------------------------- importable without JAX

def test_stopwatch_and_telemetry_import_with_jax_blocked():
    code = r"""
import sys
class Block:
    def find_spec(self, name, path=None, target=None):
        if name == "jax" or name.startswith("jax."):
            raise ImportError("jax is blocked in this process")
sys.meta_path.insert(0, Block())
from omero_ms_image_region_tpu.utils import stopwatch, telemetry
from omero_ms_image_region_tpu.utils import profile_summary
assert stopwatch.install_annotations() is False
with stopwatch.stopwatch("frontend.span", tiles=3) as span:
    pass
assert span.ms >= 0.0
assert stopwatch.REGISTRY.snapshot()["frontend.span"]["count"] == 1
assert profile_summary.summarize([], []) is None
assert "jax" not in sys.modules
print("ok")
"""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run([sys.executable, "-c", code], cwd=repo,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip() == "ok"


def test_the_names_live_in_two_files_and_the_annotation_in_one():
    """``named_scope`` only where the device programs are written, the
    profiler's annotation only behind ``utils/stopwatch``'s hook."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    package = os.path.join(repo, "omero_ms_image_region_tpu")
    found = {"named_scope": set(), "TraceAnnotation": set()}
    for path in glob.glob(os.path.join(package, "**", "*.py"),
                          recursive=True):
        with open(path) as f:
            text = f.read()
        for word in found:
            if word in text:
                found[word].add(os.path.relpath(path, package))
    assert found["named_scope"] == {
        os.path.join("ops", "render.py"),
        os.path.join("ops", "jpegenc.py")}
    assert found["TraceAnnotation"] == {
        os.path.join("utils", "stopwatch.py")}
