"""One profiler capture, reduced to the numbers ``/metrics`` carries.

A ``/debug/profile`` capture holds two kinds of lines on one clock
(nanoseconds from the session's start): the chip's (plane
``/device:TPU:<n>``, line ``XLA Ops``: one event an operation) and the
host threads' (plane ``/host:CPU``), where every ``utils.stopwatch``
span of the program is an annotation.  The arithmetic here works on
plain rows, so it is tested on hand-made ones; :func:`read_capture` is
the only part that needs JAX (``jax.profiler.ProfileData``).

    device row: (plane, op_name, start_ns, duration_ns)
    host row:   (thread, span, start_ns, duration_ns, {stat: value})

What the summary states, and by which rule:

* ``device_ms`` by stage.  A stage is a named scope (JAX's) of
  :data:`STAGES`; an operation belongs to the INNERMOST listed scope on
  its ``op_name`` (a fusion carries its root's), else to ``unnamed``.
  Each instant the chip was busy is counted once, for the operation
  that started last (a loop's body inside the loop's own event), so the
  stages sum to ``busy_ms``.
* ``busy_ms`` (union of the operations' intervals) and ``traced_ms``
  (first operation's start to last operation's end), per device plane;
  the totals add the planes up.
* ``renders``: the sum of ``tiles`` over the ``wire.d2h`` spans that
  BEGAN inside the traced interval: the copy begins the instant its
  group's ``device.wait`` ends.  A group whose program straddles the
  interval's head counts, one straddling its tail does not.  (The wait
  itself also carries ``tiles``, but it is long, 0.2 to 1.3 s, and one
  begun before the session is not in the capture though it ends in
  it: counted by their waits, three captures of ``scan`` read 16-17
  renders where the client saw 20-21 answers.)
* ``idle_ms`` by what the host was doing: every gap between busy
  stretches is split by overlap with the host spans in the fixed order
  of :data:`IDLE_ORDER` (an instant two threads spend in different
  spans goes to the earlier class): a group's own spans first, then
  what the threads between groups do (a read of the store, the open of
  a pixel source and its forced collection, a request's accounting on
  the event loop, the prefetcher staging a predicted tile on its own
  thread).  What is left goes to ``no_group`` where no
  ``batcher.group`` was alive either (no group and no listed span: the
  event loop's own work of preparing requests and speaking HTTP, or
  true silence) and to ``unattributed`` where one was.  The classes sum
  to ``traced_ms - busy_ms``.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, Iterable, List, Tuple

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"

# The named scopes of the device programs (ops/render.py,
# ops/jpegenc.py; ops/render.py's stack of channel planes).  Fixed
# here: the reduction has to find them after a refactor, and they label
# a counter on /metrics.
STAGES = ("render", "jpeg.ycbcr420", "jpeg.dct_quant",
          "wire.sparse_pack", "wire.sparse_pack.scatter",
          "wire.sparse_pack.bits", "wire.huffman_pack",
          "wire.compact_rows", "stage.channel_stack", "stage.pad_mcu")
UNNAMED = "unnamed"

COMPILE = "xla.compile"
GROUP = "batcher.group"
WAIT = "device.wait"
COPY = "wire.d2h"
# Precedence of the host spans an idle gap is put down to.  The spans
# of the threads between groups come last, so that a class listed
# before them reads what it read before they were listed.
IDLE_ORDER = (COMPILE, "device.dispatch", "batcher.stage",
              "batcher.laneWait", COPY, "jfif.encodeBatch", WAIT,
              "PixelsService.readRegion", "PixelsService.openSource",
              "PixelsService.gcDrain", "http.account", "prefetch.stage")
# No ``batcher.group`` alive and no span of IDLE_ORDER running: the
# event loop's own work (prepare, HTTP), or true silence.
NO_GROUP = "no_group"
UNATTRIBUTED = "unattributed"

# JAX's own annotation around a backend compile, under the name the
# flight recorder gives the same event.
HOST_ALIASES = {"backend_compile_and_load": COMPILE}
HOST_SPANS = frozenset(IDLE_ORDER) | {GROUP, "wire.fetch", "wire.fetch2"}

Interval = Tuple[int, int]


# ------------------------------------------------------------ intervals

def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Disjoint, sorted intervals covering the same instants."""
    out: List[Interval] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def intersect(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Both disjoint and sorted."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """The instants of ``a`` not in ``b`` (both disjoint and sorted)."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > s:
                out.append((s, b[k][0]))
            s = max(s, b[k][1])
            k += 1
        if e > s:
            out.append((s, e))
    return out


def length(intervals: Iterable[Interval]) -> int:
    return sum(e - s for s, e in intervals)


# --------------------------------------------------------------- stages

def stage_of(op_name: str) -> str:
    """The innermost scope of :data:`STAGES` on an ``op_name`` like
    ``jit(f)/wire.sparse_pack/wire.sparse_pack.scatter/scatter``."""
    for part in reversed(op_name.split("/")):
        if part in STAGES:
            return part
    return UNNAMED


def scope_depth(op_name: str) -> int:
    """How many components lie before the innermost listed scope on an
    ``op_name`` (-1: it names none)."""
    parts = op_name.split("/")
    for i in range(len(parts) - 1, -1, -1):
        if parts[i] in STAGES:
            return i
    return -1


def self_times(events: List[Tuple[str, int, int]]) -> Dict[str, int]:
    """``{label: ns}`` of ``(label, start, end)`` events, each busy
    instant counted once, for the event that started last."""
    out: Dict[str, int] = {}
    stack: List[Tuple[str, int]] = []          # (label, end)
    cursor = 0

    def advance(to: int) -> None:
        nonlocal cursor
        while stack and cursor < to:
            label, end = stack[-1]
            upto = min(end, to)
            if upto > cursor:
                out[label] = out.get(label, 0) + upto - cursor
                cursor = upto
            if end <= to:
                stack.pop()
        cursor = max(cursor, to)

    for label, start, end in sorted(events, key=lambda e: (e[1], -e[2])):
        advance(start)
        stack.append((label, end))
    advance(max((e for _, _, e in events), default=0))
    return out


# -------------------------------------------------------------- summary

def summarize(device_rows: list, host_rows: list) -> dict | None:
    """The summary of one capture; None when no operation ran on a
    device plane (the CPU backend: nothing to count)."""
    by_plane: Dict[str, list] = {}
    for plane, op_name, start, dur in device_rows:
        by_plane.setdefault(plane, []).append(
            (stage_of(op_name), start, start + dur))
    if not by_plane:
        return None
    spans: Dict[str, list] = {}
    for _thread, name, start, dur, _stats in host_rows:
        spans.setdefault(name, []).append((start, start + dur))
    host = {name: union(iv) for name, iv in spans.items()}

    planes = {}
    for plane, events in sorted(by_plane.items()):
        busy = union((s, e) for _, s, e in events)
        if not busy:
            continue
        first, last = busy[0][0], busy[-1][1]
        left = subtract([(first, last)], busy)
        idle = {}
        for name in IDLE_ORDER:
            taken = intersect(left, host.get(name, []))
            if taken:
                idle[name] = length(taken) / 1e6
                left = subtract(left, taken)
        with_group = intersect(left, host.get(GROUP, []))
        idle[NO_GROUP] = (length(left) - length(with_group)) / 1e6
        idle[UNATTRIBUTED] = length(with_group) / 1e6
        planes[plane] = {
            "first_ns": first, "last_ns": last,
            "busy_ms": length(busy) / 1e6,
            "traced_ms": (last - first) / 1e6,
            "device_ms": {k: v / 1e6 for k, v in
                          sorted(self_times(events).items())},
            "idle_ms": {k: v for k, v in idle.items() if v > 0}}
    if not planes:
        return None

    first = min(p["first_ns"] for p in planes.values())
    last = max(p["last_ns"] for p in planes.values())
    renders = sum(int(stats.get("tiles", 0))
                  for _t, name, start, dur, stats in host_rows
                  if name == COPY and first <= start <= last)

    def total(key: str) -> dict:
        out: Dict[str, float] = {}
        for p in planes.values():
            for k, v in p[key].items():
                out[k] = out.get(k, 0.0) + v
        return out

    return {"planes": planes,
            "busy_ms": sum(p["busy_ms"] for p in planes.values()),
            "traced_ms": sum(p["traced_ms"] for p in planes.values()),
            "device_ms": total("device_ms"), "idle_ms": total("idle_ms"),
            "renders": renders,
            "host_spans": {name: {"count": len(iv),
                                  "ms": length(iv) / 1e6}
                           for name, iv in sorted(spans.items())}}


# -------------------------------------------------------------- reading

def find_xplane(directory: str) -> str | None:
    found = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    return found[-1] if found else None


# Where a TPU capture keeps an operation's ``op_name`` (read on a v5e,
# PERF.md section 6, PR 26): not on its event, whose name is the HLO
# line without metadata and whose stats are three device clock values.
# The capture carries each program that ran as an ``HloProto``, a stat
# of its entry in the ``/host:metadata`` plane's event metadata, under
# the name its ``XLA Modules`` events have; an operation's event names
# the instruction, and the module event around it names the program.
# ``ProfileData`` does not show event metadata, so the few fields needed
# are read from the file's own protobuf encoding (field numbers of
# tsl ``xplane.proto`` and xla ``hlo.proto``).
METADATA_PLANE = "/host:metadata"
MODULES_LINE = "XLA Modules"


def _varint(buf, i: int) -> tuple:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one encoded message: an int for a
    varint, a memoryview for a length-delimited or fixed field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {wire}")
        yield key >> 3, value


def _text(value) -> str:
    return bytes(value).decode("utf-8", "replace")


def _packed_ints(value) -> list:
    if isinstance(value, int):
        return [value]
    out, i = [], 0
    while i < len(value):
        item, i = _varint(value, i)
        out.append(item)
    return out


def _module_op_names(hlo_proto) -> Dict[str, str]:
    """``{instruction name: op_name}`` of one ``HloProto``.

    The TPU compiler leaves some of what it makes without the name of
    what it was made from (the packer's batched scatter, a cumsum's
    reduce-window, the loops it turns a batch dimension into).  Such an
    instruction, one whose own ``op_name`` names no scope of
    :data:`STAGES`, takes the ``op_name`` of the root of a computation
    it calls, else its operands' most deeply nested one (the first such
    on a tie: what is made from a scope's results is counted with that
    scope), else its caller's.  One that finds none keeps its own."""
    own: Dict[int, str] = {}                 # instruction id -> op_name
    names: Dict[int, str] = {}
    operands: Dict[int, list] = {}
    calls: Dict[int, list] = {}
    roots: Dict[int, int] = {}               # computation id -> root id
    members: Dict[int, list] = {}            # computation id -> ids
    for field, module in _fields(hlo_proto):
        if field != 1:                       # HloProto.hlo_module
            continue
        for field, comp in _fields(module):
            if field != 3:                   # HloModuleProto.computations
                continue
            comp_id, root_id, ids = None, None, []
            for field, value in _fields(comp):
                if field == 5:
                    comp_id = value
                elif field == 6:
                    root_id = value
                elif field == 2:             # .instructions
                    name, inst_id, op_name = "", None, ""
                    ops, called = [], []
                    for f, v in _fields(value):
                        if f == 1:
                            name = _text(v)
                        elif f == 35:
                            inst_id = v
                        elif f == 7:         # .metadata (OpMetadata)
                            for mf, mv in _fields(v):
                                if mf == 2:
                                    op_name = _text(mv)
                        elif f == 36:        # .operand_ids
                            ops += _packed_ints(v)
                        elif f == 38:        # .called_computation_ids
                            called += _packed_ints(v)
                    ids.append(inst_id)
                    names[inst_id], own[inst_id] = name, op_name
                    operands[inst_id], calls[inst_id] = ops, called
            roots[comp_id], members[comp_id] = root_id, ids
    caller = {inst: by for by, comps in calls.items()
              for comp in comps for inst in members.get(comp, ())}
    found = {inst: op_name for inst, op_name in own.items()
             if stage_of(op_name) != UNNAMED}

    def from_below(inst: int) -> str | None:
        for comp in calls[inst]:
            if roots.get(comp) in found:
                return found[roots[comp]]
        named = [found[o] for o in operands[inst] if o in found]
        return max(named, key=scope_depth) if named else None

    def from_caller(inst: int) -> str | None:
        return found.get(caller.get(inst))

    # Instructions come operands first, so one sweep carries a name
    # down a chain; sweeps repeat until nothing more is named, callers'
    # names going in only where roots and operands gave none.
    for rule in (from_below, from_caller, from_below):
        changed = True
        while changed:
            changed = False
            for inst in own:
                if inst not in found:
                    got = rule(inst)
                    if got is not None:
                        found[inst], changed = got, True
    return {names[inst]: found.get(inst, op_name)
            for inst, op_name in own.items()}


def hlo_op_names(xplane: bytes) -> Dict[str, Dict[str, str]]:
    """``{module event name: {instruction name: op_name}}`` of the
    programs a capture file carries."""
    out: Dict[str, Dict[str, str]] = {}
    for field, plane in _fields(memoryview(xplane)):
        if field != 1:                       # XSpace.planes
            continue
        entries, is_metadata = [], False
        for field, value in _fields(plane):
            if field == 2:
                is_metadata = _text(value) == METADATA_PLANE
            elif field == 4:                 # XPlane.event_metadata
                entries.append(value)
        if not is_metadata:
            continue
        for entry in entries:
            for field, meta in _fields(entry):
                if field != 2:               # map value: XEventMetadata
                    continue
                name, protos = "", []
                for f, v in _fields(meta):
                    if f == 2:
                        name = _text(v)
                    elif f == 5:             # .stats (XStat)
                        protos += [sv for sf, sv in _fields(v)
                                   if sf == 6]      # .bytes_value
                for proto in protos:
                    out.setdefault(name, {}).update(
                        _module_op_names(proto))
    return out


def instruction_of(event_name: str) -> str:
    """``fusion.6`` of ``%fusion.6 = s32[3145728]{0} fusion(...)``."""
    head = event_name.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def read_capture(path: str) -> tuple:
    """``(device_rows, host_rows)`` of one ``.xplane.pb``: the ``XLA
    Ops`` line of every chip, and of the host threads only the
    program's own spans (the runtime writes thousands of others)."""
    from bisect import bisect_right

    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        data = f.read()
    op_names = hlo_op_names(data)
    device_rows, host_rows = [], []
    for plane in ProfileData.from_serialized_xspace(data).planes:
        name = plane.name
        if (name.startswith(DEVICE_PLANE_PREFIX)
                and name[len(DEVICE_PLANE_PREFIX):].isdigit()):
            lines = {line.name: line for line in plane.lines}
            modules = sorted(
                (int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
                 op_names.get(ev.name, {}))
                for ev in getattr(lines.get(MODULES_LINE), "events", ()))
            starts = [m[0] for m in modules]
            for ev in getattr(lines.get(OPS_LINE), "events", ()):
                start = int(ev.start_ns)
                at = bisect_right(starts, start) - 1
                names = (modules[at][2] if at >= 0
                         and start < modules[at][1] else {})
                device_rows.append((
                    name, names.get(instruction_of(ev.name), ""),
                    start, int(ev.duration_ns)))
        elif name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    span = HOST_ALIASES.get(ev.name, ev.name)
                    if span in HOST_SPANS:
                        host_rows.append((line.name, span,
                                          int(ev.start_ns),
                                          int(ev.duration_ns),
                                          dict(ev.stats)))
    return device_rows, host_rows
