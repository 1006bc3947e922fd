"""From a profiler capture to numbers.  The arithmetic works on plain
rows ``(plane, line, name, start_ns, duration_ns)`` so it is tested on
hand-made rows; :func:`read_xplane` is the only part that needs
JAX (``jax.profiler.ProfileData``), and the parent calls it only after
the server child has exited, with ``JAX_PLATFORMS=cpu``.

What the planes and lines of a TPU capture are called was read by hand
from a capture of this server on a v5e (PERF.md, Findings, PR 25):
one plane ``/device:TPU:<n>`` a chip; its line ``XLA Ops`` carries one
event for every operation the chip ran, ``XLA Modules`` one for every
program (``jit_...``), ``Steps`` the profiler's own grouping.
"""

from __future__ import annotations

import glob
import os

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(profile_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(
        profile_dir, "**", "*.xplane.pb"), recursive=True))
    return found[-1] if found else None


SESSION_PLANE = "Task Environment"
SESSION_START = "profile_start_time"       # unix ns; the rows' zero


def read_xplane(path: str, plane_prefix: str = "/device:") -> tuple:
    """``(rows, session)``: rows of the planes whose name starts with
    ``plane_prefix`` (the host planes carry the Python tracer's events
    by the hundred thousand and are read by nothing here), and the
    profiler's own record of when the session started and stopped, in
    nanoseconds of the machine's wall clock (``{}`` where the capture
    has none).  Every row's ``start_ns`` counts from that start."""
    from jax.profiler import ProfileData
    rows, session = [], {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name == SESSION_PLANE:
            for key, value in plane.stats:
                if key.startswith("profile_"):
                    session[key] = int(value)
        if not plane.name.startswith(plane_prefix):
            continue
        for line in plane.lines:
            for ev in line.events:
                rows.append((plane.name, line.name, ev.name,
                             int(ev.start_ns), int(ev.duration_ns)))
    return rows, session


def interval_on_clock(reduced: dict, session: dict, unix_ns: int,
                      clock_s: float) -> tuple | None:
    """The traced window (first operation's start to last operation's
    end) on the caller's own clock, given one pair of readings
    ``(unix_ns, clock_s)`` taken together on this machine.  None where
    the capture carries no session stamp."""
    if SESSION_START not in session:
        return None
    zero = clock_s + (session[SESSION_START] - unix_ns) / 1e9
    return (zero + reduced["first_ns"] / 1e9,
            zero + reduced["last_ns"] / 1e9)


def device_planes(rows: list) -> list:
    return sorted({r[0] for r in rows
                   if r[0].startswith(DEVICE_PLANE_PREFIX)
                   and r[0][len(DEVICE_PLANE_PREFIX):].isdigit()})


def short_name(name: str) -> str:
    """The trace prints an operation as its whole HLO line; keep the
    result's name, type and shape (``%fusion.6 = s32[3145728]``), which
    tells the B = 8 program's operation from the B = 2 program's."""
    return name.split("{", 1)[0].strip()[:96]


def line_events(rows: list, plane: str, line: str) -> list:
    """``(name, start_ns, end_ns)`` of one line, by start."""
    return sorted(((short_name(r[2]), r[3], r[3] + r[4]) for r in rows
                   if r[0] == plane and r[1] == line),
                  key=lambda e: e[1])


def union_ns(intervals: list) -> int:
    """Total length of the union of ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(events: list) -> list:
    """Idle gaps ``(name, seconds)`` between the busy stretches of one
    line's events, named by the event that ends the gap."""
    out, cur_e = [], None
    for name, s, e in events:
        if cur_e is not None and s > cur_e:
            out.append((f"before {name}", (s - cur_e) / 1e9))
        cur_e = e if cur_e is None else max(cur_e, e)
    return out


def reduce(rows: list) -> dict | None:
    """Busy seconds (union of the intervals in which any operation ran,
    averaged over the chips), the traced window (first operation's
    start to last operation's end, the same on every chip; both also
    as ``first_ns`` / ``last_ns`` on the rows' clock), and the
    breakdown.  None when no operation ran on a device."""
    planes = device_planes(rows)
    per_plane = [(p, line_events(rows, p, OPS_LINE)) for p in planes]
    per_plane = [(p, ev) for p, ev in per_plane if ev]
    if not per_plane:
        return None
    first = min(ev[0][1] for _, ev in per_plane)
    last = max(max(e for _, _, e in ev) for _, ev in per_plane)
    window_s = (last - first) / 1e9
    busy_s = sum(union_ns([(s, e) for _, s, e in ev])
                 for _, ev in per_plane) / 1e9 / len(per_plane)
    by_name: dict = {}
    modules: dict = {}
    for plane, ev in per_plane:
        for name, s, e in ev:
            by_name[name] = by_name.get(name, 0.0) + (e - s) / 1e9
        for name, s, e in line_events(rows, plane, MODULES_LINE):
            modules[name] = modules.get(name, 0.0) + (e - s) / 1e9
    top = lambda d, n: [[k, v] for k, v in sorted(
        d.items(), key=lambda kv: -kv[1])[:n]]
    all_gaps = sorted((g for _, ev in per_plane for g in gaps(ev)),
                      key=lambda g: -g[1])
    return {"busy_s": busy_s, "window_s": window_s,
            "first_ns": first, "last_ns": last,
            "chips": len(per_plane),
            "device_ops": top(by_name, 10),
            "device_modules": top(modules, 10),
            "idle_gaps": [[n, s] for n, s in all_gaps[:10]]}
