#!/usr/bin/env python
"""Bench regression gate: judge BENCH_r*.json records and exit
non-zero on a service-rate regression, so the round-over-round
trajectory becomes a GATE instead of a log entry someone may read.

Two modes:

* **Pairwise** (default) — diff the newest record against the previous
  one.  Catches step regressions, but a -10% drift per round compounds
  to -37% over four rounds without ever tripping a pairwise gate —
  which is exactly what BENCH_r02 -> r05 did (41 -> 26 tiles/s).
* **Watermark** (``--watermark``) — gate the newest record against the
  BEST value each key ever recorded across every earlier
  ``BENCH_r*.json`` (max for throughput keys, min for ``_ms`` latency
  keys).  Slow-burn regressions cannot hide: the gate re-anchors to
  the best round, not the latest.

Usage::

    python scripts/bench_gate.py BENCH_r04.json BENCH_r05.json
    python scripts/bench_gate.py --dir .              # newest pair
    python scripts/bench_gate.py --watermark --dir .  # newest vs best
    python scripts/bench_gate.py --key service_tiles_per_sec \
        --max-regression 0.10 old.json new.json

Exit codes: 0 pass (or nothing to judge — see --strict), 1 regression
over the threshold, 2 usage/input error.

The default keys are the full-HTTP-stack service rate, its p50 latency
(latency regressions must not hide behind a flat throughput headline;
``_ms`` keys are judged in the opposite direction — up is the
regression) AND the raw host->HBM upload rate (a collapse that ships in
pieces no pairwise service-rate gate can see).  A round may fail to
measure any of them, so an absent/None value SKIPS that key's gate
(with a printed verdict) rather than failing the build — ``--strict``
turns skips into failures for CI postures that must always measure.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

DEFAULT_KEYS = ("service_tiles_per_sec", "p50_service_tile_ms",
                "raw_upload_mb_per_sec", "p50_first_tile_byte_ms")
# --multichip: judge MULTICHIP_r*.json records on the fleet scaling
# curve (__graft_entry__.fleet_scaling_curve prints it into the
# driver's tail).  Rounds that predate the curve — every record that
# only said `ok: true` — skip on null instead of failing.  The
# multi-PROCESS federated keys (bench.py --smoke --federation: real
# spawned sidecar processes behind an agreed manifest) joined the
# family in PR 15 — rounds that predate them skip on null the same
# way, so in-process-only history keeps judging.  PR 16 added the
# control-plane forensics keys (``fed_trace_stitched`` — the
# two-process waterfall stitched with per-host clock anchoring —
# and ``decision_records`` — autoscaler ledger verdicts carrying
# measured outcomes); both skip on null for older rounds too.
MULTICHIP_KEYS = ("fleet_tiles_per_sec_m8", "fleet_tiles_per_sec_m4",
                  "fleet_scaling_efficiency",
                  "fed_tiles_per_sec_p2",
                  "fed_process_scaling_efficiency",
                  "fed_trace_stitched",
                  "decision_records")
# --sessions: judge SESSIONS_r*.json records (bench.py --smoke
# --sessions) on the multi-user serving keys.  Direction-aware by
# name: the per-session p99 is a ``_ms`` key (regresses UP), the
# fairness index and predictive hit rate regress DOWN.
SESSIONS_KEYS = ("sessions_interactive_p99_ms",
                 "sessions_fairness_index", "prefetch_hit_rate")
# --offload: judge OFFLOAD_r*.json records (bench.py --smoke
# --offload) on the repeat-viewer offload keys.  Direction-aware by
# name: the offload ratio and peer hit rate regress DOWNWARD (less
# traffic absorbed off the origin), the 304 latency is a ``_ms`` key
# and regresses UPWARD.
OFFLOAD_KEYS = ("origin_offload_ratio", "peer_hit_rate",
                "p50_304_ms")
# --capacity: judge CAPACITY_r*.json records (bench.py --smoke
# --capacity — the open-loop offered-load sweep) on the capacity
# knee.  Direction-aware by name: the knee (offered tps where p99
# crosses the SLO or shed crosses 5%) and the fleet-size scaling
# efficiency regress DOWNWARD; the p99 AT the knee is a ``_ms`` key
# and regresses UPWARD.  ``--watermark`` covers the family like every
# other: the newest round is judged against the best knee any round
# ever measured.
CAPACITY_KEYS = ("capacity_knee_offered_tps", "p99_at_knee_ms",
                 "capacity_scaling_efficiency")
# --hotkey: judge HOTKEY_r*.json records (bench.py --smoke --hotkey —
# the hot-plane replication drill) on the viral-image keys.
# Direction-aware by name: the storm's throughput retention vs the
# uniform mix and the replication gain over the disabled A/B both
# regress DOWNWARD (a gain falling toward 1.0 means the tier stopped
# earning its keep); storm throughput itself regresses DOWNWARD too.
# ``hotkey_duplicate_staged`` is judged separately below: any value
# above zero fails outright — duplicate staging is a correctness
# bug, not a trend.  Rounds that predate the family skip on null.
HOTKEY_KEYS = ("hotkey_storm_ratio", "hotkey_replication_gain",
               "hotkey_storm_tps")
# --partition: judge PARTITION_r*.json records (bench.py --smoke
# --partition — the netsplit chaos drill) on the partition-tolerance
# latencies: how long the minority takes to FENCE after the links go
# dark, and to RESTORE after heal (both ``_ms`` keys, regress UP).
# The drill's availability and split-brain guarantees are judged
# separately below as correctness riders on the NEW record alone:
# any majority-side failure that was not counted shed, a post-heal
# agreement/byte round-trip that is not bit-exact, an aborted
# majority roll, or a fenced minority that refused NOTHING all fail
# outright — they are contracts, not trends.
PARTITION_KEYS = ("part_fence_ms", "part_restore_ms")

# Device-workloads drill (``bench.py --smoke --workloads``), PR 20:
# the batched mask/overlay/animation latencies and the pyramid build
# are ``_ms`` keys (regress UP); mask renders in the parity mix
# regress DOWN (fewer exercised = a shrunken drill, not a win).
WORKLOADS_KEYS = ("mask_device_ms", "overlay_device_ms",
                  "pyramid_build_ms", "anim_first_frame_ms",
                  "anim_total_ms", "mask_renders")
_BENCH_RE = re.compile(r"^BENCH_r(\d+)\.json$")
_MULTICHIP_RE = re.compile(r"^MULTICHIP_r(\d+)\.json$")
_SESSIONS_RE = re.compile(r"^SESSIONS_r(\d+)\.json$")
_OFFLOAD_RE = re.compile(r"^OFFLOAD_r(\d+)\.json$")
_CAPACITY_RE = re.compile(r"^CAPACITY_r(\d+)\.json$")
_HOTKEY_RE = re.compile(r"^HOTKEY_r(\d+)\.json$")
_PARTITION_RE = re.compile(r"^PARTITION_r(\d+)\.json$")
_WORKLOADS_RE = re.compile(r"^WORKLOADS_r(\d+)\.json$")

# Every committed record family in one table: (name, filename
# pattern, trend keys, pairwise/watermark threshold).  ``--all``
# iterates it, and ``load_watermarks`` (the importable parser the
# live perf sentinel shares) walks the same table so a family added
# here is automatically judged by CI AND learned by the sentinel.
FAMILIES = (
    ("bench", _BENCH_RE, DEFAULT_KEYS, 0.10),
    ("multichip", _MULTICHIP_RE, MULTICHIP_KEYS, 0.10),
    ("offload", _OFFLOAD_RE, OFFLOAD_KEYS, 0.10),
    ("sessions", _SESSIONS_RE, SESSIONS_KEYS, 0.10),
    ("capacity", _CAPACITY_RE, CAPACITY_KEYS, 0.10),
    ("hotkey", _HOTKEY_RE, HOTKEY_KEYS, 0.10),
    ("partition", _PARTITION_RE, PARTITION_KEYS, 0.50),
    ("workloads", _WORKLOADS_RE, WORKLOADS_KEYS, 0.50),
)


def lower_is_better(key: str) -> bool:
    """Latency keys regress UPWARD — without direction awareness a
    latency regression would read as an improvement (and a flat
    throughput headline could hide it entirely)."""
    return key.endswith("_ms") or "_ms_" in key


def load_record(path: str) -> dict:
    """One bench record: a JSON object, or the last JSON line of the
    file (bench.py prints ONE line; drivers may append logs).  Driver
    wrappers ({"parsed": {...}} / {"tail": "..."} envelopes) unwrap to
    the bench line itself."""
    with open(path) as f:
        text = f.read().strip()
    try:
        doc = json.loads(text)
    except ValueError:
        doc = None
        for line in text.splitlines():
            line = line.strip()
            if line.startswith("{"):
                try:
                    doc = json.loads(line)
                except ValueError:
                    continue
    if not isinstance(doc, dict):
        raise ValueError(f"{path}: no JSON object found")
    if "metric" not in doc:
        # Driver envelope: prefer the pre-parsed bench line; fall back
        # to scanning the captured tail for it.
        parsed = doc.get("parsed")
        if isinstance(parsed, dict):
            return parsed
        tail = doc.get("tail")
        if isinstance(tail, str):
            for line in tail.splitlines():
                line = line.strip()
                if '"metric"' not in line:
                    continue
                # Driver tails are length-capped from the FRONT, which
                # can shear the bench line's opening brace off (seen in
                # BENCH_r05); a line that starts mid-object is repaired
                # rather than dropped — the watermark gate must be able
                # to read every historical round.
                for candidate_text in (line, "{" + line):
                    try:
                        candidate = json.loads(candidate_text)
                    except ValueError:
                        continue
                    if isinstance(candidate, dict) and "metric" in \
                            candidate:
                        return candidate
    return doc


def all_records(directory: str, pattern=_BENCH_RE):
    """Every matching record in ``directory``, round order
    (ascending).  ``pattern`` selects the record family — BENCH by
    default, MULTICHIP under ``--multichip``."""
    rounds = []
    for name in os.listdir(directory):
        m = pattern.match(name)
        if m:
            rounds.append((int(m.group(1)),
                           os.path.join(directory, name)))
    rounds.sort()
    return [path for _, path in rounds]


def newest_pair(directory: str, pattern=_BENCH_RE):
    """The two highest-numbered records in ``directory`` (old, new) —
    the pair the driver's latest round produced."""
    rounds = all_records(directory, pattern)
    if len(rounds) < 2:
        raise ValueError(
            f"{directory}: need at least two matching records, "
            f"found {len(rounds)}")
    return rounds[-2], rounds[-1]


def judge(old: dict, new: dict, keys, max_regression: float):
    """Per-key verdicts: ``pass`` / ``regression`` / ``skipped``
    (value absent or null on either side — congestion weather)."""
    verdicts = []
    for key in keys:
        v_old, v_new = old.get(key), new.get(key)
        if not isinstance(v_old, (int, float)) \
                or not isinstance(v_new, (int, float)) or v_old <= 0:
            verdicts.append({"key": key, "verdict": "skipped",
                             "old": v_old, "new": v_new})
            continue
        change = (v_new - v_old) / v_old
        # Inclusive: a dead-on 10% move against the default threshold
        # is a failure, not a float-equality pass.  Direction depends
        # on the key: throughput regresses down, latency regresses up.
        if lower_is_better(key):
            verdict = ("regression" if change >= max_regression
                       else "pass")
        else:
            verdict = ("regression" if change <= -max_regression
                       else "pass")
        verdicts.append({"key": key, "verdict": verdict,
                         "old": round(float(v_old), 2),
                         "new": round(float(v_new), 2),
                         "change": round(change, 4)})
    return verdicts


def watermark(records, keys):
    """Best-ever value per key across ``records`` (list of parsed
    record dicts): max for throughput keys, min for latency keys;
    absent/null values are ignored.  Returns {key: (value, index)}
    with the index of the record that set the mark."""
    marks = {}
    for i, rec in enumerate(records):
        for key in keys:
            v = rec.get(key)
            if not isinstance(v, (int, float)) or v <= 0:
                continue
            if key not in marks:
                marks[key] = (float(v), i)
                continue
            best, _ = marks[key]
            better = (v < best) if lower_is_better(key) else (v > best)
            if better:
                marks[key] = (float(v), i)
    return marks


def judge_watermark(records, names, new, keys,
                    max_regression: float):
    """Judge ``new`` against each key's best-ever watermark over
    ``records``; verdict rows carry which round set the mark."""
    marks = watermark(records, keys)
    synthetic_old = {key: value for key, (value, _) in marks.items()}
    verdicts = judge(synthetic_old, new, keys, max_regression)
    for v in verdicts:
        mark = marks.get(v["key"])
        v["watermark_record"] = (os.path.basename(names[mark[1]])
                                 if mark else None)
    return verdicts


def load_watermarks(root: str = "."):
    """Best-ever marks across EVERY committed record family in
    ``root``: ``{family: {key: {"value": v, "record": basename}}}``.

    The importable half of the watermark gate — the live perf
    sentinel (``server.sentinel``) calls this at startup so the marks
    a human would check with ``--watermark`` become drift floors the
    serving fleet enforces continuously.  Strictly best-effort:
    absent families, unreadable records and null keys are skipped,
    never raised — a cold repo yields ``{}`` and the sentinel learns
    from live traffic alone."""
    marks_by_family = {}
    for name, pattern, keys, _ in FAMILIES:
        try:
            paths = all_records(root, pattern)
        except OSError:
            continue
        records, names = [], []
        for p in paths:
            try:
                records.append(load_record(p))
                names.append(os.path.basename(p))
            except (OSError, ValueError):
                continue
        if not records:
            continue
        marks = watermark(records, keys)
        if marks:
            marks_by_family[name] = {
                key: {"value": value, "record": names[idx]}
                for key, (value, idx) in marks.items()}
    return marks_by_family


def hotkey_riders(new_record: dict):
    """Correctness rider, judged on the NEW record alone (no trend,
    no threshold): a single duplicate-staged plane means the
    digest-dedup staging contract broke.  Absent/null skips like
    every other key (rounds that predate the family)."""
    dup = new_record.get("hotkey_duplicate_staged")
    if not isinstance(dup, (int, float)):
        return [{"key": "hotkey_duplicate_staged",
                 "verdict": "skipped", "old": None, "new": dup}]
    return [{"key": "hotkey_duplicate_staged",
             "verdict": "regression" if dup > 0 else "pass",
             "old": 0, "new": int(dup)}]


def partition_riders(new_record: dict):
    """Correctness riders, judged on the NEW record alone (no trend,
    no threshold) — each is a partition-tolerance CONTRACT: the
    majority must never fail a request without counting it shed, the
    quorate side's roll must commit, the healed fleet must agree
    bit-exactly (manifest digest + probe owners + byte round-trip),
    and a fenced minority that refused nothing means the fence gates
    never engaged.  Absent/null skips (rounds that predate the
    family)."""
    riders = (
        ("part_majority_5xx", lambda v: v == 0, 0),
        ("part_roll_committed", lambda v: v == 1, 1),
        ("part_rejoin_epoch", lambda v: v >= 2, 2),
        ("part_postheal_agree", lambda v: v == 1, 1),
        ("part_byte_agree", lambda v: v == 1, 1),
        ("part_minority_refusals", lambda v: v >= 1, 1),
    )
    out = []
    for key, ok, want in riders:
        val = new_record.get(key)
        if not isinstance(val, (int, float)):
            out.append({"key": key, "verdict": "skipped",
                        "old": None, "new": val})
        else:
            out.append({"key": key,
                        "verdict": "pass" if ok(val)
                        else "regression",
                        "old": want, "new": val})
    return out


_RIDERS = {"hotkey": hotkey_riders, "partition": partition_riders}


def judge_all(directory: str, strict: bool = False) -> int:
    """``--all``: one invocation over every record family — newest
    pair judged pairwise AND newest-vs-best watermark, riders
    included — printing one verdict row per family plus a combined
    JSON summary line.  Families with fewer than two committed
    records print ``skipped`` (that is data absence, not a
    regression); the combined exit code is 1 when ANY family
    regressed (or, under ``--strict``, skipped)."""
    rows = []
    any_fail = False
    any_skip = False
    for name, pattern, keys, max_regression in FAMILIES:
        paths = all_records(directory, pattern)
        if len(paths) < 2:
            rows.append((name, "skipped",
                         f"{len(paths)} record(s)"))
            any_skip = True
            continue
        try:
            records = [load_record(p) for p in paths]
        except (OSError, ValueError) as e:
            rows.append((name, "error", str(e)))
            any_fail = True
            continue
        new_record = records[-1]
        verdicts = judge(records[-2], new_record, keys,
                         max_regression)
        verdicts += judge_watermark(records[:-1], paths[:-1],
                                    new_record, keys, max_regression)
        rider = _RIDERS.get(name)
        if rider:
            verdicts += rider(new_record)
        regressed = [v["key"] for v in verdicts
                     if v["verdict"] == "regression"]
        if regressed:
            any_fail = True
            rows.append((name, "fail", ",".join(sorted(
                set(regressed)))))
        else:
            rows.append((name, "pass",
                         f"{len(verdicts)} key verdicts, "
                         f"new={os.path.basename(paths[-1])}"))
    width = max(len(name) for name, _, _ in rows)
    for name, verdict, detail in rows:
        print(f"{name:<{width}}  {verdict:<7}  {detail}",
              file=sys.stderr)
    failed = any_fail or (strict and any_skip)
    print(json.dumps({
        "gate": "bench", "mode": "all",
        "verdict": "fail" if failed else "pass",
        "families": [{"family": name, "verdict": verdict,
                      "detail": detail}
                     for name, verdict, detail in rows],
    }))
    return 1 if failed else 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description="Fail on a bench-record service-rate regression")
    parser.add_argument("paths", nargs="*",
                        help="old.json new.json (pairwise), or "
                             "old1.json ... new.json (--watermark: "
                             "the LAST record is judged)")
    parser.add_argument("--dir",
                        help="scan BENCH_r*.json records (pairwise: "
                             "newest pair; --watermark: newest vs "
                             "best-ever across the rest)")
    parser.add_argument("--watermark", action="store_true",
                        help="gate the newest record against each "
                             "key's best-ever value across all prior "
                             "records, not just the previous run "
                             "(pairwise -10%% per round compounds to "
                             "-37%% over four rounds undetected)")
    parser.add_argument("--multichip", action="store_true",
                        help="judge MULTICHIP_r*.json records on the "
                             "fleet scaling-curve keys (tiles/s at "
                             "the widest member counts + "
                             "fleet_scaling_efficiency); rounds that "
                             "predate the curve skip on null")
    parser.add_argument("--sessions", action="store_true",
                        help="judge SESSIONS_r*.json records (bench "
                             "--smoke --sessions) on the multi-user "
                             "serving keys: interactive per-session "
                             "p99 (regresses up), Jain's fairness "
                             "index and predictive prefetch hit rate "
                             "(regress down)")
    parser.add_argument("--offload", action="store_true",
                        help="judge OFFLOAD_r*.json records (bench "
                             "--smoke --offload) on the repeat-viewer "
                             "offload keys: origin offload ratio and "
                             "peer byte-fetch hit rate (regress "
                             "down), 304 latency (regresses up)")
    parser.add_argument("--capacity", action="store_true",
                        help="judge CAPACITY_r*.json records (bench "
                             "--smoke --capacity, the open-loop "
                             "offered-load sweep) on the capacity "
                             "knee: knee offered tps and scaling "
                             "efficiency regress down, p99-at-knee "
                             "regresses up")
    parser.add_argument("--hotkey", action="store_true",
                        help="judge HOTKEY_r*.json records (bench "
                             "--smoke --hotkey, the hot-plane "
                             "replication drill) on the viral-image "
                             "keys: storm/uniform throughput ratio, "
                             "replication gain over the disabled A/B "
                             "and storm throughput (all regress "
                             "down); any duplicate-staged count "
                             "above zero fails outright")
    parser.add_argument("--partition", action="store_true",
                        help="judge PARTITION_r*.json records (bench "
                             "--smoke --partition, the netsplit chaos "
                             "drill) on fence/restore latency (regress "
                             "up); majority 5xx-without-shed, aborted "
                             "rolls, failed post-heal agreement/byte "
                             "round-trips and a refusal-free fence "
                             "all fail outright")
    parser.add_argument("--workloads", action="store_true",
                        help="judge WORKLOADS_r*.json records (bench "
                             "--smoke --workloads, the device mask/"
                             "overlay/pyramid/animation drill) on the "
                             "batched-latency keys (regress up) and "
                             "the parity-mix size (regresses down)")
    parser.add_argument("--all", action="store_true",
                        help="judge EVERY committed record family "
                             "(BENCH/MULTICHIP/OFFLOAD/SESSIONS/"
                             "CAPACITY/HOTKEY/PARTITION/WORKLOADS) "
                             "in --dir "
                             "(default .) pairwise AND against its "
                             "watermark, riders included; prints one "
                             "verdict row per family and exits "
                             "non-zero if any family regressed — the "
                             "single CI entrypoint")
    parser.add_argument("--key", action="append", default=None,
                        help="record key(s) to judge (default "
                             "service_tiles_per_sec, "
                             "p50_service_tile_ms, "
                             "raw_upload_mb_per_sec, "
                             "p50_first_tile_byte_ms; --multichip: "
                             "the fleet scaling keys)")
    parser.add_argument("--max-regression", type=float, default=None,
                        help="fail when new < old by this fraction or "
                             "more (default 0.10; --partition "
                             "defaults to 0.50 — fence/restore are "
                             "quantized by the gossip tick)")
    parser.add_argument("--strict", action="store_true",
                        help="treat skipped (absent/null) keys as "
                             "failures")
    args = parser.parse_args(argv)
    if args.max_regression is None:
        # Partition fence/restore latency is quantized by the gossip
        # tick (~0.3 s of honest jitter on a ~1.2 s measurement): a
        # 10% relative bar fails identical code about half the time,
        # so the family bar is a tick-sized 50%.  Real regressions
        # (a lost tick loop, a widened suspect window) move 2-3x.
        # Workloads shares the wide bar: smoke-scale batched renders
        # are a few ms, so scheduler jitter dwarfs a 10% band.
        args.max_regression = (0.50 if args.partition or args.workloads
                               else 0.10)

    if args.all:
        try:
            return judge_all(args.dir or ".", strict=args.strict)
        except OSError as e:
            print(json.dumps({"gate": "bench", "error": str(e)}))
            return 2

    if args.key:
        keys = tuple(args.key)
    elif args.multichip:
        keys = MULTICHIP_KEYS
    elif args.sessions:
        keys = SESSIONS_KEYS
    elif args.offload:
        keys = OFFLOAD_KEYS
    elif args.capacity:
        keys = CAPACITY_KEYS
    elif args.hotkey:
        keys = HOTKEY_KEYS
    elif args.partition:
        keys = PARTITION_KEYS
    elif args.workloads:
        keys = WORKLOADS_KEYS
    else:
        keys = DEFAULT_KEYS
    pattern = (_MULTICHIP_RE if args.multichip
               else _SESSIONS_RE if args.sessions
               else _OFFLOAD_RE if args.offload
               else _CAPACITY_RE if args.capacity
               else _HOTKEY_RE if args.hotkey
               else _PARTITION_RE if args.partition
               else _WORKLOADS_RE if args.workloads else _BENCH_RE)
    try:
        if args.watermark:
            if args.dir:
                paths = all_records(args.dir, pattern)
            else:
                paths = list(args.paths)
            if len(paths) < 2:
                raise ValueError(
                    "watermark mode needs at least two records "
                    f"(got {len(paths)})")
            records = [load_record(p) for p in paths]
            new_record = records[-1]
            verdicts = judge_watermark(
                records[:-1], paths[:-1], new_record,
                keys, args.max_regression)
            doc = {
                "gate": "bench", "mode": "watermark",
                "records": len(paths) - 1,
                "new": os.path.basename(paths[-1]),
                "max_regression": args.max_regression,
            }
        else:
            if args.dir:
                old_path, new_path = newest_pair(args.dir, pattern)
            elif len(args.paths) == 2:
                old_path, new_path = args.paths
            else:
                parser.error("give exactly two record paths, or --dir")
            old, new = load_record(old_path), load_record(new_path)
            new_record = new
            verdicts = judge(old, new, keys, args.max_regression)
            doc = {
                "gate": "bench", "mode": "pairwise",
                "old": os.path.basename(old_path),
                "new": os.path.basename(new_path),
                "max_regression": args.max_regression,
            }
    except (OSError, ValueError) as e:
        print(json.dumps({"gate": "bench", "error": str(e)}))
        return 2

    if args.hotkey:
        verdicts += hotkey_riders(new_record)

    if args.partition:
        verdicts += partition_riders(new_record)

    regressed = [v for v in verdicts if v["verdict"] == "regression"]
    skipped = [v for v in verdicts if v["verdict"] == "skipped"]
    failed = bool(regressed) or (args.strict and bool(skipped))
    doc["verdict"] = "fail" if failed else "pass"
    doc["keys"] = verdicts
    print(json.dumps(doc))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
