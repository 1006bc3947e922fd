#!/usr/bin/env python3
"""One run of one cell of ``BENCHMARK.json`` on the chip.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Starts the combined-role tile server as the one chip-owning child, makes
the cell's images from ``--seed`` while it prewarms, warms up with the
cell's own traffic, drives a closed loop of viewers over HTTP for
``--seconds``, stops the child, compares a seeded sample of the bodies
with the plain reference, and prints one JSON object as its last line.
This process imports no JAX while the child lives.  README.md beside
this file says how a run spends its time and how to add a cell.

What belongs to one cell is found by name: the configuration's file and
the reference module it names (``references/<reference>.py``), the
traffic mix and the generator it names (``traffic_kinds/<kind>.py``),
each per-layer metric's declaration and reader.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()

import argparse                                            # noqa: E402
import concurrent.futures as cf                            # noqa: E402
import importlib                                           # noqa: E402
import json                                                # noqa: E402
import math                                                # noqa: E402
import os                                                  # noqa: E402
import shutil                                              # noqa: E402
import statistics                                          # noqa: E402
import sys                                                 # noqa: E402
import tempfile                                            # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import numpy as np                                         # noqa: E402

from benchmark import datagen, prom                        # noqa: E402
from benchmark.procs import (BenchFailure, Child, check,   # noqa: E402
                             free_port, http_get, say, wait_ready)

# tests/ patches these four for its CPU rehearsal at 64^2 tiles; the
# command itself has no option for any of them.
EXPECT_PLATFORM = "tpu"
BENCH_FILE = os.path.join(REPO, "BENCHMARK.json")
BENCH_ROOT = REPO                  # what a configuration's "file" is under
TRAFFIC_DIR = os.path.join(HERE, "traffic")
PACKAGE = "omero_ms_image_region_tpu"
SAMPLE_STREAM = 0xC0FFEE


def load_named(package: str, name: str, wants: tuple):
    """``benchmark/<package>/<name>.py``, which has to offer ``wants``."""
    try:
        module = importlib.import_module(f"benchmark.{package}.{name}")
    except ModuleNotFoundError as e:
        raise BenchFailure(f"no benchmark/{package}/{name}.py: {e}")
    missing = [w for w in wants if not callable(getattr(module, w, None))]
    check(not missing, f"benchmark/{package}/{name}.py lacks {missing}")
    return module


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def find(entries: list, name: str, what: str) -> dict:
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise BenchFailure(f"no {what} named {name!r} in BENCHMARK.json")


def metrics(port: int) -> dict:
    status, _, body = http_get(port, "/metrics", timeout=30.0)
    check(status == 200, f"/metrics answered {status}")
    return prom.parse_metrics(body.decode())


def compile_events(m: dict) -> int:
    return int(prom.series(m, "imageregion_compile_events_total"))


def cache_dir() -> str:
    """Where compiles are kept, as ``utils/jaxenv`` places them:
    ``JAX_COMPILATION_CACHE_DIR`` if set, else the fixed path in the
    checkout.  Handed to the child through that variable."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or os.path.join(REPO, ".jax_cache")


def percentile(values: list, q: float) -> float:
    """Nearest-rank percentile of all of ``values``."""
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[rank - 1]


def require_native(doc: dict, who: str) -> None:
    check(doc["entropy_coder"] == "native"
          and doc["tile_cache"] == "native",
          f"{who} runs pure-Python pieces: {doc}")


# ---------------------------------------------------------------- phases

def capture_task(seconds: float, trace_ms: float, out: dict):
    """The traced run's side task: a third of the way into the window,
    one ``/debug/profile`` capture by the server itself."""
    import asyncio

    async def task(session, base):
        await asyncio.sleep(seconds / 3.0)
        # One pair of readings ties this process's clock to the wall
        # clock the profiler stamps its session with.
        out["anchor"] = (time.time_ns(), time.perf_counter())
        out["t_start"] = time.perf_counter()
        async with session.get(
                f"{base}/debug/profile?ms={trace_ms:g}") as resp:
            out["status"] = resp.status
            out["doc"] = json.loads(await resp.read())
        out["t_end"] = time.perf_counter()
    return task


def check_sample(ref, records: list, images: dict, config: dict,
                 seed: int, n: int, control: dict | None = None) -> tuple:
    """Compare ``n`` of the window's answers, drawn by the seed, with the
    configuration's plain reference ``ref``.  Returns (numbers of each,
    indexes compared).  ``control`` (control.py only) puts the reference
    at lower precision in the program's place: the same requests, its
    bodies."""
    rng = np.random.default_rng(
        np.random.SeedSequence([int(seed), SAMPLE_STREAM]))
    picks = sorted(rng.choice(len(records), size=min(n, len(records)),
                              replace=False).tolist())

    def one(i: int) -> dict:
        req = records[i]["req"]
        body = records[i]["body"] if control is None else \
            ref.control_request(images, req, config, **control)
        return ref.compare_request(body, images, req, config)

    with cf.ThreadPoolExecutor(4) as pool:
        return list(pool.map(one, picks)), picks


def judge(ref, ok: list, images: dict, config: dict, mix: dict,
          seed: int, control: dict | None = None) -> tuple:
    """(correct, each number compared beside its limit, wrong answers)
    of a seeded sample of the answers that came back 200."""
    t0 = time.perf_counter()
    numbers, picks = check_sample(ref, ok, images, config, seed,
                                  int(mix.get("check_sample", 32)),
                                  control)
    limits = config["limits"]
    wrong = [i for i, n in zip(picks, numbers) if "error" in n
             or any(n[k] > limits[k] for k in limits)]
    if control is None:
        for i, n in zip(picks, numbers):
            if i in wrong:
                say(f"WRONG answer {ok[i]['req']['path']}: {n}")
    # The program is held to its worst answer; a control's reading is
    # its least (the upper reading a limit is set under).
    worst = min if control else max
    compared = {key: {"value": worst(n.get(key, float("inf"))
                                     for n in numbers), "limit": limit}
                for key, limit in limits.items()}
    compared["sampled"] = {"value": len(picks), "limit": 1}
    say(f"compared {len(picks)} answers "
        f"{'of the control ' + str(control) if control else ''}with the "
        f"plain reference in {time.perf_counter() - t0:.1f}s: mean abs "
        f"error {statistics.fmean(n.get('err', 0) for n in numbers):.3f}"
        f" grey levels (libjpeg's own "
        f"{statistics.fmean(n.get('libjpeg_err', 0) for n in numbers):.3f}"
        f"), excess_err of each "
        f"{[round(n.get('excess_err', 9), 4) for n in numbers]}")
    return not wrong and len(picks) >= 1, compared, len(wrong)


def read_layer_metrics(bench: dict, cell: dict, ctx: dict) -> dict:
    """Every per-layer metric that lists this cell (or lists none), read
    by the reader its own file names.  A reader that finds nothing
    returns None and the metric is left out."""
    out = {}
    for entry in bench["per_layer"]:
        if cell["name"] not in entry.get("workloads", [cell["name"]]):
            continue
        spec = load_json(os.path.join(
            HERE, "layer_metrics", entry["name"] + ".json"))
        reader = importlib.import_module(
            f"benchmark.readers.{spec['reader']}")
        value = reader.read(ctx, **spec.get("args", {}))
        if value is not None:
            out[entry["name"]] = {"value": float(value),
                                  "unit": entry["unit"]}
    return out


# ------------------------------------------------------------------ run

def run(args, bench: dict, workdir: str) -> dict:
    cell = find(bench["workloads"], args.workload, "workload")
    cfg_entry = find(bench["configs"], cell["config"], "configuration")
    config = load_json(os.path.join(BENCH_ROOT, cfg_entry["file"]))
    mix = load_json(os.path.join(TRAFFIC_DIR, cell["traffic"] + ".json"))
    kind = load_named("traffic_kinds", mix["kind"], ("warm_up", "window"))
    ref = load_named("references", config["reference"],
                     ("compare_request", "control_request"))
    check(os.path.isdir(os.path.join(REPO, PACKAGE)),
          f"the system under test ({PACKAGE}/) is not in this checkout")

    from omero_ms_image_region_tpu import native
    t0 = time.perf_counter()
    built = native.status()
    say(f"native: {built} ({time.perf_counter() - t0:.1f}s)")
    require_native(built, "this checkout (the native build failed)")

    data_dir = os.path.join(workdir, "data")
    os.makedirs(data_dir)
    yaml_path = os.path.join(workdir, "server.yaml")
    shutil.copy(os.path.join(os.path.dirname(
        os.path.join(BENCH_ROOT, cfg_entry["file"])),
        config["server_yaml"]), yaml_path)
    port = free_port()
    child = Child("server", [
        "--role", "combined", "--config", yaml_path,
        "--data-dir", data_dir, "--port", str(port)], workdir,
        env={"JAX_COMPILATION_CACHE_DIR": cache_dir(),
             # Programs that compile in under a second are kept too, so
             # a second run finds every program in the cache.
             "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
             "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "0"})
    try:
        return drive_cell(args, bench, cell, config, mix, kind, ref,
                          child, port, data_dir, workdir)
    except BaseException:
        print(child.log_tail(), file=sys.stderr, flush=True)
        raise
    finally:
        child.kill()


def drive_cell(args, bench, cell, config, mix, kind, ref, child, port,
               data_dir, workdir) -> dict:
    seed, seconds = args.seed, float(args.seconds)
    # The platform is known in seconds; the data is made while the
    # child prewarms (it reads nothing until the first request).
    doc, _ = wait_ready(child, port, EXPECT_PLATFORM, device_only=True)
    check(doc["device"]["count"] == cell["chips"],
          f"the server holds {doc['device']['count']} device(s), the cell "
          f"asks for {cell['chips']}")
    data = datagen.generate(config, seed, data_dir)
    say(f"data: {len(data['images'])} image(s), "
        f"{data['level0_bytes'] / 2**30:.2f} GiB at level 0, generated in "
        f"{data['gen_s']:.1f}s, ingested in {data['ingest_s']:.1f}s")
    ready_doc, ready_s = wait_ready(child, port, EXPECT_PLATFORM)
    device, native_doc = ready_doc["device"], ready_doc["native"]
    say(f"ready in {ready_s:.1f}s on {device['platform']} "
        f"{device['kind']} x{device['count']}; {native_doc}")
    require_native(native_doc, "the server")
    m_ready = metrics(port)
    say(f"at ready: {compile_events(m_ready)} compile events, "
        f"{int(prom.series(m_ready, 'imageregion_compile_cache_hits_total'))}"
        f" from the persistent cache, "
        f"{prom.series(m_ready, 'imageregion_compile_ms_total') / 1e3:.1f}s")

    env = {"port": port, "mix": mix, "config": config, "seed": seed,
           "compile_events": lambda: compile_events(metrics(port))}
    t0 = time.perf_counter()
    warm = kind.warm_up(env)
    say(f"warm-up: {warm} in {time.perf_counter() - t0:.1f}s")
    if not warm.get("quiet", True):
        say("warm-up ended UNQUIET: its last pass still added a compile "
            "event, so the window starts with shapes yet to come")

    # ------------------------------------------------------- the window
    capture: dict = {}
    side = capture_task(seconds, float(mix.get("trace_ms", 1500)),
                        capture) if args.trace else None
    m0 = metrics(port)
    setup_s = time.perf_counter() - T_PROCESS_START
    records, _, t_stop = kind.window(env, seconds, warm, side)
    m1 = metrics(port)
    peak_bytes = int(prom.series(m1, "imageregion_device_peak_bytes"))
    stop_s = child.terminate()
    say(f"window closed; server exit 0 in {stop_s:.1f}s after SIGTERM")
    check("jax" not in sys.modules, "the parent imported JAX while the "
          "child lived")

    # ------------------------------------------- after the child is gone
    # The deployment answers every request 200: one that was refused,
    # shed, cut or never answered is for ``correct``, like a wrong body.
    ok = [r for r in records if r["status"] == 200]
    in_window = [r for r in ok if r["t_done"] <= t_stop]
    refused = len(records) - len(ok)
    for r in [r for r in records if r["status"] != 200][:5]:
        say(f"UNANSWERED {r['req']['path']}: status {r['status']} "
            f"{r['body'][:120]!r}")
    check(in_window, f"no request completed in the window "
          f"({len(records)} issued, {refused} refused)")
    latencies = [(r["t_done"] - r["t_issue"]) * 1e3 for r in records]
    e2e = {"renders_per_s": len(in_window) / seconds,
           "p50_ms": statistics.median(latencies),
           "p95_ms": percentile(latencies, 95.0),
           "setup_s": setup_s}
    say(f"window: {len(records)} issued, {len(in_window)} completed in "
        f"{seconds:g}s, {refused} refused; " + ", ".join(
            f"{k} {v:.4f}" for k, v in e2e.items()))

    correct, compared, n_wrong = judge(ref, ok, data["images"], config,
                                       mix, seed)
    compared["unanswered"] = {"value": refused, "limit": 0}
    correct = correct and refused == 0
    # control.py only: the same requests answered by the reference at
    # lower precision, which has to come out as not correct.
    controls = {
        name: dict(zip(("correct", "compared"), judge(
            ref, ok, data["images"], config, mix, seed, control)[:2]))
        for name, control in args.controls.items()}

    result_metrics = {
        entry["name"]: {"value": e2e[entry["name"]], "unit": entry["unit"]}
        for entry in bench["end_to_end"]
        if cell["name"] in entry.get("workloads", [cell["name"]])}
    device_doc = {"platform": device["platform"], "kind": device["kind"],
                  "count": device["count"],
                  "memory_peak_bytes": peak_bytes}
    result = {"correct": correct, "attempted": len(records),
              "failed": refused + n_wrong, "metrics": result_metrics,
              "device": device_doc}
    if args.trace:
        result["metrics"], breakdown = traced(
            bench, cell, config, capture, ok, m0, m1, device,
            device_doc, workdir)
        if breakdown:
            result["breakdown"] = breakdown
    if controls:
        result["controls"] = controls
    result["warm_up"] = {k: warm.get(k) for k in ("passes", "quiet")}
    result["compared"] = compared
    return result


def traced(bench, cell, config, capture, ok, m0, m1, device,
           device_doc, workdir) -> tuple:
    """The ``--trace 1`` half: reduce the server's capture (the parent
    may import JAX now, held to the CPU) and read every per-layer
    metric of the cell."""
    from benchmark import trace as trace_mod
    check(capture.get("status") == 200,
          f"/debug/profile answered {capture.get('status')}: "
          f"{capture.get('doc')}")
    os.environ["JAX_PLATFORMS"] = "cpu"
    t0 = time.perf_counter()
    profile_dir = capture["doc"]["dir"]
    if not os.path.isabs(profile_dir):
        profile_dir = os.path.join(workdir, profile_dir)
    path = trace_mod.find_xplane(profile_dir)
    check(path is not None, f"no .xplane.pb under {profile_dir}: "
          f"{capture['doc']}")
    rows, session = trace_mod.read_xplane(path)
    reduced = trace_mod.reduce(rows)
    say(f"trace: {os.path.getsize(path)} bytes, {len(rows)} events on "
        f"device planes {trace_mod.device_planes(rows)}, session "
        f"{session}, read in {time.perf_counter() - t0:.1f}s")
    check(reduced is not None or device["platform"] != "tpu",
          "the capture holds no operation on a device plane")
    # Answers back between the first traced operation's start and the
    # last one's end: the rows count from the session's start, which the
    # profiler stamps with the machine's wall clock.
    done = None
    span = None if reduced is None else trace_mod.interval_on_clock(
        reduced, session, *capture["anchor"])
    if span is not None:
        lo, hi = span
        check(capture["t_start"] <= lo and hi <= capture["t_end"],
              f"the traced interval [{lo:.3f}, {hi:.3f}] lies outside "
              f"the capture call [{capture['t_start']:.3f}, "
              f"{capture['t_end']:.3f}]: the session's stamp is not on "
              f"this machine's clock")
        done = [r for r in ok if lo <= r["t_done"] <= hi]
    peaks = load_json(os.path.join(HERE, "peaks.json"))
    if reduced is not None:
        check(device["kind"] in peaks, f"device kind {device['kind']!r} "
              f"is not in benchmark/peaks.json")
    ctx = {"m0": m0, "m1": m1, "trace": reduced, "config": config,
           "peak": peaks.get(device["kind"]),
           "capture": {"renders": None if done is None else len(done)},
           "mean_body_bytes": statistics.fmean(len(r["body"])
                                               for r in ok)}
    out = read_layer_metrics(bench, cell, ctx)
    breakdown = None
    if reduced is not None:
        device_doc["busy_s"] = reduced["busy_s"]
        device_doc["window_s"] = reduced["window_s"]
        # Ten entries: the seven longest operations and, marked as
        # such, the three longest programs (which sum their own).
        breakdown = {"device_ops": reduced["device_ops"][:7] + [
            ["module " + n, s] for n, s in reduced["device_modules"][:3]],
            "idle_gaps": reduced["idle_gaps"]}
        say("trace: programs " + ", ".join(
            f"{n} {s:.4f}s" for n, s in reduced["device_modules"]))
        say(f"trace: busy {reduced['busy_s']:.4f}s of "
            f"{reduced['window_s']:.4f}s on {reduced['chips']} chip(s); "
            f"{None if done is None else len(done)} answers came back "
            f"inside the traced interval (the capture call took "
            f"{capture['t_end'] - capture['t_start']:.2f}s); roofline "
            f"bound: "
            f"{ctx.get('notes', {}).get('roofline_bound')}")
    return out, breakdown


def main(argv=None, controls: dict | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    args.controls = controls or {}     # control.py's only; never a flag
    bench = load_json(BENCH_FILE)
    workdir = tempfile.mkdtemp(prefix="imageregion_bench_")
    try:
        result = run(args, bench, workdir)
    except BenchFailure as e:
        print(f"benchmark failed: {e}", file=sys.stderr, flush=True)
        return 1
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    lines = [f"compared {k}: {v['value']} (limit {v['limit']})"
             for k, v in result["compared"].items()]
    print("\n".join(lines), file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
