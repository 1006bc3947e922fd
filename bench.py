"""Benchmark harness: the 5 BASELINE.md configs, TPU vs CPU reference.

The reference publishes no numbers (BASELINE.md), so the baseline is our own
faithful CPU implementation of the Java ``Renderer`` semantics
(``omero_ms_image_region_tpu.refimpl``) run on the same workload.

Headline metric (BASELINE.json): tiles/sec on 4-channel uint16 1024x1024
tiles (config 3, batched deep-zoom pan).  ``vs_baseline`` = TPU tiles/sec
divided by CPU-reference tiles/sec on identical tiles.  The other four
configs report as extras in the same JSON line.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...extras}
"""

from __future__ import annotations

import json
import statistics
import sys
import time

import numpy as np


def _settings_for(C, ptype="uint16", window=(100.0, 40000.0), model="rgb"):
    from omero_ms_image_region_tpu.flagship import FLAGSHIP_COLORS
    from omero_ms_image_region_tpu.models.pixels import Pixels
    from omero_ms_image_region_tpu.models.rendering import (
        RenderingModel, default_rendering_def,
    )
    from omero_ms_image_region_tpu.ops.render import pack_settings

    pixels = Pixels(image_id=1, pixels_type=ptype, size_x=8192, size_y=8192,
                    size_c=C)
    rdef = default_rendering_def(pixels)
    rdef.model = (RenderingModel.RGB if model == "rgb"
                  else RenderingModel.GREYSCALE)
    for i, cb in enumerate(rdef.channel_bindings):
        cb.active = True
        cb.red, cb.green, cb.blue = FLAGSHIP_COLORS[i % len(FLAGSHIP_COLORS)]
        cb.input_start, cb.input_end = window
    return rdef, pack_settings(rdef)


def _timed(fn, *args, repeats=3, warmup=True):
    """Best-of-N wall time for fn(*args) with one warm-up call."""
    if warmup:
        fn(*args)
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn(*args)
        times.append(time.perf_counter() - t0)
    return min(times)


def _cpu_contract_drill(name: str) -> None:
    """Declare a ``--smoke`` drill a CPU contract check and hold it to
    that.  These drills build device services in THIS process and (most
    of them) spawn device-owning sidecars besides; a chip belongs to one
    process, so on an accelerator the children would fail or hang.  Pin
    the CPU backend for this process and for every child it starts
    (children inherit the environment), and refuse outright when this
    process already holds an accelerator."""
    import os

    from omero_ms_image_region_tpu.utils.jaxenv import require_chip_free
    require_chip_free(f"bench.py --smoke {name} (a CPU contract drill)")
    os.environ["JAX_PLATFORMS"] = "cpu"
    jax = sys.modules.get("jax")
    if jax is not None:
        # Imported but not initialised: the environment was read at
        # import, so re-pin the live config too.
        jax.config.update("jax_platforms", "cpu")


def telemetry_wire_frames_per_flush():
    """Process-global wire coalescing mean, None when the run never
    crossed the sidecar wire (combined posture)."""
    try:
        from omero_ms_image_region_tpu.utils import telemetry
        return telemetry.WIRE.frames_per_flush()
    except Exception:
        return None


def telemetry_wire_ring_hit_rate():
    try:
        from omero_ms_image_region_tpu.utils import telemetry
        return telemetry.WIRE.ring_hit_rate()
    except Exception:
        return None


def _opt_round(v, nd):
    return None if v is None else round(v, nd)


def _cpu_jpeg(rgba, quality=85):
    """The CPU comparators' shared encode convention: PIL/libjpeg RGB."""
    import io

    from PIL import Image

    out = io.BytesIO()
    Image.fromarray(np.ascontiguousarray(rgba[..., :3])).save(
        out, format="JPEG", quality=quality)
    return out.getvalue()


# ----------------------------------------------------------- config 3 (HEAD)

def bench_flagship(rng):
    """4-ch uint16 1024^2 batched pan, raw -> JPEG bytes, TPU vs CPU.

    The deliverable of the hot path is an encoded tile (the reference
    renders packed ints then JPEG-compresses them on the CPU,
    ``ImageRegionRequestHandler.java:559,580-582``).  TPU path: uint16
    host batch -> fused render + JPEG DCT/quantize kernel (one dispatch,
    packed RGBA never leaves HBM) -> async coefficient fetch -> native
    C++ entropy coder on a thread pool.  CPU path: the numpy reference
    renderer + PIL (libjpeg) encode on identical tiles.
    """
    import concurrent.futures as cf

    from omero_ms_image_region_tpu.flagship import (
        batched_args, flagship_settings, synthetic_wsi_tiles,
    )
    from omero_ms_image_region_tpu.ops.jpegenc import (
        quant_tables, render_to_jpeg_coefficients,
    )
    from omero_ms_image_region_tpu.refimpl import render_ref

    from omero_ms_image_region_tpu.native import jpeg_native_available
    if jpeg_native_available():
        from omero_ms_image_region_tpu.native import (
            jpeg_encode_native as entropy_encode,
        )
    else:
        from omero_ms_image_region_tpu.jfif import (
            encode_jfif as entropy_encode,
        )

    from omero_ms_image_region_tpu.ops.jpegenc import (
        compact_fetcher, default_sparse_cap, default_words_cap,
        encode_sparse_buffers, finish_huffman_batch,
        render_to_jpeg_coefficients, render_to_jpeg_huffman_compact,
        render_to_jpeg_sparse_compact, spec_kernel_arrays,
    )

    import jax

    rdef, settings = flagship_settings()
    B, C, H, W = 8, 4, 1024, 1024
    n_batches = 4
    quality = 85
    cap = default_sparse_cap(H, W)
    cap_words = default_words_cap(H, W)
    raw_batches = [synthetic_wsi_tiles(rng, B, C, H, W)
                   for _ in range(n_batches)]
    args_suffix = batched_args(settings, raw_batches[0])[1:]
    qy, qc = (t.astype(np.int32) for t in quant_tables(quality))
    # Tune the huffman wire to the workload before sampling — the same
    # tables the serving path's background tuner would publish after
    # its first group (one dense-coefficient sample, outside the timed
    # windows); the framing below must declare them.
    from omero_ms_image_region_tpu.jfif import (
        symbol_frequencies, tuned_huffman_spec)
    _one = tuple(a[:1] if getattr(a, "ndim", 0) else a
                 for a in args_suffix)
    _y0, _cb0, _cr0 = (np.asarray(a)[0] for a in
                       render_to_jpeg_coefficients(
                           raw_batches[0][:1], *_one, qy, qc))
    tuned8 = tuned_huffman_spec(*symbol_frequencies(_y0, _cb0, _cr0))
    spec = spec_kernel_arrays(tuned8)
    pool = cf.ThreadPoolExecutor(max_workers=8)
    # Compacted wire (the serving path's format): the fetch carries
    # exactly the batch's used bytes behind a lengths header.
    fetchers = {"sparse": compact_fetcher("sparse", H, W, cap, 0, B),
                "huffman": compact_fetcher("huffman", H, W, cap,
                                           cap_words, B)}

    # Stage the pan's raw tiles into HBM once — the warm interactive
    # posture (the service keeps hot tiles device-resident and re-renders
    # on settings/pan changes).  Upload is reported separately, and the
    # cold number below charges it end to end.
    t0 = time.perf_counter()
    dev_raw = [jax.device_put(r) for r in raw_batches]
    jax.block_until_ready(dev_raw)
    upload_s = time.perf_counter() - t0
    upload_mb_s = sum(r.nbytes for r in raw_batches) / 1e6 / upload_s

    def dense_fallback(raw, i):
        y, cb, cr = render_to_jpeg_coefficients(
            raw[i:i + 1].astype(np.float32), *(
                a[i:i + 1] if getattr(a, "ndim", 0) else a
                for a in args_suffix), qy, qc)
        return entropy_encode(np.asarray(y)[0], np.asarray(cb)[0],
                              np.asarray(cr)[0], W, H, quality)

    def dispatch(raw, engine):
        """One device dispatch of the chosen wire engine for a batch."""
        if engine == "sparse":
            return render_to_jpeg_sparse_compact(
                raw, *args_suffix, qy, qc, np.int32(B), cap=cap)
        return render_to_jpeg_huffman_compact(
            raw, *args_suffix, qy, qc, *spec, np.int32(B),
            h16=H // 16, w16=W // 16, cap=cap, cap_words=cap_words)

    def run_once(batches, engine="sparse"):
        """One full pan: all batches raw -> JPEG bytes; returns p50 ms.

        Device: fused render + JPEG front end + wire packing — 18-bit
        sparse entries or the device fixed-table Huffman stream (one
        dispatch per batch, all dispatched up-front so the device
        pipelines).  Wire: predictive prefix fetch — only the
        entropy-bearing bytes leave the device, started async for every
        batch before the first host encode.  Host: entropy coding
        (sparse) or 0xFF-stuff + framing (huffman), overlapping later
        batches' wire time.
        """
        starter = fetchers[engine]
        handles = [starter.start(dispatch(raw, engine))
                   for raw in batches]
        batch_ms, jpegs = [], []
        # `batches`, not the closure's raw_batches: the cold path passes
        # perturbed arrays and the dense fallback must see those pixels.
        for raw, h in zip(batches, handles):
            t0 = time.perf_counter()
            rows = starter.finish(h)
            if engine == "sparse":
                jpegs.extend(encode_sparse_buffers(
                    rows, W, H, quality, cap, executor=pool,
                    dense_fallback=lambda i, raw=raw:
                        dense_fallback(raw, i)))
            else:
                jpegs.extend(finish_huffman_batch(
                    rows, [(W, H)] * B, H, W, quality, cap, cap_words,
                    dense_fallback=lambda i, raw=raw:
                        dense_fallback(raw, i), spec=tuned8))
            batch_ms.append((time.perf_counter() - t0) * 1000.0)
        assert all(j[:2] == b"\xff\xd8" for j in jpegs)
        return statistics.median(batch_ms)

    # Sample each engine (alternating, up to 7 rounds each) until its
    # best stops improving, then let the better engine carry the
    # headline — both are supported serving configurations
    # (renderer.jpeg-engine).  Engine rounds INTERLEAVE (sparse,
    # huffman, sparse, ...) so whatever else the host is doing hits
    # both engines alike.
    engines = ("sparse", "huffman")
    for e in engines:
        run_once(dev_raw, e)        # warm-up/compile + prefix prediction
    times = {e: [] for e in engines}
    p50s = {e: [] for e in engines}
    stale = {e: 0 for e in engines}
    for _round in range(7):
        live = [e for e in engines
                if not (len(times[e]) >= 4 and stale[e] >= 3)]
        if not live:
            break
        for e in live:
            t0 = time.perf_counter()
            p50s[e].append(run_once(dev_raw, e))
            times[e].append(time.perf_counter() - t0)
            if times[e][-1] <= min(times[e]) * 1.02:
                stale[e] = 0
            else:
                stale[e] += 1
    results = {
        e: ((B * n_batches) / min(times[e]), statistics.median(p50s[e]))
        for e in engines
    }
    engine = max(results, key=lambda e: results[e][0])
    tiles_per_sec, p50_batch_ms = results[engine]

    # Cold path: charge host->HBM staging too (fresh uploads feeding
    # the same pipeline, twice; best of 2) as the serving path stages:
    # a plain asynchronous device_put of the storage-dtype stack.
    cold_times = []
    for rep in range(2):
        t0 = time.perf_counter()
        run_once([jax.device_put(r) for r in raw_batches], engine)
        cold_times.append(time.perf_counter() - t0)
    cold_tiles_per_sec = (B * n_batches) / min(cold_times)
    # Overlap honesty: cold throughput expressed as staged bytes/s over
    # the raw upload rate measured ADJACENT to the cold window.  ~1.0 =
    # staging hides everything but the upload; well below 1.0 = staging
    # serializes against upload and double-buffering has room.
    cold_bytes_per_sec = (B * n_batches * raw_batches[0][0].nbytes
                          / min(cold_times))
    t0 = time.perf_counter()
    jax.block_until_ready(jax.device_put(raw_batches[0]))
    cold_window_upload_mb_s = raw_batches[0].nbytes / 1e6 \
        / (time.perf_counter() - t0)

    # Per-batch device execution time of each wire program (resident
    # input, result left on the device): the tiles/sec the device
    # pipeline sustains before fetch and host encode matter.
    exec_ms = {}
    for eng in ("sparse", "huffman"):
        reps = []
        for k in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(dispatch(dev_raw[k % n_batches], eng))
            if k:   # first rep carries compile
                reps.append(time.perf_counter() - t0)
        exec_ms[eng] = statistics.median(reps) * 1000.0
    device_ceiling_tps = B / (min(exec_ms.values()) / 1000.0)

    # Interactive single-tile latency (warm, B=1): raw resident -> JPEG
    # bytes on host, BOTH wire engines measured.
    one = dev_raw[0][:1]
    one_args = tuple(a[:1] if getattr(a, "ndim", 0) else a
                     for a in args_suffix)
    one_fetchers = {
        "sparse": compact_fetcher("sparse", H, W, cap, 0, 1),
        "huffman": compact_fetcher("huffman", H, W, cap, cap_words, 1)}

    def one_tile(x, eng):
        if eng == "sparse":
            rows = one_fetchers[eng].fetch(render_to_jpeg_sparse_compact(
                x, *one_args, qy, qc, np.int32(1), cap=cap))
            encode_sparse_buffers(rows, W, H, quality, cap)
        else:
            rows = one_fetchers[eng].fetch(render_to_jpeg_huffman_compact(
                x, *one_args, qy, qc, *spec, np.int32(1),
                h16=H // 16, w16=W // 16, cap=cap,
                cap_words=cap_words))
            finish_huffman_batch(rows, [(W, H)], H, W, quality, cap,
                                 cap_words,
                                 dense_fallback=lambda i:
                                     dense_fallback(raw_batches[0], i),
                                 spec=tuned8)
    p50_by_engine = {}
    for eng in ("sparse", "huffman"):
        lat = []
        for k in range(8):
            t0 = time.perf_counter()
            one_tile(one, eng)
            lat.append((time.perf_counter() - t0) * 1000.0)
        # Reps 0-1 carry compile AND the fetcher's prefix-prediction
        # warm-up (measured ~1.2 s vs ~0.2 s steady); the steady-state
        # interactive latency is what the metric means.
        p50_by_engine[eng] = statistics.median(lat[2:])
    p50_tile_ms = min(p50_by_engine.values())

    # CPU reference on identical tiles: render + PIL JPEG (libjpeg).
    # Fixed >=18 s window so the denominator is stable run to run.
    def cpu_tile(raw_tile):
        return _cpu_jpeg(render_ref(raw_tile.astype(np.float32), rdef),
                         quality)

    n, t0 = 0, time.perf_counter()
    while True:
        cpu_tile(raw_batches[n // B % n_batches][n % B])
        n += 1
        dt = time.perf_counter() - t0
        if dt >= 18.0:
            break
    cpu_tps = n / dt
    return {
        "tiles_per_sec": tiles_per_sec,
        "engine": engine,
        "sparse_tiles_per_sec": results["sparse"][0],
        "huffman_tiles_per_sec": results["huffman"][0],
        "cold_tiles_per_sec": cold_tiles_per_sec,
        "cold_overlap_efficiency": (cold_bytes_per_sec / 1e6
                                    / cold_window_upload_mb_s),
        "p50_batch_ms": p50_batch_ms,
        "p50_tile_ms": p50_tile_ms,
        "p50_tile_ms_sparse": p50_by_engine["sparse"],
        "p50_tile_ms_huffman": p50_by_engine["huffman"],
        "cpu_tps": cpu_tps,
        "upload_mb_s": upload_mb_s,
        "sparse_exec_ms_batch": exec_ms["sparse"],
        "huffman_exec_ms_batch": exec_ms["huffman"],
        "device_ceiling_tps": device_ceiling_tps,
    }


# ------------------------------------------------------- service level

def bench_service_level(rng):
    """Config-3 pan through the FULL HTTP stack (routes, ctx parsing,
    caches, batcher, device dispatch, JPEG wire, entropy encode):
    sustained closed-loop load — 16 in-flight clients issuing 1024^2
    4-channel tile renders against the real app for a fixed window.

    Every request varies its channel windows, so each is a DISTINCT
    render (no byte-cache hit); raw tiles stay device-resident after
    first touch — the honest warm interactive posture.  Both wire
    engines are measured and the better one carries the number.

    Returns (tiles/s, per-engine dict) or (None, {}) if the app stack
    cannot boot here."""
    import asyncio
    import os
    import tempfile

    from omero_ms_image_region_tpu.flagship import synthetic_wsi_tiles
    from omero_ms_image_region_tpu.io.store import build_pyramid
    from omero_ms_image_region_tpu.server.config import (
        AppConfig, BatcherConfig, RawCacheConfig, RendererConfig)

    from omero_ms_image_region_tpu.services.cache import CacheConfig

    with tempfile.TemporaryDirectory() as tmp:
        planes = synthetic_wsi_tiles(rng, 4, 1, 4096, 4096).reshape(
            4, 1, 4096, 4096)
        build_pyramid(planes, os.path.join(tmp, "1"), n_levels=1)
        per_engine = {}
        for engine in ("sparse", "huffman"):
            config = AppConfig(
                data_dir=tmp,
                # Byte caches ON (the serving posture): the throughput
                # window's k-varied requests never repeat a key, so the
                # headline is unchanged, and the warm-repeat probe can
                # prove the acceptance path (second identical request
                # answers from the byte cache with no device span).
                caches=CacheConfig.enabled_all(),
                batcher=BatcherConfig(enabled=True, linger_ms=3.0),
                raw_cache=RawCacheConfig(enabled=True, prefetch=False),
                renderer=RendererConfig(cpu_fallback_max_px=0,
                                        jpeg_engine=engine))
            per_engine[engine] = asyncio.run(_service_run(config))
        best = max(v[0] for v in per_engine.values())
        return best, per_engine


async def _service_run(config, concurrency: int = 16,
                       duration_s: float = 8.0, grid: int = 4,
                       tile_edge: int = 1024, channels: int = 4,
                       fmt: str = "jpeg"):
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from omero_ms_image_region_tpu.server.app import create_app
    from omero_ms_image_region_tpu.utils.stopwatch import (
        REGISTRY as _REG)

    app = create_app(config)
    client = TestClient(TestServer(app))
    await client.start_server()
    try:
        seq = 0
        colors = ("FF0000", "00FF00", "0000FF", "FFFF00")

        def url(i, k):
            x, y = i % grid, (i // grid) % grid
            # k-varied windows: every request is a distinct render of
            # the SAME device-resident raw tile.  k comes from a shared
            # monotone counter (period 5000 — far beyond any realistic
            # request count in the window), so no (tile, window) pair
            # repeats.
            w = 20000 + (k % 5000) * 9
            chans = ",".join(
                f"{c + 1}|0:{w - 1000 * c}${colors[c % len(colors)]}"
                for c in range(channels))
            return (f"/webgateway/render_image_region/1/0/0"
                    f"?tile=0,{x},{y},{tile_edge},{tile_edge}"
                    f"&format={fmt}&m=c&c={chans}")
        # Warm: stage raw tiles into HBM + compile both grid shapes.
        resps = await asyncio.gather(
            *(client.get(url(i, i)) for i in range(grid * grid)))
        assert all(r.status == 200 for r in resps)
        snap0 = _REG.snapshot()
        t_stop = time.perf_counter() + duration_s
        done = 0
        failed = 0
        latencies_ms: list = []
        first_byte_ms: list = []

        async def worker(i: int) -> None:
            nonlocal done, seq, failed
            while time.perf_counter() < t_stop:
                seq += 1
                t_req = time.perf_counter()
                r = await client.get(url(i, 16 + seq))
                # First body bytes (the progressive-wire headline),
                # then the rest: with streaming on, chunked responses
                # surface the first tile bytes before the batch tail.
                await r.content.readany()
                t_first = time.perf_counter()
                await r.read()
                if r.status == 200:
                    done += 1
                    first_byte_ms.append((t_first - t_req) * 1000.0)
                    latencies_ms.append(
                        (time.perf_counter() - t_req) * 1000.0)
                else:
                    # Count it (failures don't add to done) and only
                    # fail the window when errors aren't rare.
                    failed += 1
                    if failed > 5:
                        raise AssertionError(
                            f"service window: {failed} failed requests "
                            f"(last status {r.status})")

        t0 = time.perf_counter()
        # return_exceptions: one worker's failure must not strand the
        # other 15 mid-request while the client closes under them —
        # drain everyone (bounded by t_stop), then surface the error.
        results = await asyncio.gather(
            *(worker(i) for i in range(concurrency)),
            return_exceptions=True)
        errors = [r for r in results if isinstance(r, BaseException)]
        if errors:
            raise errors[0]
        wall_s = time.perf_counter() - t0
        tps = done / wall_s
        p50 = (statistics.median(latencies_ms) if latencies_ms
               else None)
        extras = await _hot_path_probes(app, client, url, seq,
                                        _REG.snapshot(), snap0, wall_s)
        extras["p50_first_tile_byte_ms"] = (
            round(statistics.median(first_byte_ms), 2)
            if first_byte_ms else None)
        return tps, p50, extras
    finally:
        await client.close()


async def _hot_path_probes(app, client, url, seq, snap1, snap0,
                           wall_s):
    """Dedup / plane-cache / overlap probes run right after a service
    window (same app instance, counters still live).

    * ``overlap_efficiency`` — device-execute span coverage of the
      window wall clock (exec_total_ms / wall_ms): 1.0 means the device
      never idled behind the fetch/stage half of the two-stage group
      pipeline; a regression back to serial fetch->render shows up as
      this falling with tiles/s.
    * ``dedup_hit_rate`` — of a burst of 8 concurrent IDENTICAL
      requests, the fraction coalesced by the single-flight table.
    * ``warm_repeat_cached`` — a repeated identical request answers
      from the byte cache with ZERO new device dispatches (the
      acceptance criterion's warm repeated-tile path).
    * ``planecache_hits/misses`` — content-digest staging skips.
    """
    import asyncio

    from omero_ms_image_region_tpu.server.app import SERVICES_KEY

    def total_ms(snap, name):
        return snap.get(name, {}).get("total_ms", 0.0)

    exec_ms = (total_ms(snap1, "Renderer.renderAsPackedInt.batch")
               - total_ms(snap0, "Renderer.renderAsPackedInt.batch"))
    stage_ms = (total_ms(snap1, "batcher.stage")
                - total_ms(snap0, "batcher.stage"))
    extras = {
        "overlap_efficiency": (round(exec_ms / (wall_s * 1000.0), 3)
                               if wall_s > 0 else None),
        "stage_ms_total": round(stage_ms, 1),
        "exec_ms_total": round(exec_ms, 1),
        "dedup_hit_rate": None,
        "warm_repeat_cached": None,
        "planecache_hits": None,
        "planecache_misses": None,
    }
    services = app[SERVICES_KEY]
    if services is None:
        return extras
    raw_cache = getattr(services, "raw_cache", None)
    if raw_cache is not None and hasattr(raw_cache, "plane_hits"):
        extras["planecache_hits"] = raw_cache.plane_hits
        extras["planecache_misses"] = raw_cache.plane_misses
    single_flight = getattr(services, "single_flight", None)
    renderer = services.renderer
    # Concurrent-identical burst: one render identity, 8 in flight.
    burst_url = url(0, seq + 2500)
    burst = 8
    hits0 = single_flight.hits if single_flight is not None else 0
    resps = await asyncio.gather(*(client.get(burst_url)
                                   for _ in range(burst)))
    bodies = [await r.read() for r in resps]
    if all(r.status == 200 for r in resps) and len(set(bodies)) == 1:
        if single_flight is not None:
            extras["dedup_hit_rate"] = round(
                (single_flight.hits - hits0) / burst, 3)
        # Warm repeat: the identical request again, now byte-cached —
        # zero new device dispatches proves no wire/device span ran.
        dispatched0 = getattr(renderer, "batches_dispatched", None)
        r = await client.get(burst_url)
        body = await r.read()
        extras["warm_repeat_cached"] = bool(
            r.status == 200 and body == bodies[0]
            and (dispatched0 is None
                 or renderer.batches_dispatched == dispatched0))
    return extras


def _overhead_table(n: int = 2000) -> dict:
    """ns/op of each cross-cutting feature's HOT-PATH guard cost —
    the per-request/per-tile tax of tracing, cost accounting, deadline
    checks, admission control and the disk write-behind enqueue,
    measured as tight micro-loops over the exact calls the serving
    path makes.

    This is the pay-for-what-you-use ledger for the feature layers
    PRs 1-5 added: each entry must stay ns-to-µs scale (the smoke gate
    asserts a budget in tests/test_bench_smoke.py), so a refactor that
    quietly puts a lock round-trip, a directory scan or a JSON encode
    on the hot path fails tier-1 instead of surfacing as the next
    BENCH round's -10%.
    """
    import queue as _queue
    import tempfile

    from omero_ms_image_region_tpu.server.admission import (
        AdmissionController)
    from omero_ms_image_region_tpu.services.diskcache import (
        DiskByteCache)
    from omero_ms_image_region_tpu.utils import telemetry, transient
    from omero_ms_image_region_tpu.utils.stopwatch import (
        REGISTRY as _REG)

    def per_op(fn) -> float:
        fn()                                   # warm
        t0 = time.perf_counter_ns()
        for _ in range(n):
            fn()
        return round((time.perf_counter_ns() - t0) / n, 1)

    out = {}
    with telemetry.trace_scope(telemetry.new_trace_id(),
                               "bench.overhead"):
        # One stage span landing on a live trace's waterfall (the
        # stopwatch registry + histogram + trace attach).
        out["trace"] = per_op(
            lambda: _REG.record("bench.overhead", 0.01))
        # One batched cost-ledger flush (two fields, one lock).
        out["ledger"] = per_op(
            lambda: telemetry.add_costs({"device_ms": 0.01,
                                         "stage_ms": 0.01}))
        with transient.deadline_scope(30000.0):
            out["deadline"] = per_op(
                lambda: transient.check_deadline("bench"))
    adm = AdmissionController(max_queue=64)

    def admit_release():
        t = adm.admit()
        adm.release(t)

    out["admission"] = per_op(admit_release)
    with tempfile.TemporaryDirectory() as tmp:
        cache = DiskByteCache(tmp, max_bytes=1 << 20)

        def write_behind():
            # The request thread's share of a disk-cache set: enqueue
            # onto the bounded queue (a full queue drops + counts —
            # also the request thread's cost, never a block).
            try:
                cache._queue.put_nowait(("k", b"v"))
            except _queue.Full:
                telemetry.PERSIST.count_disk_write(dropped=True)
            try:
                cache._queue.get_nowait()
            except _queue.Empty:
                pass

        out["write_behind"] = per_op(write_behind)
    # The perf sentinel's per-request tax: one bounded-vocabulary key
    # probe + one sketch insert (bisect over ~350 bucket bounds).
    from omero_ms_image_region_tpu.server.sentinel import SentinelEngine
    eng = SentinelEngine(member="bench", bundle_dir="")
    out["sentinel"] = per_op(
        lambda: eng.observe("render_image_region", 65536, 12.5))
    return out


def _wire_smoke() -> dict:
    """Wire-transport probes at smoke scale (protocol v3): a REAL
    frontend -> sidecar hop over a unix socket with coalescing,
    chunked streaming and the same-host shm ring live.

    Three measurements, one JSON block merged into the smoke line:

    * ``p50_first_tile_byte_ms`` vs ``p50_batch_complete_ms`` — bursts
      of 4 concurrent distinct renders of one tile co-batch into one
      group; first-tile-out + chunk frames must land a request's first
      body byte strictly before the burst's last request completes
      (the v2 barrier settled everyone together at the tail).
    * ``wire_frames_per_flush`` — mean frames per vectored flush
      across the window; > 1 under concurrent load proves the
      coalescer amortizes syscalls/RTTs.
    * ``shm_ring_hit_rate`` + ``shm_upload_mb_per_sec`` vs
      ``socket_upload_mb_per_sec`` — the same bulk ``stage_planes``
      upload through a ring-negotiated client and a ring-disabled one
      (fresh content each, so digest dedup cannot short-circuit).
    """
    import asyncio
    import os
    import tempfile

    from omero_ms_image_region_tpu.flagship import synthetic_wsi_tiles
    from omero_ms_image_region_tpu.io.store import build_pyramid

    rng = np.random.default_rng(11)
    with tempfile.TemporaryDirectory() as tmp:
        planes = synthetic_wsi_tiles(rng, 2, 1, 512, 512).reshape(
            2, 1, 512, 512)
        build_pyramid(planes, os.path.join(tmp, "1"), n_levels=1)
        sock = os.path.join(tmp, "wire.sock")
        return asyncio.run(_wire_run(tmp, sock, rng))


async def _wire_run(tmp: str, sock: str, rng) -> dict:
    import asyncio
    import os

    from aiohttp.test_utils import TestClient, TestServer

    from omero_ms_image_region_tpu.server.app import create_app
    from omero_ms_image_region_tpu.server.config import (
        AppConfig, BatcherConfig, RawCacheConfig, RendererConfig,
        SidecarConfig, WireConfig)
    from omero_ms_image_region_tpu.server.sidecar import (SidecarClient,
                                                          run_sidecar)
    from omero_ms_image_region_tpu.utils import telemetry

    telemetry.WIRE.reset()
    sidecar_cfg = AppConfig(
        data_dir=tmp,
        # linger long enough that an 8-way burst forms ONE group (the
        # batch whose barrier the streaming path must beat — a bigger
        # group means a longer per-tile encode tail to get ahead of).
        batcher=BatcherConfig(enabled=True, linger_ms=15.0,
                              max_batch=8),
        raw_cache=RawCacheConfig(enabled=True, prefetch=False),
        renderer=RendererConfig(cpu_fallback_max_px=0))
    task = asyncio.create_task(run_sidecar(sidecar_cfg, sock))
    for _ in range(600):
        if task.done():
            raise RuntimeError(f"wire smoke sidecar died: "
                               f"{task.exception()!r}")
        if os.path.exists(sock):
            break
        await asyncio.sleep(0.05)
    front_cfg = AppConfig(data_dir=tmp,
                          sidecar=SidecarConfig(socket=sock,
                                                role="frontend"))
    app = create_app(front_cfg)
    client = TestClient(TestServer(app))
    await client.start_server()
    try:
        colors = ("FF0000", "00FF00")

        def url(k: int) -> str:
            # k-varied windows: 4 DISTINCT renders of the same raw
            # tile (no byte-cache or single-flight short-circuit), all
            # in one bucket/batch key.
            w = 20000 + (k % 5000) * 9
            chans = ",".join(
                f"{c + 1}|0:{w - 1000 * c}${colors[c]}"
                for c in range(2))
            return (f"/webgateway/render_image_region/1/0/0"
                    f"?tile=0,0,0,256,256&format=jpeg&m=c&c={chans}")

        seq_box = [100]

        async def one(cl, k: int):
            t0 = time.perf_counter()
            r = await cl.get(url(k))
            await r.content.readany()
            t_first = time.perf_counter()
            await r.read()
            return (r.status, (t_first - t0) * 1000.0,
                    (time.perf_counter() - t0) * 1000.0)

        async def burst_stats(cl, n_bursts: int):
            # Warm: stage the tile + compile the burst's group shape
            # (the second stack reuses the in-process jit caches).
            warm = await asyncio.gather(*(cl.get(url(seq_box[0] + i))
                                          for i in range(8)))
            assert all(r.status == 200 for r in warm), \
                [r.status for r in warm]
            for r in warm:
                await r.read()
            seq_box[0] += 8
            firsts, completes = [], []
            for _ in range(n_bursts):
                rs = await asyncio.gather(*(one(cl, seq_box[0] + j)
                                            for j in range(8)))
                seq_box[0] += 8
                assert all(s == 200 for s, _, _ in rs), rs
                # The burst's first body byte vs its batch completion
                # (last member fully answered) — the gap IS the
                # first-tile-out + chunk-forwarding win.
                firsts.append(min(f for _, f, _ in rs))
                completes.append(max(t for _, _, t in rs))
            return firsts, completes

        firsts, batch_completes = await burst_stats(client, 12)

        # Upload-path A/B on the same live sidecar: ring-negotiated vs
        # ring-disabled client shipping the SAME MB-scale bodies.  The
        # bodies ride ``ping`` requests (whose body the server reads
        # and discards), so this isolates the WIRE leg the ring
        # replaces — ``stage_planes`` end-to-end would be dominated by
        # the server's digest + device staging, identical both ways
        # (and already measured by ``raw_upload_mb_per_sec``).
        body = rng.integers(0, 60000, size=(1024, 1024)) \
            .astype(np.uint16).tobytes()               # 2 MiB
        n_bodies = 8
        ring_client = SidecarClient(sock)
        sock_client = SidecarClient(sock, wire=WireConfig(ring_bytes=0))
        try:
            await ring_client.call("ping", {})     # handshakes +
            await sock_client.call("ping", {})     # connection setup

            async def upload_window(cl) -> float:
                t0 = time.perf_counter()
                rs = await asyncio.gather(
                    *(cl.call("ping", {}, body=body)
                      for _ in range(n_bodies)))
                assert all(s == 200 for s, _ in rs)
                return (n_bodies * len(body) / 1e6
                        / (time.perf_counter() - t0))

            rates = {"socket": 0.0, "ring": 0.0}
            # Interleaved best-of-3 per path: single-rep ordering (and
            # this box's scheduler) otherwise decides the A/B.
            for _ in range(3):
                for name, cl in (("socket", sock_client),
                                 ("ring", ring_client)):
                    rates[name] = max(rates[name],
                                      await upload_window(cl))
        finally:
            await ring_client.close()
            await sock_client.close()

        # Barrier A/B (informational, not gated: the CPU-smoke margin
        # is a few ms and CI jitter would flake a strict ordering):
        # the same bursts against a streaming-OFF stack, where the v2
        # barrier settles everyone at the batch tail.  The mechanism
        # itself is gated deterministically in
        # tests/test_wire_v3.py::test_first_tile_out_settles_before_barrier.
        p50_first_barrier = None
        sock2 = sock + ".barrier"
        barrier_cfg = AppConfig(
            data_dir=tmp,
            batcher=BatcherConfig(enabled=True, linger_ms=15.0,
                                  max_batch=8),
            raw_cache=RawCacheConfig(enabled=True, prefetch=False),
            renderer=RendererConfig(cpu_fallback_max_px=0),
            wire=WireConfig(streaming=False))
        task2 = asyncio.create_task(run_sidecar(barrier_cfg, sock2))
        client2 = None
        try:
            for _ in range(600):
                if task2.done():
                    raise RuntimeError(f"barrier sidecar died: "
                                       f"{task2.exception()!r}")
                if os.path.exists(sock2):
                    break
                await asyncio.sleep(0.05)
            app2 = create_app(AppConfig(
                data_dir=tmp,
                sidecar=SidecarConfig(socket=sock2, role="frontend"),
                wire=WireConfig(streaming=False)))
            client2 = TestClient(TestServer(app2))
            await client2.start_server()
            b_firsts, _ = await burst_stats(client2, 6)
            p50_first_barrier = round(statistics.median(b_firsts), 2)
        except Exception:
            pass     # informational only: never fail the smoke on it
        finally:
            if client2 is not None:
                await client2.close()
            task2.cancel()
            try:
                await task2
            except (asyncio.CancelledError, Exception):
                pass

        wire = telemetry.WIRE
        hit_rate = wire.ring_hit_rate()
        return {
            "p50_first_tile_byte_ms": round(
                statistics.median(firsts), 2),
            "p50_batch_complete_ms": round(
                statistics.median(batch_completes), 2),
            "p50_first_tile_byte_ms_barrier": p50_first_barrier,
            "wire_frames_per_flush": round(
                wire.frames_per_flush() or 0.0, 3),
            "shm_ring_hit_rate": (round(hit_rate, 3)
                                  if hit_rate is not None else None),
            "shm_upload_mb_per_sec": round(rates["ring"], 1),
            "socket_upload_mb_per_sec": round(rates["socket"], 1),
            "wire_streams": wire.streams,
            "wire_ring_negotiated": wire.ring_negotiated,
        }
    finally:
        await client.close()
        task.cancel()
        try:
            await task
        except (asyncio.CancelledError, Exception):
            pass


def _fleet_smoke(exec_ms: float = 150.0, grid: int = 4,
                 tile_edge: int = 128, variants: int = 3) -> dict:
    """Fleet-serving smoke probe: the data-parallel router over N=4
    virtual members vs the same burst through ONE member.

    Each member is a REAL serving stack — its own renderer + its own
    ``DeviceRawCache`` shard over a shared pyramid — plus a calibrated
    virtual device-execute occupancy (``exec_ms`` of lane time per
    render).  On this 2-core CI host the chips' compute parallelism
    cannot exist, so the sleep stands in for the member's device
    service time; what the probe then honestly measures is that the
    ROUTING layer scales — consistent-hash spread, per-member lanes,
    stealing under skew — with zero added serialization, and that the
    HBM tier SHARDS: after a mixed-digest burst each staged plane is
    resident on exactly ONE member (duplicates asserted 0 in tier-1;
    total residency ~= the working set, minus any plane whose every
    render happened to be stolen — stealing never adopts).  The real
    1->8 chip curve is the MULTICHIP record's job
    (``__graft_entry__.fleet_scaling_curve``).
    """
    import asyncio
    import os
    import tempfile

    from omero_ms_image_region_tpu.flagship import synthetic_wsi_tiles
    from omero_ms_image_region_tpu.io.store import build_pyramid
    from omero_ms_image_region_tpu.parallel.fleet import (
        FleetImageHandler, FleetRouter, LocalMember,
        build_local_members)
    from omero_ms_image_region_tpu.server.admission import (
        AdmissionController)
    from omero_ms_image_region_tpu.server.app import build_services
    from omero_ms_image_region_tpu.server.config import (
        AppConfig, BatcherConfig, RawCacheConfig, RendererConfig)
    from omero_ms_image_region_tpu.server.ctx import ImageRegionCtx
    from omero_ms_image_region_tpu.server.singleflight import (
        SingleFlight)
    from omero_ms_image_region_tpu.utils import telemetry

    rng = np.random.default_rng(13)
    exec_s = exec_ms / 1000.0

    class VirtualDeviceMember(LocalMember):
        """A fleet member whose device-execute service time is the
        calibrated occupancy above — the render itself (read, stage,
        HBM cache, render kernel, encode) is entirely real."""

        async def render(self, ctx, adopt_cache=True):
            data = await super().render(ctx, adopt_cache)
            await asyncio.sleep(exec_s)
            return data

    def urls(k_base: int):
        out = []
        for v in range(variants):
            for x in range(grid):
                for y in range(grid):
                    w = 20000 + (k_base + v) * 700
                    out.append({
                        "imageId": "1", "theZ": "0", "theT": "0",
                        "tile": f"0,{x},{y},{tile_edge},{tile_edge}",
                        "format": "png", "m": "c",
                        "c": f"1|0:{w}$FF0000,2|0:{w - 900}$00FF00",
                    })
        return out

    async def run_fleet(tmp: str, n_members: int) -> dict:
        config = AppConfig(
            data_dir=tmp,
            batcher=BatcherConfig(enabled=False),
            raw_cache=RawCacheConfig(enabled=True, prefetch=False),
            renderer=RendererConfig(cpu_fallback_max_px=0))
        services = build_services(config)
        members = build_local_members(config, services, n_members)
        members = [VirtualDeviceMember(
            m.name, m.handler, m.services,
            down_cooldown_s=m.down_cooldown_s,
            byte_cache_prechecked=m.byte_cache_prechecked)
            for m in members]
        router = FleetRouter(members, lane_width=2,
                             steal_min_backlog=2)
        handler = FleetImageHandler(
            router, single_flight=SingleFlight(),
            admission=AdmissionController(512, renderer=router),
            base_services=services)
        before = telemetry.FLEET.totals()
        try:
            ctxs = [ImageRegionCtx.from_params(p) for p in urls(16)]
            # Warm the compile (shared in-process jit cache) outside
            # the window; the plane reads/staging stay in it.
            await handler.render_image_region(
                ImageRegionCtx.from_params(urls(900)[0]))
            t0 = time.perf_counter()
            out = await asyncio.gather(
                *(handler.render_image_region(c) for c in ctxs))
            wall = time.perf_counter() - t0
            assert all(out)
            after = telemetry.FLEET.totals()
            report = router.shard_report()
            return {
                "tps": len(ctxs) / wall,
                "shard": report,
                "routed": after["routed"] - before["routed"],
                "stolen": after["stolen"] - before["stolen"],
            }
        finally:
            await router.close()
            services.pixels_service.close()

    with tempfile.TemporaryDirectory() as tmp:
        planes = synthetic_wsi_tiles(rng, 2, 1, grid * tile_edge,
                                     grid * tile_edge).reshape(
            2, 1, grid * tile_edge, grid * tile_edge)
        build_pyramid(planes, os.path.join(tmp, "1"), n_levels=1)
        single = asyncio.run(run_fleet(tmp, 1))
        fleet = asyncio.run(run_fleet(tmp, 4))
    working_set = grid * grid * 2     # one plane a channel of a tile
    return {
        "fleet_members": 4,
        "fleet_virtual_exec_ms": exec_ms,
        "fleet_tiles_per_sec": round(fleet["tps"], 2),
        "fleet_single_member_tiles_per_sec": round(single["tps"], 2),
        "fleet_speedup": round(fleet["tps"] / single["tps"], 2),
        "fleet_working_set_planes": working_set,
        # Sharded, not duplicated: every plane of the working set
        # resident on exactly one member after the mixed-digest burst.
        "fleet_resident_planes": fleet["shard"]["resident_digests"],
        "fleet_duplicate_staged_planes":
            fleet["shard"]["duplicate_digests"],
        "fleet_member_planes": fleet["shard"]["members"],
        "fleet_routed_total": fleet["routed"],
        "fleet_stolen_total": fleet["stolen"],
    }


def bench_smoke(duration_s: float = 1.5):
    """Hot-path regression gate at smoke scale: CPU, small shapes, <60 s.

    The FULL app — routes, ctx parsing, byte caches, single-flight
    dedup, two-stage batcher pipeline, device plane cache — over a
    small synthetic pyramid (2-channel 512^2, 256^2 png tiles, so
    compiles stay in the seconds on the host platform).  Prints ONE
    JSON line mirroring the service-level keys; wired into tier-1
    (tests/test_bench_smoke.py) so a cache or pipeline regression fails
    tests instead of waiting for the next BENCH round.
    """
    import asyncio
    import os
    import tempfile

    from omero_ms_image_region_tpu.flagship import synthetic_wsi_tiles
    from omero_ms_image_region_tpu.io.store import build_pyramid
    from omero_ms_image_region_tpu.server.config import (
        AppConfig, BatcherConfig, RawCacheConfig, RendererConfig)
    from omero_ms_image_region_tpu.services.cache import CacheConfig

    t_start = time.perf_counter()
    # The gate below judges THIS window's ledger: the top-K table is
    # process-global, and a stale expensive request from whatever this
    # interpreter ran earlier (tier-1 shares it) must not stand in for
    # the smoke run's attribution.
    from omero_ms_image_region_tpu.utils import telemetry
    telemetry.COST_TOPK.reset()
    rng = np.random.default_rng(7)
    with tempfile.TemporaryDirectory() as tmp:
        planes = synthetic_wsi_tiles(rng, 2, 1, 512, 512).reshape(
            2, 1, 512, 512)
        build_pyramid(planes, os.path.join(tmp, "1"), n_levels=1)
        config = AppConfig(
            data_dir=tmp,
            caches=CacheConfig.enabled_all(),
            batcher=BatcherConfig(enabled=True, linger_ms=2.0),
            raw_cache=RawCacheConfig(enabled=True, prefetch=False),
            renderer=RendererConfig(cpu_fallback_max_px=0))
        tps, p50, extras = asyncio.run(_service_run(
            config, concurrency=4, duration_s=duration_s, grid=2,
            tile_edge=256, channels=2, fmt="png"))
    # Wire-transport probes (protocol v3): split posture over a unix
    # socket — first-byte vs batch barrier, frames per vectored flush,
    # and the shm-ring vs socket upload A/B.
    wire = _wire_smoke()
    # Fleet-serving probes: N=4 virtual members vs one member over the
    # same mixed-digest burst — routing-layer scaling + HBM sharding
    # (gated in tests/test_bench_smoke.py).
    fleet = _fleet_smoke()
    # Cost-ledger liveness: the attribution layer must have recorded
    # WHERE the smoke window's time went, request by request — a
    # refactor that silently drops the ledger fails the gate here.
    top = telemetry.COST_TOPK.snapshot()
    cost_keys = sorted(top[0]["cost"].keys()) if top else []
    assert {"device_ms", "queue_ms", "total_ms",
            "wire_bytes"} <= set(cost_keys), \
        f"cost ledger missing fields: {cost_keys}"
    out = {
        "metric": "smoke_hotpath_tiles_per_sec",
        "value": round(tps, 2),
        "unit": "tiles/s",
        "p50_ms": _opt_round(p50, 2),
        "dedup_hit_rate": extras.get("dedup_hit_rate"),
        "warm_repeat_cached": extras.get("warm_repeat_cached"),
        "overlap_efficiency": extras.get("overlap_efficiency"),
        "planecache_hits": extras.get("planecache_hits"),
        "planecache_misses": extras.get("planecache_misses"),
        "cost_ledger_keys": cost_keys,
        # Per-feature hot-path tax (ns/op): trace span record, cost
        # ledger flush, deadline check, admission admit+release, disk
        # write-behind enqueue.  Gated in tests/test_bench_smoke.py so
        # the feature layers stay pay-for-what-you-use.
        "overhead_ns_per_op": (_overheads := _overhead_table()),
        # The perf sentinel's per-request tax, named at top level for
        # the record diff (same number as overhead_ns_per_op.sentinel;
        # the <100µs/op budget gate lives in tests/test_bench_smoke.py).
        "sentinel_overhead_ns_per_op": _overheads.get("sentinel"),
        # Wire v3 probes (split posture, streaming + coalescing + shm
        # ring live) — gated in tests/test_bench_smoke.py.
        **wire,
        # Fleet probes (virtual members; see _fleet_smoke) — gated in
        # tests/test_bench_smoke.py.
        **fleet,
        "elapsed_s": round(time.perf_counter() - t_start, 1),
    }
    print(json.dumps(out))
    return out


def _jain_index(shares) -> float:
    """Jain's fairness index over per-session service shares:
    (sum x)^2 / (n * sum x^2) — 1.0 = perfectly even, 1/n = one
    session took everything."""
    xs = [max(0.0, float(x)) for x in shares]
    n = len(xs)
    if n == 0:
        return 1.0
    total = sum(xs)
    if total <= 0:
        return 1.0
    return (total * total) / (n * sum(x * x for x in xs))


def _p99(samples_ms) -> float:
    ordered = sorted(samples_ms)
    return ordered[int(0.99 * (len(ordered) - 1))]


def bench_sessions_smoke(viewers: int = 6, tiles_per_viewer: int = 32,
                         warmup_tiles: int = 6, grid: int = 8,
                         tile_edge: int = 64, exec_ms: float = 20.0,
                         bulk_exec_ms: float = 120.0,
                         bulk_concurrency: int = 6):
    """Multi-user serving gate (``bench.py --smoke --sessions``,
    tier-1 via tests/test_bench_smoke.py): "millions of users" as a
    TESTED scenario at smoke scale.

    Three deterministic legs over one fleet stack (2 members, virtual
    device occupancy per the `_fleet_smoke` idiom — ``exec_ms`` of
    lane time per interactive tile, ``bulk_exec_ms`` per bulk render):

    * **baseline** — N panning viewer sessions, no bulk traffic: the
      no-bulk per-session p99 floor.
    * **qos on** — the same viewers plus ONE hostile bulk client
      (full-plane renders, ``bulk_concurrency`` in flight, open-loop)
      with per-session token buckets and the weighted two-class
      dequeue live.  The gate: worst-session interactive p99 within
      2x the baseline, Jain's fairness index over per-session device
      time >= 0.8, and the hostile's overrun shed 503 with the
      ``"fairness"`` reason.
    * **qos off** — the identical hostile scenario with buckets off
      and FIFO dequeue: the A/B leg that PROVES the mechanism (both
      gates regress to failure — one bulk client convoys the fleet).

    A fourth leg replays a deterministic single-session pan trace with
    the predictive viewport prefetcher live (fleet-aware: predictions
    stage into the owning member's HBM shard) and reports the
    predictive hit rate + duplicate-staged count.
    """
    import asyncio
    import os
    import tempfile

    from omero_ms_image_region_tpu.flagship import synthetic_wsi_tiles
    from omero_ms_image_region_tpu.io.store import build_pyramid
    from omero_ms_image_region_tpu.parallel.fleet import (
        FleetImageHandler, FleetRouter, LocalMember,
        build_local_members)
    from omero_ms_image_region_tpu.server.admission import (
        AdmissionController, SessionTokenBuckets)
    from omero_ms_image_region_tpu.server.app import build_services
    from omero_ms_image_region_tpu.server.config import (
        AppConfig, BatcherConfig, RawCacheConfig, RendererConfig)
    from omero_ms_image_region_tpu.server.ctx import ImageRegionCtx
    from omero_ms_image_region_tpu.server.errors import OverloadedError
    from omero_ms_image_region_tpu.utils import telemetry

    t_start = time.perf_counter()
    rng = np.random.default_rng(23)
    exec_s = exec_ms / 1000.0
    bulk_exec_s = bulk_exec_ms / 1000.0

    from omero_ms_image_region_tpu.server.pressure import is_bulk

    class VirtualDeviceMember(LocalMember):
        """Calibrated virtual device occupancy per QoS class: the
        render itself (read, stage, HBM cache, kernel, encode) is
        entirely real; the sleep models the device service time a
        2-core CI host cannot exhibit."""

        async def render(self, ctx, adopt_cache=True):
            data = await super().render(ctx, adopt_cache)
            await asyncio.sleep(bulk_exec_s if is_bulk(ctx)
                                else exec_s)
            return data

    def tile_params(x, y, w):
        return {
            "imageId": "1", "theZ": "0", "theT": "0",
            "tile": f"0,{x},{y},{tile_edge},{tile_edge}",
            "format": "png", "m": "c",
            "c": f"1|0:{w}$FF0000,2|0:{w - 900}$00FF00",
        }

    def bulk_params(w):
        return {
            "imageId": "1", "theZ": "0", "theT": "0",
            "format": "png", "m": "c",
            "c": f"1|0:{w}$FF0000",
        }

    def build_stack(tmp, qos_on: bool, prefetch: bool = False):
        from omero_ms_image_region_tpu.server.config import (
            SessionsConfig)
        config = AppConfig(
            data_dir=tmp,
            batcher=BatcherConfig(enabled=False),
            raw_cache=RawCacheConfig(enabled=True, prefetch=prefetch),
            renderer=RendererConfig(cpu_fallback_max_px=0))
        if prefetch:
            # The viewport model only builds with the session tier on
            # (anonymous traffic would share one trajectory, so
            # build_services gates it); the prefetch leg replays a
            # keyed session.  Traffic legs stay sessions-off at the
            # member layer — THIS stack's own FleetImageHandler
            # carries the buckets under test, and default member
            # buckets would meter the hostile even in the qos-off
            # A/B leg.
            config.sessions = SessionsConfig(enabled=True)
        services = build_services(config)
        members = [VirtualDeviceMember(
            m.name, m.handler, m.services,
            down_cooldown_s=m.down_cooldown_s,
            byte_cache_prechecked=m.byte_cache_prechecked)
            for m in build_local_members(config, services, 2)]
        router = FleetRouter(members, lane_width=2,
                             steal_min_backlog=0,
                             qos_weight=4 if qos_on else 0)
        buckets = None
        if qos_on:
            # Sized so the meter separates the CLASSES, not the load:
            # a panning viewer (cost 1, ~30-50 serial tiles/s) never
            # touches its budget, while one full-plane render costs
            # the ENTIRE burst — the hostile is held to ~1 bulk/s, so
            # the mesh lane's two device lanes are never both bulk-
            # occupied and interactive head-of-line blocking is
            # bounded by a single in-flight bulk render.
            buckets = SessionTokenBuckets(
                refill_per_s=100.0, burst=100.0, bulk_cost=100.0)
        handler = FleetImageHandler(
            router,
            admission=AdmissionController(4096, renderer=router,
                                          session_buckets=buckets),
            base_services=services)
        if prefetch and services.prefetcher is not None:
            # The production combined-fleet wiring (server.app): one
            # shared prefetcher, predictions staged into the OWNING
            # member's shard.
            services.prefetcher.cache_for_route = \
                router.cache_for_route
            for member in members[1:]:
                member.services.prefetcher = services.prefetcher
        return config, services, members, router, handler

    async def run_traffic_leg(tmp, qos_on: bool,
                              hostile: bool) -> dict:
        _, services, members, router, handler = build_stack(
            tmp, qos_on)
        try:
            # Warm both compile shapes outside every measured window.
            await handler.render_image_region(
                ImageRegionCtx.from_params(tile_params(0, 0, 61000)))
            await handler.render_image_region(
                ImageRegionCtx.from_params(bulk_params(61000)))

            measuring = asyncio.Event()
            done = asyncio.Event()
            latencies = {v: [] for v in range(viewers)}
            served_ms = {f"viewer-{v}": 0.0 for v in range(viewers)}
            served_ms["bulk-hog"] = 0.0
            # Per-session measuring window [t_first, t_last]: shares
            # are judged as device time per wall-second of EACH
            # session's own window, so a starved viewer (same tile
            # count, longer wall clock) drags the fairness index —
            # equal closed-loop totals cannot mask unfairness.
            windows = {}
            bulk_served = bulk_shed = 0

            async def viewer(v: int):
                # Deterministic pan trace: each session marches along
                # its own row, distinct windows per step (no
                # byte-cache or dedup shortcuts).
                steps = warmup_tiles + tiles_per_viewer
                for step in range(steps):
                    x = step % grid
                    y = (v + step // grid) % grid
                    ctx = ImageRegionCtx.from_params(
                        tile_params(x, y,
                                    22000 + v * 2500 + step * 60))
                    ctx.omero_session_key = f"viewer-{v}"
                    t0 = time.perf_counter()
                    if step == warmup_tiles:
                        measuring.set()
                        windows[f"viewer-{v}"] = [t0, t0]
                    out = await handler.render_image_region(ctx)
                    assert out
                    if step >= warmup_tiles:
                        t1 = time.perf_counter()
                        latencies[v].append((t1 - t0) * 1000.0)
                        served_ms[f"viewer-{v}"] += exec_ms
                        windows[f"viewer-{v}"][1] = t1

            async def bulk_client():
                nonlocal bulk_served, bulk_shed
                seq = 0

                async def one():
                    nonlocal bulk_served, bulk_shed, seq
                    seq += 1
                    ctx = ImageRegionCtx.from_params(
                        bulk_params(30000 + seq * 40))
                    ctx.omero_session_key = "bulk-hog"
                    if measuring.is_set():
                        window = windows.setdefault(
                            "bulk-hog", [time.perf_counter()] * 2)
                        window[1] = time.perf_counter()
                    try:
                        await handler.render_image_region(ctx)
                        if measuring.is_set():
                            bulk_served += 1
                            served_ms["bulk-hog"] += bulk_exec_ms
                            if "bulk-hog" in windows:
                                windows["bulk-hog"][1] = \
                                    time.perf_counter()
                    except OverloadedError:
                        if measuring.is_set():
                            bulk_shed += 1
                        # Hostile: ignores the 1 s Retry-After, but a
                        # floor keeps the gate about QoS, not about
                        # the 2-core CI loop drowning in shed churn
                        # (~120 attempts/s across the 6 streams is
                        # still a hammering client).
                        await asyncio.sleep(0.05)

                pending = set()
                while not done.is_set():
                    while len(pending) < bulk_concurrency:
                        pending.add(asyncio.create_task(one()))
                    finished, pending = await asyncio.wait(
                        pending, timeout=0.02,
                        return_when=asyncio.FIRST_COMPLETED)
                for task in pending:
                    task.cancel()
                await asyncio.gather(*pending,
                                     return_exceptions=True)

            tasks = [asyncio.create_task(viewer(v))
                     for v in range(viewers)]
            hog = (asyncio.create_task(bulk_client()) if hostile
                   else None)
            await asyncio.gather(*tasks)
            done.set()
            if hog is not None:
                await hog
            def rate(key):
                t0, t1 = windows.get(key, (0.0, 0.0))
                return served_ms[key] / max(t1 - t0, 1e-6)

            shares = [rate(f"viewer-{v}") for v in range(viewers)]
            if hostile:
                # The hog's window spans its whole measured activity
                # (sheds included): the rate the fleet actually
                # granted it, not just its completions.
                shares.append(rate("bulk-hog")
                              if "bulk-hog" in windows else 0.0)
            return {
                "p99_ms": max(_p99(latencies[v])
                              for v in range(viewers)),
                "jain": _jain_index(shares),
                "bulk_served": bulk_served,
                "bulk_shed": bulk_shed,
            }
        finally:
            await router.close()
            services.pixels_service.close()

    async def run_prefetch_leg(tmp) -> dict:
        _, services, members, router, handler = build_stack(
            tmp, qos_on=True, prefetch=True)
        prefetcher = services.prefetcher
        try:
            # Deterministic single-session pan: two rows, left to
            # right, velocity (1, 0) — the viewport model should
            # stage each next tile before its request arrives.
            for row in range(2):
                for x in range(grid):
                    ctx = ImageRegionCtx.from_params(
                        tile_params(x, row, 45000 + row * 300 + x))
                    ctx.omero_session_key = "panner"
                    out = await handler.render_image_region(ctx)
                    assert out
                    # Idle device lanes: speculative staging runs
                    # between pan steps, as in a real viewer cadence.
                    await asyncio.to_thread(prefetcher.flush, 2.0)
            report = router.shard_report()
            return {
                "staged": prefetcher.staged,
                "hits": prefetcher.hits,
                "hit_rate": prefetcher.hit_rate(),
                "duplicates": report["duplicate_digests"],
            }
        finally:
            await router.close()
            services.pixels_service.close()

    shed_before = telemetry.RESILIENCE.shed.get("fairness", 0)
    with tempfile.TemporaryDirectory() as tmp:
        planes = synthetic_wsi_tiles(rng, 2, 1, grid * tile_edge,
                                     grid * tile_edge).reshape(
            2, 1, grid * tile_edge, grid * tile_edge)
        build_pyramid(planes, os.path.join(tmp, "1"), n_levels=1)
        baseline = asyncio.run(run_traffic_leg(tmp, qos_on=True,
                                               hostile=False))
        qos_on = asyncio.run(run_traffic_leg(tmp, qos_on=True,
                                             hostile=True))
        qos_off = asyncio.run(run_traffic_leg(tmp, qos_on=False,
                                              hostile=True))
        prefetch = asyncio.run(run_prefetch_leg(tmp))
    fairness_sheds = (telemetry.RESILIENCE.shed.get("fairness", 0)
                      - shed_before)
    out = {
        "metric": "sessions_smoke",
        "sessions_viewers": viewers,
        "sessions_tiles_per_viewer": tiles_per_viewer,
        "sessions_virtual_exec_ms": exec_ms,
        "sessions_bulk_exec_ms": bulk_exec_ms,
        # The headline pair the gate judges: hostile-bulk p99 with the
        # QoS tier live vs the no-bulk floor.
        "sessions_baseline_p99_ms": _opt_round(baseline["p99_ms"], 1),
        "sessions_interactive_p99_ms": _opt_round(qos_on["p99_ms"], 1),
        "sessions_qos_off_p99_ms": _opt_round(qos_off["p99_ms"], 1),
        "sessions_fairness_index": _opt_round(qos_on["jain"], 3),
        "sessions_fairness_index_off": _opt_round(qos_off["jain"], 3),
        "sessions_bulk_served": qos_on["bulk_served"],
        "sessions_bulk_shed": qos_on["bulk_shed"],
        "sessions_fairness_sheds": fairness_sheds,
        # Predictive prefetch over the deterministic pan trace.
        "prefetch_staged_planes": prefetch["staged"],
        "prefetch_hits": prefetch["hits"],
        "prefetch_hit_rate": _opt_round(prefetch["hit_rate"], 3),
        "prefetch_duplicate_staged_planes": prefetch["duplicates"],
        "elapsed_s": round(time.perf_counter() - t_start, 1),
    }
    print(json.dumps(out))
    return out


def bench_overload_smoke(burst: int = 160, exec_ms: float = 40.0,
                         members: int = 2, lane_width: int = 2):
    """Overload-brownout gate at smoke scale (tier-1 via
    tests/test_bench_smoke.py): a ~10x-capacity burst through a real
    fleet handler with the PRESSURE GOVERNOR live must

    * engage brownout ladder steps IN CONFIGURED ORDER (read back
      from the flight recorder's ``pressure.step`` events);
    * keep ZERO 5xx-without-shed (every request either serves or
      sheds 503; nothing errors bare) with a bounded p99;
    * release every step IN REVERSE with hysteresis once the burst
      ends — engage/release exactly once per step, no flapping.

    The members carry a calibrated virtual device occupancy
    (``exec_ms`` of lane time per render, the `_fleet_smoke` idiom)
    so the burst actually QUEUES on this CPU host; the governor's
    queue signal, the ladder walk and the shed/serve accounting are
    all the production code paths.
    """
    import asyncio
    import os
    import tempfile

    from omero_ms_image_region_tpu.flagship import synthetic_wsi_tiles
    from omero_ms_image_region_tpu.io.store import build_pyramid
    from omero_ms_image_region_tpu.parallel.fleet import (
        FleetImageHandler, FleetRouter, LocalMember,
        build_local_members)
    from omero_ms_image_region_tpu.server import pressure
    from omero_ms_image_region_tpu.server.admission import (
        AdmissionController)
    from omero_ms_image_region_tpu.server.app import build_services
    from omero_ms_image_region_tpu.server.config import (
        AppConfig, BatcherConfig, RawCacheConfig, RendererConfig)
    from omero_ms_image_region_tpu.server.ctx import ImageRegionCtx
    from omero_ms_image_region_tpu.server.errors import OverloadedError
    from omero_ms_image_region_tpu.server.singleflight import (
        SingleFlight)
    from omero_ms_image_region_tpu.utils import telemetry

    t_start = time.perf_counter()
    grid, tile_edge = 4, 64
    exec_s = exec_ms / 1000.0
    rng = np.random.default_rng(17)

    class VirtualDeviceMember(LocalMember):
        async def render(self, ctx, adopt_cache=True):
            data = await super().render(ctx, adopt_cache)
            await asyncio.sleep(exec_s)
            return data

    def urls():
        out = []
        variants = -(-burst // (grid * grid))
        for v in range(variants):
            for x in range(grid):
                for y in range(grid):
                    w = 21000 + v * 650
                    out.append({
                        "imageId": "1", "theZ": "0", "theT": "0",
                        "tile": f"0,{x},{y},{tile_edge},{tile_edge}",
                        "format": "png", "m": "c",
                        "c": f"1|0:{w}$FF0000,2|0:{w - 900}$00FF00",
                    })
        return out[:burst]

    async def run(tmp: str) -> dict:
        config = AppConfig(
            data_dir=tmp,
            batcher=BatcherConfig(enabled=False),
            raw_cache=RawCacheConfig(enabled=True, prefetch=False),
            renderer=RendererConfig(cpu_fallback_max_px=0))
        services = build_services(config)
        members = [VirtualDeviceMember(
            m.name, m.handler, m.services,
            down_cooldown_s=m.down_cooldown_s,
            byte_cache_prechecked=m.byte_cache_prechecked)
            for m in build_local_members(config, services, members_n)]
        router = FleetRouter(members, lane_width=lane_width,
                             steal_min_backlog=0)
        handler = FleetImageHandler(
            router, single_flight=SingleFlight(),
            admission=AdmissionController(4 * burst, renderer=router),
            base_services=services)
        pcfg = AppConfig.from_dict({"pressure": {
            "enabled": True, "interval-s": 0.02,
            "queue-high": 4 * members_n * lane_width,
            "queue-low": members_n * lane_width,
            "critical-factor": 1.5,
            "step-hold-ticks": 2, "release-hold-ticks": 2,
        }}).pressure
        governor = pressure.PressureGovernor(
            pcfg,
            pressure.build_actuators(pcfg, services=services),
            {"queue": lambda: float(router.queue_depth())})
        pressure.install(governor)
        # The gate reads the ladder walk back from the flight ring;
        # start it clean (and big enough that burst noise cannot
        # push the pressure.step events off the tape).
        telemetry.FLIGHT.reset()
        telemetry.FLIGHT.configure(4096)

        async def governor_loop():
            while True:
                await asyncio.sleep(pcfg.interval_s)
                governor.tick()

        gov_task = asyncio.create_task(governor_loop())
        ctxs = [ImageRegionCtx.from_params(p) for p in urls()]
        # One warm render outside the window (shared jit compile).
        await handler.render_image_region(ctxs[0])
        latencies: list = []
        sheds = unshed = 0

        async def one(ctx):
            nonlocal sheds, unshed
            t0 = time.perf_counter()
            try:
                out = await handler.render_image_region(ctx)
                assert out
                latencies.append(time.perf_counter() - t0)
            except OverloadedError:
                sheds += 1           # shed = 503 + Retry-After: legal
            except Exception:
                unshed += 1          # bare failure: the gate breaker

        try:
            # Ramp through the ELEVATED band first: the continuous
            # prefetch budget must scale down (x0.5) strictly before
            # the binary pause_prefetch step engages — the PR 10
            # budget-before-pause gate.  The pre-wave is sized inside
            # the band (>= queue-high, < critical), held until the
            # governor publishes a scaled budget.
            pre = min(pcfg.queue_high + 4, len(ctxs))
            tasks = [asyncio.create_task(one(c))
                     for c in ctxs[:pre]]
            for _ in range(12):
                await asyncio.sleep(pcfg.interval_s)
                if governor.prefetch_budget() < 1.0:
                    break
            tasks += [asyncio.create_task(one(c))
                      for c in ctxs[pre:]]
            await asyncio.gather(*tasks)
            # Burst over: keep ticking until the ladder fully
            # releases (bounded — hysteresis means a few quiet ticks
            # per step).
            for _ in range(400):
                if not governor.engaged_steps():
                    break
                await asyncio.sleep(pcfg.interval_s)
            released = not governor.engaged_steps()
        finally:
            gov_task.cancel()
            pressure.uninstall()
            await router.close()
            services.pixels_service.close()

        steps = [e for e in telemetry.FLIGHT.snapshot()
                 if e["kind"] == "pressure.step"]
        engages = [e["step"] for e in steps
                   if e["action"] == "engage"]
        releases = [e["step"] for e in steps
                    if e["action"] == "release"]
        ladder = list(pcfg.ladder)
        order_ok = engages == ladder[:len(engages)]
        reverse_ok = releases == list(reversed(engages))[
            :len(releases)]
        flapping = (len(engages) != len(set(engages))
                    or len(releases) != len(set(releases)))
        # The continuous prefetch-budget trajectory (prefetch.budget
        # flight events): the first move must be a SCALE-DOWN in
        # (0, 1) — the level cut the budget before the binary pause
        # floored it — and the last must be the full restore.
        budgets = [e["scale"] for e in telemetry.FLIGHT.snapshot()
                   if e["kind"] == "prefetch.budget"]
        scaled_before_pause = bool(budgets) and 0.0 < budgets[0] < 1.0
        budget_restored = (bool(budgets) and 0.0 in budgets
                           and budgets[-1] == 1.0)
        ordered = sorted(latencies)
        p99 = (ordered[int(0.99 * (len(ordered) - 1))] * 1000.0
               if ordered else None)
        return {
            "served": len(latencies), "sheds": sheds,
            "unshed_failures": unshed,
            "steps_engaged": engages, "steps_released": releases,
            "ladder_order_ok": bool(order_ok),
            "release_reverse_ok": bool(reverse_ok),
            "released_all": bool(released),
            "flapping": bool(flapping),
            "budget_trajectory": budgets,
            "budget_scaled_before_pause": bool(scaled_before_pause),
            "budget_restored": bool(budget_restored),
            "p99_ms": _opt_round(p99, 1),
        }

    members_n = members
    with tempfile.TemporaryDirectory() as tmp:
        planes = synthetic_wsi_tiles(rng, 2, 1, grid * tile_edge,
                                     grid * tile_edge).reshape(
            2, 1, grid * tile_edge, grid * tile_edge)
        build_pyramid(planes, os.path.join(tmp, "1"), n_levels=1)
        doc = asyncio.run(run(tmp))
    out = {
        "metric": "overload_smoke",
        "burst": burst,
        "virtual_exec_ms": exec_ms,
        **{f"overload_{k}": v for k, v in doc.items()},
        "elapsed_s": round(time.perf_counter() - t_start, 1),
    }
    print(json.dumps(out))
    return out


# The committed synthetic shape-mask fixtures (tests/data/masks):
# mask-class load-model arrivals render these through the real mask
# endpoint during the capacity sweep.
_MASK_FIXTURE_IDS = (9001, 9002, 9003)


def _copy_mask_fixtures(data_dir: str) -> int:
    """Copy the committed mask fixtures into a bench data tree
    (LocalMetadataService reads ``<data_dir>/masks/<id>.{json,bin}``).
    Returns fixtures copied; 0 if the fixture tree is absent."""
    import os
    import shutil
    src = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "tests", "data", "masks")
    if not os.path.isdir(src):
        return 0
    dst = os.path.join(data_dir, "masks")
    os.makedirs(dst, exist_ok=True)
    n = 0
    for name in os.listdir(src):
        if name.endswith((".json", ".bin")):
            shutil.copy(os.path.join(src, name),
                        os.path.join(dst, name))
            n += name.endswith(".json")
    return n


def bench_capacity_smoke(exec_ms: float = 60.0, grid: int = 4,
                         tile_edge: int = 64,
                         fleet_sizes=(1, 2, 4), lane_width: int = 2,
                         slo_ms: float = 360.0,
                         shed_limit: float = 0.05,
                         window_s: float = 1.0,
                         load_factors=(0.45, 0.9, 1.5, 2.25),
                         viewers: int = 64,
                         mask_fraction: float = 0.1,
                         pyramid_fraction: float = 0.02,
                         animation_fraction: float = 0.03):
    """Capacity-knee measurement (``bench.py --smoke --capacity``,
    tier-1 via tests/test_bench_smoke.py): the latency-vs-OFFERED-load
    curve of a real in-process fleet under an OPEN-loop arrival
    process, per fleet size.

    Every other bench leg is closed-loop (workers that wait), which
    structurally cannot see queueing collapse — when the service slows
    the offered load slows with it.  Here the ``services.loadmodel``
    generator replays a seeded viewer population (heavy-tailed think
    times and session lengths, per-session pan trajectories)
    time-compressed to each target offered rate, and arrivals fire ON
    SCHEDULE regardless of completions:

    * per fleet size m1/m2/m4 (virtual device occupancy per the
      ``_fleet_smoke`` idiom — ``exec_ms`` of lane time per render),
      sweep offered load across ``load_factors`` x the size's nominal
      capacity and extract the CAPACITY KNEE: the highest offered
      load whose p99 still meets ``slo_ms`` and whose shed rate stays
      under ``shed_limit``;
    * the knee must SCALE with fleet size (the figure the autoscaler's
      floor/ceiling sizing reads — deploy/DEPLOY.md "Capacity &
      autoscaling");
    * **open-loop honesty A/B**: the first past-knee point's arrival
      list replays CLOSED-loop on the same stack — the closed p99
      must come out LOWER (flattering), which is the regression test
      that keeps future bench legs from quietly reverting to
      closed-loop arrivals and reporting a collapse-free curve.

    Emits ONE JSON line (the ``CAPACITY_r*.json`` record family)
    judged direction-aware by ``scripts/bench_gate.py --capacity``
    (knee regresses DOWN, ``_ms`` keys UP).
    """
    import asyncio
    import os
    import tempfile

    from omero_ms_image_region_tpu.flagship import synthetic_wsi_tiles
    from omero_ms_image_region_tpu.io.store import build_pyramid
    from omero_ms_image_region_tpu.parallel.fleet import (
        FleetImageHandler, FleetRouter, LocalMember,
        build_local_members)
    from omero_ms_image_region_tpu.server.admission import (
        AdmissionController)
    from omero_ms_image_region_tpu.server.app import build_services
    from omero_ms_image_region_tpu.server.config import (
        AppConfig, BatcherConfig, RawCacheConfig, RendererConfig)
    from omero_ms_image_region_tpu.server.ctx import ImageRegionCtx
    from omero_ms_image_region_tpu.server.singleflight import (
        SingleFlight)
    from omero_ms_image_region_tpu.services.loadmodel import (
        Arrival, LoadModel, find_knee, run_closed_loop,
        run_open_loop)
    from omero_ms_image_region_tpu.utils import telemetry

    t_start = time.perf_counter()
    rng = np.random.default_rng(29)
    exec_s = exec_ms / 1000.0
    telemetry.LOADMODEL.reset()

    class VirtualDeviceMember(LocalMember):
        """Calibrated virtual device occupancy (the `_fleet_smoke`
        idiom): the render is entirely real; the sleep models the
        device service time a small CI host cannot exhibit — which
        makes the measured knee a property of the QUEUEING STRUCTURE
        (lanes x members x service time), not of CI core count."""

        async def render(self, ctx, adopt_cache=True):
            data = await super().render(ctx, adopt_cache)
            await asyncio.sleep(exec_s)
            return data

    # The simulated population comes from the validated `loadmodel:`
    # config block (operators tune think/session tails there; a
    # driver round can point this at a real config).  The sweep pins
    # the STRUCTURAL knobs: seeded small population time-compressed
    # per offered rate, FLAT arrivals (diurnal 0 — the knee wants a
    # stationary offered rate; the diurnal ramp is the elasticity
    # drill's input), no bulk (bulk pins to m0 and would muddy the
    # per-size comparison).  Mask-class arrivals DO run — against the
    # committed synthetic fixtures under tests/data/masks — so the
    # measured knee carries the real served mix's mask tax.
    lm_config = AppConfig.from_dict({"loadmodel": {
        "seed": 31, "viewers": viewers, "diurnal-amplitude": 0.0,
        "bulk-fraction": 0.0, "mask-fraction": float(mask_fraction),
        "pyramid-fraction": float(pyramid_fraction),
        "animation-fraction": float(animation_fraction),
        "zoom-fraction": 0.0}}).loadmodel
    model = LoadModel.from_config(lm_config, duration_s=60.0,
                                  grid=grid)
    natural_events = model.events()

    def params_for(arrival):
        sid = int(arrival.session.rsplit("-", 1)[1])
        w = 21000 + (sid * 131 + arrival.step * 37) % 18000
        return {
            "imageId": "1", "theZ": "0", "theT": "0",
            "tile": f"0,{arrival.x},{arrival.y},{tile_edge},"
                    f"{tile_edge}",
            "format": "png", "m": "c",
            "c": f"1|0:{w}$FF0000,2|0:{w - 900}$00FF00",
        }

    def nominal_tps(n_members: int) -> float:
        return n_members * lane_width * 1000.0 / exec_ms

    async def run_size(tmp: str, n_members: int) -> tuple:
        from omero_ms_image_region_tpu.server.ctx import ShapeMaskCtx
        from omero_ms_image_region_tpu.server.handler import (
            ShapeMaskHandler)
        config = AppConfig(
            data_dir=tmp,
            batcher=BatcherConfig(enabled=False),
            raw_cache=RawCacheConfig(enabled=True, prefetch=False),
            renderer=RendererConfig(cpu_fallback_max_px=0))
        services = build_services(config)
        members = [VirtualDeviceMember(
            m.name, m.handler, m.services,
            down_cooldown_s=m.down_cooldown_s,
            byte_cache_prechecked=m.byte_cache_prechecked)
            for m in build_local_members(config, services, n_members)]
        router = FleetRouter(members, lane_width=lane_width,
                             steal_min_backlog=0)
        handler = FleetImageHandler(
            router, single_flight=SingleFlight(),
            admission=AdmissionController(4096, renderer=router),
            base_services=services)
        mask_handler = ShapeMaskHandler(services)
        # The PR 20 workload classes ride the measured mix: animation
        # strips compose the SAME fleet handler (each frame shares the
        # plain tile identity), pyramid arrivals exercise the submit
        # path (idempotent dedup — the build itself is background bulk
        # work, not request service time).
        from omero_ms_image_region_tpu.server.handler import (
            WorkloadsHandler)
        from omero_ms_image_region_tpu.server.jobs import (
            PyramidJobManager)
        workloads = WorkloadsHandler(handler, services, max_frames=8)
        pyramid_jobs = PyramidJobManager(
            pixels_service=services.pixels_service)

        async def submit(arrival):
            if arrival.cls == "pyramid":
                job = pyramid_jobs.submit(
                    services.pixels_service.image_dir(1), image_id=1)
                assert job.job_id
                return
            if arrival.cls == "animation":
                fparams = params_for(arrival)
                frame_ctxs = []
                for i in range(2):
                    fp = dict(fparams)
                    fp["theZ"] = str(i)
                    fctx = ImageRegionCtx.from_params(fp)
                    fctx.omero_session_key = arrival.session
                    frame_ctxs.append(fctx)
                n = 0
                async for record in workloads \
                        .render_animation_stream(frame_ctxs):
                    assert record[:4] == b"FRME"
                    n += 1
                assert n == len(frame_ctxs)
                return
            if arrival.cls == "mask":
                # Mask-class arrivals serve the committed synthetic
                # fixtures (tests/data/masks, copied into the bench
                # data tree) — the real mask endpoint, request-color
                # rotated so the explicit-color cache rule is in the
                # measured mix too.
                sid = _MASK_FIXTURE_IDS[
                    arrival.step % len(_MASK_FIXTURE_IDS)]
                ctx = ShapeMaskCtx(
                    shape_id=sid,
                    color=("FF8800" if arrival.step % 2 else None),
                    omero_session_key=arrival.session)
                out = await mask_handler.render_shape_mask(ctx)
                assert out
                return
            ctx = ImageRegionCtx.from_params(params_for(arrival))
            ctx.omero_session_key = arrival.session
            out = await handler.render_image_region(ctx)
            assert out

        try:
            # Warm EVERY class lane outside the measured windows —
            # first-use costs (jit compile per shape, codec and
            # metadata loads) otherwise land as a p99 outlier in the
            # first sweep point, whose p99 is the max of only ~16
            # arrivals.  Masks cycle all (fixture, color) combos the
            # submit() rotation can produce.
            warm = [Arrival(t=0.0, session="warm-0", cls="image",
                            step=0),
                    Arrival(t=0.0, session="warm-0", cls="animation",
                            step=0)]
            warm += [Arrival(t=0.0, session="warm-0", cls="mask",
                             step=s)
                     for s in range(2 * len(_MASK_FIXTURE_IDS))]
            for a in warm:
                await submit(a)
            points = []
            past_knee_arrivals = None
            for factor in load_factors:
                offered = factor * nominal_tps(n_members)
                # Steady-state slice of the simulated day, rescaled
                # to this offered rate (LoadModel.window — the
                # compressed day's thin edges must not under-offer).
                sched = model.window(offered, window_s,
                                     natural_events)
                report = await run_open_loop(
                    submit, sched,
                    offered_tps=len(sched) / window_s)
                assert not report.errors, \
                    f"open-loop leg failed bare: {report.errors[:3]}"
                points.append(report.as_point())
            knee, p99_at_knee, censored = find_knee(
                points, slo_ms, shed_limit)
            ab = None
            if n_members == 1 and knee is not None:
                # Open-loop honesty A/B on the SAME stack: replay the
                # first past-knee point's arrival list closed-loop —
                # workers that wait self-throttle to the service rate,
                # so the flattering p99 must come out LOWER than the
                # open-loop p99 the sweep just measured.
                past = next((p for p in points
                             if p["offered_tps"] > knee), None)
                if past is not None:
                    past_knee_arrivals = model.window(
                        past["offered_tps"], window_s,
                        natural_events)
                    closed = await run_closed_loop(
                        submit, past_knee_arrivals,
                        concurrency=lane_width * n_members)
                    ab = {
                        "offered_tps": past["offered_tps"],
                        "openloop_p99_ms": past["p99_ms"],
                        "closedloop_p99_ms": _opt_round(
                            closed.p99_ms(), 1),
                    }
            return points, knee, p99_at_knee, censored, ab
        finally:
            await router.close()
            services.pixels_service.close()

    curve = {}
    knees = {}
    p99s = {}
    censored_any = False
    honesty = None
    with tempfile.TemporaryDirectory() as tmp:
        # [C=2, Z=2]: two channels for the rendering-window params,
        # two z-planes so animation-class arrivals have a real scrub
        # axis (the strip renders theZ=0 and theZ=1).
        planes = synthetic_wsi_tiles(rng, 4, 1, grid * tile_edge,
                                     grid * tile_edge).reshape(
            2, 2, grid * tile_edge, grid * tile_edge)
        build_pyramid(planes, os.path.join(tmp, "1"), n_levels=1)
        if mask_fraction > 0 and not _copy_mask_fixtures(tmp):
            raise RuntimeError(
                "mask fixtures missing under tests/data/masks — "
                "run with mask_fraction=0 or restore the fixtures")
        for n in fleet_sizes:
            points, knee, p99_at_knee, censored, ab = asyncio.run(
                run_size(tmp, n))
            curve[f"m{n}"] = points
            knees[f"m{n}"] = knee
            p99s[f"m{n}"] = p99_at_knee
            censored_any = censored_any or censored
            if ab is not None:
                honesty = ab
    widest = f"m{max(fleet_sizes)}"
    knee_1 = knees.get(f"m{min(fleet_sizes)}")
    knee_w = knees.get(widest)
    out = {
        "metric": "capacity_smoke",
        "capacity_slo_ms": slo_ms,
        "capacity_shed_limit": shed_limit,
        "capacity_virtual_exec_ms": exec_ms,
        "capacity_window_s": window_s,
        "capacity_viewers": viewers,
        "capacity_fleet_sizes": list(fleet_sizes),
        "capacity_curve": curve,
        **{f"capacity_knee_offered_tps_{k}": _opt_round(v, 1)
           for k, v in knees.items()},
        # The headline pair the gate judges: the widest fleet's knee
        # (regresses DOWN) and its p99 at the knee (regresses UP).
        "capacity_knee_offered_tps": _opt_round(knee_w, 1),
        "p99_at_knee_ms": _opt_round(p99s.get(widest), 1),
        "capacity_knee_censored": bool(censored_any),
        "capacity_scaling_efficiency": _opt_round(
            (knee_w / (knee_1 * max(fleet_sizes) / min(fleet_sizes)))
            if knee_w and knee_1 else None, 3),
        # The open-loop honesty A/B (m1): closed must flatter.
        "openloop_p99_past_knee_ms": (honesty or {}).get(
            "openloop_p99_ms"),
        "closedloop_p99_past_knee_ms": (honesty or {}).get(
            "closedloop_p99_ms"),
        "capacity_ab_offered_tps": (honesty or {}).get("offered_tps"),
        # Mask-class arrivals in the measured mix (the committed
        # tests/data/masks fixtures through the real mask endpoint):
        # offered vs completed per the LOADMODEL accumulator — a
        # mask error surfaces as completed < offered, never silently.
        "capacity_mask_fraction": float(mask_fraction),
        "capacity_mask_offered":
            telemetry.LOADMODEL.offered.get("mask", 0),
        "capacity_mask_completed":
            telemetry.LOADMODEL.completed.get("mask", 0),
        # PR 20 workload classes in the measured mix: same
        # offered-vs-completed honesty as masks.
        "capacity_pyramid_fraction": float(pyramid_fraction),
        "capacity_pyramid_offered":
            telemetry.LOADMODEL.offered.get("pyramid", 0),
        "capacity_pyramid_completed":
            telemetry.LOADMODEL.completed.get("pyramid", 0),
        "capacity_animation_fraction": float(animation_fraction),
        "capacity_animation_offered":
            telemetry.LOADMODEL.offered.get("animation", 0),
        "capacity_animation_completed":
            telemetry.LOADMODEL.completed.get("animation", 0),
        # Open-loop integrity: arrivals the generator fired behind
        # its own schedule (counted, never hidden).
        "loadmodel_late_fires": telemetry.LOADMODEL.late,
        "elapsed_s": round(time.perf_counter() - t_start, 1),
    }
    print(json.dumps(out))
    return out


def bench_workloads_smoke(edge: int = 128, mask_rounds: int = 4,
                          frames: int = 8):
    """Device-workloads drill (``bench.py --smoke --workloads``,
    tier-1 via tests/test_bench_smoke.py): the PR 20 plane end to end
    on a real in-process stack.

    Legs:

    * **mask parity + timing** — every committed mask fixture renders
      through the ENDPOINT twice: device-batched (the BatchingRenderer
      ``("mask", ...)`` group path) and host rasterizer.  The bytes
      must be IDENTICAL (the refimpl-golden contract); both sides are
      timed.
    * **overlay** — the composite endpoint (region render + device
      mask blend) against the refimpl ``overlay_masks_batch`` formula.
    * **pyramid** — a background-class build over the device
      downsample with atomic per-level commits; the committed group
      must open through the NGFF reader.
    * **animation** — a z-strip streamed through the workloads
      handler: ordered ``FRME`` records, first-frame latency, and a
      mid-stream close cancelling the remaining frames.

    Emits ONE JSON line (the ``WORKLOADS_r*.json`` record family)
    judged direction-aware by ``scripts/bench_gate.py`` (``_ms`` keys
    regress UP, counts DOWN).
    """
    import asyncio
    import os
    import tempfile

    from omero_ms_image_region_tpu import codecs
    from omero_ms_image_region_tpu.flagship import synthetic_wsi_tiles
    from omero_ms_image_region_tpu.io.ngff import (NgffZarrSource,
                                                   find_ngff)
    from omero_ms_image_region_tpu.io.store import build_pyramid
    from omero_ms_image_region_tpu.ops import maskops
    from omero_ms_image_region_tpu.server.app import build_services
    from omero_ms_image_region_tpu.server.config import (AppConfig,
                                                         RawCacheConfig)
    from omero_ms_image_region_tpu.server.ctx import (ImageRegionCtx,
                                                      ShapeMaskCtx)
    from omero_ms_image_region_tpu.server.handler import (
        ImageRegionHandler, ShapeMaskHandler, WorkloadsHandler)
    from omero_ms_image_region_tpu.server.jobs import PyramidJobManager
    from omero_ms_image_region_tpu.utils import telemetry

    t_start = time.perf_counter()
    rng = np.random.default_rng(47)
    telemetry.WORKLOADS.reset()

    out = {"metric": "workloads_smoke"}

    async def run(tmp: str) -> None:
        config = AppConfig(
            data_dir=tmp,
            raw_cache=RawCacheConfig(enabled=True, prefetch=False))
        services = build_services(config)
        image_handler = ImageRegionHandler(services)
        workloads = WorkloadsHandler(image_handler, services,
                                     max_frames=max(frames, 8))
        device_masks = ShapeMaskHandler(services, device_masks=True)
        host_masks = ShapeMaskHandler(services, device_masks=False)
        try:
            # ---- leg 1: endpoint mask parity + timing (fresh ctx
            # objects defeat the byte cache; fixture colors rotate so
            # both the stored-fill and explicit-color paths run).
            def mask_ctxs():
                # Stored-fill colors only (explicit colors byte-cache,
                # which would let the second pass serve the first
                # pass's bytes); flips vary so the device flip lanes
                # are in the measured mix.
                return [ShapeMaskCtx(
                    shape_id=_MASK_FIXTURE_IDS[
                        i % len(_MASK_FIXTURE_IDS)],
                    flip_horizontal=bool(i % 2),
                    flip_vertical=bool(i % 3 == 0))
                    for i in range(mask_rounds
                                   * len(_MASK_FIXTURE_IDS))]

            # Warm every flip lane first so the timed passes measure
            # steady-state dispatch, not the one-off device compiles.
            for fh, fv in ((False, False), (True, False),
                           (False, True), (True, True)):
                warm = await device_masks.render_shape_mask(
                    ShapeMaskCtx(shape_id=_MASK_FIXTURE_IDS[0],
                                 flip_horizontal=fh,
                                 flip_vertical=fv))
                assert warm
            t0 = time.perf_counter()
            device_pngs = await asyncio.gather(
                *(device_masks.render_shape_mask(c)
                  for c in mask_ctxs()))
            device_ms = (time.perf_counter() - t0) * 1000.0
            t0 = time.perf_counter()
            host_pngs = await asyncio.gather(
                *(host_masks.render_shape_mask(c)
                  for c in mask_ctxs()))
            host_ms = (time.perf_counter() - t0) * 1000.0
            assert device_pngs == host_pngs, \
                "device mask bytes diverged from host rasterizer"
            out["mask_renders"] = len(device_pngs)
            out["mask_device_ms"] = round(device_ms, 1)
            out["mask_host_ms"] = round(host_ms, 1)
            out["mask_parity_ok"] = True

            # ---- leg 2: overlay composite vs the refimpl formula.
            oparams = {"imageId": "1", "theZ": "0", "theT": "0",
                       "region": "0,0,64,64", "format": "png",
                       "m": "c", "c": "1|0:30000$FF0000"}
            octx = ImageRegionCtx.from_params(oparams)
            t0 = time.perf_counter()
            overlay_png = await workloads.render_overlay(
                octx, [_MASK_FIXTURE_IDS[0], _MASK_FIXTURE_IDS[1]])
            overlay_ms = (time.perf_counter() - t0) * 1000.0
            base_png = await image_handler.render_image_region(
                ImageRegionCtx.from_params(oparams))
            base = codecs.decode_to_rgba(base_png)
            ref = base
            for sid in (_MASK_FIXTURE_IDS[0], _MASK_FIXTURE_IDS[1]):
                mask = await services.metadata.get_mask(sid, None)
                grid, _ = maskops.rasterize_mask(mask)
                fill = np.array([mask.resolved_fill_color(None)],
                                dtype=np.uint8)
                ref = maskops.overlay_masks_batch(
                    ref[None], grid[None], fill)[0]
            ref_png = codecs.encode_rgba(ref, "png")
            assert overlay_png == ref_png, \
                "overlay composite diverged from refimpl golden"
            out["overlay_device_ms"] = round(overlay_ms, 1)
            out["overlay_parity_ok"] = True

            # ---- leg 3: pyramid build through the job manager.
            jobs = PyramidJobManager(
                pixels_service=services.pixels_service,
                chunk=(64, 64), min_level_size=32)
            job = jobs.submit(os.path.join(tmp, "2"), image_id=2)
            t0 = time.perf_counter()
            await asyncio.to_thread(jobs.run_job_sync, job)
            out["pyramid_build_ms"] = round(
                (time.perf_counter() - t0) * 1000.0, 1)
            out["pyramid_levels"] = job.levels_done
            ngff_root = find_ngff(os.path.join(tmp, "2"))
            assert ngff_root is not None, "pyramid group not committed"
            reader = NgffZarrSource(ngff_root)
            out["pyramid_readable_levels"] = \
                reader.resolution_levels()
            reader.close()

            # ---- leg 4: animation strip, ordered + first-frame ms,
            # then a mid-stream close (the disconnect path) that must
            # cancel the remaining frames.
            def strip_ctxs(n):
                ctxs = []
                for i in range(n):
                    p = {"imageId": "1", "theZ": str(i % 2),
                         "theT": "0", "region": "0,0,64,64",
                         "format": "png", "m": "c",
                         "c": f"1|0:{30000 + i}$FF0000"}
                    ctxs.append(ImageRegionCtx.from_params(p))
                return ctxs

            t0 = time.perf_counter()
            first_ms = None
            n_served = 0
            async for record in workloads.render_animation_stream(
                    strip_ctxs(frames)):
                if first_ms is None:
                    first_ms = (time.perf_counter() - t0) * 1000.0
                assert record[:4] == b"FRME"
                n_served += 1
            total_ms = (time.perf_counter() - t0) * 1000.0
            assert n_served == frames
            out["anim_frames"] = n_served
            out["anim_first_frame_ms"] = round(first_ms, 1)
            out["anim_total_ms"] = round(total_ms, 1)

            # The disconnect drill wants later frames STILL IN FLIGHT
            # when the stream closes; tiny CPU renders settle together
            # under the batcher, so a staggered-latency wrapper keeps
            # the tail pending deterministically.
            class _StaggeredHandler:
                def __init__(self, inner):
                    self.inner = inner
                    self.calls = 0

                async def render_image_region(self, ctx):
                    self.calls += 1
                    await asyncio.sleep(0.02 * self.calls)
                    return await self.inner.render_image_region(ctx)

            slow = WorkloadsHandler(
                _StaggeredHandler(image_handler), services,
                max_frames=max(frames, 8))
            cancelled_before = telemetry.WORKLOADS.stream_cancels
            agen = slow.render_animation_stream(strip_ctxs(frames))
            assert (await agen.__anext__())[:4] == b"FRME"
            await agen.aclose()
            out["anim_cancel_ok"] = (
                telemetry.WORKLOADS.stream_cancels
                == cancelled_before + 1)
        finally:
            close = services.renderer.close()
            if asyncio.iscoroutine(close):
                await close
            services.pixels_service.close()

    with tempfile.TemporaryDirectory() as tmp:
        # [C=1, Z=2, H, W]: two z-planes so the animation strip has a
        # real scrub axis; image "2" (the pyramid job target) keeps
        # one plane.
        planes = synthetic_wsi_tiles(rng, 2, 1, edge, edge).reshape(
            1, 2, edge, edge)
        build_pyramid(planes, os.path.join(tmp, "1"), n_levels=1)
        build_pyramid(planes[:, :1], os.path.join(tmp, "2"),
                      n_levels=1)
        if not _copy_mask_fixtures(tmp):
            raise RuntimeError(
                "mask fixtures missing under tests/data/masks")
        asyncio.run(run(tmp))

    out["elapsed_s"] = round(time.perf_counter() - t_start, 1)
    print(json.dumps(out))
    return out


def bench_hotkey_smoke(exec_ms: float = 30.0, grid: int = 4,
                       tile_edge: int = 32, n_members: int = 2,
                       lane_width: int = 2, window_s: float = 1.2,
                       load_factor: float = 0.95,
                       viewers: int = 48, skew: float = 2.2,
                       image_population: int = 12,
                       threshold: float = 6.0, decay_s: float = 0.35,
                       emit: bool = True):
    """Hot-plane replication drill (``bench.py --smoke --hotkey``,
    tier-1 via tests/test_bench_smoke.py): survive the viral image.

    Three legs on the same virtual-occupancy fleet (work stealing OFF,
    so every measured delta is the replication tier's and nothing
    else's):

    * **uniform** — the zipf-0 mix (every image rank equally likely):
      the baseline throughput a balanced population gets;
    * **storm, replication disabled** — a zipf-``skew`` population
      (``services.loadmodel`` ``skew``/``image_population`` knobs;
      rank 0 is the viral plane, distinct render identities over ONE
      ``plane_route_key``) with the hot-key tier OFF: the ring pins
      every hot read to one member and its queue eats the storm;
    * **storm, replication enabled** — the same arrival schedule with
      the tier ON: the heat tracker promotes the viral route to an
      R=2 replica set drawn from the ring chain, reads least-queued
      balance across it, and throughput must come back toward the
      uniform mix (the gate: storm >= 0.7x uniform AND the disabled
      A/B measures LESS than the replicated leg).

    The enabled leg also drives the full lifecycle from live state:
    promotion + digest-deduped replica staging (``duplicate_staged``
    must be 0 and ``shard_report`` must classify the hot plane as
    ``replicated_digests``, never ``duplicate_digests``), one
    autoscaler tick at the fleet ceiling while replica pressure holds
    (the ``blocked:ceiling`` decision record must CARRY the
    replica-pressure signal), then heat decay past the demote
    fraction with cool traffic sweeping the route back to R=1.

    Emits ONE JSON line (the ``HOTKEY_r*.json`` record family) judged
    direction-aware by ``scripts/bench_gate.py --hotkey``.
    """
    import asyncio
    import os
    import tempfile

    from omero_ms_image_region_tpu.flagship import synthetic_wsi_tiles
    from omero_ms_image_region_tpu.io.store import build_pyramid
    from omero_ms_image_region_tpu.parallel.fleet import (
        FleetImageHandler, FleetRouter, LocalMember,
        build_local_members)
    from omero_ms_image_region_tpu.server.admission import (
        AdmissionController)
    from omero_ms_image_region_tpu.server.app import build_services
    from omero_ms_image_region_tpu.server.autoscaler import Autoscaler
    from omero_ms_image_region_tpu.server.config import (
        AppConfig, AutoscalerConfig, BatcherConfig, HotkeyConfig,
        RawCacheConfig, RendererConfig)
    from omero_ms_image_region_tpu.server.ctx import ImageRegionCtx
    from omero_ms_image_region_tpu.server.singleflight import (
        SingleFlight)
    from omero_ms_image_region_tpu.services.loadmodel import (
        LoadModel, run_open_loop)
    from omero_ms_image_region_tpu.utils import decisions, telemetry

    t_start = time.perf_counter()
    rng = np.random.default_rng(37)
    exec_s = exec_ms / 1000.0

    class VirtualDeviceMember(LocalMember):
        """Calibrated virtual device occupancy (the ``_fleet_smoke``
        idiom): the measured deltas are properties of the queueing
        structure, not of CI core count."""

        async def render(self, ctx, adopt_cache=True):
            data = await super().render(ctx, adopt_cache)
            await asyncio.sleep(exec_s)
            return data

    def make_model(s: float) -> "LoadModel":
        lm_config = AppConfig.from_dict({"loadmodel": {
            "seed": 53, "viewers": viewers, "diurnal-amplitude": 0.0,
            "bulk-fraction": 0.0, "mask-fraction": 0.0,
            "zoom-fraction": 0.0, "skew": float(s),
            "image-population": int(image_population)}}).loadmodel
        return LoadModel.from_config(lm_config, duration_s=60.0,
                                     grid=grid)

    def params_for(arrival):
        # The session's popularity RANK addresses the tile lattice:
        # rank 0 is THE viral tile — one plane_route_key — while the
        # channel window varies per (session, step), so the storm is
        # distinct render identities over one source plane (the
        # byte cache cannot flatten it; the plane tier must).
        sid = int(arrival.session.rsplit("-", 1)[1])
        tx = arrival.image % grid
        ty = (arrival.image // grid) % grid
        w = 21000 + (sid * 131 + arrival.step * 37) % 18000
        return {
            "imageId": "1", "theZ": "0", "theT": "0",
            "tile": f"0,{tx},{ty},{tile_edge},{tile_edge}",
            "format": "png", "m": "c",
            "c": f"1|0:{w}$FF0000,2|0:{w - 900}$00FF00",
        }

    nominal_tps = n_members * lane_width * 1000.0 / exec_ms
    offered = load_factor * nominal_tps

    async def run_leg(tmp: str, s: float, hot_enabled: bool) -> tuple:
        telemetry.LOADMODEL.reset()
        telemetry.HOTKEY.reset()
        model = make_model(s)
        events = model.events()
        config = AppConfig(
            data_dir=tmp,
            batcher=BatcherConfig(enabled=False),
            raw_cache=RawCacheConfig(enabled=True, prefetch=False),
            renderer=RendererConfig(cpu_fallback_max_px=0))
        services = build_services(config)
        members = [VirtualDeviceMember(
            m.name, m.handler, m.services,
            down_cooldown_s=m.down_cooldown_s,
            byte_cache_prechecked=m.byte_cache_prechecked)
            for m in build_local_members(config, services, n_members)]
        router = FleetRouter(
            members, lane_width=lane_width, steal_min_backlog=0,
            hotkey=HotkeyConfig(
                enabled=hot_enabled, threshold=threshold,
                decay_s=decay_s, max_replicas=2,
                demote_fraction=0.5, scale_factor=1.5))
        handler = FleetImageHandler(
            router, single_flight=SingleFlight(),
            admission=AdmissionController(4096, renderer=router),
            base_services=services)

        async def submit(arrival):
            ctx = ImageRegionCtx.from_params(params_for(arrival))
            ctx.omero_session_key = arrival.session
            out = await handler.render_image_region(ctx)
            assert out

        try:
            # One warm render outside the measured window (shared jit
            # compile across stacks of one process).
            await submit(events[0])
            sched = model.window(offered, window_s, events)
            report = await run_open_loop(
                submit, sched, offered_tps=len(sched) / window_s)
            assert not report.errors, \
                f"hotkey leg failed bare: {report.errors[:3]}"
            tps = report.served / report.window_s
            extra: dict = {}
            if hot_enabled and s > 0:
                # Live lifecycle state, read BEFORE decay: peak
                # pressure, replica sets, shard classification.
                extra["pressure"] = router.replica_pressure()
                extra["hot_routes"] = router.hot_route_count()
                extra["shard"] = router.shard_report()
                # One autoscaler tick at the fleet ceiling while the
                # pressure holds: the want-up it forces is refused as
                # blocked:ceiling, and THAT decision record must carry
                # the replica-pressure signal (the acceptance line).
                scaler = Autoscaler(AutoscalerConfig(
                    enabled=True, floor=1, ceiling=n_members,
                    hold_ticks=1, cooldown_s=0.0), router)
                extra["tick"] = scaler.tick()
                # Heat decay past the demote fraction, then cool
                # traffic drives the sweep on the LIVE dispatch path.
                await asyncio.sleep(max(4.0 * decay_s, 1.0))
                cool = [a for a in sched if a.image != 0][:4] \
                    or sched[:2]
                for a in cool:
                    await submit(a)
                extra["hot_after"] = router.hot_route_count()
                extra["totals"] = telemetry.HOTKEY.totals()
            return tps, extra
        finally:
            await router.close()
            services.pixels_service.close()

    with tempfile.TemporaryDirectory() as tmp:
        planes = synthetic_wsi_tiles(rng, 2, 1, grid * tile_edge,
                                     grid * tile_edge).reshape(
            2, 1, grid * tile_edge, grid * tile_edge)
        build_pyramid(planes, os.path.join(tmp, "1"), n_levels=1)
        uniform_tps, _ = asyncio.run(run_leg(tmp, 0.0, True))
        disabled_tps, _ = asyncio.run(run_leg(tmp, skew, False))
        decisions.LEDGER.reset()
        storm_tps, storm = asyncio.run(run_leg(tmp, skew, True))

    totals = storm.get("totals", {})
    shard = storm.get("shard", {})
    ledger = decisions.LEDGER.snapshot()
    autoscaler_signal = any(
        r.get("kind") == "autoscaler"
        and float((r.get("detail") or {}).get("signals", {})
                  .get("replica_pressure", 0.0) or 0.0) > 0.0
        for r in ledger)
    ledger_promotions = sum(
        1 for r in ledger if r.get("kind") == "hotkey"
        and r.get("verdict") == "promoted")
    out = {
        "metric": "hotkey_smoke",
        "hotkey_fleet_size": n_members,
        "hotkey_virtual_exec_ms": exec_ms,
        "hotkey_window_s": window_s,
        "hotkey_offered_tps": round(offered, 1),
        "hotkey_skew": float(skew),
        "hotkey_image_population": int(image_population),
        # The headline pair the gate judges: the storm's throughput
        # retention vs the uniform mix (regresses DOWN), and the
        # replication gain over the disabled A/B (regresses DOWN,
        # must stay > 1 — disabled measuring MORE means the tier is
        # dead weight).
        "hotkey_uniform_tps": round(uniform_tps, 1),
        "hotkey_storm_tps": round(storm_tps, 1),
        "hotkey_storm_ratio": round(storm_tps / uniform_tps, 3),
        "hotkey_disabled_tps": round(disabled_tps, 1),
        "hotkey_replication_gain": round(
            storm_tps / max(disabled_tps, 1e-9), 3),
        "hotkey_promotions": int(totals.get("promoted", 0)),
        "hotkey_demotions": int(totals.get("demoted", 0)),
        "hotkey_replica_staged": int(totals.get("staged", 0)),
        "hotkey_duplicate_staged": int(
            totals.get("duplicate_staged", 0)),
        "hotkey_balanced_reads": int(totals.get("balanced", 0)),
        "hotkey_peak_replica_pressure": round(
            float(storm.get("pressure", 0.0)), 2),
        "hotkey_hot_routes_peak": int(storm.get("hot_routes", 0)),
        "hotkey_hot_routes_after_decay": int(
            storm.get("hot_after", 0)),
        "hotkey_demoted_after_decay": bool(
            totals.get("demoted", 0) >= 1
            and storm.get("hot_after", 1) == 0),
        "hotkey_shard_duplicates": int(
            shard.get("duplicate_digests", 0)),
        "hotkey_shard_replicated": int(
            shard.get("replicated_digests", 0)),
        "hotkey_autoscaler_signal": bool(autoscaler_signal),
        "hotkey_ledger_promotions": int(ledger_promotions),
        "loadmodel_late_fires": telemetry.LOADMODEL.late,
        "elapsed_s": round(time.perf_counter() - t_start, 1),
    }
    if emit:
        print(json.dumps(out))
    return out


def bench_sentinel_smoke(emit: bool = True):
    """Induced-drift sentinel drill (``bench.py --smoke --sentinel``,
    tier-1 via tests/test_bench_smoke.py): the full confirm → capture
    → recover cycle, deterministically, on a virtual clock.

    A REAL 2-member fleet (the ``_fleet_smoke`` virtual-occupancy
    members) serves a small burst each phase so the forensic
    artifacts a bundle snapshots — flight ring, top-K cost ledgers,
    request exemplars — hold live content; each member runs its OWN
    ``SentinelEngine`` fed a deterministic per-request latency
    (window jitter included, so the sketches are non-degenerate):

    * **warmup** — both members at ~10 ms until their baselines
      learn;
    * **step** — member m1's latency steps to 4x while m0 holds: m1
      must confirm EXACTLY ONE drift after ``confirm_ticks``
      breaching windows, capture EXACTLY ONE complete bundle
      (manifest listing profile + flight + costs + sketch_diff +
      exemplars) and write ONE ``kind=sentinel`` ledger record,
      while m0 stays quiet;
    * **recover** — m1 returns to baseline and ``recover_ticks``
      clean windows must clear the verdict.

    Both members' summaries are ingested into ``telemetry.SENTINEL``
    exactly as the gossip path does, so the asserted merged view is
    the /debug/sentinel shape.  Emits ONE JSON line.
    """
    import asyncio
    import os
    import tempfile

    from omero_ms_image_region_tpu.flagship import synthetic_wsi_tiles
    from omero_ms_image_region_tpu.io.store import build_pyramid
    from omero_ms_image_region_tpu.parallel.fleet import (
        FleetImageHandler, FleetRouter, build_local_members)
    from omero_ms_image_region_tpu.server.admission import (
        AdmissionController)
    from omero_ms_image_region_tpu.server.app import build_services
    from omero_ms_image_region_tpu.server.config import (
        AppConfig, BatcherConfig, RawCacheConfig, RendererConfig)
    from omero_ms_image_region_tpu.server.ctx import ImageRegionCtx
    from omero_ms_image_region_tpu.server.sentinel import SentinelEngine
    from omero_ms_image_region_tpu.server.singleflight import (
        SingleFlight)
    from omero_ms_image_region_tpu.utils import decisions, telemetry

    t_start = time.perf_counter()
    rng = np.random.default_rng(41)
    grid, tile_edge = 2, 32
    route = "render_image_region"
    base_ms, step_ms = 10.0, 40.0
    min_samples, warmup, confirm, recover = 16, 2, 2, 2

    clk = [0.0]

    def stub_profile(directory: str, ms: float) -> dict:
        # The drill stands in for telemetry.capture_profile (a real
        # jax.profiler capture needs a device window and wall time);
        # the app path keeps the real single-flight capture.
        sub = os.path.join(directory, "profile")
        os.makedirs(sub, exist_ok=True)
        with open(os.path.join(sub, "capture.stub"), "w") as f:
            f.write("drill\n")
        return {"dir": sub, "ms": 0.0, "requested_ms": ms,
                "files": 1, "bytes": 6}

    def make_engine(member: str, bundle_dir: str) -> SentinelEngine:
        return SentinelEngine(
            member=member, tick_interval_s=5.0,
            confirm_ticks=confirm, recover_ticks=recover,
            min_samples=min_samples, warmup_ticks=warmup,
            drift_ratio=1.5, baseline_alpha=0.2,
            bundle_dir=bundle_dir, profile_ms=50.0,
            # Real watermark SHAPE, drill-scaled values: the latency
            # floor sits under the induced step (so the breach is
            # above it) and the throughput mark is tiny (this drill
            # induces a latency drift, not a starvation).
            watermarks={"bench": {
                "p50_service_tile_ms": {"value": 5.0},
                "service_tiles_per_sec": {"value": 0.001},
            }},
            clock=lambda: clk[0],
            profile_fn=stub_profile)

    def feed(engine: SentinelEngine, center_ms: float) -> None:
        # One window's worth of deterministic observations: a fixed
        # sawtooth around the center so quantiles interpolate over
        # several sketch buckets instead of collapsing into one.
        for i in range(max(min_samples, 24)):
            engine.observe(route, 64 * 1024,
                           center_ms * (1.0 + 0.04 * (i % 5)))

    async def serve_burst(handler, n: int = 4) -> None:
        # Live fleet traffic so the bundle's flight/cost/exemplar
        # snapshots hold real content (durations the ENGINES judge
        # stay the deterministic feed above).
        for i in range(n):
            ctx = ImageRegionCtx.from_params({
                "imageId": "1", "theZ": "0", "theT": "0",
                "tile": f"0,{i % grid},{(i // grid) % grid},"
                        f"{tile_edge},{tile_edge}",
                "format": "png", "m": "c", "c": "1|0:39000$FF0000",
            })
            out = await handler.render_image_region(ctx)
            assert out

    async def run_drill(tmp: str, bundle_dir: str) -> dict:
        config = AppConfig(
            data_dir=tmp,
            batcher=BatcherConfig(enabled=False),
            raw_cache=RawCacheConfig(enabled=True, prefetch=False),
            renderer=RendererConfig(cpu_fallback_max_px=0))
        services = build_services(config)
        members = build_local_members(config, services, 2)
        router = FleetRouter(members, lane_width=2,
                             steal_min_backlog=0)
        handler = FleetImageHandler(
            router, single_flight=SingleFlight(),
            admission=AdmissionController(256, renderer=router),
            base_services=services)
        engines = {
            "m0": make_engine("m0", ""),
            "m1": make_engine("m1", bundle_dir),
        }

        def tick_all() -> dict:
            clk[0] += 5.0
            summaries = {}
            for name, eng in engines.items():
                summaries[name] = eng.tick()
                # The gossip ingest path, verbatim: per-member
                # summaries join the fleet merge.
                telemetry.SENTINEL.ingest(name, summaries[name])
            return summaries

        try:
            # Warmup: both members learn "normal".
            for _ in range(warmup + 1):
                await serve_burst(handler)
                for eng in engines.values():
                    feed(eng, base_ms)
                tick_all()
            assert engines["m1"].verdict == "ok"

            # Latency step on m1 only: confirm_ticks breaching
            # windows -> ONE confirmed drift + ONE bundle.
            for _ in range(confirm):
                await serve_burst(handler)
                feed(engines["m0"], base_ms)
                feed(engines["m1"], step_ms)
                summaries = tick_all()
            drift_summary = summaries["m1"]
            merged_at_drift = telemetry.SENTINEL.merged()

            # Recovery: clean windows clear the verdict.
            for _ in range(recover):
                await serve_burst(handler)
                for eng in engines.values():
                    feed(eng, base_ms)
                summaries = tick_all()
            return {"drift": drift_summary,
                    "merged": merged_at_drift,
                    "final": summaries}
        finally:
            await router.close()
            services.pixels_service.close()

    telemetry.SENTINEL.reset()
    decisions.LEDGER.reset()
    with tempfile.TemporaryDirectory() as tmp, \
            tempfile.TemporaryDirectory() as bundle_dir:
        planes = synthetic_wsi_tiles(rng, 1, 1, grid * tile_edge,
                                     grid * tile_edge).reshape(
            1, 1, grid * tile_edge, grid * tile_edge)
        build_pyramid(planes, os.path.join(tmp, "1"), n_levels=1)
        phases = asyncio.run(run_drill(tmp, bundle_dir))

        # -- exactly one confirmed drift, on m1, never m0 ------------
        drift = phases["drift"]
        assert drift["verdict"] == "drifting", drift
        assert drift["drifting"], drift
        sentinel_records = [
            r for r in decisions.LEDGER.snapshot()
            if r.get("kind") == "sentinel"]
        drift_records = [r for r in sentinel_records
                         if r.get("verdict") == "drift"]
        assert len(drift_records) == 1, sentinel_records
        assert drift_records[0].get("member") == "m1", drift_records

        # -- exactly one COMPLETE bundle ------------------------------
        bundles = sorted(
            n for n in os.listdir(bundle_dir)
            if n.startswith("sentinel-"))
        assert len(bundles) == 1, bundles
        bundle_path = os.path.join(bundle_dir, bundles[0])
        with open(os.path.join(bundle_path, "manifest.json")) as f:
            manifest = json.load(f)
        files = manifest["files"]
        missing = [k for k in ("profile", "flight", "costs",
                               "sketch_diff", "exemplars")
                   if not files.get(k)]
        assert not missing, f"incomplete bundle: missing {missing}"
        for fname in files.values():
            assert os.path.exists(os.path.join(bundle_path, fname))
        with open(os.path.join(bundle_path, files["flight"])) as f:
            flight_doc = json.load(f)
        assert flight_doc.get("events"), "flight dump empty"

        # -- the merged fleet view saw both members + the drift -------
        merged = phases["merged"]
        assert set(merged["members"]) >= {"m0", "m1"}, merged
        assert merged["verdict"] == "drifting", merged
        assert merged["drifting_members"] == ["m1"], merged

        # -- recovery clears the verdict ------------------------------
        final = phases["final"]
        assert final["m1"]["verdict"] == "ok", final["m1"]
        recovered_records = [r for r in decisions.LEDGER.snapshot()
                             if r.get("kind") == "sentinel"
                             and r.get("verdict") == "recovered"]
        assert len(recovered_records) == 1, recovered_records

    out = {
        "metric": "sentinel_smoke",
        "sentinel_drift_confirms": len(drift_records),
        "sentinel_drifting_member": "m1",
        "sentinel_bundles": len(bundles),
        "sentinel_bundle_files": sorted(files),
        "sentinel_recovered": True,
        "sentinel_merged_members": sorted(merged["members"]),
        "sentinel_drift_keys": list(drift["drifting"]),
        "elapsed_s": round(time.perf_counter() - t_start, 1),
    }
    if emit:
        print(json.dumps(out))
    return out


def bench_federation_smoke(grid: int = 3, tile_edge: int = 32,
                           burst: int = 24, emit: bool = True):
    """Multi-PROCESS federated fleet smoke (``bench.py --smoke
    --federation``): this process runs host A of a federated combined
    fleet (one local device-pinned member) and SPAWNS a real sidecar
    process as host B's member, behind one agreed manifest.

    Measured (the MULTICHIP record family grew these keys; rounds
    that predate them skip on null in ``bench_gate --multichip``):

    * **agreement** — the manifest digest agrees and the spawned
      process's OWN ring math assigns every golden probe key to the
      same owner this process computes (``fed_manifest_agreed``);
    * **process scaling** — a closed-loop distinct-tile burst through
      1 process vs 2 (``fed_tiles_per_sec_p1/p2``,
      ``fed_process_scaling_efficiency``);
    * **cross-host warm handoff** — draining the LOCAL member ships
      its HBM shard bytes over the ``shard_transfer`` wire op, and
      the remote process answers the digests resident
      (``fed_drain_prestaged`` / ``fed_remote_resident``);
    * **stitched control-plane forensics** — the gossip round and the
      drain run inside ONE trace, producing a two-process waterfall
      whose ``fed.hop`` spans are causally ordered and whose remote
      stage grafts sit INSIDE their wire exchange's window after
      per-host clock anchoring (``fed_trace_stitched``); an
      autoscaler ticks against the live router until its ledger
      verdicts carry MEASURED outcomes, and the local + remote
      decision rings merge into one host-attributed timeline
      (``decision_records`` / ``fed_decision_hosts``) — with a
      renderer-span delta of ZERO across all forensics reads
      (``forensics_render_delta``).
    """
    _cpu_contract_drill("--federation")
    import asyncio
    import os
    import tempfile

    import yaml

    from omero_ms_image_region_tpu.flagship import synthetic_wsi_tiles
    from omero_ms_image_region_tpu.io.store import build_pyramid
    from omero_ms_image_region_tpu.parallel import federation
    from omero_ms_image_region_tpu.parallel.fleet import (
        FleetImageHandler, FleetRouter)
    from omero_ms_image_region_tpu.server.app import build_services
    from omero_ms_image_region_tpu.server.config import (
        AppConfig, BatcherConfig, RawCacheConfig, RendererConfig)
    from omero_ms_image_region_tpu.server.ctx import ImageRegionCtx
    from omero_ms_image_region_tpu.server.sidecar import (
        SidecarClient, spawn_sidecar)
    from omero_ms_image_region_tpu.server.singleflight import (
        SingleFlight)

    t_start = time.perf_counter()
    rng = np.random.default_rng(53)

    def params_for(i: int, leg: str):
        x, y = i % grid, (i // grid) % grid
        w = 20000 + 700 * i + (0 if leg == "p1" else 11)
        return {
            "imageId": "1", "theZ": "0", "theT": "0",
            "tile": f"0,{x},{y},{tile_edge},{tile_edge}",
            "format": "png", "m": "c",
            "c": f"1|0:{w}$FF0000",
        }

    async def run(tmp: str, sock: str) -> dict:
        config = AppConfig(
            data_dir=tmp,
            batcher=BatcherConfig(enabled=False),
            raw_cache=RawCacheConfig(enabled=True, prefetch=False),
            renderer=RendererConfig(cpu_fallback_max_px=0))
        services = build_services(config)
        manifest = federation.FleetManifest(
            [federation.MemberSpec("a0", "hostA"),
             federation.MemberSpec("b0", "hostB", sock)],
            version=1, ring_seed="bench-fed")
        federation.install(manifest, self_host="hostA")
        members = federation.build_federated_members(
            config, services, manifest, SidecarClient, "hostA")
        router = FleetRouter(members, lane_width=2,
                             steal_min_backlog=0,
                             ring_seed=manifest.ring_seed,
                             wire_handoff=True)
        handler = FleetImageHandler(
            router, single_flight=SingleFlight(),
            base_services=services)
        coord = federation.FederationCoordinator(manifest, "hostA",
                                                 router)
        out: dict = {}
        try:
            verdicts = await coord.agree(strict=True)
            out["fed_manifest_agreed"] = all(
                v == "agreed" for v in verdicts.values())

            async def measure(leg: str) -> float:
                ctxs = [ImageRegionCtx.from_params(
                    params_for(i, leg)) for i in range(burst)]
                t0 = time.perf_counter()
                done = await asyncio.gather(
                    *(handler.render_image_region(c) for c in ctxs))
                assert all(done)
                return burst / (time.perf_counter() - t0)

            # p1: host B parked (draining — no routes land there),
            # p2: both processes serve.
            await measure("warm")          # shared compile warm-up
            router.members["b0"].draining = True
            p1 = await measure("p1")
            router.members["b0"].draining = False
            p2 = await measure("p2")
            out["fed_tiles_per_sec_p1"] = round(p1, 2)
            out["fed_tiles_per_sec_p2"] = round(p2, 2)
            out["fed_process_scaling_efficiency"] = round(
                p2 / (2.0 * p1), 3)

            # Cross-host warm handoff: the LOCAL member's HBM shard
            # ships over shard_transfer when it drains; the remote
            # process must answer the digests resident.  The gossip
            # round and the drain run inside ONE trace so the
            # cross-host control plane leaves a stitched waterfall.
            from omero_ms_image_region_tpu.utils import (
                decisions, telemetry)
            local = router.members["a0"]
            digests = sorted(local.resident_digests())
            with telemetry.trace_scope("bench-fed-forensics") as trace:
                await coord.gossip_once()
                doc = await router.drain_member("a0",
                                                settle_timeout_s=5.0)
            spans = trace.export_spans()
            telemetry.TRACES.finish("bench-fed-forensics")
            out["fed_drain_planes"] = doc["planes"]
            out["fed_drain_prestaged"] = doc["prestaged"]
            resident = 0
            if digests:
                import json as _json
                status, body = await members[1].client.call(
                    "plane_probe", {}, extra={"digests": digests})
                if status == 200 and body:
                    resident = sum(
                        bool(r) for r in _json.loads(
                            bytes(body).decode()).get("resident", ()))
            out["fed_remote_resident"] = resident
            router.undrain_member("a0")

            # --- stitched two-process waterfall: >=1 fed.hop span,
            # host B's clock anchored, spans causally ordered, and
            # every remote stage graft INSIDE its wire exchange's
            # [send, recv] window (the clock-anchoring contract).
            hops = sorted((s for s in spans if s["name"] == "fed.hop"),
                          key=lambda s: s["start_ms"])
            anchored = federation.host_clock_offset("hostB") is not None
            eps = 0.5    # float rounding on exported ms offsets
            # Causal: no hop starts before the trace began (a
            # mis-anchored clock would fling a graft negative) and
            # none has negative extent.
            ordered = bool(hops) and all(
                s["start_ms"] >= -eps and s["dur_ms"] >= 0.0
                for s in hops)
            wrappers = [s for s in hops
                        if s.get("kind") == "shard_transfer"]
            grafts = [s for s in hops if s.get("kind") == "stage"]
            contained = all(any(
                w["start_ms"] - eps <= g["start_ms"]
                and (g["start_ms"] + g["dur_ms"]
                     <= w["start_ms"] + w["dur_ms"] + eps)
                for w in wrappers) for g in grafts)
            out["fed_hop_spans"] = len(hops)
            out["fed_hop_grafts"] = len(grafts)
            out["fed_trace_stitched"] = int(
                bool(hops) and anchored and ordered and contained)

            # --- decision ledger: an autoscaler ticks against the
            # live router (floor == active members, so the quiet
            # queue wants "down" and the floor refuses it — one
            # "blocked" verdict) until the outcome horizon attaches
            # the MEASURED queue/member deltas; then the local and
            # remote rings merge into one host-attributed timeline,
            # with a renderer-span delta of ZERO for all of it.
            from omero_ms_image_region_tpu.server.autoscaler import (
                Autoscaler)
            from omero_ms_image_region_tpu.server.config import (
                AutoscalerConfig)
            from omero_ms_image_region_tpu.utils.stopwatch import (
                REGISTRY as span_reg)

            def _renders() -> int:
                snap = span_reg.snapshot()
                return sum(snap.get(n, {}).get("count", 0) for n in
                           ("Renderer.renderAsPackedInt",
                            "Renderer.renderAsPackedInt.cpu",
                            "Renderer.renderAsPackedInt.batch"))

            renders_before = _renders()
            fake_now = [0.0]
            scaler = Autoscaler(
                AutoscalerConfig(enabled=True, floor=2,
                                 hold_ticks=1, cooldown_s=0.0),
                router, clock=lambda: fake_now[0])
            horizon = decisions.LEDGER.outcome_horizon_ticks
            for _ in range(horizon + 2):
                fake_now[0] += 1.0
                scaler.tick()
            local_ring = decisions.LEDGER.snapshot()
            remote_ring = []
            import json as _json
            status, body = await members[1].client.call(
                "decisions", {})
            if status == 200 and body:
                remote_ring = list(_json.loads(
                    bytes(body).decode()).get("ring") or ())
            merged = ([dict(r, host=r.get("host") or "hostA")
                       for r in local_ring]
                      + [dict(r, host=r.get("host") or "hostB")
                         for r in remote_ring])
            merged.sort(key=lambda r: r.get("ts", 0.0))
            out["decision_records"] = sum(
                1 for r in merged if r["kind"] == "autoscaler"
                and "outcome" in r)
            out["fed_decision_hosts"] = len(
                {r["host"] for r in merged})
            out["forensics_render_delta"] = _renders() - renders_before
            assert out["fed_trace_stitched"] == 1, \
                "cross-host waterfall failed to stitch: " \
                f"hops={len(hops)} anchored={anchored} " \
                f"ordered={ordered} contained={contained}"
            assert out["decision_records"] >= 1, \
                "no autoscaler decision carried a measured outcome"
            assert out["fed_decision_hosts"] >= 2, \
                "merged decision timeline is missing a host"
            assert out["forensics_render_delta"] == 0, \
                "forensics reads performed render work"
            return out
        finally:
            await router.close()
            for member in members:
                if getattr(member, "remote", False):
                    await member.client.close()
            federation.uninstall()
            services.pixels_service.close()

    out = {"metric": "federation_smoke"}
    with tempfile.TemporaryDirectory() as tmp:
        planes = synthetic_wsi_tiles(rng, 2, 1, grid * tile_edge,
                                     grid * tile_edge).reshape(
            2, 1, grid * tile_edge, grid * tile_edge)
        build_pyramid(planes, os.path.join(tmp, "1"), n_levels=1)
        sock = os.path.join(tmp, "fed-b0.sock")
        sidecar_cfg = {
            "data-dir": tmp,
            "batcher": {"enabled": False},
            "raw-cache": {"enabled": True, "prefetch": False,
                          "digest-dedup": True},
            "renderer": {"cpu-fallback-max-px": 0},
            "federation": {
                "enabled": True, "host": "hostB", "shard-epoch": 1,
                "ring-seed": "bench-fed",
                "members": [
                    {"name": "a0", "host": "hostA"},
                    {"name": "b0", "host": "hostB", "address": sock},
                ]},
        }
        cfg_path = os.path.join(tmp, "sidecar.yaml")
        with open(cfg_path, "w") as f:
            yaml.safe_dump(sidecar_cfg, f)
        proc = spawn_sidecar(cfg_path, sock)
        try:
            out.update(asyncio.run(run(tmp, sock)))
        finally:
            proc.terminate()
            try:
                proc.wait(timeout=15)
            except Exception:
                proc.kill()
    out["elapsed_s"] = round(time.perf_counter() - t_start, 1)
    if emit:
        print(json.dumps(out))
    return out


def bench_partition_smoke(grid: int = 3, tile_edge: int = 32,
                          emit: bool = True):
    """Netsplit chaos drill (``bench.py --smoke --partition``): a
    3-host federated fleet (this process = host A's router + local
    member; two REAL spawned sidecar processes = hosts B and C, each
    running quorum tracking and its own gossip loop) driven through a
    full partition -> fence -> heal -> rejoin cycle UNDER SUSTAINED
    LOAD, with a two-phase epoch roll committed mid-partition.

    The drill cuts every link to host C at the sidecar wire layer
    (``utils.faultinject.PARTITIONS`` locally + the ``partition``
    control op remotely — that op is partition-exempt so the drill
    can always heal what it broke) and gates, on one record:

    * **majority availability** — the A+B majority serves the whole
      load loop with ZERO failures that are not counted shed
      (``part_majority_5xx`` == 0; breaker fail-fasts count as shed);
    * **minority fencing** — C loses quorum within the suspect
      window (``part_fence_ms``), REFUSES state-changing ops
      gracefully while still answering (``part_minority_refusals``
      from byte_put/prestage probes), and restores within
      ``part_restore_ms`` of heal;
    * **mid-partition epoch roll** — the coordinator rolls the fleet
      to epoch 2 while C is dark: strict-majority acks commit it
      (``part_roll_committed``/``part_roll_acks``), and the healed
      minority converges to the committed epoch through gossip
      anti-entropy with NO operator action (``part_rejoin_epoch``);
    * **no split-brain** — after heal every host agrees on the
      epoch-2 digest AND assigns every golden probe key with its OWN
      ring math (``part_postheal_agree``); C's byte tier accepts and
      returns byte-identical content again (``part_byte_agree``); and
      C's decision ledger holds the kind=``quorum`` fenced/restored
      pair (``part_quorum_ledger``).

    Judged by ``scripts/bench_gate.py --partition`` on the PARTITION
    record family.
    """
    _cpu_contract_drill("--partition")
    import asyncio
    import os
    import tempfile

    import yaml

    from omero_ms_image_region_tpu.flagship import synthetic_wsi_tiles
    from omero_ms_image_region_tpu.io.store import build_pyramid
    from omero_ms_image_region_tpu.parallel import federation
    from omero_ms_image_region_tpu.parallel.fleet import (
        FleetImageHandler, FleetRouter)
    from omero_ms_image_region_tpu.server.app import build_services
    from omero_ms_image_region_tpu.server.config import (
        AppConfig, BatcherConfig, RawCacheConfig, RendererConfig)
    from omero_ms_image_region_tpu.server.ctx import ImageRegionCtx
    from omero_ms_image_region_tpu.server.errors import OverloadedError
    from omero_ms_image_region_tpu.server.sidecar import (
        SidecarClient, spawn_sidecar)
    from omero_ms_image_region_tpu.server.singleflight import (
        SingleFlight)
    from omero_ms_image_region_tpu.utils import faultinject

    t_start = time.perf_counter()
    rng = np.random.default_rng(59)
    suspect_s = 1.2

    def params_for(i: int):
        x, y = i % grid, (i // grid) % grid
        w = 21000 + 600 * i
        return {
            "imageId": "1", "theZ": "0", "theT": "0",
            "tile": f"0,{x},{y},{tile_edge},{tile_edge}",
            "format": "png", "m": "c",
            "c": f"1|0:{w}$FF0000",
        }

    async def _poll(client: SidecarClient, timeout_s: float, pred):
        """Poll host C's partition-exempt control op until ``pred``
        accepts the reply doc; returns (doc, waited_ms)."""
        t0 = time.perf_counter()
        doc = None
        while time.perf_counter() - t0 < timeout_s:
            status, body = await client.call(
                "partition", {}, extra={"action": "show"})
            if status == 200 and body:
                doc = json.loads(bytes(body).decode())
                if pred(doc):
                    return doc, (time.perf_counter() - t0) * 1000.0
            await asyncio.sleep(0.06)
        return doc, (time.perf_counter() - t0) * 1000.0

    async def run(tmp: str, sock_b: str, sock_c: str) -> dict:
        config = AppConfig(
            data_dir=tmp,
            batcher=BatcherConfig(enabled=False),
            raw_cache=RawCacheConfig(enabled=True, prefetch=False),
            renderer=RendererConfig(cpu_fallback_max_px=0))
        services = build_services(config)
        specs = [federation.MemberSpec("a0", "hostA"),
                 federation.MemberSpec("b0", "hostB", sock_b),
                 federation.MemberSpec("c0", "hostC", sock_c)]
        manifest = federation.FleetManifest(
            list(specs), version=1, ring_seed="bench-part")
        federation.install(manifest, self_host="hostA")
        federation.install_quorum(federation.QuorumTracker(
            manifest, "hostA", suspect_after_s=suspect_s))
        members = federation.build_federated_members(
            config, services, manifest, SidecarClient, "hostA")
        router = FleetRouter(members, lane_width=2,
                             steal_min_backlog=0,
                             ring_seed=manifest.ring_seed,
                             wire_handoff=True)
        federation.set_roll_hook(router.apply_manifest)
        handler = FleetImageHandler(
            router, single_flight=SingleFlight(),
            base_services=services)
        coord = federation.FederationCoordinator(
            manifest, "hostA", router, gossip_interval_s=0.25)
        # Control channel to C: a raw client with no peer_host stamp
        # is partition-exempt by construction — the drill's scalpel
        # must keep working while the fleet's own links are dark.
        ctl_c = SidecarClient(sock_c, wire=config.wire)
        ctl_b = SidecarClient(sock_b, wire=config.wire)
        load = {"n": 0, "shed": 0, "hard": 0}
        stop_load = asyncio.Event()

        async def load_loop() -> None:
            i = 0
            while not stop_load.is_set():
                ctxs = [ImageRegionCtx.from_params(params_for(j))
                        for j in range(i % 5, i % 5 + 4)]
                done = await asyncio.gather(
                    *(handler.render_image_region(c) for c in ctxs),
                    return_exceptions=True)
                for r in done:
                    load["n"] += 1
                    if isinstance(r, OverloadedError):
                        load["shed"] += 1
                    elif isinstance(r, BaseException):
                        load["hard"] += 1
                i += 1
                await asyncio.sleep(0.02)

        out: dict = {}
        gossip_task = None
        load_task = None
        try:
            verdicts = await coord.agree(strict=True)
            out["part_manifest_agreed"] = int(all(
                v == "agreed" for v in verdicts.values()))
            gossip_task = asyncio.create_task(coord.run())
            # Warm-up: compile every process's render program before
            # the clock-sensitive phases (first-compile stalls would
            # smear the fence/restore latencies).
            warm = [ImageRegionCtx.from_params(params_for(i))
                    for i in range(grid * grid)]
            await asyncio.gather(
                *(handler.render_image_region(c) for c in warm))
            load_task = asyncio.create_task(load_loop())
            await asyncio.sleep(0.4)

            # --- partition: cut every link to/from host C.  A's
            # outbound edge is process-local; B's and C's outbound
            # edges go over the exempt control op.
            faultinject.PARTITIONS.add("hostA", "hostC")
            await ctl_b.call("partition", {}, extra={
                "action": "add", "src": "hostB", "dst": "hostC"})
            await ctl_c.call("partition", {}, extra={
                "action": "add", "src": "hostC", "dst": "hostA"})
            await ctl_c.call("partition", {}, extra={
                "action": "add", "src": "hostC", "dst": "hostB"})
            doc, waited = await _poll(
                ctl_c, timeout_s=suspect_s * 6 + 5.0,
                pred=lambda d: (d.get("quorum") or {}).get("fenced"))
            assert doc and (doc.get("quorum") or {}).get("fenced"), \
                f"host C never fenced: {doc}"
            out["part_fence_ms"] = round(waited, 1)

            # --- fenced refusals: state-changing ops answer
            # gracefully (200 + fenced flag), and each one counts.
            payload = b"partition-drill-bytes"
            import hashlib as _hashlib
            digest = _hashlib.blake2b(
                payload, digest_size=16).hexdigest()
            status, body = await ctl_c.call(
                "byte_put", {}, body=payload,
                extra={"key": "bench:part:byte", "digest": digest})
            assert status == 200, f"fenced byte_put errored: {body}"
            assert json.loads(bytes(body).decode()).get("fenced"), \
                "fenced minority accepted byte-tier write authority"
            status, body = await ctl_c.call(
                "prestage", {}, extra={"entries": []})
            assert status == 200 and json.loads(
                bytes(body).decode()).get("fenced"), \
                "fenced minority accepted inbound shard staging"
            refusals = ((doc.get("quorum") or {}).get("refusals")
                        or {})
            status, body = await ctl_c.call(
                "partition", {}, extra={"action": "show"})
            if status == 200 and body:
                refusals = (json.loads(bytes(body).decode())
                            .get("quorum") or {}).get("refusals") or {}
            out["part_minority_refusals"] = int(
                sum(refusals.values()))

            # --- mid-partition epoch roll: strict majority (A + B)
            # acks; dark C is "unreachable" and must not block it.
            rolled = federation.FleetManifest(
                list(specs), version=2, ring_seed="bench-part-v2")
            roll = await coord.roll_epoch(rolled)
            out["part_roll_committed"] = int(bool(roll["committed"]))
            out["part_roll_acks"] = roll["acks"]
            assert roll["committed"], f"majority roll aborted: {roll}"
            await asyncio.sleep(0.5)       # roll rides under load

            # --- heal: clear every rule, then watch C restore and
            # converge to the committed epoch via anti-entropy.
            faultinject.PARTITIONS.clear()
            await ctl_b.call("partition", {},
                             extra={"action": "clear"})
            await ctl_c.call("partition", {},
                             extra={"action": "clear"})
            doc, waited = await _poll(
                ctl_c, timeout_s=suspect_s * 6 + 5.0,
                pred=lambda d: not (d.get("quorum")
                                    or {}).get("fenced", True))
            assert doc and not (doc.get("quorum") or {}).get(
                "fenced", True), f"host C never restored: {doc}"
            out["part_restore_ms"] = round(waited, 1)
            doc, _ = await _poll(
                ctl_c, timeout_s=10.0,
                pred=lambda d: d.get("epoch") == 2)
            out["part_rejoin_epoch"] = int(doc.get("epoch") or 0) \
                if doc else 0
            assert out["part_rejoin_epoch"] == 2, \
                f"healed minority never converged to epoch 2: {doc}"

            # --- post-heal agreement: every host answers the epoch-2
            # digest AND its own ring math assigns the golden probe
            # keys identically (the split-brain gate).  The breaker on
            # A's c0 link may still be half-open — give it a few
            # rounds to prove the link again.
            agree_deadline = time.perf_counter() + 8.0
            agreed = {}
            while time.perf_counter() < agree_deadline:
                agreed = await coord.agree(strict=False)
                if agreed and all(v == "agreed"
                                  for v in agreed.values()):
                    break
                await asyncio.sleep(0.25)
            out["part_postheal_agree"] = int(bool(agreed) and all(
                v == "agreed" for v in agreed.values()))
            assert out["part_postheal_agree"] == 1, \
                f"post-heal agreement incomplete: {agreed}"

            # --- byte-tier rejoin: the restored C accepts write
            # authority again and answers the bytes back verbatim.
            status, body = await ctl_c.call(
                "byte_put", {}, body=payload,
                extra={"key": "bench:part:byte", "digest": digest})
            stored = (status == 200 and json.loads(
                bytes(body).decode()).get("stored"))
            status, body = await ctl_c.call(
                "byte_fetch", {}, extra={"key": "bench:part:byte"})
            out["part_byte_agree"] = int(
                bool(stored) and status == 200
                and bytes(body) == payload)

            # --- C's own ledger holds the fence/restore pair.
            ledger = 0
            status, body = await ctl_c.call("decisions", {})
            if status == 200 and body:
                ring = json.loads(
                    bytes(body).decode()).get("ring") or ()
                ledger = sum(1 for r in ring
                             if r.get("kind") == "quorum")
            out["part_quorum_ledger"] = ledger

            stop_load.set()
            await load_task
            load_task = None
            out["part_load_requests"] = load["n"]
            out["part_majority_shed"] = load["shed"]
            out["part_majority_5xx"] = load["hard"]
            assert load["n"] > 0, "load loop never ran"
            assert load["hard"] == 0, \
                f"majority side failed {load['hard']} requests " \
                f"without shedding (of {load['n']})"
            return out
        finally:
            stop_load.set()
            for task in (load_task, gossip_task):
                if task is not None:
                    task.cancel()
                    try:
                        await task
                    except (asyncio.CancelledError, Exception):
                        pass
            faultinject.PARTITIONS.clear()
            await ctl_c.close()
            await ctl_b.close()
            await router.close()
            for member in members:
                if getattr(member, "remote", False):
                    await member.client.close()
            federation.uninstall()
            services.pixels_service.close()

    out = {"metric": "partition_smoke"}
    with tempfile.TemporaryDirectory() as tmp:
        planes = synthetic_wsi_tiles(rng, 2, 1, grid * tile_edge,
                                     grid * tile_edge).reshape(
            2, 1, grid * tile_edge, grid * tile_edge)
        build_pyramid(planes, os.path.join(tmp, "1"), n_levels=1)
        sock_b = os.path.join(tmp, "part-b0.sock")
        sock_c = os.path.join(tmp, "part-c0.sock")
        members_doc = [
            {"name": "a0", "host": "hostA"},
            {"name": "b0", "host": "hostB", "address": sock_b},
            {"name": "c0", "host": "hostC", "address": sock_c},
        ]
        procs = []
        try:
            for host, sock in (("hostB", sock_b), ("hostC", sock_c)):
                sidecar_cfg = {
                    "data-dir": tmp,
                    "batcher": {"enabled": False},
                    "raw-cache": {"enabled": True, "prefetch": False,
                                  "digest-dedup": True},
                    "renderer": {"cpu-fallback-max-px": 0},
                    "image-region-cache": {"enabled": True},
                    "federation": {
                        "enabled": True, "host": host,
                        "shard-epoch": 1, "ring-seed": "bench-part",
                        "quorum": True,
                        "suspect-after-s": suspect_s,
                        "gossip-interval-s": 0.3,
                        "members": members_doc,
                    },
                }
                cfg_path = os.path.join(
                    tmp, f"sidecar-{host}.yaml")
                with open(cfg_path, "w") as f:
                    yaml.safe_dump(sidecar_cfg, f)
                procs.append(spawn_sidecar(cfg_path, sock))
            out.update(asyncio.run(run(tmp, sock_b, sock_c)))
        finally:
            for proc in procs:
                proc.terminate()
            for proc in procs:
                try:
                    proc.wait(timeout=15)
                except Exception:
                    proc.kill()
    out["elapsed_s"] = round(time.perf_counter() - t_start, 1)
    if emit:
        print(json.dumps(out))
    return out


def bench_restart_smoke():
    """Warm-restart gate at smoke scale: render, "kill", restart with
    persistence on, and prove the first previously-seen tile serves
    from the disk byte tier + a deserialized executable — no pixel
    read, no device dispatch, no XLA compile.

    In-process restart semantics: the second life builds a completely
    fresh service stack (new memory caches, new HBM cache, new
    executable registry) over the SAME persistence directory — what a
    process restart drops is exactly what a fresh stack starts
    without.  (The one thing an in-process "kill" cannot drop is
    XLA's jit cache; the compile assertion therefore ALSO checks that
    the second life's registry really deserialized its programs from
    disk, which is the mechanism a real restart rides.)

    Reported keys (one JSON line, like the other smoke gates):

    * ``restart_time_to_first_tile_ms`` — boot-to-first-200 on the
      repeat working set;
    * ``restart_warm_hit_rate`` — fraction of the repeat working set
      served with ZERO new device dispatches (acceptance: >= 0.9);
    * ``restart_first_tile_identical`` — rehydrated bytes ==
      pre-restart bytes, and == the jax-free refimpl render of the
      same request (golden check);
    * ``rehydrate_*`` — what the boot rehydrator replayed.
    """
    _cpu_contract_drill("--restart")
    import asyncio
    import os
    import tempfile

    from omero_ms_image_region_tpu.flagship import synthetic_wsi_tiles
    from omero_ms_image_region_tpu.io.store import build_pyramid
    from omero_ms_image_region_tpu.server.config import (
        AppConfig, BatcherConfig, PersistenceConfig, RawCacheConfig,
        RendererConfig)
    from omero_ms_image_region_tpu.services.cache import CacheConfig
    from omero_ms_image_region_tpu.utils import telemetry

    t_start = time.perf_counter()
    rng = np.random.default_rng(11)
    grid, edge, channels = 2, 256, 2
    with tempfile.TemporaryDirectory() as tmp:
        planes = synthetic_wsi_tiles(rng, 2, 1, 512, 512).reshape(
            2, 1, 512, 512)
        build_pyramid(planes, os.path.join(tmp, "1"), n_levels=1)
        warm_dir = os.path.join(tmp, "warm-state")

        def mkconfig():
            return AppConfig(
                data_dir=tmp,
                # sync disk writes: the gate must judge durability, not
                # race the write-behind queue.
                caches=CacheConfig.enabled_all(disk_sync_writes=True),
                batcher=BatcherConfig(enabled=True, linger_ms=2.0),
                raw_cache=RawCacheConfig(enabled=True, prefetch=False),
                renderer=RendererConfig(cpu_fallback_max_px=0),
                persistence=PersistenceConfig(
                    enabled=True, dir=warm_dir,
                    snapshot_interval_s=0))   # snapshot explicitly

        def url(i):
            x, y = i % grid, (i // grid) % grid
            chans = ",".join(f"{c + 1}|0:{60000 - 5000 * c}$FF0000"
                             for c in range(channels))
            return (f"/webgateway/render_image_region/1/0/0"
                    f"?tile=0,{x},{y},{edge},{edge}"
                    f"&format=png&m=c&c={chans}")

        out = asyncio.run(_restart_run(mkconfig, url, grid * grid))

        # Golden check via the jax-free refimpl path: the rehydrated
        # bytes must equal what the reference renderer produces for
        # the identical request — a poisoned or stale disk entry
        # cannot pass this.
        from omero_ms_image_region_tpu.server.ctx import ImageRegionCtx
        from omero_ms_image_region_tpu.server.degraded import (
            DegradedCpuHandler)
        chans = ",".join(f"{c + 1}|0:{60000 - 5000 * c}$FF0000"
                         for c in range(channels))
        ctx = ImageRegionCtx.from_params({
            "imageId": "1", "theZ": "0", "theT": "0",
            "tile": f"0,0,0,{edge},{edge}", "format": "png",
            "m": "c", "c": chans}, None)
        golden = asyncio.run(
            DegradedCpuHandler(mkconfig()).render_image_region(ctx))
        out["restart_first_tile_identical"] = bool(
            out.pop("_first_body") == golden
            and out["restart_bytes_identical"])

    out.update({
        "metric": "restart_smoke",
        "unit": "invariants",
        "rehydrate_executables_loaded":
            telemetry.PERSIST.rehydrate_executables_loaded,
        "rehydrate_planes_restaged":
            telemetry.PERSIST.rehydrate_planes_restaged,
        "elapsed_s": round(time.perf_counter() - t_start, 1),
    })
    print(json.dumps(out))
    return out


async def _restart_run(mkconfig, url, working_set: int):
    import asyncio
    import os

    from aiohttp.test_utils import TestClient, TestServer

    from omero_ms_image_region_tpu.server.app import (SERVICES_KEY,
                                                      create_app)
    from omero_ms_image_region_tpu.utils import telemetry

    # ---- life 1: render the working set, persist, "die".
    app = create_app(mkconfig())
    client = TestClient(TestServer(app))
    await client.start_server()
    try:
        bodies = []
        for i in range(working_set):
            r = await client.get(url(i))
            body = await r.read()
            assert r.status == 200, f"life-1 render failed: {r.status}"
            bodies.append(body)
        services = app[SERVICES_KEY]
        exec_cache = services.renderer.exec_cache
        if exec_cache is not None:
            # The background executable captures must land before the
            # "crash" — a real deployment has its whole life for this;
            # the smoke has seconds.
            await asyncio.to_thread(exec_cache.drain, 30.0)
        snapshot_path = await asyncio.to_thread(
            services.warmstate.snapshot_now)
        assert snapshot_path and os.path.exists(snapshot_path)
    finally:
        await client.close()

    # ---- life 2: fresh stack over the same persistence dir.
    compiles_before = telemetry.COMPILE.events
    t_boot = time.perf_counter()
    app = create_app(mkconfig())
    client = TestClient(TestServer(app))
    await client.start_server()
    try:
        # The rehydrator is background + best-effort; the gate waits
        # for it so the assertions below judge the REHYDRATED state.
        for _ in range(200):
            if (not telemetry.PERSIST.rehydrate_running
                    and telemetry.PERSIST.rehydrate_items_total):
                break
            await asyncio.sleep(0.05)
        renderer = app[SERVICES_KEY].renderer
        first_ms = None
        identical = True
        warm_hits = 0
        for i in range(working_set):
            d0 = renderer.batches_dispatched
            t0 = time.perf_counter()
            r = await client.get(url(i))
            body = await r.read()
            if first_ms is None:
                first_ms = (time.perf_counter() - t_boot) * 1000.0
            assert r.status == 200, f"restart render failed: {r.status}"
            if body != bodies[i]:
                identical = False
            if renderer.batches_dispatched == d0:
                warm_hits += 1
        return {
            "value": working_set,
            "restart_time_to_first_tile_ms": round(first_ms, 1),
            "restart_warm_hit_rate": round(warm_hits / working_set, 3),
            "restart_bytes_identical": identical,
            "restart_compile_events": (telemetry.COMPILE.events
                                       - compiles_before),
            "_first_body": bodies[0],
        }
    finally:
        await client.close()


def bench_offload_smoke(grid: int = 3, edge: int = 128,
                        variants: int = 2):
    """Repeat-viewer offload gate (``bench.py --smoke --offload``):
    the edge ladder end to end over a REAL 2-sidecar remote fleet —
    cold render, warm-local byte hit, warm-peer byte fetch (the owner
    drains; its successor serves the owner's bytes over
    ``byte_probe``/``byte_fetch`` instead of re-rendering), and
    If-None-Match -> 304 revalidation.

    Reported keys (one JSON line, like the other smoke gates):

    * ``origin_offload_ratio`` — fraction of the repeat-viewer mix
      served with ZERO device render work (acceptance: >= 0.8);
    * ``p50_304_ms`` — revalidation latency (acceptance: at least 10x
      below ``p50_service_tile_ms``, the cold render p50 measured in
      the same run);
    * ``peer_hit_rate`` — fraction of the re-routed working set served
      from the draining owner's byte tier, byte-identical to the
      origin render.
    """
    _cpu_contract_drill("--offload")
    import asyncio
    import os
    import tempfile

    from omero_ms_image_region_tpu.flagship import synthetic_wsi_tiles
    from omero_ms_image_region_tpu.io.store import build_pyramid
    from omero_ms_image_region_tpu.server.config import (
        AppConfig, BatcherConfig, FleetConfig, RawCacheConfig,
        RendererConfig, SidecarConfig)
    from omero_ms_image_region_tpu.services.cache import CacheConfig

    t_start = time.perf_counter()
    rng = np.random.default_rng(7)
    with tempfile.TemporaryDirectory() as tmp:
        planes = synthetic_wsi_tiles(
            rng, 2, 1, grid * edge, grid * edge).reshape(
            2, 1, grid * edge, grid * edge)
        build_pyramid(planes, os.path.join(tmp, "1"), n_levels=1)
        socks = [os.path.join(tmp, f"m{i}.sock") for i in range(2)]

        def member_cfg():
            # Each sidecar owns its OWN byte-cache chain (memory LRU
            # per process-alike stack): the peer tier is real, not an
            # artifact of a shared cache.
            return AppConfig(
                data_dir=tmp,
                caches=CacheConfig.enabled_all(),
                batcher=BatcherConfig(enabled=False),
                raw_cache=RawCacheConfig(enabled=True, prefetch=False),
                renderer=RendererConfig(cpu_fallback_max_px=0))

        frontend_cfg = AppConfig(
            data_dir=tmp,
            sidecar=SidecarConfig(role="frontend"),
            fleet=FleetConfig(enabled=True, sockets=tuple(socks)))

        params = []
        for v in range(variants):
            w = 30000 + v * 900
            for x in range(grid):
                for y in range(grid):
                    params.append({
                        "imageId": "1", "theZ": "0", "theT": "0",
                        "tile": f"0,{x},{y},{edge},{edge}",
                        "format": "png", "m": "c",
                        "c": f"1|0:{w}$FF0000,2|0:{w - 700}$00FF00",
                    })

        def url_of(p):
            q = "&".join(f"{k}={p[k]}" for k in
                         ("tile", "format", "m", "c"))
            return (f"/webgateway/render_image_region/"
                    f"{p['imageId']}/{p['theZ']}/{p['theT']}?{q}")

        out = asyncio.run(_offload_run(member_cfg, frontend_cfg,
                                       socks, params, url_of))

    out.update({
        "metric": "offload_smoke",
        "unit": "invariants",
        "elapsed_s": round(time.perf_counter() - t_start, 1),
    })
    print(json.dumps(out))
    return out


async def _offload_run(member_cfg, frontend_cfg, socks, params,
                       url_of):
    import asyncio
    import os

    from aiohttp.test_utils import TestClient, TestServer

    from omero_ms_image_region_tpu.server.app import (FLEET_ROUTER_KEY,
                                                      create_app)
    from omero_ms_image_region_tpu.server.ctx import ImageRegionCtx
    from omero_ms_image_region_tpu.server.sidecar import run_sidecar
    from omero_ms_image_region_tpu.utils import telemetry
    from omero_ms_image_region_tpu.utils.stopwatch import \
        REGISTRY as SPANS

    def render_spans() -> int:
        snap = SPANS.snapshot()
        return (snap.get("Renderer.renderAsPackedInt",
                         {}).get("count", 0)
                + snap.get("Renderer.renderAsPackedInt.cpu",
                           {}).get("count", 0))

    sidecars = [asyncio.create_task(run_sidecar(member_cfg(), sock))
                for sock in socks]
    for sock in socks:
        for _ in range(400):
            for task in sidecars:
                if task.done():
                    task.result()     # surface an early death
            if os.path.exists(sock):
                break
            await asyncio.sleep(0.05)
        else:
            raise AssertionError(f"sidecar socket {sock} missing")

    app = create_app(frontend_cfg)
    client = TestClient(TestServer(app))
    await client.start_server()
    router = app[FLEET_ROUTER_KEY]
    try:
        urls = [url_of(p) for p in params]
        ctxs = [ImageRegionCtx.from_params(dict(p), None)
                for p in params]

        # ---- cold: every tile renders once on its ring owner.
        bodies, etags, cold_ms = {}, {}, []
        for u in urls:
            t0 = time.perf_counter()
            r = await client.get(u)
            body = await r.read()
            cold_ms.append((time.perf_counter() - t0) * 1000.0)
            assert r.status == 200, f"cold render failed: {r.status}"
            etags[u] = r.headers.get("ETag")
            assert etags[u], "200 missing its ETag"
            bodies[u] = body
        renders_cold = render_spans()
        assert renders_cold > 0, "cold leg rendered nothing"

        warm_total = 0
        # ---- warm-local: straight repeats hit the owner's byte tier.
        for u in urls:
            r = await client.get(u)
            body = await r.read()
            assert r.status == 200 and body == bodies[u]
            warm_total += 1

        # ---- 304: revalidation with the cold leg's ETags.
        t304 = []
        for u in urls:
            t0 = time.perf_counter()
            r = await client.get(
                u, headers={"If-None-Match": etags[u]})
            await r.read()
            t304.append((time.perf_counter() - t0) * 1000.0)
            assert r.status == 304, f"expected 304, got {r.status}"
            assert r.headers.get("ETag") == etags[u]
            warm_total += 1

        # ---- warm-peer: drain one member; its shard re-routes to
        # the survivor, which must serve the DRAINING owner's bytes
        # over byte_probe/byte_fetch — zero re-renders.
        owners = {u: router.owner_of(ctx)
                  for u, ctx in zip(urls, ctxs)}
        victim = next(name for name in router.order
                      if any(o == name for o in owners.values()))
        owned = [u for u in urls if owners[u] == victim]
        await router.drain_member(victim, prestage=False,
                                  settle_timeout_s=5.0)
        fetches_before = telemetry.HTTPCACHE.peer_fetches
        for u in owned:
            r = await client.get(u)
            body = await r.read()
            assert r.status == 200, f"peer leg failed: {r.status}"
            assert body == bodies[u], \
                "peer bytes differ from the origin render"
            warm_total += 1
        peer_fetches = telemetry.HTTPCACHE.peer_fetches \
            - fetches_before
        router.undrain_member(victim)

        renders_warm = render_spans() - renders_cold
        offload = 1.0 - renders_warm / max(1, warm_total)
        return {
            "value": round(offload, 3),
            "origin_offload_ratio": round(offload, 3),
            "p50_service_tile_ms": round(
                float(np.median(cold_ms)), 2),
            "p50_304_ms": round(float(np.median(t304)), 3),
            "peer_hit_rate": round(
                peer_fetches / max(1, len(owned)), 3),
            "peer_working_set": len(owned),
            "warm_requests": warm_total,
            "warm_renders": renders_warm,
            "n_304": len(t304),
        }
    finally:
        await client.close()
        for task in sidecars:
            task.cancel()
        await asyncio.gather(*sidecars, return_exceptions=True)


def bench_chaos_smoke(duration_s: float = 1.5, seed: int = 1234,
                      artifacts_dir: str = None):
    """Robustness gate at smoke scale: the full frontend -> sidecar ->
    batcher chain under SEEDED fault injection (wire drops/truncations/
    delays, transient device errors, a freezing device lane), with
    deadlines + admission control + breaker armed.

    The invariants (tests/test_chaos_smoke.py wires this into tier-1):

    * **zero 5xx-without-shed** — every response is 200, 503 (shed,
      with ``Retry-After``) or 504 (deadline); a bare 500 means a
      fault leaked through the tolerance layer as a raw failure;
    * **bounded p99** — chaos-window latency stays under the request
      deadline plus scheduling slack (the deadline actually cuts
      tails, rather than work queueing toward a timeout);
    * the chaos actually happened (injected-fault counters are
      nonzero — a chaos run that injected nothing proves nothing) and
      the service still made progress (some 200s);
    * ``plane_put`` was never auto-retried;
    * the FORENSIC chain fired: the flight-recorder ring is non-empty
      after the chaos window, and the induced availability-SLO breach
      (the sidecar is killed at the end and requests shed) produced a
      black-box dump plus slow-request waterfalls.

    ``artifacts_dir`` keeps the dump/waterfall files after the run
    (tests round-trip them through scripts/trace_report.py); None
    spools them inside the run's tempdir.  Prints ONE JSON line, like
    the other smoke gate.
    """
    import asyncio
    import os
    import tempfile

    from omero_ms_image_region_tpu.flagship import synthetic_wsi_tiles
    from omero_ms_image_region_tpu.io.store import build_pyramid
    from omero_ms_image_region_tpu.server.config import (
        AppConfig, BatcherConfig, FaultToleranceConfig, RawCacheConfig,
        RendererConfig, SidecarConfig, SloConfig, TelemetryConfig)
    from omero_ms_image_region_tpu.utils import telemetry
    from omero_ms_image_region_tpu.utils.faultinject import (
        FaultInjectionConfig)

    DEADLINE_MS = 5000.0
    t_start = time.perf_counter()
    rng = np.random.default_rng(seed)
    with tempfile.TemporaryDirectory() as tmp:
        art = artifacts_dir or os.path.join(tmp, "artifacts")
        planes = synthetic_wsi_tiles(rng, 2, 1, 512, 512).reshape(
            2, 1, 512, 512)
        build_pyramid(planes, os.path.join(tmp, "1"), n_levels=1)
        sock = os.path.join(tmp, "chaos.sock")
        sidecar_cfg = AppConfig(
            data_dir=tmp,
            batcher=BatcherConfig(enabled=True, linger_ms=2.0),
            raw_cache=RawCacheConfig(enabled=True, prefetch=False),
            renderer=RendererConfig(cpu_fallback_max_px=0))
        frontend_cfg = AppConfig(
            data_dir=tmp,
            sidecar=SidecarConfig(socket=sock, role="frontend"),
            # Forensics under chaos: every request over 1 ms dumps its
            # waterfall, and an availability SLO tight enough that the
            # induced outage below must breach it (short windows keep
            # the smoke run fast; the burn math is scale-free).
            telemetry=TelemetryConfig(
                slow_request_ms=1.0,
                slow_request_dir=os.path.join(art, "slow"),
                flight_recorder_dir=os.path.join(art, "flight")),
            slo=SloConfig(availability_target=0.999,
                          fast_window_s=5.0, slow_window_s=10.0,
                          breach_burn_rate=5.0),
            fault_tolerance=FaultToleranceConfig(
                request_deadline_ms=DEADLINE_MS,
                retry_base_backoff_ms=10.0,
                retry_max_backoff_ms=100.0,
                # One injected connection death fails EVERY multiplexed
                # in-flight call at once, so consecutive-failure bursts
                # run 4-5 deep per fault; 8 keeps the breaker for real
                # outages rather than single chaos events.
                breaker_failure_threshold=8,
                breaker_reset_s=0.25,
                admission_max_queue=64))
        chaos = FaultInjectionConfig(
            seed=seed,
            wire_drop_rate=0.04,
            wire_truncate_rate=0.02,
            wire_delay_rate=0.05, wire_delay_ms=30.0,
            device_error_rate=0.08,
            freeze_rate=0.05, freeze_ms=100.0)
        retries_before = dict(telemetry.RESILIENCE.retries)
        try:
            out = asyncio.run(_chaos_run(sidecar_cfg, frontend_cfg,
                                         sock, chaos, duration_s,
                                         DEADLINE_MS))
        finally:
            # The chaos SLO posture must not leak into whatever this
            # process runs next (tier-1 shares the interpreter).
            telemetry.SLO.reset()
        # Diff against the pre-run counters: the gate must judge THIS
        # window, not retries other tests in the process accumulated.
        retried_ops = {
            op for op, n in telemetry.RESILIENCE.retries.items()
            if n > retries_before.get(op, 0)}
        slow_dir = os.path.join(art, "slow")
        out.update({
            "metric": "chaos_smoke",
            "unit": "invariants",
            "deadline_ms": DEADLINE_MS,
            "plane_put_retried": "plane_put" in retried_ops,
            "retried_ops": sorted(retried_ops),
            "slow_dumps": (len(os.listdir(slow_dir))
                           if os.path.isdir(slow_dir) else 0),
            "elapsed_s": round(time.perf_counter() - t_start, 1),
        })
    print(json.dumps(out))
    return out


async def _chaos_run(sidecar_cfg, frontend_cfg, sock, chaos,
                     duration_s, deadline_ms):
    import asyncio
    import os

    from aiohttp.test_utils import TestClient, TestServer

    from omero_ms_image_region_tpu.server.app import create_app
    from omero_ms_image_region_tpu.server.sidecar import run_sidecar
    from omero_ms_image_region_tpu.utils import faultinject

    sidecar_task = asyncio.create_task(run_sidecar(sidecar_cfg, sock))
    for _ in range(600):
        if sidecar_task.done():
            raise AssertionError(
                f"chaos sidecar died at startup: "
                f"{sidecar_task.exception()!r}")
        if os.path.exists(sock):
            break
        await asyncio.sleep(0.05)
    app = create_app(frontend_cfg)
    client = TestClient(TestServer(app))
    await client.start_server()
    try:
        grid, channels, edge = 2, 2, 256

        def url(i, k):
            x, y = i % grid, (i // grid) % grid
            w = 20000 + (k % 5000) * 9
            chans = ",".join(f"{c + 1}|0:{w - 1000 * c}$FF0000"
                             for c in range(channels))
            return (f"/webgateway/render_image_region/1/0/0"
                    f"?tile=0,{x},{y},{edge},{edge}"
                    f"&format=png&m=c&c={chans}")

        # Warm FIRST (compiles, byte-cache-miss path) with no chaos, so
        # the p99 bound below measures the tolerance layer, not XLA's
        # first-compile.
        resps = await asyncio.gather(
            *(client.get(url(i, i)) for i in range(grid * grid)))
        assert all(r.status == 200 for r in resps), \
            [r.status for r in resps]

        faultinject.install(chaos)
        statuses: list = []
        latencies_ms: list = []
        missing_retry_after = 0
        seq = 0
        t_stop = time.perf_counter() + duration_s

        async def worker(i: int) -> None:
            nonlocal seq, missing_retry_after
            while time.perf_counter() < t_stop:
                seq += 1
                t0 = time.perf_counter()
                r = await client.get(url(i, 16 + seq))
                await r.read()
                statuses.append(r.status)
                latencies_ms.append(
                    (time.perf_counter() - t0) * 1000.0)
                if r.status == 503 and "Retry-After" not in r.headers:
                    missing_retry_after += 1

        await asyncio.gather(*(worker(i) for i in range(4)))
        ok = sum(1 for s in statuses if s == 200)
        shed = sum(1 for s in statuses if s == 503)
        deadline_hit = sum(1 for s in statuses if s == 504)
        bare_5xx = sum(1 for s in statuses
                       if s >= 500 and s not in (503, 504))
        lat = sorted(latencies_ms)
        p99 = lat[max(0, int(len(lat) * 0.99) - 1)] if lat else 0.0
        inj = faultinject.active()
        injected = inj.snapshot() if inj is not None else {}
        # The black box must have been recording through the window
        # (batch formation, retries, breaker transitions) — a chaos
        # run whose flight ring is empty proves the recorder is dead.
        from omero_ms_image_region_tpu.utils import telemetry
        flight_events = len(telemetry.FLIGHT)

        # Induced SLO breach: kill the device backend and keep asking.
        # Every request now sheds (503 after the retry ladder, then
        # breaker-fast), availability burns through the tight budget in
        # both windows, and the breach transition must dump the flight
        # recorder — the acceptance-criteria forensic chain, end to
        # end, deterministic (no chaos dice involved).
        faultinject.uninstall()
        sidecar_task.cancel()
        try:
            await sidecar_task
        except (asyncio.CancelledError, Exception):
            pass
        try:
            os.unlink(sock)
        except OSError:
            pass
        outage_statuses = []
        for i in range(12):
            r = await client.get(url(i, 9000 + i))
            await r.read()
            outage_statuses.append(r.status)
        slo_breached = telemetry.SLO.any_breached()
        flight_dir = frontend_cfg.telemetry.flight_recorder_dir
        dumps = (sorted(os.listdir(flight_dir))
                 if os.path.isdir(flight_dir) else [])
        dump_events = 0
        if dumps:
            with open(os.path.join(flight_dir, dumps[-1])) as f:
                dump_events = len(json.load(f).get("events", ()))
        return {
            "injected": injected,
            "value": len(statuses),
            "ok": ok, "shed": shed, "deadline_hit": deadline_hit,
            "bare_5xx": bare_5xx,
            "missing_retry_after": missing_retry_after,
            "p99_ms": round(p99, 1),
            "zero_bare_5xx": bare_5xx == 0,
            "p99_bounded": p99 <= deadline_ms + 2000.0,
            "flight_events": flight_events,
            "outage_sheds": sum(1 for s in outage_statuses
                                if s in (503, 504)),
            "slo_breached": slo_breached,
            "flight_dumps": len(dumps),
            "flight_dump": (os.path.join(flight_dir, dumps[-1])
                            if dumps else None),
            "flight_dump_events": dump_events,
        }
    finally:
        await client.close()
        faultinject.uninstall()
        sidecar_task.cancel()
        try:
            await sidecar_task
        except (asyncio.CancelledError, Exception):
            pass


# -------------------------------------------------------------- config 1

def bench_config1(rng):
    """1-ch uint8 256^2 linear tile on the host reference kernel:
    single-tile renders/sec, the CPU comparator of BASELINE's first
    row.

    There is no served number beside it any more: since PR 28 a
    DEFAULT deployment renders a full 256^2 tile on the device, in
    groups of up to 64 (``batcher.group_cap``), and what that gives end
    to end is the benchmark cell ``stock4-u16-t256.pan`` (PERF.md).
    The host kernel still serves what is smaller than a stock tile.
    """
    from omero_ms_image_region_tpu.refimpl import render_ref

    rdef, s = _settings_for(1, ptype="uint8", window=(0.0, 255.0),
                            model="greyscale")
    raw = rng.integers(0, 255, size=(1, 256, 256)).astype(np.float32)
    return 1.0 / _timed(lambda: render_ref(raw, rdef), repeats=10)


# -------------------------------------------------------------- config 2

def bench_config2(rng):
    """3-ch uint16 full planes (2048^2) -> JPEG bytes, streamed.

    ``render_image`` traffic is a stream of plane requests; the device
    pipeline (dispatch all, prefix-fetch + entropy-code in arrival
    order) hides the per-dispatch round trip exactly as the flagship
    tile path does.  A CPU comparator (reference renderer + PIL) runs on
    identical planes.
    """
    import jax

    from omero_ms_image_region_tpu.flagship import (
        batched_args, synthetic_wsi_tiles,
    )
    from omero_ms_image_region_tpu.ops.jpegenc import (
        SparseWireFetcher, default_sparse_cap, encode_sparse_buffers,
        quant_tables, render_to_jpeg_sparse,
    )
    from omero_ms_image_region_tpu.refimpl import render_ref

    import concurrent.futures as cf

    n_planes = 6
    rdef, s = _settings_for(3)
    planes = synthetic_wsi_tiles(rng, n_planes, 3, 2048, 2048)
    dev = [jax.device_put(p[None]) for p in planes]
    jax.block_until_ready(dev)
    args = batched_args(s, np.zeros((1, 3, 1, 1), np.float32))[1:]
    qy, qc = (t.astype(np.int32) for t in quant_tables(85))
    cap = default_sparse_cap(2048, 2048)
    fetcher = SparseWireFetcher(2048, 2048, cap)

    def stream(pool):
        # Dispatch every plane up-front (device pipelines), then hand each
        # finished wire buffer to the pool: plane k's entropy encode (C++,
        # GIL released) overlaps plane k+1's prefix fetch.
        handles = [
            fetcher.start(render_to_jpeg_sparse(p, *args, qy, qc, cap=cap))
            for p in dev
        ]
        futs = [
            pool.submit(encode_sparse_buffers,
                        fetcher.finish(h), 2048, 2048, 85, cap)
            for h in handles
        ]
        for f in futs:
            assert f.result()[0][:2] == b"\xff\xd8"

    with cf.ThreadPoolExecutor(max_workers=4) as pool:
        planes_per_sec = n_planes / _timed(lambda: stream(pool), repeats=3)

    # CPU comparator: reference render + PIL JPEG on one identical plane.
    def cpu_plane():
        _cpu_jpeg(render_ref(planes[0].astype(np.float32), rdef))

    cpu_planes_per_sec = 1.0 / _timed(cpu_plane, repeats=3)
    return planes_per_sec, cpu_planes_per_sec


# -------------------------------------------------------------- config 4

def bench_config4(rng):
    """intmax Z-projection over 32-plane 3-ch 512^2 stacks -> JPEG.

    Projection + render + JPEG front end fuse into one device dispatch
    per request; a stream of projection requests pipelines (dispatch all,
    prefix-fetch + encode in arrival order) so the link round trip is
    paid once, not per request.
    """
    import jax
    import jax.numpy as jnp

    from omero_ms_image_region_tpu.flagship import (
        batched_args, synthetic_wsi_tiles,
    )
    from omero_ms_image_region_tpu.models.rendering import Projection
    from omero_ms_image_region_tpu.ops.jpegenc import (
        SparseWireFetcher, default_sparse_cap, encode_sparse_buffers,
        quant_tables, render_to_jpeg_sparse,
    )
    from omero_ms_image_region_tpu.ops.projection import project_stack

    n_req = 6
    rdef, s = _settings_for(3)
    stacks = [jax.device_put(synthetic_wsi_tiles(rng, 3, 32, 512, 512))
              for _ in range(n_req)]          # [C=3, Z=32, H, W] each
    jax.block_until_ready(stacks)
    args = batched_args(s, np.zeros((1, 3, 1, 1), np.float32))[1:]
    qy, qc = (np.asarray(t, np.int32) for t in quant_tables(85))
    cap = default_sparse_cap(512, 512)
    fetcher = SparseWireFetcher(512, 512, cap)

    @jax.jit
    def project_render(stacks_):
        planes = jax.vmap(
            lambda st: project_stack(st, Projection.MAXIMUM_INTENSITY,
                                     0, 31, 1, 65535.0)
        )(stacks_.astype(jnp.float32))
        return render_to_jpeg_sparse(planes[None], *args, qy, qc, cap=cap)

    def stream():
        handles = [fetcher.start(project_render(st)) for st in stacks]
        for h in handles:
            jpegs = encode_sparse_buffers(
                fetcher.finish(h), 512, 512, 85, cap)
            assert jpegs[0][:2] == b"\xff\xd8"

    tpu_rate = n_req / _timed(stream, repeats=3)

    # CPU comparator: reference projection + render + PIL JPEG on one
    # identical stack.
    from omero_ms_image_region_tpu.refimpl import project_ref, render_ref

    host_stack = np.asarray(stacks[0], np.float32)   # [C, Z, H, W]

    def cpu_projection():
        planes = np.stack([
            project_ref(host_stack[c], Projection.MAXIMUM_INTENSITY,
                        0, 31, 1, 65535.0)
            for c in range(3)
        ])
        _cpu_jpeg(render_ref(planes, rdef))

    cpu_rate = 1.0 / _timed(cpu_projection, repeats=3)
    return tpu_rate, cpu_rate


# -------------------------------------------------------------- config 5

def bench_config4_stream(rng):
    """WSI-scale streamed Z-projection, 32-plane 1024^2 uint16 stack.

    Cold: banded host-side folds (``project_region_banded`` with
    ``placement="host"`` — the serving default for host sources: a
    projection is a reduction, so only the finished plane crosses the
    link), projections/s end to end; fresh bytes per rep.  Warm: the
    same banded fold over DEVICE-resident planes (the HBM raw-cache
    serving case — interactive re-projection after the stack is
    staged), with a per-rep on-device XOR so content differs every rep.
    """
    import jax.numpy as jnp

    from omero_ms_image_region_tpu.models.rendering import Projection
    from omero_ms_image_region_tpu.ops.projection import (
        project_region_banded)

    base = rng.integers(0, 60000, size=(32, 1024, 1024)).astype(np.uint16)

    def run_cold(stack):
        # placement="host" (the serving default for host sources): the
        # fold is a reduction, so only the projected plane crosses the
        # link — the old device-fold cold path uploaded all 64 MB.
        out = project_region_banded(
            lambda z, y0, h: stack[z, y0:y0 + h],
            Projection.MAXIMUM_INTENSITY, 32, 0, 31, 1, 65535.0,
            plane_shape=(1024, 1024), band_rows=256, z_chunk=8,
            placement="host")
        np.asarray(out.ravel()[:1])    # force the fold chain to land

    run_cold(base)                     # compile folds + stitch
    cold_times = []
    for rep in (1, 2):
        fresh = base ^ np.uint16(rep)
        t0 = time.perf_counter()
        run_cold(fresh)
        cold_times.append(time.perf_counter() - t0)

    staged = jnp.asarray(base)         # one upload; stays in HBM
    staged.block_until_ready()

    def run_warm(rep):
        stack = staged ^ jnp.uint16(rep)   # fresh content, zero upload
        # Device-resident source: one sliced [z, band, W] chunk per
        # fold dispatch (per-plane slicing would cost a dispatch per
        # plane — ~150 dispatches).
        out = project_region_banded(
            None, Projection.MAXIMUM_INTENSITY, 32, 0, 31, 1, 65535.0,
            plane_shape=(1024, 1024), band_rows=512, z_chunk=32,
            get_chunk=lambda zs, y0, h:
                stack[zs[0]:zs[-1] + 1, y0:y0 + h],
            placement="device")
        np.asarray(out.ravel()[:1])

    run_warm(0)                        # compile the device-slice path
    warm_times = []
    for rep in (1, 2):
        t0 = time.perf_counter()
        run_warm(rep + 1)
        warm_times.append(time.perf_counter() - t0)
    return 1.0 / min(cold_times), 1.0 / min(warm_times)


def bench_config5(rng):
    """Batched mask rasterize + alpha overlay over rendered tiles."""
    from omero_ms_image_region_tpu.models.mask import Mask
    from omero_ms_image_region_tpu.ops.maskops import (
        overlay_masks_batch, unpack_mask_bits,
    )

    B, H, W = 16, 512, 512
    masks = [
        Mask(shape_id=i, width=W, height=H,
             bytes_=np.packbits(
                 rng.integers(0, 2, size=H * W).astype(np.uint8)).tobytes())
        for i in range(B)
    ]
    base = rng.integers(0, 255, size=(B, H, W, 4)).astype(np.uint8)
    fills = rng.integers(0, 255, size=(B, 4)).astype(np.uint8)

    def run():
        grids = np.stack([unpack_mask_bits(m.bytes_, W, H) for m in masks])
        overlay_masks_batch(base, grids, fills)

    def run_cpu():
        # Reference flavor: one mask at a time, PIL rasterize +
        # alpha_composite (the way the Java service's BufferedImage +
        # IndexColorModel path would overlay, ShapeMaskRequestHandler
        # .java:185-203) — the comparator BASELINE.json config 5 needs.
        from PIL import Image
        for m, tile, fill in zip(masks, base, fills):
            grid = unpack_mask_bits(m.bytes_, W, H)
            over = np.empty((H, W, 4), np.uint8)
            over[..., 0] = fill[0]
            over[..., 1] = fill[1]
            over[..., 2] = fill[2]
            over[..., 3] = grid * fill[3]
            Image.alpha_composite(Image.fromarray(tile, "RGBA"),
                                  Image.fromarray(over, "RGBA"))

    return B / _timed(run, repeats=3), B / _timed(run_cpu, repeats=3)


def main():
    # --smoke: the CPU-fast hot-path gate (also a tier-1 test); no
    # device, no multi-minute windows, one JSON line.  --smoke --chaos
    # runs the same scale under seeded fault injection instead (the
    # robustness gate: zero bare 5xx, bounded p99); --smoke --restart
    # runs the cold-restart scenario (render, kill, restart with
    # persistence on — the warm-state gate).
    # --smoke --overload runs the brownout-ladder scenario (a 10x
    # burst must engage ladder steps in configured order, keep zero
    # 5xx-without-shed with bounded p99, and release with hysteresis).
    # --smoke --sessions runs the multi-user serving scenario (N
    # panning viewers + one hostile bulk client: per-session p99,
    # Jain's fairness index, predictive prefetch hit rate).
    # --smoke --offload runs the repeat-viewer offload scenario
    # (cold -> warm-local -> warm-peer -> 304 over a 2-sidecar fleet:
    # origin offload ratio, 304 latency, peer byte-fetch hit rate).
    # --smoke --capacity runs the open-loop capacity sweep (the
    # services.loadmodel arrival process against m1/m2/m4 fleets:
    # latency-vs-offered-load curve, capacity knee per size, and the
    # closed-vs-open honesty A/B) — the CAPACITY record family.
    # --smoke --hotkey runs the hot-plane replication drill (zipf
    # storm vs uniform mix, replication-disabled A/B, promotion →
    # staging → balanced reads → decay demotion) — the HOTKEY family.
    # --smoke --partition runs the netsplit chaos drill (3-process
    # fleet under load: partition → fence → heal → rejoin, plus a
    # mid-partition epoch roll) — the PARTITION record family.
    # --smoke --workloads runs the device-workloads drill (batched
    # device mask parity + timing, overlay vs refimpl golden, pyramid
    # job build, animation stream first-frame/cancel) — the WORKLOADS
    # record family.
    if "--smoke" in sys.argv[1:]:
        if "--chaos" in sys.argv[1:]:
            bench_chaos_smoke()
        elif "--restart" in sys.argv[1:]:
            bench_restart_smoke()
        elif "--overload" in sys.argv[1:]:
            bench_overload_smoke()
        elif "--sessions" in sys.argv[1:]:
            bench_sessions_smoke()
        elif "--offload" in sys.argv[1:]:
            bench_offload_smoke()
        elif "--capacity" in sys.argv[1:]:
            bench_capacity_smoke()
        elif "--workloads" in sys.argv[1:]:
            # Device workloads: batched mask parity + timing, overlay
            # vs refimpl golden, crash-safe pyramid build, animation
            # streaming — the WORKLOADS record family.
            bench_workloads_smoke()
        elif "--hotkey" in sys.argv[1:]:
            # Hot-plane replication: zipf storm vs uniform mix on a
            # 2-member fleet, replication-disabled A/B, promotion →
            # staging → balanced reads → decay demotion lifecycle —
            # the HOTKEY record family.
            bench_hotkey_smoke()
        elif "--federation" in sys.argv[1:]:
            # Multi-process federated fleet: manifest agreement
            # against a REAL spawned sidecar process, 1-vs-2-process
            # scaling, cross-host warm shard handoff over the wire —
            # the MULTICHIP family's multi-process keys.
            bench_federation_smoke()
        elif "--partition" in sys.argv[1:]:
            # Netsplit chaos drill: a 3-process fleet under sustained
            # load through partition -> fence -> heal -> rejoin with
            # a mid-partition two-phase epoch roll — the PARTITION
            # record family.
            bench_partition_smoke()
        elif "--sentinel" in sys.argv[1:]:
            # Induced-drift sentinel drill: deterministic latency
            # step on a virtual clock through a 2-member fleet ->
            # one confirmed drift -> one complete incident bundle ->
            # recovery clears the verdict.
            bench_sentinel_smoke()
        else:
            bench_smoke()
        return
    # Persistent compilation cache, placed like every other entry
    # point's (JAX_COMPILATION_CACHE_DIR, else the fixed in-checkout
    # directory): repeat runs skip the first compiles per program.  A
    # failure to place it is an error, not a default.
    from omero_ms_image_region_tpu.utils.jaxenv import (
        place_compilation_cache)
    place_compilation_cache()
    rng = np.random.default_rng(0)

    # Each section gets one retry on a transient device transport error
    # (utils.transient) instead of zeroing out the whole record.
    from omero_ms_image_region_tpu.utils.transient import retry_transient

    flag = retry_transient(lambda: bench_flagship(rng), "bench_flagship",
                           backoff_s=15.0)
    _WATERFALL_SPANS = (
        "batcher.queueWait", "batcher.groupTiles", "batcher.stage",
        "wire.fetch", "wire.fetch2", "jfif.encodeBatch",
        "Renderer.renderAsPackedInt.batch")
    try:
        # Fixed sampling policy: ALWAYS two windows, best-of-2 per
        # engine, regardless of where the first window lands.
        # Sampling the same way on every
        # run keeps the statistic comparable (a retry only-when-low
        # would be a one-sided filter that inflates the estimate).
        # EVERY window's tiles/s is reported (service_windows_*), so
        # the round-over-round trend carries its own spread.
        from omero_ms_image_region_tpu.utils.stopwatch import (
            REGISTRY as _SPAN_REG)
        _SPAN_REG.reset()
        windows = [bench_service_level(rng)[1]]
        try:
            windows.append(bench_service_level(rng)[1])
        except Exception:
            pass
        service_windows = {
            e: [round(w[e][0], 1) for w in windows if e in w]
            for e in ("sparse", "huffman")}
        service_engines = {e: max(v) for e, v in service_windows.items()
                           if v}
        service_tps = (max(service_engines.values())
                       if service_engines else None)
        # p50 request latency from the window that carried the headline
        # (closed-loop, 16-way concurrency — the number a user feels).
        service_p50_ms = None
        service_hot_path = {}
        if service_engines:
            best_eng = max(service_engines, key=service_engines.get)
            best_i = max(range(len(windows)),
                         key=lambda i: windows[i].get(best_eng,
                                                      (0.0, None))[0])
            service_p50_ms = windows[best_i][best_eng][1]
            # Dedup / plane-cache / pipeline-overlap probes from the
            # headline window (so the next BENCH round can falsify the
            # hot-path win mechanically).
            service_hot_path = windows[best_i][best_eng][2] or {}
        # The stage waterfall across the service windows: where a tile's
        # wall time goes between the HTTP socket and the JPEG bytes.
        service_waterfall = {
            k: v for k, v in _SPAN_REG.snapshot().items()
            if k in _WATERFALL_SPANS}
    except Exception:
        # App stack unavailable; library numbers stand.
        service_tps, service_engines = None, {}
        service_windows, service_waterfall = {}, {}
        service_p50_ms = None
        service_hot_path = {}
    c1_cpu = retry_transient(
        lambda: bench_config1(rng), "bench_config1", backoff_s=15.0)
    c2_planes, c2_cpu = retry_transient(
        lambda: bench_config2(rng), "bench_config2", backoff_s=15.0)
    c4_projections, c4_cpu = retry_transient(
        lambda: bench_config4(rng), "bench_config4", backoff_s=15.0)
    c4_stream, c4_stream_warm = retry_transient(
        lambda: bench_config4_stream(rng), "bench_config4_stream",
        backoff_s=15.0)
    c5_masks, c5_cpu = retry_transient(
        lambda: bench_config5(rng), "bench_config5", backoff_s=15.0)

    print(json.dumps({
        "metric": "jpeg_tiles_per_sec_1024sq_4ch_u16",
        "value": round(flag["tiles_per_sec"], 2),
        "unit": "tiles/s",
        "vs_baseline": round(flag["tiles_per_sec"] / flag["cpu_tps"], 2),
        "jpeg_engine": flag["engine"],
        "sparse_tiles_per_sec": round(flag["sparse_tiles_per_sec"], 2),
        "huffman_tiles_per_sec": round(flag["huffman_tiles_per_sec"], 2),
        "cold_tiles_per_sec": round(flag["cold_tiles_per_sec"], 2),
        # RAW-bytes/s over the adjacent raw upload rate: ~1.0 = wire-
        # bound plain staging; >1.0 = the packed wire (io.staging)
        # is carrying the same planes in fewer bytes than raw.
        "cold_overlap_efficiency": round(
            flag["cold_overlap_efficiency"], 2),
        "p50_batch_ms": round(flag["p50_batch_ms"], 2),
        "p50_tile_ms": round(flag["p50_tile_ms"], 2),
        "p50_tile_ms_sparse": round(flag["p50_tile_ms_sparse"], 2),
        "p50_tile_ms_huffman": round(flag["p50_tile_ms_huffman"], 2),
        "cpu_ref_tiles_per_sec": round(flag["cpu_tps"], 2),
        "raw_upload_mb_per_sec": round(flag["upload_mb_s"], 1),
        "sparse_exec_ms_batch": _opt_round(
            flag["sparse_exec_ms_batch"], 1),
        "huffman_exec_ms_batch": _opt_round(
            flag["huffman_exec_ms_batch"], 1),
        "device_ceiling_tiles_per_sec": _opt_round(
            flag["device_ceiling_tps"], 1),
        "device_ceiling_vs_baseline": _opt_round(
            flag["device_ceiling_tps"]
            and flag["device_ceiling_tps"] / flag["cpu_tps"], 2),
        # Config-3 pan through the FULL HTTP stack (16-way concurrency).
        "service_tiles_per_sec": _opt_round(service_tps, 1),
        "service_vs_baseline": _opt_round(
            service_tps and service_tps / flag["cpu_tps"], 2),
        "service_sparse_tiles_per_sec": _opt_round(
            service_engines.get("sparse"), 1),
        "service_huffman_tiles_per_sec": _opt_round(
            service_engines.get("huffman"), 1),
        # Every sampled window per engine (the spread behind the
        # best-of headline).
        "service_windows_tiles_per_sec": service_windows,
        # Closed-loop p50 request latency at service concurrency (16
        # clients, batched — includes queue + group amortization).
        # Recorded every run so a serving-stack latency regression
        # shows in the trend.
        "p50_service_tile_ms": _opt_round(service_p50_ms, 2),
        # First BODY byte at the client (the progressive-wire
        # headline): with streaming + first-tile-out this lands a
        # batch-tail before request completion; watermark-gated in
        # scripts/bench_gate.py (direction: _ms regresses upward).
        "p50_first_tile_byte_ms": service_hot_path.get(
            "p50_first_tile_byte_ms"),
        # BASELINE.md's <50 ms target is INTERACTIVE tile latency
        # (single in-flight tile); pinned as a boolean so the r3-style
        # 68 ms regression class cannot pass silently.
        "p50_target_met": bool(flag["p50_tile_ms"] < 50.0),
        # Hot-path probes from the headline window: single-flight
        # coalescing of a concurrent-identical burst, byte-cache warm
        # repeat (no device span), content-digest staging skips, and
        # device-execute coverage of the wall clock (1.0 = the device
        # never idled behind the fetch/stage half).
        "service_dedup_hit_rate": service_hot_path.get(
            "dedup_hit_rate"),
        "service_warm_repeat_cached": service_hot_path.get(
            "warm_repeat_cached"),
        "service_overlap_efficiency": service_hot_path.get(
            "overlap_efficiency"),
        "service_planecache_hits": service_hot_path.get(
            "planecache_hits"),
        "service_planecache_misses": service_hot_path.get(
            "planecache_misses"),
        # Stage waterfall over the service windows (span -> count,
        # mean, p50 ms): queue wait, device batch, wire fetch (+second
        # fetches), host entropy/framing.
        "service_waterfall": service_waterfall,
        # Wire-transport accounting across the run (frames per
        # vectored flush, shm-ring hit rate): populated when the
        # serving posture actually crosses the sidecar wire; the
        # combined-mode windows report null rather than a fake 1.0.
        "wire_frames_per_flush": _opt_round(
            telemetry_wire_frames_per_flush(), 3),
        "shm_ring_hit_rate": _opt_round(
            telemetry_wire_ring_hit_rate(), 3),
        "batch": 8,
        "config1_cpu_ref_per_sec": round(c1_cpu, 2),
        "config2_fullplane_2048_3ch_per_sec": round(c2_planes, 2),
        "config2_cpu_ref_per_sec": round(c2_cpu, 2),
        "config4_zproj32_3ch_512_per_sec": round(c4_projections, 2),
        "config4_stream_zproj32_1024_per_sec": round(c4_stream, 2),
        "config4_stream_zproj32_1024_warm_per_sec": round(
            c4_stream_warm, 2),
        "config4_cpu_ref_per_sec": round(c4_cpu, 2),
        "config5_mask_overlay_512_per_sec": round(c5_masks, 2),
        "config5_cpu_ref_per_sec": round(c5_cpu, 2),
    }))


if __name__ == "__main__":
    sys.exit(main())
