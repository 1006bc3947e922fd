#!/usr/bin/env python3
"""Start the tile server on the chip, ask it for real tiles, check them.

    python chip_smoke.py            # one chip: phases A (combined) + B (split)
    python chip_smoke.py --chips 4  # four chips: 1-chip sidecar vs fleet vs mesh

The quickest proof that the serving path still starts, compiles, answers
and shuts down on a TPU.  This process NEVER imports JAX: the chip belongs
to the one server child that needs it, and everything said about the
device below comes from that server's own ``/readyz`` and ``/metrics``.

Data (one whole-slide-class 4-channel uint16 pyramid, one Z-stack, one
packed mask) is made from a fixed seed with numpy; every body that comes
back over HTTP is decoded and compared with ``refimpl`` on the same data.
Any failed check, any phase exception, any child that dies ends the run
non-zero with no result line.  The last line of a passing run is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""

from __future__ import annotations

import argparse
import concurrent.futures as cf
import contextlib
import io
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.parse
import urllib.request

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))

# ---------------------------------------------------------------- sizes
# Widths are the deployment's and never cut: 4 channels, uint16, 1024^2
# tiles, B=8 groups.  (tests/test_chip_smoke.py patches these constants
# for its CPU rehearsal; the script itself has no option for it.)
SEED = 20260926
EXPECT_PLATFORM = "tpu"
CHANNELS = 4
TILE = 1024
IMAGE_EDGE = 16384          # level 0 is ~2.1 GB; 256 tiles of 8 MB
LEVEL_EDGE = 2048           # the whole pyramid level render_image asks for
ZSTACK_EDGE, ZSTACK_Z = 2048, 8
MASK_EDGE = 1024
TINY_EDGE = 128             # smaller than a stock 256^2 tile: host path
CPU_FALLBACK_MAX_PX = None  # None = leave the server's default
MAX_BATCH = 8
N_COLD, N_WARM, INFLIGHT = 64, 8, 16
N_SPLIT = 16
N_FLEET = 32                # --chips 4: half JPEG, half PNG
QUALITY = 0.9
READY_TIMEOUT_S = 900.0
SHUTDOWN_TIMEOUT_S = 60.0

# Bounds, as the repo's own tests hold them: device vs refimpl on a
# multi-channel composite within 2 grey levels (tests/test_handler.py),
# JPEG mean abs error under 8 and no worse than libjpeg's at the same
# quality x1.3 + 0.5 (tests/test_jpeg.py).
PNG_MAX_ABS = 2
JPEG_MEAN_ABS = 8.0

IMG, ZIMG, MASK_ID = 1, 2, 7
COLORS = ("FF0000", "00FF00", "0000FF", "FFFF00")


class SmokeFailure(AssertionError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(msg: str) -> None:
    print(msg, flush=True)


# ----------------------------------------------------------------- data

def generate_data(data_dir: str, edge_x: int, edge_y: int) -> dict:
    """The image class the old bench used (``synthetic_wsi_tiles``: cell
    blobs + sensor noise per tile), assembled into one plane and written
    through the library's own ingest (``build_pyramid``)."""
    from omero_ms_image_region_tpu.flagship import synthetic_wsi_tiles
    from omero_ms_image_region_tpu.io.store import build_pyramid
    from omero_ms_image_region_tpu.models.mask import Mask
    from omero_ms_image_region_tpu.services.metadata import write_mask

    t0 = time.perf_counter()
    nx, ny = edge_x // TILE, edge_y // TILE
    img = np.empty((CHANNELS, edge_y, edge_x), np.uint16)
    seeds = np.random.SeedSequence(SEED).spawn(ny + 2)

    def tile_row(y: int) -> None:
        tiles = synthetic_wsi_tiles(np.random.default_rng(seeds[y]),
                                    nx, CHANNELS, TILE, TILE)
        for x in range(nx):
            img[:, y * TILE:(y + 1) * TILE,
                x * TILE:(x + 1) * TILE] = tiles[x]

    with cf.ThreadPoolExecutor(min(8, os.cpu_count() or 2)) as pool:
        list(pool.map(tile_row, range(ny)))
    t_gen = time.perf_counter() - t0
    build_pyramid(img[:, None], os.path.join(data_dir, str(IMG)),
                  chunk=(TILE, TILE),
                  min_level_size=min(256, LEVEL_EDGE))
    t_pyr = time.perf_counter() - t0 - t_gen

    rng = np.random.default_rng(seeds[ny])
    zstack = synthetic_wsi_tiles(rng, ZSTACK_Z, 1, ZSTACK_EDGE,
                                 ZSTACK_EDGE)[:, 0]          # [Z, H, W]
    build_pyramid(zstack[None], os.path.join(data_dir, str(ZIMG)),
                  chunk=(min(TILE, ZSTACK_EDGE),) * 2, n_levels=1)

    rng = np.random.default_rng(seeds[ny + 1])
    yy, xx = np.mgrid[:MASK_EDGE, :MASK_EDGE]
    r = MASK_EDGE * rng.uniform(0.2, 0.4)
    grid = ((yy - MASK_EDGE / 2) ** 2 + (xx - MASK_EDGE / 3) ** 2
            < r * r).astype(np.uint8)
    write_mask(data_dir, Mask(MASK_ID, MASK_EDGE, MASK_EDGE,
                              np.packbits(grid.reshape(-1)).tobytes(),
                              fill_color=(255, 255, 0, 255)))
    say(f"data: image {CHANNELS}x{edge_y}x{edge_x} uint16 "
        f"({img.nbytes / 2**30:.2f} GiB at level 0) generated in "
        f"{t_gen:.1f}s, pyramid written in {t_pyr:.1f}s; z-stack "
        f"1x{ZSTACK_Z}x{ZSTACK_EDGE}^2; mask {MASK_EDGE}^2 "
        f"(total {time.perf_counter() - t0:.1f}s)")
    return {"img": img, "zstack": zstack, "mask": grid}


# ------------------------------------------------------------ processes

def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Child:
    """One server process in its own process group, logged to a file."""

    def __init__(self, name: str, argv: list, workdir: str,
                 env: dict | None = None):
        self.name = name
        self.log_path = os.path.join(workdir, f"{name}.log")
        child_env = dict(os.environ)
        child_env["PYTHONPATH"] = os.pathsep.join(
            p for p in (REPO, os.environ.get("PYTHONPATH")) if p)
        child_env.update(env or {})
        self._log = open(self.log_path, "wb")
        self.t_start = time.perf_counter()
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "omero_ms_image_region_tpu.server",
             *argv],
            cwd=workdir, env=child_env, stdout=self._log,
            stderr=subprocess.STDOUT, start_new_session=True)

    def alive(self) -> bool:
        return self.proc.poll() is None

    def log_tail(self, n: int = 40) -> str:
        self._log.flush()
        with open(self.log_path, "rb") as f:
            lines = f.read().decode(errors="replace").splitlines()
        return "\n".join(f"    [{self.name}] {ln}" for ln in lines[-n:])

    def grep(self, needle: str) -> list:
        with open(self.log_path, "rb") as f:
            return [ln for ln in f.read().decode(errors="replace")
                    .splitlines() if needle in ln]

    def terminate(self) -> float:
        """SIGTERM, and require a clean exit inside the shutdown bound."""
        t0 = time.perf_counter()
        self.proc.send_signal(signal.SIGTERM)
        try:
            code = self.proc.wait(timeout=SHUTDOWN_TIMEOUT_S)
        except subprocess.TimeoutExpired:
            raise SmokeFailure(
                f"{self.name} did not exit within {SHUTDOWN_TIMEOUT_S}s "
                f"of SIGTERM\n{self.log_tail()}")
        check(code == 0, f"{self.name} exited {code} after SIGTERM\n"
              f"{self.log_tail()}")
        return time.perf_counter() - t0

    def kill(self) -> None:
        """Unconditional clean-up of the whole group (grandchildren
        included); the checked path is :meth:`terminate`."""
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
        try:
            self.proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            pass
        self._log.close()


@contextlib.contextmanager
def running(*children: Child):
    """Children of one phase: their log tails on any failure, and the
    whole group killed on the way out either way (a phase that passes
    has already ``terminate()``d them and checked the exit codes)."""
    children = list(children)
    try:
        yield children
    except BaseException:
        for child in children:
            say(child.log_tail())
        raise
    finally:
        for child in children:
            child.kill()


def http_get(port: int, path: str, timeout: float = 600.0):
    url = f"http://127.0.0.1:{port}{path}"
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.status, dict(r.headers), r.read()
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), e.read()


def wait_ready(child: Child, port: int, device_only: bool = False
               ) -> tuple:
    """Poll /readyz until 200; returns (document, seconds since the
    child was started).  The device document is there from the first
    answer (503 while prewarm compiles), so a server that is not on
    the expected platform fails the run in seconds, not minutes —
    ``device_only`` returns as soon as that much is known."""
    doc, device_checked = None, False
    while time.perf_counter() - child.t_start < READY_TIMEOUT_S:
        check(child.alive(), f"{child.name} died during start-up "
              f"(exit {child.proc.returncode})\n{child.log_tail()}")
        try:
            status, _, body = http_get(port, "/readyz", timeout=10.0)
        except (OSError, urllib.error.URLError):
            time.sleep(0.5)
            continue
        doc = json.loads(body)
        device = doc.get("device")
        if device is not None and not device_checked:
            check(device["platform"] == EXPECT_PLATFORM,
                  f"{child.name} serves from platform "
                  f"{device['platform']!r} ({device['kind']}), expected "
                  f"{EXPECT_PLATFORM!r}")
            device_checked = True
        if status == 200 or (device_only and device_checked):
            check(device_checked, f"/readyz carries no device: {doc}")
            return doc, time.perf_counter() - child.t_start
        time.sleep(1.0)
    raise SmokeFailure(f"{child.name} not ready after {READY_TIMEOUT_S}s: "
                       f"{doc}\n{child.log_tail()}")


def metrics(port: int) -> dict:
    """``{'name{labels}': value}`` from the server's own exposition."""
    status, _, body = http_get(port, "/metrics", timeout=30.0)
    check(status == 200, f"/metrics answered {status}")
    return parse_metrics(body.decode())


def parse_metrics(text: str) -> dict:
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        key, _, value = line.rpartition(" ")
        try:
            out[key] = float(value)
        except ValueError:
            pass
    return out


def series(m: dict, family: str, **labels) -> float:
    """Sum of every series of ``family`` carrying ``labels`` (a proxy
    frontend re-exports the sidecar's with ``process="sidecar"``)."""
    total = 0.0
    for key, value in m.items():
        name, _, rest = key.partition("{")
        if name == family and all(f'{k}="{v}"' in rest
                                  for k, v in labels.items()):
            total += value
    return total


def span_count(m: dict, span: str) -> int:
    return int(series(m, "imageregion_span_count", span=span))


def write_config(workdir: str, name: str, extra: dict | None = None
                 ) -> str:
    import yaml
    cfg = {
        "renderer": {"prewarm": [f"{CHANNELS}x{TILE}@"
                                 f"{round(QUALITY * 100)}"]},
        # B=8 groups, and no growth past them: a 16-tile program is a
        # shape prewarm does not compile.
        "batcher": {"max-batch": MAX_BATCH, "max-batch-limit": MAX_BATCH},
        "workloads": {"device-masks": True},
        # The ring must still hold the first batch.formed at the end.
        "telemetry": {"provenance-header": True,
                      "flight-recorder-events": 8192},
    }
    if CPU_FALLBACK_MAX_PX is not None:
        cfg["renderer"]["cpu-fallback-max-px"] = CPU_FALLBACK_MAX_PX
    for key, block in (extra or {}).items():
        cfg.setdefault(key, {}).update(block)
    path = os.path.join(workdir, f"{name}.yaml")
    with open(path, "w") as f:
        yaml.safe_dump(cfg, f)
    return path


# ------------------------------------------------- requests + reference

def render_url(k: int, tile: tuple | None = None, fmt: str = "jpeg",
               route: str = "render_image_region", image: int = IMG,
               channels: int = CHANNELS, projection: str | None = None
               ) -> tuple:
    """(path, params) of one render request.  ``tile`` is
    ``(level, x, y, edge)`` or None for the whole plane; ``k`` makes
    every channel's window distinct from every other request's."""
    params = {"imageId": str(image), "theZ": "0", "theT": "0"}
    if tile is not None:
        level, x, y, edge = tile
        params["tile"] = f"{level},{x},{y},{edge},{edge}"
    params["c"] = ",".join(
        f"{c + 1}|{100 + 37 * k + 11 * c}:{40000 - 150 * k - 900 * c}"
        f"${COLORS[c % len(COLORS)]}" for c in range(channels))
    params.update({"m": "c", "format": fmt, "q": str(QUALITY)})
    if projection is not None:
        params["p"] = projection
    query = urllib.parse.urlencode(
        {k_: v for k_, v in params.items()
         if k_ not in ("imageId", "theZ", "theT")}, safe="|:$,")
    return f"/webgateway/{route}/{image}/0/0?{query}", params


def tile_url(x: int, y: int, k: int, fmt: str = "jpeg") -> tuple:
    return render_url(k, (0, x, y, TILE), fmt)


def reference_rgba(raw: np.ndarray, params: dict, size_c: int
                   ) -> np.ndarray:
    """``refimpl.render_ref`` of ``raw`` [C, h, w] under the request's
    own settings (parsed by the server's ctx/settings code, rendered by
    the numpy reference that shares nothing with ``ops/``)."""
    from omero_ms_image_region_tpu.models.pixels import Pixels
    from omero_ms_image_region_tpu.models.rendering import (
        default_rendering_def, restrict_to_active)
    from omero_ms_image_region_tpu.refimpl import render_ref
    from omero_ms_image_region_tpu.server.ctx import ImageRegionCtx
    from omero_ms_image_region_tpu.server.settings import update_settings

    ctx = ImageRegionCtx.from_params(params)
    pixels = Pixels(image_id=ctx.image_id, size_x=raw.shape[-1],
                    size_y=raw.shape[-2], size_c=size_c,
                    pixels_type="uint16")
    rdef, active = restrict_to_active(
        update_settings(default_rendering_def(pixels), ctx))
    return render_ref(raw[active].astype(np.float32), rdef)


def compare(body: bytes, want_rgba: np.ndarray, fmt: str, what: str
            ) -> dict:
    from PIL import Image

    from omero_ms_image_region_tpu import codecs
    got = codecs.decode_to_rgba(body)
    check(got.shape == want_rgba.shape,
          f"{what}: decoded {got.shape}, expected {want_rgba.shape}")
    check(np.isfinite(got).all(), f"{what}: non-finite pixels")
    a = got[..., :3].astype(np.float64)
    b = want_rgba[..., :3].astype(np.float64)
    err = np.abs(a - b)
    mse = float(((a - b) ** 2).mean())
    psnr = float("inf") if mse == 0 else 10 * np.log10(255.0 ** 2 / mse)
    out = {"max_abs": float(err.max()), "mean_abs": float(err.mean()),
           "psnr": psnr}
    if fmt == "png":
        check(err.max() <= PNG_MAX_ABS,
              f"{what}: PNG differs from refimpl by {err.max():.0f} "
              f"grey levels (bound {PNG_MAX_ABS})")
    else:
        buf = io.BytesIO()
        Image.fromarray(np.ascontiguousarray(want_rgba[..., :3])).save(
            buf, format="JPEG", quality=round(QUALITY * 100))
        pil = np.asarray(Image.open(buf).convert("RGB"), np.float64)
        pil_err = float(np.abs(pil - b).mean())
        check(err.mean() < JPEG_MEAN_ABS
              and err.mean() <= pil_err * 1.3 + 0.5,
              f"{what}: JPEG mean abs error {err.mean():.2f} (bound "
              f"{JPEG_MEAN_ABS}; libjpeg at q{round(QUALITY * 100)} "
              f"gives {pil_err:.2f})")
    return out


class Tally:
    def __init__(self):
        self.sent = self.ok = 0
        self.worst_psnr = float("inf")
        self.worst_png = 0.0
        self.statuses: dict = {}

    def add(self, status: int, result: dict | None, fmt: str) -> None:
        self.sent += 1
        self.statuses[status] = self.statuses.get(status, 0) + 1
        if result is not None:
            self.ok += 1
            if fmt == "jpeg":
                self.worst_psnr = min(self.worst_psnr, result["psnr"])
            else:
                self.worst_png = max(self.worst_png, result["max_abs"])


def fetch_and_check(port: int, url: tuple, raw: np.ndarray, size_c: int,
                    tally: Tally, what: str, want_tier: str | None = None
                    ) -> bytes:
    path, params = url
    status, headers, body = http_get(port, path)
    fmt = params["format"]
    if status != 200:
        tally.add(status, None, fmt)
        raise SmokeFailure(f"{what}: HTTP {status} {body[:200]!r}")
    if want_tier is not None:
        prov = headers.get("X-Image-Region-Provenance", "")
        check(f"tier={want_tier}" in prov,
              f"{what}: provenance {prov!r}, expected tier={want_tier}")
    result = compare(body, reference_rgba(raw, params, size_c), fmt,
                     what)
    tally.add(status, result, fmt)
    return body


def level0(data: dict, x: int, y: int) -> np.ndarray:
    return data["img"][:, y * TILE:(y + 1) * TILE,
                       x * TILE:(x + 1) * TILE]


def run_pool(jobs: list) -> list:
    """``INFLIGHT`` requests in the air at once; every future is read
    so the first failure surfaces."""
    with cf.ThreadPoolExecutor(INFLIGHT) as pool:
        futures = [pool.submit(*job) for job in jobs]
        return [f.result() for f in futures]


def pan_tiles(n: int, nx: int) -> list:
    """The first ``n`` tiles of a row-major pan over an ``nx``-wide
    window (8 wide at full size: an 8x8 viewport walk)."""
    width = min(nx, 8)
    return [(i % width, i // width) for i in range(n)]


# -------------------------------------------------------------- phase A

def compile_line(m: dict) -> str:
    return (f"compile events {int(series(m, 'imageregion_compile_events_total'))}"
            f" ({series(m, 'imageregion_compile_ms_total') / 1000.0:.1f}s,"
            f" {int(series(m, 'imageregion_compile_cache_hits_total'))}"
            f" from the persistent cache)")


def assert_clean(m: dict, what: str) -> None:
    """Zero degraded renders, zero retries, zero 5xx — from the server's
    own counters."""
    check(series(m, "imageregion_degraded_renders_total") == 0,
          f"{what}: degraded renders counted")
    retries = {k: v for k, v in m.items()
               if k.startswith("imageregion_retries_total") and v}
    check(not retries, f"{what}: retries fired: {retries}")
    fives = {k: v for k, v in m.items()
             if k.startswith("imageregion_requests_total")
             and 'status="5' in k and v}
    check(not fives, f"{what}: 5xx answered: {fives}")


def phase_a(workdir: str, data_dir: str, get_data) -> dict:
    """Combined role: one process owns HTTP and the chip."""
    t_phase = time.perf_counter()
    port = free_port()
    child = Child("combined", [
        "--role", "combined", "--config", write_config(workdir, "a"),
        "--data-dir", data_dir, "--port", str(port)], workdir)
    with running(child):
        # Which platform the server found is known in seconds; the
        # data is then generated while it compiles (it reads nothing
        # until the first request).
        wait_ready(child, port, device_only=True)
        data = get_data()
        ready_doc, ready_s = wait_ready(child, port)
        device, native = ready_doc["device"], ready_doc["native"]
        say(f"A combined: ready in {ready_s:.1f}s on {device['platform']} "
            f"{device['kind']} x{device['count']} (ids {device['ids']}); "
            f"entropy coder {native['entropy_coder']}, tile cache "
            f"{native['tile_cache']}")
        check(native["entropy_coder"] == "native"
              and native["tile_cache"] == "native",
              f"server runs pure-Python pieces: {native}")
        for line in child.grep(" device: platform="):
            say(f"A combined log: {line.split(' - ', 1)[-1]}")
        m_ready = metrics(port)
        say(f"A combined at ready: {compile_line(m_ready)}")
        check(series(m_ready, "imageregion_compile_events_total") > 0,
              "no compile events counted on start-up")

        nx = data["img"].shape[-1] // TILE
        tally = Tally()
        t0 = time.perf_counter()
        # (a) N_COLD distinct level-0 JPEG tiles, distinct windows.
        cold = pan_tiles(N_COLD, nx)
        run_pool([(fetch_and_check, port, tile_url(x, y, i),
                   level0(data, x, y), CHANNELS, tally,
                   f"A(a) tile {x},{y}")
                  for i, (x, y) in enumerate(cold)])
        t_a = time.perf_counter() - t0
        # (b) N_WARM of them again, new window: the raw planes are in
        # the HBM cache, only settings change.
        run_pool([(fetch_and_check, port, tile_url(x, y, 500 + i),
                   level0(data, x, y), CHANNELS, tally,
                   f"A(b) tile {x},{y}", "hbm_warm")
                  for i, (x, y) in enumerate(cold[:N_WARM])])
        # (c) one PNG tile.
        x, y = cold[-1]
        fetch_and_check(port, tile_url(x, y, 900, "png"),
                        level0(data, x, y), CHANNELS, tally, "A(c) png")
        t_abc = time.perf_counter() - t0
        # (d) render_image of one whole pyramid level.
        from omero_ms_image_region_tpu.io.store import ChunkedPyramidStore
        from omero_ms_image_region_tpu.server.region import RegionDef
        level = (data["img"].shape[-1] // LEVEL_EDGE).bit_length() - 1
        store = ChunkedPyramidStore(os.path.join(data_dir, str(IMG)))
        lw, lh = store.resolution_descriptions()[level]
        check(max(lw, lh) == LEVEL_EDGE,
              f"level {level} is {lw}x{lh}, expected {LEVEL_EDGE}")
        whole = np.stack([store.get_region(0, c, 0,
                                           RegionDef(0, 0, lw, lh), level)
                          for c in range(CHANNELS)])
        store.close()
        fetch_and_check(
            port, render_url(901, (level, 0, 0, LEVEL_EDGE),
                             route="render_image"),
            whole, CHANNELS, tally, f"A(d) level {level} {lw}x{lh}")
        # (e) max-intensity Z-projection of the Z-stack, as PNG.
        from omero_ms_image_region_tpu.models.rendering import Projection
        from omero_ms_image_region_tpu.refimpl import project_ref
        projected = project_ref(data["zstack"],
                                Projection.MAXIMUM_INTENSITY, 0,
                                ZSTACK_Z - 1, 1, 65535.0)[None]
        fetch_and_check(
            port, render_url(902, fmt="png", route="render_image",
                             image=ZIMG, channels=1,
                             projection=f"intmax|0:{ZSTACK_Z - 1}"),
            projected, 1, tally, "A(e) z-projection")
        # (f) one shape mask through the batched device rasterizer.
        from omero_ms_image_region_tpu import codecs
        status, _, body = http_get(
            port, f"/webgateway/render_shape_mask/{MASK_ID}"
                  f"?color=FF00FF80")
        check(status == 200, f"A(f) mask: HTTP {status}")
        want = codecs.encode_mask_png(data["mask"], (255, 0, 255, 128))
        check(body == want, "A(f) mask: PNG bytes differ from the host "
              "rasterizer's")
        tally.add(status, {"max_abs": 0.0}, "png")
        # (g) exactly one request small enough for the designed host
        # path (refimpl on the server's CPU) — the only one.
        fetch_and_check(port, render_url(903, (0, 0, 0, TINY_EDGE), "png"),
                        level0(data, 0, 0)[:, :TINY_EDGE, :TINY_EDGE],
                        CHANNELS, tally, "A(g) tiny (host path by design)")

        m = metrics(port)
        largest = max_group(port)
        n_jpeg = N_COLD + N_WARM + 1            # (a) + (b) + (d)
        n_device = n_jpeg + 1 + 1 + 1           # + (c) + (e) + (f)
        groups = span_count(m, "batcher.groupTiles")
        jpeg_tiles = int(series(m, "imageregion_span_ms_sum",
                                span="batcher.groupTiles"))
        batches = int(series(m, "imageregion_batches_dispatched"))
        rendered = int(series(m, "imageregion_tiles_rendered"))
        say(f"A combined: {tally.sent} requests sent, {tally.ok} ok "
            f"{tally.statuses}; (a) {N_COLD} cold tiles in {t_a:.1f}s, "
            f"(a)-(c) in {t_abc:.1f}s; worst JPEG PSNR "
            f"{tally.worst_psnr:.2f} dB, worst PNG diff "
            f"{tally.worst_png:.0f}")
        say(f"A combined spans: {rendered} tiles rendered on the device "
            f"in {batches} batches; Renderer.renderAsPackedInt.batch="
            f"{span_count(m, 'Renderer.renderAsPackedInt.batch')}, "
            f"wire.fetch={span_count(m, 'wire.fetch')} (prewarm "
            f"{span_count(m_ready, 'wire.fetch')}) over {groups} "
            f"JPEG groups carrying {jpeg_tiles} tiles (largest group "
            f"{largest}); rasterizeMask.batch="
            f"{span_count(m, 'Renderer.rasterizeMask.batch')}; host path "
            f"by design: Renderer.renderAsPackedInt.cpu="
            f"{span_count(m, 'Renderer.renderAsPackedInt.cpu')}; "
            f"rawcache hits {int(series(m, 'imageregion_rawcache_hits'))}"
            f", wire.fetch2 {span_count(m, 'wire.fetch2')}")
        say(f"A combined at end: {compile_line(m)}; peak HBM "
            f"{peak_hbm(m)}")
        check(rendered == n_device,
              f"server counts {rendered} device tiles, {n_device} sent")
        check(jpeg_tiles == n_jpeg,
              f"JPEG groups carried {jpeg_tiles} tiles, {n_jpeg} sent")
        # (Prewarm fetches too, outside the batcher: count from ready.)
        fetches = span_count(m, "wire.fetch") \
            - span_count(m_ready, "wire.fetch")
        # A (shape, quality, engine) workload whose first dense tile
        # overflows its cap takes ONE retry, a second fetch, and starts
        # at the doubled cap ever after (``ops.jpegenc._CAP_MEMO``); the
        # rehearsal's noise tiles do in their own 64^2 bucket.
        check(groups <= fetches <= groups + 1,
              f"{fetches} wire fetches for {groups} JPEG groups")
        check(span_count(m, "Renderer.renderAsPackedInt.batch")
              == batches - span_count(m, "Renderer.rasterizeMask.batch")
              and span_count(m, "Renderer.rasterizeMask.batch") == 1,
              "render batch spans do not add up to the dispatched "
              "batches")
        check(span_count(m, "Renderer.renderAsPackedInt.cpu") == 1,
              "the host path served something other than the one tiny "
              "request")
        # max-batch counts renders of a 1024^2 bucket, which TILE is
        # on the chip: a full group is exactly that.  The CPU
        # rehearsal's 64^2 tiles fall in the 256^2 bucket, whose cap is
        # a multiple (batcher.group_cap), so a group there may pass it.
        check(largest == MAX_BATCH if TILE >= 1024
              else largest >= MAX_BATCH,
              f"no B={MAX_BATCH} group formed (largest {largest})")
        check(series(m, "imageregion_rawcache_hits") >= N_WARM,
              "the re-windowed tiles missed the HBM raw cache")
        check(series(m, "imageregion_compile_events_total") > 0,
              "no compile events counted")
        assert_clean(m, "A")
        stop_s = child.terminate()
        say(f"A combined: SIGTERM -> exit 0 in {stop_s:.1f}s; phase took "
            f"{time.perf_counter() - t_phase:.1f}s")
        return {"device": device, "ready_s": ready_s, "data": data,
                "ready_compile_ms":
                    series(m_ready, "imageregion_compile_ms_total"),
                "cache_hits":
                    series(m, "imageregion_compile_cache_hits_total")}


def max_group(port: int) -> int:
    """Largest group the batcher formed, from the server's black-box
    ring (every dispatch records ``batch.formed`` with its size)."""
    status, _, body = http_get(port, "/debug/flightrecorder",
                               timeout=30.0)
    check(status == 200, f"/debug/flightrecorder answered {status}")
    sizes = [e["tiles"] for e in json.loads(body)["events"]
             if e.get("kind") == "batch.formed"]
    return max(sizes, default=0)


def peak_hbm(m: dict) -> str:
    peak = series(m, "imageregion_device_peak_bytes")
    return (f"{peak / 2**30:.2f} GiB" if peak
            else "not reported by this backend")


# -------------------------------------------------------------- phase B

def phase_b(workdir: str, data_dir: str, a: dict) -> dict:
    """Split role: a JAX-free frontend and the supervised sidecar child
    that owns the chip, on the SAME compile cache as phase A."""
    t_phase = time.perf_counter()
    port = free_port()
    sock = os.path.join(workdir, "render.sock")
    child = Child("split", [
        "--role", "split", "--config", write_config(workdir, "b"),
        "--data-dir", data_dir, "--port", str(port),
        "--sidecar-socket", sock], workdir)
    with running(child):
        ready_doc, ready_s = wait_ready(child, port)
        device = ready_doc["device"]
        check({k: device[k] for k in ("platform", "kind", "count")}
              == {k: a["device"][k] for k in ("platform", "kind",
                                              "count")},
              f"B sees {device}, A saw {a['device']}")
        m_ready = metrics(port)
        hits = series(m_ready, "imageregion_compile_cache_hits_total")
        ready_compile_ms = series(m_ready, "imageregion_compile_ms_total")
        say(f"B split: ready in {ready_s:.1f}s (A: {a['ready_s']:.1f}s) "
            f"on {device['platform']} {device['kind']}; at ready: "
            f"{compile_line(m_ready)} (A at ready: "
            f"{a['ready_compile_ms'] / 1000.0:.1f}s of compiles, "
            f"{'cold' if not a['cache_hits'] else 'warm'} cache)")
        check(hits > 0, "B reached ready with no persistent-cache hit: "
              "the compile cache is not where both processes see it")
        check(a["cache_hits"] > 0
              or ready_compile_ms < a["ready_compile_ms"],
              f"B spent {ready_compile_ms / 1000.0:.1f}s compiling to "
              f"ready, cold A spent {a['ready_compile_ms'] / 1000.0:.1f}s")
        data = a["data"]
        nx = data["img"].shape[-1] // TILE
        tally = Tally()
        t0 = time.perf_counter()
        run_pool([(fetch_and_check, port, tile_url(x, y, i),
                   level0(data, x, y), CHANNELS, tally,
                   f"B tile {x},{y}")
                  for i, (x, y) in enumerate(pan_tiles(N_SPLIT, nx))])
        m = metrics(port)
        rendered = int(series(m, "imageregion_tiles_rendered"))
        say(f"B split: {tally.sent} requests sent, {tally.ok} ok "
            f"{tally.statuses} in {time.perf_counter() - t0:.1f}s; "
            f"worst JPEG PSNR {tally.worst_psnr:.2f} dB; sidecar "
            f"rendered {rendered} tiles; at end: {compile_line(m)}")
        check(rendered == N_SPLIT,
              f"sidecar counts {rendered} device tiles, {N_SPLIT} sent")
        assert_clean(m, "B")
        stop_s = child.terminate()
        say(f"B split: SIGTERM -> exit 0 in {stop_s:.1f}s; phase took "
            f"{time.perf_counter() - t_phase:.1f}s")
        return {"device": device}


# -------------------------------------------------------- four chips

def chip_env(index: int) -> dict:
    """Environment that restricts one process to ONE chip of a
    multi-chip TPU host (what deploy/DEPLOY.md "Fleet serving" and the
    sidecar unit file prescribe).  Each process is its own one-chip
    "slice": a bounds pair of 1,1,1, the chip it may see, and a port of
    its own for the runtime's per-process service."""
    if EXPECT_PLATFORM != "tpu":
        return {}
    port = free_port()
    return {"TPU_VISIBLE_CHIPS": str(index),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_ADDRESSES": f"localhost:{port}",
            "TPU_PROCESS_PORT": str(port),
            "CLOUD_TPU_TASK_ID": "0",
            "TPU_RUNTIME_METRICS_PORTS": str(free_port())}


def open_device_files(pid: int) -> list:
    """Accelerator device files a process holds open, read from /proc:
    the operating system's word on which chip it has."""
    found = set()
    try:
        for fd in os.listdir(f"/proc/{pid}/fd"):
            try:
                target = os.readlink(f"/proc/{pid}/fd/{fd}")
            except OSError:
                continue
            if target.startswith(("/dev/accel", "/dev/vfio/")) \
                    and target != "/dev/vfio/vfio":
                found.add(target)
    except OSError:
        pass
    return sorted(found)


def sidecar_ping(sock: str) -> dict:
    """One sidecar's own readiness document (its /readyz equivalent),
    asked directly over its socket."""
    import asyncio

    from omero_ms_image_region_tpu.server.sidecar import SidecarClient

    async def go():
        client = SidecarClient(sock)
        try:
            status, body = await asyncio.wait_for(
                client.call("ping", {}), timeout=10.0)
            check(status == 200, f"ping {sock}: status {status}")
            return json.loads(bytes(body).decode())
        finally:
            await client.close()
    return asyncio.run(go())


def wait_sidecars(children: list, socks: list) -> list:
    t0 = time.perf_counter()
    docs = [None] * len(socks)
    while time.perf_counter() - t0 < READY_TIMEOUT_S:
        for i, (child, sock) in enumerate(zip(children, socks)):
            check(child.alive(), f"{child.name} died during start-up "
                  f"(exit {child.proc.returncode})\n{child.log_tail()}")
            if os.path.exists(sock):
                try:
                    docs[i] = sidecar_ping(sock)
                except (OSError, ConnectionError):
                    docs[i] = None
        if all(d is not None and not d["prewarm_pending"] for d in docs):
            for child, d in zip(children, docs):
                check(d["device"]["platform"] == EXPECT_PLATFORM,
                      f"{child.name} serves from {d['device']}")
            return docs
        time.sleep(1.0)
    raise SmokeFailure(f"sidecars not ready after {READY_TIMEOUT_S}s: "
                       f"{docs}")


def fleet_jobs(data: dict) -> list:
    """The 32 tiles of phases (i)-(iii): alternating JPEG / PNG."""
    nx = data["img"].shape[-1] // TILE
    return [(x, y, i, "jpeg" if i % 2 == 0 else "png")
            for i, (x, y) in enumerate(pan_tiles(N_FLEET, nx))]


def run_fleet_tiles(port: int, data: dict, tally: Tally, what: str
                    ) -> dict:
    jobs = fleet_jobs(data)
    bodies = run_pool([(fetch_and_check, port,
                        tile_url(x, y, i, fmt), level0(data, x, y),
                        CHANNELS, tally, f"{what} tile {x},{y} {fmt}")
                       for x, y, i, fmt in jobs])
    return {(x, y, fmt): body
            for (x, y, _, fmt), body in zip(jobs, bodies)}


def same_as_one_chip(bodies: dict, one_chip: dict, what: str) -> float:
    """PNG byte for byte; JPEG within the bound against the one-chip
    body (both already passed against refimpl)."""
    from omero_ms_image_region_tpu import codecs
    worst = float("inf")
    for key, body in bodies.items():
        if key[2] == "png":
            check(body == one_chip[key],
                  f"{what}: PNG {key} differs from the one-chip bytes")
        else:
            result = compare(body, codecs.decode_to_rgba(one_chip[key]),
                             "jpeg", f"{what} vs one chip {key}")
            worst = min(worst, result["psnr"])
    return worst


def four_chips(workdir: str, data_dir: str) -> dict:
    """(i) one one-chip sidecar, (ii) four of them behind the fleet
    router, (iii) the 2x2 mesh in one process.  Nothing else."""
    # 8 x 8 tiles: the N_FLEET of the comparison and as many spare
    # planes for reaching every ring member.
    data = generate_data(data_dir, 8 * TILE, 8 * TILE)
    socks = [os.path.join(workdir, f"render-{i}.sock") for i in range(4)]
    envs = [chip_env(i) for i in range(4)]
    side_cfg = write_config(workdir, "sidecar")

    def sidecar(i: int) -> Child:
        return Child(f"sidecar-{i}", [
            "--role", "sidecar", "--config", side_cfg, "--data-dir",
            data_dir, "--sidecar-socket", socks[i]], workdir, env=envs[i])

    def frontend(name: str, extra_cfg: dict, extra_argv: list,
                 port: int) -> Child:
        return Child(name, [
            "--role", "frontend", "--config",
            write_config(workdir, name, extra_cfg), "--data-dir",
            data_dir, "--port", str(port), *extra_argv], workdir)

    def stop(children: list) -> None:
        for child in reversed(children):      # frontend first
            child.terminate()

    # (i) the comparison: one sidecar, one chip.
    t0 = time.perf_counter()
    port = free_port()
    with running(sidecar(0)) as children:
        ping = wait_sidecars(children, socks[:1])[0]
        children.append(frontend("frontend-1", {},
                                 ["--sidecar-socket", socks[0]], port))
        wait_ready(children[-1], port)
        tally = Tally()
        one_chip = run_fleet_tiles(port, data, tally, "(i)")
        rendered = sidecar_ping(socks[0])["tiles_rendered"]
        say(f"(i) one sidecar on one chip ({ping['device']['kind']}, ids "
            f"{ping['device']['ids']}, device files "
            f"{open_device_files(children[0].proc.pid)}): {tally.sent} "
            f"sent, {tally.ok} ok, rendered {rendered}; worst JPEG PSNR "
            f"{tally.worst_psnr:.2f} dB; {time.perf_counter() - t0:.1f}s")
        check(ping["device"]["count"] == 1,
              f"the pinned sidecar sees {ping['device']['count']} devices")
        check(rendered == N_FLEET, f"(i) rendered {rendered}")
        stop(children)

    # (ii) the replica fleet: a device-free frontend over four sidecars,
    # each on its own chip.
    t0 = time.perf_counter()
    port = free_port()
    with running(*(sidecar(i) for i in range(4))) as children:
        wait_sidecars(children, socks)
        children.append(frontend(
            "frontend-4", {"fleet": {"enabled": True, "sockets": socks}},
            [], port))
        wait_ready(children[-1], port)
        tally = Tally()
        bodies = run_fleet_tiles(port, data, tally, "(ii)")
        # More distinct planes until the hash ring has reached everyone.
        nx = data["img"].shape[-1] // TILE
        spare = [(x, y) for y in range(data["img"].shape[-2] // TILE)
                 for x in range(nx)][N_FLEET:]
        extra = 0
        while spare and min(sidecar_ping(s)["tiles_rendered"]
                            for s in socks) == 0:
            x, y = spare.pop(0)
            fetch_and_check(port, tile_url(x, y, 700 + extra, "png"),
                            level0(data, x, y), CHANNELS, tally,
                            f"(ii) extra tile {x},{y}")
            extra += 1
        pings = [sidecar_ping(s) for s in socks]
        held = [open_device_files(c.proc.pid) for c in children[:4]]
        for i, (p, files) in enumerate(zip(pings, held)):
            say(f"(ii) member m{i}: {p['device']['platform']} "
                f"{p['device']['kind']} x{p['device']['count']} ids "
                f"{p['device']['ids']}, TPU_VISIBLE_CHIPS="
                f"{envs[i].get('TPU_VISIBLE_CHIPS')}, device files "
                f"{files}, rendered {p['tiles_rendered']} tiles")
        check(all(p["device"]["count"] == 1 for p in pings),
              "a fleet member sees more than its own chip")
        check(all(p["tiles_rendered"] > 0 for p in pings),
              f"a fleet member did no work: "
              f"{[p['tiles_rendered'] for p in pings]}")
        check(sum(p["tiles_rendered"] for p in pings) == N_FLEET + extra,
              "fleet members' render counts do not add up")
        # Every pinned process calls its chip device 0, so the chips are
        # told apart by the device file each process holds.
        if EXPECT_PLATFORM == "tpu":
            check(all(held) and len({tuple(f) for f in held}) == 4,
                  f"fleet members do not hold four distinct chips: {held}")
        worst = same_as_one_chip(bodies, one_chip, "(ii)")
        say(f"(ii) fleet of four: {tally.sent} sent ({extra} extra "
            f"planes), {tally.ok} ok; PNG bodies equal (i) byte for "
            f"byte, worst JPEG PSNR vs (i) {worst:.2f} dB; "
            f"{time.perf_counter() - t0:.1f}s")
        stop(children)

    # (iii) the mesh: one process, four chips, (data=2, chan=2).
    t0 = time.perf_counter()
    port = free_port()
    mesh = Child("mesh", [
        "--role", "combined", "--config", write_config(
            workdir, "mesh",
            {"parallel": {"enabled": True, "n-devices": 4,
                          "chan-parallel": 2,
                          # Explicit coordinates: JAX then looks
                          # nothing up (no metadata server here).
                          "coordinator-address":
                              f"localhost:{free_port()}",
                          "num-processes": 1, "process-id": 0}}),
        "--data-dir", data_dir, "--port", str(port)], workdir)
    with running(mesh):
        ready_doc, ready_s = wait_ready(mesh, port)
        device = ready_doc["device"]
        check(device["count"] == 4 and len(set(device["ids"])) == 4,
              f"the mesh reports {device}")
        tally = Tally()
        bodies = run_fleet_tiles(port, data, tally, "(iii)")
        m = metrics(port)
        worst = same_as_one_chip(bodies, one_chip, "(iii)")
        say(f"(iii) mesh 2x2 on {device['kind']} ids {device['ids']}: "
            f"ready in {ready_s:.1f}s; {tally.sent} sent, {tally.ok} ok, "
            f"rendered {int(series(m, 'imageregion_tiles_rendered'))}; "
            f"PNG bodies equal (i) byte for byte, worst JPEG PSNR vs (i) "
            f"{worst:.2f} dB; {compile_line(m)}; "
            f"{time.perf_counter() - t0:.1f}s")
        check(series(m, "imageregion_tiles_rendered") == N_FLEET,
              "(iii) render count")
        assert_clean(m, "(iii)")
        mesh.terminate()
    return {"device": device}


# ----------------------------------------------------------------- main

def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4 = the builder-run replica-fleet and "
                             "mesh phases (and nothing else)")
    args = parser.parse_args(argv)
    t_start = time.perf_counter()

    # The native pieces are built from the committed sources (staleness
    # is keyed on a hash of the source, so a copied _build cannot lie).
    from omero_ms_image_region_tpu import native
    t0 = time.perf_counter()
    built = native.status()
    say(f"native: {built} ({time.perf_counter() - t0:.1f}s)")
    check(built["entropy_coder"] == "native"
          and built["tile_cache"] == "native",
          f"native libraries did not build: {built}")

    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    data_dir = os.path.join(workdir, "data")
    os.makedirs(data_dir)
    try:
        if args.chips == 4:
            result = four_chips(workdir, data_dir)
        else:
            a = phase_a(workdir, data_dir,
                        lambda: generate_data(data_dir, IMAGE_EDGE,
                                              IMAGE_EDGE))
            result = phase_b(workdir, data_dir, a)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    check("jax" not in sys.modules, "the parent imported JAX")
    say(f"total {time.perf_counter() - t_start:.1f}s")
    device = result["device"]
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
