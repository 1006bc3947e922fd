"""Startup pre-warming of the hot render executables.

Everything under ``jit`` compiles on first use — ~20 s per JPEG program
shape when compiled for a v5e (cached across restarts by the persistent
compilation cache, but a fresh deployment pays it once per shape).
Without this, the FIRST interactive request of each shape eats that
compile; the reference's analogue is the Bio-Formats memoizer wait that
front-loads reader construction cost at startup
(``beanRefContext.xml:19-21``).

``renderer.prewarm`` lists the tile shapes a deployment expects, e.g.::

    renderer:
        prewarm: ["4x1024", "3x512@90", "2x1024:uint8", "5x1080"]

Each spec is ``<channels>x<tile-edge>[@quality][:dtype]`` (quality
defaults to the LocalCompress default; ``:dtype`` names the images'
storage dtype, default uint16 — serving stages storage dtype in both
cache postures, and the dtype keys the compiled program).  The edge is
the edge of a plane as the store holds it, any whole number of pixels:
it is where a site STATES its plane sizes, and the renderer gives each
stated size a bucket of its own, its MCU grid (:func:`stated_planes`,
``batcher.bucket_lattice``: ``5x1080`` is served from a 1088^2 array,
where a size nobody stated falls to the fixed ladder's next bucket,
2048^2).  For every spec the serving-path programs are compiled through
the real ops entry points with the renderer's own wire engine:

- the batched JPEG program at EVERY launchable padded batch shape up
  to the cap of the spec's bucket (``batcher.group_cap``: ``max_batch``
  at 1024^2 and above, up to 64 below; ``batcher._BATCH_SHAPES``:
  batch 1 is the idle lone-tile path single-tile p50 rides, the cap the
  loaded steady state, and the intermediate shapes — including the
  non-power-of-two 3 and 6 — are what a queue shorter than the cap and
  the inflight-aware group split launch);
- the packed-RGBA program at batch 1 (png/tif formats);
- the stack of a group's resident channel planes at each of those
  batch shapes (``ops.render.stack_group_planes``: what the batcher
  dispatches first for a group whose members' channels are
  HBM-resident, one program a (B, C, shape, bucket, dtype), which also
  pads a plane smaller than its bucket), and the stack of
  one request's planes (``ops.render.stack_channel_planes``: a flipped
  request stacks by itself).

Settings use the ramp-weight table form (plain color channels; LUT
renders compile on first use).
"""

from __future__ import annotations

import logging
import re
import time
from typing import List, Sequence, Tuple

import numpy as np

from ..codecs import DEFAULT_JPEG_QUALITY

logger = logging.getLogger(__name__)

_SPEC_RE = re.compile(r"^(\d+)x(\d+)(?:@(\d+))?(?::([a-z0-9]+))?$")

# Storage dtypes a pixel source can stage — imported from the TIFF
# reader's sample table so the two can never drift.
from ..io.tiff import STORAGE_DTYPE_NAMES as _SPEC_DTYPES  # noqa: E402


def parse_spec(spec: str) -> Tuple[int, int, int, "np.dtype"]:
    """``"4x1024[@90][:uint8]"`` -> (channels, edge, quality, dtype).

    The dtype suffix names the images' STORAGE dtype (serving stages
    storage dtype in both cache postures, and dtype keys the compiled
    program); default uint16, the WSI class.
    """
    m = _SPEC_RE.match(spec.strip())
    if not m:
        raise ValueError(
            f"renderer.prewarm spec {spec!r} is not "
            f"'<channels>x<tile-edge>[@quality][:dtype]'")
    channels, edge, q = (int(m.group(1)), int(m.group(2)),
                        int(m.group(3)) if m.group(3)
                        else round(DEFAULT_JPEG_QUALITY * 100))
    if not (1 <= channels <= 64):
        raise ValueError(f"prewarm channels out of range: {spec!r}")
    if not (16 <= edge <= 8192):
        raise ValueError(
            f"prewarm tile edge must lie in [16, 8192]: {spec!r}")
    if not (1 <= q <= 100):
        raise ValueError(f"prewarm quality out of range: {spec!r}")
    dt = m.group(4) or "uint16"
    if dt not in _SPEC_DTYPES:
        raise ValueError(
            f"prewarm dtype {dt!r} not one of {_SPEC_DTYPES}: {spec!r}")
    return channels, edge, q, np.dtype(dt)


def stated_planes(specs: Sequence[str]) -> tuple:
    """The plane shapes ``(h, w)`` that ``renderer.prewarm`` states, in
    its order: what every renderer of the process builds its bucket
    lattice from (``BatchingRenderer(planes=...)``), the one chip's,
    the mesh's and each fleet member's alike."""
    return tuple(dict.fromkeys(
        (edge, edge) for _, edge, _, _ in map(parse_spec, specs)))


def _warm_one(C: int, edge: int, quality: int, batch_sizes: Sequence[int],
              engine: str, bucket: Tuple[int, int], raw_dtype,
              exec_cache=None) -> None:
    import jax

    from ..flagship import flagship_settings
    from ..ops.jpegenc import render_batch_to_jpeg
    from ..ops.render import (render_tile_batch_packed,
                              stack_channel_planes, stack_group_planes)
    from .batcher import mcu_grid

    bh, bw = bucket
    _, settings = flagship_settings(C)
    # A plane as the raw cache holds it: the spec's own shape where the
    # bucket is its MCU grid (the group's program pads it), else the
    # bucket's.
    shape = (edge, edge) if mcu_grid(edge, edge) == bucket else bucket
    pad = None if shape == bucket else bucket   # as _Pending.pad_to
    plane = jax.device_put(np.zeros(shape, raw_dtype))
    # The fallback of a request that is flipped or padded by itself.
    stack_channel_planes(*[plane] * C).block_until_ready()
    for B in dict.fromkeys(batch_sizes):   # de-dup, keep order
        stack_group_planes(((plane,) * C,) * B,
                           pad=pad).block_until_ready()
        # Zeros: programs are content-independent.  The dtype must
        # match what serving stacks (it keys the compiled program);
        # both cache postures stage the images' STORAGE dtype.
        raw = np.zeros((B, C, bh, bw), raw_dtype)
        stacked = {
            k: (np.stack([v] * B) if getattr(v, "ndim", 0) else v)
            for k, v in settings.items()
        }
        args = (raw, stacked["window_start"], stacked["window_end"],
                stacked["family"], stacked["coefficient"],
                stacked["reverse"], settings["cd_start"],
                settings["cd_end"], stacked["tables"])
        # tune=False: these all-zero compile probes must never feed
        # the per-workload Huffman tuning — tables fitted to a black
        # tile would be published permanently and serve every real
        # tile of this shape with mismatched codes.
        render_batch_to_jpeg(*args, quality=quality,
                             dims=[(edge, edge)] * B, engine=engine,
                             tune=False)
        if B == 1:
            if exec_cache is not None:
                # Persistence posture: the packed program loads from a
                # prior life's serialized executable (no trace, no
                # compile) or compiles once and is serialized for the
                # next life; either way the registered program is what
                # serving groups of this signature will call.
                fn = exec_cache.ensure("render_tile_batch_packed",
                                       render_tile_batch_packed, args)
                np.asarray(fn(*args) if fn is not None
                           else render_tile_batch_packed(*args))
            else:
                np.asarray(render_tile_batch_packed(*args))


def prewarm_batch_sizes(cap: int) -> tuple:
    """Every padded batch shape the dispatcher can launch at or below
    ``cap`` (a bucket's ``batcher.group_cap``) — imported from the
    batcher's own shape table so the two can never drift.  Warming
    only (1, cap) left the intermediate entries (3, 6) to lazy XLA
    compiles on the first 3-/6-tile group."""
    from .batcher import _BATCH_SHAPES
    sizes = tuple(s for s in _BATCH_SHAPES if s <= cap)
    return sizes if cap in sizes else sizes + (cap,)


def prewarm_renderer(specs: List[str], engine: str,
                     max_batch: int, buckets,
                     cpu_fallback_max_px: int = 0,
                     exec_cache=None) -> None:
    """Compile the serving programs for each spec; failures are logged,
    never fatal (serving still works, it just compiles lazily).

    Each spec carries its images' storage dtype (default uint16) — the
    dtype serving stacks in either cache posture, which keys the
    compiled program.  Specs at or below ``cpu_fallback_max_px`` are
    skipped: the handler routes those renders to the host kernel, so a
    device program would never be hit.  ``/readyz`` reports degraded
    while this runs (telemetry.READINESS).
    """
    from ..utils.telemetry import READINESS
    from .batcher import group_cap, mcu_grid, pick_bucket
    # Malformed specs raise HERE, before the readiness flag flips or
    # any compile starts (the loader's contract: config errors are
    # loud, and a caller spawning this on a background thread gets the
    # raise before the thread — never a silently-degraded prewarm or a
    # stuck-pending /readyz).
    parsed = [(spec,) + tuple(parse_spec(spec)) for spec in specs]
    READINESS.prewarm_pending = bool(specs)
    try:
        for spec, C, edge, quality, raw_dtype in parsed:
            if edge * edge <= cpu_fallback_max_px:
                logger.info(
                    "prewarm %s skipped: %dx%d px is at/below "
                    "renderer.cpu-fallback-max-px (%d) and serves on "
                    "the host kernel", spec, edge, edge,
                    cpu_fallback_max_px)
                continue
            t0 = time.perf_counter()
            bucket = pick_bucket(*mcu_grid(edge, edge), buckets)
            batch_sizes = prewarm_batch_sizes(
                group_cap(max_batch, bucket[0] * bucket[1]))
            try:
                _warm_one(C, edge, quality, batch_sizes, engine,
                          bucket, raw_dtype, exec_cache=exec_cache)
            except Exception:
                # Per-spec: one shape's dead compile must not strand
                # the others (serving still works, it compiles lazily).
                logger.warning("prewarm %s failed; first requests of "
                               "this shape will compile lazily", spec,
                               exc_info=True)
            else:
                logger.info("prewarmed %s (engine %s, batches %s, %s) "
                            "in %.1fs", spec, engine,
                            "/".join(map(str, batch_sizes)),
                            np.dtype(raw_dtype).name,
                            time.perf_counter() - t0)
    finally:
        READINESS.prewarm_pending = False
