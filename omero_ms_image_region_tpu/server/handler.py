"""Request orchestration: parsed ctx -> encoded image bytes.

The analogue of ``ImageRegionRequestHandler.java`` (cache-first render flow
``:159-249``, metadata fetch + write-back ``:316-427``, region pipeline
``:429-604``) and ``ShapeMaskRequestHandler.java`` (``:49-278``) — with the
device-facing part factored behind a ``Renderer`` callable so the direct
path and the micro-batched path are interchangeable.

Ordering guarantees preserved from the reference:
  * a cache hit is served only after the ACL check passes
    (``ImageRegionRequestHandler.java:229-243``);
  * mask PNGs are cached only when the request sets an explicit color
    (``ShapeMaskVerticle.java:140-148``);
  * the projection branch renders the full projected plane (the reference
    resets the plane definition without a region, ``:554-557``) and only
    the active channels survive projection (``:506-539``).
"""

from __future__ import annotations

import asyncio
import json
import time
from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import numpy as np

from .. import codecs
from ..models.pixels import Pixels
from ..models.rendering import RenderingDef
from ..ops import projection as projection_ops
from ..ops.render import (pack_settings, render_tile_packed,
                          stack_channel_planes, unpack_rgba)
from ..services.cache import Caches
from ..services.metadata import CanReadMemo, MetadataService
from ..utils import telemetry
from ..utils.color import split_html_color
from ..utils.stopwatch import record_since, stopwatch
from .config import RendererConfig
from .ctx import BadRequestError, ImageRegionCtx, ShapeMaskCtx
from .region import (RegionDef, clamp_region_to_plane, edge_tile_shapes,
                     get_region_def, tile_extent)
from .settings import render_identity_key, update_settings

DEFAULT_MAX_TILE_LENGTH = 2048  # beanRefContext.xml:63-66
# Cold-staging band height: regions at least 2 bands tall ship as
# per-band async device_puts so disk reads overlap H2D transfers.
_STAGE_BAND_ROWS = 256


def _stage_band_bounds(height: int, y: int, tile_h: int) -> List[int]:
    """Row bounds ``[0, ..., height]`` of a region's upload bands: up
    to four, none under ``_STAGE_BAND_ROWS`` rows.  Interior bounds
    snap to the source's tile-row grid (absolute rows: the region
    starts at ``y``) so a boundary never splits a chunk row, which
    both adjacent bands would otherwise read and decode; a bound that
    snaps onto its predecessor, or within a band's height of either
    end, is dropped.  ``[0, height]`` means one band: no banding."""
    n_bands = min(4, height // _STAGE_BAND_ROWS)
    bounds = [0]
    for k in range(1, n_bands):
        b = ((y + height * k // n_bands + tile_h // 2) // tile_h
             * tile_h - y)
        if (b - bounds[-1] >= _STAGE_BAND_ROWS
                and height - b >= _STAGE_BAND_ROWS):
            bounds.append(b)
    bounds.append(height)
    return bounds


from .errors import (NotFoundError,  # noqa: E402,F401  (re-export;
                     OverloadedError)
# The exceptions live in the device-free errors module so frontend
# proxy processes can share the status contract without importing JAX.

# Projection banding: planes whose u16 storage exceeds the threshold
# project via row bands (project_region_banded) so peak host memory is
# chunk-sized; each band targets ~_PROJECTION_BAND_BYTES of f32 rows.
_PROJECTION_BAND_THRESHOLD_BYTES = 64 * 1024 * 1024
_PROJECTION_BAND_BYTES = 32 * 1024 * 1024


class Renderer:
    """Direct device render: one dispatch per request.

    The micro-batcher (``server.batcher``) exposes the same ``render`` /
    ``render_jpeg`` coroutines and substitutes transparently.

    ``jpeg_engine`` is the deployment's device JPEG wire format
    (``renderer.jpeg-engine``); ``ops.jpegenc.render_batch_to_jpeg``
    acts on it.
    """

    def __init__(self, jpeg_engine: str = "sparse"):
        if jpeg_engine not in ("sparse", "huffman"):
            raise ValueError(f"unknown jpeg engine {jpeg_engine!r}")
        self.jpeg_engine = jpeg_engine
        # Per-member device pin (cross-host federation): when a fleet
        # member owns a device set, its renders dispatch there instead
        # of the process default device.  None = default device.
        self.device = None

    async def render(self, raw: np.ndarray, settings: dict) -> np.ndarray:
        """f32[C, H, W] + packed settings -> u32[H, W] packed RGBA."""
        return await asyncio.to_thread(self._pinned, self._render_sync,
                                       raw, settings)

    def _pinned(self, fn, *args):
        """Run one sync render under this member's device pin (the
        worker thread's dispatches land on ``self.device``; None is a
        straight call)."""
        from ..io.staging import pin_scope
        with pin_scope(self.device):
            return fn(*args)

    def _render_sync(self, raw: np.ndarray, settings: dict) -> np.ndarray:
        out = render_tile_packed(
            raw, settings["window_start"], settings["window_end"],
            settings["family"], settings["coefficient"],
            settings["reverse"], settings["cd_start"], settings["cd_end"],
            settings["tables"],
        )
        return np.asarray(out)

    async def render_jpeg(self, raw: np.ndarray, settings: dict,
                          quality: int, width: int, height: int) -> bytes:
        """Fused render + device JPEG front end for one tile.

        Only quantized coefficients leave the device, never the full
        RGBA tile.
        ``raw`` is f32[C, h, w] at the tile's true size; MCU padding and
        the SOF0 crop are handled here.
        """
        return await asyncio.to_thread(
            self._pinned, self._render_jpeg_sync, raw, settings,
            quality, width, height)

    def _render_jpeg_sync(self, raw, settings, quality, width, height):
        from ..flagship import batched_args
        from ..ops.jpegenc import pad_planes_to_mcu, render_batch_to_jpeg

        if isinstance(raw, np.ndarray):
            raw = np.ascontiguousarray(raw)
        padded = pad_planes_to_mcu(raw)[None]
        args = batched_args(settings, padded)
        return render_batch_to_jpeg(
            *args, quality=quality, dims=[(width, height)],
            engine=self.jpeg_engine)[0]


from .singleflight import SingleFlight  # noqa: E402,F401  (re-export;
# the class moved to the device-free singleflight module so frontend
# fleet routers can coalesce without importing the JAX stack — every
# existing ``from .handler import SingleFlight`` keeps working)


@dataclass
class ImageRegionServices:
    """Everything a handler needs, injected once at startup (the analogue of
    the Spring wiring, ``beanRefContext.xml:68-79``)."""

    pixels_service: object            # io.service.PixelsService
    metadata: MetadataService
    caches: Caches
    can_read_memo: CanReadMemo
    renderer: Renderer
    lut_provider: object = None       # ops.lut.LutProvider
    max_tile_length: int = DEFAULT_MAX_TILE_LENGTH
    raw_cache: object = None          # io.devicecache.DeviceRawCache
    prefetcher: object = None         # services.prefetch.TilePrefetcher
    # In-flight render dedup (SingleFlight); None disables coalescing.
    single_flight: object = None
    # Admission control / load shedding (server.admission); None
    # admits everything (the batcher queues unboundedly).
    admission: object = None
    # Warm-state persistence engine (services.warmstate); None when
    # persistence is disabled — nothing survives the process then.
    warmstate: object = None
    # Renders at or below this pixel count take the CPU reference kernel
    # (refimpl) instead of a device round trip — the SURVEY north star's
    # fallback path, for what is smaller than a stock 256x256 tile
    # (edge slivers, pyramid tops).  0 disables.  The default is
    # server.config.RendererConfig's, which says what was measured: a
    # full stock tile is a device render.
    cpu_fallback_max_px: int = RendererConfig.cpu_fallback_max_px
    # What this process serves from, as build_services found it:
    # ``{platform, kind, count, ids}`` (utils.jaxenv.device_identity)
    # and ``{entropy_coder, tile_cache}`` (native.status).  Carried on
    # /readyz and the sidecar ping; None for injected test stacks.
    device: Optional[dict] = None
    native: Optional[dict] = None
    # This member's dispatch device (an in-process fleet or federation
    # partitions the host's devices across its members —
    # parallel.fleet.partition_local_devices); its reads upload there.
    # None = the process default device.
    pin_device: object = None


from ..models.rendering import restrict_to_active \
    as _restrict_to_active  # noqa: E402  (shared with server.degraded
# so the device pipeline and the CPU fallback cannot silently diverge
# on channel selection)


async def check_can_read(services: ImageRegionServices, object_type: str,
                         object_id: int,
                         session_key: Optional[str]) -> bool:
    """Memoized ACL check (memo -> metadata service -> memo write-back),
    shared by the image and mask pipelines."""
    memo = await services.can_read_memo.get_async(
        session_key, object_type, object_id)
    if memo is not None:
        return memo
    t0 = time.perf_counter()
    try:
        ok = await services.metadata.can_read(object_type, object_id,
                                              session_key)
    finally:
        record_since("canRead", t0)
    await services.can_read_memo.put_async(
        session_key, object_type, object_id, ok)
    return ok


# Brownout-ladder request hooks (device-free, shared with the fleet
# and proxy handlers — see server.pressure for the contract).
from .pressure import pressure_quality as _pressure_quality  # noqa: E402
from .pressure import \
    shed_bulk_under_pressure as _shed_bulk_under_pressure  # noqa: E402


class ImageRegionHandler:
    """One instance per service; per-request state stays on the stack
    (the reference builds a handler per request, this one is stateless)."""

    def __init__(self, services: ImageRegionServices):
        self.s = services

    # ------------------------------------------------------------ ACL

    async def _can_read(self, object_type: str, object_id: int,
                        session_key: Optional[str]) -> bool:
        return await check_can_read(self.s, object_type, object_id,
                                    session_key)

    # ------------------------------------------------------- metadata

    async def _get_pixels(self, ctx: ImageRegionCtx) -> Optional[Pixels]:
        """Pixels metadata, Redis-style cache in front of the service
        (``ImageRegionRequestHandler.java:316-427``)."""
        key = ImageRegionCtx.pixels_metadata_cache_key(ctx.image_id)
        cached = await self.s.caches.pixels_metadata.get(key)
        if cached is not None:
            try:
                return Pixels.from_json(json.loads(cached))
            except (ValueError, KeyError):
                pass  # poisoned entry: fall through to the service
        t0 = time.perf_counter()
        try:
            pixels = await self.s.metadata.get_pixels_description(
                ctx.image_id, ctx.omero_session_key)
        finally:
            record_since("get_pixels_description", t0)
        if pixels is not None:
            await self.s.caches.pixels_metadata.set(
                key, json.dumps(pixels.to_json()).encode())
        return pixels

    # ---------------------------------------------------------- entry

    async def render_image_region(self, ctx: ImageRegionCtx,
                                  adopt_cache: bool = True,
                                  skip_byte_cache: bool = False
                                  ) -> bytes:
        """The cache-first flow (``renderImageRegion``, ``:159-249``).

        ``adopt_cache=False`` is the fleet's work-stealing contract
        (``parallel.fleet``): a STOLEN render reads from source bytes
        and never inserts into this member's HBM raw cache — the
        plane's shard ownership stays with its hash-ring owner.  Probe
        hits still serve (reading costs nothing in ownership), and the
        byte-cache write-back is unaffected (the byte tier is shared
        fleet-wide).

        ``skip_byte_cache=True`` (fleet members only) skips the probe
        of the shared byte tier: ``FleetImageHandler`` already probed
        it — and ran the caller's ACL gate — immediately before
        dispatching, so the member-level get would be a guaranteed
        miss paying a wasted walk of the memory/disk tiers on the hot
        path.  The write-back below still runs."""
        from ..services.cache import get_with_tier
        from ..utils import provenance
        t0 = time.perf_counter()
        if ctx.t_accept is None:
            ctx.t_accept = t0     # no HTTP layer stamped the request
        cached, cache_tier = ((None, None) if skip_byte_cache else
                              await get_with_tier(
                                  self.s.caches.image_region,
                                  ctx.cache_key))
        if cached is not None:
            if await self._can_read("Image", ctx.image_id,
                                    ctx.omero_session_key):
                # Waterfall/access-log marker: the byte cache answered
                # (the render stages below never ran).
                telemetry.record_span(
                    "cache.hit", t0,
                    (time.perf_counter() - t0) * 1000.0)
                provenance.mark(
                    ctx, tier=("disk" if cache_tier == "disk"
                               else "byte_cache"))
                return cached
            raise NotFoundError(f"Cannot find Image:{ctx.image_id}")

        pixels = await self._get_pixels(ctx)
        if pixels is None or not await self._can_read(
                "Image", ctx.image_id, ctx.omero_session_key):
            raise NotFoundError(f"Cannot find Image:{ctx.image_id}")
        # Span ``handler.metadata``: accepted -> the pixels description
        # and the ACL resolved (the byte cache's probe before them).
        # The first part of ``handler.prepare``, recorded as it is.
        record_since("handler.metadata", ctx.t_accept)

        single_flight = self.s.single_flight
        admission = self.s.admission
        # Per-session fairness runs PER CALLER, before coalescing —
        # like the ACL gate above: single-flight shares the leader's
        # outcome across SESSIONS, so a hostile session's over-budget
        # 503 inside the producer would propagate to coalesced
        # followers from under-budget sessions.  Here every request
        # pays its own token (ctx.omero_session_key — the one session
        # identity the middleware resolved) and sheds only itself.
        debit = admission.admit_session(ctx) if admission is not None \
            else None
        if debit is not None:
            provenance.mark(ctx, tokens=debit[1])

        async def produce() -> bytes:
            # GLOBAL admission sits HERE — after the byte cache (hits
            # are nearly free and must never shed) and inside the
            # single-flight producer (a coalesced follower adds no
            # work, so only the leader's pipeline run claims a slot).
            _shed_bulk_under_pressure(ctx)
            t_admit = admission.admit() if admission is not None \
                else None
            completed = False
            try:
                from ..utils.transient import check_deadline
                check_deadline("render pipeline")
                data = await self._get_region(ctx, pixels,
                                              adopt_cache=adopt_cache)
                completed = True
            finally:
                if admission is not None:
                    admission.release(t_admit, completed=completed)
            if not getattr(ctx, "_pressure_quality_capped", False):
                await self.s.caches.image_region.set(ctx.cache_key,
                                                     data)
            return data

        try:
            if single_flight is None:
                # Deadline-bounded await even without coalescing: a
                # group popped before its members' budgets died can
                # still wedge in the device thread, and the caller
                # must get its 504 at budget end, not hang behind the
                # lane (the device work itself cannot be interrupted;
                # its future settles into the void).
                from ..utils import transient
                remaining = transient.remaining_ms()
                if remaining is None:
                    return await produce()
                try:
                    return await asyncio.wait_for(
                        produce(),
                        timeout=max(0.0, remaining) / 1000.0)
                except asyncio.TimeoutError:
                    raise transient.DeadlineExceededError(
                        "deadline exceeded awaiting render")
            # Coalesce concurrent identical requests onto one pipeline
            # run: the leader renders and writes the byte cache back;
            # followers settle from the same task.  ACL and fairness
            # already ran per caller above, so sharing the bytes is
            # exactly as safe as the byte-cache hit path.
            data, coalesced = await single_flight.run(
                render_identity_key(ctx), produce)
        except OverloadedError:
            # Refused GLOBALLY (queue/deadline/pressure — directly or
            # via the leader this caller coalesced onto) after the
            # fairness gate debited tokens: refund them — the session
            # never got the render.
            if admission is not None:
                admission.refund_session(debit)
            raise
        if coalesced:
            # Waterfall marker for the follower: its wall time was one
            # await on the leader's pipeline, not a pipeline of its own.
            telemetry.record_span(
                "dedup.coalesced", t0,
                (time.perf_counter() - t0) * 1000.0)
            provenance.mark(ctx, coalesced=True)
        return data

    async def render_image_region_stream(self, ctx: ImageRegionCtx):
        """Progressive surface parity with the sidecar proxy
        (``SidecarImageHandler.render_image_region_stream``): combined
        mode has no wire hop to pipeline over, so the stream is the one
        body — which the batcher's first-tile-out settlement already
        resolves the moment this tile's encode slice lands, a
        batch-tail ahead of the v2 barrier.  The HTTP layer gets ONE
        uniform chunked-response path either way."""
        yield await self.render_image_region(ctx)

    # --------------------------------------------------------- pipeline

    async def _open_pixel_source(self, image_id: int, pixels: Pixels):
        """Resolve + open the image's pixel data.

        The per-image ``data_dir`` layout is tried first; when it has no
        entry and the metadata backend can resolve binary-repository
        paths (``metadata-service: postgres`` + a mounted
        ``omero.data.dir``), the image serves straight out of the OMERO
        repository — the reference's resolver-bean + Bio-Formats flow
        (``ImageRegionRequestHandler.java:302-309``).
        """
        svc = self.s.pixels_service
        resolver = getattr(self.s.metadata, "resolve_image_paths", None)
        opened = getattr(svc, "get_open_source", None)
        if opened is not None:
            # Hot path: an already-open source is a lock + dict hit —
            # the thread-pool hop would cost more than the lookup
            # (measured ~2-4 ms per request at service concurrency on
            # one core, paid on the batching convoy's critical path).
            # get_open_source NEVER sniffs or opens, so a concurrent
            # eviction just returns None and the full path runs
            # off-loop below.
            src = opened(image_id)
            if src is not None:
                return src
        try:
            # The handle cache or the data_dir layout serves without
            # any DB round trip (and without a second sniff, or a
            # check-then-open race against LRU eviction).
            return await asyncio.to_thread(svc.get_pixel_source,
                                           image_id)
        except FileNotFoundError:
            if resolver is None or not getattr(svc, "repo_root", None):
                raise
        candidates = await resolver(image_id)
        return await asyncio.to_thread(
            svc.get_pixel_source, image_id, candidates, pixels)

    async def _get_region(self, ctx: ImageRegionCtx, pixels: Pixels,
                          adopt_cache: bool = True) -> bytes:
        if ctx.z < 0 or ctx.z >= pixels.size_z:
            raise BadRequestError(
                f"Parameter 'theZ' not within bounds: {ctx.z}")
        if ctx.t < 0 or ctx.t >= pixels.size_t:
            raise BadRequestError(
                f"Parameter 'theT' not within bounds: {ctx.t}")

        # Once a request, hit or miss; the open itself has a span on
        # the thread that does it (``PixelsService.openSource``).
        t0 = time.perf_counter()
        try:
            src = await self._open_pixel_source(ctx.image_id, pixels)
        finally:
            record_since("PixelsService.getPixelBuffer", t0)

        if src.resolution_levels() > 1:
            levels: Sequence[Sequence[int]] = [
                list(d) for d in src.resolution_descriptions()]
        else:
            levels = [[pixels.size_x, pixels.size_y]]
        if ctx.resolution is not None and not (
                0 <= ctx.resolution < len(levels)):
            raise BadRequestError(
                f"Resolution {ctx.resolution} not within [0, {len(levels)})")

        region = get_region_def(
            levels, ctx.resolution, ctx.tile, ctx.region, src.tile_size(),
            self.s.max_tile_length, ctx.flip_horizontal, ctx.flip_vertical,
        )
        # The request resolution indexes the largest-first descriptions
        # list directly (the reference's getRegionDef/checkPlaneDef do the
        # same, and its testSelectResolution locks it in).  The reference's
        # extra ``n - res - 1`` inversion (setResolutionLevel, ``:845-852``)
        # exists only because OMERO's PixelBuffer numbers levels
        # smallest-first; our PixelSource numbers them largest-first like
        # the descriptions, so the read level IS the resolution index.
        level = ctx.resolution or 0
        clamp_region_to_plane(levels, ctx.resolution, region)
        if region.width <= 0 or region.height <= 0:
            raise BadRequestError(
                f"Region {region.as_tuple()} outside image bounds")

        rdef = update_settings(_default_rdef(pixels), ctx)
        active_rdef, active = _restrict_to_active(rdef)
        if not active:
            raise BadRequestError("No active channels to render")

        tiny = bool(
            self.s.cpu_fallback_max_px
            and region.width * region.height <= self.s.cpu_fallback_max_px
            and ctx.projection is None)
        # The handler's choice, counted where it is made
        # (/metrics imageregion_renders_routed_total{route=...}).
        route = "host" if tiny else "device"
        telemetry.ROUTES.count(route)
        # What was resolved: its unpadded pixels and, for a tile, its
        # level and whether the level's edge cut it below the tile.
        tile_level = edge = None
        if ctx.tile is not None:
            tile_w, tile_h = tile_extent(ctx.tile, src.tile_size(),
                                         self.s.max_tile_length)
            tile_level = level
            edge = region.width < tile_w or region.height < tile_h
        telemetry.TILE_RENDERS.count(region.width * region.height,
                                     tile_level, edge)
        warm_edges = getattr(self.s.renderer, "warm_edges", None)
        if (edge and not tiny and warm_edges is not None
                and ctx.format == "jpeg" and ctx.projection is None):
            # The image's first edge tile: every edge shape of its
            # levels, compiled off the loop before a viewer meets it.
            warm_edges(len(active), src.dtype,
                       edge_tile_shapes(levels, tile_w, tile_h))

        if ctx.projection is not None:
            raw, region = await self._project(ctx, pixels, src, active)
        else:
            planes = None
            if not tiny and self.s.raw_cache is not None:
                # One probe a shown channel: a plane is cached under
                # its own channel, whatever is shown beside it.
                keys = self._plane_keys(ctx, region, level or 0, active)
                planes = self.s.raw_cache.get_planes(keys)
                if self.s.prefetcher is not None:
                    # Predictive-hit accounting: if the prefetcher
                    # staged a plane, the pan/zoom step just paid
                    # render + encode only — the number the sessions
                    # bench gates on.
                    for key, plane in zip(keys, planes):
                        if plane is not None:
                            self.s.prefetcher.note_hit(key)
            from ..utils import provenance
            if planes is not None and all(p is not None for p in planes):
                # Every shown channel is HBM-resident: dict lookups
                # (and one dispatch where the request has to be stacked
                # here) — skip the thread-pool hop (same economics as
                # the open-source fast path above).
                raw = self._channel_stack(planes, 0, ctx)
                provenance.mark(ctx, tier="hbm_warm")
            else:
                provenance.mark(ctx, tier="render_cold")
                raw = await asyncio.to_thread(
                    self._read_region, src, ctx, region, level or 0,
                    active, planes,
                    # Tiny renders stay host-side; stolen fleet work
                    # reads from source without adopting ownership.
                    not tiny and adopt_cache)
            if (self.s.prefetcher is not None and ctx.tile is not None
                    and not tiny):   # tiny neighbors never read the cache
                self.s.prefetcher.tile_served(
                    src, ctx.image_id, ctx.z, ctx.t, ctx.resolution,
                    levels, ctx.tile, src.tile_size(),
                    self.s.max_tile_length, active,
                    ctx.flip_horizontal, ctx.flip_vertical,
                    session_key=ctx.omero_session_key)

        if tiny:
            self._record_prepare(ctx, route)
            data = await asyncio.to_thread(
                self._render_cpu, np.asarray(raw), active_rdef, ctx)
            telemetry.mark_answered(time.perf_counter())
            return data

        settings = pack_settings(active_rdef, self.s.lut_provider)
        t_handoff = self._record_prepare(ctx, route)

        if ctx.format == "jpeg":
            # Device JPEG path: flips fold into the raw planes (render is
            # pointwise), and only quantized coefficients leave the device.
            if ctx.flip_vertical:
                raw = raw[:, ::-1, :]
            if ctx.flip_horizontal:
                raw = raw[:, :, ::-1]
            # Planes handed on as they are have no flip (_channel_stack).
            h, w = (raw[0] if isinstance(raw, tuple) else raw).shape[-2:]
            quality = codecs.quality_percent(ctx.compression_quality)
            quality = _pressure_quality(quality, ctx)
            try:
                return await self.s.renderer.render_jpeg(
                    raw, settings, quality, w, h)
            finally:
                # The hand-off -> the answer back in this coroutine.
                # Where no batcher stamped the answer earlier, this is
                # where ``handler.respond`` begins.
                telemetry.mark_answered(record_since(
                    "Renderer.renderAsPackedInt", t_handoff))

        try:
            packed = await self.s.renderer.render(raw, settings)
        finally:
            telemetry.mark_answered(record_since(
                "Renderer.renderAsPackedInt", t_handoff))

        if ctx.flip_horizontal or ctx.flip_vertical:
            if ctx.flip_vertical:
                packed = packed[::-1, :]
            if ctx.flip_horizontal:
                packed = packed[:, ::-1]
        rgba = unpack_rgba(np.ascontiguousarray(packed))
        return await asyncio.to_thread(self._encode_rgba, rgba, ctx)

    @staticmethod
    def _record_prepare(ctx: ImageRegionCtx, route: str) -> float:
        """Span ``handler.prepare``: request accepted (the HTTP layer's
        ``ctx.t_accept``, else this handler's entry) to the hand-off to
        the batcher or the host render thread — parse, metadata, ACL,
        region, raw-cache probe or read, ``pack_settings``.  Wall time
        on the event loop, so it holds the loop's own queue.  Recorded
        like ``batcher.queueWait``, not under ``stopwatch``: it spans
        awaits, where a profiler annotation would interleave with other
        requests' on the loop's thread.  On the request's trace it
        carries the route the render took.  Returns the hand-off's
        stamp."""
        return record_since("handler.prepare", ctx.t_accept, route=route)

    def _encode_rgba(self, rgba: np.ndarray, ctx: ImageRegionCtx) -> bytes:
        """Shared encode tail (format dispatch + 404 on unknown format)."""
        try:
            with stopwatch("encodeImage"):
                return codecs.encode_rgba(np.ascontiguousarray(rgba),
                                          ctx.format,
                                          ctx.compression_quality)
        except codecs.UnknownFormatError as e:
            raise NotFoundError(str(e))

    def _render_cpu(self, raw: np.ndarray, rdef: RenderingDef,
                    ctx: ImageRegionCtx) -> bytes:
        """CPU reference path for tiny renders (refimpl semantics).

        Flips fold into the raw planes (render is pointwise), so the
        encode tail is shared verbatim with the device path.
        """
        from ..refimpl import render_ref

        if ctx.flip_vertical:
            raw = raw[:, ::-1, :]
        if ctx.flip_horizontal:
            raw = raw[:, :, ::-1]
        with stopwatch("Renderer.renderAsPackedInt.cpu"):
            rgba = render_ref(raw.astype(np.float32), rdef,
                              self.s.lut_provider)
        return self._encode_rgba(rgba, ctx)

    @staticmethod
    def _plane_keys(ctx: ImageRegionCtx, region: RegionDef, level: int,
                    active: List[int]) -> list:
        """The raw read's cache identities, one a shown channel — ONE
        construction site shared by the event-loop probe and the loader
        (a drifted duplicate would silently defeat the fast path)."""
        from ..io.devicecache import region_key
        where = (ctx.image_id, ctx.z, ctx.t, level, region.as_tuple())
        return [region_key(*where, c) for c in active]

    def _channel_stack(self, planes: list, missing: int,
                       ctx: ImageRegionCtx):
        """Span ``handler.channelStack``: one request's resident channel
        planes put together for the renderer, ``missing`` of which this
        request had to read and upload first.  Where nothing has to be
        done to them per request (no flip, and the renderer says they
        fill their bucket: ``takes_planes``) they go on as a tuple and
        their group stacks them, once for all its members; otherwise
        one jitted program stacks this request's ``[C_active, h, w]``
        here.  Either is the request's own; the cache keeps the planes
        only."""
        with stopwatch("handler.channelStack", channels=len(planes),
                       missing=missing):
            takes = getattr(self.s.renderer, "takes_planes", None)
            if (takes is not None
                    and not (ctx.flip_horizontal or ctx.flip_vertical)
                    and takes(*planes[0].shape,
                              jpeg=ctx.format == "jpeg")):
                return tuple(planes)
            from ..io.staging import pin_scope
            with pin_scope(getattr(self.s, "pin_device", None)):
                return stack_channel_planes(*planes)

    def _read_region(self, src, ctx: ImageRegionCtx, region: RegionDef,
                     level: int, active: List[int],
                     planes: Optional[list] = None,
                     device_cache: bool = True):
        """Raw [C_active, h, w] planes (storage dtype) for the region.

        With a device raw cache configured (and ``device_cache`` true)
        the result is HBM-resident, the region's channel planes as
        ``_channel_stack`` hands them on (a ``jax.Array`` stack, or the
        planes themselves): ``planes`` is the caller's probe of
        the cache (the resident planes, None where one is missing;
        probed here when not given), only the missing ones are read and
        uploaded, each adopted under its own channel.  Raw planes are
        settings-independent, so the interactive re-window / re-color /
        channel-toggle pattern re-renders without moving a resident
        byte over the host link.

        Wrapped in the ``PixelsService.readRegion`` span (and the
        ledger's ``read_ms``): the cold disk-read + staging half of a
        request's wall time, which the render/encode spans never see —
        without it a slow store and a slow device look identical in a
        waterfall.
        """
        from ..io.staging import pin_scope
        # A fleet member's reads upload to its own chip.
        with stopwatch("PixelsService.readRegion"), \
                pin_scope(getattr(self.s, "pin_device", None)):
            if self.s.raw_cache is None or not device_cache:
                # Storage dtype, not float32: the kernels cast on
                # device (dtype keys the batch group), and a float32
                # staging copy would double the host->device bytes of
                # the posture that pays for every upload.
                return np.stack([
                    src.get_region(ctx.z, c, ctx.t, region, level)
                    for c in active])
            keys = self._plane_keys(ctx, region, level, active)
            if planes is None:
                planes = self.s.raw_cache.get_planes(keys)
            missing = sum(p is None for p in planes)
            planes = self._load_missing_planes(src, ctx, region, level,
                                               zip(keys, active, planes))
        return self._channel_stack(planes, missing, ctx)

    def _load_missing_planes(self, src, ctx: ImageRegionCtx,
                             region: RegionDef, level: int,
                             probed) -> list:
        """``probed``: (key, channel, resident plane or None) of each
        shown channel.  The planes of all, the missing ones loaded."""
        def load_staged(c: int):
            """Cold staging pipeline of one channel plane: band the
            region's rows and ship each band as its own async
            ``device_put``, so band k+1's disk read overlaps band k's
            host->HBM transfer (the dispatch returns before the copy
            lands).  A region that yields one band is read in one
            piece — banding only pays when the read itself has
            substance."""
            bounds = _stage_band_bounds(region.height, region.y,
                                        max(1, src.tile_size()[1]))
            if len(bounds) == 2:
                return src.get_region(ctx.z, c, ctx.t, region, level)
            import jax
            import jax.numpy as jnp
            parts = []
            for y0, y1 in zip(bounds, bounds[1:]):
                sub = RegionDef(region.x, region.y + y0,
                                region.width, y1 - y0)
                parts.append(jax.device_put(
                    src.get_region(ctx.z, c, ctx.t, sub, level)))
            return jnp.concatenate(parts, axis=0)

        # The routing identity rides along so a rolling drain can hand
        # each plane to the ring member that will serve its future
        # requests (parallel.fleet drain handoff).
        from ..parallel.fleet import plane_route_key
        route = plane_route_key(ctx)
        return [
            plane if plane is not None else self.s.raw_cache.get_or_load(
                key, lambda c=c: load_staged(c), route_key=route)
            for key, c, plane in probed]

    async def _project(self, ctx: ImageRegionCtx, pixels: Pixels, src,
                       active: List[int]
                       ) -> Tuple[np.ndarray, RegionDef]:
        """Z-projection branch (``:506-558``): project each active
        channel, then render the projected full plane.

        WSI-scale by construction: planes stream through
        :func:`ops.projection.project_planes` — only the Z window's
        planes are read, one at a time, into a device accumulator —
        where the reference materializes the whole stack
        (``pixelBuffer.getStack``, ``ProjectionService.java:72``) and
        stalls on real WSIs.  Projected planes are device-cached like
        raw tiles (same interactive re-window pattern), keyed by
        everything the projection depends on.
        """
        start = ctx.projection_start or 0
        end = (ctx.projection_end if ctx.projection_end is not None
               else pixels.size_z - 1)
        projection_ops.check_projection_bounds(
            start, end, 1, active[0], ctx.t,
            pixels.size_z, pixels.size_c, pixels.size_t)
        type_max = pixels.type_range()[1]
        full = RegionDef(0, 0, pixels.size_x, pixels.size_y)

        def project_one(c: int):
            with stopwatch("ProjectionService.projectStack"):
                if (pixels.size_x * pixels.size_y * 2
                        > _PROJECTION_BAND_THRESHOLD_BYTES):
                    # WSI-scale plane: band over rows so peak host
                    # memory is one [z_chunk, band, W] chunk, never a
                    # full plane (VERDICT r3 weak 5; the reference's
                    # getStack would materialize Z full planes here).
                    band = max(64, _PROJECTION_BAND_BYTES
                               // max(pixels.size_x * 4, 1))
                    # placement="host": PixelSource reads are host
                    # numpy, and a projection is a reduction — folding
                    # host-side ships ONE plane over the link instead
                    # of the whole Z window (the cold-path bottleneck
                    # on network-attached devices).
                    return projection_ops.project_region_banded(
                        lambda z, y0, h: src.get_region(
                            z, c, ctx.t,
                            RegionDef(0, y0, pixels.size_x, h), 0),
                        ctx.projection, pixels.size_z, start, end, 1,
                        type_max,
                        plane_shape=(pixels.size_y, pixels.size_x),
                        band_rows=band, placement="host")
                return projection_ops.project_planes(
                    lambda z: src.get_region(z, c, ctx.t, full, 0),
                    ctx.projection, pixels.size_z, start, end, 1,
                    type_max, shape=(pixels.size_y, pixels.size_x),
                    placement="host")

        # Full-plane f32 entries can dwarf the raw tiles the shared HBM
        # cache exists for; cache a projection only when it fits well
        # within the budget, so one WSI plane cannot flush the pan/zoom
        # hot set.
        cache = self.s.raw_cache
        plane_bytes = pixels.size_x * pixels.size_y * 4
        cacheable = (cache is not None
                     and plane_bytes <= cache.max_bytes // 8)

        def run():
            import jax.numpy as jnp
            out = []
            for c in active:
                if cacheable:
                    key = ("proj", ctx.image_id, ctx.t, c,
                           int(ctx.projection), start, end)
                    out.append(cache.get_or_load(
                        key, lambda c=c: project_one(c)))
                else:
                    out.append(project_one(c))
            # Stays device-resident: the projected planes feed straight
            # into the render/JPEG dispatch (the batcher stacks on device
            # when members are resident), so full-plane f32 pixels never
            # cross the host link between the two stages.
            return jnp.stack(out)

        raw = await asyncio.to_thread(run)
        return raw, full


def _default_rdef(pixels: Pixels) -> RenderingDef:
    from ..models.rendering import default_rendering_def
    return default_rendering_def(pixels)


class ShapeMaskHandler:
    """Mask pipeline (``ShapeMaskVerticle.java:67-155`` +
    ``ShapeMaskRequestHandler.java``).

    ``device_masks=True`` routes rasterization through the renderer's
    batched mask group path (``BatchingRenderer.rasterize_mask``) when
    the wired renderer has one — same-shape masks coalesce into one
    device dispatch.  The PNG tail is shared with the host path, and
    the device kernel reproduces the host unpack/flip bit-for-bit, so
    the served bytes are IDENTICAL either way (the PR 20 parity
    contract); a renderer without the group path (plain ``Renderer``,
    fleet router) silently keeps the host rasterizer."""

    def __init__(self, services: ImageRegionServices,
                 device_masks: bool = False):
        self.s = services
        self.device_masks = device_masks

    async def cached_shape_mask(self, ctx: ShapeMaskCtx
                                ) -> Optional[bytes]:
        """Byte-cache probe + per-caller ACL — the hit branch alone,
        exposed so the app's fairness gate can put mask cache hits on
        the tile route's footing (already-rendered bytes never cost a
        session token and never shed).  None = miss or unreadable
        (the render path then decides 404 vs render)."""
        from ..services.cache import get_with_tier
        from ..utils import provenance
        t0 = time.perf_counter()
        cached, cache_tier = await get_with_tier(
            self.s.caches.shape_mask, ctx.cache_key())
        if cached is None or not await self._can_read(ctx):
            return None
        telemetry.record_span(
            "cache.hit", t0, (time.perf_counter() - t0) * 1000.0)
        provenance.mark(ctx, tier=("disk" if cache_tier == "disk"
                                   else "byte_cache"))
        return cached

    async def render_shape_mask(self, ctx: ShapeMaskCtx) -> bytes:
        cached = await self.cached_shape_mask(ctx)
        if cached is not None:
            return cached
        if not await self._can_read(ctx):
            raise NotFoundError(f"Cannot find Shape:{ctx.shape_id}")

        t0 = time.perf_counter()
        try:
            mask = await self.s.metadata.get_mask(ctx.shape_id,
                                                  ctx.omero_session_key)
        finally:
            record_since("getMask", t0)
        if mask is None:
            raise NotFoundError(f"Cannot find Shape:{ctx.shape_id}")

        color = None
        if ctx.color is not None:
            color = split_html_color(ctx.color)
            if color is None:
                raise BadRequestError(f"Invalid color '{ctx.color}'")

        t0 = time.perf_counter()
        try:
            rasterize = (getattr(self.s.renderer, "rasterize_mask", None)
                         if self.device_masks else None)
            if rasterize is not None:
                png = await self._render_device(mask, color, ctx,
                                                rasterize)
                telemetry.WORKLOADS.count_request("mask_device")
            else:
                png = await asyncio.to_thread(self._render, mask, color,
                                              ctx)
                telemetry.WORKLOADS.count_request("mask_host")
        finally:
            record_since("renderShapeMask", t0)

        # Cached only under an explicit color, as the reference: a cached
        # default-color PNG would mask later changes to the stored fill
        # (``ShapeMaskVerticle.java:140-148``).
        if ctx.color is not None:
            await self.s.caches.shape_mask.set(ctx.cache_key(), png)
        return png

    async def _can_read(self, ctx: ShapeMaskCtx) -> bool:
        return await check_can_read(self.s, "Mask", ctx.shape_id,
                                    ctx.omero_session_key)

    def _render(self, mask, color, ctx: ShapeMaskCtx) -> bytes:
        from ..ops.maskops import rasterize_mask
        grid, palette = rasterize_mask(
            mask, color, ctx.flip_horizontal, ctx.flip_vertical)
        return codecs.encode_mask_png(grid, tuple(palette[1]))

    async def _render_device(self, mask, color, ctx: ShapeMaskCtx,
                             rasterize) -> bytes:
        """Batched device rasterization: validate + normalize the packed
        payload on host (the host path's exact checks), one awaited
        group dispatch for the grid, then the IDENTICAL PNG tail."""
        from ..ops.maskops import pack_mask_payload
        fill = mask.resolved_fill_color(color)
        packed = pack_mask_payload(mask.bytes_, mask.width, mask.height)
        grid = await rasterize(packed, mask.width, mask.height,
                               ctx.flip_horizontal, ctx.flip_vertical)
        return await asyncio.to_thread(
            codecs.encode_mask_png, grid, tuple(fill))


# Animation wire framing: each frame leaves as a tiny length-prefixed
# record inside the HTTP chunked body, so a scrubbing client can carve
# frame boundaries without guessing at encoder byte counts.
ANIMATION_FRAME_MAGIC = b"FRME"


def frame_record(body: bytes) -> bytes:
    """``FRME`` + u32be length + encoded frame bytes."""
    return (ANIMATION_FRAME_MAGIC
            + len(body).to_bytes(4, "big") + body)


class WorkloadsHandler:
    """The PR 20 device-workloads endpoints that compose the image and
    mask pipelines: overlay composites (region render + device mask
    blend in one pass) and z/t animation strips (a frame range rendered
    as ONE batched device job, streamed in order).

    Owns no pixels/caches of its own — it drives the SAME handlers the
    plain routes use, so every identity, ACL, provenance, and QoS rule
    those paths enforce holds here too."""

    def __init__(self, image_handler, services: ImageRegionServices,
                 max_frames: int = 64):
        self.image_handler = image_handler
        self.s = services
        self.max_frames = max_frames

    # ------------------------------------------------------------ overlay

    async def render_overlay(self, ctx: ImageRegionCtx,
                             shape_ids: Sequence[int],
                             color: Optional[str] = None) -> bytes:
        """Region pixels + ROI mask(s) composited on device -> PNG.

        ``ctx`` must already carry ``format="png"`` (the app forces it:
        the base render must be lossless or the composite would bake
        JPEG artifacts under the mask).  Masks must match the rendered
        region's size — the endpoint serves same-geometry ROI planes,
        not a general transform engine.  The composite is the exact
        ``ops.maskops.overlay_masks_batch`` integer blend, computed on
        device (``overlay_masks_device``), masks applied in request
        order — the refimpl-golden contract."""
        from ..ops.maskops import (overlay_masks_device,
                                   pack_mask_payload,
                                   rasterize_packed_batch)
        if not shape_ids:
            raise BadRequestError("overlay needs at least one shapeId")
        fill_override = None
        if color is not None:
            fill_override = split_html_color(color)
            if fill_override is None:
                raise BadRequestError(f"Invalid color '{color}'")

        masks = []
        for sid in shape_ids:
            if not await check_can_read(self.s, "Mask", sid,
                                        ctx.omero_session_key):
                raise NotFoundError(f"Cannot find Shape:{sid}")
            t0 = time.perf_counter()
            try:
                mask = await self.s.metadata.get_mask(
                    sid, ctx.omero_session_key)
            finally:
                record_since("getMask", t0)
            if mask is None:
                raise NotFoundError(f"Cannot find Shape:{sid}")
            masks.append(mask)

        base_png = await self.image_handler.render_image_region(ctx)
        base = await asyncio.to_thread(codecs.decode_to_rgba, base_png)

        def composite() -> bytes:
            out = base
            for mask in masks:
                if (mask.height, mask.width) != out.shape[:2]:
                    raise BadRequestError(
                        f"Shape:{mask.shape_id} is "
                        f"{mask.width}x{mask.height}, region is "
                        f"{out.shape[1]}x{out.shape[0]}")
                packed = pack_mask_payload(mask.bytes_, mask.width,
                                           mask.height)
                grid = rasterize_packed_batch(
                    packed[None, :], mask.width, mask.height,
                    ctx.flip_horizontal, ctx.flip_vertical)[0]
                fill = np.array(
                    [mask.resolved_fill_color(fill_override)],
                    dtype=np.uint8)
                out = overlay_masks_device(out[None], grid[None],
                                           fill)[0]
            return codecs.encode_rgba(out, "png")

        t0 = time.perf_counter()
        try:
            body = await asyncio.to_thread(composite)
        finally:
            record_since("renderOverlay", t0)
        telemetry.WORKLOADS.count_request("overlay")
        return body

    # ---------------------------------------------------------- animation

    async def render_animation_stream(self, frame_ctxs:
                                      Sequence[ImageRegionCtx]):
        """Async generator: render a z/t frame range as one batched
        device job, yield length-prefixed frames IN ORDER.

        Every frame's render task starts up front, so the batcher's
        linger window coalesces the strip into grouped device
        dispatches while the client is still reading frame 0 — the
        first frame's latency stays a single-group render, the rest
        hide behind the wire.  Closing the generator (client
        disconnect, deadline) cancels every not-yet-settled frame task:
        remaining device work is abandoned at the dispatch queue, never
        rendered for a viewer that left."""
        if not frame_ctxs:
            raise BadRequestError("animation needs at least one frame")
        if len(frame_ctxs) > self.max_frames:
            raise BadRequestError(
                f"animation of {len(frame_ctxs)} frames exceeds the "
                f"configured cap of {self.max_frames}")
        t0 = time.perf_counter()
        telemetry.WORKLOADS.count_stream()
        telemetry.FLIGHT.record(
            "animation.stream", image=frame_ctxs[0].image_id,
            frames=len(frame_ctxs))
        tasks = [asyncio.ensure_future(
            self.image_handler.render_image_region(fctx))
            for fctx in frame_ctxs]
        served = 0
        try:
            for task in tasks:
                body = await task
                if served == 0:
                    telemetry.WORKLOADS.observe_first_frame_ms(
                        (time.perf_counter() - t0) * 1000.0)
                served += 1
                telemetry.WORKLOADS.count_frames()
                yield frame_record(body)
        finally:
            remaining = [t for t in tasks if not t.done()]
            for t in remaining:
                t.cancel()
            if remaining:
                telemetry.WORKLOADS.count_stream_cancelled()
                telemetry.FLIGHT.record(
                    "animation.cancelled",
                    image=frame_ctxs[0].image_id, served=served,
                    cancelled=len(remaining))
                # Settle the cancellations so no "exception was never
                # retrieved" noise outlives the stream.
                await asyncio.gather(*remaining,
                                     return_exceptions=True)
