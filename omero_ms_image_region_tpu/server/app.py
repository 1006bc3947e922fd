"""The HTTP service (≙ ``ImageRegionMicroserviceVerticle``).

Routes, response mapping and the OPTIONS feature document mirror the
reference exactly (``ImageRegionMicroserviceVerticle.java:186-231`` routes,
``:263-284`` details, ``:294-352`` image responses, ``:362-400`` masks):

  OPTIONS *                                                  -> details JSON
  GET /webgateway/render_image_region/{imageId}/{theZ}/{theT}
  GET /webgateway/render_image/{imageId}/{theZ}/{theT}
  GET /webclient/render_image_region/{imageId}/{theZ}/{theT}
  GET /webclient/render_image/{imageId}/{theZ}/{theT}
  GET /webgateway/render_shape_mask/{shapeId}

Status mapping: parameter errors 400 with the message as body, missing or
unreadable objects 404 (empty body), anything else 500 (empty body) — the
reference's ReplyException failure-code propagation
(``ImageRegionVerticle.java:163-188``).
"""

from __future__ import annotations

import json
import logging
from typing import Optional

from aiohttp import web

from .. import __version__, codecs
from ..io.service import PixelsService, derive_max_open
from ..ops.lut import LutProvider
from ..services.cache import Caches
from ..services.metadata import CanReadMemo, LocalMetadataService
from ..services.sessions import (DjangoRedisSessionStore, SessionStore,
                                 StaticSessionStore, resolve_session_key)
from ..utils import provenance, telemetry
from .config import AppConfig
from .ctx import BadRequestError, ImageRegionCtx, ShapeMaskCtx
from .errors import NotFoundError

# NOTE: .handler and .batcher are imported lazily (inside
# build_services / the combined-mode branch) — they pull in the JAX
# device stack, and `--role frontend` processes must stay device-free so
# they restart in milliseconds.

log = logging.getLogger("omero_ms_image_region_tpu.server")
access_log = logging.getLogger("omero_ms_image_region_tpu.access")

PROVIDER = "ImageRegionMicroservice"
FEATURES = ["flip", "mask-color", "png-tiles"]

SERVICES_KEY = web.AppKey("services", object)
CONFIG_KEY = web.AppKey("config", object)
FLEET_ROUTER_KEY = web.AppKey("fleet_router", object)
_ROBUSTNESS_TASKS_KEY = web.AppKey("robustness_tasks", list)


def _session_required(config: AppConfig) -> bool:
    """Reject-by-default for real stores; the standalone ACL-only
    posture (static/no store) must opt in explicitly
    (≙ the reference's mandatory session handler,
    ``ImageRegionMicroserviceVerticle.java:199-212``)."""
    if config.session_store_required is not None:
        return config.session_store_required
    return config.session_store_type in ("redis", "postgres")


def _make_session_store(config: AppConfig) -> Optional[SessionStore]:
    required = _session_required(config)

    def unavailable(msg: str) -> None:
        # With enforcement on, a config whose session store cannot be
        # built must refuse to start (the reference throws;
        # ImageRegionMicroserviceVerticle.java:199-212) — silently
        # serving 403s for every request helps nobody.
        if required:
            raise ValueError(f"session enforcement is on but {msg}")
        log.warning("%s; sessions disabled", msg)

    if config.session_store_type == "redis":
        if not config.session_store_uri:
            unavailable("session-store.type is 'redis' with no uri")
            return None
        try:
            return DjangoRedisSessionStore(config.session_store_uri)
        except ImportError:
            unavailable("the redis package is unavailable")
            return None
    if config.session_store_type == "static":
        return StaticSessionStore(accept_all=True)
    if config.session_store_type not in (None, "postgres"):
        # Typo'd types must not silently serve anonymously
        # (the reference throws on invalid types too).
        raise ValueError(f"invalid session-store.type "
                         f"{config.session_store_type!r} (expected "
                         f"redis | postgres | static)")
    if config.session_store_type == "postgres":
        if not config.session_store_uri:
            unavailable("session-store.type is 'postgres' with no uri")
            return None
        try:
            from ..services.sessions import DjangoPostgresSessionStore
            return DjangoPostgresSessionStore(config.session_store_uri)
        except ImportError:
            unavailable("no async postgres driver (asyncpg/psycopg) "
                        "is available")
            return None
    if required:
        raise ValueError("session-store.required is true but no "
                         "session-store.type is configured")
    return None


def _session_buckets(config: AppConfig):
    """Per-session fairness token buckets (None when sessions are not
    enabled).  Keyed on ``ctx.omero_session_key`` — the identity the
    session middleware resolves and the fleet single-flight folds;
    deliberately NO second session-resolution path."""
    if not config.sessions.enabled:
        return None
    from .admission import SessionTokenBuckets
    return SessionTokenBuckets(
        refill_per_s=config.sessions.bucket_refill_per_s,
        burst=config.sessions.bucket_burst,
        max_sessions=config.sessions.max_tracked,
        bulk_cost=(config.qos.bulk_cost if config.qos.enabled
                   else 1.0))


def _install_fault_injection(config: AppConfig) -> None:
    """Arm the seeded chaos layer when the config asks for it.  Guarded
    on the seed so a default config can never clobber an injector a
    test installed directly."""
    if config.fault_injection.seed is not None:
        from ..utils import faultinject
        faultinject.install(config.fault_injection)


def build_services(config: AppConfig) -> "ImageRegionServices":
    """Construct the full render service stack for one device-owning
    process (shared by the in-process app and the render sidecar)."""
    # Mechanical XLA compile accounting (count + cumulative ms on
    # /metrics): a serving shape missed by prewarm shows up as a
    # compile event with a seconds-scale duration.  Installed before
    # anything can compile.
    telemetry.install_compile_listener()
    # Every stopwatch span of this process is also an annotation in a
    # /debug/profile capture, on the device planes' clock.
    from ..utils.stopwatch import install_annotations
    install_annotations()
    telemetry.FLIGHT.configure(config.telemetry.flight_recorder_events)
    _install_fault_injection(config)
    # Warm restarts: compiled executables persist across processes.
    # Placed before anything compiles, and from outside when the
    # operator says where (utils.jaxenv).  With persistence on, this
    # cache is the FALLBACK under the serialized-executable tier
    # (server.execcache).
    from ..utils import jaxenv
    cache_dir = jaxenv.place_compilation_cache(
        config.renderer.compilation_cache_dir)
    if config.persistence.enabled and not config.caches.disk_dir:
        # Durable byte tier: slot the disk cache into every named
        # cache's chain (between memory and Redis) so rendered bytes
        # survive process death with no external dependency.
        import os as _os
        config.caches.disk_dir = _os.path.join(
            config.persistence.dir, "bytecache")
        config.caches.disk_max_bytes = \
            config.persistence.disk_cache_max_bytes
    from .batcher import BatchingRenderer, batcher_knobs, build_renderer
    from .handler import ImageRegionServices
    renderer = None
    if config.parallel.enabled:
        # Mesh-sharded serving (≙ the reference's -cluster mode):
        # groups dispatch through the (data, chan) mesh steps.
        from ..parallel import cluster
        from ..parallel.serve import MeshRenderer
        cluster.initialize(
            coordinator_address=config.parallel.coordinator_address,
            num_processes=config.parallel.num_processes,
            process_id=config.parallel.process_id)
        import jax
        if jax.process_count() > 1:
            from ..utils import faultinject
            if faultinject.active() is not None:
                # Chaos on one pod process stalls/re-launches ITS SPMD
                # lockstep sequence only and hangs the slice; config
                # load rejects explicit multi-host + seed, and this
                # disarms the auto-discovered-pod case.
                log.warning("multi-host pod: disarming fault "
                            "injection (chaos would diverge SPMD "
                            "lockstep)")
                faultinject.uninstall()
        if jax.process_count() > 1 and jax.process_index() != 0:
            raise ValueError(
                "mesh-serving leader must be process 0 of the pod; "
                "run the other processes with --role pod-worker")
        mesh = cluster.global_mesh(
            chan_parallel=config.parallel.chan_parallel,
            n_devices=config.parallel.n_devices)
        log.info("mesh serving enabled: %s (jpeg engine %s)",
                 dict(mesh.shape), config.renderer.jpeg_engine)
        renderer = MeshRenderer(mesh, **batcher_knobs(config))
    renderer, raw_cache = build_renderer(config, renderer=renderer)
    # Say ONCE what this process serves from, and refuse the CPU
    # backend unless JAX_PLATFORMS asked for it: a server that found
    # no chip must not look like one that did.  The same document
    # rides /readyz and the sidecar's ping.
    device = jaxenv.device_identity(
        renderer.mesh.devices.flat if config.parallel.enabled else None)
    from .. import native
    native_status = native.status()
    log.info("device: platform=%s kind=%s count=%d ids=%s; entropy "
             "coder: %s; tile cache: %s; compile cache: %s",
             device["platform"], device["kind"], device["count"],
             device["ids"], native_status["entropy_coder"],
             native_status["tile_cache"], cache_dir)
    caches = Caches.from_config(config.caches)
    if config.caches.redis_uri and caches.redis is None:
        log.warning("redis package unavailable; redis cache tier and "
                    "shared canRead memo disabled")
    services = ImageRegionServices(
        pixels_service=PixelsService(config.data_dir,
                                     max_open=derive_max_open(),
                                     repo_root=config.omero_data_dir),
        metadata=LocalMetadataService(config.data_dir),
        caches=caches,
        # The canRead memo's shared tier plays the reference's
        # Hazelcast distributed-map role across service instances; it
        # rides the caches' one Redis client
        # (ImageRegionVerticle.java:107-111).
        can_read_memo=CanReadMemo(shared=caches.redis),
        renderer=renderer,
        lut_provider=LutProvider(config.lut_root),
        max_tile_length=config.max_tile_length,
        cpu_fallback_max_px=config.renderer.cpu_fallback_max_px,
        # HBM-resident raw tile tier: settings changes re-render hot
        # tiles without re-crossing the host link.  The digest index
        # makes it content-addressed: planes resident under any key
        # (wire pushes included) are never re-shipped.
        raw_cache=raw_cache,
        device=device,
        native=native_status,
    )
    if config.single_flight:
        # In-flight render dedup: concurrent identical requests
        # coalesce onto one pipeline run (server.handler.SingleFlight).
        from .handler import SingleFlight
        services.single_flight = SingleFlight()
    if config.fault_tolerance.admission_max_queue > 0:
        # Bounded admission in front of the batcher: overload sheds
        # with 503 + Retry-After instead of queueing toward a timeout;
        # with sessions enabled, per-session token buckets shed a
        # hostile session ("fairness") before the global bound bites.
        from .admission import AdmissionController
        services.admission = AdmissionController(
            config.fault_tolerance.admission_max_queue,
            renderer=renderer,
            retry_after_s=config.fault_tolerance.shed_retry_after_s,
            session_buckets=_session_buckets(config))
    if services.raw_cache is not None and config.raw_cache.prefetch:
        from ..services.prefetch import TilePrefetcher
        viewport = None
        if config.sessions.enabled:
            # Session viewport model: per-session pan/zoom
            # trajectories drive PREDICTED-tile prefetch (falls back
            # to lattice neighbors for trajectory-less sessions).
            # Gated on sessions.enabled: without the session
            # middleware every request is anonymous, and one SHARED
            # trajectory interleaving unrelated viewers would predict
            # garbage while suppressing the lattice fallback.
            from ..services.viewport import ViewportTracker
            viewport = ViewportTracker(
                max_sessions=config.sessions.max_tracked)
        services.prefetcher = TilePrefetcher(
            services.raw_cache, viewport=viewport,
            lookahead=config.sessions.prefetch_lookahead)
    exec_cache = None
    if config.persistence.enabled:
        import os as _os
        if (config.persistence.executables
                and isinstance(renderer, BatchingRenderer)
                and not config.parallel.enabled):
            # Serialized compiled-program tier.  Batched single-host
            # posture only: mesh-sharded programs are topology-bound
            # and stay on the pod's lockstep compile path.
            from .execcache import ExecutableCache
            exec_cache = ExecutableCache(
                _os.path.join(config.persistence.dir, "executables"))
            renderer.exec_cache = exec_cache
        # Snapshot/rehydrate engine: periodic (+ SIGTERM, through the
        # shutdown chain) manifest of the hot state; a background
        # rehydrator replays it on boot — disk->memory byte promote,
        # HBM plane re-stage, executable deserialize.
        from ..services.warmstate import WarmStateManager
        services.warmstate = WarmStateManager(
            config.persistence.dir, services,
            snapshot_interval_s=config.persistence.snapshot_interval_s,
            snapshot_top_k=config.persistence.snapshot_top_k,
            max_plane_entries=config.persistence.max_plane_entries,
            rehydrate_concurrency=(
                config.persistence.rehydrate_concurrency))
        services.warmstate.start(
            rehydrate=config.persistence.rehydrate)
    if not _local_fleet(config):
        # An in-process fleet is warmed by create_app once its members
        # hold their devices.
        start_prewarm(config, renderer)
    return services


def _local_fleet(config: AppConfig) -> bool:
    """Does this process serve an in-process fleet (create_app builds
    its members over the base stack: ``fleet`` or ``federation`` in
    the combined role)?"""
    return ((config.fleet.enabled or config.federation.enabled)
            and config.sidecar.role == "combined")


def start_prewarm(config: AppConfig, renderer, members=None) -> None:
    """Compile the listed shapes' serving programs so the first
    request of each shape doesn't pay 20-40 s of jit; an in-process
    fleet's ``members`` (``(name, device)``) each on its own device.
    MeshRenderer is excluded: its sharded steps are warmed by the pod
    bring-up dryrun instead.

    On a BACKGROUND thread, flagged in telemetry.READINESS: the
    listener binds immediately and /readyz answers 503 until the
    compiles land, so orchestration (the systemd ExecStartPost poll,
    k8s readiness probes) gates traffic on warm — instead of minutes
    of connection-refused during a blocking prewarm that no probe
    could distinguish from a hung boot."""
    if not (config.renderer.prewarm and config.batcher.enabled
            and not config.parallel.enabled):
        return
    import threading

    from .prewarm import parse_spec, prewarm_renderer
    for spec in config.renderer.prewarm:
        parse_spec(spec)   # malformed specs fail the BOOT, loudly —
        # never a background thread dying into a silently-unwarmed
        # "ready" service (YAML loads validate too; this covers
        # programmatic AppConfigs).
    telemetry.READINESS.prewarm_pending = True
    threading.Thread(
        target=prewarm_renderer,
        args=(list(config.renderer.prewarm), renderer.jpeg_engine,
              renderer.max_batch, renderer.buckets),
        kwargs={"cpu_fallback_max_px":
                config.renderer.cpu_fallback_max_px,
                # Persistence: warmed packed programs deserialize
                # from a prior life instead of compiling, and
                # fresh compiles are serialized for the next one.
                "exec_cache": getattr(renderer, "exec_cache", None),
                "members": members},
        name="prewarm", daemon=True).start()


def create_app(config: Optional[AppConfig] = None,
               services: Optional["ImageRegionServices"] = None
               ) -> web.Application:
    """Build the application; ``services`` injection is the test seam.

    With ``sidecar.socket`` configured and role ``frontend``, the app
    builds NO device-side services: render requests forward over the
    sidecar socket (unix path, or ``host:port`` TCP for cross-host
    frontends) to the shared sidecar process (the reference's
    event-bus seam, ``ImageRegionVerticle.java:128-136``)."""
    config = config or AppConfig()

    # Forensics layer: size the black-box ring, and declare the SLOs.
    # A breach TRANSITION dumps the flight recorder — the black box
    # snapshots exactly when the objective says things fell over.
    telemetry.FLIGHT.configure(config.telemetry.flight_recorder_events)

    def _on_slo_breach(objective: str, fast: float,
                       slow: float) -> None:
        telemetry.FLIGHT.record("slo.breach", objective=objective,
                                fast=round(fast, 2),
                                slow=round(slow, 2))
        path = telemetry.FLIGHT.dump(
            config.telemetry.flight_recorder_dir,
            f"slo-{objective}")
        log.warning("SLO breach on %s (burn %.1f fast / %.1f slow); "
                    "flight recorder dumped to %s", objective, fast,
                    slow, path)

    telemetry.SLO.configure(
        availability_target=config.slo.availability_target,
        latency_ms=config.slo.latency_ms,
        latency_target=config.slo.latency_target,
        fast_window_s=config.slo.fast_window_s,
        slow_window_s=config.slo.slow_window_s,
        breach_burn_rate=config.slo.breach_burn_rate,
        on_breach=_on_slo_breach)

    # Control-plane decision ledger (``decisions:`` config block):
    # autoscaler verdicts, epoch rolls, manifest agreement, gossip
    # convergence and drain lifecycle land in one bounded ring
    # (/debug/decisions) + optional JSONL spool.
    from ..utils import decisions as decisions_mod
    decisions_mod.LEDGER.configure(
        ring_size=config.decisions.ring_size,
        spool_dir=config.decisions.spool_dir or None,
        outcome_horizon_ticks=config.decisions.outcome_horizon_ticks)

    fleet_router = None
    fleet_members: list = []
    federation_coord = None
    unit_lifecycle = None
    fleet_remote = (services is None and config.fleet.enabled
                    and config.fleet.sockets
                    and config.sidecar.role == "frontend")
    proxy_mode = (services is None and config.sidecar.socket
                  and config.sidecar.role == "frontend"
                  and not fleet_remote)

    if config.http_cache.enabled \
            and config.http_cache.epoch == "auto":
        # ``http-cache.epoch: auto``: derive the deployment epoch
        # from the data tree's ingest/source mtimes ONCE at startup
        # (re-ingesting any image bumps it on the next boot/roll); an
        # explicit operator value skips this entirely.  A derivation
        # that found NOTHING on a device-free frontend is a config
        # error, not a silent "0": the frontend is exactly where the
        # ETags are emitted, and a never-bumping auto epoch would
        # keep edge caches 304-confirming stale renders forever —
        # the failure the knob exists to prevent.
        from . import httpcache as _hc
        derived = _hc.derive_epoch(config.data_dir)
        if derived == "0" and (fleet_remote or proxy_mode):
            raise ValueError(
                "http-cache.epoch: auto found no ingest stamps under "
                f"data-dir {config.data_dir!r} — device-free "
                "frontends have no local source tree; set an "
                "explicit epoch (or mount the data tree read-only)")
        if derived == "0":
            log.warning("http-cache.epoch: auto derived '0' (no "
                        "ingest stamps under %r) — epoch bumps will "
                        "not happen until images exist",
                        config.data_dir)
        config.http_cache.epoch = derived
        log.info("http-cache.epoch: auto -> %r", derived)

    def _sidecar_client(socket_path: str):
        from ..utils.transient import CircuitBreaker, RetryPolicy
        from .sidecar import SidecarClient
        ft = config.fault_tolerance
        return SidecarClient(
            socket_path,
            breaker=CircuitBreaker(
                failure_threshold=ft.breaker_failure_threshold,
                reset_after_s=ft.breaker_reset_s),
            retry=RetryPolicy(
                max_attempts=ft.retry_max_attempts,
                base_backoff_s=ft.retry_base_backoff_ms / 1000.0,
                max_backoff_s=ft.retry_max_backoff_ms / 1000.0),
            # Wire v3 knobs: coalescing bounds, shm-ring sizing,
            # chunk streaming (deploy/DEPLOY.md "Wire transport").
            wire=config.wire)

    if fleet_remote:
        # Data-parallel sidecar fleet (deploy/DEPLOY.md "Fleet
        # serving"): one SidecarClient per member, consistent-hash
        # routing of plane identities so each sidecar's HBM cache
        # holds its shard, fleet-wide single-flight + admission above
        # the router, hash-ring-next failover on member death.
        from ..parallel.fleet import (FleetImageHandler, FleetRouter,
                                      RemoteMember)
        from .sidecar import SidecarMaskHandler
        _install_fault_injection(config)
        fleet_members = [
            RemoteMember(f"m{i}", _sidecar_client(sock),
                         down_cooldown_s=config.fleet.down_cooldown_s)
            for i, sock in enumerate(config.fleet.sockets)]
        fleet_router = FleetRouter(
            fleet_members, lane_width=config.fleet.lane_width,
            steal_min_backlog=config.fleet.steal_min_backlog,
            hash_replicas=config.fleet.hash_replicas,
            failover=config.fleet.failover,
            qos_weight=(config.qos.interactive_weight
                        if config.qos.enabled else 0),
            peer_fetch=(config.http_cache.enabled
                        and config.http_cache.peer_fetch),
            peer_timeout_s=config.http_cache.peer_timeout_ms / 1000.0,
            hotkey=config.hotkey)
        single_flight = None
        if config.single_flight:
            from .singleflight import SingleFlight
            single_flight = SingleFlight()
        admission = None
        if config.fault_tolerance.admission_max_queue > 0:
            from .admission import AdmissionController
            admission = AdmissionController(
                config.fault_tolerance.admission_max_queue,
                renderer=fleet_router,
                retry_after_s=config.fault_tolerance.shed_retry_after_s,
                session_buckets=_session_buckets(config))
        fallback = None
        if config.fault_tolerance.degraded_mode:
            from .degraded import DegradedCpuHandler
            fallback = DegradedCpuHandler(config)
        image_handler = FleetImageHandler(
            fleet_router, single_flight=single_flight,
            admission=admission, fallback=fallback)
        # Masks and the merged sidecar surfaces (/metrics,
        # /debug/*, readiness ping) ride the FIRST member — the
        # designated member, like the multi-frontend scrape note.
        client = fleet_members[0].client
        mask_handler = SidecarMaskHandler(client, fallback=fallback)
        services = None
    elif proxy_mode:
        from .sidecar import SidecarImageHandler, SidecarMaskHandler
        _install_fault_injection(config)
        client = _sidecar_client(config.sidecar.socket)
        fallback = None
        if config.fault_tolerance.degraded_mode:
            # Graceful degradation: while the device backend is down,
            # tiles render on this process's CPU reference path
            # (server.degraded — jax-free) at reduced rate.
            from .degraded import DegradedCpuHandler
            fallback = DegradedCpuHandler(config)
        image_handler = SidecarImageHandler(client, fallback=fallback)
        mask_handler = SidecarMaskHandler(client, fallback=fallback)
        services = None
    else:
        from .handler import ImageRegionHandler, ShapeMaskHandler
        injected = services is not None
        if services is None:
            services = build_services(config)
        if _local_fleet(config) and not injected:
            # In-process device fleet: member 0 is the base stack
            # (the lockstep mesh lane in mesh deployments); members
            # 1..N-1 own their renderer + DeviceRawCache shard; each
            # member holds a chip of its own where the host has one.
            # Single-flight and admission MOVE above the router so
            # identical renders coalesce once fleet-wide and shedding
            # sees the fleet's total depth.
            from ..parallel.fleet import (FleetImageHandler,
                                          FleetRouter,
                                          build_local_members,
                                          partition_local_devices)
            ring_seed = ""
            wire_handoff = False
            if config.federation.enabled:
                # Cross-host federation (deploy/DEPLOY.md "Multi-host
                # federation"): the member list comes from the agreed
                # MANIFEST — members on this host build in-process
                # with per-member device pinning, the rest are
                # RemoteMember handles over their sidecar addresses.
                # The ring seed/replicas ride the manifest, so every
                # agreeing host computes identical shard assignments.
                from ..parallel import federation as federation_mod
                fed_manifest = federation_mod.FleetManifest \
                    .from_config(config.federation)
                federation_mod.install(fed_manifest,
                                       self_host=config.federation.host)
                fleet_members = federation_mod.build_federated_members(
                    config, services, fed_manifest, _sidecar_client,
                    config.federation.host)
                ring_seed = fed_manifest.ring_seed
                wire_handoff = True
            else:
                fed_manifest = None
                # One chip a member, as far as the host's go.
                fleet_members = build_local_members(
                    config, services, config.fleet.members,
                    device_sets=partition_local_devices(
                        config.fleet.members))
            local = [m for m in fleet_members
                     if not getattr(m, "remote", False)]
            if services.device is not None:
                # /readyz: which chip each member holds.
                services.device = dict(services.device, members={
                    m.name: telemetry.pinned_device_id(m.services)
                    for m in local})
            start_prewarm(config, services.renderer, members=[
                (m.name, m.services.pin_device) for m in local])
            fleet_router = FleetRouter(
                fleet_members, lane_width=config.fleet.lane_width,
                steal_min_backlog=config.fleet.steal_min_backlog,
                hash_replicas=(config.federation.hash_replicas
                               if fed_manifest is not None
                               else config.fleet.hash_replicas),
                failover=config.fleet.failover,
                qos_weight=(config.qos.interactive_weight
                            if config.qos.enabled else 0),
                peer_fetch=(config.http_cache.enabled
                            and config.http_cache.peer_fetch),
                peer_timeout_s=(
                    config.http_cache.peer_timeout_ms / 1000.0),
                ring_seed=ring_seed, wire_handoff=wire_handoff,
                hotkey=config.hotkey)
            if fed_manifest is not None:
                from ..parallel import federation as federation_mod
                from ..parallel.federation import FederationCoordinator
                if config.federation.quorum:
                    # Quorum membership: this host's OWN failure
                    # detector over the manifest hosts — a minority
                    # island fences itself (deploy/DEPLOY.md
                    # "Partitions & quorum").
                    federation_mod.install_quorum(
                        federation_mod.QuorumTracker(
                            fed_manifest,
                            self_host=config.federation.host,
                            suspect_after_s=(
                                config.federation.suspect_after_s)))
                # Orchestrated epoch rolls: the router swaps its ring
                # ONLY at commit (activate_manifest), never mid-flight.
                federation_mod.set_roll_hook(
                    fleet_router.apply_manifest)
                federation_coord = FederationCoordinator(
                    fed_manifest, config.federation.host,
                    fleet_router,
                    gossip_interval_s=(
                        config.federation.gossip_interval_s))
            single_flight = services.single_flight
            services.single_flight = None
            services.admission = None
            admission = None
            if config.fault_tolerance.admission_max_queue > 0:
                from .admission import AdmissionController
                admission = AdmissionController(
                    config.fault_tolerance.admission_max_queue,
                    renderer=fleet_router,
                    retry_after_s=(
                        config.fault_tolerance.shed_retry_after_s),
                    session_buckets=_session_buckets(config))
            if services.prefetcher is not None:
                # Fleet-aware prefetch: ONE shared prefetcher (and
                # viewport model) across every member — predictions
                # route by plane_route_key to the OWNING member's HBM
                # shard, so speculative staging warms the member that
                # will serve the request and never duplicates planes.
                services.prefetcher.cache_for_route = \
                    fleet_router.cache_for_route
                if federation_coord is not None:
                    # Shard-aware prefetch, cross-host seam: a
                    # predicted plane owned by a REMOTE member stages
                    # on ITS owner's host (a prestage hint over the
                    # wire) instead of this host's wrong shard.
                    services.prefetcher.remote_prestage = \
                        fleet_router.remote_prestage_for_route
                # Hot-route predictions warm every LOCAL replica
                # shard, not just the ring owner's — a balanced read
                # on a cold replica would re-read from disk.
                services.prefetcher.replica_caches = \
                    fleet_router.local_replica_caches
                for member in fleet_members[1:]:
                    if getattr(member, "services", None) is not None \
                            and member.services is not services:
                        member.services.prefetcher = \
                            services.prefetcher
            image_handler = FleetImageHandler(
                fleet_router, single_flight=single_flight,
                admission=admission, base_services=services)
        else:
            image_handler = ImageRegionHandler(services)
        mask_handler = ShapeMaskHandler(
            services, device_masks=config.workloads.device_masks)

    # Device workloads plane (deploy/DEPLOY.md "Device workloads"):
    # overlay composites + animation strips compose the SAME image
    # handler the plain routes run, and the pyramid job subsystem
    # builds NGFF levels in the background over the bulk QoS class.
    # Combined role only — a proxy frontend's sidecars own the device.
    workloads_handler = None
    jobs_manager = None
    if services is not None:
        if config.workloads.overlay_enabled \
                or config.workloads.animation_enabled:
            from .handler import WorkloadsHandler
            workloads_handler = WorkloadsHandler(
                image_handler, services,
                max_frames=config.workloads.animation_max_frames)
        if config.pyramid.enabled:
            from .jobs import PyramidJobManager
            jobs_manager = PyramidJobManager(
                pixels_service=services.pixels_service,
                chunk=(config.pyramid.chunk, config.pyramid.chunk),
                min_level_size=config.pyramid.min_level_size,
                compressor=(None
                            if config.pyramid.compressor == "none"
                            else config.pyramid.compressor),
                defer_poll_s=config.pyramid.defer_poll_s)

    # Self-preservation layer (deploy/DEPLOY.md "Overload & rolling
    # restarts"): the pressure governor + brownout ladder and the
    # stuck-lane/hung-wire watchdog.  Built synchronously here (the
    # governor installs module-global so admission/handler hooks see
    # it); their tick loops start as tasks in on_startup.
    from . import pressure as pressure_mod
    from ..utils.stopwatch import LoopLagSampler
    # The event loop's lag, sampled whether the governor is on or off
    # (span ``loop.lag``); its task starts in on_startup.
    loop_lag = LoopLagSampler()
    governor = None
    if config.pressure.enabled:
        # Host-RSS watermarks default from the cgroup memory limit
        # (v2 memory.max, v1 fallback) when the knob is unset — a
        # containerized deploy gets RSS brownouts with zero config;
        # the explicit knob still wins.
        pressure_mod.apply_cgroup_rss_defaults(config.pressure)
        governor = pressure_mod.PressureGovernor(
            config.pressure,
            pressure_mod.build_actuators(config.pressure,
                                         services=services,
                                         router=fleet_router),
            pressure_mod.build_sources(services=services,
                                       router=fleet_router,
                                       loop_lag=loop_lag))
        pressure_mod.install(governor)

    # Live perf-regression sentinel (deploy/DEPLOY.md "Perf
    # sentinel"): always-on quantile baselines + watermark floors +
    # automatic incident bundles.  Installed module-global (the
    # governor idiom) so _finish_request pays one probe when it is
    # off; the tick loop starts in on_startup.
    from . import sentinel as sentinel_mod
    sentinel_engine = None
    if config.sentinel.enabled:
        def _sentinel_flight():
            # The process flight ring IS the fleet view for local
            # members (every member stamps its events into it);
            # remote members' rings stay reachable via
            # /debug/flightrecorder and are named here for the
            # investigator.
            return {
                "member": getattr(config.federation, "host", "")
                or "local",
                "fleet_members": [m.name for m in fleet_members],
                "events": telemetry.FLIGHT.snapshot(),
            }

        sentinel_engine = sentinel_mod.engine_from_config(
            config.sentinel,
            member=(getattr(config.federation, "host", "")
                    or "local"),
            flight_fn=_sentinel_flight)
        sentinel_mod.install(sentinel_engine)

    watchdog = None
    if config.watchdog.enabled:
        from .watchdog import build_watchdog

        def _escalate(event: dict) -> None:
            # The bigger-hammer hook: in split deployments the PR 3
            # supervisor owns restarts, so escalation here is the
            # LOUD record that repeated smallest-scope healing did
            # not hold — the black box + metrics carry it to the
            # operator/orchestrator.
            telemetry.FLIGHT.record("watchdog.escalate", **{
                k: v for k, v in event.items() if k != "escalate"})
            log.error("watchdog escalation: %s on %s",
                      event.get("action"), event.get("target"))

        wd_clients = ([m.client for m in fleet_members]
                      if fleet_remote
                      else ([client] if proxy_mode else []))
        watchdog = build_watchdog(
            config.watchdog,
            renderer=(services.renderer if services is not None
                      else None),
            clients=wd_clients, escalate_cb=_escalate)
        for member in fleet_members:
            # Extra local members own their own batchers — each is a
            # stuck-lane target of its own.
            extra = getattr(getattr(member, "services", None),
                            "renderer", None)
            if (extra is not None and services is not None
                    and extra is not services.renderer
                    and hasattr(extra, "watchdog_scan")):
                extra.watchdog_stall_factor = config.watchdog \
                    .stall_factor
                extra.watchdog_stall_min_s = config.watchdog \
                    .stall_min_s
                extra.watchdog_escalate_after = config.watchdog \
                    .escalate_after
                watchdog.add_target(extra)

    # Elastic autoscaler (deploy/DEPLOY.md "Capacity & autoscaling"):
    # the controller that closes the loop between measured pressure /
    # predicted demand and fleet size — scale-down drains with warm
    # shard handoff (intent=autoscale, so /readyz never reads a
    # routine scale-down as an operator roll), scale-up undrains with
    # pre-stage-back.  Config validation already required a fleet.
    autoscaler = None
    diurnal_estimator = None
    if config.autoscaler.enabled and fleet_router is not None:
        from .autoscaler import Autoscaler

        demand_source = None
        if config.autoscaler.lane_capacity_tps > 0 \
                and config.sessions.enabled:
            if config.autoscaler.diurnal_period_s > 0:
                # Diurnal-phase demand prediction: a harmonic fit
                # over OBSERVED request arrivals (fed by
                # _finish_request below) scales the session-model
                # demand by where "now + horizon" sits in the fitted
                # day — the controller provisions for the demand a
                # scale op completes INTO, not the demand at tick
                # time.  Unfit (cold boot, flat day) multiplies by 1.
                from ..services.loadmodel import DiurnalEstimator
                diurnal_estimator = DiurnalEstimator(
                    period_s=config.autoscaler.diurnal_period_s)

            # The session model's predicted demand: viewport-tracked
            # live sessions x the calibrated per-session steady rate,
            # diurnal-scaled when the estimator has a fit.
            def demand_source() -> float:
                demand = (telemetry.SESSIONS.tracked
                          * config.autoscaler.session_tps)
                if diurnal_estimator is not None:
                    demand *= diurnal_estimator.multiplier(
                        horizon_s=config.autoscaler.diurnal_horizon_s)
                return demand
        if config.autoscaler.unit_config and fleet_remote:
            # Sidecar-unit process lifecycle: the autoscaler actually
            # STOPS a parked member's process and RESTARTS it on
            # scale-up, instead of parking warm pre-provisioned
            # members (PR 13 follow-on).  Units spawn in the startup
            # hook; /readyz holds traffic until their sockets accept.
            from .sidecar import SidecarUnitLifecycle
            unit_lifecycle = SidecarUnitLifecycle.for_config(
                config.autoscaler.unit_config,
                {m.name: sock for m, sock in
                 zip(fleet_members, config.fleet.sockets)})
        autoscaler = Autoscaler(
            config.autoscaler, fleet_router, governor=governor,
            demand_source=demand_source,
            lifecycle=unit_lifecycle,
            drain_kwargs={
                "prestage": config.drain.prestage,
                "max_planes": config.drain.prestage_max_planes,
                "settle_timeout_s": config.drain.settle_timeout_s,
            })

    session_store = _make_session_store(config)

    async def session_key(request: web.Request) -> Optional[str]:
        return await resolve_session_key(
            session_store, request.cookies, config.session_cookie_name)

    # Session enforcement (≙ the mandatory OmeroWebSessionRequestHandler,
    # ImageRegionMicroserviceVerticle.java:199-212: requests whose cookie
    # does not resolve are failed before any handler runs).
    session_required = _session_required(config)

    class _NoSession(Exception):
        pass

    async def require_session_key(request: web.Request) -> Optional[str]:
        key = await session_key(request)
        if key is None and session_required:
            raise _NoSession()
        return key

    def _status_of(e: Exception) -> web.Response:
        """Failure-code mapping with the reference's empty 404/500 bodies
        (``ImageRegionMicroserviceVerticle.java:314-323``), extended by
        the fault-tolerance statuses (``server.errors`` documents the
        full contract): shed -> 503 + Retry-After, spent deadline ->
        504.  Never a traceback: unexpected exceptions log server-side
        and answer an empty 500."""
        from .errors import DeadlineExceededError, OverloadedError
        if isinstance(e, BadRequestError):
            return web.Response(status=400, text=str(e))
        if isinstance(e, (NotFoundError, FileNotFoundError)):
            return web.Response(status=404)
        if isinstance(e, OverloadedError):
            # Honoring Retry-After spreads the client retry storm past
            # the congestion (or breaker-reset) window.
            retry_after = max(1, round(e.retry_after_s))
            return web.json_response(
                {"error": str(e)}, status=503,
                headers={"Retry-After": str(retry_after)})
        if isinstance(e, ConnectionError):
            # The render backend is unreachable (connection died
            # through every policy retry).  That is an AVAILABILITY
            # failure, not a server bug: 503 + Retry-After tells the
            # client to come back once the supervisor (or operator)
            # has the sidecar serving again — never a bare 500.
            telemetry.RESILIENCE.count_shed("sidecar-unreachable")
            retry_after = max(1, round(
                config.fault_tolerance.shed_retry_after_s))
            return web.json_response(
                {"error": "render backend unreachable"}, status=503,
                headers={"Retry-After": str(retry_after)})
        if isinstance(e, DeadlineExceededError):
            return web.json_response({"error": str(e)}, status=504)
        from ..utils.transient import is_transient_device_error
        if is_transient_device_error(e):
            # Combined-mode twin of the sidecar's mapping: a transport
            # drop that outlived the group-render retry is weather the
            # client retries through, not a bug — shed class, not 500.
            log.warning("render failed on a transient device "
                        "transport error: %s", e)
            return web.json_response(
                {"error": "transient device transport error"},
                status=503, headers={"Retry-After": "1"})
        log.exception("render failed")
        return web.Response(status=500)

    def _params_of(request: web.Request) -> dict:
        params = dict(request.query)
        params.update(request.match_info)
        # The wildcard route's tail must not reach the ctx: cache keys
        # hash all params, and /7/0/0 vs /7/0/0/ must share a key
        # (and, downstream, one ETag — the edge-cache alias contract).
        params.pop("tail", None)
        return params

    # ---- Conditional HTTP (server.httpcache; deploy/DEPLOY.md "Edge
    # caching"): content-addressed ETags on every image/mask response,
    # If-None-Match -> 304 and HEAD -> headers-only with ZERO render,
    # admission or session-token work, honest Cache-Control/Vary so
    # nginx/CDN edges can absorb repeat viewers safely.
    from . import httpcache

    async def _acl_gated(object_type: str, object_id: int) -> bool:
        """Is this object PRIVATE for edge-cache purposes (not
        anonymously readable)?  Decides ``private`` + ``Vary`` vs
        ``public``.  Combined role probes the memoized ACL with a None
        session; proxy/fleet frontends cannot probe and use the
        session-enforcement posture (enforced sessions => everything
        private).  Errs toward private on any doubt — a wrongly-public
        header is a data leak, a wrongly-private one just a cache-hit-
        rate loss."""
        if not config.http_cache.vary_acl:
            return True
        if services is None:
            return session_required
        from .handler import check_can_read
        try:
            return not await check_can_read(services, object_type,
                                            object_id, None)
        except Exception:
            return True

    async def _cache_headers(headers: dict, identity: str,
                             object_type: str,
                             object_id: int) -> Optional[str]:
        """Stamp ETag/Cache-Control/Vary onto ``headers``; returns the
        ETag (None when conditional HTTP is off — the legacy static
        cache-control-header string then applies, success-only)."""
        hc = config.http_cache
        if not hc.enabled:
            if config.cache_control_header:
                headers["Cache-Control"] = config.cache_control_header
            return None
        etag = httpcache.etag_for(identity, hc.epoch)
        headers["ETag"] = etag
        gated = await _acl_gated(object_type, object_id)
        cc, vary = httpcache.cache_headers(hc.max_age_s, gated)
        # An explicitly configured legacy cache-control-header string
        # is the operator's deliberate policy: it stays the
        # Cache-Control VALUE; the ETag/Vary layer still applies.
        headers["Cache-Control"] = (config.cache_control_header
                                    or cc)
        if vary:
            headers["Vary"] = vary
        return etag

    async def _source_mtime(object_type: str,
                            object_id: int) -> Optional[float]:
        """The object's ingest/source mtime for Last-Modified, via
        the metadata path (combined role only — proxy/fleet frontends
        have no local source tree; their sidecars' ETags still give
        clients free revalidation).  Images only: the mask metadata
        path has no ingest stamp worth lying about."""
        if (services is None or object_type != "Image"
                or not config.http_cache.enabled):
            return None
        mtime_fn = getattr(services.metadata, "source_mtime", None)
        if mtime_fn is None:
            return None
        peek = getattr(services.metadata, "source_mtime_cached", None)
        if peek is not None:
            # Inline memo fast path: within the TTL this is a lock +
            # dict hit — the thread-pool hop would cost more than the
            # lookup (the handler.py fast-path economics).
            hit, value = peek(object_id)
            if hit:
                return value
        import asyncio as _asyncio
        try:
            return await _asyncio.to_thread(mtime_fn, object_id)
        except Exception:
            return None

    async def _conditional_answer(request: web.Request, headers: dict,
                                  etag: Optional[str],
                                  revalidate_ok,
                                  mtime: Optional[float] = None
                                  ) -> Optional[web.Response]:
        """The renderless answers, checked BEFORE fairness buckets,
        single-flight and admission ever see the request: a matching
        ``If-None-Match`` is a 304, an ``If-Modified-Since``-only
        request against a fresh source mtime is a 304 (ETag WINS when
        both are present — RFC 9110 says evaluate If-None-Match and
        ignore If-Modified-Since then), a ``HEAD`` is headers-only.
        All carry the same ETag/Cache-Control/Vary (+ Last-Modified)
        as the 200 they stand in for.  ``revalidate_ok`` is the
        per-caller ACL gate — a session that cannot read the object
        falls through to the render path and gets its honest 404
        there."""
        inm = request.headers.get("If-None-Match")
        if etag is not None and inm:
            telemetry.HTTPCACHE.count_etag_request()
            if httpcache.if_none_match_matches(inm, etag) \
                    and await revalidate_ok():
                telemetry.HTTPCACHE.count_not_modified()
                return web.Response(status=304, headers=headers)
        elif not inm and mtime is not None \
                and request.headers.get("If-Modified-Since"):
            # The If-Modified-Since-only client (no ETag stored):
            # same zero-work contract as If-None-Match — answered
            # before fairness/single-flight/admission, ACL-gated per
            # caller.
            telemetry.HTTPCACHE.count_ims_request()
            if httpcache.not_modified_since(
                    request.headers.get("If-Modified-Since"), mtime) \
                    and await revalidate_ok():
                telemetry.HTTPCACHE.count_not_modified()
                return web.Response(status=304, headers=headers)
        if request.method == "HEAD" and services is not None:
            # Headers-only when the caller could read the object (the
            # memoized ACL check, no render); an unreadable or missing
            # object falls through so the pipeline answers its honest
            # 404 — aiohttp strips the body for HEAD on every path.
            # Proxy/fleet frontends cannot probe existence locally, so
            # their HEADs always run the pipeline: status fidelity
            # over the renderless shortcut (a HEAD 200 for a deleted
            # image would keep edge entries alive forever).
            if await revalidate_ok():
                telemetry.HTTPCACHE.count_head()
                return web.Response(headers=headers)
        return None

    def _strip_cache_headers_if_degraded(ctx, headers: dict) -> None:
        """Brownout-capped bytes must never be edge-cached under the
        permanent render identity: the ETag is a pure function of
        URL + epoch, so once an edge stored a degraded body every
        later If-None-Match would 304-confirm it FOREVER (until an
        epoch bump).  A capped 200 therefore drops its ETag/Vary and
        answers ``no-store`` — the same never-under-the-full-quality-
        key contract the byte tiers follow (server.pressure
        drop_quality)."""
        if getattr(ctx, "_pressure_quality_capped", False):
            headers.pop("ETag", None)
            headers.pop("Vary", None)
            headers["Cache-Control"] = "no-store"

    def _stamp_provenance(ctx, headers: dict) -> None:
        """Opt-in debug header (telemetry.provenance-header): the
        response's provenance record, compact.  Success paths ONLY —
        every error/status mapping skips this, so a failure can never
        carry (or cache) a provenance claim."""
        if not config.telemetry.provenance_header:
            return
        record = provenance.assemble(
            ctx, 200, telemetry.current_trace_id())
        value = provenance.header_value(record)
        if value:
            headers["X-Image-Region-Provenance"] = value

    def _can_revalidate(object_type: str, object_id: int, session_key):
        """Per-caller gate for the 304 path.  Combined role runs the
        SAME memoized ACL check a byte-cache hit runs; proxy/fleet
        frontends cannot check locally and answer on the ETag alone —
        safe, because the ETag derives from the request params + epoch
        and never from pixels, so a 304 reveals nothing the URL does
        not (the sidecar's ACL still gates every byte that moves)."""
        async def check() -> bool:
            if services is None:
                return True
            from .handler import check_can_read
            try:
                return await check_can_read(services, object_type,
                                            object_id, session_key)
            except Exception:
                return False
        return check

    async def render_image_region(request: web.Request) -> web.Response:
        import time as _time

        t_req = _time.perf_counter()
        params = _params_of(request)
        try:
            ctx = ImageRegionCtx.from_params(
                params, await require_session_key(request))
        except _NoSession:
            return web.Response(status=403)
        except BadRequestError as e:
            # Parse errors return the message body (the reference's 400
            # path, ImageRegionMicroserviceVerticle.java:300-305).
            # NOTE error responses (this 400, every _status_of answer)
            # deliberately carry NO Cache-Control/ETag: an edge must
            # never cache a failure under a render identity.
            return web.Response(status=400, text=str(e))
        request["prov_ctx"] = ctx
        ctx.t_accept = t_req      # span handler.prepare starts here
        headers = {
            "Content-Type": codecs.CONTENT_TYPES.get(
                ctx.format, "application/octet-stream"),
        }
        etag = await _cache_headers(headers, ctx.cache_key, "Image",
                                    ctx.image_id)
        # The Last-Modified basis folds the cache EPOCH with the
        # source mtime (httpcache.last_modified_basis): an epoch bump
        # must stale IMS-only clients exactly like it stales ETags —
        # un-ordered operator epochs disarm this leg entirely.
        mtime = httpcache.last_modified_basis(
            await _source_mtime("Image", ctx.image_id),
            config.http_cache.epoch)
        if mtime is not None:
            # Last-Modified on every cacheable answer (200 and the
            # 304s below): If-Modified-Since-only clients get free
            # revalidation; conditional caches store an honest stamp.
            headers["Last-Modified"] = httpcache.http_date(mtime)
        renderless = await _conditional_answer(
            request, headers, etag,
            _can_revalidate("Image", ctx.image_id,
                            ctx.omero_session_key), mtime=mtime)
        if renderless is not None:
            # Renderless HEADs share the 304 provenance tier: the
            # zero-byte conditional class (actual 304s override by
            # status anyway).
            provenance.mark(ctx, tier="304")
            return renderless
        stream_fn = (getattr(image_handler,
                             "render_image_region_stream", None)
                     if config.wire.streaming else None)
        if stream_fn is None:
            try:
                body = await image_handler.render_image_region(ctx)
            except Exception as e:
                return _status_of(e)
            _strip_cache_headers_if_degraded(ctx, headers)
            _stamp_provenance(ctx, headers)
            return web.Response(body=body, headers=headers)
        # Progressive first-byte-out response (wire v3 leg 2): the
        # body leaves as an HTTP chunked response, each chunk written
        # the moment its wire frame (or, combined-mode, the
        # first-tile-out settled body) arrives — first bytes reach the
        # client while the rest of the batch is still encoding.  The
        # FIRST chunk is awaited before the response is prepared, so
        # every pre-body failure maps through the identical status
        # contract as the unary path.
        agen = stream_fn(ctx)
        try:
            first = await agen.__anext__()
        except StopAsyncIteration:
            first = b""
        except Exception as e:
            return _status_of(e)
        # Combined mode settles the whole body before the first chunk
        # yields, so the cap flag is known here; proxy streaming only
        # learns it on the fin frame, after headers left — that path's
        # capped bodies are protected by the sidecar never writing
        # them to the byte tier, and streaming under brownout is the
        # degraded exception, not the cacheable steady state.
        _strip_cache_headers_if_degraded(ctx, headers)
        if not proxy_mode:
            # Combined/fleet streams settle the whole body before the
            # first chunk yields, so the marks are complete here.  A
            # PLAIN PROXY stream only learns the sidecar's marks on
            # the fin frame — after headers left — so it skips the
            # header rather than echo a half-assembled record (the
            # access log and counters, computed post-fin, stay
            # complete and authoritative for that posture).
            _stamp_provenance(ctx, headers)
        resp = web.StreamResponse(headers=headers)
        nbytes = 0
        try:
            await resp.prepare(request)
            if first:
                await resp.write(first)
                nbytes += len(first)
            telemetry.record_span(
                "http.firstByte", t_req,
                (_time.perf_counter() - t_req) * 1000.0)
            async for chunk in agen:
                await resp.write(chunk)
                nbytes += len(chunk)
            await resp.write_eof()
        except ConnectionResetError:
            # The HTTP CLIENT went away mid-stream (with buffered
            # responses aiohttp swallows this internally; manual
            # StreamResponse writes surface it here).  A peer's
            # disconnect is not a server failure — stop writing and
            # account what left.
            request["streamed_nbytes"] = nbytes
            log.debug("client disconnected mid-stream")
            return resp
        except Exception:
            # Mid-stream RENDER failure with bytes already on the
            # wire: the status cannot be rewritten under them —
            # truncate the connection (the client sees a short chunked
            # body), and let _observed's abort accounting see the
            # raise.
            request["streamed_nbytes"] = nbytes
            log.warning("streamed render truncated mid-body",
                        exc_info=True)
            raise
        request["streamed_nbytes"] = nbytes
        return resp

    async def render_shape_mask(request: web.Request) -> web.Response:
        params = _params_of(request)
        try:
            ctx = ShapeMaskCtx.from_params(
                params, await require_session_key(request))
        except _NoSession:
            return web.Response(status=403)
        except BadRequestError as e:
            return web.Response(status=400, text=str(e))
        request["prov_ctx"] = ctx
        headers = {"Content-Type": "image/png"}
        # The mask's BYTE-cache key keeps the reference's exact
        # id:color format; the ETag identity additionally folds the
        # flips, which change the produced bytes but (for reference
        # parity) never reached that key.
        identity = (f"{ctx.cache_key()}"
                    f":f{int(ctx.flip_horizontal)}"
                    f"{int(ctx.flip_vertical)}")
        etag = await _cache_headers(headers, identity, "Mask",
                                    ctx.shape_id)
        renderless = await _conditional_answer(
            request, headers, etag,
            _can_revalidate("Mask", ctx.shape_id,
                            ctx.omero_session_key))
        if renderless is not None:
            provenance.mark(ctx, tier="304")
            return renderless
        # Masks join the session model (the PR 10 follow-on): the
        # request debits its session's fairness tokens, QoS-classed
        # INTERACTIVE (pressure.is_bulk knows mask ctxs), so a
        # hostile mask-scraping session sheds on ITS budget with the
        # same "fairness" 503 the tile route gives — it used to
        # bypass the meter entirely.  Conditional 304s stay free,
        # exactly like the image route (zero-work contract).
        # ...and its session reads as LIVE to the demand model: the
        # viewport tracker keeps the session in its LRU (no lattice
        # pollution — a mask has no tile coordinates to vote with).
        tracker = (getattr(services.prefetcher, "viewport", None)
                   if services is not None
                   and services.prefetcher is not None else None)
        if tracker is not None and ctx.omero_session_key:
            tracker.observe_activity(ctx.omero_session_key)
        # Byte-cache hits BEFORE the fairness gate — the tile route's
        # footing exactly: already-rendered bytes never cost a token
        # and never shed (the probe runs the per-caller ACL itself).
        cache_probe = getattr(mask_handler, "cached_shape_mask", None)
        if cache_probe is not None:
            try:
                cached_mask = await cache_probe(ctx)
            except Exception as e:
                return _status_of(e)
            if cached_mask is not None:
                _stamp_provenance(ctx, headers)
                return web.Response(body=cached_mask, headers=headers)
        # Federated mask byte tier (PR 11 contract, mask leg): on a
        # local miss, ask the mask identity's ring OWNER for its
        # cached PNG before paying the rasterize — the owner's ACL
        # gate runs on its host, and a miss/timeout just falls
        # through to the local render.
        peer_mask = (getattr(fleet_router, "fetch_peer_mask", None)
                     if fleet_router is not None else None)
        if peer_mask is not None:
            try:
                peer_png = await peer_mask(ctx)
            except Exception:
                peer_png = None
            if peer_png is not None:
                _stamp_provenance(ctx, headers)
                return web.Response(body=peer_png, headers=headers)
        mask_admission = (getattr(image_handler, "admission", None)
                          or (services.admission
                              if services is not None else None))
        debit = None
        if mask_admission is not None:
            try:
                debit = mask_admission.admit_session(ctx)
            except Exception as e:
                return _status_of(e)
        if debit is not None:
            provenance.mark(ctx, tokens=debit[1])
        try:
            body = await mask_handler.render_shape_mask(ctx)
        except Exception as e:
            # Tokens pay for the ATTEMPT, exactly like the image
            # route: a request-level failure (404/400) keeps its
            # debit — refunding it would let a hostile session scrape
            # nonexistent shape ids unmetered, the loophole this gate
            # exists to close.  (Masks have no GLOBAL admission leg,
            # so there is no shed-class refund here at all.)
            return _status_of(e)
        # Write-back to the mask identity's byte-tier authority
        # (fire-and-forget; only explicit-color masks are cacheable —
        # the same rule ShapeMaskHandler applies locally).
        put_mask = (getattr(fleet_router, "put_peer_mask", None)
                    if fleet_router is not None else None)
        if put_mask is not None:
            try:
                put_mask(ctx, body)
            except Exception:
                log.debug("peer mask put failed", exc_info=True)
        _stamp_provenance(ctx, headers)
        return web.Response(body=body, headers=headers)

    async def render_overlay(request: web.Request) -> web.Response:
        """Region pixels + ROI mask composite in ONE device pass
        (deploy/DEPLOY.md "Device workloads").  ``?shapes=<id,id,...>``
        names the masks (request order = paint order), ``?color=``
        overrides fills; the base render is FORCED lossless (png) so
        the composite never bakes JPEG artifacts under the mask.  The
        ETag identity folds the base render's cache key with the shape
        list + color override — edge caching works exactly like the
        plain routes."""
        if workloads_handler is None \
                or not config.workloads.overlay_enabled:
            return web.Response(status=404)
        params = _params_of(request)
        shapes_raw = params.pop("shapes", "")
        color = params.pop("color", None)
        params["format"] = "png"
        try:
            shape_ids = [int(s) for s in shapes_raw.split(",") if s]
        except ValueError:
            return web.Response(
                status=400,
                text=f"Incorrect format for shapes '{shapes_raw}'")
        if not shape_ids:
            return web.Response(
                status=400, text="overlay needs ?shapes=<id,id,...>")
        try:
            ctx = ImageRegionCtx.from_params(
                params, await require_session_key(request))
        except _NoSession:
            return web.Response(status=403)
        except BadRequestError as e:
            return web.Response(status=400, text=str(e))
        request["prov_ctx"] = ctx
        headers = {"Content-Type": "image/png"}
        identity = (f"{ctx.cache_key}:ov:"
                    + ",".join(str(s) for s in shape_ids)
                    + f":{color or ''}")
        etag = await _cache_headers(headers, identity, "Image",
                                    ctx.image_id)
        renderless = await _conditional_answer(
            request, headers, etag,
            _can_revalidate("Image", ctx.image_id,
                            ctx.omero_session_key))
        if renderless is not None:
            provenance.mark(ctx, tier="304")
            return renderless
        try:
            body = await workloads_handler.render_overlay(
                ctx, shape_ids, color=color)
        except Exception as e:
            return _status_of(e)
        _strip_cache_headers_if_degraded(ctx, headers)
        _stamp_provenance(ctx, headers)
        return web.Response(body=body, headers=headers)

    async def render_animation(request: web.Request) -> web.Response:
        """A z/t frame range rendered as ONE batched device job and
        streamed in order: ``FRME`` + u32be length + frame bytes per
        frame over chunked transport.  ``?axis=z|t`` picks the scrub
        axis, ``?frames=N`` the strip length starting at the URL's
        theZ/theT.  The FIRST frame is awaited before headers leave,
        so every pre-body failure keeps the unary status contract; a
        client disconnect mid-stream closes the generator, which
        cancels every frame still queued on the device."""
        if workloads_handler is None \
                or not config.workloads.animation_enabled:
            return web.Response(status=404)
        params = _params_of(request)
        axis = (params.pop("axis", "t") or "t").lower()
        if axis not in ("z", "t"):
            return web.Response(
                status=400,
                text=f"Incorrect format for axis '{axis}'")
        frames_raw = params.pop("frames", "2")
        try:
            n_frames = int(frames_raw)
        except ValueError:
            return web.Response(
                status=400,
                text=f"Incorrect format for frames '{frames_raw}'")
        if n_frames < 1:
            return web.Response(status=400,
                                text="frames must be >= 1")
        axis_key = "theZ" if axis == "z" else "theT"
        try:
            # Per-frame ctxs re-parse the SAME params with only the
            # scrub coordinate changed, so each frame shares identity
            # (cache key, byte tiers, single-flight) with the plain
            # tile route serving that plane.
            skey = await require_session_key(request)
            start = int(params.get(axis_key) or 0)
            frame_ctxs = []
            for i in range(n_frames):
                fparams = dict(params)
                fparams[axis_key] = str(start + i)
                frame_ctxs.append(
                    ImageRegionCtx.from_params(fparams, skey))
        except _NoSession:
            return web.Response(status=403)
        except BadRequestError as e:
            return web.Response(status=400, text=str(e))
        request["prov_ctx"] = frame_ctxs[0]
        # A stream of frames has no single stable body: never
        # edge-cached (each FRAME's bytes stay cacheable through the
        # plain route's identity).
        headers = {
            "Content-Type": "application/x-image-region-animation",
            "Cache-Control": "no-store",
        }
        agen = workloads_handler.render_animation_stream(frame_ctxs)
        try:
            first = await agen.__anext__()
        except StopAsyncIteration:
            first = b""
        except Exception as e:
            return _status_of(e)
        resp = web.StreamResponse(headers=headers)
        nbytes = 0
        try:
            await resp.prepare(request)
            if first:
                await resp.write(first)
                nbytes += len(first)
            async for chunk in agen:
                await resp.write(chunk)
                nbytes += len(chunk)
            await resp.write_eof()
        except ConnectionResetError:
            # The viewer left mid-animation: stop writing; closing
            # the generator (finally below) cancels the frames still
            # queued on the device.
            request["streamed_nbytes"] = nbytes
            log.debug("animation client disconnected mid-stream")
            return resp
        except Exception:
            request["streamed_nbytes"] = nbytes
            log.warning("animation stream truncated mid-body",
                        exc_info=True)
            raise
        finally:
            await agen.aclose()
        request["streamed_nbytes"] = nbytes
        return resp

    async def pyramid_submit(request: web.Request) -> web.Response:
        """``POST /pyramid`` ``{"imageId": N}`` (or ``{"path": dir}``):
        queue a background on-device pyramid build.  Idempotent — an
        unfinished job for the same destination is returned as-is.
        Answers 202 + the job document; poll ``GET /pyramid/{jobId}``."""
        if jobs_manager is None:
            return web.Response(status=404)
        try:
            doc = await request.json()
        except Exception:
            return web.Response(status=400, text="body must be JSON")
        if not isinstance(doc, dict) \
                or (doc.get("imageId") is None and not doc.get("path")):
            return web.Response(
                status=400,
                text='body needs {"imageId": N} or {"path": dir}')
        try:
            if doc.get("imageId") is not None:
                job = jobs_manager.submit_image(int(doc["imageId"]))
            else:
                job = jobs_manager.submit(str(doc["path"]))
        except FileNotFoundError:
            return web.Response(status=404)
        except (ValueError, TypeError) as e:
            return web.Response(status=400, text=str(e))
        return web.json_response(job.to_doc(), status=202)

    async def pyramid_status(request: web.Request) -> web.Response:
        """Job-state read: memory first, then the crash-safe sidecar
        (a restarted server still answers for jobs it ran before)."""
        if jobs_manager is None:
            return web.Response(status=404)
        job = jobs_manager.get(request.match_info["jobId"])
        if job is None:
            return web.Response(status=404)
        return web.json_response(job.to_doc())

    def _finish_request(route: str, status: int, nbytes: int,
                        total_ms: float, trace,
                        prov_ctx=None) -> None:
        """Post-response accounting: request histogram + totals (with
        a trace-id + provenance-tier EXEMPLAR per latency bucket), the
        SLO windows, the cost ledger (histograms + top-K), the
        provenance record (counters + access line), and the
        slow-request waterfall dump."""
        record = None
        if prov_ctx is not None and status < 400:
            # The response's provenance record: errors stay out of the
            # tier counters (their tier claim would be a guess), the
            # 499 abort path never reaches here.
            record = provenance.assemble(
                prov_ctx, status,
                trace.trace_id if trace is not None else None)
            telemetry.PROVENANCE.count(record)
        exemplar = None
        if trace is not None and record is not None:
            # Bucket exemplar: this trace id (+ its provenance tier)
            # becomes the bucket's pullable example — the p99 bucket
            # then NAMES a waterfall (closing the metrics->trace
            # loop).  Success-only, like the record itself: an error
            # response must not land in a bucket slot wearing a
            # fabricated tier.
            exemplar = (trace.trace_id, record["tier"])
        telemetry.REQUEST_HIST.observe(route, total_ms,
                                       exemplar=exemplar)
        telemetry.count_request(route, status)
        telemetry.SLO.record(status, total_ms)
        sentinel_engine = sentinel_mod.active()
        if sentinel_engine is not None and status < 400:
            # Perf-sentinel quantile sketch: one bounded-vocabulary
            # key probe + one sketch insert (errors stay out — their
            # latency describes the failure path, not the serving
            # regression the sentinel hunts).
            sentinel_engine.observe(
                route, nbytes, total_ms,
                trace.trace_id if trace is not None else None)
        if diurnal_estimator is not None:
            # One observation per finished request: the arrival stream
            # the diurnal demand fit regresses over (ns-scale bin
            # bump; pay-for-what-you-use — None when prediction is
            # off).
            diurnal_estimator.observe()
        if status >= 500:
            telemetry.FLIGHT.record(
                "request.error", route=route, status=status,
                trace=trace.trace_id if trace is not None else None,
                ms=round(total_ms, 1))
        if trace is None:
            return
        ledger, cache_class = telemetry.assemble_ledger(
            trace, total_ms, nbytes)
        telemetry.observe_request_cost(route, ledger)
        telemetry.COST_TOPK.offer({
            "trace": trace.trace_id, "route": route, "status": status,
            "ts": round(trace.wall_ts, 3), "cache": cache_class,
            "total_ms": round(total_ms, 3), "cost": ledger,
        })
        if config.telemetry.access_log:
            queue_ms = trace.span_ms("batcher.queueWait")
            render_ms = trace.span_ms("Renderer.renderAsPackedInt",
                                      "Renderer.renderAsPackedInt.cpu")
            if render_ms is not None and queue_ms:
                # The handler's render span wraps the whole await of
                # the batcher — queue wait included; the stage
                # breakdown must not blame backlog on the renderer.
                render_ms = max(0.0, render_ms - queue_ms)
            encode_ms = trace.span_ms("encodeImage",
                                      "jfif.encodeBatch")
            line = {
                "ts": round(trace.wall_ts, 3),
                "trace": trace.trace_id,
                "route": route,
                "status": status,
                "bytes": nbytes,
                "ms": round(total_ms, 3),
                "queue_ms": queue_ms,
                "render_ms": render_ms,
                "encode_ms": encode_ms,
                "cache": cache_class,
                "cost": ledger,
            }
            if record is not None:
                # The provenance record, verbatim: tier, member,
                # flags, QoS class, ladder prefix, tokens charged.
                line["prov"] = {k: v for k, v in record.items()
                                if k != "trace"}
            access_log.info("%s", json.dumps(line))
        if (config.telemetry.slow_request_ms > 0
                and total_ms >= config.telemetry.slow_request_ms):
            path = telemetry.dump_slow_trace(
                trace, total_ms, status,
                config.telemetry.slow_request_dir,
                extra=({"prov": record} if record is not None
                       else None))
            if path:
                log.warning("slow request %s (%.0f ms) on %s: "
                            "waterfall dumped to %s", trace.trace_id,
                            total_ms, route, path)

    def _observed(route: str, handler):
        """Wrap a render handler in a request trace: a fresh trace id
        becomes the context's recording target (and rides the sidecar
        wire), every stopwatch span below lands on the waterfall, and
        completion feeds the duration histogram / access log / slow
        dump."""
        import time as _time

        from ..utils.stopwatch import REGISTRY, stopwatch
        from ..utils.transient import deadline_scope
        deadline_ms = config.fault_tolerance.request_deadline_ms

        async def wrapper(request: web.Request) -> web.Response:
            trace_id = telemetry.new_trace_id()
            t0 = _time.perf_counter()
            try:
                with telemetry.trace_scope(trace_id, route), \
                        deadline_scope(deadline_ms):
                    resp = await handler(request)
            except BaseException:
                # Client-disconnect cancellation (or a handler bug)
                # must not leak the trace into the active registry —
                # finish it, count the abort, and let the exception
                # propagate to aiohttp.
                telemetry.TRACES.finish(trace_id)
                telemetry.count_request(route, 499)
                raise
            t_end = _time.perf_counter()
            total_ms = (t_end - t0) * 1000.0
            trace = telemetry.TRACES.finish(trace_id)
            if trace is not None and trace.t_answered is not None:
                # Span ``handler.respond``: the request's answer
                # existed (its future settled, or the renderer returned
                # where no batcher answered) -> here.  The last of a
                # request's four phases: the loop's hop that resumes
                # the coroutine, the byte cache's ``set``, the
                # ``Response``.
                respond_ms = (t_end - trace.t_answered) * 1000.0
                REGISTRY.add("handler.respond", respond_ms)
                trace.add_span("handler.respond", trace.t_answered,
                               respond_ms)
            nbytes = request.get("streamed_nbytes")
            if nbytes is None:
                # Buffered Response path; StreamResponse has no .body.
                body = getattr(resp, "body", None)
                nbytes = len(body) if body else 0
            # The accounting's own cost, on the one thread every
            # request shares.
            with stopwatch("http.account"):
                _finish_request(route, resp.status, nbytes,
                                total_ms, trace,
                                prov_ctx=request.get("prov_ctx"))
            return resp

        return wrapper

    async def metrics(request: web.Request) -> web.Response:
        """Prometheus text exposition (≙ the reference's optional metrics
        beans, ``beanRefContext.xml:36-46`` — Graphite there, a scrape
        endpoint here).  Spans keep the perf4j names from the Java logs;
        per-span and per-route latencies are proper histogram series
        (``_bucket``/``_sum``/``_count``), and TYPE headers are emitted
        once per family by the shared finalizer."""
        from ..utils.stopwatch import span_lines

        # Exemplars are OpenMetrics syntax; the classic text/plain
        # parser rejects them (one tail would fail the whole scrape),
        # so they ride ONLY a scrape that negotiated the OpenMetrics
        # exposition.  /debug/exemplars serves the same data as JSON
        # for everything else.
        openmetrics = ("application/openmetrics-text"
                       in request.headers.get("Accept", ""))
        lines = telemetry.request_metric_lines(exemplars=openmetrics)
        lines += span_lines()
        # Fault-tolerance series: breaker state (proxy mode), sheds,
        # retries, deadline cancellations, supervisor restarts.
        lines += telemetry.resilience_metric_lines(
            breaker=(client.breaker if services is None else None))
        # Self-preservation families: pressure level/ladder, watchdog
        # fires, drain states (both roles emit their own copy).
        lines += telemetry.robustness_metric_lines()
        # Wire transport series: vectored-flush coalescing, shm-ring
        # hits/fallbacks, chunk streams (this process's side of the
        # socket; the sidecar merge below carries the other side).
        lines += telemetry.wire_metric_lines()
        if fleet_router is not None:
            # Fleet routing series: per-member depth/inflight/health,
            # routed/stolen/failed-over counters, shard ownership —
            # plus the fleet-wide single-flight table (it moved off
            # services, whose emitter would otherwise carry it).
            lines += telemetry.fleet_metric_lines(
                fleet_router,
                single_flight=image_handler.single_flight)
        if services is None:
            # Frontend proxy: local series plus the device process's
            # fetched over the sidecar socket (best-effort with a hard
            # timeout — a dead OR partitioned sidecar must not hang the
            # scrape).  NOTE for multi-frontend deployments: every
            # frontend exposes an identical copy of the sidecar
            # counters, so aggregate them with max(), or scrape only a
            # designated frontend for process="sidecar" series.
            import asyncio as _asyncio
            try:
                status, body = await _asyncio.wait_for(
                    client.call("metrics", {}), timeout=2.0)
                if status == 200 and body:
                    lines += bytes(body).decode().splitlines()
            except Exception:
                lines.append("# sidecar metrics unavailable")
        else:
            lines += telemetry.device_metric_lines(services)
            if fleet_router is not None:
                # The other in-process members' shards and batchers.
                lines += telemetry.fleet_shard_metric_lines(
                    fleet_members, services)
        if openmetrics:
            # The OpenMetrics exposition is grammar-strict (the
            # finalizer drops free-form comments and maps the legacy
            # type/naming cases), EOF-terminated, and served under
            # its own media type.
            text = telemetry.finalize_exposition(lines,
                                                 openmetrics=True)
            return web.Response(
                text=text + "# EOF\n",
                content_type="application/openmetrics-text")
        return web.Response(
            text=telemetry.finalize_exposition(lines),
            content_type="text/plain")

    async def healthz(request: web.Request) -> web.Response:
        """Liveness: the process answers HTTP.  Deeper state belongs to
        /readyz — a loaded-but-alive service must NOT be restarted."""
        return web.json_response({"status": "ok"})

    async def debug_costs(request: web.Request) -> web.Response:
        """Top-K most expensive recent requests with their full cost
        ledgers — "which requests are expensive, and where did the
        time go" without grepping the access log."""
        return web.json_response({
            "observed": telemetry.COST_TOPK.observed,
            "k": telemetry.COST_TOPK.k,
            "top": telemetry.COST_TOPK.snapshot(),
            "shapes": telemetry.SHAPE_COSTS.snapshot(),
        })

    async def debug_flightrecorder(request: web.Request) -> web.Response:
        """The black-box ring as JSON; ``?dump=1`` also snapshots it to
        the configured spool directory (the same artifact a SIGTERM or
        SLO breach writes).  Proxy mode merges the sidecar's ring; a
        FLEET frontend fetches EVERY member's ring, stamps each event
        with its member identity, and returns ONE causally-merged
        fleet ring (``ring``, sorted by wall timestamp) — plus the
        per-member raw rings for anyone who wants them unmixed."""
        doc = {
            "events": telemetry.FLIGHT.snapshot(),
            "events_total": telemetry.FLIGHT.events_total,
            "dumps_written": telemetry.FLIGHT.dumps_written,
        }
        if services is None:
            import asyncio as _asyncio

            async def _fetch_ring(probe_client):
                try:
                    status, body = await _asyncio.wait_for(
                        probe_client.call("flightrecorder", {}),
                        timeout=2.0)
                    return (json.loads(bytes(body).decode())
                            if status == 200 and body else None)
                except Exception:
                    return None

            if fleet_remote:
                names = [m.name for m in fleet_members]
                rings = await _asyncio.gather(
                    *(_fetch_ring(m.client) for m in fleet_members))
                merged = [dict(e, member="frontend")
                          if "member" not in e else dict(e)
                          for e in doc["events"]]
                members_doc = {}
                for name, ring in zip(names, rings):
                    members_doc[name] = ring
                    for event in (ring or {}).get("events", ()):
                        stamped = dict(event)
                        # The member identity the satellite fix is
                        # about: frontend-side stamp (the sidecar
                        # does not know its fleet name), events that
                        # already name a member keep their own.
                        stamped.setdefault("member", name)
                        merged.append(stamped)
                merged.sort(key=lambda e: e.get("ts", 0.0))
                doc["members"] = members_doc
                doc["ring"] = merged
                # Back-compat: the designated member's ring where the
                # old single-sidecar field pointed.
                doc["sidecar"] = members_doc.get(names[0]) \
                    if names else None
            else:
                doc["sidecar"] = await _fetch_ring(client)
        if request.query.get("dump"):
            doc["dumped_to"] = telemetry.FLIGHT.dump(
                config.telemetry.flight_recorder_dir, "manual")
        return web.json_response(doc)

    async def debug_decisions(request: web.Request) -> web.Response:
        """The control-plane decision ledger as JSON — why the fleet
        scaled/rolled/forked, with measured outcomes.  A FLEET
        frontend fetches EVERY member's ring over the ``decisions``
        wire op, stamps member (and host, from the federation
        manifest) on each record, and returns ONE ts-sorted merged
        timeline (``ledger``) — the flight-ring merge's exact shape —
        plus the per-member raw rings."""
        local = decisions_mod.LEDGER.snapshot()
        doc: dict = {
            "records": local,
            "status": decisions_mod.LEDGER.status(),
        }
        if services is None and fleet_remote:
            import asyncio as _asyncio
            from ..parallel import federation as _federation

            async def _fetch_ring(probe_client):
                try:
                    status, body = await _asyncio.wait_for(
                        probe_client.call("decisions", {}),
                        timeout=2.0)
                    return (json.loads(bytes(body).decode())
                            if status == 200 and body else None)
                except Exception:
                    return None

            names = [m.name for m in fleet_members]
            rings = await _asyncio.gather(
                *(_fetch_ring(m.client) for m in fleet_members))
            self_host = _federation.self_host()
            merged = []
            for rec in local:
                stamped = dict(rec, member="frontend") \
                    if "member" not in rec else dict(rec)
                if self_host:
                    stamped.setdefault("host", self_host)
                merged.append(stamped)
            members_doc = {}
            manifest = _federation.current()
            for name, ring in zip(names, rings):
                members_doc[name] = ring
                host = manifest.host_of(name) if manifest else ""
                for rec in (ring or {}).get("ring", ()):
                    stamped = dict(rec)
                    # Frontend-side identity stamp (the member's own
                    # host/member fields win when present — a record
                    # that already names its subject keeps it).
                    stamped.setdefault("member", name)
                    if host:
                        stamped.setdefault("host", host)
                    merged.append(stamped)
            merged.sort(key=lambda r: r.get("ts", 0.0))
            doc["members"] = members_doc
            doc["ledger"] = merged
        else:
            doc["ledger"] = local
        return web.json_response(doc)

    async def debug_exemplars(request: web.Request) -> web.Response:
        """The request-duration histogram's live exemplars as JSON:
        per route, each latency bucket's most recent trace id +
        provenance tier — the JSON twin of the OpenMetrics exemplars
        on /metrics (pull the named trace's waterfall from the
        slow-request spool, or correlate with the access log)."""
        return web.json_response(
            {"request_duration_ms": telemetry.exemplars_snapshot()})

    async def debug_sentinel(request: web.Request) -> web.Response:
        """The perf sentinel's merged fleet view: this process's
        engine (live, not the last tick), every gossiped/ingested
        member summary, and — on fleet frontends — each remote
        member's own view fetched over the ``sentinel`` wire op and
        stamped with its member name (the flight-ring merge's exact
        shape)."""
        doc = telemetry.SENTINEL.merged()
        engine = sentinel_mod.active()
        if engine is not None:
            local = engine.summary()
            doc["members"][str(local.get("member") or "local")] = {
                "age_s": 0.0, "summary": local}
            if (local.get("verdict") == "drifting"
                    and doc["verdict"] != "drifting"):
                doc["verdict"] = "drifting"
        if services is None:
            import asyncio as _asyncio

            async def _fetch_view(probe_client):
                try:
                    status, body = await _asyncio.wait_for(
                        probe_client.call("sentinel", {}),
                        timeout=2.0)
                    return (json.loads(bytes(body).decode())
                            if status == 200 and body else None)
                except Exception:
                    return None

            members = (fleet_members if fleet_remote else [])
            views = await _asyncio.gather(
                *(_fetch_view(m.client) for m in members))
            if not fleet_remote and client is not None:
                views = [await _fetch_view(client)]
                members_names = ["sidecar"]
            else:
                members_names = [m.name for m in members]
            for name, view in zip(members_names, views):
                if not isinstance(view, dict):
                    continue
                summary = view.get("local") or {}
                if summary:
                    doc["members"].setdefault(
                        name, {"age_s": 0.0, "summary": summary})
                    if summary.get("verdict") == "drifting":
                        doc["verdict"] = "drifting"
                        if name not in doc["drifting_members"]:
                            doc["drifting_members"].append(name)
        doc["drifting_members"] = sorted(set(
            name for name, row in doc["members"].items()
            if row.get("summary", {}).get("verdict") == "drifting"))
        return web.json_response(doc)

    async def debug_profile(request: web.Request) -> web.Response:
        """On-demand device profiling: wrap ``jax.profiler`` around
        whatever the batcher lanes are doing for ``?ms=N`` and return
        the artifact manifest.  Single-flight (409 while one is live);
        proxy mode forwards over the sidecar wire (``profile`` op) so
        the capture runs in the process that owns the device."""
        try:
            ms = float(request.query.get("ms", 500.0))
        except ValueError:
            return web.Response(status=400,
                                text="ms must be a number")
        ms = max(1.0, min(ms, config.telemetry.profile_max_ms))
        if services is None:
            try:
                resp_header, body = await client.call_full(
                    "profile", {}, extra={"ms": ms})
            except Exception as e:
                return _status_of(e)
            status = resp_header["status"]
            if status == 200:
                return web.json_response(
                    json.loads(bytes(body).decode()))
            return web.json_response(
                {"error": resp_header.get("error", "")}, status=status)
        import asyncio as _asyncio
        try:
            doc = await _asyncio.to_thread(
                telemetry.capture_profile,
                config.telemetry.profile_dir, ms)
        except telemetry.ProfileInProgressError as e:
            return web.json_response({"error": str(e)}, status=409)
        except Exception:
            log.exception("profile capture failed")
            return web.json_response(
                {"error": "profiler unavailable"}, status=503)
        return web.json_response(doc)

    async def debug_warmstate(request: web.Request) -> web.Response:
        """Warm-state persistence status: live rehydrate progress,
        snapshot accounting, and (``?snapshot=1``) an on-demand
        manifest write.  Proxy mode forwards to the device process
        over the sidecar ``warmstate`` op — the state lives where the
        device lives."""
        want_snapshot = bool(request.query.get("snapshot"))
        if services is None:
            import asyncio as _asyncio
            try:
                status, body = await _asyncio.wait_for(
                    client.call("warmstate", {},
                                extra=({"snapshot": 1}
                                       if want_snapshot else None)),
                    timeout=10.0)
            except Exception as e:
                return _status_of(e)
            if status != 200:
                return web.json_response(
                    {"error": str(body)}, status=status)
            return web.json_response(json.loads(bytes(body).decode()))
        warmstate = services.warmstate
        doc = {
            "enabled": warmstate is not None,
            "rehydrate": telemetry.PERSIST.rehydrate_summary(),
            "snapshots": telemetry.PERSIST.snapshots,
            "snapshot_errors": telemetry.PERSIST.snapshot_errors,
        }
        if warmstate is not None and want_snapshot:
            import asyncio as _asyncio
            doc["snapshot_path"] = await _asyncio.to_thread(
                warmstate.snapshot_now)
        return web.json_response(doc)

    def _fleet_note(checks: dict) -> None:
        """The fleet membership annotation on /readyz, both roles."""
        down = [n for n in fleet_router.order
                if n not in fleet_router.healthy_members()]
        if down:
            checks["fleet"] = f"members down: {','.join(down)}"
        else:
            checks["fleet"] = f"{len(fleet_router.order)} members"
        draining = fleet_router.draining_members()
        if draining:
            # Annotation by default: a draining member is an OPERATOR
            # act, and the survivors serve every shard — not in
            # itself a reason to pull the instance from rotation.
            # With ``drain.fail-readyz`` on, the drain IS surfaced to
            # the load balancer: /readyz answers 503 while the roll is
            # in progress, so nginx/k8s pull the instance and the
            # restart happens with zero in-flight traffic.
            # Autoscale-parked members annotate with their intent —
            # and (below) never trip the fail-readyz posture: a
            # routine scale-down must not read identically to a node
            # being pulled from rotation.
            parts = [
                n + ("(autoscale)"
                     if getattr(fleet_router.members[n],
                                "drain_intent", None) == "autoscale"
                     else "")
                for n in draining]
            checks["drain"] = f"draining: {','.join(parts)}"

    async def _ready_state() -> tuple:
        """(ok, checks, backends) for /readyz: sidecar reachability
        (proxy mode), prewarm completion, and batcher backlog below
        the configured threshold.  ``backends`` is one
        ``{device, native}`` document per device-owning process behind
        this one (itself in the combined role; every answering
        sidecar for a frontend)."""
        checks = {}
        backends: list = []
        ok = True
        max_depth = config.telemetry.ready_max_queue_depth
        if services is None:
            import asyncio as _asyncio
            breaker = client.breaker
            if breaker is not None and breaker.state == breaker.OPEN:
                # Fail-fast surface: the probe log says WHY requests
                # are shedding before the ping below even times out.
                checks["breaker"] = "open"
            # A fleet frontend probes EVERY currently-healthy member —
            # health flags alone are not evidence (a member nobody has
            # called yet reads healthy even with a dead socket), so an
            # unanswered or garbled ping marks that member down, and
            # readiness aggregates the answering survivors: prewarm is
            # pending until ALL of them finished (a single warm member
            # answering for the fleet would admit traffic whose other
            # shards still pay cold XLA compiles), and queue pressure
            # is the SUM of their depths.  All-sidecars-dead reads
            # UNREADY on the very first probe, not after traffic
            # burns through.
            probes = ([(m, m.client) for m in fleet_members]
                      if fleet_remote else [(None, client)])

            async def _probe(member, probe_client):
                try:
                    status, body = await _asyncio.wait_for(
                        probe_client.call("ping", {}), timeout=2.0)
                    return status, (json.loads(bytes(body).decode())
                                    if status == 200 and body else {})
                except Exception:
                    if member is not None:
                        member.mark_down()
                    return None, None

            # Concurrently: probe latency must stay ~one ping RTT
            # (worst case one 2 s timeout), not scale with fleet size
            # — a serial walk over a few unresponsive members would
            # outlast the LB's probe timeout and pull a servable
            # instance (survivors cover every shard) from rotation.
            results = await _asyncio.gather(
                *(_probe(m, c) for m, c in probes
                  if m is None or m.healthy))
            infos = []
            for status, info in results:
                if info is None:
                    continue
                if status != 200 or not info.get("ok"):
                    ok = False
                    checks["sidecar"] = f"status {status}"
                else:
                    checks.setdefault("sidecar", "ok")
                infos.append(info)
            backends = [{"device": i.get("device"),
                         "native": i.get("native")} for i in infos]
            if infos:
                prewarm_pending = any(
                    bool(i.get("prewarm_pending")) for i in infos)
                depth = sum(
                    int(i.get("queue_depth", 0)) for i in infos)
                notes = [str(i["rehydrate"]) for i in infos
                         if i.get("rehydrate") is not None]
                if notes:
                    # Annotation only (like the SLO line): a slow
                    # rehydrate is a cold-ish first minute, never a
                    # reason to pull the instance from rotation.
                    checks["rehydrate"] = notes[0]
                if fleet_router is not None:
                    # Fleet backlog joins the pressure check, and the
                    # membership annotation mirrors the combined
                    # role's (a PARTIALLY dead fleet stays ready —
                    # survivors serve every shard hash-ring-next).
                    depth += fleet_router.queue_depth()
                    _fleet_note(checks)
            else:
                checks["sidecar"] = "unreachable"
                if fleet_router is not None:
                    _fleet_note(checks)
                if fallback is not None:
                    # Degraded mode IS servable: the CPU fallback keeps
                    # answering tiles, so a load balancer must keep
                    # routing here — the probe body carries the
                    # degradation for operators and alerting.
                    checks["degraded-mode"] = "active"
                    return True, checks, backends
                return False, checks, backends
        else:
            backends = [{"device": services.device,
                         "native": services.native}]
            prewarm_pending = telemetry.READINESS.prewarm_pending
            renderer = services.renderer
            if fleet_router is not None:
                # Fleet depth (queued + executing across members) IS
                # the pressure check: a unit handed to member 0's
                # batcher stays counted as router inflight until it
                # settles, so adding renderer.queue_depth() on top
                # would double-count member 0's backlog and pull the
                # instance from rotation at half the configured
                # threshold.  A half-dead fleet is an annotation, not
                # a readiness failure — the survivors still serve
                # every shard hash-ring-next.
                depth = fleet_router.queue_depth()
                _fleet_note(checks)
            else:
                depth = (renderer.queue_depth()
                         if hasattr(renderer, "queue_depth") else 0)
            if services.warmstate is not None:
                checks["rehydrate"] = \
                    telemetry.PERSIST.rehydrate_summary()
        if prewarm_pending:
            ok = False
            checks["prewarm"] = "pending"
        else:
            checks["prewarm"] = "complete"
        if depth > max_depth:
            ok = False
            checks["queue"] = f"depth {depth} over {max_depth}"
        else:
            checks["queue"] = "ok"
        if telemetry.SLO.enabled:
            # Annotation only: a burning error budget is an ALERT (and
            # a flight-recorder dump), not a reason to pull the last
            # healthy-enough instance out of rotation.
            checks["slo"] = telemetry.SLO.summary()
        _sentinel = sentinel_mod.active()
        if _sentinel is not None:
            # Annotation only, same posture as the SLO line: a
            # drifting instance is slower than its own baseline, not
            # unhealthy — pulling it from rotation would shift its
            # load onto peers and widen the regression.  The page
            # comes from sentinel.drift / the incident bundle.
            checks["sentinel"] = (
                "drifting" if _sentinel.verdict == "drifting" else "ok")
        if governor is not None:
            # Annotation only, same posture as the SLO line: a
            # browned-out instance is still SERVING (that is the whole
            # point of the ladder) — pulling it from rotation would
            # convert chosen degradation into the overload collapse
            # the governor exists to prevent.
            checks["pressure"] = governor.summary()
        if federation_coord is not None:
            # Annotation only: disagreement with a peer host is loud
            # on /admin/federation and the agreement counters; this
            # process still serves its own shard either way.
            checks["federation"] = federation_coord.summary()
        if (config.drain.fail_readyz and fleet_router is not None
                and [n for n in fleet_router.draining_members()
                     if getattr(fleet_router.members[n],
                                "drain_intent", None)
                     not in ("autoscale", "gossip")]):
            # drain.fail-readyz: surface the roll to the LB — a
            # draining instance answers 503 so nginx/k8s pull it from
            # rotation until /admin/undrain (the default annotation-
            # only posture is preserved with the flag off).
            # Everything EXCEPT autoscale drains: an autoscaler
            # scale-down is a routine in-instance act (survivors
            # serve every shard, the controller undrains on demand)
            # so it annotates instead of pulling the instance — but
            # operator drains AND the SIGTERM quiesce (which flips
            # draining with no intent) must keep pulling it.  A
            # "gossip" drain is ANOTHER host's roll reflected here:
            # this instance still serves and must stay in rotation.
            ok = False
        if autoscaler is not None:
            # Annotation only, like the pressure line: fleet size is
            # the controller's business, readiness is the instance's.
            checks["autoscaler"] = autoscaler.summary()
        return ok, checks, backends

    def _drain_status() -> dict:
        return {
            "members": {
                name: {
                    "healthy": fleet_router.members[name].healthy,
                    "draining": fleet_router.members[name].draining,
                    "intent": getattr(fleet_router.members[name],
                                      "drain_intent", None),
                    "depth": fleet_router.member_depth(name),
                    "inflight": fleet_router.member_inflight(name),
                    "planes":
                        fleet_router.members[name].resident_planes(),
                }
                for name in fleet_router.order
            },
        }

    async def admin_drain(request: web.Request) -> web.Response:
        """Zero-downtime rolling drains (deploy/DEPLOY.md "Overload &
        rolling restarts"): ``GET`` reports per-member drain state;
        ``POST ?member=mN`` drains that member — it finishes in-flight
        work, stops accepting routes, and hands its shard manifest to
        its ring successors as a pre-stage hint list so the shard
        arrives WARM instead of cold-missing."""
        if fleet_router is None:
            return web.json_response(
                {"error": "drains require a fleet topology "
                          "(fleet.enabled)"}, status=400)
        if request.method == "GET":
            return web.json_response(_drain_status())
        member = request.query.get("member")
        if not member or member not in fleet_router.members:
            return web.json_response(
                {"error": f"unknown member {member!r}",
                 "members": list(fleet_router.order)}, status=400)
        routable = [n for n in fleet_router.order
                    if fleet_router._routable(n) and n != member]
        if not routable:
            # Draining the LAST servable member is an outage, not a
            # rolling restart; refuse so a scripted roll that lost
            # track cannot take the fleet to zero.
            return web.json_response(
                {"error": "refusing to drain the last routable "
                          "member"}, status=409)
        doc = await fleet_router.drain_member(
            member, prestage=config.drain.prestage,
            max_planes=config.drain.prestage_max_planes,
            settle_timeout_s=config.drain.settle_timeout_s)
        doc.update(_drain_status())
        return web.json_response(doc)

    async def admin_autoscaler(request: web.Request) -> web.Response:
        """Elastic-autoscaler status (deploy/DEPLOY.md "Capacity &
        autoscaling"): active/routable members, the floor/ceiling
        band, cooldown state, the last refused decision, recent
        transitions and the live signals the policy read."""
        if autoscaler is None:
            return web.json_response(
                {"enabled": False,
                 "error": "autoscaler requires autoscaler.enabled "
                          "and a fleet topology"}, status=400)
        return web.json_response(autoscaler.status())

    async def admin_federation(request: web.Request) -> web.Response:
        """Cross-host federation status (deploy/DEPLOY.md "Multi-host
        federation"): the agreed manifest (epoch/digest/members), the
        last agreement verdict per remote member, the last gossip
        round's outcomes and the merged membership view.
        ``?agree=1`` re-runs a (non-strict) agreement round first —
        the operator's "did the fleet converge after my epoch bump"
        probe."""
        if federation_coord is None:
            return web.json_response(
                {"enabled": False,
                 "error": "federation requires federation.enabled "
                          "in the combined role"}, status=400)
        if request.query.get("agree"):
            await federation_coord.agree(strict=False)
        return web.json_response(federation_coord.status())

    async def admin_undrain(request: web.Request) -> web.Response:
        """Rejoin a drained member (same remap bound as a ring join)."""
        if fleet_router is None:
            return web.json_response(
                {"error": "drains require a fleet topology "
                          "(fleet.enabled)"}, status=400)
        member = request.query.get("member")
        if not member or member not in fleet_router.members:
            return web.json_response(
                {"error": f"unknown member {member!r}",
                 "members": list(fleet_router.order)}, status=400)
        fleet_router.undrain_member(member)
        return web.json_response(_drain_status())

    async def readyz(request: web.Request) -> web.Response:
        """Readiness: 200 only when this process can serve renders NOW
        (sidecar up, prewarm done, backlog sane); 503 carries the
        degradation detail so a probe log reads like a diagnosis."""
        ok, checks, backends = await _ready_state()
        doc = {"status": "ready" if ok else "degraded",
               "checks": checks}
        if backends and backends[0]["device"] is not None:
            # What the render backend runs on, as ITS JAX reports it
            # (platform / kind / count), and which native pieces it
            # got.  A fleet frontend lists every answering member.
            doc.update(backends[0])
            if len(backends) > 1:
                doc["members"] = backends
        return web.json_response(doc, status=200 if ok else 503)

    async def details(request: web.Request) -> web.Response:
        doc = {
            "provider": PROVIDER,
            "version": __version__,
            "features": FEATURES,
            "options": {"maxTileLength":
                        (services.max_tile_length if services is not None
                         else config.max_tile_length)},
        }
        if config.cache_control_header:
            doc["options"]["cacheControl"] = config.cache_control_header
        return web.json_response(doc)

    app = web.Application()

    async def on_startup_metadata(app):
        """Swap in the OMERO-DB metadata/ACL backend when configured
        (≙ the backbone services the reference reaches over the bus,
        ImageRegionRequestHandler.java:316-427).  Degrades to the local
        backend with a warning when asyncpg is unavailable, the same
        posture as the session stores."""
        if services is None or config.metadata_backend != "postgres":
            return
        from ..services.db_metadata import PostgresMetadataService
        try:
            services.metadata = await PostgresMetadataService.connect(
                config.metadata_dsn)
            app["_db_metadata"] = services.metadata
        except ImportError:
            log.warning("metadata-service.type is 'postgres' but asyncpg "
                        "is unavailable; using the local backend")

    app.on_startup.append(on_startup_metadata)

    async def on_startup(app):
        # ≙ the reference's worker verticle pool sizing
        # (``worker_pool_size``, default 2 x cores,
        # ``ImageRegionMicroserviceVerticle.java:83-85``): every render
        # offload (asyncio.to_thread) runs on the loop's default executor.
        import asyncio
        import concurrent.futures as cf
        import os as _os

        workers = config.worker_pool_size or 2 * (_os.cpu_count() or 4)
        asyncio.get_running_loop().set_default_executor(
            cf.ThreadPoolExecutor(
                max_workers=workers, thread_name_prefix="render-worker"))

    app.on_startup.append(on_startup)

    async def on_startup_robustness(app):
        """Start the governor/watchdog tick loops (they need the
        running loop, so they cannot start in create_app)."""
        import asyncio
        tasks = [asyncio.create_task(loop_lag.run(), name="loop-lag")]
        if unit_lifecycle is not None:
            # Spawn every member's sidecar unit (blocking per unit
            # until its socket accepts — off-loop); /readyz holds
            # external traffic until the members answer their pings.
            await asyncio.to_thread(unit_lifecycle.start_all)
        if governor is not None:
            tasks.append(asyncio.create_task(
                governor.run(), name="pressure-governor"))
        if watchdog is not None and watchdog._targets:
            tasks.append(asyncio.create_task(
                watchdog.run(), name="watchdog"))
        if autoscaler is not None:
            tasks.append(asyncio.create_task(
                autoscaler.run(), name="autoscaler"))
        if federation_coord is not None:
            # Join the federation: one agreement round with every
            # remote member (split-brain REFUSES the join — serving a
            # forked shard map is the failure this subsystem exists
            # to prevent), then the periodic gossip loop.
            await federation_coord.agree(strict=True)
            tasks.append(asyncio.create_task(
                federation_coord.run(), name="federation-gossip"))
        if sentinel_engine is not None:
            tasks.append(asyncio.create_task(
                sentinel_engine.run(), name="perf-sentinel"))
        if jobs_manager is not None:
            tasks.append(asyncio.create_task(
                jobs_manager.run(), name="pyramid-jobs"))
        app[_ROBUSTNESS_TASKS_KEY] = tasks

    app.on_startup.append(on_startup_robustness)
    # Trailing segments are tolerated like the reference's `:theT*` /
    # `:shapeId*` patterns (ImageRegionMicroserviceVerticle.java:214-231):
    # OMERO.web emits URLs with suffixes past the last parameter.
    traced_image = {
        route: _observed(route, render_image_region)
        for route in ("render_image_region", "render_image")
    }
    traced_mask = _observed("render_shape_mask", render_shape_mask)
    for prefix in ("webgateway", "webclient"):
        for route in ("render_image_region", "render_image"):
            base = f"/{prefix}/{route}/{{imageId}}/{{theZ}}/{{theT}}"
            app.router.add_get(base, traced_image[route])
            app.router.add_get(base + "/{tail:.*}", traced_image[route])
    app.router.add_get("/webgateway/render_shape_mask/{shapeId}",
                       traced_mask)
    app.router.add_get("/webgateway/render_shape_mask/{shapeId}/{tail:.*}",
                       traced_mask)
    # Device-workloads routes (registered unconditionally — a disabled
    # or proxy deployment answers 404 from the handler, so the route
    # table never depends on config).
    traced_overlay = _observed("render_overlay", render_overlay)
    traced_animation = _observed("render_animation", render_animation)
    overlay_base = "/webgateway/render_overlay/{imageId}/{theZ}/{theT}"
    app.router.add_get(overlay_base, traced_overlay)
    app.router.add_get(overlay_base + "/{tail:.*}", traced_overlay)
    anim_base = "/webgateway/render_animation/{imageId}/{theZ}/{theT}"
    app.router.add_get(anim_base, traced_animation)
    app.router.add_get(anim_base + "/{tail:.*}", traced_animation)
    app.router.add_post("/pyramid",
                        _observed("pyramid_submit", pyramid_submit))
    app.router.add_get("/pyramid/{jobId}", pyramid_status)
    app.router.add_get("/metrics", metrics)
    app.router.add_get("/healthz", healthz)
    app.router.add_get("/readyz", readyz)
    app.router.add_get("/debug/costs", debug_costs)
    app.router.add_get("/debug/flightrecorder", debug_flightrecorder)
    app.router.add_get("/debug/decisions", debug_decisions)
    app.router.add_get("/debug/profile", debug_profile)
    app.router.add_get("/debug/warmstate", debug_warmstate)
    app.router.add_get("/debug/exemplars", debug_exemplars)
    app.router.add_get("/debug/sentinel", debug_sentinel)
    # The dry-run explain plane: resolve a render URL — identity,
    # ETag, ring owner/chain, per-member residency, admission posture
    # — with ZERO render work (server.explain).
    from .explain import build_explain_handler
    app.router.add_get("/debug/explain", build_explain_handler(
        config, services=services, fleet_router=fleet_router,
        fleet_members=fleet_members,
        admission=(getattr(image_handler, "admission", None)
                   or (services.admission if services is not None
                       else None)),
        proxy_client=(client if proxy_mode else None),
        federation_coord=federation_coord, jobs=jobs_manager))
    app.router.add_get("/admin/drain", admin_drain)
    app.router.add_post("/admin/drain", admin_drain)
    app.router.add_post("/admin/undrain", admin_undrain)
    app.router.add_get("/admin/autoscaler", admin_autoscaler)
    app.router.add_get("/admin/federation", admin_federation)
    app.router.add_route("OPTIONS", "/{tail:.*}", details)

    async def on_cleanup(app):
        import asyncio as _asyncio
        for task in app.get(_ROBUSTNESS_TASKS_KEY, ()):
            task.cancel()
            try:
                await task
            except (_asyncio.CancelledError, Exception):
                pass
        if governor is not None and pressure_mod.active() is governor:
            pressure_mod.uninstall()
        if sentinel_engine is not None:
            sentinel_engine.close()
            if sentinel_mod.active() is sentinel_engine:
                sentinel_mod.uninstall()
        if autoscaler is not None and autoscaler._op is not None \
                and not autoscaler._op.done():
            # An in-flight scale-down (mid-settle/handoff) must not
            # outlive the router it drains — cancel it BEFORE the
            # lanes and member stacks close under it.
            autoscaler._op.cancel()
            try:
                await autoscaler._op
            except (_asyncio.CancelledError, Exception):
                pass
        if fleet_router is not None:
            # Stop the lane workers BEFORE the member stacks (and the
            # shared host services) close under them.
            await fleet_router.close()
        if fleet_remote:
            for member in fleet_members:
                await member.client.close()
        elif federation_coord is not None:
            # Federated combined role: the manifest's remote members
            # carry their own wire clients.
            from ..parallel import federation as federation_mod
            for member in fleet_members:
                if getattr(member, "remote", False):
                    await member.client.close()
            if federation_mod.current() is federation_coord.manifest:
                federation_mod.uninstall()
        if unit_lifecycle is not None:
            # The frontend owns the unit processes it spawned: stop
            # them on the deliberate shutdown path (no restart).
            await _asyncio.to_thread(unit_lifecycle.stop_all)
        if proxy_mode:
            await client.close()
        db_meta = app.get("_db_metadata")
        if db_meta is not None:
            await db_meta.close()
        if services is not None:
            from .batcher import BatchingRenderer as _BR
            for member in fleet_members:
                # Extra members' batchers (member 0's renderer is the
                # base services' — closed below with the rest).
                # Federated fleets mix in RemoteMembers: no services.
                member_services = getattr(member, "services", None)
                if (member_services is not None
                        and member_services is not services
                        and isinstance(member_services.renderer, _BR)):
                    await member_services.renderer.close()
        if services is not None:
            if services.warmstate is not None:
                # Stop the snapshot timer and abort any in-flight
                # rehydrate BEFORE the stores it reads close under it.
                import asyncio as _asyncio
                await _asyncio.to_thread(services.warmstate.close)
            from .batcher import BatchingRenderer
            if isinstance(services.renderer, BatchingRenderer):
                await services.renderer.close()
            # Drain prefetch workers before the pixel stores close under
            # them.
            if services.prefetcher is not None:
                services.prefetcher.flush(timeout=2.0)
                services.prefetcher.close()
            services.pixels_service.close()
            close_caches = getattr(services.caches, "close", None)
            if close_caches is not None:
                await close_caches()  # one shared Redis client (memo too)
        close = getattr(session_store, "close", None)
        if close is not None:
            await close()

    app.on_cleanup.append(on_cleanup)
    app[SERVICES_KEY] = services
    app[CONFIG_KEY] = config
    app[FLEET_ROUTER_KEY] = fleet_router
    return app


def configure_logging(config: AppConfig) -> None:
    """Console always; optional time-rolling file appender
    (≙ ``logback.xml.example:1-26``'s STDOUT + RollingFileAppender)."""
    import logging.handlers

    level = getattr(logging, config.logging.level.upper(), logging.INFO)
    fmt = logging.Formatter(
        "%(asctime)s [%(threadName)s] %(levelname)-5s %(name)s - "
        "%(message)s")
    root = logging.getLogger()
    root.setLevel(level)
    console = logging.StreamHandler()
    console.setFormatter(fmt)
    root.addHandler(console)
    if config.logging.file:
        import os
        os.makedirs(os.path.dirname(config.logging.file) or ".",
                    exist_ok=True)
        rolling = logging.handlers.TimedRotatingFileHandler(
            config.logging.file, when=config.logging.when,
            backupCount=config.logging.backup_count)
        rolling.setFormatter(fmt)
        root.addHandler(rolling)


def run_app(app: web.Application, config: AppConfig) -> None:
    """Serve with the configured HTTP parse limits.

    ``web.run_app`` cannot forward protocol options, so this drives an
    ``AppRunner`` directly; the kwargs reach ``RequestHandler`` (aiohttp's
    ``max_line_size``/``max_field_size``/``max_headers`` ≙ the Vert.x
    ``max-initial-line-length``/``max-header-size`` limits,
    ``config.yaml:5-12``).
    """
    import asyncio
    import signal

    async def serve():
        runner = web.AppRunner(
            app,
            max_line_size=config.http.max_initial_line_length,
            max_field_size=config.http.max_header_size,
            max_headers=config.http.max_headers,
        )
        await runner.setup()
        site = web.TCPSite(runner, port=config.port)
        await site.start()
        log.info("serving on :%d", config.port)
        # web.run_app would install these for us; a bare runner must do it
        # itself or SIGTERM (docker/k8s stop) kills the process without
        # running on_cleanup (renderer close, prefetcher drain, cache
        # client shutdown).
        stop = asyncio.Event()
        loop = asyncio.get_running_loop()
        # ONE ordered shutdown hook chain (warm-state snapshot first —
        # it captures serving state while services are live; the
        # black-box flight dump LAST — it must exist even if the
        # snapshot wedged and the supervisor escalates to SIGKILL).
        # Each hook is guarded: one failing never skips the rest.  The
        # chain runs on its OWN thread, started at signal time: it
        # must not stall the event loop (in-flight responses are still
        # draining), and it must not wait for the orderly teardown (a
        # wedged drain must not cost the black box); the teardown
        # below joins it so a fast exit cannot truncate the writes.
        import threading as _threading

        from .shutdown import build_shutdown_chain
        chain = build_shutdown_chain(config, app[SERVICES_KEY],
                                     fleet_router=app[FLEET_ROUTER_KEY])
        chain_thread: list = []

        def _on_signal(signame: str) -> None:
            telemetry.FLIGHT.record("signal", sig=signame)
            t = _threading.Thread(target=chain.run, args=(signame,),
                                  name="shutdown-chain", daemon=True)
            chain_thread.append(t)
            t.start()
            stop.set()

        for sig in (signal.SIGINT, signal.SIGTERM):
            try:
                loop.add_signal_handler(
                    sig, _on_signal, sig.name)
            except NotImplementedError:
                pass
        try:
            await stop.wait()
            log.info("shutdown signal received")
        finally:
            await runner.cleanup()
            if chain_thread:
                # Bounded: the snapshot/dump must land before the
                # process exits, but a wedged hook cannot hold the
                # exit hostage either.
                await asyncio.to_thread(chain_thread[0].join, 15.0)
            log.info("shutdown complete")

    try:
        asyncio.run(serve())
    except KeyboardInterrupt:
        pass


def main(argv=None) -> None:
    import argparse

    parser = argparse.ArgumentParser(description="TPU image-region service")
    parser.add_argument("--config", help="YAML config path")
    parser.add_argument("--port", type=int)
    parser.add_argument("--data-dir")
    parser.add_argument(
        "--role",
        choices=["combined", "frontend", "sidecar", "split",
                 "pod-worker"],
        help="process role for the frontend/compute split "
             "(sidecar.role in the config); pod-worker = non-leader "
             "process of a multi-host mesh (joins the cluster and "
             "replays the leader's group dispatches)")
    parser.add_argument(
        "--sidecar-socket",
        help="render sidecar address: unix socket path, or host:port "
             "for cross-host TCP (bind to a private interface; the "
             "protocol is unauthenticated)")
    args = parser.parse_args(argv)

    config = (AppConfig.from_yaml(args.config) if args.config
              else AppConfig())
    if args.port is not None:
        config.port = args.port
    if args.data_dir is not None:
        config.data_dir = args.data_dir
    if args.sidecar_socket is not None:
        config.sidecar.socket = args.sidecar_socket
    if args.role == "pod-worker":
        configure_logging(config)
        if not config.parallel.enabled:
            parser.error("--role pod-worker requires parallel.enabled")
        if config.parallel.process_id == 0:
            # broadcast_one_to_all sources from process 0; a follower
            # there would read its own zeros as a shutdown and exit
            # while the real leader blocks forever.
            parser.error("--role pod-worker must not be process-id 0 "
                         "(process 0 is the serving leader)")
        from ..parallel import cluster
        from ..parallel.serve import run_pod_follower
        cluster.initialize(
            coordinator_address=config.parallel.coordinator_address,
            num_processes=config.parallel.num_processes,
            process_id=config.parallel.process_id)
        from ..utils import jaxenv
        jaxenv.place_compilation_cache(
            config.renderer.compilation_cache_dir)
        mesh = cluster.global_mesh(
            chan_parallel=config.parallel.chan_parallel,
            n_devices=config.parallel.n_devices)
        log.info("pod-worker device: %s",
                 jaxenv.device_identity(mesh.devices.flat))
        run_pod_follower(mesh, jpeg_engine=config.renderer.jpeg_engine)
        return
    if args.role is not None:
        config.sidecar.role = args.role
    if config.sidecar.role != "combined" and not config.sidecar.socket \
            and not (config.sidecar.role == "frontend"
                     and config.fleet.enabled and config.fleet.sockets):
        parser.error(f"--role {config.sidecar.role} requires "
                     f"--sidecar-socket (or a fleet.sockets list for "
                     f"a frontend fleet router)")

    configure_logging(config)

    if config.sidecar.role == "sidecar":
        # Device-owning process: no HTTP listener, serves renders on the
        # unix socket (≙ a worker-verticle-only deployment).
        from .sidecar import sidecar_main
        sidecar_main(config)
        return

    child = None
    supervisor = None
    if config.sidecar.role == "split":
        extra = ["--data-dir", args.data_dir] if args.data_dir else None
        if config.fault_tolerance.supervise:
            # Supervised child (the reference's Vert.x supervisor
            # posture): a sidecar crash restarts it with capped
            # backoff; /readyz holds traffic until the restart's
            # prewarm gate clears.  fault-tolerance.supervise: false
            # restores the bare spawn (orchestrator-managed restarts).
            from .sidecar import SidecarSupervisor
            supervisor = SidecarSupervisor.for_config(
                args.config, config.sidecar.socket, extra_args=extra,
                max_backoff_s=(
                    config.fault_tolerance.supervisor_max_backoff_s))
            supervisor.start()
        else:
            from .sidecar import spawn_sidecar
            child = spawn_sidecar(args.config, config.sidecar.socket,
                                  extra_args=extra)
        config.sidecar.role = "frontend"
    try:
        run_app(create_app(config), config)
    finally:
        if supervisor is not None:
            supervisor.stop()
        if child is not None:
            child.terminate()
            try:
                child.wait(timeout=15)
            except Exception:
                child.kill()


if __name__ == "__main__":
    main()
