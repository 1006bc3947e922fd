"""Service configuration (≙ ``src/dist/conf/config.yaml`` + the Vert.x
ConfigRetriever, ``ImageRegionMicroserviceVerticle.java:98-118``).

YAML keys keep the reference's names where a setting has a direct analogue
(``port``, ``cache-control-header``, ``omero.web.session_cookie_name``,
``session-store``, ``redis-cache``, per-cache ``enabled`` flags,
``omero.server.omero.pixeldata.max_tile_length``) so an existing deployment
file ports by deleting the Java-only blocks and adding ``data-dir``.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional, Tuple

import yaml

from ..services.cache import CacheConfig
from ..utils.faultinject import FaultInjectionConfig


@dataclass
class BatcherConfig:
    enabled: bool = True
    # Renders a group, counted at buckets of 1024x1024 and larger.  A
    # smaller bucket's cap follows its pixels (batcher.group_cap):
    # max_batch x (1024^2 / bucket pixels), held to 64 - so max_batch 1
    # still groups 16 tiles of 256x256.  It is a bound on the device's
    # work a group, not on its count.
    max_batch: int = 8
    # Queue-pressure growth bound; None = 2x max_batch.  Not measured
    # on the current chip.
    max_batch_limit: Optional[int] = None
    linger_ms: float = 2.0
    # Concurrent group renders per bucket key, each on a worker thread
    # from its stacking to the last byte of its host entropy coding
    # (which holds no device lane since PR 31, so group k+1's device
    # work overlaps group k's entropy tail).  4 is the value every
    # benchmark cell runs.  Its A/B was taken over a device link that
    # is gone; ROADMAP S3 measures it on the current chip.
    pipeline_depth: int = 4
    # Preferred concurrent group count under backlog: >1 makes the
    # dispatcher split a burst across that many wire streams instead
    # of popping max_batch-sized convoys.  1 (off) is the value every
    # benchmark cell runs; its A/B, too, was taken over the link that
    # is gone, and ROADMAP S3 measures it.  Single-host only;
    # multi-host meshes always pop max_batch.
    target_inflight: int = 1
    # Bounded device-execute stage of the group pipeline: a group
    # render is fetch/stage (stack + host->device upload), then
    # device-execute (the jitted call until the group's wire rows are
    # in host memory), then for JPEG the host's entropy coding, and at
    # most this many groups occupy the execute stage at once; the
    # other two hold no lane (span batcher.laneHold is the hold).
    # Default 2 (double-buffered): group N+1's program is queued
    # behind group N's without letting every pipeline_depth group pile
    # onto the device.  Multi-host meshes force 1 (SPMD launch order).
    device_lanes: int = 2


@dataclass
class RawCacheConfig:
    """HBM-resident raw tile tier (io.devicecache.DeviceRawCache)."""

    enabled: bool = True
    max_bytes: int = 2 * 1024 * 1024 * 1024
    prefetch: bool = True              # pan-ahead neighbor staging
    # Content-digest index over the cache: planes whose bytes are
    # already HBM-resident (under any key — wire pushes included) are
    # never re-shipped over the host->device link, and the sidecar
    # answers digest probes (wire protocol v2) from it.  Costs one
    # BLAKE2b pass per cold host read (~ms per 8 MB tile).
    digest_dedup: bool = True


@dataclass
class RendererConfig:
    """Render path selection knobs."""

    # Renders of at most this many pixels take the CPU reference kernel
    # (refimpl) instead of a device round trip.  0 disables.  Default:
    # everything smaller than a stock 256x256 tile (edge slivers,
    # pyramid tops); a full stock tile is a device render.  Measured on
    # a v5e with 13 host cores, 16 viewers x 6 connections panning a
    # resident 4-channel uint16 slide in 256x256 JPEG tiles (benchmark
    # cell stock4-u16-t256.pan; PERF.md, PR 28, six 51 s runs a side):
    # the host route 110-113 tiles/s, p50 830-852 ms, the chip
    # untouched (refimpl and the encoder share one GIL: the pool's
    # threads together finish a tile every 9 ms); the device route
    # 314-332 tiles/s, p50 285-296 ms, in groups the batcher caps by
    # the bucket's pixels (batcher.group_cap: 64 of 256x256 where
    # max-batch is 8), the chip busy 47 % and the host's per-request
    # Python the limit.
    # A lone viewer (one connection, a scratch run of the same
    # deployment, two pairs): the host route 42-45 tiles/s, p50 21.8 ms
    # (it reads the tile from the store for every request; refimpl and
    # the encoder alone are 7 ms), the device route 110-112, p50 8.7 ms at
    # B = 1 from the HBM raw cache.  ROADMAP S6 still wants the choice
    # made from queue depth and pixels, on other image classes too.
    cpu_fallback_max_px: int = 256 * 256 - 1
    # Device JPEG wire format, a static choice of the deployment:
    # "sparse" (18-bit coefficient entries + host entropy coding; what
    # every benchmark cell runs) or "huffman" (device fixed-table
    # Huffman stream, ~3x fewer wire bytes, less host coding).  The two
    # have not been compared on the current chip.
    jpeg_engine: str = "sparse"
    # JAX persistent compilation cache directory: restarts reuse
    # compiled executables instead of paying first-compile (~20 s per
    # JPEG program shape when compiled for a v5e).  Precedence
    # (utils.jaxenv): JAX_COMPILATION_CACHE_DIR in the environment,
    # then this, then the fixed <checkout>/.jax_cache.
    compilation_cache_dir: Optional[str] = None
    # Tile shapes ("<channels>x<tile-edge>[@quality][:dtype]", e.g.
    # "4x1024" or "3x1024:uint8" — :dtype is the images' storage dtype,
    # default uint16) whose serving programs compile at STARTUP instead
    # of on the first request of that shape (server.prewarm; ≙ the
    # reference's Bio-Formats memoizer wait, beanRefContext.xml:19-21).
    # Batched postures only.  Empty = lazy compiles.
    prewarm: Tuple[str, ...] = ()


@dataclass
class SidecarConfig:
    """Frontend/compute process split (≙ the reference's event-bus seam,
    ``ImageRegionVerticle.java:128-136``): N frontend processes forward
    serialized request ctxs over a unix socket — or TCP when ``socket``
    is ``host:port``, for frontends on other hosts — to ONE
    device-owning sidecar process.

    role:
      combined — single process, HTTP + device (default; socket unused)
      frontend — HTTP only; forward renders to ``socket``
      sidecar  — device only; serve renders on ``socket``
      split    — spawn a sidecar child, then serve as a frontend
    """

    socket: Optional[str] = None
    role: str = "combined"


@dataclass
class WireConfig:
    """Frontend<->sidecar transport knobs (wire protocol v3 — see
    deploy/DEPLOY.md "Wire transport").  All three legs degrade
    per-feature against previous-round peers, so a mixed-version fleet
    keeps serving on the v2 behavior."""

    # Scatter-gather frame coalescing: queued frames flush as ONE
    # vectored write + ONE drain(), bounded per flush by these two
    # knobs.  Purely sender-local (the byte stream is identical), so
    # it needs no negotiation and no version gate.
    coalesce_max_frames: int = 64
    coalesce_max_bytes: int = 1 * 1024 * 1024
    # Same-host shared-memory ring per connection direction: bodies of
    # at least ring-min-body-bytes ride the ring with only a
    # descriptor frame on the socket.  0 disables (and declines peer
    # hellos offering one).  Negotiation failure or ring exhaustion
    # falls back to socket bodies automatically.
    ring_bytes: int = 32 * 1024 * 1024
    ring_min_body_bytes: int = 4096
    # Progressive first-tile-out streaming: render responses leave as
    # per-tile chunk frames the moment the tile's encode slice lands,
    # and the HTTP frontend forwards them as a chunked response.
    streaming: bool = True
    chunk_max_bytes: int = 256 * 1024


@dataclass
class FleetConfig:
    """Data-parallel device fleet (``parallel.fleet``) — the TPU-native
    analogue of the reference's Hazelcast-clustered verticle fleet: N
    members each own a shard of the hot HBM state, requests route by a
    consistent hash of their plane identity, load skew is handled by
    bounded work stealing, and a dead member's shard fails over
    hash-ring-next.  See deploy/DEPLOY.md "Fleet serving"."""

    enabled: bool = False
    # Combined role: N in-process member lanes (member 0 is the base
    # stack — the lockstep mesh lane in mesh deployments; members
    # 1..N-1 get their own renderer + DeviceRawCache shard).  One JAX
    # process: each member holds a chip of its own where the host has
    # one (``parallel.fleet.partition_local_devices``), the tail
    # members of a host with fewer chips the default device.
    members: int = 2
    # Frontend role: one render sidecar per address; each sidecar owns
    # its own device set.  Overrides ``members``.
    sockets: Tuple[str, ...] = ()
    # Concurrent renders per member (models the member's device
    # lanes); fleet admission sees lane-width x members as the
    # service parallelism.
    lane_width: int = 2
    # An idle member lane steals the OLDEST queued request from the
    # most-backlogged peer once that backlog reaches this depth; the
    # stolen render runs from source bytes without adopting cache
    # ownership.  0 disables stealing.
    steal_min_backlog: int = 2
    # Virtual nodes per member on the hash ring (higher = smoother
    # key-space split; the golden-assignment tests pin 64).
    hash_replicas: int = 64
    # Fail a dead member's shard over hash-ring-next (and re-assign
    # its queued work).  Off = its requests fail as the member does.
    failover: bool = True
    # How long a remote member stays out of the ring after its
    # connection died through every policy retry (the supervisor's
    # restart window); the first successful call re-admits it.
    down_cooldown_s: float = 5.0


@dataclass
class HotkeyConfig:
    """Hot-plane replication (``parallel.fleet`` popularity tier) —
    survive the viral image: routes whose decayed request heat passes
    ``threshold`` get an R>1 replica set drawn deterministically from
    the ring chain, reads balance least-queued across live replicas,
    and heat decay demotes back to R=1 (replica HBM reclaimed by the
    cache-pressure ladder, not eagerly).  See deploy/DEPLOY.md
    "Hot objects"."""

    enabled: bool = False
    # Promotion threshold in units of decayed requests: under a
    # sustained rate of r req/s a route's heat converges to
    # r * decay_s, so the default promotes a plane holding more than
    # ~12/decay_s req/s of one member's demand.
    threshold: float = 12.0
    # Heat decay time constant (seconds): how fast popularity ages
    # out.  Demotion happens below threshold * demote_fraction.
    decay_s: float = 20.0
    # Replica-set size for promoted routes (chain prefix, owner
    # included): 2 = owner + one replica.  Capped by fleet size.
    max_replicas: int = 2
    # Bounded heat-table cardinality (top-K routes tracked).
    top_k: int = 128
    # Hysteresis: demote when heat falls below threshold * this.
    demote_fraction: float = 0.5
    # Autoscaler coupling: replica pressure (hottest route's heat /
    # threshold) at or past this factor wants a scale-up, distinct
    # from queue depth.  0 disables the signal.
    scale_factor: float = 2.0


@dataclass
class FederationConfig:
    """Cross-host fleet federation (``parallel.federation``) — the
    rack-scale Hazelcast analogue: the fleet's membership becomes a
    VERSIONED MANIFEST every host carries identically (member names,
    hosts, addresses, ring seed, shard epoch), agreed by digest at
    join time over the ``manifest_hello`` wire op, gossiped for
    cross-host drain/death propagation, with cross-host drains handing
    warm HBM bytes over ``shard_transfer``.  See deploy/DEPLOY.md
    "Multi-host federation"."""

    enabled: bool = False
    # This process's host identity — must name the ``host`` of at
    # least one manifest member (those build in-process; the rest are
    # reached over their addresses).
    host: str = ""
    # The SHARD EPOCH: bump it with every membership/ring change.
    # Agreement is epoch-ordered — a peer carrying a higher epoch
    # wins; equal epochs must match digest-exactly (split-brain is a
    # refused join).
    shard_epoch: int = 1
    # Folded into every hash-ring point so two federations sharing
    # member names can never share a key space.  "" keeps the
    # single-host golden assignments bit-exact.
    ring_seed: str = ""
    # Virtual ring nodes per member (part of the agreed manifest).
    hash_replicas: int = 64
    # Seconds between membership gossip rounds (each process jitters
    # its ticks ±20%, seeded, so fleets never herd their bursts).
    gossip_interval_s: float = 5.0
    # Quorum membership (deploy/DEPLOY.md "Partitions & quorum"):
    # when on, a host that cannot exchange gossip with a strict
    # MAJORITY of manifest hosts within ``suspect_after_s`` FENCES —
    # it keeps serving reads it can prove from its own shards/byte
    # tier but refuses shard adoption, byte-tier write authority,
    # hot-key promotions, autoscaler transitions and epoch rolls
    # until the partition heals.  Off keeps the trusting PR 15
    # behavior bit-exact.
    quorum: bool = False
    # Silence window before a manifest host is counted unreachable
    # for the quorum verdict (monotonic clock; gossip and any inbound
    # federation op from the host both refresh it).
    suspect_after_s: float = 10.0
    # Per-host ack wait during the two-phase roll's propose leg.
    roll_ack_timeout_s: float = 5.0
    # The full fleet-wide member list, in ring order: dicts of
    # {name, host, address?} — address required for members other
    # hosts must reach (unix socket path or host:port TCP).
    members: Tuple[dict, ...] = ()


@dataclass
class ParallelConfig:
    """Mesh-sharded serving (≙ the reference's ``-cluster`` mode:
    Hazelcast-clustered worker verticles,
    ``ImageRegionMicroserviceVerticle.java:406-424``).  When enabled the
    service renders every coalesced group through a ``(data, chan)``
    ``jax.sharding.Mesh`` — tiles data-parallel, channels optionally
    tensor-parallel with a ``psum`` composite over ICI."""

    enabled: bool = False
    chan_parallel: int = 1
    # None = every visible device (multi-host: the whole slice via
    # jax.distributed).  A number requests that mesh width; a platform
    # with fewer devices refuses to start.
    n_devices: Optional[int] = None
    # Explicit jax.distributed coordinates for multi-host deployments
    # outside auto-discovering environments (TPU pods, Slurm, K8s).
    # When coordinator-address is set, a failed cluster join is LOUD —
    # the service refuses to silently serve standalone.
    coordinator_address: Optional[str] = None
    num_processes: Optional[int] = None
    process_id: Optional[int] = None


@dataclass
class FaultToleranceConfig:
    """The fault-tolerant serving chain's knobs (the reference leaned
    on Vert.x supervisor restarts and bounded event-loop backpressure;
    these are the TPU build's equivalents — see deploy/DEPLOY.md's
    failure-mode runbook)."""

    # Per-request time budget, opened at the HTTP frontend and carried
    # over the sidecar wire; queued work whose budget is spent is
    # cancelled cooperatively (504), never rendered for nobody.
    # 0 disables deadlines.
    request_deadline_ms: float = 0.0
    # Sidecar circuit breaker: this many CONSECUTIVE connection
    # failures trip it open; after breaker-reset-s one trial call is
    # admitted (half-open).  Open = calls fail fast with 503.
    breaker_failure_threshold: int = 5
    breaker_reset_s: float = 5.0
    # Op-aware sidecar retry: idempotent ops (render, probe, ping)
    # get up to this many total attempts with capped exponential
    # backoff + jitter; plane_put is NEVER auto-retried.
    retry_max_attempts: int = 3
    retry_base_backoff_ms: float = 25.0
    retry_max_backoff_ms: float = 1000.0
    # Admission control: at most this many admitted-but-unfinished
    # renders; beyond it (or when the estimated wait exceeds the
    # caller's remaining deadline) requests shed with 503 +
    # Retry-After instead of queueing toward a timeout.  0 disables.
    admission_max_queue: int = 512
    shed_retry_after_s: float = 1.0
    # Degraded mode: while the sidecar is unreachable (connection dead
    # or breaker open), frontends render on the in-process CPU
    # reference path (refimpl) so tiles stay servable at reduced rate.
    # Off by default: it requires the frontend host to mount data-dir.
    degraded_mode: bool = False
    # --role split: supervise the sidecar child — restart with capped
    # backoff on crash; the respawn gate (socket accept + prewarm via
    # /readyz) holds traffic until the device stack is back.
    supervise: bool = True
    supervisor_max_backoff_s: float = 30.0


@dataclass
class PressureConfig:
    """Resource-pressure governor + brownout ladder
    (``server.pressure``): a periodic sampler folds HBM occupancy,
    host RSS, disk-cache fill, queue depth and event-loop lag into a
    pressure level (ok/elevated/critical, per-signal hysteresis) and
    walks the configured degradation ladder so overload costs quality
    before it costs availability.  See deploy/DEPLOY.md "Overload &
    rolling restarts"."""

    enabled: bool = False
    interval_s: float = 1.0
    # Per-signal watermarks: enter elevated at ``high``, exit only
    # below ``low`` (the hysteresis band); a signal at
    # ``high * critical-factor`` reads critical.  high 0 disables the
    # signal.
    hbm_high: float = 0.90
    hbm_low: float = 0.75
    host_rss_high_mb: float = 0.0      # 0 disables (set to ~80% of
    host_rss_low_mb: float = 0.0       # the cgroup/host limit)
    disk_high: float = 0.95
    disk_low: float = 0.85
    queue_high: int = 48
    queue_low: int = 16
    loop_lag_high_ms: float = 250.0
    loop_lag_low_ms: float = 50.0
    critical_factor: float = 1.25
    # Ladder pacing: engage the next step after this many consecutive
    # elevated ticks (critical engages one step EVERY tick); release
    # the last step after this many consecutive ok ticks.
    step_hold_ticks: int = 2
    release_hold_ticks: int = 3
    # The ordered degradation ladder (server.pressure.KNOWN_STEPS).
    # Engages front-to-back, releases back-to-front; shed_bulk must
    # precede tighten_admission (interactive tiles are never shed
    # before bulk/projection work — validated at load).
    ladder: Tuple[str, ...] = (
        "pause_prefetch", "pause_snapshots", "evict_caches",
        "cap_lanes", "drop_quality", "shed_bulk",
        "tighten_admission")
    # Step parameters.
    quality_cap: int = 60              # drop_quality: JPEG ceiling
    evict_to_frac: float = 0.70        # evict_caches: low-water target
    lane_cap: int = 1                  # cap_lanes: concurrent groups
    admission_scale: float = 0.25      # tighten_admission multiplier
    # Continuous prefetch budget by level (PressureGovernor
    # .prefetch_budget): speculative staging scales down with pressure
    # BEFORE the binary pause_prefetch step engages (which floors the
    # budget at 0), and restores in exact reverse on release.
    prefetch_budget_elevated: float = 0.5
    prefetch_budget_critical: float = 0.25


@dataclass
class WatchdogConfig:
    """Stuck-lane / hung-wire watchdog (``server.watchdog``): detects
    a device lane stuck past ``stall-factor`` x its observed p99 (with
    the ``stall-min-s`` floor) or a wire connection wedged mid-frame
    past ``wire-hang-s``, and heals the smallest thing that works —
    requeue the group / drop the connection — escalating to the
    supervisor hook only on repeated fire."""

    enabled: bool = True
    interval_s: float = 2.0
    # A group render is stuck past max(stall-min-s, stall-factor x
    # observed p99 group duration).  The floor keeps cold compiles
    # (tens of seconds on some backends) from reading as stalls.
    stall_factor: float = 8.0
    stall_min_s: float = 30.0
    # A connection with in-flight requests and no received frame for
    # this long is wedged mid-frame; 0 disables the wire check.
    wire_hang_s: float = 60.0
    # The Nth fire on the same victim escalates (supervisor restart
    # hook) instead of re-healing.
    escalate_after: int = 2


@dataclass
class DrainConfig:
    """Zero-downtime rolling drains (``/admin/drain`` +
    ``parallel.fleet``): a draining member finishes in-flight work,
    stops accepting routes, snapshots its shard manifest and
    pre-stages it WARM onto its hash-ring successors."""

    # Pre-stage the drained member's shard manifest onto its ring
    # successors (off = the successors cold-miss instead).
    prestage: bool = True
    prestage_max_planes: int = 256
    # How long a drain waits for the member's in-flight work to
    # settle before reporting (the work itself is never cancelled).
    settle_timeout_s: float = 30.0
    # Surface drain state to load balancers: while ANY member is
    # draining, /readyz answers 503 so nginx/k8s pull the instance
    # from rotation during a rolling restart.  Off (default) keeps
    # the PR 9 annotation-only posture — the survivors serve every
    # shard, so readiness is honest either way; this flag is for LBs
    # that should route around the roll.
    fail_readyz: bool = False


@dataclass
class LoadModelConfig:
    """Open-loop load model (``services.loadmodel``): the simulated
    viewer population ``bench.py --smoke --capacity`` replays against
    a real in-process fleet to measure the latency-vs-offered-load
    curve and the capacity knee.  Deterministic by seed — same seed,
    same event stream.  See deploy/DEPLOY.md "Capacity &
    autoscaling"."""

    seed: int = 1234
    # Simulated viewer sessions per generated window (10^4..10^6 at
    # measurement scale; the smoke sweep uses a small population
    # time-compressed to each offered rate).
    viewers: int = 10000
    # Heavy-tailed per-viewer think time between requests (lognormal:
    # median + sigma; sigma ~1 gives the long-pause tail real viewers
    # have).
    think_time_median_ms: float = 350.0
    think_time_sigma: float = 1.0
    # Heavy-tailed session length in requests (lognormal).
    session_length_median: float = 24.0
    session_length_sigma: float = 1.2
    # Diurnal intensity: session starts bunch toward the peak of a
    # half-sine "day" (0 = flat arrivals, toward 1 = sharp peak).
    diurnal_amplitude: float = 0.6
    # Request-class mix (remainder is interactive tiles).  pyramid =
    # a build-job submission (bulk, rare); animation = a z/t strip
    # stream (PR 20 workload classes).
    bulk_fraction: float = 0.02
    mask_fraction: float = 0.0
    pyramid_fraction: float = 0.0
    animation_fraction: float = 0.0
    # Fraction of pan steps that change zoom level.
    zoom_fraction: float = 0.05
    # Trending-traffic skew: each session picks its image from a
    # zipf(s=skew) rank-frequency law over ``image_population`` ranks
    # (rank 0 hottest).  0 (or a population of 1) keeps every session
    # on image rank 0 — the pre-skew stream, bit-exact.
    skew: float = 0.0
    image_population: int = 1


@dataclass
class WorkloadsConfig:
    """Device-workloads plane (PR 20): the batched mask rasterizer,
    the overlay-composite endpoint, and the z/t animation streamer.
    See deploy/DEPLOY.md "Device workloads"."""

    # Route mask rasterization through the renderer's batched device
    # group path when the wired renderer has one (byte-identical to
    # the host rasterizer by contract; off = host path everywhere).
    device_masks: bool = True
    # Serve GET /webgateway/render_overlay (region + ROI mask
    # composite in one device pass).
    overlay_enabled: bool = True
    # Serve GET /webgateway/render_animation (z/t strip streamed as
    # ordered length-prefixed frames over chunked transport).
    animation_enabled: bool = True
    # Hard cap on frames per animation request (each frame is a full
    # region render; the cap bounds what one URL can pin).
    animation_max_frames: int = 64


@dataclass
class PyramidConfig:
    """Crash-safe background pyramid builds (``server.jobs``): POST
    /pyramid queues a device-downsampled NGFF build for an unpyramided
    source; ``ingest.py pyramid`` drives the same code path from the
    CLI.  See deploy/DEPLOY.md "Device workloads"."""

    # Serve POST /pyramid + GET /pyramid/{jobId} and run the
    # background job runner.
    enabled: bool = True
    # NGFF chunk edge (pixels) for written levels.
    chunk: int = 256
    # Stop halving when the next level's min dimension would fall
    # below this (the store/ngff writers' shared rule).
    min_level_size: int = 256
    # Chunk codec for written levels: zlib | gzip | none.
    compressor: str = "zlib"
    # Poll cadence while a build is parked behind the shed_bulk
    # pressure step (bulk class never starves interactive).
    defer_poll_s: float = 0.25


@dataclass
class AutoscalerConfig:
    """Elastic fleet autoscaler (``server.autoscaler``): closes the
    loop between measured pressure / predicted demand and fleet size,
    using the drain/undrain machinery (scale-down = warm shard
    handoff, scale-up = pre-stage-back).  Requires a fleet topology.
    See deploy/DEPLOY.md "Capacity & autoscaling"."""

    enabled: bool = False
    interval_s: float = 2.0
    # The member-count band the controller may move within.  floor is
    # a hard serving invariant (property-tested: concurrent ticks +
    # member deaths can never shrink past it); ceiling 0 = every
    # configured member.
    floor: int = 1
    ceiling: int = 0
    # Queue-depth watermarks, per active lane (fleet depth / (lanes x
    # routable members)): sustained >= high scales up, sustained <=
    # low scales down — the hysteresis band.
    queue_high_per_lane: float = 3.0
    queue_low_per_lane: float = 0.5
    # Consecutive over/under ticks before acting, and the minimum
    # spacing between transitions (the flapping bound the elasticity
    # drill asserts).
    hold_ticks: int = 2
    cooldown_s: float = 30.0
    # Measured per-lane service capacity in requests/s — read it off
    # the newest CAPACITY record (knee / total lanes).  > 0 arms the
    # predicted-demand signal: scale up when the session model's
    # predicted offered load exceeds the routable capacity, refuse to
    # scale down below it.  0 = queue/pressure signals only.
    lane_capacity_tps: float = 0.0
    # Predicted per-session steady request rate (requests/s) used to
    # turn viewport-tracked sessions into predicted demand.
    session_tps: float = 2.0
    # Diurnal demand prediction (services.loadmodel.DiurnalEstimator):
    # a single-tone harmonic fit over observed request arrivals scales
    # the predicted demand by where "now + horizon" sits in the fitted
    # day.  period-s 0 disables (flat prediction, the pre-PR-15
    # behavior); horizon-s is how far ahead the multiplier looks —
    # scale for the demand a drain/undrain completes INTO, not the
    # demand at tick time.
    diurnal_period_s: float = 86400.0
    diurnal_horizon_s: float = 300.0
    # Sidecar-unit process lifecycle (server.sidecar
    # SidecarUnitLifecycle): with a config path here and a
    # fleet.sockets topology, the FRONTEND spawns every member's
    # sidecar unit itself at startup, and the autoscaler actually
    # STOPS a parked member's process after its drain settles and
    # RESTARTS it (waiting for its socket) before undraining on
    # scale-up — elasticity that releases real memory/devices instead
    # of parking warm processes.  "" = pre-provisioned members
    # (operator-owned processes), the default.
    unit_config: str = ""


@dataclass
class SessionsConfig:
    """Session-aware serving (services.viewport + the admission token
    buckets): model the CLIENT, not just the request.  The session
    identity is the one the stack already resolves —
    ``ctx.omero_session_key`` from the session store middleware, the
    same key the fleet single-flight folds (PR 8) — never a second
    resolution path.  See deploy/DEPLOY.md "Sessions & QoS"."""

    enabled: bool = False
    # Per-session admission token bucket: refill rate (requests/s of
    # steady budget) and burst (the pan-flurry allowance).  An
    # interactive tile draws 1 token; bulk/projection work draws
    # ``qos.bulk-cost``.  Over-budget requests shed 503 + Retry-After
    # with the "fairness" reason BEFORE global admission tightens.
    bucket_refill_per_s: float = 20.0
    bucket_burst: float = 40.0
    # Bounded LRU over live sessions (buckets AND viewport states);
    # an evicted session restarts with a full burst.
    max_tracked: int = 4096
    # Viewport predictor depth: how many pan steps ahead the
    # trajectory extrapolates (services.viewport -> prefetch).
    prefetch_lookahead: int = 2


@dataclass
class QosConfig:
    """Tiered QoS: interactive tile vs bulk export/projection
    (classified by ``pressure.is_bulk`` — the ONE classification the
    brownout ladder and the fleet pin already share).  With it on, the
    fleet router dequeues through a weighted two-class queue so
    interactive work jumps bulk backlogs, and bulk requests draw
    ``bulk-cost`` session tokens each."""

    enabled: bool = False
    # Weighted dequeue: up to this many interactive units pop for
    # every bulk unit while both classes wait (bulk never starves —
    # after the quota one bulk unit always pops).
    interactive_weight: int = 4
    # Session-bucket token cost of one bulk/projection request.
    bulk_cost: float = 4.0


@dataclass
class PersistenceConfig:
    """Warm-state persistence tier (services.diskcache +
    services.warmstate + server.execcache): what survives a restart.
    Off by default — enabling it turns every deploy/respawn/crash from
    minutes of wire fetches and XLA compiles (BENCH_r05: 0.73 cold vs
    26 warm tiles/s) into a disk read."""

    enabled: bool = False
    # Root directory; the tier lays out bytecache/, executables/ and
    # manifest.json under it.  Must be service-user-owned (executables
    # are pickles, same trust model as jax's compilation cache).
    dir: str = "./warm-state"
    # Disk byte-cache budget (LRU by mtime; evicts to 90% on breach).
    disk_cache_max_bytes: int = 1024 * 1024 * 1024
    # Serialize compiled render executables
    # (jax.experimental.serialize_executable); restarts deserialize
    # instead of re-tracing + re-compiling.  The trace cache
    # (renderer.compilation-cache-dir) remains the fallback when the
    # backend cannot serialize.
    executables: bool = True
    # Manifest cadence; SIGTERM always snapshots through the shutdown
    # chain regardless.  0 disables the timer.
    snapshot_interval_s: float = 60.0
    # Hot-set bounds recorded per snapshot.
    snapshot_top_k: int = 512
    max_plane_entries: int = 256
    # Boot rehydrate: replay the manifest in the background.
    rehydrate: bool = True
    rehydrate_concurrency: int = 2


@dataclass
class TelemetryConfig:
    """Tracing / health-probe knobs (utils.telemetry; ≙ the reference's
    optional metrics beans, ``beanRefContext.xml:36-46`` — Graphite
    there, Prometheus scrape + trace waterfalls here)."""

    # Requests slower than this dump their full span waterfall as JSON
    # into slow_request_dir (scripts/trace_report.py renders them).
    # 0 disables the tracer.
    slow_request_ms: float = 0.0
    slow_request_dir: str = "./slow-traces"
    # One-line JSON access log per request (route, status, bytes, cache
    # tier, queue-wait/render/encode ms, trace id, cost ledger) on the
    # "omero_ms_image_region_tpu.access" logger.
    access_log: bool = True
    # /readyz reports degraded (503) when the batcher backlog exceeds
    # this many queued requests.
    ready_max_queue_depth: int = 64
    # Black-box flight recorder (utils.telemetry.FLIGHT): bounded ring
    # of structured events (admission sheds, batch formation, breaker
    # transitions, deadline cancels, cache evictions, compiles) that
    # snapshots to flight_recorder_dir on SIGTERM, on SLO breach, or
    # via /debug/flightrecorder?dump=1.
    flight_recorder_events: int = 512
    flight_recorder_dir: str = "./flight-recorder"
    # /debug/profile?ms=N artifacts (jax.profiler traces) land here;
    # requests are clamped to profile_max_ms.
    profile_dir: str = "./profiles"
    profile_max_ms: float = 10000.0
    # Echo each successful response's provenance record (serving
    # member, byte-source tier, steal/failover/drain flags, QoS class,
    # engaged ladder prefix, tokens charged) as an
    # ``X-Image-Region-Provenance`` debug header.  Off by default
    # (operator debugging surface); NEVER emitted on errors.
    provenance_header: bool = False


@dataclass
class SloConfig:
    """Service-level objectives evaluated as multi-window burn rates
    (utils.telemetry.SloEngine); gauges on /metrics, an annotation on
    /readyz, and a flight-recorder dump on breach.  Both objectives
    default off."""

    # Availability objective: target fraction of requests answering
    # below 500 (sheds and deadline 504s spend the budget).  0 = off.
    availability_target: float = 0.0
    # Latency objective: latency_target fraction of successful
    # requests must finish under latency_ms (p99 tile latency ex-RTT
    # when latency_ms is set to the interactive bound minus the
    # deployment's measured RTT floor).  latency_ms 0 = off.
    latency_ms: float = 0.0
    latency_target: float = 0.99
    # Multi-window burn evaluation: breach = burn rate over threshold
    # in BOTH windows (fast catches the cliff, slow filters blips).
    fast_window_s: float = 60.0
    slow_window_s: float = 600.0
    breach_burn_rate: float = 14.4


@dataclass
class DecisionsConfig:
    """Control-plane decision ledger (utils.decisions.LEDGER): every
    autoscaler verdict, epoch roll, manifest agreement, gossip
    convergence transition and drain lifecycle move lands in one
    bounded ring surfaced on /debug/decisions (federated frontends
    merge every host's into one timeline)."""

    # In-memory ring size (records); clamped to >= 16.
    ring_size: int = 256
    # JSONL spool directory (decisions.jsonl, one-file rotation);
    # "" disables spooling — the ring alone carries the story.
    spool_dir: str = ""
    # Autoscaler verdicts get their MEASURED outcome (queue delta,
    # active-member delta) attached this many ticks later.
    outcome_horizon_ticks: int = 3


@dataclass
class SentinelConfig:
    """Live perf-regression sentinel (``server.sentinel``): always-on
    per-route/per-shape quantile sketches, a tick-driven drift engine
    judging live p50/p99 and served-tiles/s against BOTH a
    self-learned rolling baseline (persisted through the warm-state
    manifest) and the committed bench watermarks, and an automatic
    forensic incident bundle on confirmed drift.  Annotation-only on
    /readyz; never fails a request."""

    enabled: bool = True
    # Drift evaluation cadence; each tick closes one quantile window.
    tick_interval_s: float = 5.0
    # Multi-window confirmation: a breach must hold this many
    # consecutive ticks before the drift verdict fires (one slow
    # request — or one slow window — never pages anyone).
    confirm_ticks: int = 3
    # Clean consecutive ticks that clear a confirmed verdict.
    recover_ticks: int = 3
    # A window with fewer observations than this gives no verdict
    # either way and teaches the baseline nothing.
    min_samples: int = 32
    # Baseline windows to learn before drift can be judged at all.
    warmup_ticks: int = 3
    # Live p99 above baseline-p99 x ratio = one breached window.
    drift_ratio: float = 1.5
    # EWMA step for the rolling baseline (non-breaching windows only).
    baseline_alpha: float = 0.2
    # Served-tiles/s under watermark x ratio (with real traffic) is
    # throughput drift even when the learned baseline sagged with it.
    throughput_floor_ratio: float = 0.5
    # Incident bundles: directory ("" disables capture — verdicts and
    # events still fire), retention cap, device-profile duration.
    bundle_dir: str = ""
    max_bundles: int = 8
    profile_ms: int = 200
    # Where the committed BENCH_r*/OFFLOAD_r* records (and
    # scripts/bench_gate.py) live; "" skips the watermark floors.
    records_dir: str = "."


@dataclass
class HttpConfig:
    """Request parse limits (≙ ``config.yaml:5-12`` — the Vert.x
    ``HttpServerOptions`` line/header limits, mapped onto aiohttp's
    ``max_line_size`` / ``max_field_size`` / ``max_headers`` knobs)."""

    max_initial_line_length: int = 4096    # max-initial-line-length
    max_header_size: int = 8192            # max-header-size (per field)
    max_headers: int = 32768               # header count bound


@dataclass
class HttpCacheConfig:
    """Edge-cache-grade conditional HTTP (``server.httpcache``;
    deploy/DEPLOY.md "Edge caching"): content-addressed ETags on
    region/tile/mask responses, ``If-None-Match`` -> 304 with zero
    render/admission/token work, honest ``Cache-Control``/``Vary``,
    and the fleet's peer byte-fetch short-circuit."""

    enabled: bool = True
    # Deployment cache epoch: folded into (and visible in) every ETag.
    # Bumping it invalidates EVERY edge-cached entry at once — the
    # knob to turn when source data or the render pipeline changes
    # under live URLs.  Token characters only ([A-Za-z0-9._-]).
    # The literal "auto" derives the epoch from the data tree's
    # ingest/source mtimes at startup (httpcache.derive_epoch) —
    # re-ingesting any image then bumps the deployment epoch
    # mechanically; an explicit value stays the operator override.
    epoch: str = "0"
    # Cache-Control max-age for 200s.  0 (default) emits ``no-cache``:
    # edges store but revalidate every serve — safe because the 304
    # answer is free.  >0 lets edges serve without revalidation for
    # that window (an epoch bump then takes up to max-age-s to
    # propagate).
    max_age_s: int = 0
    # Emit ``Vary: <session cookie header>`` (+ ``private``) on
    # ACL-gated images so shared caches key entries per session;
    # public images stay ``public`` with no Vary.  Off = everything
    # private+Vary (the conservative posture for deployments that
    # cannot probe ACL at the edge process).
    vary_acl: bool = True
    # Fleet-global byte tier: on a byte miss, digest-probe the plane's
    # ring authority and fetch the bytes over the idempotent
    # byte_probe/byte_fetch wire ops before any re-render.
    peer_fetch: bool = True
    # Bound on one peer probe+fetch round-trip; past it the render
    # path proceeds (the peer tier may only ever REMOVE work).
    peer_timeout_ms: float = 500.0


@dataclass
class LoggingConfig:
    """≙ ``logback.xml.example:1-26``: console always; optional
    time-rolling file appender; per-subsystem level."""

    level: str = "INFO"
    file: Optional[str] = None             # enables the rolling appender
    when: str = "midnight"                 # TimedRotatingFileHandler unit
    backup_count: int = 7


@dataclass
class AppConfig:
    port: int = 8080
    # None = 2 x cores, the reference's worker verticle default
    # (``config.yaml:3-4``, ``ImageRegionMicroserviceVerticle.java:83-85``);
    # sizes the asyncio default executor every render offload runs on.
    worker_pool_size: Optional[int] = None
    data_dir: str = "./data"
    # OMERO binary-repository mount (``omero.server:
    # omero.data.dir``, reference ``config.yaml:19-20``): when set and
    # the metadata backend is postgres, images resolve from the DB's
    # fileset/originalfile rows under <root>/ManagedRepository (legacy
    # images under <root>/Pixels) with zero re-arrangement.
    omero_data_dir: Optional[str] = None
    max_tile_length: int = 2048            # omero.pixeldata.max_tile_length
    cache_control_header: str = ""         # cache-control-header
    session_cookie_name: str = "sessionid"  # omero.web.session_cookie_name
    session_store_type: Optional[str] = None   # redis | postgres | static
    session_store_uri: Optional[str] = None
    # Reject requests whose cookie does not resolve to an OMERO session
    # (the reference's session handler is mandatory and fails them:
    # ImageRegionMicroserviceVerticle.java:199-212).  None = default on
    # for redis/postgres stores, off for static/no store (the standalone
    # ACL-only posture stays available as an explicit opt-out).
    session_store_required: Optional[bool] = None
    lut_root: Optional[str] = None         # omero.script_repo_root analogue
    # Metadata/ACL backend: "local" (filesystem acl.json + meta.json) or
    # "postgres" (OMERO-schema DB, ≙ the backbone services the reference
    # reaches over the bus — ImageRegionRequestHandler.java:316-427).
    metadata_backend: str = "local"
    metadata_dsn: Optional[str] = None
    # In-flight render dedup (server.handler.SingleFlight): concurrent
    # identical requests coalesce onto one pipeline run instead of each
    # paying the full read/stage/render/encode.  Off only for A/B
    # measurement — coalescing is semantics-free (ACL still runs per
    # caller; followers get the exact bytes the byte cache would).
    single_flight: bool = True
    caches: CacheConfig = field(default_factory=CacheConfig)
    batcher: BatcherConfig = field(default_factory=BatcherConfig)
    raw_cache: RawCacheConfig = field(default_factory=RawCacheConfig)
    renderer: RendererConfig = field(default_factory=RendererConfig)
    http: HttpConfig = field(default_factory=HttpConfig)
    http_cache: HttpCacheConfig = field(default_factory=HttpCacheConfig)
    logging: LoggingConfig = field(default_factory=LoggingConfig)
    parallel: ParallelConfig = field(default_factory=ParallelConfig)
    fleet: FleetConfig = field(default_factory=FleetConfig)
    hotkey: HotkeyConfig = field(default_factory=HotkeyConfig)
    federation: FederationConfig = field(
        default_factory=FederationConfig)
    sidecar: SidecarConfig = field(default_factory=SidecarConfig)
    wire: WireConfig = field(default_factory=WireConfig)
    persistence: PersistenceConfig = field(
        default_factory=PersistenceConfig)
    sessions: SessionsConfig = field(default_factory=SessionsConfig)
    loadmodel: LoadModelConfig = field(
        default_factory=LoadModelConfig)
    workloads: WorkloadsConfig = field(
        default_factory=WorkloadsConfig)
    pyramid: PyramidConfig = field(
        default_factory=PyramidConfig)
    autoscaler: AutoscalerConfig = field(
        default_factory=AutoscalerConfig)
    qos: QosConfig = field(default_factory=QosConfig)
    pressure: PressureConfig = field(default_factory=PressureConfig)
    watchdog: WatchdogConfig = field(default_factory=WatchdogConfig)
    drain: DrainConfig = field(default_factory=DrainConfig)
    telemetry: TelemetryConfig = field(default_factory=TelemetryConfig)
    slo: SloConfig = field(default_factory=SloConfig)
    decisions: DecisionsConfig = field(
        default_factory=DecisionsConfig)
    sentinel: SentinelConfig = field(
        default_factory=SentinelConfig)
    fault_tolerance: FaultToleranceConfig = field(
        default_factory=FaultToleranceConfig)
    # Seeded chaos layer (utils.faultinject); seed absent = disabled.
    fault_injection: FaultInjectionConfig = field(
        default_factory=FaultInjectionConfig)

    @classmethod
    def from_yaml(cls, path: str) -> "AppConfig":
        with open(path) as f:
            raw = yaml.safe_load(f) or {}
        return cls.from_dict(raw)

    @classmethod
    def from_dict(cls, raw: dict) -> "AppConfig":
        cfg = cls()
        cfg.port = int(raw.get("port", cfg.port))
        if raw.get("worker_pool_size") is not None:
            cfg.worker_pool_size = int(raw["worker_pool_size"])
            if cfg.worker_pool_size <= 0:
                raise ValueError("worker_pool_size must be positive")
        http_defaults = HttpConfig()
        cfg.http = HttpConfig(
            max_initial_line_length=int(raw.get(
                "max-initial-line-length",
                http_defaults.max_initial_line_length)),
            max_header_size=int(raw.get(
                "max-header-size", http_defaults.max_header_size)),
            max_headers=int(raw.get(
                "max-headers", http_defaults.max_headers)),
        )
        logging_block = raw.get("logging", {}) or {}
        log_defaults = LoggingConfig()
        cfg.logging = LoggingConfig(
            level=str(logging_block.get("level", log_defaults.level)),
            file=logging_block.get("file"),
            when=str(logging_block.get("when", log_defaults.when)),
            backup_count=int(logging_block.get(
                "backup-count", log_defaults.backup_count)),
        )
        cfg.data_dir = raw.get("data-dir", cfg.data_dir)
        server_block = raw.get("omero.server", {}) or {}
        cfg.max_tile_length = int(server_block.get(
            "omero.pixeldata.max_tile_length", cfg.max_tile_length))
        cfg.omero_data_dir = server_block.get("omero.data.dir",
                                              cfg.omero_data_dir)
        cfg.lut_root = server_block.get("omero.script_repo_root",
                                        cfg.lut_root)
        cfg.cache_control_header = raw.get("cache-control-header",
                                           cfg.cache_control_header)
        hc = raw.get("http-cache", {}) or {}
        hc_defaults = HttpCacheConfig()
        cfg.http_cache = HttpCacheConfig(
            enabled=bool(hc.get("enabled", hc_defaults.enabled)),
            epoch=str(hc.get("epoch", hc_defaults.epoch)),
            max_age_s=int(hc.get("max-age-s", hc_defaults.max_age_s)),
            vary_acl=bool(hc.get("vary-acl", hc_defaults.vary_acl)),
            peer_fetch=bool(hc.get("peer-fetch",
                                   hc_defaults.peer_fetch)),
            peer_timeout_ms=float(hc.get(
                "peer-timeout-ms", hc_defaults.peer_timeout_ms)),
        )
        from .httpcache import EPOCH_RE
        if not EPOCH_RE.match(cfg.http_cache.epoch):
            # The epoch rides inside the quoted ETag header: a stray
            # quote/comma/space would corrupt every response header.
            raise ValueError(
                "http-cache.epoch must match [A-Za-z0-9._-]+, got "
                f"{cfg.http_cache.epoch!r}")
        if cfg.http_cache.max_age_s < 0:
            raise ValueError("http-cache.max-age-s must be >= 0 "
                             "(0 = no-cache, revalidate every serve)")
        if cfg.http_cache.peer_timeout_ms <= 0:
            raise ValueError("http-cache.peer-timeout-ms must be > 0")
        web = raw.get("omero.web", {}) or {}
        cfg.session_cookie_name = web.get("session_cookie_name",
                                          cfg.session_cookie_name)
        store = raw.get("session-store", {}) or {}
        cfg.session_store_type = store.get("type")
        cfg.session_store_uri = store.get("uri")
        if store.get("required") is not None:
            cfg.session_store_required = bool(store["required"])
        meta = raw.get("metadata-service", {}) or {}
        cfg.metadata_backend = str(meta.get("type", cfg.metadata_backend))
        cfg.metadata_dsn = meta.get("dsn")
        if cfg.metadata_backend not in ("local", "postgres"):
            raise ValueError(
                "metadata-service.type must be 'local' or 'postgres', "
                f"got {cfg.metadata_backend!r}")
        if cfg.metadata_backend == "postgres" and not cfg.metadata_dsn:
            raise ValueError("metadata-service.type 'postgres' requires "
                             "a dsn")

        redis_cache = raw.get("redis-cache", {}) or {}
        cfg.caches = CacheConfig(
            redis_uri=redis_cache.get("uri"),
            image_region=bool((raw.get("image-region-cache") or {})
                              .get("enabled", False)),
            pixels_metadata=bool((raw.get("pixels-metadata-cache") or {})
                                 .get("enabled", False)),
            shape_mask=bool((raw.get("shape-mask-cache") or {})
                            .get("enabled", False)),
        )
        batcher = raw.get("batcher", {}) or {}
        defaults = BatcherConfig()
        cfg.batcher = BatcherConfig(
            enabled=bool(batcher.get("enabled", defaults.enabled)),
            max_batch=int(batcher.get("max-batch", defaults.max_batch)),
            max_batch_limit=(int(batcher["max-batch-limit"])
                             if batcher.get("max-batch-limit")
                             is not None else None),
            linger_ms=float(batcher.get("linger-ms", defaults.linger_ms)),
            pipeline_depth=int(batcher.get("pipeline-depth",
                                           defaults.pipeline_depth)),
            target_inflight=int(batcher.get("target-inflight",
                                            defaults.target_inflight)),
            device_lanes=int(batcher.get("device-lanes",
                                         defaults.device_lanes)),
        )
        if cfg.batcher.pipeline_depth < 1:
            raise ValueError("batcher.pipeline-depth must be >= 1")
        if cfg.batcher.target_inflight < 1:
            raise ValueError("batcher.target-inflight must be >= 1")
        if cfg.batcher.device_lanes < 1:
            raise ValueError("batcher.device-lanes must be >= 1")
        # An EMPTY "single-flight:" section (all children commented
        # out, the standard pattern in the example config) parses as
        # YAML null and must keep the default — only an explicit value
        # changes it.
        sf = raw.get("single-flight")
        if isinstance(sf, dict):
            cfg.single_flight = bool(sf.get("enabled",
                                            cfg.single_flight))
        elif sf is not None:
            cfg.single_flight = bool(sf)
        rc = raw.get("raw-cache", {}) or {}
        rc_defaults = RawCacheConfig()
        cfg.raw_cache = RawCacheConfig(
            enabled=bool(rc.get("enabled", rc_defaults.enabled)),
            max_bytes=int(rc.get("max-bytes", rc_defaults.max_bytes)),
            prefetch=bool(rc.get("prefetch", rc_defaults.prefetch)),
            digest_dedup=bool(rc.get("digest-dedup",
                                     rc_defaults.digest_dedup)),
        )
        sc = raw.get("sidecar", {}) or {}
        sc_defaults = SidecarConfig()
        cfg.sidecar = SidecarConfig(
            socket=sc.get("socket", sc_defaults.socket),
            role=str(sc.get("role", sc_defaults.role)),
        )
        if cfg.sidecar.role not in ("combined", "frontend", "sidecar",
                                    "split"):
            raise ValueError(f"invalid sidecar.role {cfg.sidecar.role!r}")
        _fleet_raw = raw.get("fleet") or {}
        if cfg.sidecar.role != "combined" and not cfg.sidecar.socket \
                and not (cfg.sidecar.role == "frontend"
                         and _fleet_raw.get("enabled")
                         and _fleet_raw.get("sockets")):
            # A frontend may address a FLEET of sidecars instead of
            # one socket (fleet.enabled + fleet.sockets, parsed
            # below) — enabled must be set too, because create_app
            # only takes the fleet topology when it is.
            raise ValueError(f"sidecar.role {cfg.sidecar.role!r} "
                             f"requires sidecar.socket (or an "
                             f"enabled fleet.sockets list)")
        wi = raw.get("wire", {}) or {}
        wi_defaults = WireConfig()
        cfg.wire = WireConfig(
            coalesce_max_frames=int(wi.get(
                "coalesce-max-frames", wi_defaults.coalesce_max_frames)),
            coalesce_max_bytes=int(wi.get(
                "coalesce-max-bytes", wi_defaults.coalesce_max_bytes)),
            ring_bytes=int(wi.get("ring-bytes", wi_defaults.ring_bytes)),
            ring_min_body_bytes=int(wi.get(
                "ring-min-body-bytes", wi_defaults.ring_min_body_bytes)),
            streaming=bool(wi.get("streaming", wi_defaults.streaming)),
            chunk_max_bytes=int(wi.get(
                "chunk-max-bytes", wi_defaults.chunk_max_bytes)),
        )
        if cfg.wire.coalesce_max_frames < 1:
            raise ValueError("wire.coalesce-max-frames must be >= 1")
        if cfg.wire.coalesce_max_bytes < 4096:
            raise ValueError("wire.coalesce-max-bytes must be >= 4096")
        if cfg.wire.ring_bytes != 0 and cfg.wire.ring_bytes < 1024 * 1024:
            raise ValueError("wire.ring-bytes must be 0 (disabled) or "
                             ">= 1 MiB")
        if cfg.wire.ring_min_body_bytes < 1:
            raise ValueError("wire.ring-min-body-bytes must be >= 1")
        if cfg.wire.chunk_max_bytes < 4096:
            raise ValueError("wire.chunk-max-bytes must be >= 4096")
        fl = raw.get("fleet", {}) or {}
        fl_defaults = FleetConfig()
        cfg.fleet = FleetConfig(
            enabled=bool(fl.get("enabled", fl_defaults.enabled)),
            members=int(fl.get("members", fl_defaults.members)),
            sockets=tuple(str(s) for s in fl.get("sockets", ())
                          or ()),
            lane_width=int(fl.get("lane-width",
                                  fl_defaults.lane_width)),
            steal_min_backlog=int(fl.get(
                "steal-min-backlog", fl_defaults.steal_min_backlog)),
            hash_replicas=int(fl.get("hash-replicas",
                                     fl_defaults.hash_replicas)),
            failover=bool(fl.get("failover", fl_defaults.failover)),
            down_cooldown_s=float(fl.get(
                "down-cooldown-s", fl_defaults.down_cooldown_s)),
        )
        if cfg.fleet.enabled:
            if not cfg.fleet.sockets and cfg.fleet.members < 2:
                raise ValueError("fleet.enabled requires members >= 2 "
                                 "or a fleet.sockets list")
        if cfg.fleet.members < 1:
            raise ValueError("fleet.members must be >= 1")
        if cfg.fleet.lane_width < 1:
            raise ValueError("fleet.lane-width must be >= 1")
        if cfg.fleet.steal_min_backlog < 0:
            raise ValueError("fleet.steal-min-backlog must be >= 0 "
                             "(0 disables stealing)")
        if cfg.fleet.hash_replicas < 1:
            raise ValueError("fleet.hash-replicas must be >= 1")
        if cfg.fleet.down_cooldown_s < 0:
            raise ValueError("fleet.down-cooldown-s must be >= 0")
        hk = raw.get("hotkey", {}) or {}
        hk_defaults = HotkeyConfig()
        cfg.hotkey = HotkeyConfig(
            enabled=bool(hk.get("enabled", hk_defaults.enabled)),
            threshold=float(hk.get("threshold",
                                   hk_defaults.threshold)),
            decay_s=float(hk.get("decay-s", hk_defaults.decay_s)),
            max_replicas=int(hk.get("max-replicas",
                                    hk_defaults.max_replicas)),
            top_k=int(hk.get("top-k", hk_defaults.top_k)),
            demote_fraction=float(hk.get(
                "demote-fraction", hk_defaults.demote_fraction)),
            scale_factor=float(hk.get("scale-factor",
                                      hk_defaults.scale_factor)),
        )
        if cfg.hotkey.threshold <= 0:
            raise ValueError("hotkey.threshold must be > 0")
        if cfg.hotkey.decay_s <= 0:
            raise ValueError("hotkey.decay-s must be > 0")
        if cfg.hotkey.max_replicas < 2:
            raise ValueError("hotkey.max-replicas must be >= 2 "
                             "(R=1 is the unreplicated ring)")
        if cfg.hotkey.top_k < 1:
            raise ValueError("hotkey.top-k must be >= 1")
        if not 0.0 < cfg.hotkey.demote_fraction < 1.0:
            raise ValueError("hotkey.demote-fraction must be in "
                             "(0, 1) — the promotion/demotion "
                             "hysteresis band")
        if cfg.hotkey.scale_factor < 0:
            raise ValueError("hotkey.scale-factor must be >= 0 "
                             "(0 disables the autoscaler signal)")
        fe = raw.get("federation", {}) or {}
        fe_defaults = FederationConfig()
        members_raw = fe.get("members", ()) or ()
        if not isinstance(members_raw, (list, tuple)):
            raise ValueError("federation.members must be a list of "
                             "{name, host, address?} entries")
        fed_members = []
        for i, m in enumerate(members_raw):
            if not isinstance(m, dict) or not m.get("name") \
                    or not m.get("host"):
                raise ValueError(
                    f"federation.members[{i}] must be a mapping with "
                    f"at least name and host")
            fed_members.append({
                "name": str(m["name"]), "host": str(m["host"]),
                "address": str(m.get("address") or "")})
        cfg.federation = FederationConfig(
            enabled=bool(fe.get("enabled", fe_defaults.enabled)),
            host=str(fe.get("host", fe_defaults.host) or ""),
            shard_epoch=int(fe.get("shard-epoch",
                                   fe_defaults.shard_epoch)),
            ring_seed=str(fe.get("ring-seed",
                                 fe_defaults.ring_seed) or ""),
            hash_replicas=int(fe.get("hash-replicas",
                                     fe_defaults.hash_replicas)),
            gossip_interval_s=float(fe.get(
                "gossip-interval-s", fe_defaults.gossip_interval_s)),
            quorum=bool(fe.get("quorum", fe_defaults.quorum)),
            suspect_after_s=float(fe.get(
                "suspect-after-s", fe_defaults.suspect_after_s)),
            roll_ack_timeout_s=float(fe.get(
                "roll-ack-timeout-s",
                fe_defaults.roll_ack_timeout_s)),
            members=tuple(fed_members),
        )
        if cfg.federation.shard_epoch < 1:
            raise ValueError("federation.shard-epoch must be >= 1 "
                             "(bump it with every membership change)")
        if cfg.federation.hash_replicas < 1:
            raise ValueError("federation.hash-replicas must be >= 1")
        if cfg.federation.gossip_interval_s <= 0:
            raise ValueError("federation.gossip-interval-s must be "
                             "> 0")
        if cfg.federation.suspect_after_s <= 0:
            raise ValueError("federation.suspect-after-s must be > 0")
        if cfg.federation.roll_ack_timeout_s <= 0:
            raise ValueError("federation.roll-ack-timeout-s must be "
                             "> 0")
        if cfg.federation.quorum and not cfg.federation.enabled:
            raise ValueError("federation.quorum requires "
                             "federation.enabled (quorum is a verdict "
                             "over manifest hosts)")
        if cfg.federation.enabled:
            if len(cfg.federation.members) < 2:
                raise ValueError("federation.enabled requires >= 2 "
                                 "members (one host needs no "
                                 "federation — use fleet.members)")
            names = [m["name"] for m in cfg.federation.members]
            if len(set(names)) != len(names):
                raise ValueError("federation.members names must be "
                                 "unique fleet-wide")
            if not cfg.federation.host:
                # Default this process's identity from the cluster
                # layer (``procN`` when jax.distributed is joined,
                # else the OS hostname) — multi-host manifests stop
                # needing an explicit host string per process.  It
                # must still name a manifest member; the check below
                # catches a hostname the manifest never heard of.
                from ..parallel.cluster import host_identity
                cfg.federation.host = host_identity()
            hosts = {m["host"] for m in cfg.federation.members}
            if cfg.federation.host not in hosts:
                raise ValueError(
                    f"federation.host {cfg.federation.host!r} owns no "
                    f"manifest member (hosts: {sorted(hosts)}); set "
                    f"federation.host explicitly, or name manifest "
                    f"hosts by cluster.host_identity() — the default "
                    f"when the key is omitted")
            # NOTE: remote members' addresses are validated where the
            # topology is actually built (build_federated_members —
            # only a process that ROUTES needs to reach them; a
            # passive sidecar member answering manifest_hello does
            # not), so a member-process config may legally omit
            # addresses it never dials.
            if cfg.fleet.sockets:
                raise ValueError(
                    "federation.enabled and fleet.sockets are "
                    "mutually exclusive — the manifest IS the "
                    "membership; list remote members with addresses "
                    "in federation.members instead")
        par = raw.get("parallel", {}) or {}
        par_defaults = ParallelConfig()
        cfg.parallel = ParallelConfig(
            enabled=bool(par.get("enabled", par_defaults.enabled)),
            chan_parallel=int(par.get("chan-parallel",
                                      par_defaults.chan_parallel)),
            n_devices=(int(par["n-devices"])
                       if par.get("n-devices") is not None else None),
            coordinator_address=par.get("coordinator-address"),
            num_processes=(int(par["num-processes"])
                           if par.get("num-processes") is not None
                           else None),
            process_id=(int(par["process-id"])
                        if par.get("process-id") is not None else None),
        )
        if cfg.parallel.chan_parallel < 1:
            raise ValueError("parallel.chan-parallel must be >= 1")
        if (cfg.parallel.coordinator_address is not None
                and cfg.parallel.num_processes is None):
            raise ValueError("parallel.coordinator-address requires "
                             "num-processes and process-id")
        per = raw.get("persistence", {}) or {}
        per_defaults = PersistenceConfig()
        cfg.persistence = PersistenceConfig(
            enabled=bool(per.get("enabled", per_defaults.enabled)),
            dir=str(per.get("dir", per_defaults.dir)),
            disk_cache_max_bytes=int(per.get(
                "disk-cache-max-bytes",
                per_defaults.disk_cache_max_bytes)),
            executables=bool(per.get("executables",
                                     per_defaults.executables)),
            snapshot_interval_s=float(per.get(
                "snapshot-interval-s",
                per_defaults.snapshot_interval_s)),
            snapshot_top_k=int(per.get("snapshot-top-k",
                                       per_defaults.snapshot_top_k)),
            max_plane_entries=int(per.get(
                "max-plane-entries", per_defaults.max_plane_entries)),
            rehydrate=bool(per.get("rehydrate",
                                   per_defaults.rehydrate)),
            rehydrate_concurrency=int(per.get(
                "rehydrate-concurrency",
                per_defaults.rehydrate_concurrency)),
        )
        if cfg.persistence.disk_cache_max_bytes < 1024 * 1024:
            raise ValueError("persistence.disk-cache-max-bytes must "
                             "be >= 1 MiB")
        if cfg.persistence.snapshot_interval_s < 0:
            raise ValueError("persistence.snapshot-interval-s must be "
                             ">= 0 (0 disables the timer)")
        if cfg.persistence.rehydrate_concurrency < 1:
            raise ValueError("persistence.rehydrate-concurrency must "
                             "be >= 1")
        if cfg.persistence.snapshot_top_k < 1:
            raise ValueError("persistence.snapshot-top-k must be >= 1")
        se = raw.get("sessions", {}) or {}
        se_defaults = SessionsConfig()
        cfg.sessions = SessionsConfig(
            enabled=bool(se.get("enabled", se_defaults.enabled)),
            bucket_refill_per_s=float(se.get(
                "bucket-refill-per-s",
                se_defaults.bucket_refill_per_s)),
            bucket_burst=float(se.get("bucket-burst",
                                      se_defaults.bucket_burst)),
            max_tracked=int(se.get("max-tracked",
                                   se_defaults.max_tracked)),
            prefetch_lookahead=int(se.get(
                "prefetch-lookahead", se_defaults.prefetch_lookahead)),
        )
        if cfg.sessions.bucket_refill_per_s <= 0:
            raise ValueError("sessions.bucket-refill-per-s must be "
                             "> 0")
        if cfg.sessions.bucket_burst < 1:
            raise ValueError("sessions.bucket-burst must be >= 1")
        if cfg.sessions.max_tracked < 1:
            raise ValueError("sessions.max-tracked must be >= 1")
        if cfg.sessions.prefetch_lookahead < 1:
            raise ValueError("sessions.prefetch-lookahead must be "
                             ">= 1")
        lm = raw.get("loadmodel", {}) or {}
        lm_defaults = LoadModelConfig()
        cfg.loadmodel = LoadModelConfig(
            seed=int(lm.get("seed", lm_defaults.seed)),
            viewers=int(lm.get("viewers", lm_defaults.viewers)),
            think_time_median_ms=float(lm.get(
                "think-time-median-ms",
                lm_defaults.think_time_median_ms)),
            think_time_sigma=float(lm.get(
                "think-time-sigma", lm_defaults.think_time_sigma)),
            session_length_median=float(lm.get(
                "session-length-median",
                lm_defaults.session_length_median)),
            session_length_sigma=float(lm.get(
                "session-length-sigma",
                lm_defaults.session_length_sigma)),
            diurnal_amplitude=float(lm.get(
                "diurnal-amplitude", lm_defaults.diurnal_amplitude)),
            bulk_fraction=float(lm.get(
                "bulk-fraction", lm_defaults.bulk_fraction)),
            mask_fraction=float(lm.get(
                "mask-fraction", lm_defaults.mask_fraction)),
            pyramid_fraction=float(lm.get(
                "pyramid-fraction", lm_defaults.pyramid_fraction)),
            animation_fraction=float(lm.get(
                "animation-fraction", lm_defaults.animation_fraction)),
            zoom_fraction=float(lm.get(
                "zoom-fraction", lm_defaults.zoom_fraction)),
            skew=float(lm.get("skew", lm_defaults.skew)),
            image_population=int(lm.get(
                "image-population", lm_defaults.image_population)),
        )
        # The generator itself re-validates at construction; failing
        # at config load keeps a bad block out of a bench round.
        if cfg.loadmodel.viewers < 1:
            raise ValueError("loadmodel.viewers must be >= 1")
        if cfg.loadmodel.think_time_median_ms <= 0 \
                or cfg.loadmodel.session_length_median <= 0:
            raise ValueError("loadmodel medians must be > 0")
        if cfg.loadmodel.think_time_sigma < 0 \
                or cfg.loadmodel.session_length_sigma < 0:
            raise ValueError("loadmodel sigmas must be >= 0")
        if not 0.0 <= cfg.loadmodel.diurnal_amplitude < 1.0:
            raise ValueError("loadmodel.diurnal-amplitude must be in "
                             "[0, 1)")
        for name in ("bulk_fraction", "mask_fraction",
                     "pyramid_fraction", "animation_fraction",
                     "zoom_fraction"):
            v = getattr(cfg.loadmodel, name)
            if not 0.0 <= v <= 1.0:
                raise ValueError(
                    f"loadmodel.{name.replace('_', '-')} must be in "
                    f"[0, 1]")
        if (cfg.loadmodel.bulk_fraction
                + cfg.loadmodel.mask_fraction
                + cfg.loadmodel.pyramid_fraction
                + cfg.loadmodel.animation_fraction) > 1.0:
            raise ValueError("loadmodel bulk-fraction + mask-fraction "
                             "+ pyramid-fraction + animation-fraction "
                             "must sum to <= 1")
        if cfg.loadmodel.skew < 0:
            raise ValueError("loadmodel.skew must be >= 0 "
                             "(0 = every session on one image)")
        if cfg.loadmodel.image_population < 1:
            raise ValueError("loadmodel.image-population must be "
                             ">= 1")
        wl = raw.get("workloads", {}) or {}
        wl_defaults = WorkloadsConfig()
        cfg.workloads = WorkloadsConfig(
            device_masks=bool(wl.get("device-masks",
                                     wl_defaults.device_masks)),
            overlay_enabled=bool(wl.get("overlay-enabled",
                                        wl_defaults.overlay_enabled)),
            animation_enabled=bool(wl.get(
                "animation-enabled", wl_defaults.animation_enabled)),
            animation_max_frames=int(wl.get(
                "animation-max-frames",
                wl_defaults.animation_max_frames)),
        )
        if cfg.workloads.animation_max_frames < 1:
            raise ValueError("workloads.animation-max-frames must be "
                             ">= 1")
        py = raw.get("pyramid", {}) or {}
        py_defaults = PyramidConfig()
        cfg.pyramid = PyramidConfig(
            enabled=bool(py.get("enabled", py_defaults.enabled)),
            chunk=int(py.get("chunk", py_defaults.chunk)),
            min_level_size=int(py.get("min-level-size",
                                      py_defaults.min_level_size)),
            compressor=str(py.get("compressor",
                                  py_defaults.compressor)),
            defer_poll_s=float(py.get("defer-poll-s",
                                      py_defaults.defer_poll_s)),
        )
        if cfg.pyramid.chunk < 16:
            raise ValueError("pyramid.chunk must be >= 16")
        if cfg.pyramid.min_level_size < 1:
            raise ValueError("pyramid.min-level-size must be >= 1")
        if cfg.pyramid.compressor not in ("zlib", "gzip", "none"):
            raise ValueError("pyramid.compressor must be zlib, gzip, "
                             "or none")
        if cfg.pyramid.defer_poll_s <= 0:
            raise ValueError("pyramid.defer-poll-s must be > 0")
        au = raw.get("autoscaler", {}) or {}
        au_defaults = AutoscalerConfig()
        cfg.autoscaler = AutoscalerConfig(
            enabled=bool(au.get("enabled", au_defaults.enabled)),
            interval_s=float(au.get("interval-s",
                                    au_defaults.interval_s)),
            floor=int(au.get("floor", au_defaults.floor)),
            ceiling=int(au.get("ceiling", au_defaults.ceiling)),
            queue_high_per_lane=float(au.get(
                "queue-high-per-lane",
                au_defaults.queue_high_per_lane)),
            queue_low_per_lane=float(au.get(
                "queue-low-per-lane", au_defaults.queue_low_per_lane)),
            hold_ticks=int(au.get("hold-ticks",
                                  au_defaults.hold_ticks)),
            cooldown_s=float(au.get("cooldown-s",
                                    au_defaults.cooldown_s)),
            lane_capacity_tps=float(au.get(
                "lane-capacity-tps", au_defaults.lane_capacity_tps)),
            session_tps=float(au.get("session-tps",
                                     au_defaults.session_tps)),
            diurnal_period_s=float(au.get(
                "diurnal-period-s", au_defaults.diurnal_period_s)),
            diurnal_horizon_s=float(au.get(
                "diurnal-horizon-s", au_defaults.diurnal_horizon_s)),
            unit_config=str(au.get("unit-config",
                                   au_defaults.unit_config) or ""),
        )
        if cfg.autoscaler.interval_s <= 0:
            raise ValueError("autoscaler.interval-s must be > 0")
        if cfg.autoscaler.floor < 1:
            raise ValueError("autoscaler.floor must be >= 1 (the "
                             "fleet must always keep a servable "
                             "member)")
        if cfg.autoscaler.ceiling != 0 \
                and cfg.autoscaler.ceiling < cfg.autoscaler.floor:
            raise ValueError("autoscaler.ceiling must be 0 (all "
                             "members) or >= autoscaler.floor")
        if not 0 <= cfg.autoscaler.queue_low_per_lane \
                < cfg.autoscaler.queue_high_per_lane:
            raise ValueError(
                "autoscaler.queue-low-per-lane must be in [0, "
                "queue-high-per-lane) — the hysteresis band needs "
                "low < high")
        if cfg.autoscaler.hold_ticks < 1:
            raise ValueError("autoscaler.hold-ticks must be >= 1")
        if cfg.autoscaler.cooldown_s < 0:
            raise ValueError("autoscaler.cooldown-s must be >= 0")
        if cfg.autoscaler.lane_capacity_tps < 0:
            raise ValueError("autoscaler.lane-capacity-tps must be "
                             ">= 0 (0 disables the demand signal)")
        if cfg.autoscaler.session_tps <= 0:
            raise ValueError("autoscaler.session-tps must be > 0")
        if cfg.autoscaler.diurnal_period_s < 0:
            raise ValueError("autoscaler.diurnal-period-s must be "
                             ">= 0 (0 disables diurnal prediction)")
        if cfg.autoscaler.diurnal_horizon_s < 0:
            raise ValueError("autoscaler.diurnal-horizon-s must be "
                             ">= 0")
        if cfg.autoscaler.unit_config and not cfg.fleet.sockets:
            raise ValueError(
                "autoscaler.unit-config manages sidecar unit "
                "processes — it requires the fleet.sockets topology")
        if cfg.autoscaler.enabled and not (cfg.fleet.enabled
                                           or cfg.federation.enabled):
            raise ValueError(
                "autoscaler.enabled requires a fleet topology "
                "(fleet.enabled or federation.enabled) — there is "
                "nothing to scale without members")
        if cfg.autoscaler.enabled:
            provisioned = (len(cfg.federation.members)
                           if cfg.federation.enabled
                           else (len(cfg.fleet.sockets)
                                 or cfg.fleet.members))
            if cfg.autoscaler.floor > provisioned:
                # An unachievable floor would block every scale-down
                # forever (blocked:floor) — the bad-block-fails-at-
                # load contract, not a silent mid-serving no-op.
                raise ValueError(
                    f"autoscaler.floor ({cfg.autoscaler.floor}) "
                    f"exceeds the provisioned fleet size "
                    f"({provisioned} members)")
        qo = raw.get("qos", {}) or {}
        qo_defaults = QosConfig()
        cfg.qos = QosConfig(
            enabled=bool(qo.get("enabled", qo_defaults.enabled)),
            interactive_weight=int(qo.get(
                "interactive-weight", qo_defaults.interactive_weight)),
            bulk_cost=float(qo.get("bulk-cost",
                                   qo_defaults.bulk_cost)),
        )
        if cfg.qos.interactive_weight < 1:
            raise ValueError("qos.interactive-weight must be >= 1")
        if cfg.qos.bulk_cost < 1:
            raise ValueError("qos.bulk-cost must be >= 1")
        pr = raw.get("pressure", {}) or {}
        pr_defaults = PressureConfig()
        cfg.pressure = PressureConfig(
            enabled=bool(pr.get("enabled", pr_defaults.enabled)),
            interval_s=float(pr.get("interval-s",
                                    pr_defaults.interval_s)),
            hbm_high=float(pr.get("hbm-high", pr_defaults.hbm_high)),
            hbm_low=float(pr.get("hbm-low", pr_defaults.hbm_low)),
            host_rss_high_mb=float(pr.get(
                "host-rss-high-mb", pr_defaults.host_rss_high_mb)),
            host_rss_low_mb=float(pr.get(
                "host-rss-low-mb", pr_defaults.host_rss_low_mb)),
            disk_high=float(pr.get("disk-high",
                                   pr_defaults.disk_high)),
            disk_low=float(pr.get("disk-low", pr_defaults.disk_low)),
            queue_high=int(pr.get("queue-high",
                                  pr_defaults.queue_high)),
            queue_low=int(pr.get("queue-low", pr_defaults.queue_low)),
            loop_lag_high_ms=float(pr.get(
                "loop-lag-high-ms", pr_defaults.loop_lag_high_ms)),
            loop_lag_low_ms=float(pr.get(
                "loop-lag-low-ms", pr_defaults.loop_lag_low_ms)),
            critical_factor=float(pr.get(
                "critical-factor", pr_defaults.critical_factor)),
            step_hold_ticks=int(pr.get(
                "step-hold-ticks", pr_defaults.step_hold_ticks)),
            release_hold_ticks=int(pr.get(
                "release-hold-ticks", pr_defaults.release_hold_ticks)),
            ladder=tuple(str(s) for s in pr.get("ladder", ()) or ())
            or pr_defaults.ladder,
            quality_cap=int(pr.get("quality-cap",
                                   pr_defaults.quality_cap)),
            evict_to_frac=float(pr.get(
                "evict-to-frac", pr_defaults.evict_to_frac)),
            lane_cap=int(pr.get("lane-cap", pr_defaults.lane_cap)),
            admission_scale=float(pr.get(
                "admission-scale", pr_defaults.admission_scale)),
            prefetch_budget_elevated=float(pr.get(
                "prefetch-budget-elevated",
                pr_defaults.prefetch_budget_elevated)),
            prefetch_budget_critical=float(pr.get(
                "prefetch-budget-critical",
                pr_defaults.prefetch_budget_critical)),
        )
        if cfg.pressure.interval_s <= 0:
            raise ValueError("pressure.interval-s must be > 0")
        from .pressure import KNOWN_STEPS
        seen_steps = set()
        for step in cfg.pressure.ladder:
            if step not in KNOWN_STEPS:
                raise ValueError(
                    f"pressure.ladder step {step!r} is not one of "
                    f"{sorted(KNOWN_STEPS)}")
            if step in seen_steps:
                raise ValueError(
                    f"pressure.ladder repeats step {step!r}")
            seen_steps.add(step)
        if ("shed_bulk" in seen_steps
                and "tighten_admission" in seen_steps
                and cfg.pressure.ladder.index("shed_bulk")
                > cfg.pressure.ladder.index("tighten_admission")):
            # The availability-ordering invariant: interactive tiles
            # are never shed before bulk/projection work.
            raise ValueError(
                "pressure.ladder must engage shed_bulk before "
                "tighten_admission (bulk work sheds first; "
                "interactive availability goes last)")
        for pair in (("hbm_high", "hbm_low"),
                     ("host_rss_high_mb", "host_rss_low_mb"),
                     ("disk_high", "disk_low"),
                     ("queue_high", "queue_low"),
                     ("loop_lag_high_ms", "loop_lag_low_ms")):
            high, low = (getattr(cfg.pressure, pair[0]),
                         getattr(cfg.pressure, pair[1]))
            if high > 0 and not 0 <= low < high:
                raise ValueError(
                    f"pressure.{pair[1].replace('_', '-')} must be in "
                    f"[0, {pair[0].replace('_', '-')}) — the "
                    f"hysteresis band needs low < high")
        if cfg.pressure.critical_factor < 1.0:
            raise ValueError("pressure.critical-factor must be >= 1")
        if cfg.pressure.step_hold_ticks < 1 \
                or cfg.pressure.release_hold_ticks < 1:
            raise ValueError("pressure step/release hold ticks must "
                             "be >= 1")
        if not 1 <= cfg.pressure.quality_cap <= 100:
            raise ValueError("pressure.quality-cap must be in "
                             "[1, 100]")
        if not 0.0 < cfg.pressure.evict_to_frac < 1.0:
            raise ValueError("pressure.evict-to-frac must be in "
                             "(0, 1)")
        if cfg.pressure.lane_cap < 1:
            raise ValueError("pressure.lane-cap must be >= 1")
        if not 0.0 < cfg.pressure.admission_scale <= 1.0:
            raise ValueError("pressure.admission-scale must be in "
                             "(0, 1]")
        if not (0.0 < cfg.pressure.prefetch_budget_critical
                <= cfg.pressure.prefetch_budget_elevated <= 1.0):
            # Monotone by construction: more pressure can never mean
            # MORE speculative staging.
            raise ValueError(
                "pressure prefetch budgets must satisfy 0 < "
                "prefetch-budget-critical <= "
                "prefetch-budget-elevated <= 1")
        wd = raw.get("watchdog", {}) or {}
        wd_defaults = WatchdogConfig()
        cfg.watchdog = WatchdogConfig(
            enabled=bool(wd.get("enabled", wd_defaults.enabled)),
            interval_s=float(wd.get("interval-s",
                                    wd_defaults.interval_s)),
            stall_factor=float(wd.get("stall-factor",
                                      wd_defaults.stall_factor)),
            stall_min_s=float(wd.get("stall-min-s",
                                     wd_defaults.stall_min_s)),
            wire_hang_s=float(wd.get("wire-hang-s",
                                     wd_defaults.wire_hang_s)),
            escalate_after=int(wd.get("escalate-after",
                                      wd_defaults.escalate_after)),
        )
        if cfg.watchdog.interval_s <= 0:
            raise ValueError("watchdog.interval-s must be > 0")
        if cfg.watchdog.stall_factor < 1.0:
            raise ValueError("watchdog.stall-factor must be >= 1")
        if cfg.watchdog.stall_min_s <= 0:
            raise ValueError("watchdog.stall-min-s must be > 0 (the "
                             "floor keeps cold compiles from reading "
                             "as stalls)")
        if cfg.watchdog.wire_hang_s < 0:
            raise ValueError("watchdog.wire-hang-s must be >= 0 "
                             "(0 disables the wire check)")
        if cfg.watchdog.escalate_after < 1:
            raise ValueError("watchdog.escalate-after must be >= 1")
        dr = raw.get("drain", {}) or {}
        dr_defaults = DrainConfig()
        cfg.drain = DrainConfig(
            prestage=bool(dr.get("prestage", dr_defaults.prestage)),
            prestage_max_planes=int(dr.get(
                "prestage-max-planes",
                dr_defaults.prestage_max_planes)),
            settle_timeout_s=float(dr.get(
                "settle-timeout-s", dr_defaults.settle_timeout_s)),
            fail_readyz=bool(dr.get("fail-readyz",
                                    dr_defaults.fail_readyz)),
        )
        if cfg.drain.prestage_max_planes < 1:
            raise ValueError("drain.prestage-max-planes must be >= 1")
        if cfg.drain.settle_timeout_s <= 0:
            raise ValueError("drain.settle-timeout-s must be > 0")
        tel = raw.get("telemetry", {}) or {}
        tel_defaults = TelemetryConfig()
        cfg.telemetry = TelemetryConfig(
            slow_request_ms=float(tel.get("slow-request-ms",
                                          tel_defaults.slow_request_ms)),
            slow_request_dir=str(tel.get(
                "slow-request-dir", tel_defaults.slow_request_dir)),
            access_log=bool(tel.get("access-log",
                                    tel_defaults.access_log)),
            ready_max_queue_depth=int(tel.get(
                "ready-max-queue-depth",
                tel_defaults.ready_max_queue_depth)),
            flight_recorder_events=int(tel.get(
                "flight-recorder-events",
                tel_defaults.flight_recorder_events)),
            flight_recorder_dir=str(tel.get(
                "flight-recorder-dir",
                tel_defaults.flight_recorder_dir)),
            profile_dir=str(tel.get("profile-dir",
                                    tel_defaults.profile_dir)),
            profile_max_ms=float(tel.get(
                "profile-max-ms", tel_defaults.profile_max_ms)),
            provenance_header=bool(tel.get(
                "provenance-header",
                tel_defaults.provenance_header)),
        )
        if cfg.telemetry.slow_request_ms < 0:
            raise ValueError("telemetry.slow-request-ms must be >= 0")
        if cfg.telemetry.ready_max_queue_depth < 1:
            raise ValueError("telemetry.ready-max-queue-depth must be "
                             ">= 1")
        if cfg.telemetry.flight_recorder_events < 16:
            raise ValueError("telemetry.flight-recorder-events must be "
                             ">= 16 (the black box needs some tape)")
        if cfg.telemetry.profile_max_ms <= 0:
            raise ValueError("telemetry.profile-max-ms must be > 0")
        slo = raw.get("slo", {}) or {}
        slo_defaults = SloConfig()
        cfg.slo = SloConfig(
            availability_target=float(slo.get(
                "availability-target",
                slo_defaults.availability_target)),
            latency_ms=float(slo.get("latency-ms",
                                     slo_defaults.latency_ms)),
            latency_target=float(slo.get(
                "latency-target", slo_defaults.latency_target)),
            fast_window_s=float(slo.get(
                "fast-window-s", slo_defaults.fast_window_s)),
            slow_window_s=float(slo.get(
                "slow-window-s", slo_defaults.slow_window_s)),
            breach_burn_rate=float(slo.get(
                "breach-burn-rate", slo_defaults.breach_burn_rate)),
        )
        for name in ("availability_target", "latency_target"):
            v = getattr(cfg.slo, name)
            if not 0.0 <= v < 1.0:
                raise ValueError(
                    f"slo.{name.replace('_', '-')} must be in [0, 1) "
                    f"(a target of 1.0 leaves zero error budget), "
                    f"got {v}")
        if cfg.slo.latency_ms < 0:
            raise ValueError("slo.latency-ms must be >= 0")
        if cfg.slo.fast_window_s <= 0 or cfg.slo.slow_window_s <= 0:
            raise ValueError("slo windows must be > 0 seconds")
        if cfg.slo.breach_burn_rate <= 0:
            raise ValueError("slo.breach-burn-rate must be > 0")
        dec = raw.get("decisions", {}) or {}
        dec_defaults = DecisionsConfig()
        cfg.decisions = DecisionsConfig(
            ring_size=int(dec.get("ring-size",
                                  dec_defaults.ring_size)),
            spool_dir=str(dec.get("spool-dir",
                                  dec_defaults.spool_dir) or ""),
            outcome_horizon_ticks=int(dec.get(
                "outcome-horizon-ticks",
                dec_defaults.outcome_horizon_ticks)),
        )
        if cfg.decisions.ring_size < 16:
            raise ValueError("decisions.ring-size must be >= 16")
        if cfg.decisions.outcome_horizon_ticks < 1:
            raise ValueError(
                "decisions.outcome-horizon-ticks must be >= 1")
        sen = raw.get("sentinel", {}) or {}
        sen_defaults = SentinelConfig()
        cfg.sentinel = SentinelConfig(
            enabled=bool(sen.get("enabled", sen_defaults.enabled)),
            tick_interval_s=float(sen.get(
                "tick-interval-s", sen_defaults.tick_interval_s)),
            confirm_ticks=int(sen.get(
                "confirm-ticks", sen_defaults.confirm_ticks)),
            recover_ticks=int(sen.get(
                "recover-ticks", sen_defaults.recover_ticks)),
            min_samples=int(sen.get(
                "min-samples", sen_defaults.min_samples)),
            warmup_ticks=int(sen.get(
                "warmup-ticks", sen_defaults.warmup_ticks)),
            drift_ratio=float(sen.get(
                "drift-ratio", sen_defaults.drift_ratio)),
            baseline_alpha=float(sen.get(
                "baseline-alpha", sen_defaults.baseline_alpha)),
            throughput_floor_ratio=float(sen.get(
                "throughput-floor-ratio",
                sen_defaults.throughput_floor_ratio)),
            bundle_dir=str(sen.get(
                "bundle-dir", sen_defaults.bundle_dir) or ""),
            max_bundles=int(sen.get(
                "max-bundles", sen_defaults.max_bundles)),
            profile_ms=int(sen.get(
                "profile-ms", sen_defaults.profile_ms)),
            records_dir=str(sen.get(
                "records-dir", sen_defaults.records_dir) or ""),
        )
        if cfg.sentinel.tick_interval_s <= 0:
            raise ValueError("sentinel.tick-interval-s must be > 0")
        if cfg.sentinel.confirm_ticks < 1:
            raise ValueError("sentinel.confirm-ticks must be >= 1 "
                             "(a zero-confirmation sentinel would "
                             "page on one slow window)")
        if cfg.sentinel.recover_ticks < 1:
            raise ValueError("sentinel.recover-ticks must be >= 1")
        if cfg.sentinel.min_samples < 1:
            raise ValueError("sentinel.min-samples must be >= 1")
        if cfg.sentinel.warmup_ticks < 1:
            raise ValueError("sentinel.warmup-ticks must be >= 1")
        if cfg.sentinel.drift_ratio <= 1.0:
            raise ValueError(
                "sentinel.drift-ratio must be > 1.0 (a ratio at or "
                "under 1.0 calls steady state a drift)")
        if not 0.0 < cfg.sentinel.baseline_alpha <= 1.0:
            raise ValueError(
                "sentinel.baseline-alpha must be in (0, 1]")
        if not 0.0 < cfg.sentinel.throughput_floor_ratio <= 1.0:
            raise ValueError(
                "sentinel.throughput-floor-ratio must be in (0, 1]")
        if cfg.sentinel.max_bundles < 1:
            raise ValueError("sentinel.max-bundles must be >= 1")
        if cfg.sentinel.profile_ms < 0:
            raise ValueError("sentinel.profile-ms must be >= 0")
        ft = raw.get("fault-tolerance", {}) or {}
        ft_defaults = FaultToleranceConfig()
        cfg.fault_tolerance = FaultToleranceConfig(
            request_deadline_ms=float(ft.get(
                "request-deadline-ms",
                ft_defaults.request_deadline_ms)),
            breaker_failure_threshold=int(ft.get(
                "breaker-failure-threshold",
                ft_defaults.breaker_failure_threshold)),
            breaker_reset_s=float(ft.get(
                "breaker-reset-s", ft_defaults.breaker_reset_s)),
            retry_max_attempts=int(ft.get(
                "retry-max-attempts", ft_defaults.retry_max_attempts)),
            retry_base_backoff_ms=float(ft.get(
                "retry-base-backoff-ms",
                ft_defaults.retry_base_backoff_ms)),
            retry_max_backoff_ms=float(ft.get(
                "retry-max-backoff-ms",
                ft_defaults.retry_max_backoff_ms)),
            admission_max_queue=int(ft.get(
                "admission-max-queue",
                ft_defaults.admission_max_queue)),
            shed_retry_after_s=float(ft.get(
                "shed-retry-after-s", ft_defaults.shed_retry_after_s)),
            degraded_mode=bool(ft.get("degraded-mode",
                                      ft_defaults.degraded_mode)),
            supervise=bool(ft.get("supervise", ft_defaults.supervise)),
            supervisor_max_backoff_s=float(ft.get(
                "supervisor-max-backoff-s",
                ft_defaults.supervisor_max_backoff_s)),
        )
        if cfg.fault_tolerance.request_deadline_ms < 0:
            raise ValueError("fault-tolerance.request-deadline-ms must "
                             "be >= 0")
        if cfg.fault_tolerance.breaker_failure_threshold < 1:
            raise ValueError("fault-tolerance.breaker-failure-threshold "
                             "must be >= 1")
        if cfg.fault_tolerance.retry_max_attempts < 1:
            raise ValueError("fault-tolerance.retry-max-attempts must "
                             "be >= 1")
        if cfg.fault_tolerance.admission_max_queue < 0:
            raise ValueError("fault-tolerance.admission-max-queue must "
                             "be >= 0 (0 disables admission control)")
        fi = raw.get("fault-injection", {}) or {}
        fi_defaults = FaultInjectionConfig()
        cfg.fault_injection = FaultInjectionConfig(
            seed=(int(fi["seed"]) if fi.get("seed") is not None
                  else None),
            wire_drop_rate=float(fi.get(
                "wire-drop-rate", fi_defaults.wire_drop_rate)),
            wire_truncate_rate=float(fi.get(
                "wire-truncate-rate", fi_defaults.wire_truncate_rate)),
            wire_delay_rate=float(fi.get(
                "wire-delay-rate", fi_defaults.wire_delay_rate)),
            wire_delay_ms=float(fi.get(
                "wire-delay-ms", fi_defaults.wire_delay_ms)),
            device_error_rate=float(fi.get(
                "device-error-rate", fi_defaults.device_error_rate)),
            freeze_rate=float(fi.get(
                "freeze-rate", fi_defaults.freeze_rate)),
            freeze_ms=float(fi.get("freeze-ms", fi_defaults.freeze_ms)),
            freeze_max=int(fi.get("freeze-max",
                                  fi_defaults.freeze_max)),
            die_after_requests=int(fi.get(
                "die-after-requests", fi_defaults.die_after_requests)),
        ).validate()   # rate/delay bounds fail at load, not mid-serving
        if (cfg.fault_injection.seed is not None
                and (raw.get("parallel", {}) or {}).get("enabled")
                and int((raw.get("parallel", {}) or {})
                        .get("num-processes") or 1) > 1):
            # Chaos fires on whatever process installed it; on a
            # multi-host pod that stalls/re-launches ONE process's SPMD
            # lockstep sequence and hangs the slice.  (Auto-discovered
            # pods without explicit coordinates are disarmed at
            # bring-up instead — see build_services.)
            raise ValueError("fault-injection.seed cannot be combined "
                             "with a multi-host parallel config")
        rd = raw.get("renderer", {}) or {}
        rd_defaults = RendererConfig()
        cfg.renderer = RendererConfig(
            cpu_fallback_max_px=int(rd.get(
                "cpu-fallback-max-px", rd_defaults.cpu_fallback_max_px)),
            jpeg_engine=str(rd.get("jpeg-engine",
                                   rd_defaults.jpeg_engine)),
            compilation_cache_dir=(
                str(rd["compilation-cache-dir"])
                if rd.get("compilation-cache-dir") is not None
                else rd_defaults.compilation_cache_dir),
            prewarm=tuple(str(s) for s in rd.get("prewarm", ()) or ()),
        )
        from .prewarm import parse_spec
        for spec in cfg.renderer.prewarm:
            parse_spec(spec)   # malformed specs fail at load, not boot
        if cfg.renderer.jpeg_engine in ("auto", "bitpack"):
            raise ValueError(
                f"renderer.jpeg-engine {cfg.renderer.jpeg_engine!r} was "
                f"removed in PR 30: the wire form is a static choice, "
                f"'sparse' or 'huffman'")
        if rd.get("kernel") == "pallas":
            raise ValueError(
                "renderer.kernel 'pallas' was removed in PR 30 with the "
                "option itself: the render kernel is ops.render's; "
                "delete the key")
        if cfg.renderer.jpeg_engine not in ("sparse", "huffman"):
            raise ValueError(
                f"renderer.jpeg-engine must be 'sparse' or 'huffman', "
                f"got {cfg.renderer.jpeg_engine!r}")
        return cfg
