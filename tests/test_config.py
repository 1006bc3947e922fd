"""YAML config loading: reference key names, defaults, example file."""

import os

import pytest

from omero_ms_image_region_tpu.server.config import AppConfig, BatcherConfig

EXAMPLE = os.path.join(os.path.dirname(__file__), "..", "conf",
                       "config.example.yaml")


class TestAppConfig:
    def test_example_file_loads(self):
        cfg = AppConfig.from_yaml(EXAMPLE)
        assert cfg.port == 8080
        assert cfg.data_dir == "./data"
        assert cfg.max_tile_length == 2048
        assert cfg.lut_root == "/opt/omero/lib/scripts"
        assert cfg.session_cookie_name == "sessionid"
        assert cfg.session_store_type == "static"
        assert cfg.cache_control_header == "private, max-age=3600"
        assert cfg.caches.image_region is True
        assert cfg.caches.pixels_metadata is True
        assert cfg.caches.shape_mask is True
        assert cfg.batcher.enabled is True
        assert cfg.batcher.max_batch == 8

    def test_minimal_dict_gets_defaults(self):
        cfg = AppConfig.from_dict({"port": 9999})
        assert cfg.port == 9999
        defaults = BatcherConfig()
        assert cfg.batcher.max_batch == defaults.max_batch
        assert cfg.batcher.linger_ms == defaults.linger_ms
        # Reference ships caches disabled.
        assert cfg.caches.image_region is False
        assert cfg.caches.pixels_metadata is False

    def test_worker_pool_and_http_limits(self):
        cfg = AppConfig.from_dict({
            "worker_pool_size": 4,
            "max-initial-line-length": 2048,
            "max-header-size": 4096,
        })
        assert cfg.worker_pool_size == 4
        assert cfg.http.max_initial_line_length == 2048
        assert cfg.http.max_header_size == 4096
        # defaults mirror the reference's commented values
        d = AppConfig.from_dict({})
        assert d.worker_pool_size is None
        assert d.http.max_initial_line_length == 4096
        assert d.http.max_header_size == 8192

    def test_worker_pool_size_must_be_positive(self):
        import pytest
        with pytest.raises(ValueError):
            AppConfig.from_dict({"worker_pool_size": 0})

    def test_logging_block(self):
        cfg = AppConfig.from_dict({"logging": {
            "level": "DEBUG", "file": "/tmp/oms.log", "when": "H",
            "backup-count": 3,
        }})
        assert cfg.logging.level == "DEBUG"
        assert cfg.logging.file == "/tmp/oms.log"
        assert cfg.logging.when == "H"
        assert cfg.logging.backup_count == 3
        d = AppConfig.from_dict({})
        assert d.logging.level == "INFO" and d.logging.file is None

    def test_rolling_file_logging_writes(self, tmp_path):
        import logging as _logging

        from omero_ms_image_region_tpu.server.app import configure_logging

        root = _logging.getLogger()
        saved = root.handlers[:]
        try:
            root.handlers = []
            cfg = AppConfig.from_dict({"logging": {
                "file": str(tmp_path / "oms.log"), "backup-count": 1,
            }})
            configure_logging(cfg)
            _logging.getLogger("omero_ms_image_region_tpu.test").info(
                "hello rolling file")
            for h in root.handlers:
                h.flush()
            assert "hello rolling file" in (tmp_path / "oms.log").read_text()
        finally:
            for h in root.handlers:
                if h not in saved:
                    h.close()
            root.handlers = saved

    def test_metadata_service_block(self):
        import pytest
        cfg = AppConfig.from_dict({"metadata-service": {
            "type": "postgres", "dsn": "postgresql://u@h/db"}})
        assert cfg.metadata_backend == "postgres"
        assert cfg.metadata_dsn == "postgresql://u@h/db"
        assert AppConfig.from_dict({}).metadata_backend == "local"
        with pytest.raises(ValueError):
            AppConfig.from_dict({"metadata-service": {"type": "postgres"}})
        with pytest.raises(ValueError):
            AppConfig.from_dict({"metadata-service": {"type": "nope"}})

    def test_cache_flags_and_redis_uri(self):
        cfg = AppConfig.from_dict({
            "redis-cache": {"uri": "redis://x:1/0"},
            "image-region-cache": {"enabled": True},
        })
        assert cfg.caches.redis_uri == "redis://x:1/0"
        assert cfg.caches.image_region is True
        assert cfg.caches.shape_mask is False


@pytest.mark.parametrize("renderer, names", [
    ({"jpeg-engine": "auto"}, "renderer.jpeg-engine 'auto'"),
    ({"jpeg-engine": "bitpack"}, "renderer.jpeg-engine 'bitpack'"),
    ({"kernel": "pallas"}, "renderer.kernel 'pallas'"),
])
def test_removed_render_options_are_rejected(renderer, names):
    """A YAML that still asks for what PR 30 removed fails at load, in
    every posture, with a message that names the key and the removal."""
    for posture in ({}, {"batcher": {"enabled": False}}):
        with pytest.raises(ValueError) as e:
            AppConfig.from_dict({"renderer": renderer, **posture})
        assert names in str(e.value) and "removed in PR 30" in str(e.value)


def test_jpeg_engine_is_one_of_two_and_kernel_is_no_field():
    for engine in ("sparse", "huffman"):
        cfg = AppConfig.from_dict({"renderer": {"jpeg-engine": engine}})
        assert cfg.renderer.jpeg_engine == engine
    with pytest.raises(ValueError, match="'sparse' or 'huffman'"):
        AppConfig.from_dict({"renderer": {"jpeg-engine": "turbo"}})
    # A bare ``kernel: xla`` is a key the loader does not know.
    cfg = AppConfig.from_dict({"renderer": {"kernel": "xla"}})
    assert not hasattr(cfg.renderer, "kernel")


def test_pipeline_depth_validated_at_load():
    import pytest

    from omero_ms_image_region_tpu.server.config import AppConfig

    cfg = AppConfig.from_dict({"batcher": {"pipeline-depth": 3}})
    assert cfg.batcher.pipeline_depth == 3
    with pytest.raises(ValueError):
        AppConfig.from_dict({"batcher": {"pipeline-depth": 0}})


def test_parallel_cluster_coordinates():
    import pytest

    from omero_ms_image_region_tpu.server.config import AppConfig

    cfg = AppConfig.from_dict({"parallel": {
        "enabled": True, "coordinator-address": "host0:8476",
        "num-processes": 4, "process-id": 2}})
    assert cfg.parallel.coordinator_address == "host0:8476"
    assert cfg.parallel.num_processes == 4
    assert cfg.parallel.process_id == 2
    assert AppConfig.from_dict({}).parallel.coordinator_address is None
    with pytest.raises(ValueError):
        AppConfig.from_dict({"parallel": {
            "coordinator-address": "host0:8476"}})


def test_compilation_cache_dir_config():
    from omero_ms_image_region_tpu.server.config import AppConfig

    cfg = AppConfig.from_dict(
        {"renderer": {"compilation-cache-dir": "/tmp/jc"}})
    assert cfg.renderer.compilation_cache_dir == "/tmp/jc"
    assert AppConfig().renderer.compilation_cache_dir is None


def test_max_batch_limit_parses():
    from omero_ms_image_region_tpu.server.config import AppConfig

    cfg = AppConfig.from_dict({"batcher": {"max-batch-limit": 16}})
    assert cfg.batcher.max_batch_limit == 16
    assert AppConfig.from_dict({}).batcher.max_batch_limit is None


def test_prewarm_specs_parse_and_validate():
    from omero_ms_image_region_tpu.server.config import AppConfig
    from omero_ms_image_region_tpu.server.prewarm import parse_spec

    cfg = AppConfig.from_dict(
        {"renderer": {"prewarm": ["4x1024", "3x512@90"]}})
    assert cfg.renderer.prewarm == ("4x1024", "3x512@90")
    assert AppConfig.from_dict({}).renderer.prewarm == ()

    import numpy as np
    assert parse_spec("4x1024") == (4, 1024, 85, np.dtype(np.uint16))
    assert parse_spec("3x512@90") == (3, 512, 90, np.dtype(np.uint16))
    assert parse_spec("1x256:uint8") == (1, 256, 85, np.dtype(np.uint8))
    assert parse_spec("2x256@70:float32") == (2, 256, 70,
                                              np.dtype(np.float32))
    # The edge is a plane's edge as the store holds it, any whole
    # number in range (PR 34): a site states a 1080^2 field as it is.
    assert parse_spec("5x1080@90") == (5, 1080, 90, np.dtype(np.uint16))
    assert parse_spec("4x1000") == (4, 1000, 85, np.dtype(np.uint16))
    assert parse_spec("4x20") == (4, 20, 85, np.dtype(np.uint16))
    for bad in ("x1024", "4x", "4x15", "4x8193", "0x256", "4x256@0",
                "4x256@101", "4x256:uint64", "4x256:bogus",
                "4x1080.5"):
        with pytest.raises(ValueError):
            parse_spec(bad)
    # Malformed specs fail at config LOAD, not at first serving touch.
    with pytest.raises(ValueError):
        AppConfig.from_dict({"renderer": {"prewarm": ["4x15"]}})
    assert AppConfig.from_dict(
        {"renderer": {"prewarm": ["5x1080@90"]}}
    ).renderer.prewarm == ("5x1080@90",)


def test_hot_path_knobs_parse_and_validate():
    """PR 2's hot-path knobs: two-stage device lanes, single-flight
    dedup, and the raw cache's content-digest index."""
    import pytest

    from omero_ms_image_region_tpu.server.config import AppConfig

    cfg = AppConfig.from_dict({})
    assert cfg.batcher.device_lanes == 2          # double-buffered
    assert cfg.single_flight is True
    assert cfg.raw_cache.digest_dedup is True

    cfg = AppConfig.from_dict({
        "batcher": {"device-lanes": 3},
        "single-flight": {"enabled": False},
        "raw-cache": {"digest-dedup": False},
    })
    assert cfg.batcher.device_lanes == 3
    assert cfg.single_flight is False
    assert cfg.raw_cache.digest_dedup is False

    # Bare boolean form tolerated too.
    assert AppConfig.from_dict(
        {"single-flight": False}).single_flight is False

    with pytest.raises(ValueError, match="device-lanes"):
        AppConfig.from_dict({"batcher": {"device-lanes": 0}})


def test_fleet_block_parses_and_validates():
    """The `fleet:` block (data-parallel device fleet): example-file
    defaults, both topologies (combined members / frontend sockets),
    and every knob's validation bound."""
    import pytest

    from omero_ms_image_region_tpu.server.config import (AppConfig,
                                                         FleetConfig)

    # The example file documents the block; it loads with defaults.
    cfg = AppConfig.from_yaml(EXAMPLE)
    defaults = FleetConfig()
    assert cfg.fleet.enabled is False
    assert cfg.fleet.members == defaults.members
    assert cfg.fleet.lane_width == defaults.lane_width
    assert cfg.fleet.steal_min_backlog == defaults.steal_min_backlog
    assert cfg.fleet.hash_replicas == defaults.hash_replicas
    assert cfg.fleet.failover is defaults.failover

    # Combined-role in-process fleet.
    cfg = AppConfig.from_dict({"fleet": {
        "enabled": True, "members": 4, "lane-width": 3,
        "steal-min-backlog": 0, "hash-replicas": 128,
        "failover": False, "down-cooldown-s": 2.5}})
    assert cfg.fleet.enabled is True
    assert cfg.fleet.members == 4
    assert cfg.fleet.lane_width == 3
    assert cfg.fleet.steal_min_backlog == 0     # stealing disabled
    assert cfg.fleet.hash_replicas == 128
    assert cfg.fleet.failover is False
    assert cfg.fleet.down_cooldown_s == 2.5

    # Frontend-role sidecar fleet: fleet.sockets stands in for
    # sidecar.socket.
    cfg = AppConfig.from_dict({
        "sidecar": {"role": "frontend"},
        "fleet": {"enabled": True,
                  "sockets": ["/tmp/a.sock", "/tmp/b.sock"]}})
    assert cfg.fleet.sockets == ("/tmp/a.sock", "/tmp/b.sock")

    # A frontend with neither sidecar.socket nor fleet.sockets still
    # refuses to start.
    with pytest.raises(ValueError, match="sidecar.socket"):
        AppConfig.from_dict({"sidecar": {"role": "frontend"}})

    with pytest.raises(ValueError, match="members"):
        AppConfig.from_dict({"fleet": {"enabled": True, "members": 1}})
    with pytest.raises(ValueError, match="lane-width"):
        AppConfig.from_dict({"fleet": {"lane-width": 0}})
    with pytest.raises(ValueError, match="steal-min-backlog"):
        AppConfig.from_dict({"fleet": {"steal-min-backlog": -1}})
    with pytest.raises(ValueError, match="hash-replicas"):
        AppConfig.from_dict({"fleet": {"hash-replicas": 0}})
    with pytest.raises(ValueError, match="down-cooldown-s"):
        AppConfig.from_dict({"fleet": {"down-cooldown-s": -1.0}})


def test_pressure_block_parses_and_validates():
    """The `pressure:` block (resource-pressure governor + brownout
    ladder): example-file defaults, full parse, the ladder vocabulary,
    the shed_bulk-before-tighten_admission ordering invariant, and the
    hysteresis-band bounds."""
    from omero_ms_image_region_tpu.server.config import PressureConfig

    cfg = AppConfig.from_yaml(EXAMPLE)
    defaults = PressureConfig()
    assert cfg.pressure.enabled is False
    assert cfg.pressure.ladder == defaults.ladder
    assert cfg.pressure.hbm_high == defaults.hbm_high

    cfg = AppConfig.from_dict({"pressure": {
        "enabled": True, "interval-s": 0.5,
        "hbm-high": 0.8, "hbm-low": 0.6,
        "host-rss-high-mb": 4096, "host-rss-low-mb": 3072,
        "queue-high": 32, "queue-low": 8,
        "loop-lag-high-ms": 100, "loop-lag-low-ms": 20,
        "critical-factor": 1.5,
        "step-hold-ticks": 3, "release-hold-ticks": 5,
        "ladder": ["pause_prefetch", "shed_bulk",
                   "tighten_admission"],
        "quality-cap": 50, "evict-to-frac": 0.5,
        "lane-cap": 2, "admission-scale": 0.5}})
    assert cfg.pressure.enabled is True
    assert cfg.pressure.interval_s == 0.5
    assert cfg.pressure.hbm_high == 0.8
    assert cfg.pressure.host_rss_high_mb == 4096
    assert cfg.pressure.ladder == ("pause_prefetch", "shed_bulk",
                                   "tighten_admission")
    assert cfg.pressure.quality_cap == 50
    assert cfg.pressure.admission_scale == 0.5

    with pytest.raises(ValueError, match="ladder step"):
        AppConfig.from_dict({"pressure": {"ladder": ["no_such_step"]}})
    with pytest.raises(ValueError, match="repeats"):
        AppConfig.from_dict({"pressure": {
            "ladder": ["shed_bulk", "shed_bulk"]}})
    # The availability-ordering invariant: interactive shedding never
    # precedes bulk shedding.
    with pytest.raises(ValueError, match="shed_bulk before"):
        AppConfig.from_dict({"pressure": {
            "ladder": ["tighten_admission", "shed_bulk"]}})
    # Hysteresis bands need low < high.
    with pytest.raises(ValueError, match="hbm-low"):
        AppConfig.from_dict({"pressure": {"hbm-high": 0.5,
                                          "hbm-low": 0.6}})
    with pytest.raises(ValueError, match="queue-low"):
        AppConfig.from_dict({"pressure": {"queue-high": 10,
                                          "queue-low": 10}})
    with pytest.raises(ValueError, match="critical-factor"):
        AppConfig.from_dict({"pressure": {"critical-factor": 0.5}})
    with pytest.raises(ValueError, match="quality-cap"):
        AppConfig.from_dict({"pressure": {"quality-cap": 0}})
    with pytest.raises(ValueError, match="evict-to-frac"):
        AppConfig.from_dict({"pressure": {"evict-to-frac": 1.5}})
    with pytest.raises(ValueError, match="admission-scale"):
        AppConfig.from_dict({"pressure": {"admission-scale": 0.0}})
    with pytest.raises(ValueError, match="interval-s"):
        AppConfig.from_dict({"pressure": {"interval-s": 0}})


def test_watchdog_block_parses_and_validates():
    from omero_ms_image_region_tpu.server.config import WatchdogConfig

    cfg = AppConfig.from_yaml(EXAMPLE)
    defaults = WatchdogConfig()
    assert cfg.watchdog.enabled is defaults.enabled
    assert cfg.watchdog.stall_factor == defaults.stall_factor

    cfg = AppConfig.from_dict({"watchdog": {
        "enabled": False, "interval-s": 1.0, "stall-factor": 4,
        "stall-min-s": 10, "wire-hang-s": 0, "escalate-after": 3}})
    assert cfg.watchdog.enabled is False
    assert cfg.watchdog.stall_factor == 4
    assert cfg.watchdog.wire_hang_s == 0     # wire check disabled

    with pytest.raises(ValueError, match="stall-factor"):
        AppConfig.from_dict({"watchdog": {"stall-factor": 0.5}})
    with pytest.raises(ValueError, match="stall-min-s"):
        AppConfig.from_dict({"watchdog": {"stall-min-s": 0}})
    with pytest.raises(ValueError, match="wire-hang-s"):
        AppConfig.from_dict({"watchdog": {"wire-hang-s": -1}})
    with pytest.raises(ValueError, match="escalate-after"):
        AppConfig.from_dict({"watchdog": {"escalate-after": 0}})
    with pytest.raises(ValueError, match="interval-s"):
        AppConfig.from_dict({"watchdog": {"interval-s": 0}})


def test_drain_block_parses_and_validates():
    from omero_ms_image_region_tpu.server.config import DrainConfig

    cfg = AppConfig.from_yaml(EXAMPLE)
    defaults = DrainConfig()
    assert cfg.drain.prestage is defaults.prestage
    assert cfg.drain.prestage_max_planes == \
        defaults.prestage_max_planes

    # fail-readyz default: off — drains stay annotation-only unless
    # the operator opts the load balancer in.
    assert cfg.drain.fail_readyz is False

    cfg = AppConfig.from_dict({"drain": {
        "prestage": False, "prestage-max-planes": 64,
        "settle-timeout-s": 5.0, "fail-readyz": True}})
    assert cfg.drain.prestage is False
    assert cfg.drain.prestage_max_planes == 64
    assert cfg.drain.settle_timeout_s == 5.0
    assert cfg.drain.fail_readyz is True

    with pytest.raises(ValueError, match="prestage-max-planes"):
        AppConfig.from_dict({"drain": {"prestage-max-planes": 0}})
    with pytest.raises(ValueError, match="settle-timeout-s"):
        AppConfig.from_dict({"drain": {"settle-timeout-s": 0}})


def test_sessions_block_parses_and_validates():
    """The `sessions:` block (viewport model + per-session admission
    token buckets): example-file defaults, full parse, validation."""
    from omero_ms_image_region_tpu.server.config import SessionsConfig

    cfg = AppConfig.from_yaml(EXAMPLE)
    defaults = SessionsConfig()
    assert cfg.sessions.enabled is False
    assert cfg.sessions.bucket_refill_per_s == \
        defaults.bucket_refill_per_s
    assert cfg.sessions.bucket_burst == defaults.bucket_burst
    assert cfg.sessions.max_tracked == defaults.max_tracked
    assert cfg.sessions.prefetch_lookahead == \
        defaults.prefetch_lookahead

    cfg = AppConfig.from_dict({"sessions": {
        "enabled": True, "bucket-refill-per-s": 10.0,
        "bucket-burst": 25.0, "max-tracked": 128,
        "prefetch-lookahead": 3}})
    assert cfg.sessions.enabled is True
    assert cfg.sessions.bucket_refill_per_s == 10.0
    assert cfg.sessions.bucket_burst == 25.0
    assert cfg.sessions.max_tracked == 128
    assert cfg.sessions.prefetch_lookahead == 3

    with pytest.raises(ValueError, match="bucket-refill-per-s"):
        AppConfig.from_dict({"sessions": {"bucket-refill-per-s": 0}})
    with pytest.raises(ValueError, match="bucket-burst"):
        AppConfig.from_dict({"sessions": {"bucket-burst": 0.5}})
    with pytest.raises(ValueError, match="max-tracked"):
        AppConfig.from_dict({"sessions": {"max-tracked": 0}})
    with pytest.raises(ValueError, match="prefetch-lookahead"):
        AppConfig.from_dict({"sessions": {"prefetch-lookahead": 0}})


def test_qos_block_parses_and_validates():
    """The `qos:` block (weighted two-class dequeue + bulk token
    cost): example-file defaults, full parse, validation."""
    from omero_ms_image_region_tpu.server.config import QosConfig

    cfg = AppConfig.from_yaml(EXAMPLE)
    defaults = QosConfig()
    assert cfg.qos.enabled is False
    assert cfg.qos.interactive_weight == defaults.interactive_weight
    assert cfg.qos.bulk_cost == defaults.bulk_cost

    cfg = AppConfig.from_dict({"qos": {
        "enabled": True, "interactive-weight": 8, "bulk-cost": 16.0}})
    assert cfg.qos.enabled is True
    assert cfg.qos.interactive_weight == 8
    assert cfg.qos.bulk_cost == 16.0

    with pytest.raises(ValueError, match="interactive-weight"):
        AppConfig.from_dict({"qos": {"interactive-weight": 0}})
    with pytest.raises(ValueError, match="bulk-cost"):
        AppConfig.from_dict({"qos": {"bulk-cost": 0.5}})


def test_pressure_prefetch_budget_parses_and_validates():
    """The continuous prefetch-budget knobs ride the pressure block
    and must stay monotone: more pressure never means MORE
    speculative staging."""
    cfg = AppConfig.from_yaml(EXAMPLE)
    assert cfg.pressure.prefetch_budget_elevated == 0.5
    assert cfg.pressure.prefetch_budget_critical == 0.25

    cfg = AppConfig.from_dict({"pressure": {
        "prefetch-budget-elevated": 0.8,
        "prefetch-budget-critical": 0.4}})
    assert cfg.pressure.prefetch_budget_elevated == 0.8
    assert cfg.pressure.prefetch_budget_critical == 0.4

    with pytest.raises(ValueError, match="prefetch-budget"):
        AppConfig.from_dict({"pressure": {
            "prefetch-budget-elevated": 0.3,
            "prefetch-budget-critical": 0.6}})
    with pytest.raises(ValueError, match="prefetch-budget"):
        AppConfig.from_dict({"pressure": {
            "prefetch-budget-elevated": 1.5}})
    with pytest.raises(ValueError, match="prefetch-budget"):
        AppConfig.from_dict({"pressure": {
            "prefetch-budget-critical": 0.0}})


def test_fault_injection_freeze_max_parses():
    cfg = AppConfig.from_dict({"fault-injection": {
        "seed": 1, "freeze-rate": 1.0, "freeze-ms": 100,
        "freeze-max": 2}})
    assert cfg.fault_injection.freeze_max == 2
    with pytest.raises(ValueError, match="freeze-max"):
        AppConfig.from_dict({"fault-injection": {
            "seed": 1, "freeze-max": -1}})


def test_http_cache_block_parses_and_validates():
    """The `http-cache:` block (conditional HTTP + fleet peer byte
    tier): example-file defaults, full parse, validation — the epoch
    rides inside the quoted ETag header, so its charset is closed."""
    from omero_ms_image_region_tpu.server.config import HttpCacheConfig

    cfg = AppConfig.from_yaml(EXAMPLE)
    defaults = HttpCacheConfig()
    assert cfg.http_cache.enabled is defaults.enabled
    assert cfg.http_cache.epoch == defaults.epoch
    assert cfg.http_cache.max_age_s == defaults.max_age_s
    assert cfg.http_cache.vary_acl is defaults.vary_acl
    assert cfg.http_cache.peer_fetch is defaults.peer_fetch
    assert cfg.http_cache.peer_timeout_ms == defaults.peer_timeout_ms

    cfg = AppConfig.from_dict({"http-cache": {
        "enabled": True, "epoch": "2026-08.r2", "max-age-s": 86400,
        "vary-acl": False, "peer-fetch": False,
        "peer-timeout-ms": 250.0}})
    assert cfg.http_cache.enabled is True
    assert cfg.http_cache.epoch == "2026-08.r2"
    assert cfg.http_cache.max_age_s == 86400
    assert cfg.http_cache.vary_acl is False
    assert cfg.http_cache.peer_fetch is False
    assert cfg.http_cache.peer_timeout_ms == 250.0

    with pytest.raises(ValueError, match="epoch"):
        AppConfig.from_dict({"http-cache": {"epoch": 'x"y'}})
    with pytest.raises(ValueError, match="epoch"):
        AppConfig.from_dict({"http-cache": {"epoch": ""}})
    with pytest.raises(ValueError, match="max-age-s"):
        AppConfig.from_dict({"http-cache": {"max-age-s": -1}})
    with pytest.raises(ValueError, match="peer-timeout-ms"):
        AppConfig.from_dict({"http-cache": {"peer-timeout-ms": 0}})


def test_provenance_header_knob_parses():
    """telemetry.provenance-header: the opt-in debug header, default
    OFF (an operator surface, never ambient)."""
    assert AppConfig().telemetry.provenance_header is False
    cfg = AppConfig.from_dict({})
    assert cfg.telemetry.provenance_header is False
    cfg = AppConfig.from_dict(
        {"telemetry": {"provenance-header": True}})
    assert cfg.telemetry.provenance_header is True


def test_http_cache_epoch_auto_accepted():
    """"auto" is a valid epoch value (resolved to a derived stamp at
    create_app time); explicit values stay verbatim overrides."""
    cfg = AppConfig.from_dict({"http-cache": {"epoch": "auto"}})
    assert cfg.http_cache.epoch == "auto"


def test_loadmodel_block_parses_and_validates():
    """The `loadmodel:` block (open-loop arrival generator): example-
    file defaults, full parse, validation — a bad block must fail at
    config load, not mid-bench-round."""
    from omero_ms_image_region_tpu.server.config import LoadModelConfig

    cfg = AppConfig.from_yaml(EXAMPLE)
    defaults = LoadModelConfig()
    assert cfg.loadmodel.seed == defaults.seed
    assert cfg.loadmodel.viewers == defaults.viewers
    assert cfg.loadmodel.diurnal_amplitude == \
        defaults.diurnal_amplitude

    cfg = AppConfig.from_dict({"loadmodel": {
        "seed": 7, "viewers": 100000,
        "think-time-median-ms": 500.0, "think-time-sigma": 1.5,
        "session-length-median": 40.0, "session-length-sigma": 0.8,
        "diurnal-amplitude": 0.9, "bulk-fraction": 0.05,
        "mask-fraction": 0.02, "zoom-fraction": 0.1}})
    assert cfg.loadmodel.seed == 7
    assert cfg.loadmodel.viewers == 100000
    assert cfg.loadmodel.think_time_median_ms == 500.0
    assert cfg.loadmodel.session_length_sigma == 0.8
    assert cfg.loadmodel.diurnal_amplitude == 0.9
    assert cfg.loadmodel.bulk_fraction == 0.05
    assert cfg.loadmodel.mask_fraction == 0.02
    assert cfg.loadmodel.zoom_fraction == 0.1

    with pytest.raises(ValueError, match="viewers"):
        AppConfig.from_dict({"loadmodel": {"viewers": 0}})
    with pytest.raises(ValueError, match="medians"):
        AppConfig.from_dict({"loadmodel": {
            "think-time-median-ms": 0}})
    with pytest.raises(ValueError, match="diurnal-amplitude"):
        AppConfig.from_dict({"loadmodel": {"diurnal-amplitude": 1.0}})
    with pytest.raises(ValueError, match="mask-fraction"):
        AppConfig.from_dict({"loadmodel": {"mask-fraction": 1.2}})
    with pytest.raises(ValueError, match="bulk-fraction"):
        AppConfig.from_dict({"loadmodel": {
            "bulk-fraction": 0.7, "mask-fraction": 0.6}})


def test_autoscaler_block_parses_and_validates():
    """The `autoscaler:` block (elastic fleet controller): example-
    file defaults, full parse, validation — floor/ceiling ordering,
    the hysteresis band, and the requires-a-fleet invariant."""
    from omero_ms_image_region_tpu.server.config import (
        AutoscalerConfig)

    cfg = AppConfig.from_yaml(EXAMPLE)
    defaults = AutoscalerConfig()
    assert cfg.autoscaler.enabled is False
    assert cfg.autoscaler.floor == defaults.floor
    assert cfg.autoscaler.cooldown_s == defaults.cooldown_s

    cfg = AppConfig.from_dict({
        "fleet": {"enabled": True, "members": 4},
        "autoscaler": {
            "enabled": True, "interval-s": 1.0, "floor": 2,
            "ceiling": 4, "queue-high-per-lane": 5.0,
            "queue-low-per-lane": 1.0, "hold-ticks": 3,
            "cooldown-s": 10.0, "lane-capacity-tps": 40.0,
            "session-tps": 1.5}})
    assert cfg.autoscaler.enabled is True
    assert cfg.autoscaler.floor == 2
    assert cfg.autoscaler.ceiling == 4
    assert cfg.autoscaler.queue_high_per_lane == 5.0
    assert cfg.autoscaler.hold_ticks == 3
    assert cfg.autoscaler.cooldown_s == 10.0
    assert cfg.autoscaler.lane_capacity_tps == 40.0
    assert cfg.autoscaler.session_tps == 1.5

    with pytest.raises(ValueError, match="floor"):
        AppConfig.from_dict({"autoscaler": {"floor": 0}})
    with pytest.raises(ValueError, match="ceiling"):
        AppConfig.from_dict({"autoscaler": {"floor": 3,
                                            "ceiling": 2}})
    with pytest.raises(ValueError, match="hysteresis"):
        AppConfig.from_dict({"autoscaler": {
            "queue-high-per-lane": 1.0, "queue-low-per-lane": 2.0}})
    with pytest.raises(ValueError, match="hold-ticks"):
        AppConfig.from_dict({"autoscaler": {"hold-ticks": 0}})
    with pytest.raises(ValueError, match="cooldown-s"):
        AppConfig.from_dict({"autoscaler": {"cooldown-s": -1}})
    with pytest.raises(ValueError, match="lane-capacity-tps"):
        AppConfig.from_dict({"autoscaler": {
            "lane-capacity-tps": -1}})
    # The controller needs something to scale: a fleetless config
    # must refuse at load.
    with pytest.raises(ValueError, match="fleet"):
        AppConfig.from_dict({"autoscaler": {"enabled": True}})
    # An unachievable floor (> the provisioned member count) would
    # block every scale-down forever: refuse at load.
    with pytest.raises(ValueError, match="provisioned"):
        AppConfig.from_dict({
            "fleet": {"enabled": True, "members": 2},
            "autoscaler": {"enabled": True, "floor": 3,
                           "ceiling": 3}})


def test_federation_block_parses_and_validates():
    """The `federation:` block (cross-host fleet federation):
    example-file defaults, full parse, and the manifest invariants —
    unique names, a host that owns members, epoch >= 1, and mutual
    exclusion with fleet.sockets (the manifest IS the membership)."""
    from omero_ms_image_region_tpu.server.config import (
        FederationConfig)

    cfg = AppConfig.from_yaml(EXAMPLE)
    defaults = FederationConfig()
    assert cfg.federation.enabled is False
    assert cfg.federation.shard_epoch == defaults.shard_epoch
    assert cfg.federation.gossip_interval_s \
        == defaults.gossip_interval_s
    # The example documents a full 2-host manifest.
    assert len(cfg.federation.members) == 4

    cfg = AppConfig.from_dict({"federation": {
        "enabled": True, "host": "hostA", "shard-epoch": 7,
        "ring-seed": "prod", "hash-replicas": 32,
        "gossip-interval-s": 2.5,
        "members": [
            {"name": "a0", "host": "hostA"},
            {"name": "b0", "host": "hostB", "address": "h:1"}]}})
    assert cfg.federation.enabled is True
    assert cfg.federation.shard_epoch == 7
    assert cfg.federation.ring_seed == "prod"
    assert cfg.federation.hash_replicas == 32
    assert cfg.federation.gossip_interval_s == 2.5
    assert cfg.federation.members[1]["address"] == "h:1"

    with pytest.raises(ValueError, match="shard-epoch"):
        AppConfig.from_dict({"federation": {"shard-epoch": 0}})
    with pytest.raises(ValueError, match="gossip-interval-s"):
        AppConfig.from_dict({"federation": {"gossip-interval-s": 0}})
    with pytest.raises(ValueError, match=">= 2 members"):
        AppConfig.from_dict({"federation": {
            "enabled": True, "host": "h",
            "members": [{"name": "a", "host": "h"}]}})
    with pytest.raises(ValueError, match="unique"):
        AppConfig.from_dict({"federation": {
            "enabled": True, "host": "h",
            "members": [{"name": "a", "host": "h"},
                        {"name": "a", "host": "h2"}]}})
    with pytest.raises(ValueError, match="federation.host"):
        AppConfig.from_dict({"federation": {
            "enabled": True,
            "members": [{"name": "a", "host": "h"},
                        {"name": "b", "host": "h2"}]}})
    with pytest.raises(ValueError, match="owns no manifest member"):
        AppConfig.from_dict({"federation": {
            "enabled": True, "host": "elsewhere",
            "members": [{"name": "a", "host": "h"},
                        {"name": "b", "host": "h2"}]}})
    with pytest.raises(ValueError, match="name and host"):
        AppConfig.from_dict({"federation": {
            "members": [{"name": "a"}]}})
    with pytest.raises(ValueError, match="mutually exclusive"):
        AppConfig.from_dict({
            "sidecar": {"role": "frontend"},
            "fleet": {"enabled": True, "sockets": ["s0", "s1"]},
            "federation": {
                "enabled": True, "host": "h",
                "members": [{"name": "a", "host": "h"},
                            {"name": "b", "host": "h2",
                             "address": "x:1"}]}})
    # Federation counts as a fleet topology for the autoscaler, and
    # its member list is the provisioned count the floor checks.
    cfg = AppConfig.from_dict({
        "federation": {
            "enabled": True, "host": "h",
            "members": [{"name": "a", "host": "h"},
                        {"name": "b", "host": "h2",
                         "address": "x:1"}]},
        "autoscaler": {"enabled": True, "floor": 2, "ceiling": 2}})
    assert cfg.autoscaler.enabled
    with pytest.raises(ValueError, match="provisioned"):
        AppConfig.from_dict({
            "federation": {
                "enabled": True, "host": "h",
                "members": [{"name": "a", "host": "h"},
                            {"name": "b", "host": "h2",
                             "address": "x:1"}]},
            "autoscaler": {"enabled": True, "floor": 3,
                           "ceiling": 3}})


def test_federation_host_defaults_to_cluster_identity(monkeypatch):
    """An enabled federation block with NO host: key takes this
    process's identity from the cluster layer (``procN`` when
    jax.distributed is joined, else the OS hostname) — multi-host
    manifests are written once and shipped verbatim to every host."""
    from omero_ms_image_region_tpu.parallel import cluster

    members = [{"name": "a0", "host": "hostA", "address": "x:1"},
               {"name": "b0", "host": "hostB", "address": "y:1"}]
    monkeypatch.setattr(cluster, "host_identity", lambda: "hostB")
    cfg = AppConfig.from_dict({"federation": {
        "enabled": True, "members": members}})
    assert cfg.federation.host == "hostB"
    # An explicit host: key still wins over the cluster identity.
    cfg = AppConfig.from_dict({"federation": {
        "enabled": True, "host": "hostA", "members": members}})
    assert cfg.federation.host == "hostA"
    # An identity the manifest never heard of fails loudly, and the
    # message teaches the default rule.
    monkeypatch.setattr(cluster, "host_identity", lambda: "rogue")
    with pytest.raises(ValueError,
                       match=r"cluster\.host_identity"):
        AppConfig.from_dict({"federation": {
            "enabled": True, "members": members}})


def test_federation_quorum_knobs_parse_and_validate():
    """PR 18 knobs (deploy/DEPLOY.md "Partitions & quorum"): quorum
    membership off by default, liveness window and roll-ack timeout
    strictly positive, and `quorum: true` meaningless without an
    enabled federation — a verdict over manifest hosts needs a
    manifest."""
    from omero_ms_image_region_tpu.server.config import (
        FederationConfig)

    defaults = FederationConfig()
    cfg = AppConfig.from_yaml(EXAMPLE)
    assert cfg.federation.quorum is False
    assert cfg.federation.suspect_after_s \
        == defaults.suspect_after_s
    assert cfg.federation.roll_ack_timeout_s \
        == defaults.roll_ack_timeout_s

    members = [{"name": "a0", "host": "hostA"},
               {"name": "b0", "host": "hostB", "address": "h:1"}]
    cfg = AppConfig.from_dict({"federation": {
        "enabled": True, "host": "hostA", "quorum": True,
        "suspect-after-s": 2.5, "roll-ack-timeout-s": 1.5,
        "members": members}})
    assert cfg.federation.quorum is True
    assert cfg.federation.suspect_after_s == 2.5
    assert cfg.federation.roll_ack_timeout_s == 1.5

    with pytest.raises(ValueError, match="suspect-after-s"):
        AppConfig.from_dict({"federation": {
            "suspect-after-s": 0}})
    with pytest.raises(ValueError, match="roll-ack-timeout-s"):
        AppConfig.from_dict({"federation": {
            "roll-ack-timeout-s": -1}})
    with pytest.raises(ValueError,
                       match="quorum requires"):
        AppConfig.from_dict({"federation": {"quorum": True}})


def test_autoscaler_lifecycle_and_diurnal_knobs():
    """PR 15 knobs: diurnal prediction bounds and the unit-config /
    fleet.sockets coupling."""
    cfg = AppConfig.from_dict({
        "sidecar": {"role": "frontend"},
        "fleet": {"enabled": True, "sockets": ["s0", "s1"]},
        "autoscaler": {"enabled": True, "floor": 1,
                       "diurnal-period-s": 3600.0,
                       "diurnal-horizon-s": 120.0,
                       "unit-config": "/etc/sidecar.yaml"}})
    assert cfg.autoscaler.diurnal_period_s == 3600.0
    assert cfg.autoscaler.diurnal_horizon_s == 120.0
    assert cfg.autoscaler.unit_config == "/etc/sidecar.yaml"
    with pytest.raises(ValueError, match="diurnal-period-s"):
        AppConfig.from_dict({"autoscaler": {"diurnal-period-s": -1}})
    with pytest.raises(ValueError, match="diurnal-horizon-s"):
        AppConfig.from_dict({"autoscaler": {"diurnal-horizon-s": -1}})
    with pytest.raises(ValueError, match="unit-config"):
        AppConfig.from_dict({
            "fleet": {"enabled": True, "members": 2},
            "autoscaler": {"enabled": True,
                           "unit-config": "/etc/sidecar.yaml"}})


def test_sentinel_block_parses_and_validates():
    """The `sentinel:` block (live perf-regression sentinel):
    example-file values, full kebab-case parse, defaults, and every
    validation bound — window sizes, the confirm/recover streaks,
    the drift ratio's >1 floor, and the (0,1] fractions."""
    from omero_ms_image_region_tpu.server.config import SentinelConfig

    cfg = AppConfig.from_yaml(EXAMPLE)
    defaults = SentinelConfig()
    assert cfg.sentinel.enabled is True
    assert cfg.sentinel.tick_interval_s == defaults.tick_interval_s
    assert cfg.sentinel.confirm_ticks == defaults.confirm_ticks
    assert cfg.sentinel.drift_ratio == defaults.drift_ratio
    assert cfg.sentinel.bundle_dir == ""

    cfg = AppConfig.from_dict({"sentinel": {
        "enabled": True, "tick-interval-s": 2.5,
        "confirm-ticks": 4, "recover-ticks": 2,
        "min-samples": 16, "warmup-ticks": 5,
        "drift-ratio": 2.0, "baseline-alpha": 0.5,
        "throughput-floor-ratio": 0.25,
        "bundle-dir": "/var/lib/ms/bundles", "max-bundles": 3,
        "profile-ms": 100, "records-dir": "/srv/records"}})
    assert cfg.sentinel.enabled is True
    assert cfg.sentinel.tick_interval_s == 2.5
    assert cfg.sentinel.confirm_ticks == 4
    assert cfg.sentinel.recover_ticks == 2
    assert cfg.sentinel.min_samples == 16
    assert cfg.sentinel.warmup_ticks == 5
    assert cfg.sentinel.drift_ratio == 2.0
    assert cfg.sentinel.baseline_alpha == 0.5
    assert cfg.sentinel.throughput_floor_ratio == 0.25
    assert cfg.sentinel.bundle_dir == "/var/lib/ms/bundles"
    assert cfg.sentinel.max_bundles == 3
    assert cfg.sentinel.profile_ms == 100
    assert cfg.sentinel.records_dir == "/srv/records"

    with pytest.raises(ValueError, match="tick-interval-s"):
        AppConfig.from_dict({"sentinel": {"tick-interval-s": 0}})
    with pytest.raises(ValueError, match="confirm-ticks"):
        AppConfig.from_dict({"sentinel": {"confirm-ticks": 0}})
    with pytest.raises(ValueError, match="recover-ticks"):
        AppConfig.from_dict({"sentinel": {"recover-ticks": 0}})
    with pytest.raises(ValueError, match="min-samples"):
        AppConfig.from_dict({"sentinel": {"min-samples": 0}})
    with pytest.raises(ValueError, match="warmup-ticks"):
        AppConfig.from_dict({"sentinel": {"warmup-ticks": 0}})
    # A ratio at or under 1.0 calls steady state a drift.
    with pytest.raises(ValueError, match="drift-ratio"):
        AppConfig.from_dict({"sentinel": {"drift-ratio": 1.0}})
    with pytest.raises(ValueError, match="baseline-alpha"):
        AppConfig.from_dict({"sentinel": {"baseline-alpha": 0.0}})
    with pytest.raises(ValueError, match="baseline-alpha"):
        AppConfig.from_dict({"sentinel": {"baseline-alpha": 1.5}})
    with pytest.raises(ValueError, match="throughput-floor-ratio"):
        AppConfig.from_dict({"sentinel": {
            "throughput-floor-ratio": 0.0}})
    with pytest.raises(ValueError, match="max-bundles"):
        AppConfig.from_dict({"sentinel": {"max-bundles": 0}})
    with pytest.raises(ValueError, match="profile-ms"):
        AppConfig.from_dict({"sentinel": {"profile-ms": -1}})


def test_workloads_block_parses_and_validates():
    """The `workloads:` block (device workloads plane: batched masks,
    overlay composites, animation streams): example-file defaults,
    full kebab-case parse, and the frame-cap bound."""
    from omero_ms_image_region_tpu.server.config import WorkloadsConfig

    cfg = AppConfig.from_yaml(EXAMPLE)
    defaults = WorkloadsConfig()
    assert cfg.workloads.device_masks is defaults.device_masks
    assert cfg.workloads.overlay_enabled is defaults.overlay_enabled
    assert cfg.workloads.animation_enabled is \
        defaults.animation_enabled
    assert cfg.workloads.animation_max_frames == \
        defaults.animation_max_frames

    cfg = AppConfig.from_dict({"workloads": {
        "device-masks": False, "overlay-enabled": False,
        "animation-enabled": True, "animation-max-frames": 16}})
    assert cfg.workloads.device_masks is False
    assert cfg.workloads.overlay_enabled is False
    assert cfg.workloads.animation_enabled is True
    assert cfg.workloads.animation_max_frames == 16

    with pytest.raises(ValueError, match="animation-max-frames"):
        AppConfig.from_dict({"workloads": {"animation-max-frames": 0}})


def test_pyramid_block_parses_and_validates():
    """The `pyramid:` block (crash-safe background builds): example-
    file defaults, full parse, and every validation bound — the chunk
    floor, the level-size floor, the codec whitelist, and the
    deferred-poll cadence."""
    from omero_ms_image_region_tpu.server.config import PyramidConfig

    cfg = AppConfig.from_yaml(EXAMPLE)
    defaults = PyramidConfig()
    assert cfg.pyramid.enabled is defaults.enabled
    assert cfg.pyramid.chunk == defaults.chunk
    assert cfg.pyramid.min_level_size == defaults.min_level_size
    assert cfg.pyramid.compressor == defaults.compressor
    assert cfg.pyramid.defer_poll_s == defaults.defer_poll_s

    cfg = AppConfig.from_dict({"pyramid": {
        "enabled": False, "chunk": 128, "min-level-size": 64,
        "compressor": "none", "defer-poll-s": 1.5}})
    assert cfg.pyramid.enabled is False
    assert cfg.pyramid.chunk == 128
    assert cfg.pyramid.min_level_size == 64
    assert cfg.pyramid.compressor == "none"
    assert cfg.pyramid.defer_poll_s == 1.5

    with pytest.raises(ValueError, match="pyramid.chunk"):
        AppConfig.from_dict({"pyramid": {"chunk": 8}})
    with pytest.raises(ValueError, match="min-level-size"):
        AppConfig.from_dict({"pyramid": {"min-level-size": 0}})
    with pytest.raises(ValueError, match="compressor"):
        AppConfig.from_dict({"pyramid": {"compressor": "lz4"}})
    with pytest.raises(ValueError, match="defer-poll-s"):
        AppConfig.from_dict({"pyramid": {"defer-poll-s": 0}})


def test_loadmodel_workload_fractions_parse_and_validate():
    """The workload-class mix knobs (`pyramid-fraction` /
    `animation-fraction`): parse, per-knob [0,1] bound, and the
    four-class sum cap — an over-committed mix fails at config load,
    not mid-bench-round."""
    cfg = AppConfig.from_dict({"loadmodel": {
        "bulk-fraction": 0.1, "mask-fraction": 0.05,
        "pyramid-fraction": 0.02, "animation-fraction": 0.03}})
    assert cfg.loadmodel.pyramid_fraction == 0.02
    assert cfg.loadmodel.animation_fraction == 0.03

    with pytest.raises(ValueError, match="pyramid-fraction"):
        AppConfig.from_dict({"loadmodel": {"pyramid-fraction": 1.2}})
    with pytest.raises(ValueError, match="animation-fraction"):
        AppConfig.from_dict({"loadmodel": {
            "animation-fraction": -0.1}})
    with pytest.raises(ValueError, match="sum to"):
        AppConfig.from_dict({"loadmodel": {
            "bulk-fraction": 0.4, "mask-fraction": 0.3,
            "pyramid-fraction": 0.2, "animation-fraction": 0.2}})
