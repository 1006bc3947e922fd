"""server/sentinel.py + telemetry.SentinelStats — the live
perf-regression sentinel.

Covered contracts:

* ``telemetry.reset()`` clears the sentinel accumulator (the
  test-isolation contract every suite here leans on);
* every ``imageregion_sentinel_*`` family lints clean against the
  committed cardinality budget, HELP/TYPE exactly once;
* the (route-class, shape-bucket) vocabularies are CLOSED — unknown
  routes and huge payloads land in the overflow classes, never a new
  series;
* the drift engine on a virtual clock: warmup -> confirmed drift
  (exactly once, with ledger record and one complete bundle,
  manifest written last) -> recovery;
* the committed-watermark latency floor suppresses baseline-relative
  drift verdicts;
* learned baselines round-trip through export/load (the warm-state
  manifest path).
"""

import importlib.util
import json
import os

import pytest

from omero_ms_image_region_tpu.server import sentinel as sentinel_mod
from omero_ms_image_region_tpu.server.sentinel import (
    ROUTE_CLASSES, SHAPE_BUCKETS, SentinelEngine, route_class,
    shape_bucket)
from omero_ms_image_region_tpu.utils import decisions, telemetry

SCRIPTS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "scripts")


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(SCRIPTS, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _fresh():
    telemetry.reset()
    decisions.LEDGER.reset()
    yield
    telemetry.reset()
    decisions.LEDGER.reset()


@pytest.fixture(scope="module")
def lint():
    return _load_script("metrics_lint")


@pytest.fixture(scope="module")
def budget(lint):
    return lint.load_budget()


def _summary(member="local", verdict="ok", **over):
    doc = {
        "member": member, "verdict": verdict, "ticks": 3,
        "observations": 240, "drifting": [],
        "throughput_drift": False, "tiles_per_s": 48.0,
        "watermark_tiles_per_s": 40.0,
        "routes": {"render_image_region":
                   {"n": 240, "p99_ms": 31.5,
                    "baseline_p99_ms": 30.0}},
        "keys": {}, "last_bundle": None,
    }
    doc.update(over)
    return doc


class TestResetContract:
    def test_reset_clears_sentinel_accumulator(self):
        telemetry.SENTINEL.set_local(_summary())
        telemetry.SENTINEL.ingest("peer", _summary(member="peer",
                                                   verdict="drifting"))
        telemetry.SENTINEL.count_drift()
        telemetry.SENTINEL.count_bundle()
        telemetry.SENTINEL.count_bundle(error=True)
        assert telemetry.SENTINEL.export() is not None
        assert telemetry.SENTINEL.metric_lines()

        telemetry.reset()

        assert telemetry.SENTINEL.export() is None
        merged = telemetry.SENTINEL.merged()
        assert merged["verdict"] == "ok"
        assert merged["members"] == {}
        assert merged["drifts"] == 0
        assert merged["bundles"] == 0
        assert merged["bundle_errors"] == 0
        # emit-when-live: a reset accumulator exports no series.
        assert telemetry.SENTINEL.metric_lines() == []

    def test_merged_folds_local_and_peers(self):
        telemetry.SENTINEL.set_local(_summary(member="m0"))
        telemetry.SENTINEL.ingest(
            "m1", _summary(member="m1", verdict="drifting"))
        merged = telemetry.SENTINEL.merged()
        assert set(merged["members"]) == {"m0", "m1"}
        assert merged["verdict"] == "drifting"
        assert merged["drifting_members"] == ["m1"]

    def test_ingest_rejects_garbage_and_bounds_members(self):
        assert not telemetry.SENTINEL.ingest("m1", None)
        assert not telemetry.SENTINEL.ingest("m1", {"no": "verdict"})
        assert not telemetry.SENTINEL.ingest("", _summary())
        for i in range(telemetry.SentinelStats._MAX_MEMBERS):
            assert telemetry.SENTINEL.ingest(f"m{i}", _summary())
        assert not telemetry.SENTINEL.ingest("overflow", _summary())
        assert telemetry.SENTINEL.merged()["dropped_members"] == 1


class TestMetricsBudget:
    def test_sentinel_families_lint_clean(self, lint, budget):
        telemetry.SENTINEL.set_local(_summary())
        telemetry.SENTINEL.ingest(
            "m1", _summary(member="m1", verdict="drifting"))
        telemetry.SENTINEL.count_drift()
        text = telemetry.finalize_exposition(
            telemetry.request_metric_lines(exemplars=True))
        assert "imageregion_sentinel_drift " in text
        assert 'imageregion_sentinel_live_p99_ms{' in text
        assert 'imageregion_sentinel_member_drift{member="m1"}' \
            in text
        assert lint.lint_exposition(text, budget) == []

    def test_help_type_emitted_once(self):
        telemetry.SENTINEL.set_local(_summary())
        text = telemetry.finalize_exposition(
            telemetry.request_metric_lines())
        for family in ("imageregion_sentinel_drift",
                       "imageregion_sentinel_ticks_total",
                       "imageregion_sentinel_live_p99_ms"):
            assert text.count(f"# HELP {family} ") == 1
            assert text.count(f"# TYPE {family} ") == 1

    def test_every_sentinel_family_registered(self):
        for family in telemetry.METRIC_TYPES:
            if family.startswith("imageregion_sentinel_"):
                assert family in telemetry.METRIC_HELP


class TestClosedVocabularies:
    def test_route_class_maps_unknowns_to_other(self):
        for route in ROUTE_CLASSES:
            assert route_class(route) == route
        assert route_class("render_thumbnail") == "other"
        assert route_class("") == "other"

    def test_shape_bucket_ladder(self):
        assert shape_bucket(0) == "s4k"
        assert shape_bucket(4096) == "s4k"
        assert shape_bucket(4097) == "s16k"
        assert shape_bucket(1 << 20) == "s1m"
        assert shape_bucket(1 << 40) == "sbig"
        assert shape_bucket(-5) == "s4k"

    def test_observe_never_mints_open_keys(self):
        eng = SentinelEngine(member="t", bundle_dir="")
        eng.observe("render_image_region", 65536, 10.0)
        eng.observe("totally/new/route", 65536, 10.0)
        eng.observe("another?weird=1", 1 << 33, 10.0)
        for route, shape in eng._keys:
            assert route in ROUTE_CLASSES
            assert shape in SHAPE_BUCKETS
        assert ("other", "s64k") in eng._keys
        assert ("other", "sbig") in eng._keys


def _make_engine(tmp_path, clk, **over):
    kwargs = dict(
        member="t0",
        tick_interval_s=5.0,
        confirm_ticks=2,
        recover_ticks=2,
        min_samples=8,
        warmup_ticks=2,
        drift_ratio=1.5,
        baseline_alpha=0.2,
        bundle_dir=str(tmp_path),
        max_bundles=3,
        profile_ms=10,
        watermarks={"bench": {
            "p50_service_tile_ms": {"value": 5.0},
            "service_tiles_per_sec": {"value": 0.001}}},
        clock=lambda: clk[0],
        profile_fn=lambda directory, ms: {"skipped": "test"},
        flight_fn=lambda: {"events": [{"kind": "test"}]},
        costs_fn=lambda: [{"trace": "t-1"}],
        exemplars_fn=lambda: {"render_image_region": []},
    )
    kwargs.update(over)
    return SentinelEngine(**kwargs)


def _feed(engine, center_ms, n=12):
    for i in range(n):
        engine.observe("render_image_region", 65536,
                       center_ms * (1.0 + 0.03 * (i % 4)))


def _tick(engine, clk):
    clk[0] += 5.0
    return engine.tick()


class TestDriftLifecycle:
    def test_confirm_capture_recover(self, tmp_path):
        clk = [0.0]
        eng = _make_engine(tmp_path, clk)

        # Warmup: learn the 12ms baseline.
        for _ in range(3):
            _feed(eng, 12.0)
            s = _tick(eng, clk)
            assert s["verdict"] == "ok"

        # Step to 40ms: first breach tick must NOT confirm...
        _feed(eng, 40.0)
        s = _tick(eng, clk)
        assert s["verdict"] == "ok"
        assert not os.listdir(tmp_path)
        # ...the second (confirm_ticks=2) must, exactly once.
        _feed(eng, 40.0)
        s = _tick(eng, clk)
        assert s["verdict"] == "drifting"
        assert s["drifting"] == ["render_image_region|s64k"]
        assert eng.verdict == "drifting"

        drift_records = [r for r in decisions.LEDGER.snapshot()
                         if r["kind"] == "sentinel"
                         and r["verdict"] == "drift"]
        assert len(drift_records) == 1
        assert drift_records[0]["detail"]["keys"] == \
            ["render_image_region|s64k"]

        # One complete bundle: every artifact present, manifest last.
        bundles = os.listdir(tmp_path)
        assert len(bundles) == 1
        bdir = os.path.join(tmp_path, bundles[0])
        with open(os.path.join(bdir, "manifest.json")) as f:
            manifest = json.load(f)
        assert manifest["kind"] == "sentinel_incident"
        assert manifest["member"] == "t0"
        for key in ("flight", "costs", "sketch_diff", "exemplars",
                    "profile"):
            fname = manifest["files"][key]
            assert fname, f"missing artifact {key}"
            assert os.path.exists(os.path.join(bdir, fname))
        assert s["last_bundle"] == bdir

        # A STILL-drifting tick re-fires neither record nor bundle.
        _feed(eng, 40.0)
        s = _tick(eng, clk)
        assert s["verdict"] == "drifting"
        assert len(os.listdir(tmp_path)) == 1
        assert len([r for r in decisions.LEDGER.snapshot()
                    if r["verdict"] == "drift"]) == 1

        # Recovery: recover_ticks=2 clean windows clear the verdict.
        _feed(eng, 12.0)
        assert _tick(eng, clk)["verdict"] == "drifting"
        _feed(eng, 12.0)
        s = _tick(eng, clk)
        assert s["verdict"] == "ok"
        assert eng.verdict == "ok"
        recovered = [r for r in decisions.LEDGER.snapshot()
                     if r["kind"] == "sentinel"
                     and r["verdict"] == "recovered"]
        assert len(recovered) == 1
        assert telemetry.SENTINEL.merged()["recoveries"] == 1

    def test_quiet_window_neither_confirms_nor_recovers(self,
                                                        tmp_path):
        clk = [0.0]
        eng = _make_engine(tmp_path, clk)
        for _ in range(3):
            _feed(eng, 12.0)
            _tick(eng, clk)
        _feed(eng, 40.0)
        _tick(eng, clk)
        # Under min_samples: no verdict either way, streak untouched.
        _feed(eng, 40.0, n=3)
        s = _tick(eng, clk)
        assert s["verdict"] == "ok"
        # The NEXT full breach window completes the confirmation —
        # the quiet window did not reset the streak.
        _feed(eng, 40.0)
        assert _tick(eng, clk)["verdict"] == "drifting"

    def test_drifted_era_does_not_teach_baseline(self, tmp_path):
        clk = [0.0]
        eng = _make_engine(tmp_path, clk)
        for _ in range(3):
            _feed(eng, 12.0)
            _tick(eng, clk)
        base = eng._keys[("render_image_region", "s64k")].baseline_p99
        for _ in range(4):
            _feed(eng, 40.0)
            _tick(eng, clk)
        st = eng._keys[("render_image_region", "s64k")]
        assert st.baseline_p99 == base

    def test_watermark_floor_suppresses_drift(self, tmp_path):
        clk = [0.0]
        # Committed p50 mark of 200ms: a 40ms p99 is under the floor
        # so the baseline-relative breach must not fire.
        eng = _make_engine(tmp_path, clk, watermarks={"bench": {
            "p50_service_tile_ms": {"value": 200.0},
            "service_tiles_per_sec": {"value": 0.001}}})
        for _ in range(3):
            _feed(eng, 12.0)
            _tick(eng, clk)
        for _ in range(4):
            _feed(eng, 40.0)
            s = _tick(eng, clk)
            assert s["verdict"] == "ok"
        assert not os.listdir(tmp_path)

    def test_bundle_retention_sweep(self, tmp_path):
        clk = [0.0]
        eng = _make_engine(tmp_path, clk, max_bundles=2)
        for i in range(4):
            os.makedirs(os.path.join(
                tmp_path, f"sentinel-0101-{i:04d}"))
        eng._sweep_bundles()
        assert len(os.listdir(tmp_path)) == 2


class TestBaselinePersistence:
    def test_export_load_round_trip(self, tmp_path):
        clk = [0.0]
        eng = _make_engine(tmp_path, clk)
        for _ in range(3):
            _feed(eng, 12.0)
            _tick(eng, clk)
        doc = eng.export_baseline()
        assert doc["version"] == 1
        assert "render_image_region|s64k" in doc["baselines"]

        clk2 = [0.0]
        fresh = _make_engine(tmp_path, clk2)
        assert fresh.load_baseline(doc) == 1
        st = fresh._keys[("render_image_region", "s64k")]
        assert st.baseline_p99 == pytest.approx(
            doc["baselines"]["render_image_region|s64k"]["p99"])
        # Restored keys count as warmed: the very next breach window
        # starts the confirmation streak without re-learning.
        assert st.baseline_ticks >= fresh.warmup_ticks

    def test_load_skips_foreign_and_open_keys(self, tmp_path):
        clk = [0.0]
        eng = _make_engine(tmp_path, clk)
        assert eng.load_baseline(None) == 0
        assert eng.load_baseline({"version": 99}) == 0
        n = eng.load_baseline({"version": 1, "baselines": {
            "render_image_region|s64k": {"p50": 1.0, "p99": 2.0,
                                         "ticks": 5},
            "made_up_route|s64k": {"p99": 2.0},       # open route
            "render_image_region|s9k": {"p99": 2.0},  # open shape
            "render_image|s4k": {"p99": "NaNope"},    # non-numeric
        }})
        assert n == 1
        assert list(eng._keys) == [("render_image_region", "s64k")]


class TestInstallIdiom:
    def test_install_active_uninstall(self):
        eng = SentinelEngine(member="t", bundle_dir="")
        try:
            assert sentinel_mod.install(eng) is eng
            assert sentinel_mod.active() is eng
        finally:
            sentinel_mod.uninstall()
        assert sentinel_mod.active() is None
