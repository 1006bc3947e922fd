"""Attribution-and-forensics layer: per-request cost ledger, flight
recorder, SLO burn-rate engine, on-demand profiling, the cancelled
queue-wait split, and the bench regression gate."""

import asyncio
import importlib.util
import json
import os
import sys

import numpy as np
import pytest

from omero_ms_image_region_tpu.utils import telemetry

SCRIPTS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "scripts")


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(SCRIPTS, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    telemetry.reset()
    yield
    telemetry.reset()


# ----------------------------------------------------------- cost ledger

class TestCostLedger:
    def test_trace_accumulates_costs(self):
        trace = telemetry.Trace("t1")
        trace.add_cost("device_ms", 2.0)
        trace.add_cost("device_ms", 3.0)
        assert trace.export_costs() == {"device_ms": 5.0}

    def test_add_cost_lands_on_every_context_trace(self):
        """A group render under group_trace attributes pro-rata to
        every member's ledger."""
        telemetry.TRACES.start("a")
        telemetry.TRACES.start("b")
        with telemetry.group_trace(("a", "b")):
            telemetry.add_cost("device_ms", 4.0)
        for tid in ("a", "b"):
            trace = telemetry.TRACES.finish(tid)
            assert trace.export_costs()["device_ms"] == 4.0

    def test_merge_costs_drops_malformed_fields(self):
        telemetry.TRACES.start("w")
        telemetry.merge_costs("w", {"device_ms": "3.5",
                                    "staged_bytes": None})
        costs = telemetry.TRACES.finish("w").export_costs()
        assert costs == {"device_ms": 3.5}

    def test_assemble_ledger_classes(self):
        trace = telemetry.Trace("t2", "r")
        trace.add_span("cache.hit", trace.t0, 0.5)
        ledger, cache_class = telemetry.assemble_ledger(trace, 10.0, 99)
        assert cache_class == "byte-cache"
        assert ledger["wire_bytes"] == 99
        assert ledger["total_ms"] == 10.0
        trace2 = telemetry.Trace("t3", "r")
        trace2.add_span("dedup.coalesced", trace2.t0, 0.5)
        assert telemetry.assemble_ledger(trace2, 1.0, 1)[1] == "coalesced"
        assert telemetry.assemble_ledger(
            telemetry.Trace("t4", "r"), 1.0, 1)[1] == "render"

    def test_topk_is_bounded_and_sorted(self):
        topk = telemetry.CostTopK(k=3)
        for ms in (5.0, 1.0, 9.0, 7.0, 3.0):
            topk.offer({"total_ms": ms})
        snap = topk.snapshot()
        assert [d["total_ms"] for d in snap] == [9.0, 7.0, 5.0]
        assert topk.observed == 5

    def test_cost_histograms_feed_per_route(self):
        telemetry.observe_request_cost("r", {
            "device_ms": 2.0, "staged_bytes": 2048, "wire_bytes": 1024,
            "queue_ms": 1.0})
        lines = telemetry.cost_metric_lines()
        text = "\n".join(lines)
        assert 'imageregion_request_cost_device_ms_count{route="r"} 1' \
            in text
        # Byte fields convert to KB for the log-scale buckets.
        assert 'imageregion_request_cost_staged_kb_sum{route="r"} 2' \
            in text
        assert 'imageregion_request_cost_wire_kb_sum{route="r"} 1' \
            in text


# ------------------------------------------------------- flight recorder

class TestFlightRecorder:
    def test_ring_is_bounded(self):
        rec = telemetry.FlightRecorder(maxlen=16)
        for i in range(100):
            rec.record("e", i=i)
        assert len(rec) == 16
        assert rec.events_total == 100
        assert rec.snapshot()[-1]["i"] == 99

    def test_configure_preserves_events(self):
        rec = telemetry.FlightRecorder(maxlen=32)
        rec.record("a")
        rec.configure(64)
        assert [e["kind"] for e in rec.snapshot()] == ["a"]

    def test_dump_roundtrips_through_trace_report(self, tmp_path):
        rec = telemetry.FlightRecorder()
        rec.record("admission.shed", reason="queue-full", inflight=64)
        rec.record("breaker.open", op="image")
        path = rec.dump(str(tmp_path), "test")
        assert path and os.path.exists(path)
        with open(path) as f:
            doc = json.load(f)
        assert doc["flight_recorder"] is True
        assert doc["reason"] == "test"
        assert [e["kind"] for e in doc["events"]] == [
            "admission.shed", "breaker.open"]
        mod = _load_script("trace_report")
        out = mod.render_doc(doc)
        assert "flight recorder" in out
        assert "admission.shed" in out and "reason=queue-full" in out

    def test_trace_report_renders_robustness_timeline(self, tmp_path):
        """Pressure transitions, ladder steps, watchdog fires and
        drain phases are marked on the flight timeline and rolled
        into a self-preservation summary — a post-incident dump tells
        the whole degrade-by-choice story."""
        rec = telemetry.FlightRecorder()
        rec.record("pressure.level", level="elevated", prev="ok",
                   queue=52.0)
        rec.record("pressure.step", step="pause_prefetch",
                   action="engage", engaged=1)
        rec.record("watchdog.fire", action="requeue-group",
                   target="lane:2x256x256", age_s=0.42, tiles=3)
        rec.record("drain.phase", member="m1", phase="drained",
                   settled=True, planes=12, prestaged=12)
        rec.record("pressure.step", step="pause_prefetch",
                   action="release", engaged=0)
        path = rec.dump(str(tmp_path), "incident")
        with open(path) as f:
            doc = json.load(f)
        mod = _load_script("trace_report")
        out = mod.render_doc(doc)
        assert "pressure.level" in out
        assert "watchdog.fire" in out and "action=requeue-group" in out
        assert "drain.phase" in out and "phase=drained" in out
        assert "self-preservation:" in out
        assert "pressure.step:engage:pause_prefetch=1" in out
        assert "watchdog.fire:requeue-group=1" in out
        assert "drain:drained=1" in out

    def test_trace_report_renders_autoscale_events(self, tmp_path):
        """Autoscaler transitions and refusals join the
        self-preservation footer: a post-incident dump says when the
        fleet grew/shrank and why a wanted move was refused."""
        rec = telemetry.FlightRecorder()
        rec.record("autoscale.down", member="m2", active=2, queue=0)
        rec.record("autoscale.up", member="m2", active=3, queue=31)
        rec.record("autoscale.blocked", reason="cooldown",
                   want="down")
        rec.record("autoscale.blocked", reason="floor", want="down")
        path = rec.dump(str(tmp_path), "elastic")
        with open(path) as f:
            doc = json.load(f)
        mod = _load_script("trace_report")
        out = mod.render_doc(doc)
        assert "self-preservation:" in out
        assert "autoscale.down:m2=1" in out
        assert "autoscale.up:m2=1" in out
        assert "autoscale.blocked:cooldown=1" in out
        assert "autoscale.blocked:floor=1" in out

    def test_trace_report_renders_quorum_epoch_events(self, tmp_path):
        """PR 18's partition-tolerance events (quorum fence/restore,
        two-phase epoch propose/commit) are marked on the flight
        timeline and rolled into the self-preservation footer — a
        netsplit post-mortem reads when each island fenced, with what
        reachability, and which epoch the majority rolled."""
        rec = telemetry.FlightRecorder()
        rec.record("quorum.fence", host="hostC", reachable=1, hosts=3)
        rec.record("epoch.propose", epoch=2, digest="201e036bb714",
                   by="hostA")
        rec.record("epoch.commit", epoch=2, digest="201e036bb714",
                   by="hostA")
        rec.record("quorum.restore", host="hostC", reachable=3,
                   hosts=3)
        path = rec.dump(str(tmp_path), "netsplit")
        with open(path) as f:
            doc = json.load(f)
        mod = _load_script("trace_report")
        out = mod.render_doc(doc)
        assert "quorum.fence" in out and "host=hostC" in out
        assert "epoch.commit" in out and "epoch=2" in out
        assert "self-preservation:" in out
        assert "quorum.fence:1/3=1" in out
        assert "quorum.restore:3/3=1" in out
        assert "epoch.propose:v2=1" in out
        assert "epoch.commit:v2=1" in out

    def test_trace_report_renders_session_serving_events(
            self, tmp_path):
        """PR 10's session-serving events (fairness sheds, viewport
        predictions, prefetch budget moves) are marked on the flight
        timeline and rolled into their own summary footer."""
        rec = telemetry.FlightRecorder()
        rec.record("qos.shed", reason="fairness", cls="bulk",
                   session="abc123", cost=4.0)
        rec.record("prefetch.predict", n=2, session="abc123",
                   x=3, y=1)
        rec.record("prefetch.budget", scale=0.5, prev=1.0,
                   level="elevated", paused=False)
        rec.record("prefetch.budget", scale=0.0, prev=0.5,
                   level="critical", paused=True)
        path = rec.dump(str(tmp_path), "incident")
        with open(path) as f:
            doc = json.load(f)
        mod = _load_script("trace_report")
        out = mod.render_doc(doc)
        assert "qos.shed" in out and "reason=fairness" in out
        assert "prefetch.predict" in out
        assert "prefetch.budget" in out and "scale=0.5" in out
        assert "session-serving:" in out
        assert "qos.shed:bulk=1" in out
        assert "prefetch.budget:0.0=1" in out
        assert "prefetch.predict=1" in out

    def test_same_second_dumps_do_not_collide(self, tmp_path):
        rec = telemetry.FlightRecorder()
        rec.record("e")
        a = rec.dump(str(tmp_path), "manual")
        b = rec.dump(str(tmp_path), "manual")
        assert a != b
        assert len(os.listdir(tmp_path)) == 2

    def test_spool_prunes_oldest(self, tmp_path):
        rec = telemetry.FlightRecorder()
        rec.record("e")
        for _ in range(rec.MAX_DUMPS + 5):
            rec.dump(str(tmp_path), "x")
        assert len(os.listdir(tmp_path)) == rec.MAX_DUMPS

    def test_shape_estimate_claim_is_one_shot(self):
        assert telemetry.SHAPE_COSTS.claim_estimate("B1x1x8x8")
        assert not telemetry.SHAPE_COSTS.claim_estimate("B1x1x8x8")
        telemetry.SHAPE_COSTS.reset()
        assert telemetry.SHAPE_COSTS.claim_estimate("B1x1x8x8")

    def test_dump_never_raises(self):
        rec = telemetry.FlightRecorder()
        rec.record("e")
        # An unwritable spool directory yields None, not an exception.
        assert rec.dump("/proc/definitely/not/writable", "x") is None


# ------------------------------------------------------------ SLO engine

class TestSloEngine:
    def _engine(self, clock, **kw):
        eng = telemetry.SloEngine()
        kw.setdefault("availability_target", 0.99)
        kw.setdefault("fast_window_s", 10.0)
        kw.setdefault("slow_window_s", 30.0)
        kw.setdefault("breach_burn_rate", 10.0)
        eng.configure(clock=lambda: clock[0], **kw)
        return eng

    def test_burn_rate_math(self):
        clock = [1000.0]
        eng = self._engine(clock)
        for _ in range(98):
            eng.record(200, 1.0)
        for _ in range(2):
            eng.record(503, 1.0)
        # 2% errors against a 1% budget = burn rate 2.0 both windows.
        fast, slow = eng.burn_rates()["availability"]
        assert fast == pytest.approx(2.0)
        assert slow == pytest.approx(2.0)
        assert not eng.any_breached()

    def test_breach_fires_once_per_episode(self):
        clock = [1000.0]
        fired = []
        eng = self._engine(clock)
        eng.on_breach = lambda obj, fast, slow: fired.append(obj)
        for _ in range(10):
            eng.record(503, 1.0)
        assert eng.any_breached()
        assert fired == ["availability"]
        # Still breached: no second callback while the episode holds.
        eng.record(503, 1.0)
        assert fired == ["availability"]
        # Recovery (errors age out of both windows) re-arms the hook.
        clock[0] += 60.0
        for _ in range(50):
            eng.record(200, 1.0)
        assert not eng.any_breached()
        for _ in range(50):
            eng.record(503, 1.0)
        assert fired == ["availability", "availability"]

    def test_latency_objective(self):
        clock = [5000.0]
        eng = self._engine(clock, availability_target=0.0,
                           latency_ms=100.0, latency_target=0.9)
        for _ in range(8):
            eng.record(200, 10.0)
        for _ in range(2):
            eng.record(200, 500.0)
        # 20% slow against a 10% budget = burn 2.0; errors excluded.
        eng.record(503, 9999.0)
        fast, _slow = eng.burn_rates()["latency"]
        assert fast == pytest.approx(2.0)

    def test_both_objectives_breaching_fire_both_hooks(self):
        """One record can transition BOTH objectives at once (a window
        boundary dropping good buckets moves every denominator); each
        breach owns its own flight-recorder dump."""
        clock = [1000.0]
        fired = []
        eng = self._engine(clock, availability_target=0.9,
                           latency_ms=10.0, latency_target=0.9)
        eng.on_breach = lambda obj, fast, slow: fired.append(obj)
        # Pin the burn computation over threshold for both objectives
        # so the one record() transitions them together.
        eng._burn_rates_locked = lambda: {
            "availability": (99.0, 99.0), "latency": (99.0, 99.0)}
        eng.record(200, 1.0)
        assert sorted(fired) == ["availability", "latency"]
        assert eng.breaches_total == 2

    def test_disabled_is_free_and_silent(self):
        eng = telemetry.SloEngine()
        eng.record(500, 1.0)
        assert eng.burn_rates() == {}
        assert eng.metric_lines() == []
        assert eng.summary() == "disabled"

    def test_metric_lines_and_summary(self):
        clock = [1000.0]
        eng = self._engine(clock)
        for _ in range(10):
            eng.record(503, 1.0)
        text = "\n".join(eng.metric_lines())
        assert 'imageregion_slo_burn_rate{slo="availability",' \
               'window="fast"}' in text
        assert 'imageregion_slo_breach{slo="availability"} 1' in text
        assert eng.summary().startswith("BREACH availability burn")


# ------------------------------------------------- cancelled queue waits

class TestCancelledQueueWaits:
    def test_cancelled_waits_use_separate_series(self):
        """Deadline- and fault-cancelled pendings must not enter the
        dispatched-wait series or its high-water gauge (the BENCH_r05
        mean-vs-p50 skew)."""
        import time as _time

        from omero_ms_image_region_tpu.server.batcher import (
            BatchingRenderer, _Pending)
        from omero_ms_image_region_tpu.utils.stopwatch import REGISTRY

        REGISTRY.reset()
        renderer = BatchingRenderer()
        loop = asyncio.new_event_loop()
        try:
            pend = _Pending(raw=None, settings={}, h=1, w=1,
                            future=loop.create_future())
            pend.t_enqueue = _time.perf_counter() - 2.0  # waited ~2 s
            renderer._record_queue_waits([pend], _time.perf_counter(),
                                         cancelled=True)
            snap = REGISTRY.snapshot()
            assert "batcher.queueWait" not in snap
            assert snap["batcher.queueWait.cancelled"]["count"] == 1
            assert snap["batcher.queueWait.cancelled"]["mean_ms"] \
                >= 1900.0
            assert renderer.queue_wait_max_ms == 0.0
        finally:
            loop.close()
        REGISTRY.reset()

    def test_expired_pending_cancelled_not_rendered(self):
        """A pending whose budget died in the queue gets its 504 at
        dispatch pop and records a CANCELLED wait, not a dispatched
        one."""
        from omero_ms_image_region_tpu.server.batcher import (
            BatchingRenderer)
        from omero_ms_image_region_tpu.utils.stopwatch import REGISTRY
        from omero_ms_image_region_tpu.utils.transient import (
            DeadlineExceededError, deadline_scope)

        from test_batcher import _settings

        REGISTRY.reset()
        rng = np.random.default_rng(3)
        settings = _settings()
        raw = rng.integers(0, 60000, size=(3, 8, 8)).astype(np.float32)

        async def main():
            batcher = BatchingRenderer(linger_ms=5.0)
            try:
                with deadline_scope(0.01):   # spent before dispatch
                    with pytest.raises(DeadlineExceededError):
                        await batcher.render(raw, settings)
            finally:
                await batcher.close()

        loop = asyncio.new_event_loop()
        try:
            loop.run_until_complete(main())
        finally:
            loop.close()
        snap = REGISTRY.snapshot()
        assert snap["batcher.queueWait.cancelled"]["count"] == 1
        assert "batcher.queueWait" not in snap
        assert telemetry.RESILIENCE.deadline_cancelled == 1
        kinds = [e["kind"] for e in telemetry.FLIGHT.snapshot()]
        assert "batch.deadline-cancelled" in kinds
        REGISTRY.reset()


# ------------------------------------------------------------ bench gate

class TestBenchGate:
    def _gate(self):
        return _load_script("bench_gate")

    def _write(self, tmp_path, name, doc):
        path = tmp_path / name
        path.write_text(json.dumps(doc) + "\n")
        return str(path)

    def test_regression_fails(self, tmp_path, capsys):
        gate = self._gate()
        old = self._write(tmp_path, "BENCH_r01.json",
                          {"service_tiles_per_sec": 100.0})
        new = self._write(tmp_path, "BENCH_r02.json",
                          {"service_tiles_per_sec": 89.0})
        assert gate.main([old, new]) == 1
        verdict = json.loads(capsys.readouterr().out)
        assert verdict["verdict"] == "fail"
        assert verdict["keys"][0]["verdict"] == "regression"

    def test_exact_ten_percent_pair_fails(self, tmp_path):
        """The acceptance pair: a synthetic dead-on 10% drop."""
        gate = self._gate()
        old = self._write(tmp_path, "a.json",
                          {"service_tiles_per_sec": 100.0})
        new = self._write(tmp_path, "b.json",
                          {"service_tiles_per_sec": 90.0})
        assert gate.main([old, new]) == 1

    def test_within_threshold_passes(self, tmp_path):
        gate = self._gate()
        old = self._write(tmp_path, "a.json",
                          {"service_tiles_per_sec": 100.0})
        new = self._write(tmp_path, "b.json",
                          {"service_tiles_per_sec": 91.0})
        assert gate.main([old, new]) == 0
        # Improvements obviously pass too.
        better = self._write(tmp_path, "c.json",
                             {"service_tiles_per_sec": 140.0})
        assert gate.main([old, better]) == 0

    def test_null_value_skips_unless_strict(self, tmp_path):
        gate = self._gate()
        old = self._write(tmp_path, "a.json",
                          {"service_tiles_per_sec": None})
        new = self._write(tmp_path, "b.json",
                          {"service_tiles_per_sec": 50.0})
        assert gate.main([old, new]) == 0
        assert gate.main(["--strict", old, new]) == 1

    def test_dir_mode_picks_newest_pair(self, tmp_path, capsys):
        gate = self._gate()
        self._write(tmp_path, "BENCH_r01.json",
                    {"service_tiles_per_sec": 500.0})
        self._write(tmp_path, "BENCH_r04.json",
                    {"service_tiles_per_sec": 100.0})
        self._write(tmp_path, "BENCH_r05.json",
                    {"service_tiles_per_sec": 50.0})
        assert gate.main(["--dir", str(tmp_path)]) == 1
        verdict = json.loads(capsys.readouterr().out)
        assert verdict["old"] == "BENCH_r04.json"
        assert verdict["new"] == "BENCH_r05.json"

    def test_custom_keys(self, tmp_path):
        gate = self._gate()
        old = self._write(tmp_path, "a.json",
                          {"x": 10.0, "service_tiles_per_sec": 1.0})
        new = self._write(tmp_path, "b.json",
                          {"x": 5.0, "service_tiles_per_sec": 1.0})
        assert gate.main(["--key", "x", old, new]) == 1
        assert gate.main([old, new]) == 0

    def test_sessions_keys_gated_direction_aware(self, tmp_path,
                                                 capsys):
        """--sessions judges SESSIONS_r*.json on the multi-user
        serving keys, direction-aware by name: the per-session p99
        regresses UP (a ``_ms`` key), the fairness index and the
        predictive hit rate regress DOWN."""
        gate = self._gate()
        good = {"sessions_interactive_p99_ms": 120.0,
                "sessions_fairness_index": 0.95,
                "prefetch_hit_rate": 0.9}
        self._write(tmp_path, "SESSIONS_r01.json", good)
        # p99 UP 50% = regression even though the other keys held.
        self._write(tmp_path, "SESSIONS_r02.json",
                    {**good, "sessions_interactive_p99_ms": 180.0})
        assert gate.main(["--sessions", "--dir", str(tmp_path)]) == 1
        verdict = json.loads(capsys.readouterr().out)
        by_key = {v["key"]: v["verdict"] for v in verdict["keys"]}
        assert by_key["sessions_interactive_p99_ms"] == "regression"
        assert by_key["sessions_fairness_index"] == "pass"
        # Fairness index DOWN past threshold = regression.
        self._write(tmp_path, "SESSIONS_r03.json",
                    {**good, "sessions_fairness_index": 0.7})
        assert gate.main(["--sessions", "--dir", str(tmp_path)]) == 1
        # Holding every key passes; records predating the sessions
        # bench skip on null instead of failing.
        self._write(tmp_path, "SESSIONS_r04.json", good)
        self._write(tmp_path, "SESSIONS_r05.json",
                    {**good, "sessions_interactive_p99_ms": 115.0})
        assert gate.main(["--sessions", "--dir", str(tmp_path)]) == 0
        capsys.readouterr()

    def test_offload_keys_gated_direction_aware(self, tmp_path,
                                                capsys):
        """--offload judges OFFLOAD_r*.json on the repeat-viewer
        offload keys, direction-aware by name: the offload ratio and
        peer hit rate regress DOWN (less traffic absorbed off the
        origin), the 304 latency is a ``_ms`` key and regresses UP."""
        gate = self._gate()
        good = {"origin_offload_ratio": 1.0, "peer_hit_rate": 1.0,
                "p50_304_ms": 1.6}
        self._write(tmp_path, "OFFLOAD_r01.json", good)
        # Offload ratio DOWN 20% = regression.
        self._write(tmp_path, "OFFLOAD_r02.json",
                    {**good, "origin_offload_ratio": 0.8})
        assert gate.main(["--offload", "--dir", str(tmp_path)]) == 1
        verdict = json.loads(capsys.readouterr().out)
        by_key = {v["key"]: v["verdict"] for v in verdict["keys"]}
        assert by_key["origin_offload_ratio"] == "regression"
        assert by_key["p50_304_ms"] == "pass"
        # 304 latency UP 10x = regression even with the ratios flat.
        self._write(tmp_path, "OFFLOAD_r03.json",
                    {**good, "p50_304_ms": 16.0})
        assert gate.main(["--offload", "--dir", str(tmp_path)]) == 1
        capsys.readouterr()
        # Holding (or improving) every key passes.
        self._write(tmp_path, "OFFLOAD_r04.json", good)
        self._write(tmp_path, "OFFLOAD_r05.json",
                    {**good, "p50_304_ms": 1.2})
        assert gate.main(["--offload", "--dir", str(tmp_path)]) == 0
        # BENCH records in the same dir are ignored under --offload.
        verdict = json.loads(capsys.readouterr().out)
        assert verdict["new"] == "OFFLOAD_r05.json"

    def test_capacity_keys_gated_direction_aware(self, tmp_path,
                                                 capsys):
        """--capacity judges CAPACITY_r*.json (bench --smoke
        --capacity, the open-loop offered-load sweep) direction-aware
        by name: the knee and the scaling efficiency regress DOWN
        (less capacity before the SLO breaks), the p99 AT the knee is
        a ``_ms`` key and regresses UP."""
        gate = self._gate()
        good = {"capacity_knee_offered_tps": 120.0,
                "p99_at_knee_ms": 80.0,
                "capacity_scaling_efficiency": 0.5}
        self._write(tmp_path, "CAPACITY_r01.json", good)
        # Knee DOWN 25% = regression (the service hits collapse at
        # lower offered load) even with the p99 flat.
        self._write(tmp_path, "CAPACITY_r02.json",
                    {**good, "capacity_knee_offered_tps": 90.0})
        assert gate.main(["--capacity", "--dir", str(tmp_path)]) == 1
        verdict = json.loads(capsys.readouterr().out)
        by_key = {v["key"]: v["verdict"] for v in verdict["keys"]}
        assert by_key["capacity_knee_offered_tps"] == "regression"
        assert by_key["p99_at_knee_ms"] == "pass"
        # p99-at-knee UP 50% = regression even with the knee flat.
        self._write(tmp_path, "CAPACITY_r03.json",
                    {**good, "p99_at_knee_ms": 120.0})
        assert gate.main(["--capacity", "--dir", str(tmp_path)]) == 1
        capsys.readouterr()
        # Holding or improving every key passes; --watermark covers
        # the family (the newest round judged against the best knee
        # ever measured — r01's 120, not r03's).
        self._write(tmp_path, "CAPACITY_r04.json",
                    {**good, "capacity_knee_offered_tps": 130.0})
        assert gate.main(["--capacity", "--dir", str(tmp_path)]) == 0
        capsys.readouterr()
        assert gate.main(["--capacity", "--watermark", "--dir",
                          str(tmp_path)]) == 0
        verdict = json.loads(capsys.readouterr().out)
        assert verdict["mode"] == "watermark"
        by_key = {v["key"]: v for v in verdict["keys"]}
        assert by_key["capacity_knee_offered_tps"][
            "watermark_record"] == "CAPACITY_r01.json"
        # A new round under the best-ever knee by >10% fails the
        # watermark even if it passes pairwise against a sagged r04.
        self._write(tmp_path, "CAPACITY_r05.json",
                    {**good, "capacity_knee_offered_tps": 100.0})
        assert gate.main(["--capacity", "--watermark", "--dir",
                          str(tmp_path)]) == 1
        capsys.readouterr()

    def test_hotkey_keys_gated_direction_aware(self, tmp_path,
                                               capsys):
        """--hotkey judges HOTKEY_r*.json (bench --smoke --hotkey,
        the viral-image storm) direction-aware by name: the storm
        throughput ratio, the replication gain and the absolute storm
        throughput all regress DOWN.  ``hotkey_duplicate_staged`` is a
        correctness rider judged on the new record alone — any value
        above zero is an outright regression regardless of trend."""
        gate = self._gate()
        good = {"hotkey_storm_ratio": 0.95,
                "hotkey_replication_gain": 1.6,
                "hotkey_storm_tps": 100.0,
                "hotkey_duplicate_staged": 0}
        self._write(tmp_path, "HOTKEY_r01.json", good)
        # Storm ratio DOWN 30% = regression (the hot member melts
        # again) even with the raw throughput flat.
        self._write(tmp_path, "HOTKEY_r02.json",
                    {**good, "hotkey_storm_ratio": 0.65})
        assert gate.main(["--hotkey", "--dir", str(tmp_path)]) == 1
        verdict = json.loads(capsys.readouterr().out)
        by_key = {v["key"]: v["verdict"] for v in verdict["keys"]}
        assert by_key["hotkey_storm_ratio"] == "regression"
        assert by_key["hotkey_replication_gain"] == "pass"
        assert by_key["hotkey_duplicate_staged"] == "pass"
        # Replication gain collapsing toward 1.0 = regression (the
        # A/B says replication no longer buys anything).
        self._write(tmp_path, "HOTKEY_r03.json",
                    {**good, "hotkey_replication_gain": 1.05})
        assert gate.main(["--hotkey", "--dir", str(tmp_path)]) == 1
        capsys.readouterr()
        # A single duplicate-staged plane fails outright even with
        # every trend key flat or improving.
        self._write(tmp_path, "HOTKEY_r04.json", good)
        self._write(tmp_path, "HOTKEY_r05.json",
                    {**good, "hotkey_storm_tps": 110.0,
                     "hotkey_duplicate_staged": 1})
        assert gate.main(["--hotkey", "--dir", str(tmp_path)]) == 1
        verdict = json.loads(capsys.readouterr().out)
        by_key = {v["key"]: v["verdict"] for v in verdict["keys"]}
        assert by_key["hotkey_duplicate_staged"] == "regression"
        assert by_key["hotkey_storm_tps"] == "pass"
        # Holding every key passes; records predating the hotkey
        # bench skip on null instead of failing.
        self._write(tmp_path, "HOTKEY_r06.json", good)
        self._write(tmp_path, "HOTKEY_r07.json",
                    {**good, "hotkey_storm_tps": 104.0})
        assert gate.main(["--hotkey", "--dir", str(tmp_path)]) == 0
        capsys.readouterr()
        self._write(tmp_path, "HOTKEY_r08.json", {"ok": True})
        assert gate.main(["--hotkey", "--dir", str(tmp_path)]) == 0
        verdict = json.loads(capsys.readouterr().out)
        by_key = {v["key"]: v["verdict"] for v in verdict["keys"]}
        assert by_key["hotkey_storm_ratio"] == "skipped"
        assert by_key["hotkey_duplicate_staged"] == "skipped"
        # --watermark holds the best storm throughput ever measured.
        assert gate.main(["--hotkey", "--watermark", "--dir",
                          str(tmp_path)]) == 0
        capsys.readouterr()
        self._write(tmp_path, "HOTKEY_r09.json",
                    {**good, "hotkey_storm_tps": 80.0})
        assert gate.main(["--hotkey", "--watermark", "--dir",
                          str(tmp_path)]) == 1
        verdict = json.loads(capsys.readouterr().out)
        by_key = {v["key"]: v for v in verdict["keys"]}
        assert by_key["hotkey_storm_tps"][
            "watermark_record"] == "HOTKEY_r05.json"
        capsys.readouterr()

    def test_partition_keys_gated_direction_aware(self, tmp_path,
                                                  capsys):
        """--partition judges PARTITION_r*.json (bench --smoke
        --partition, the netsplit chaos drill): fence/restore latency
        are ``_ms`` keys and regress UP; the availability and
        split-brain contracts (majority 5xx-without-shed, roll
        commit, rejoin epoch, post-heal agreement, byte round-trip,
        counted refusals) are correctness riders judged on the new
        record alone."""
        gate = self._gate()
        good = {"part_fence_ms": 1200.0, "part_restore_ms": 1400.0,
                "part_majority_5xx": 0, "part_roll_committed": 1,
                "part_rejoin_epoch": 2, "part_postheal_agree": 1,
                "part_byte_agree": 1, "part_minority_refusals": 2}
        self._write(tmp_path, "PARTITION_r01.json", good)
        # Fence latency UP 3x = regression (the minority served
        # un-fenced — potentially split-brain — for 3x longer).
        self._write(tmp_path, "PARTITION_r02.json",
                    {**good, "part_fence_ms": 3600.0})
        assert gate.main(["--partition", "--dir",
                          str(tmp_path)]) == 1
        verdict = json.loads(capsys.readouterr().out)
        by_key = {v["key"]: v["verdict"] for v in verdict["keys"]}
        assert by_key["part_fence_ms"] == "regression"
        assert by_key["part_restore_ms"] == "pass"
        assert by_key["part_majority_5xx"] == "pass"
        # One majority-side failure that was not counted shed fails
        # outright, with every trend key flat.
        self._write(tmp_path, "PARTITION_r03.json", good)
        self._write(tmp_path, "PARTITION_r04.json",
                    {**good, "part_majority_5xx": 1})
        assert gate.main(["--partition", "--dir",
                          str(tmp_path)]) == 1
        verdict = json.loads(capsys.readouterr().out)
        by_key = {v["key"]: v["verdict"] for v in verdict["keys"]}
        assert by_key["part_majority_5xx"] == "regression"
        assert by_key["part_fence_ms"] == "pass"
        # An aborted roll, a minority that refused nothing, or a
        # post-heal disagreement each fail the same way.
        self._write(tmp_path, "PARTITION_r05.json",
                    {**good, "part_roll_committed": 0,
                     "part_minority_refusals": 0,
                     "part_postheal_agree": 0})
        assert gate.main(["--partition", "--dir",
                          str(tmp_path)]) == 1
        verdict = json.loads(capsys.readouterr().out)
        by_key = {v["key"]: v["verdict"] for v in verdict["keys"]}
        assert by_key["part_roll_committed"] == "regression"
        assert by_key["part_minority_refusals"] == "regression"
        assert by_key["part_postheal_agree"] == "regression"
        # Holding every contract passes — including a one-gossip-tick
        # restore wobble (+29%): fence/restore are tick-quantized, so
        # the family's default bar is 0.50, not the 0.10 that would
        # fail identical code on honest jitter.  Records that predate
        # the family skip on null instead of failing.
        self._write(tmp_path, "PARTITION_r06.json", good)
        self._write(tmp_path, "PARTITION_r07.json",
                    {**good, "part_restore_ms": 1800.0})
        assert gate.main(["--partition", "--dir",
                          str(tmp_path)]) == 0
        capsys.readouterr()
        self._write(tmp_path, "PARTITION_r08.json", {"ok": True})
        assert gate.main(["--partition", "--dir",
                          str(tmp_path)]) == 0
        verdict = json.loads(capsys.readouterr().out)
        by_key = {v["key"]: v["verdict"] for v in verdict["keys"]}
        assert by_key["part_fence_ms"] == "skipped"
        assert by_key["part_majority_5xx"] == "skipped"
        capsys.readouterr()

    def test_workloads_keys_gated_direction_aware(self, tmp_path,
                                                  capsys):
        """--workloads judges WORKLOADS_r*.json (bench --smoke
        --workloads, the device mask/overlay/pyramid/animation drill)
        direction-aware by name: the batched latencies and the
        pyramid build are ``_ms`` keys and regress UP; the parity-mix
        size (``mask_renders``) regresses DOWN — fewer masks
        exercised is a shrunken drill, not a win."""
        gate = self._gate()
        good = {"mask_device_ms": 12.0, "overlay_device_ms": 8.0,
                "pyramid_build_ms": 150.0, "anim_first_frame_ms": 9.0,
                "anim_total_ms": 40.0, "mask_renders": 12}
        self._write(tmp_path, "WORKLOADS_r01.json", good)
        # First-frame latency UP 3x = regression (the stream promise
        # is "first frame fast"), with every other key flat.
        self._write(tmp_path, "WORKLOADS_r02.json",
                    {**good, "anim_first_frame_ms": 27.0})
        assert gate.main(["--workloads", "--dir",
                          str(tmp_path)]) == 1
        verdict = json.loads(capsys.readouterr().out)
        by_key = {v["key"]: v["verdict"] for v in verdict["keys"]}
        assert by_key["anim_first_frame_ms"] == "regression"
        assert by_key["mask_device_ms"] == "pass"
        assert by_key["mask_renders"] == "pass"
        # The parity mix shrinking is judged DOWNWARD: 12 -> 4 masks
        # rendered means the drill stopped proving what it claims.
        self._write(tmp_path, "WORKLOADS_r03.json", good)
        self._write(tmp_path, "WORKLOADS_r04.json",
                    {**good, "mask_renders": 4})
        assert gate.main(["--workloads", "--dir",
                          str(tmp_path)]) == 1
        verdict = json.loads(capsys.readouterr().out)
        by_key = {v["key"]: v["verdict"] for v in verdict["keys"]}
        assert by_key["mask_renders"] == "regression"
        assert by_key["pyramid_build_ms"] == "pass"
        # Smoke-scale batched renders are a few ms, so the family bar
        # is the wide 0.50, not 0.10: a +40% wobble on the overlay
        # latency passes; a faster round obviously passes too.
        self._write(tmp_path, "WORKLOADS_r05.json", good)
        self._write(tmp_path, "WORKLOADS_r06.json",
                    {**good, "overlay_device_ms": 11.2,
                     "anim_total_ms": 30.0})
        assert gate.main(["--workloads", "--dir",
                          str(tmp_path)]) == 0
        capsys.readouterr()
        # Records that predate the workloads bench skip on null.
        self._write(tmp_path, "WORKLOADS_r07.json", {"ok": True})
        assert gate.main(["--workloads", "--dir",
                          str(tmp_path)]) == 0
        verdict = json.loads(capsys.readouterr().out)
        by_key = {v["key"]: v["verdict"] for v in verdict["keys"]}
        assert by_key["mask_device_ms"] == "skipped"
        assert by_key["mask_renders"] == "skipped"
        capsys.readouterr()

    def test_multichip_fleet_curve_gated(self, tmp_path, capsys):
        """--multichip judges MULTICHIP_r*.json on the fleet scaling
        keys: ok-true-only rounds (every record predating the curve)
        skip on null, a scaling regression fails, and --watermark
        holds the best-ever curve."""
        gate = self._gate()
        curve = {"fleet_tiles_per_sec_m1": 100.0,
                 "fleet_tiles_per_sec_m4": 360.0,
                 "fleet_tiles_per_sec_m8": 650.0,
                 "fleet_scaling_efficiency": 0.81}
        self._write(tmp_path, "MULTICHIP_r01.json", {"ok": True})
        self._write(tmp_path, "MULTICHIP_r02.json",
                    {"ok": True, **curve})
        # r01 -> r02: the legacy record carries no curve — skip, pass.
        assert gate.main(["--multichip", "--dir",
                          str(tmp_path)]) == 0
        verdict = json.loads(capsys.readouterr().out)
        assert all(k["verdict"] == "skipped" for k in verdict["keys"])
        # BENCH records in the same dir are ignored under --multichip.
        self._write(tmp_path, "BENCH_r09.json",
                    {"service_tiles_per_sec": 1.0})
        # A fleet that stopped scaling fails the gate.
        self._write(tmp_path, "MULTICHIP_r03.json", {
            "ok": True, "fleet_tiles_per_sec_m1": 100.0,
            "fleet_tiles_per_sec_m4": 200.0,
            "fleet_tiles_per_sec_m8": 300.0,
            "fleet_scaling_efficiency": 0.37})
        assert gate.main(["--multichip", "--dir",
                          str(tmp_path)]) == 1
        verdict = json.loads(capsys.readouterr().out)
        assert verdict["new"] == "MULTICHIP_r03.json"
        assert {k["key"] for k in verdict["keys"]
                if k["verdict"] == "regression"} == {
            "fleet_tiles_per_sec_m8", "fleet_tiles_per_sec_m4",
            "fleet_scaling_efficiency"}
        # Watermark mode: r03 is judged against r02's best-ever marks.
        assert gate.main(["--multichip", "--watermark", "--dir",
                          str(tmp_path)]) == 1
        verdict = json.loads(capsys.readouterr().out)
        assert verdict["keys"][0]["watermark_record"] == \
            "MULTICHIP_r02.json"

    def test_multichip_forensics_keys_gated_skip_on_null(
            self, tmp_path, capsys):
        """--multichip also judges the control-plane forensics
        acceptance keys: fed_trace_stitched (the stitched cross-host
        waterfall verdict, 1 or 0) and decision_records (outcome-
        carrying autoscaler records in the merged ledger).  Records
        predating the forensics bench skip on null instead of
        failing; losing the stitch (1 -> 0) fails the gate."""
        gate = self._gate()
        curve = {"fleet_tiles_per_sec_m8": 650.0,
                 "fleet_scaling_efficiency": 0.81}
        self._write(tmp_path, "MULTICHIP_r01.json",
                    {"ok": True, **curve})
        self._write(tmp_path, "MULTICHIP_r02.json",
                    {"ok": True, **curve,
                     "fed_trace_stitched": 1, "decision_records": 3})
        # r01 predates the forensics bench: both new keys skip.
        assert gate.main(["--multichip", "--dir",
                          str(tmp_path)]) == 0
        verdict = json.loads(capsys.readouterr().out)
        by_key = {k["key"]: k["verdict"] for k in verdict["keys"]}
        assert by_key["fed_trace_stitched"] == "skipped"
        assert by_key["decision_records"] == "skipped"
        # A round that lost the stitch regresses 1 -> 0.
        self._write(tmp_path, "MULTICHIP_r03.json",
                    {"ok": True, **curve,
                     "fed_trace_stitched": 0, "decision_records": 3})
        assert gate.main(["--multichip", "--dir",
                          str(tmp_path)]) == 1
        verdict = json.loads(capsys.readouterr().out)
        by_key = {k["key"]: k["verdict"] for k in verdict["keys"]}
        assert by_key["fed_trace_stitched"] == "regression"
        assert by_key["decision_records"] == "pass"

    def test_latency_key_gates_in_the_up_direction(self, tmp_path):
        """p50_service_tile_ms is a DEFAULT key and judged
        lower-is-better: a >=10% latency INCREASE fails even when
        throughput is flat (the regression class a throughput-only
        gate cannot see)."""
        gate = self._gate()
        old = self._write(tmp_path, "a.json",
                          {"service_tiles_per_sec": 100.0,
                           "p50_service_tile_ms": 100.0})
        worse = self._write(tmp_path, "b.json",
                           {"service_tiles_per_sec": 100.0,
                            "p50_service_tile_ms": 110.0})
        assert gate.main([old, worse]) == 1
        # A latency DROP (improvement) passes, as does one within
        # threshold.
        better = self._write(tmp_path, "c.json",
                             {"service_tiles_per_sec": 100.0,
                              "p50_service_tile_ms": 50.0})
        assert gate.main([old, better]) == 0
        near = self._write(tmp_path, "d.json",
                           {"service_tiles_per_sec": 100.0,
                            "p50_service_tile_ms": 109.0})
        assert gate.main([old, near]) == 0

    def test_latency_key_skips_on_null_like_throughput(self, tmp_path):
        gate = self._gate()
        old = self._write(tmp_path, "a.json",
                          {"service_tiles_per_sec": 100.0,
                           "p50_service_tile_ms": None})
        new = self._write(tmp_path, "b.json",
                          {"service_tiles_per_sec": 100.0,
                           "p50_service_tile_ms": 50.0})
        assert gate.main([old, new]) == 0
        assert gate.main(["--strict", old, new]) == 1

    def test_raw_upload_is_a_default_key(self, tmp_path):
        """The r01 -> r05 524 -> 4.8 MB/s upload collapse class gates
        by default now."""
        gate = self._gate()
        old = self._write(tmp_path, "a.json",
                          {"service_tiles_per_sec": 100.0,
                           "raw_upload_mb_per_sec": 500.0})
        new = self._write(tmp_path, "b.json",
                          {"service_tiles_per_sec": 100.0,
                           "raw_upload_mb_per_sec": 5.0})
        assert gate.main([old, new]) == 1

    def test_watermark_catches_compounded_drift(self, tmp_path,
                                                capsys):
        """The r02 -> r05 failure mode in miniature: -10% per round
        passes every PAIRWISE gate but compounds past the watermark
        threshold — the watermark gate fails where pairwise cannot."""
        gate = self._gate()
        rates = [100.0, 91.0, 83.0, 76.0]      # each pair within 10%
        for i, rate in enumerate(rates):
            self._write(tmp_path, f"BENCH_r{i + 1:02d}.json",
                        {"service_tiles_per_sec": rate})
        # Every pairwise gate over the sequence passes...
        paths = sorted(str(p) for p in tmp_path.iterdir())
        for old, new in zip(paths, paths[1:]):
            assert gate.main([old, new]) == 0
        capsys.readouterr()
        # ...but the best-ever watermark (100, set by r01) fails r04.
        assert gate.main(["--watermark", "--dir", str(tmp_path)]) == 1
        verdict = json.loads(capsys.readouterr().out)
        assert verdict["mode"] == "watermark"
        row = verdict["keys"][0]
        assert row["verdict"] == "regression"
        assert row["old"] == 100.0
        assert row["watermark_record"] == "BENCH_r01.json"

    def test_watermark_passes_a_recovered_record(self, tmp_path):
        """A new record at (or within threshold of) the best-ever mark
        passes — recovery closes the gate cleanly."""
        gate = self._gate()
        for i, rate in enumerate([100.0, 70.0, 60.0, 96.0]):
            self._write(tmp_path, f"BENCH_r{i + 1:02d}.json",
                        {"service_tiles_per_sec": rate})
        assert gate.main(["--watermark", "--dir", str(tmp_path)]) == 0

    def test_watermark_latency_key_uses_min(self, tmp_path, capsys):
        """Latency watermarks are the BEST (lowest) value ever seen;
        a new record >=10% above that mark fails even if it beats the
        previous round."""
        gate = self._gate()
        lat = [40.0, 90.0, 80.0]   # best-ever 40 set in r01
        for i, v in enumerate(lat):
            self._write(tmp_path, f"BENCH_r{i + 1:02d}.json",
                        {"service_tiles_per_sec": 100.0,
                         "p50_service_tile_ms": v})
        assert gate.main(["--watermark", "--dir", str(tmp_path)]) == 1
        verdict = json.loads(capsys.readouterr().out)
        rows = {r["key"]: r for r in verdict["keys"]}
        row = rows["p50_service_tile_ms"]
        assert row["verdict"] == "regression"
        assert row["old"] == 40.0

    def test_watermark_skips_never_recorded_keys(self, tmp_path):
        """A key no historical record ever carried skips (weather
        semantics), and --strict turns that into a failure."""
        gate = self._gate()
        for i in range(2):
            self._write(tmp_path, f"BENCH_r{i + 1:02d}.json",
                        {"service_tiles_per_sec": 100.0})
        assert gate.main(["--watermark", "--dir", str(tmp_path)]) == 0
        assert gate.main(["--watermark", "--strict", "--dir",
                          str(tmp_path)]) == 1

    def test_watermark_reads_driver_envelopes(self, tmp_path):
        """Historical BENCH records are driver envelopes ({parsed} or
        a {tail} whose bench line may have its leading brace sheared
        off by the front-truncated capture); the watermark gate must
        read every round or the mark silently shrinks."""
        gate = self._gate()
        self._write(tmp_path, "BENCH_r01.json",
                    {"parsed": {"metric": "m",
                                "service_tiles_per_sec": 100.0}})
        bench_line = json.dumps({"metric": "m",
                                 "service_tiles_per_sec": 50.0})
        self._write(tmp_path, "BENCH_r02.json",
                    {"parsed": None,
                     "tail": "noise\n" + bench_line[1:] + "\n"})
        assert gate.main(["--watermark", "--dir", str(tmp_path)]) == 1


# -------------------------------------------------------- debug surface

IMG = 7
URL = (f"/webgateway/render_image_region/{IMG}/0/0"
       "?tile=0,0,0,32,32&format=jpeg&m=c&c=1|0:60000$FF0000")


@pytest.fixture(scope="module")
def data_dir(tmp_path_factory):
    from omero_ms_image_region_tpu.io.store import build_pyramid
    root = tmp_path_factory.mktemp("forensicsdata")
    rng = np.random.default_rng(13)
    planes = rng.integers(0, 60000, size=(2, 2, 64, 64)).astype(
        np.uint16)
    build_pyramid(planes, str(root / str(IMG)), chunk=(32, 32),
                  n_levels=1)
    return str(root)


def _device_config(data_dir, tmp_path=None):
    from omero_ms_image_region_tpu.server.config import AppConfig
    cfg = AppConfig(data_dir=data_dir)
    cfg.renderer.cpu_fallback_max_px = 0   # exercise the batched path
    # Barrier settlement so device-cost attribution lands before the
    # request finishes (first-tile-out races it on slow hosts); the
    # streaming path is gated deterministically in test_wire_v3.
    cfg.wire.streaming = False
    if tmp_path is not None:
        cfg.telemetry.profile_dir = str(tmp_path / "profiles")
        cfg.telemetry.flight_recorder_dir = str(tmp_path / "flight")
    return cfg


class TestDebugEndpoints:
    def test_combined_costs_flight_profile(self, data_dir, tmp_path):
        from aiohttp.test_utils import TestClient, TestServer

        from omero_ms_image_region_tpu.server.app import create_app

        async def main():
            app = create_app(_device_config(data_dir, tmp_path))
            client = TestClient(TestServer(app))
            await client.start_server()
            try:
                r = await client.get(URL)
                assert r.status == 200
                await r.read()

                r = await client.get("/debug/costs")
                costs = await r.json()
                assert r.status == 200
                assert costs["observed"] >= 1
                top = costs["top"][0]
                assert top["route"] == "render_image_region"
                assert top["cost"]["device_ms"] > 0
                assert top["cost"]["wire_bytes"] > 0
                # The shape cost model saw the batched dispatch.
                assert any(s["dispatches"] >= 1
                           for s in costs["shapes"].values())

                r = await client.get("/debug/flightrecorder?dump=1")
                flight = await r.json()
                assert r.status == 200
                kinds = {e["kind"] for e in flight["events"]}
                assert "batch.formed" in kinds
                assert flight["dumped_to"] and os.path.exists(
                    flight["dumped_to"])

                # The acceptance criterion: a capture artifact on the
                # CPU backend.
                r = await client.get("/debug/profile?ms=50")
                prof = await r.json()
                assert r.status == 200, prof
                assert prof["files"], prof
                assert os.path.isdir(prof["dir"])
                assert prof["bytes"] > 0
                # The capture's reduction rides the same answer; the
                # CPU backend has no device plane to reduce, so only
                # the count of captures moves on /metrics.
                assert prof["summary"] is None
                assert "summary_error" not in prof
                r = await client.get("/metrics")
                text = await r.text()
                assert "imageregion_profile_captures_total 1" in text
                assert "imageregion_profile_busy_ms_total 0.0" in text
                assert "imageregion_profile_device_ms_total{" \
                    not in text
                assert "imageregion_span_mean_ms" not in text
                assert 'imageregion_span_count{span="batcher.laneWait"}' \
                    in text
            finally:
                await client.close()

        asyncio.run(main())

    def test_profile_bad_ms_is_400(self, data_dir):
        from aiohttp.test_utils import TestClient, TestServer

        from omero_ms_image_region_tpu.server.app import create_app

        async def main():
            app = create_app(_device_config(data_dir))
            client = TestClient(TestServer(app))
            await client.start_server()
            try:
                r = await client.get("/debug/profile?ms=banana")
                assert r.status == 400
            finally:
                await client.close()

        asyncio.run(main())

    def test_proxy_forwards_profile_and_merges_flight(self, data_dir,
                                                      tmp_path):
        """Frontend proxy: /debug/profile rides the sidecar wire (the
        capture runs in the device-owning process) and the frontend's
        /debug/flightrecorder merges the sidecar's ring."""
        from aiohttp.test_utils import TestClient, TestServer

        from omero_ms_image_region_tpu.server.app import create_app
        from omero_ms_image_region_tpu.server.config import (
            AppConfig, SidecarConfig)
        from omero_ms_image_region_tpu.server.sidecar import run_sidecar

        sock = str(tmp_path / "f.sock")

        async def main():
            task = asyncio.create_task(
                run_sidecar(_device_config(data_dir, tmp_path), sock))
            for _ in range(200):
                if task.done():
                    raise AssertionError(
                        f"sidecar died: {task.exception()!r}")
                if os.path.exists(sock):
                    break
                await asyncio.sleep(0.05)
            app = create_app(AppConfig(
                data_dir=data_dir,
                sidecar=SidecarConfig(socket=sock, role="frontend")))
            client = TestClient(TestServer(app))
            await client.start_server()
            try:
                r = await client.get(URL)
                assert r.status == 200
                await r.read()
                r = await client.get("/debug/profile?ms=50")
                prof = await r.json()
                assert r.status == 200, prof
                assert prof["files"], prof
                # The summary crosses the sidecar wire with the
                # manifest (None: no device plane on the CPU backend).
                assert "summary" in prof and prof["summary"] is None
                assert "summary_error" not in prof
                r = await client.get("/debug/flightrecorder")
                flight = await r.json()
                assert r.status == 200
                assert flight["sidecar"] is not None
                assert flight["sidecar"]["events_total"] > 0
                # Proxy-side cost ledger: the render above carried its
                # device-side costs over the wire (in-process sidecar
                # shares the trace; either path must yield a ledger).
                r = await client.get("/debug/costs")
                costs = await r.json()
                assert costs["top"], costs
                assert costs["top"][0]["cost"]["device_ms"] > 0
            finally:
                await client.close()
                task.cancel()
                try:
                    await task
                except (asyncio.CancelledError, Exception):
                    pass

        asyncio.run(main())


# ------------------------------------------------------- reset contract

class TestResetContract:
    def test_reset_clears_every_accumulator(self):
        """Repeated in-process test apps must not leak counts across
        tests: everything reset() owns goes back to zero."""
        telemetry.RESILIENCE.count_shed("queue-full")
        telemetry.RESILIENCE.count_retry("image")
        telemetry.RESILIENCE.observe_attempts("image", 2)
        telemetry.RESILIENCE.count_deadline_cancelled()
        telemetry.READINESS.prewarm_pending = True
        telemetry.FLIGHT.record("e")
        telemetry.SLO.configure(availability_target=0.9)
        telemetry.SLO.record(503, 1.0)
        telemetry.SHAPE_COSTS.observe("B1x1x8x8", 1.0)
        telemetry.COST_TOPK.offer({"total_ms": 5.0})
        telemetry.observe_request_cost("r", {"device_ms": 1.0})
        telemetry.count_request("r", 200)
        telemetry.FLEET.count_routed("m0")
        telemetry.FLEET.count_stolen("m1")
        telemetry.FLEET.count_failed_over("m2")
        telemetry.SESSIONS.set_tracked(5)
        telemetry.SESSIONS.count_observation()
        telemetry.SESSIONS.count_evicted()
        telemetry.PREFETCH.count_predicted()
        telemetry.PREFETCH.count_staged()
        telemetry.PREFETCH.count_hit()
        telemetry.PREFETCH.count_skipped("budget")
        telemetry.PREFETCH.set_budget(0.5)
        telemetry.QOS.count_shed("interactive")
        telemetry.QOS.count_dequeued("bulk")
        telemetry.QOS.count_jump()
        telemetry.HTTPCACHE.count_etag_request()
        telemetry.HTTPCACHE.count_not_modified()
        telemetry.HTTPCACHE.count_head()
        telemetry.HTTPCACHE.count_peer_probe()
        telemetry.HTTPCACHE.count_peer_hit()
        telemetry.HTTPCACHE.count_peer_fetch()
        telemetry.HTTPCACHE.count_peer_fallback()
        telemetry.HTTPCACHE.count_peer_putback()

        telemetry.reset()

        assert telemetry.RESILIENCE.shed == {}
        assert telemetry.RESILIENCE.retries == {}
        assert telemetry.RESILIENCE.deadline_cancelled == 0
        assert telemetry.RESILIENCE.attempts_hist.series("x") == []
        assert telemetry.READINESS.prewarm_pending is False
        assert len(telemetry.FLIGHT) == 0
        assert telemetry.FLIGHT.events_total == 0
        assert telemetry.SLO.enabled is False
        assert telemetry.SLO.metric_lines() == []
        assert telemetry.SHAPE_COSTS.metric_lines() == []
        assert telemetry.COST_TOPK.snapshot() == []
        assert telemetry.cost_metric_lines() == []
        assert telemetry.FLEET.totals() == {
            "routed": 0, "stolen": 0, "failed_over": 0}
        assert telemetry.fleet_metric_lines() == []
        assert telemetry.SESSIONS.tracked == 0
        assert telemetry.SESSIONS.observations == 0
        assert telemetry.SESSIONS.evicted == 0
        assert telemetry.PREFETCH.predicted == 0
        assert telemetry.PREFETCH.staged == 0
        assert telemetry.PREFETCH.hits == 0
        assert telemetry.PREFETCH.skipped == {}
        assert telemetry.PREFETCH.budget_scale == 1.0
        assert telemetry.PREFETCH.hit_rate() is None
        assert telemetry.QOS.shed == {}
        assert telemetry.QOS.dequeued == {}
        assert telemetry.QOS.jumps == 0
        assert telemetry.HTTPCACHE.not_modified == 0
        assert telemetry.HTTPCACHE.etag_requests == 0
        assert telemetry.HTTPCACHE.head == 0
        assert telemetry.HTTPCACHE.peer_probes == 0
        assert telemetry.HTTPCACHE.peer_hits == 0
        assert telemetry.HTTPCACHE.peer_fetches == 0
        assert telemetry.HTTPCACHE.peer_fallbacks == 0
        assert telemetry.HTTPCACHE.peer_putbacks == 0
        assert telemetry.HTTPCACHE.metric_lines() == []
        assert telemetry.request_metric_lines() == [
            "imageregion_flight_events 0",
            "imageregion_flight_events_total 0",
            "imageregion_flight_dumps_total 0",
        ]


# ------------------------------------------- waterfall tail breakdown

class TestWaterfallTailBreakdown:
    def test_span_stats_report_tail_percentiles_and_max(self):
        """The r05 anomaly class made visible: a stage whose mean is
        dominated by a few stragglers exposes p95/p99/max alongside
        the mean and p50 in every stats export."""
        from omero_ms_image_region_tpu.utils.stopwatch import (
            StopWatchRegistry)

        reg = StopWatchRegistry()
        for _ in range(90):
            reg.record("batcher.queueWait", 2.0)
        for _ in range(10):                         # straggler decile
            reg.record("batcher.queueWait", 5000.0)
        s = reg.snapshot()["batcher.queueWait"]
        assert s["count"] == 100
        assert s["p50_ms"] <= 4.0                   # bucket bound of 2ms
        assert s["mean_ms"] > 400.0                 # the mean conflates
        assert s["p95_ms"] >= 4000.0                # the tail is visible
        assert s["p99_ms"] >= 4000.0
        assert s["max_ms"] == 5000.0                # exact high-water
        assert s["p95_ms"] <= s["p99_ms"] <= 2 * s["max_ms"]

    def test_trace_report_renders_stats_tables(self, capsys):
        """scripts/trace_report.py renders a per-stage stats mapping
        (the bench record's service_waterfall export) as a table and
        flags heavy-tail stages."""
        mod = _load_script("trace_report")
        doc = {
            "service_waterfall": {
                "batcher.queueWait": {
                    "count": 672, "total_ms": 1530041.2,
                    "mean_ms": 2276.8, "p50_ms": 2.2,
                    "p95_ms": 16384.0, "p99_ms": 16384.0,
                    "max_ms": 21034.7},
                "wire.fetch": {
                    "count": 102, "total_ms": 218004.3,
                    "mean_ms": 2137.3, "p50_ms": 598.7,
                    "p95_ms": 8192.0, "p99_ms": 8192.0,
                    "max_ms": 9123.0},
            },
        }
        out = mod.render_doc(doc)
        assert "batcher.queueWait" in out
        assert "p95" in out and "p99" in out and "max" in out
        # The 1000x mean-vs-p50 stage is called out; the 3.5x one not.
        assert out.count("heavy tail") == 1
        # Plain {span: stats} mappings (REGISTRY.snapshot()) render too.
        out2 = mod.render_doc(doc["service_waterfall"])
        assert "wire.fetch" in out2
        # Legacy stats without the tail fields still render (dashes).
        legacy = {"x": {"count": 1, "total_ms": 1.0, "mean_ms": 1.0,
                        "p50_ms": 1.0}}
        assert "x" in mod.render_doc(legacy)


# ----------------------------------- cross-host waterfall rendering

class TestFederatedTraceRendering:
    def test_fed_hop_spans_render_kind_at_host_with_footer(self):
        """fed.hop spans render as fed:kind@host and the report gains
        a per-HOST ms footer — the stitched multi-host story the
        Control-plane forensics runbook documents."""
        mod = _load_script("trace_report")
        doc = {
            "trace_id": "t-fed", "route": "region", "status": 200,
            "total_ms": 20.0,
            "spans": [
                {"name": "service.total", "start_ms": 0.0,
                 "dur_ms": 20.0},
                {"name": "fed.hop", "start_ms": 2.0, "dur_ms": 6.0,
                 "host": "hostB", "member": "b0",
                 "kind": "shard_transfer", "bytes": 4096},
                {"name": "fed.hop", "start_ms": 3.0, "dur_ms": 2.0,
                 "host": "hostB", "member": "b0", "kind": "stage"},
                {"name": "fed.hop", "start_ms": 10.0, "dur_ms": 1.0,
                 "host": "hostC", "member": "c0", "kind": "gossip"},
            ],
        }
        out = mod.render_trace(doc)
        assert "fed:shard_transfer@hostB" in out
        assert "fed:stage@hostB" in out
        assert "fed:gossip@hostC" in out
        # kind/host fold into the marker, not the extras suffix.
        assert "'kind'" not in out and "'host'" not in out
        assert "'bytes': 4096" in out
        # Per-host footer sums each host's span time.
        assert "hosts: hostB=8.0ms  hostC=1.0ms" in out
        # The member lane column still works alongside.
        assert "members=b0,c0" in out

    def test_single_host_trace_has_no_hosts_footer(self):
        mod = _load_script("trace_report")
        doc = {"spans": [{"name": "render", "start_ms": 0.0,
                          "dur_ms": 5.0}]}
        assert "hosts:" not in mod.render_trace(doc)

    def test_decision_events_marked_and_summed_in_flight_render(self):
        """decision.<kind> flight events get the ``+`` mark and a
        control-plane footer keyed kind:verdict."""
        mod = _load_script("trace_report")
        doc = {
            "reason": "test", "pid": 1, "ts": 100.0,
            "events": [
                {"ts": 98.0, "kind": "decision.autoscaler",
                 "verdict": "blocked", "seq": 1, "member": "m0"},
                {"ts": 99.0, "kind": "decision.gossip",
                 "verdict": "mismatch", "seq": 2},
                {"ts": 99.5, "kind": "decision.gossip",
                 "verdict": "mismatch", "seq": 3},
                {"ts": 99.9, "kind": "request.shed"},
            ],
        }
        out = mod.render_flight(doc)
        assert "+ decision.autoscaler" in out
        assert ("control-plane: decision.autoscaler:blocked=1  "
                "decision.gossip:mismatch=2") in out
        # Non-decision events keep their unmarked rendering.
        assert "+ request.shed" not in out

    def test_flight_render_without_decisions_has_no_footer(self):
        mod = _load_script("trace_report")
        doc = {"reason": "r", "pid": 1, "ts": 1.0,
               "events": [{"ts": 0.5, "kind": "request.shed"}]}
        assert "control-plane:" not in mod.render_flight(doc)
