"""Observability layer: trace waterfalls, bucketed histograms, health
probes, slow-request dumps, and the Prometheus exposition contract."""

import asyncio
import json
import os
import re

import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from omero_ms_image_region_tpu.io.store import build_pyramid
from omero_ms_image_region_tpu.server.app import create_app
from omero_ms_image_region_tpu.server.config import (AppConfig,
                                                     SidecarConfig)
from omero_ms_image_region_tpu.server.sidecar import run_sidecar
from omero_ms_image_region_tpu.utils import telemetry
from omero_ms_image_region_tpu.utils.stopwatch import REGISTRY

IMG = 7
H = W = 64


@pytest.fixture(scope="module")
def data_dir(tmp_path_factory):
    root = tmp_path_factory.mktemp("teledata")
    rng = np.random.default_rng(13)
    planes = rng.integers(0, 60000, size=(2, 2, H, W)).astype(np.uint16)
    build_pyramid(planes, str(root / str(IMG)), chunk=(32, 32),
                  n_levels=1)
    return str(root)


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    # telemetry.reset() covers the process-global accumulators but NOT
    # the stopwatch span registry (a separate module to stay importable
    # everywhere) — in a full tier-1 run other test files' spans leak
    # into this file's span-count assertions without the explicit
    # registry reset.
    telemetry.reset()
    REGISTRY.reset()
    yield
    telemetry.reset()
    REGISTRY.reset()


def _finished_render_traces():
    """Finished render traces that actually recorded a waterfall.

    Tier-1 runs the whole suite in ONE process: a prior test's
    cancelled straggler (a leaked dispatcher task or late sidecar
    reply) can finish a span-LESS trace into the freshly reset
    registry AFTER this test's own request lands, so positional
    ``recent[-1]`` selection is host-dependent.  Selecting the traces
    that carry spans pins the assertions to real renders."""
    return [t for t in telemetry.TRACES.recent
            if t.route == "render_image_region" and t.spans]


def _device_config(data_dir, **kw):
    cfg = AppConfig(data_dir=data_dir, **kw)
    # Tiny test tiles must exercise the batched device path the traces
    # thread through, not the host-kernel fallback.
    cfg.renderer.cpu_fallback_max_px = 0
    # Barrier settlement: first-tile-out resolves request futures from
    # inside the encode, racing the group tail (batch span close,
    # device_ms attribution) against the request's access line — which
    # loses on slow hosts.  These tests assert that accounting, so they
    # run the A/B barrier path; streaming has its own deterministic
    # gate in test_wire_v3.
    cfg.wire.streaming = False
    return cfg


def _fetch(config, *requests, cookies=None):
    async def main():
        app = create_app(config)
        client = TestClient(TestServer(app), cookies=cookies)
        await client.start_server()
        out = []
        try:
            for method, path in requests:
                resp = await client.request(method, path)
                out.append((resp.status, dict(resp.headers),
                            await resp.read()))
        finally:
            await client.close()
        return out

    return asyncio.run(main())


URL = (f"/webgateway/render_image_region/{IMG}/0/0"
       "?tile=0,0,0,32,32&format=jpeg&m=c&c=1|0:60000$FF0000")


# ------------------------------------------------------------ histograms

class TestHistogram:
    def test_fixed_log_scale_bounds(self):
        b = telemetry.BUCKET_BOUNDS_MS
        assert b[0] == 0.25 and len(b) == 18
        assert all(hi == lo * 2 for lo, hi in zip(b, b[1:]))

    def test_bucket_boundaries_are_le(self):
        h = telemetry.Histogram(bounds=(1.0, 2.0, 4.0))
        for v in (0.5, 1.0, 1.5, 2.0, 4.0, 5.0):
            h.add(v)
        # le semantics: a sample equal to the bound lands IN the bucket.
        assert h.counts == [2, 2, 1, 1]
        assert h.cumulative() == [2, 4, 5, 6]
        assert h.count == 6
        assert h.sum == pytest.approx(14.0)

    def test_series_exposition(self):
        h = telemetry.Histogram(bounds=(1.0, 2.0))
        h.add(0.5)
        h.add(3.0)
        lines = h.series("x_ms", 'route="r"')
        assert 'x_ms_bucket{route="r",le="1"} 1' in lines
        assert 'x_ms_bucket{route="r",le="2"} 1' in lines
        assert 'x_ms_bucket{route="r",le="+Inf"} 2' in lines
        assert 'x_ms_sum{route="r"} 3.5' in lines
        assert 'x_ms_count{route="r"} 2' in lines

    def test_unlabelled_series(self):
        h = telemetry.Histogram(bounds=(1.0,))
        h.add(0.5)
        lines = h.series("y_ms")
        assert 'y_ms_bucket{le="1"} 1' in lines
        assert "y_ms_sum 0.5" in lines
        assert "y_ms_count 1" in lines

    def test_quantile_estimate(self):
        h = telemetry.Histogram()
        for v in [1.0] * 50 + [100.0] * 50:
            h.add(v)
        assert h.quantile(0.25) == 1.0
        assert h.quantile(0.9) >= 100.0


# ----------------------------------------------------------- trace flow

class TestTracePropagation:
    def test_combined_batcher_spans_share_request_trace(self, data_dir):
        [(status, _, _)] = _fetch(_device_config(data_dir),
                                  ("GET", URL))
        assert status == 200
        traces = _finished_render_traces()
        assert traces, "request trace was never finished"
        trace = traces[-1]
        names = {s["name"] for s in trace.spans}
        # The frontend handler span, the batcher queue-wait, the
        # batched device render and the wire fetch all landed on the
        # ONE request trace.
        assert "Renderer.renderAsPackedInt" in names
        assert "batcher.queueWait" in names
        assert "Renderer.renderAsPackedInt.batch" in names
        assert "wire.fetch" in names

    def test_sidecar_spans_join_frontend_trace(self, data_dir,
                                               tmp_path):
        """frontend -> sidecar -> batcher: every child span carries the
        trace id the FRONTEND generated (same-process sidecar, so both
        sides share the registry the assertion reads)."""
        sock = str(tmp_path / "t.sock")

        async def scenario():
            sidecar_cfg = _device_config(data_dir)
            task = asyncio.create_task(run_sidecar(sidecar_cfg, sock))
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
            finally:
                await client.close()
                task.cancel()
                try:
                    await task
                except (asyncio.CancelledError, Exception):
                    pass

        asyncio.run(scenario())
        traces = _finished_render_traces()
        assert traces
        trace = traces[-1]
        names = {s["name"] for s in trace.spans}
        assert "sidecar.render" in names          # crossed the wire
        assert "batcher.queueWait" in names       # batcher child
        assert "Renderer.renderAsPackedInt.batch" in names  # device
        assert "jfif.encodeBatch" in names        # encode tail

    def test_cross_process_sidecar_spans_graft_onto_trace(self,
                                                          data_dir,
                                                          tmp_path):
        """A REAL split (sidecar subprocess): the device process's spans
        come back on the wire response and graft onto the frontend's
        waterfall — the frontend's slow dump shows the full render."""
        import signal
        import subprocess
        import sys
        import time as _time

        sock = str(tmp_path / "x.sock")
        conf = tmp_path / "sidecar.yaml"
        conf.write_text(f"data-dir: {json.dumps(data_dir)}\n"
                        "renderer:\n    cpu-fallback-max-px: 0\n"
                        # Barrier settlement in the device process too:
                        # the grafted batch span must exist on the wire
                        # reply, not race the early-settled response.
                        "wire:\n    streaming: false\n")
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        proc = subprocess.Popen(
            [sys.executable, "-m", "omero_ms_image_region_tpu.server",
             "--config", str(conf), "--role", "sidecar",
             "--sidecar-socket", sock],
            env=env, stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL)
        try:
            deadline = _time.monotonic() + 120
            while not os.path.exists(sock):
                assert proc.poll() is None, "sidecar died at startup"
                assert _time.monotonic() < deadline
                _time.sleep(0.2)

            async def scenario():
                app = create_app(AppConfig(
                    data_dir=data_dir,
                    sidecar=SidecarConfig(socket=sock,
                                          role="frontend")))
                client = TestClient(TestServer(app))
                await client.start_server()
                try:
                    r = await client.get(URL)
                    assert r.status == 200
                    await r.read()
                finally:
                    await client.close()

            asyncio.run(scenario())
        finally:
            proc.send_signal(signal.SIGTERM)
            try:
                proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                proc.kill()
        traces = _finished_render_traces()
        assert traces
        names = {s["name"] for s in traces[-1].spans}
        # Device-process children landed on the frontend trace even
        # though they were recorded in another process.
        assert "sidecar.render" in names
        assert "Renderer.renderAsPackedInt.batch" in names
        assert "batcher.queueWait" in names
        # The cost ledger rode the wire too: the sidecar's device-
        # execute/staging attribution landed on the FRONTEND's ledger.
        costs = traces[-1].export_costs()
        assert costs.get("device_ms", 0) > 0, costs

    def test_dispatcher_task_does_not_adopt_first_request(self,
                                                          data_dir):
        """The per-key dispatcher loop is spawned from the FIRST
        request's context; its spans must not all attach to that one
        trace forever."""
        cfg = _device_config(data_dir)
        reqs = [("GET", URL),
                ("GET", URL.replace("0:60000", "0:50000"))]
        out = _fetch(cfg, *reqs)
        assert [s for s, _, _ in out] == [200, 200]
        traces = _finished_render_traces()
        assert len(traces) >= 2
        # Both requests carry their own render waterfall.
        for t in traces[-2:]:
            assert any(s["name"] == "Renderer.renderAsPackedInt.batch"
                       for s in t.spans), t.to_json()


# -------------------------------------------------------- health probes

class TestHealthProbes:
    def test_healthz_always_ok(self, data_dir):
        [(status, _, body)] = _fetch(_device_config(data_dir),
                                     ("GET", "/healthz"))
        assert status == 200
        assert json.loads(body)["status"] == "ok"

    def test_readyz_combined_ready(self, data_dir):
        [(status, _, body)] = _fetch(_device_config(data_dir),
                                     ("GET", "/readyz"))
        assert status == 200
        doc = json.loads(body)
        assert doc["status"] == "ready"
        assert doc["checks"]["prewarm"] == "complete"

    def test_readyz_503_during_prewarm(self, data_dir):
        telemetry.READINESS.prewarm_pending = True
        [(status, _, body)] = _fetch(_device_config(data_dir),
                                     ("GET", "/readyz"))
        assert status == 503
        assert json.loads(body)["checks"]["prewarm"] == "pending"

    def test_readyz_503_on_backlog(self, data_dir):
        cfg = _device_config(data_dir)
        cfg.telemetry.ready_max_queue_depth = 1

        async def main():
            app = create_app(cfg)
            from omero_ms_image_region_tpu.server.app import SERVICES_KEY
            app[SERVICES_KEY].renderer.queue_depth = lambda: 99
            client = TestClient(TestServer(app))
            await client.start_server()
            try:
                r = await client.get("/readyz")
                return r.status, await r.json()
            finally:
                await client.close()

        status, doc = asyncio.run(main())
        assert status == 503
        assert doc["checks"]["queue"].startswith("depth 99")

    def test_readyz_flips_on_sidecar_death_and_recovery(self, data_dir,
                                                        tmp_path):
        sock = str(tmp_path / "r.sock")

        async def scenario():
            async def start_sidecar():
                task = asyncio.create_task(
                    run_sidecar(_device_config(data_dir), sock))
                for _ in range(200):
                    if task.done():
                        raise AssertionError(
                            f"sidecar died: {task.exception()!r}")
                    if os.path.exists(sock):
                        return task
                    await asyncio.sleep(0.05)
                raise AssertionError("sidecar socket never appeared")

            async def stop(task):
                task.cancel()
                try:
                    await task
                except (asyncio.CancelledError, Exception):
                    pass
                import pathlib
                pathlib.Path(sock).unlink(missing_ok=True)

            task = await start_sidecar()
            app = create_app(AppConfig(
                data_dir=data_dir,
                sidecar=SidecarConfig(socket=sock, role="frontend")))
            client = TestClient(TestServer(app))
            await client.start_server()
            try:
                r1 = await client.get("/readyz")
                assert r1.status == 200, await r1.text()
                doc1 = await r1.json()
                assert doc1["checks"]["sidecar"] == "ok"

                await stop(task)
                r2 = await client.get("/readyz")
                assert r2.status == 503
                doc2 = await r2.json()
                assert doc2["status"] == "degraded"
                assert doc2["checks"]["sidecar"] == "unreachable"

                task = await start_sidecar()
                try:
                    r3 = await client.get("/readyz")
                    assert r3.status == 200, await r3.text()
                finally:
                    await stop(task)
            finally:
                await client.close()

        asyncio.run(scenario())


# ------------------------------------------------------- slow requests

class TestSlowRequestTracer:
    def test_dump_written_and_renderable(self, data_dir, tmp_path):
        cfg = _device_config(data_dir)
        cfg.telemetry.slow_request_ms = 0.001   # everything is "slow"
        cfg.telemetry.slow_request_dir = str(tmp_path / "slow")
        [(status, _, _)] = _fetch(cfg, ("GET", URL))
        assert status == 200
        dumps = os.listdir(cfg.telemetry.slow_request_dir)
        assert dumps
        path = os.path.join(cfg.telemetry.slow_request_dir, dumps[0])
        with open(path) as f:
            doc = json.load(f)
        assert doc["route"] == "render_image_region"
        assert doc["status"] == 200
        assert doc["total_ms"] > 0
        assert doc["trace_id"] == os.path.splitext(dumps[0])[0]
        names = [s["name"] for s in doc["spans"]]
        assert "Renderer.renderAsPackedInt" in names
        # Spans carry offsets + durations (the waterfall coordinates).
        for s in doc["spans"]:
            assert s["dur_ms"] >= 0 and "start_ms" in s

        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "trace_report",
            os.path.join(os.path.dirname(__file__), "..", "scripts",
                         "trace_report.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        table = mod.render_trace(doc)
        assert "render_image_region" in table
        assert "Renderer.renderAsPackedInt" in table
        assert "#" in table                     # the bars rendered

    def test_threshold_zero_disables(self, data_dir, tmp_path):
        cfg = _device_config(data_dir)
        cfg.telemetry.slow_request_ms = 0.0
        cfg.telemetry.slow_request_dir = str(tmp_path / "never")
        [(status, _, _)] = _fetch(cfg, ("GET", URL))
        assert status == 200
        assert not os.path.exists(cfg.telemetry.slow_request_dir)


# ----------------------------------------------------------- access log

class TestAccessLog:
    def test_one_json_line_per_request(self, data_dir, caplog):
        import logging
        with caplog.at_level(
                logging.INFO, logger="omero_ms_image_region_tpu.access"):
            [(status, _, body)] = _fetch(_device_config(data_dir),
                                         ("GET", URL))
        assert status == 200
        lines = [r.message for r in caplog.records
                 if r.name == "omero_ms_image_region_tpu.access"]
        assert lines
        doc = json.loads(lines[-1])
        assert doc["route"] == "render_image_region"
        assert doc["status"] == 200
        assert doc["bytes"] == len(body)
        assert doc["ms"] > 0
        assert re.fullmatch(r"[0-9a-f]{16}", doc["trace"])
        assert doc["cache"] in ("byte-cache", "coalesced", "render")
        assert doc["render_ms"] is not None
        # The per-request cost ledger rides the access line: the
        # batched device render attributed its pro-rata execute ms and
        # the response bytes to this request.
        assert doc["cost"]["device_ms"] > 0
        assert doc["cost"]["wire_bytes"] == len(body)
        assert doc["cost"]["total_ms"] == doc["ms"]


# ------------------------------------------------------ exposition lint

_SERIES_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)"
    r'(\{[a-zA-Z_][a-zA-Z0-9_]*="[^"]*"'
    r'(,[a-zA-Z_][a-zA-Z0-9_]*="[^"]*")*\})?'
    r" -?[0-9]+(\.[0-9]+)?([eE][+-]?[0-9]+)?"
    # Optional OpenMetrics exemplar tail (``_bucket`` lines only —
    # enforced below): `` # {k="v",...} value [timestamp]``.
    r'(?P<exemplar> # \{[a-zA-Z_][a-zA-Z0-9_]*="[^"]*"'
    r'(,[a-zA-Z_][a-zA-Z0-9_]*="[^"]*")*\}'
    r" -?[0-9]+(\.[0-9]+)?( [0-9]+(\.[0-9]+)?)?)?$")

_LABEL_KEY_RE = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)=')

# Exemplar label keys are their own closed set: a trace id is
# unbounded AS A LABEL but fine as an exemplar (exemplars are
# per-bucket slots, not series — cardinality stays fixed).
_EXEMPLAR_LABEL_KEYS = frozenset({"trace_id", "tier"})

# Every label key any family may legally use.  The closed set is the
# cardinality guard: a per-request label (trace id, image id, client
# address) sneaking onto a series would grow without bound — it fails
# here, mechanically, before it melts a Prometheus.
_ALLOWED_LABEL_KEYS = frozenset({
    "route", "status", "span", "le", "cache", "tier", "op", "reason",
    "process", "slo", "window", "shape", "member",
    # Self-preservation families (closed by construction: signal
    # names from the governor's fixed sampler set, steps from the
    # config-validated ladder, actions from the watchdog/ladder
    # vocabulary).
    "signal", "step", "action",
    # Session-aware serving (PR 10): the QoS class label is the
    # two-value interactive/bulk vocabulary of ``pressure.is_bulk``;
    # prefetch skip reasons are the prefetcher's own fixed set.
    # Sessions themselves NEVER label a series (unbounded
    # cardinality) — only aggregates reach the exposition.
    "class",
    # Response provenance (PR 12): ``tier`` is utils.provenance.TIERS
    # verbatim, ``flag`` is utils.provenance.FLAGS — both closed by
    # construction (ProvenanceStats clamps drifted strings).
    "flag",
    # The profile summary (PR 26): ``stage`` is
    # utils.profile_summary.STAGES plus "unnamed", ``during`` its
    # IDLE_ORDER plus "no_group" / "unattributed" -- both fixed there.
    "stage", "during",
    # How a group's raw array came to be (PR 33): "planes" or
    # "arrays", the two keys of ``BatchingRenderer.group_stacks``.
    "path",
    # What a launched group's pixels were (PR 34): "image" or "pad",
    # the two keys of ``BatchingRenderer.bucket_px``.
    "part",
    # The chip that ran a render, or that a fleet member holds (PR 38):
    # a JAX device id of this process, as many as the host's chips.
    "device",
})


def _lint_exposition(text):
    """Line-by-line Prometheus text-format check: valid series syntax,
    # HELP and # TYPE exactly once per family (HELP first), no
    duplicate (name, labels), and label keys drawn from the closed
    bounded-cardinality set."""
    typed = set()
    helped = set()
    seen = set()
    for line in text.rstrip("\n").split("\n"):
        if line.startswith("# HELP "):
            parts = line.split(None, 3)
            assert len(parts) == 4 and parts[3], line
            assert parts[2] not in helped, f"duplicate HELP: {line}"
            helped.add(parts[2])
            continue
        if line.startswith("# TYPE "):
            parts = line.split()
            assert len(parts) == 4, line
            assert parts[3] in ("counter", "gauge", "histogram",
                                "summary", "untyped"), line
            assert parts[2] not in typed, f"duplicate TYPE: {line}"
            assert parts[2] in helped, f"TYPE without HELP: {line}"
            typed.add(parts[2])
            continue
        if line.startswith("#") or not line:
            continue
        m = _SERIES_RE.match(line)
        assert m, f"malformed series line: {line!r}"
        name = m.group(1)
        assert re.fullmatch(r"[a-z0-9_]+", name), \
            f"metric name not snake_case: {line!r}"
        exemplar = m.group("exemplar") or ""
        if exemplar:
            assert name.endswith("_bucket"), \
                f"exemplar outside a _bucket series: {line!r}"
            for label_key in _LABEL_KEY_RE.findall(exemplar):
                assert label_key in _EXEMPLAR_LABEL_KEYS, \
                    f"unexpected exemplar label {label_key!r}: " \
                    f"{line!r}"
        family = name
        for suffix in ("_bucket", "_sum", "_count"):
            if name.endswith(suffix) and name[:-len(suffix)] in typed:
                family = name[:-len(suffix)]
        assert family in typed, f"series without # TYPE: {line!r}"
        labels = m.group(2) or ""
        for label_key in _LABEL_KEY_RE.findall(labels):
            assert label_key in _ALLOWED_LABEL_KEYS, \
                f"unexpected label key {label_key!r} (unbounded " \
                f"cardinality risk): {line!r}"
        key = (name, labels)
        assert key not in seen, f"duplicate series: {line!r}"
        seen.add(key)
    assert typed == helped, "HELP/TYPE family sets diverge"
    assert typed and seen


class TestExpositionLint:
    def test_combined_app_metrics_parse(self, data_dir):
        [(s1, _, _), (s2, _, body)] = _fetch(
            _device_config(data_dir), ("GET", URL), ("GET", "/metrics"))
        assert (s1, s2) == (200, 200)
        text = body.decode()
        _lint_exposition(text)
        assert "imageregion_request_duration_ms_bucket" in text
        assert "imageregion_batcher_queue_depth" in text
        assert "imageregion_pipeline_inflight" in text
        assert "imageregion_compile_events_total" in text
        assert "imageregion_link_fetches_total" in text
        # The JPEG render's wire fetch registered, so the link-health
        # gauge is live (0.0 until a bandwidth-class fetch rates it).
        assert "imageregion_link_mb_s" in text
        # The attribution layer's families are live: per-route cost
        # histograms, the per-shape device cost model, and the flight
        # recorder's ring gauges.
        assert "imageregion_request_cost_device_ms_bucket" in text
        assert "imageregion_request_cost_queue_ms_bucket" in text
        assert "imageregion_request_cost_wire_kb_bucket" in text
        assert "imageregion_shape_dispatches_total" in text
        assert "imageregion_shape_device_ms_total" in text
        assert "imageregion_flight_events" in text
        # Self-preservation families are present from scrape one
        # (level 0, no steps engaged) so dashboards/alerts can bind
        # before the first brownout.
        assert "imageregion_pressure_level 0" in text
        assert "imageregion_pressure_steps_engaged 0" in text
        assert "imageregion_drains_total 0" in text

    def test_robustness_families_lint_with_labels(self):
        """Engaged ladder steps, watchdog fires and drain states emit
        under the closed signal/step/action/member label keys and the
        whole exposition still lints."""
        telemetry.PRESSURE.declare_steps(("pause_prefetch",
                                          "shed_bulk"))
        telemetry.PRESSURE.set_level(2)
        telemetry.PRESSURE.set_signal("hbm", 0.93)
        telemetry.PRESSURE.set_step("pause_prefetch", True)
        telemetry.WATCHDOG.count_fire("requeue-group")
        telemetry.WATCHDOG.count_fire("drop-connection")
        telemetry.DRAIN.set_state("m1", "draining")
        telemetry.DRAIN.count_prestaged(7)
        text = telemetry.finalize_exposition(
            telemetry.robustness_metric_lines())
        _lint_exposition(text)
        assert "imageregion_pressure_level 2" in text
        assert 'imageregion_pressure_signal{signal="hbm"} 0.93' \
            in text
        assert ('imageregion_pressure_step_engaged'
                '{step="pause_prefetch"} 1') in text
        assert ('imageregion_pressure_step_transitions_total'
                '{step="pause_prefetch",action="engage"} 1') in text
        assert ('imageregion_watchdog_fires_total'
                '{action="requeue-group"} 1') in text
        assert 'imageregion_drain_state{member="m1"} 1' in text
        assert "imageregion_drain_prestaged_planes_total 7" in text

    def test_session_families_lint_with_labels(self):
        """The session-serving families (imageregion_session_* /
        imageregion_prefetch_* / imageregion_qos_*) emit under the
        closed class/reason label keys, ride the robustness exposition
        from both roles, and the whole thing still lints."""
        telemetry.SESSIONS.set_tracked(3)
        telemetry.SESSIONS.count_observation()
        telemetry.SESSIONS.count_evicted()
        telemetry.PREFETCH.count_predicted(2)
        telemetry.PREFETCH.count_scheduled()
        telemetry.PREFETCH.count_staged()
        telemetry.PREFETCH.count_hit()
        telemetry.PREFETCH.count_skipped("budget")
        telemetry.PREFETCH.count_skipped("paused")
        telemetry.PREFETCH.set_budget(0.25)
        telemetry.QOS.count_shed("interactive")
        telemetry.QOS.count_shed("bulk")
        telemetry.QOS.count_dequeued("interactive")
        telemetry.QOS.count_jump()
        text = telemetry.finalize_exposition(
            telemetry.robustness_metric_lines())
        _lint_exposition(text)
        assert "imageregion_session_tracked 3" in text
        assert "imageregion_session_observations_total 1" in text
        assert "imageregion_session_evictions_total 1" in text
        assert "imageregion_prefetch_predicted_total 2" in text
        assert "imageregion_prefetch_hits_total 1" in text
        assert "imageregion_prefetch_budget_scale 0.25" in text
        assert ('imageregion_prefetch_skipped_total{reason="budget"}'
                ' 1') in text
        assert ('imageregion_prefetch_skipped_total{reason="paused"}'
                ' 1') in text
        assert 'imageregion_qos_shed_total{class="bulk"} 1' in text
        assert ('imageregion_qos_shed_total{class="interactive"} 1'
                ) in text
        assert ('imageregion_qos_dequeued_total'
                '{class="interactive"} 1') in text
        assert "imageregion_qos_interactive_jumps_total 1" in text

    def test_httpcache_family_lints_and_resets(self):
        """The imageregion_httpcache_* families (304s / renderless
        HEADs / peer probe-fetch-fallback-putback) lint under the
        closed (label-free) schema, ride request_metric_lines, stay
        quiet until traffic, and clear on reset()."""
        assert telemetry.HTTPCACHE.metric_lines() == []
        telemetry.HTTPCACHE.count_etag_request()
        telemetry.HTTPCACHE.count_not_modified()
        telemetry.HTTPCACHE.count_head()
        telemetry.HTTPCACHE.count_peer_probe()
        telemetry.HTTPCACHE.count_peer_hit()
        telemetry.HTTPCACHE.count_peer_fetch()
        telemetry.HTTPCACHE.count_peer_fallback()
        telemetry.HTTPCACHE.count_peer_putback()
        text = telemetry.finalize_exposition(
            telemetry.request_metric_lines())
        _lint_exposition(text)
        for family in ("etag_requests", "304", "head", "peer_probes",
                       "peer_hits", "peer_fetches", "peer_fallbacks",
                       "peer_putbacks"):
            assert f"imageregion_httpcache_{family}_total 1" in text
        telemetry.reset()
        assert telemetry.HTTPCACHE.metric_lines() == []

    def test_provenance_families_lint_and_reset(self):
        """imageregion_provenance_total{tier,member} +
        imageregion_provenance_flags_total{flag}: closed label sets
        (drifted tiers clamp, member overflow guarded), ride
        request_metric_lines, clear on reset()."""
        telemetry.PROVENANCE.count(
            {"tier": "render_cold", "member": "m1", "stolen": 1,
             "coalesced": 1})
        telemetry.PROVENANCE.count({"tier": "peer", "member": "m0"})
        telemetry.PROVENANCE.count({"tier": "304"})
        text = telemetry.finalize_exposition(
            telemetry.request_metric_lines())
        _lint_exposition(text)
        assert ('imageregion_provenance_total{tier="render_cold",'
                'member="m1"} 1') in text
        assert ('imageregion_provenance_total{tier="304",'
                'member="-"} 1') in text
        assert ('imageregion_provenance_flags_total{flag="stolen"} 1'
                in text)
        assert telemetry.PROVENANCE.totals() == {
            "render_cold": 1, "peer": 1, "304": 1}
        # Member overflow guard: a buggy caller minting member names
        # lands in _overflow, never unbounded label values.
        for i in range(80):
            telemetry.PROVENANCE.count(
                {"tier": "byte_cache", "member": f"x{i}"})
        members = {m for _, m in
                   telemetry.PROVENANCE.by_tier_member}
        assert "_overflow" in members
        assert len(members) <= 66
        telemetry.reset()
        assert telemetry.PROVENANCE.metric_lines() == []

    def test_exemplars_ride_request_exposition_and_lint(self):
        """OpenMetrics exemplars on the request-duration histogram:
        one per bucket (most recent wins), linted, reset-clean — and
        STRICTLY opt-in: the classic text exposition must stay free
        of exemplar tails (the text/plain parser rejects them, and
        one tail would fail the whole scrape)."""
        telemetry.REQUEST_HIST.observe(
            "render_image_region", 41.0,
            exemplar=("0123456789abcdef", "byte_cache"))
        plain = telemetry.finalize_exposition(
            telemetry.request_metric_lines())
        _lint_exposition(plain)
        assert " # {" not in plain, \
            "exemplars must not leak into the classic exposition"
        text = telemetry.finalize_exposition(
            telemetry.request_metric_lines(exemplars=True))
        _lint_exposition(text)
        assert 'trace_id="0123456789abcdef"' in text
        assert 'tier="byte_cache"' in text
        snap = telemetry.exemplars_snapshot()
        assert snap["render_image_region"][0]["trace"] \
            == "0123456789abcdef"
        telemetry.reset()
        assert telemetry.exemplars_snapshot() == {}

    def test_openmetrics_mode_is_grammar_strict(self):
        """finalize_exposition(openmetrics=True) — the negotiated
        exposition that carries exemplars — must satisfy the STRICT
        OpenMetrics grammar: no free-form comments, no 'untyped',
        counters declared under their _total-less name (degrading to
        'unknown' when the suffix-less name collides with another
        family or the legacy name has no suffix)."""
        telemetry.count_request("render_image_region", 200)
        telemetry.FLIGHT.record("drill")
        lines = telemetry.request_metric_lines()
        lines.append("# sidecar metrics unavailable")
        lines.append("made_up_metric 1")
        classic = telemetry.finalize_exposition(lines)
        assert "# sidecar metrics unavailable" in classic
        assert "untyped" in classic           # made_up_metric
        om = telemetry.finalize_exposition(lines, openmetrics=True)
        assert "# sidecar metrics unavailable" not in om
        assert "untyped" not in om
        assert "# TYPE made_up_metric unknown" in om
        assert "# TYPE imageregion_requests counter" in om
        # The flight gauge/counter pair: stripping _total would
        # collide with the gauge family — the counter degrades.
        assert "# TYPE imageregion_flight_events gauge" in om
        assert "# TYPE imageregion_flight_events_total unknown" in om
        for line in om.rstrip("\n").split("\n"):
            if line.startswith("#"):
                assert line.startswith(("# HELP ", "# TYPE ")), line

    def test_flight_recorder_member_stamp(self):
        """A process that knows its fleet identity stamps every
        recorded event; events naming their own member keep it; the
        stamp clears on reset()."""
        telemetry.FLIGHT.set_member("m2")
        telemetry.FLIGHT.record("xla.compile", ms=1.0)
        telemetry.FLIGHT.record("fleet.steal", member="m0")
        events = telemetry.FLIGHT.snapshot()
        assert events[-2]["member"] == "m2"
        assert events[-1]["member"] == "m0"
        telemetry.reset()
        assert telemetry.FLIGHT.member is None

    def test_fleet_app_metrics_parse(self, data_dir):
        """A combined-role fleet app exposes the imageregion_fleet_*
        families — per-member gauges under the closed ``member``
        label, routed/stolen/failed-over counters — and the whole
        exposition still lints (HELP/TYPE once per family)."""
        from omero_ms_image_region_tpu.server.config import FleetConfig

        cfg = _device_config(data_dir)
        cfg.fleet = FleetConfig(enabled=True, members=2)
        [(s1, _, _), (s2, _, body)] = _fetch(
            cfg, ("GET", URL), ("GET", "/metrics"))
        assert (s1, s2) == (200, 200)
        text = body.decode()
        _lint_exposition(text)
        assert "imageregion_fleet_members 2" in text
        assert "imageregion_fleet_members_healthy 2" in text
        assert 'imageregion_fleet_member_depth{member="m0"}' in text
        assert 'imageregion_fleet_member_depth{member="m1"}' in text
        assert 'imageregion_fleet_member_planes{member=' in text
        assert 'imageregion_fleet_routed_total{member=' in text

    def test_split_merged_metrics_parse(self, data_dir, tmp_path):
        sock = str(tmp_path / "m.sock")

        async def scenario():
            task = asyncio.create_task(
                run_sidecar(_device_config(data_dir), sock))
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
                return await (await client.get("/metrics")).text()
            finally:
                await client.close()
                task.cancel()
                try:
                    await task
                except (asyncio.CancelledError, Exception):
                    pass

        text = asyncio.run(scenario())
        _lint_exposition(text)
        assert 'process="sidecar"' in text
        assert "imageregion_request_duration_ms_bucket" in text

    def test_finalize_emits_one_type_per_family(self):
        lines = [
            "imageregion_cache_hits 1",
            'imageregion_cache_hits{tier="1"} 2',
            "made_up_metric 3",
            "# a comment",
        ]
        text = telemetry.finalize_exposition(lines)
        assert text.count("# TYPE imageregion_cache_hits counter") == 1
        assert "# TYPE made_up_metric untyped" in text
        assert "# a comment" in text


# ------------------------------------------------- the handler's route

STOCK_IMG = 9


@pytest.fixture(scope="module")
def stock_dir(tmp_path_factory):
    """One column of stock tiles: a full 256^2 tile over a 256 x 40
    sliver (an image's bottom edge)."""
    root = tmp_path_factory.mktemp("stockdata")
    rng = np.random.default_rng(28)
    planes = rng.integers(0, 60000, size=(2, 1, 296, 256)).astype(
        np.uint16)
    build_pyramid(planes, str(root / str(STOCK_IMG)), chunk=(256, 256),
                  n_levels=1)
    return str(root)


@pytest.mark.parametrize("tile_y, route, padded", [
    (0, "device", 0),     # a full stock tile: the batcher, alone in B=1
    (1, "host", 0),       # the sliver under it: refimpl on a thread
])
def test_route_counters_and_prepare_span_on_metrics_and_the_trace(
        stock_dir, tile_y, route, padded):
    """The DEFAULT configuration (nothing of the route is set): the
    handler's choice is counted under its label, the batcher's slot
    counters move with a device render only, and ``handler.prepare``
    is on ``/metrics`` and on the request's trace with the route."""
    cfg = AppConfig(data_dir=stock_dir)
    cfg.wire.streaming = False
    url = (f"/webgateway/render_image_region/{STOCK_IMG}/0/0"
           f"?tile=0,0,{tile_y},256,256&format=jpeg&m=c"
           "&c=1|0:60000$FF0000,2|0:50000$00FF00")
    (status, _, body), (_, _, metrics) = _fetch(
        cfg, ("GET", url), ("GET", "/metrics"))
    assert status == 200 and body[:2] == b"\xff\xd8"
    text = metrics.decode()
    other = "host" if route == "device" else "device"
    assert f'imageregion_renders_routed_total{{route="{route}"}} 1' \
        in text
    assert f'imageregion_renders_routed_total{{route="{other}"}} 0' \
        in text
    device = int(route == "device")
    assert f"imageregion_batcher_shape_slots_total {device}" in text
    assert f"imageregion_batcher_padded_slots_total {padded}" in text
    assert 'imageregion_span_count{span="handler.prepare"} 1' in text
    assert "# TYPE imageregion_renders_routed_total counter" in text
    assert "# TYPE imageregion_batcher_padded_slots_total counter" \
        in text
    (trace,) = _finished_render_traces()
    (prepare,) = [s for s in trace.spans if s["name"] == "handler.prepare"]
    assert prepare["route"] == route and prepare["dur_ms"] > 0
    # From the request's acceptance: nothing on the trace starts
    # before it but the trace itself.
    assert prepare["start_ms"] < 5.0
    names = {s["name"] for s in trace.spans}
    assert ("Renderer.renderAsPackedInt.cpu" in names) == (
        route == "host")
    assert ("batcher.queueWait" in names) == (route == "device")


# ----------------------------------------------------------- satellites

class TestSatellites:
    def test_prewarm_covers_intermediate_batch_shapes(self):
        from omero_ms_image_region_tpu.server.batcher import \
            _BATCH_SHAPES
        from omero_ms_image_region_tpu.server.prewarm import \
            prewarm_batch_sizes
        sizes = prewarm_batch_sizes(8)
        # Every launchable padded shape <= max_batch, including the
        # non-power-of-two split shapes 3 and 6 (ADVICE #3).
        assert sizes == tuple(s for s in _BATCH_SHAPES if s <= 8)
        assert 3 in sizes and 6 in sizes
        assert prewarm_batch_sizes(5) == (1, 2, 3, 4, 5)

    def test_ngff_mtime_tracks_level_zarray(self, tmp_path):
        from omero_ms_image_region_tpu.services.metadata import \
            _ngff_meta_mtime
        root = tmp_path / "img"
        planes = np.zeros((1, 1, 1, 64, 64), np.uint16)   # t,c,z,y,x
        from omero_ms_image_region_tpu.io.ngff import (find_ngff,
                                                       write_ngff)
        write_ngff(planes, str(root))
        ngff = find_ngff(str(root))
        assert ngff is not None
        before = _ngff_meta_mtime(ngff)
        # Rewrite the level-0 array metadata in place, root untouched.
        level0 = os.path.join(ngff, "0", ".zarray")
        assert os.path.exists(level0)
        stamp = os.stat(level0).st_mtime_ns + 10**9
        os.utime(level0, ns=(stamp, stamp))
        assert _ngff_meta_mtime(ngff) != before

    def test_link_health_conflated_is_lower_bound(self):
        link = telemetry.LinkHealth()
        mb = 1024 * 1024
        link.observe(8 * mb, 1.0)                  # 8 MB/s measured
        assert link.ewma_mb_s == pytest.approx(8.39, rel=0.01)
        # A conflated slow sample proves nothing about the RAW link ->
        # the floor holds...
        link.observe(8 * mb, 100.0, conflated=True)
        assert link.ewma_mb_s == pytest.approx(8.39, rel=0.01)
        # ...but the EFFECTIVE rate tracks the slowdown requests feel.
        assert link.effective_mb_s < link.ewma_mb_s
        # A conflated FAST sample raises the floor.
        link.observe(80 * mb, 1.0, conflated=True)
        assert link.ewma_mb_s > 20.0
        # Tiny fetches are latency-dominated: counted, not rated.
        before = link.ewma_mb_s
        link.observe(1024, 5.0)
        assert link.ewma_mb_s == before
        assert link.fetches == 4

    def test_link_effective_tracks_conflated_only_slowdown(self):
        """An all-conflated stream (the real serving pattern) must
        still move the effective gauge DOWN when the wire degrades."""
        link = telemetry.LinkHealth()
        mb = 1024 * 1024
        for _ in range(5):
            link.observe(80 * mb, 1.0, conflated=True)   # 80 MB/s
        fast = link.effective_mb_s
        for _ in range(20):
            link.observe(8 * mb, 1.0, conflated=True)    # now 8 MB/s
        assert link.effective_mb_s < fast / 5
        assert link.ewma_mb_s >= fast                    # floor holds
