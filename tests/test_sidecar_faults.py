"""Fault tolerance across the sidecar wire: op-aware retry, circuit
breaking, deadline propagation, degraded-mode CPU fallback, admission
shedding, and supervised crash recovery — the frontend -> sidecar ->
batcher chain failing the way the runbook says it fails
(deploy/DEPLOY.md)."""

import asyncio
import os
import time

import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from omero_ms_image_region_tpu.io.store import build_pyramid
from omero_ms_image_region_tpu.models.mask import Mask
from omero_ms_image_region_tpu.server.app import (SERVICES_KEY,
                                                  create_app)
from omero_ms_image_region_tpu.server.config import (
    AppConfig, FaultToleranceConfig, SidecarConfig)
from omero_ms_image_region_tpu.server.errors import (
    DeadlineExceededError, OverloadedError)
from omero_ms_image_region_tpu.server.sidecar import (
    SidecarClient, _pack, _read_frame, run_sidecar)
from omero_ms_image_region_tpu.services.metadata import write_mask
from omero_ms_image_region_tpu.utils.transient import (CircuitBreaker,
                                                       RetryPolicy,
                                                       deadline_scope)

IMG, MASK = 3, 9
H = W = 64


@pytest.fixture()
def data_dir(tmp_path):
    rng = np.random.default_rng(21)
    planes = rng.integers(0, 60000, size=(2, 2, H, W)).astype(np.uint16)
    build_pyramid(planes, str(tmp_path / str(IMG)), chunk=(32, 32),
                  n_levels=1)
    bits = np.zeros(H * W, np.uint8)
    bits[:512] = 1
    write_mask(str(tmp_path), Mask(shape_id=MASK, width=W, height=H,
                                   bytes_=np.packbits(bits).tobytes()))
    return str(tmp_path)


URL = (f"/webgateway/render_image_region/{IMG}/0/0"
       f"?c=1|0:60000$FF0000&m=g&format=png")


async def _wait_socket(sock, task):
    for _ in range(200):
        if task.done():
            raise AssertionError(
                f"sidecar died at startup: {task.exception()!r}")
        if os.path.exists(sock):
            return
        await asyncio.sleep(0.05)
    raise AssertionError("sidecar socket never appeared")


# ------------------------------------------------------- op-aware retry

def test_idempotent_ops_retry_plane_put_does_not(tmp_path):
    """A connection that dies under a request is retried transparently
    for idempotent ops — and NEVER for plane_put (the acceptance
    criterion: a state-changing upload the dead peer may or may not
    have executed must surface, not silently re-run)."""
    sock = str(tmp_path / "fake.sock")

    async def scenario():
        received = []

        async def on_conn(reader, writer):
            try:
                while True:
                    header, _body = await _read_frame(reader)
                    received.append(header["op"])
                    if received.count(header["op"]) == 1:
                        # First sight of this op: die under it.
                        writer.close()
                        return
                    writer.write(_pack({"id": header["id"],
                                        "status": 200}, b"ok"))
                    await writer.drain()
            except (asyncio.IncompleteReadError, ConnectionResetError):
                pass

        server = await asyncio.start_unix_server(on_conn, path=sock)
        client = SidecarClient(
            sock, retry=RetryPolicy(max_attempts=3,
                                    base_backoff_s=0.005, jitter=0.0))
        try:
            status, payload = await client.call("image", {})
            assert status == 200 and bytes(payload) == b"ok"
            assert received.count("image") == 2      # one retry
            with pytest.raises(ConnectionError):
                await client.call("plane_put", {}, body=b"\x00",
                                  extra={"digest": "d",
                                         "dtype": "uint8",
                                         "shape": [1]})
            assert received.count("plane_put") == 1  # NO auto-retry
        finally:
            await client.close()
            server.close()
            await server.wait_closed()

    asyncio.run(scenario())


def test_byte_tier_ops_retry_contract(tmp_path):
    """The fleet-global byte tier's wire ops inherit the op-aware
    retry contract: ``byte_probe``/``byte_fetch`` are pure reads and
    retry through a dropped connection; ``byte_put`` — the peer
    write-back — is NEVER blind-retried (the plane_put contract,
    extended: a state-changing store the dead peer may or may not
    have executed must surface, not silently re-run)."""
    sock = str(tmp_path / "fake-bytes.sock")

    async def scenario():
        received = []

        async def on_conn(reader, writer):
            try:
                while True:
                    header, _body = await _read_frame(reader)
                    received.append(header["op"])
                    if received.count(header["op"]) == 1:
                        writer.close()   # die under the first sight
                        return
                    writer.write(_pack({"id": header["id"],
                                        "status": 200}, b"ok"))
                    await writer.drain()
            except (asyncio.IncompleteReadError, ConnectionResetError):
                pass

        server = await asyncio.start_unix_server(on_conn, path=sock)
        client = SidecarClient(
            sock, retry=RetryPolicy(max_attempts=3,
                                    base_backoff_s=0.005, jitter=0.0))
        try:
            status, payload = await client.call(
                "byte_fetch", {}, extra={"key": "k"})
            assert status == 200 and bytes(payload) == b"ok"
            assert received.count("byte_fetch") == 2    # one retry
            status, payload = await client.call(
                "byte_probe", {}, extra={"keys": ["k"]})
            assert status == 200
            assert received.count("byte_probe") == 2    # one retry
            with pytest.raises(ConnectionError):
                await client.call("byte_put", {}, body=b"\x00",
                                  extra={"key": "k", "digest": "d"})
            assert received.count("byte_put") == 1      # NO auto-retry
        finally:
            await client.close()
            server.close()
            await server.wait_closed()

    asyncio.run(scenario())


# ------------------------------------------------------- circuit breaker

def test_breaker_fails_fast_and_recovers(tmp_path):
    """Consecutive connection failures open the breaker (calls fail
    fast with OverloadedError instead of paying the connect path);
    after the reset window a half-open trial against a now-live
    sidecar closes it again."""
    sock = str(tmp_path / "dead.sock")   # nothing listening

    async def scenario():
        client = SidecarClient(
            sock, breaker=CircuitBreaker(2, reset_after_s=0.2),
            retry=None)
        server = None
        try:
            for _ in range(2):
                with pytest.raises(ConnectionError):
                    await client.call("ping", {})
            with pytest.raises(OverloadedError) as ei:
                await client.call("ping", {})
            assert ei.value.retry_after_s > 0
            assert client.breaker.state_name == "open"

            # Bring a live answerer up; after the reset window the
            # half-open trial succeeds and the breaker closes.
            async def on_conn(reader, writer):
                try:
                    while True:
                        header, _ = await _read_frame(reader)
                        writer.write(_pack({"id": header["id"],
                                            "status": 200}, b"{}"))
                        await writer.drain()
                except (asyncio.IncompleteReadError,
                        ConnectionResetError):
                    pass
                finally:
                    # EOF from the client leaves the server side
                    # half-open; wait_closed() below waits for it.
                    writer.close()

            server = await asyncio.start_unix_server(on_conn, path=sock)
            await asyncio.sleep(0.25)
            status, _ = await client.call("ping", {})
            assert status == 200
            assert client.breaker.state_name == "closed"
        finally:
            # Client first: Server.wait_closed() (Python 3.12) waits for
            # every open connection, this client's included.
            await client.close()
            if server is not None:
                server.close()
                await server.wait_closed()

    asyncio.run(scenario())


# -------------------------------------------------- deadline propagation

def test_deadline_rides_wire_and_spent_budget_is_504(data_dir,
                                                     tmp_path):
    """The remaining budget crosses the wire as deadline_ms; a request
    arriving with nothing left answers 504 WITHOUT rendering, and a
    client-side spent budget never even sends."""
    sock = str(tmp_path / "render.sock")

    async def scenario():
        cfg = AppConfig(data_dir=data_dir)
        task = asyncio.create_task(run_sidecar(cfg, sock))
        client = SidecarClient(sock)
        try:
            await _wait_socket(sock, task)
            # Server side: explicit spent budget -> 504, no render.
            status, err = await client.call(
                "ping", {}, extra={"deadline_ms": 0})
            assert status == 504 and "deadline" in str(err)
            # Generous budget flows through to a 200.
            with deadline_scope(30000.0):
                status, _ = await client.call("ping", {})
            assert status == 200
            # Client side: a spent budget raises before sending.
            with deadline_scope(0.0001):
                await asyncio.sleep(0.001)
                with pytest.raises(DeadlineExceededError):
                    await client.call("ping", {})
            return True
        finally:
            await client.close()
            task.cancel()
            try:
                await task
            except (asyncio.CancelledError, Exception):
                pass

    assert asyncio.run(scenario())


def test_request_deadline_maps_to_http_504(data_dir, tmp_path):
    """fault-tolerance.request-deadline-ms opens the budget at the
    HTTP frontend; an impossible budget surfaces as 504 + JSON error
    (never a 500, never a hang)."""
    sock = str(tmp_path / "render.sock")

    async def scenario():
        cfg = AppConfig(
            data_dir=data_dir,
            sidecar=SidecarConfig(socket=sock, role="frontend"),
            fault_tolerance=FaultToleranceConfig(
                request_deadline_ms=0.0001))
        sidecar_task = asyncio.create_task(
            run_sidecar(AppConfig(data_dir=data_dir), sock))
        app = create_app(cfg)
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            await _wait_socket(sock, sidecar_task)
            r = await client.get(URL)
            assert r.status == 504
            doc = await r.json()
            assert "deadline" in doc["error"]
            return True
        finally:
            await client.close()
            sidecar_task.cancel()
            try:
                await sidecar_task
            except (asyncio.CancelledError, Exception):
                pass

    assert asyncio.run(scenario())


# ------------------------------------------------------- degraded mode

def test_degraded_mode_serves_tiles_while_sidecar_down(data_dir,
                                                       tmp_path):
    """With degraded-mode on and NO sidecar listening, tiles and masks
    still serve — on the frontend's CPU reference path — and /readyz
    stays 200 (the LB must keep routing) while reporting the
    degradation; /metrics counts the fallback renders."""
    sock = str(tmp_path / "never.sock")
    mask_url = f"/webgateway/render_shape_mask/{MASK}?color=00FF00"

    def frontend_cfg():
        return AppConfig(
            data_dir=data_dir,
            sidecar=SidecarConfig(socket=sock, role="frontend"),
            fault_tolerance=FaultToleranceConfig(
                degraded_mode=True, retry_max_attempts=1))

    async def degraded():
        app = create_app(frontend_cfg())
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            r = await client.get(URL)
            png = await r.read()
            assert r.status == 200 and png[:4] == b"\x89PNG"
            rm = await client.get(mask_url)
            assert rm.status == 200
            mask_png = await rm.read()
            # Projections are refused in degraded mode: shed, not a
            # frontend-CPU-minutes render.
            rp = await client.get(
                f"/webgateway/render_image_region/{IMG}/0/0"
                f"?c=1|0:60000$FF0000&m=g&p=intmax|0:1&format=png")
            assert rp.status == 503
            assert "Retry-After" in rp.headers
            rz = await client.get("/readyz")
            assert rz.status == 200
            doc = await rz.json()
            assert doc["checks"]["degraded-mode"] == "active"
            assert doc["checks"]["sidecar"] == "unreachable"
            m = await (await client.get("/metrics")).text()
            line = [ln for ln in m.splitlines() if ln.startswith(
                "imageregion_degraded_renders_total")]
            assert line and int(line[0].rsplit(" ", 1)[1]) >= 2
            return png, mask_png
        finally:
            await client.close()

    png, mask_png = asyncio.run(degraded())

    # The degraded bytes ARE the combined app's bytes: 64^2 tiles take
    # the same refimpl CPU path there, so the fallback is bit-exact.
    async def combined():
        app = create_app(AppConfig(data_dir=data_dir))
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            r = await client.get(URL)
            rm = await client.get(mask_url)
            return await r.read(), await rm.read()
        finally:
            await client.close()

    assert (png, mask_png) == asyncio.run(combined())


def test_without_degraded_mode_sidecar_outage_is_503(data_dir,
                                                     tmp_path):
    """Degraded mode off (the default): a dead sidecar surfaces as
    503 + Retry-After — an availability failure the client should
    retry, never a bare 500 — and /readyz goes unready."""
    sock = str(tmp_path / "never.sock")

    async def scenario():
        cfg = AppConfig(
            data_dir=data_dir,
            sidecar=SidecarConfig(socket=sock, role="frontend"),
            fault_tolerance=FaultToleranceConfig(retry_max_attempts=1))
        app = create_app(cfg)
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            r = await client.get(URL)
            assert r.status == 503
            assert "Retry-After" in r.headers
            body = await r.read()
            assert b"Traceback" not in body
            rz = await client.get("/readyz")
            assert rz.status == 503
            assert (await rz.json())["checks"]["sidecar"] == \
                "unreachable"
            return True
        finally:
            await client.close()

    assert asyncio.run(scenario())


# ------------------------------------------------------ admission shed

def test_admission_shed_is_503_with_retry_after(data_dir):
    """A full admission queue sheds at the HTTP surface with 503 +
    Retry-After + JSON error body; freeing the queue admits again."""

    async def scenario():
        cfg = AppConfig(
            data_dir=data_dir,
            fault_tolerance=FaultToleranceConfig(admission_max_queue=1))
        app = create_app(cfg)
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            admission = app[SERVICES_KEY].admission
            assert admission is not None
            admission.inflight = 1          # pin the queue full
            r = await client.get(URL)
            assert r.status == 503
            assert "Retry-After" in r.headers
            assert "error" in await r.json()
            admission.inflight = 0
            r2 = await client.get(URL)
            assert r2.status == 200
            m = await (await client.get("/metrics")).text()
            assert 'imageregion_shed_total{reason="queue-full"}' in m
            return True
        finally:
            await client.close()

    assert asyncio.run(scenario())


# ------------------------------------------------- startup probe detail

def test_spawn_sidecar_surfaces_boot_crash_exit_code(tmp_path,
                                                     monkeypatch):
    """A sidecar that crashes during boot (here: unreadable config)
    fails the spawn IMMEDIATELY with the child's exit code — it must
    never masquerade as the 3-minute 'socket never appeared'
    timeout."""
    from omero_ms_image_region_tpu.server.sidecar import spawn_sidecar

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")   # the child inherits it
    t0 = time.monotonic()
    with pytest.raises(RuntimeError, match=r"exited with \d+ during "
                                           r"startup"):
        spawn_sidecar(str(tmp_path / "does-not-exist.yaml"),
                      str(tmp_path / "never.sock"))
    # Well under the 180 s socket timeout: the probe read the child's
    # death, it did not wait it out.
    assert time.monotonic() - t0 < 120.0


# --------------------------------------------- supervised crash recovery

def test_supervised_sidecar_recovers_from_mid_request_crash(
        data_dir, tmp_path, monkeypatch):
    """The acceptance drill, with REAL processes: a seeded fault kills
    the sidecar MID-request (die-after-requests); the in-flight caller
    sees a connection failure, and the supervisor restarts the device
    process so later requests succeed WITHOUT operator action."""
    import yaml

    from omero_ms_image_region_tpu.server.sidecar import (
        SidecarSupervisor)

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")   # the child inherits it
    sock = str(tmp_path / "render.sock")
    cfg_path = tmp_path / "sidecar.yaml"
    cfg_path.write_text(yaml.safe_dump({
        "data-dir": data_dir,
        "fault-injection": {"seed": 1, "die-after-requests": 2},
    }))

    sup = SidecarSupervisor.for_config(str(cfg_path), sock,
                                       max_backoff_s=2.0)
    sup.start()
    try:
        async def drive():
            client = SidecarClient(sock, breaker=None)
            try:
                status, _ = await client.call("ping", {})
                assert status == 200
                # Request #2 kills the sidecar process mid-call.
                with pytest.raises(ConnectionError):
                    await client.call("ping", {})
                # Recovery without operator action: keep asking until
                # the supervisor's respawn answers.
                deadline = time.monotonic() + 240.0
                while time.monotonic() < deadline:
                    try:
                        status, _ = await client.call("ping", {})
                        if status == 200:
                            return True
                    except (ConnectionError, OSError):
                        pass
                    await asyncio.sleep(1.0)
                return False
            finally:
                await client.close()

        assert asyncio.run(drive()), "sidecar never came back"
        # The monitor thread counts a restart only once its startup
        # probe returns — which can trail the first successful ping by
        # a poll interval; wait for the bookkeeping, not just the
        # serving.
        deadline = time.monotonic() + 30.0
        while sup.restarts < 1 and time.monotonic() < deadline:
            time.sleep(0.1)
        assert sup.restarts >= 1
    finally:
        sup.stop()
    assert sup.proc.poll() is not None   # stop() really stopped it
