"""Frontend/compute process split (render sidecar over a unix socket).

≙ the reference's event-bus seam: HTTP verticles serialize ctxs to
``omero.render_image_region``; worker verticles render
(``ImageRegionVerticle.java:128-136``).
"""

import asyncio
import os
import signal
import socket as pysocket
import subprocess
import sys
import time
import urllib.request

import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from omero_ms_image_region_tpu.io.store import build_pyramid
from omero_ms_image_region_tpu.models.mask import Mask
from omero_ms_image_region_tpu.server.app import create_app
from omero_ms_image_region_tpu.server.config import (AppConfig,
                                                     SidecarConfig)
from omero_ms_image_region_tpu.server.sidecar import run_sidecar
from omero_ms_image_region_tpu.services.metadata import write_mask

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


def _frontend_config(data_dir, sock):
    return AppConfig(data_dir=data_dir,
                     sidecar=SidecarConfig(socket=sock, role="frontend"))


async def _wait_socket(sock, task):
    """Wait for the sidecar's socket, surfacing an early task death
    instead of timing out into an unrelated connection error."""
    for _ in range(200):
        if task.done():
            exc = task.exception()
            raise AssertionError(f"sidecar died at startup: {exc!r}")
        if os.path.exists(sock):
            return
        await asyncio.sleep(0.05)
    raise AssertionError("sidecar socket never appeared")


async def _with_sidecar(data_dir, sock, body):
    """Run the sidecar task + `body()` in one loop."""
    sidecar_cfg = AppConfig(data_dir=data_dir)
    task = asyncio.create_task(run_sidecar(sidecar_cfg, sock))
    try:
        await _wait_socket(sock, task)
        return await body()
    finally:
        task.cancel()
        try:
            await task
        except (asyncio.CancelledError, Exception):
            pass


def test_render_through_sidecar_matches_combined(data_dir, tmp_path):
    sock = str(tmp_path / "render.sock")
    url = (f"/webgateway/render_image_region/{IMG}/1/0"
           f"?c=1|0:60000$FF0000,2|0:55000$00FF00&m=c&format=png")
    mask_url = f"/webgateway/render_shape_mask/{MASK}?color=00FF00"

    async def body():
        app = create_app(_frontend_config(data_dir, sock))
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            r = await client.get(url)
            png = await r.read()
            assert r.status == 200
            assert r.headers["Content-Type"] == "image/png"
            rm = await client.get(mask_url)
            mask_png = await rm.read()
            assert rm.status == 200
            # Status mapping crosses the boundary intact.
            r400 = await client.get(
                f"/webgateway/render_image_region/{IMG}/9/0?m=c")
            assert r400.status == 400 and b"" != await r400.read()
            r404 = await client.get(
                "/webgateway/render_image_region/777/0/0?m=c")
            assert r404.status == 404
            return png, mask_png
        finally:
            await client.close()

    png, mask_png = asyncio.run(_with_sidecar(data_dir, sock, body))

    # Byte-identical to the combined single-process render.
    async def combined():
        app = create_app(AppConfig(data_dir=data_dir))
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            r = await client.get(url)
            rm = await client.get(mask_url)
            return await r.read(), await rm.read()
        finally:
            await client.close()

    png2, mask_png2 = asyncio.run(combined())
    assert png == png2
    assert mask_png == mask_png2


def test_two_frontends_share_one_sidecar(data_dir, tmp_path):
    sock = str(tmp_path / "render.sock")
    url = (f"/webgateway/render_image_region/{IMG}/0/0"
           f"?c=1|0:60000$FF0000&m=g&format=png")

    async def body():
        apps = [create_app(_frontend_config(data_dir, sock))
                for _ in range(2)]
        clients = []
        for app in apps:
            c = TestClient(TestServer(app))
            await c.start_server()
            clients.append(c)
        try:
            rs = await asyncio.gather(*(c.get(url) for c in clients))
            bodies = [await r.read() for r in rs]
            assert all(r.status == 200 for r in rs)
            assert bodies[0] == bodies[1]
            # Tearing one frontend down leaves the other serving.
            await clients[0].close()
            r = await clients[1].get(url)
            assert r.status == 200
            return True
        finally:
            for c in clients[1:]:
                await c.close()

    assert asyncio.run(_with_sidecar(data_dir, sock, body))


def _wait_http(port, path, deadline_s=120):
    deadline = time.monotonic() + deadline_s
    while time.monotonic() < deadline:
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}{path}", timeout=5) as r:
                return r.status, r.read()
        except Exception:
            time.sleep(0.3)
    raise TimeoutError(f"no HTTP answer on :{port}")


def test_split_processes_survive_frontend_crash(data_dir, tmp_path):
    """Real processes: one sidecar, two frontends.  SIGKILL one frontend;
    the sidecar and the other frontend keep serving."""
    sock = str(tmp_path / "render.sock")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"

    def spawn(args, log_name):
        log = open(tmp_path / log_name, "wb")
        return subprocess.Popen(
            [sys.executable, "-m", "omero_ms_image_region_tpu.server",
             "--data-dir", data_dir] + args,
            env=env, stdout=log, stderr=subprocess.STDOUT)

    def free_port():
        with pysocket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    p1, p2 = free_port(), free_port()
    sidecar = spawn(["--role", "sidecar", "--sidecar-socket", sock],
                    "sidecar.log")
    front1 = front2 = None
    try:
        deadline = time.monotonic() + 120
        while not os.path.exists(sock):
            assert sidecar.poll() is None, "sidecar died at startup"
            assert time.monotonic() < deadline, "sidecar socket missing"
            time.sleep(0.2)
        front1 = spawn(["--role", "frontend", "--sidecar-socket", sock,
                        "--port", str(p1)], "front1.log")
        front2 = spawn(["--role", "frontend", "--sidecar-socket", sock,
                        "--port", str(p2)], "front2.log")
        url = (f"/webgateway/render_image_region/{IMG}/0/0"
               f"?c=1|0:60000$FF0000&m=g&format=png")
        s1, b1 = _wait_http(p1, url)
        s2, b2 = _wait_http(p2, url)
        assert (s1, s2) == (200, 200)
        assert b1 == b2 and b1[:4] == b"\x89PNG"

        front1.kill()          # hard crash, no cleanup
        front1.wait(timeout=30)
        # The sidecar shrugs; the surviving frontend still renders.
        s3, b3 = _wait_http(p2, url)
        assert s3 == 200 and b3 == b2
        assert sidecar.poll() is None
    finally:
        for proc in (front1, front2, sidecar):
            if proc is not None and proc.poll() is None:
                proc.send_signal(signal.SIGTERM)
        for proc in (front1, front2, sidecar):
            if proc is not None:
                try:
                    proc.wait(timeout=30)
                except subprocess.TimeoutExpired:
                    proc.kill()


def test_sidecar_serves_from_device_mesh(data_dir, tmp_path):
    """Composition of the two process postures: a sidecar whose
    renderer is the mesh-sharded MeshRenderer (8-device virtual mesh)
    behind a thin frontend — the reference's clustered worker verticles
    reached over the bus seam."""
    from omero_ms_image_region_tpu.server.config import ParallelConfig

    sock = str(tmp_path / "mesh.sock")
    url = (f"/webgateway/render_image_region/{IMG}/0/0"
           f"?c=1|0:60000$FF0000,2|0:55000$00FF00&m=c&format=png")

    async def body():
        app = create_app(_frontend_config(data_dir, sock))
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            r = await client.get(url)
            png = await r.read()
            assert r.status == 200
            return png
        finally:
            await client.close()

    async def with_mesh_sidecar():
        from omero_ms_image_region_tpu.server.sidecar import run_sidecar
        # n_devices=8 asks for the 8-wide mesh the tests' 8 virtual
        # CPU devices (conftest.py) provide.
        cfg = AppConfig(data_dir=data_dir,
                        parallel=ParallelConfig(enabled=True,
                                                chan_parallel=2,
                                                n_devices=8))
        task = asyncio.create_task(run_sidecar(cfg, sock))
        try:
            await _wait_socket(sock, task)
            return await body()
        finally:
            task.cancel()
            try:
                await task
            except (asyncio.CancelledError, Exception):
                pass

    png = asyncio.run(with_mesh_sidecar())

    # Byte-identical to the combined single-process (non-mesh) app —
    # the sharded steps are bit-exact vs single-device.
    async def combined():
        app = create_app(AppConfig(data_dir=data_dir))
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            r = await client.get(url)
            return await r.read()
        finally:
            await client.close()

    assert png == asyncio.run(combined())


def test_frontend_survives_sidecar_restart(data_dir, tmp_path):
    """A request issued AFTER a sidecar restart succeeds transparently:
    the client notices the dead cached connection at send time and
    retries once on the new socket."""
    sock = str(tmp_path / "render.sock")
    url = (f"/webgateway/render_image_region/{IMG}/0/0"
           f"?c=1|0:60000$FF0000&m=g&format=png")

    async def scenario():
        app = create_app(_frontend_config(data_dir, sock))
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            cfg = AppConfig(data_dir=data_dir)
            task = asyncio.create_task(run_sidecar(cfg, sock))
            await _wait_socket(sock, task)
            r1 = await client.get(url)
            b1 = await r1.read()
            assert r1.status == 200

            # Restart the sidecar (old socket torn down, new one up).
            task.cancel()
            try:
                await task
            except (asyncio.CancelledError, Exception):
                pass
            # 3.13+ asyncio unlinks unix sockets on server close itself.
            import pathlib
            pathlib.Path(sock).unlink(missing_ok=True)
            task = asyncio.create_task(run_sidecar(cfg, sock))
            await _wait_socket(sock, task)
            try:
                r2 = await client.get(url)
                b2 = await r2.read()
                assert r2.status == 200 and b2 == b1
            finally:
                task.cancel()
                try:
                    await task
                except (asyncio.CancelledError, Exception):
                    pass
            return True
        finally:
            await client.close()

    assert asyncio.run(scenario())


def test_parse_address_forms():
    from omero_ms_image_region_tpu.server.sidecar import parse_address

    assert parse_address("/run/x/render.sock") == ("unix",
                                                   "/run/x/render.sock",
                                                   None)
    assert parse_address("render.sock") == ("unix", "render.sock", None)
    assert parse_address("10.0.0.5:8476") == ("tcp", "10.0.0.5", 8476)
    assert parse_address(":8476") == ("tcp", "127.0.0.1", 8476)
    # A name with a colon but non-numeric tail stays a path.
    assert parse_address("weird:name")[0] == "unix"


def test_tcp_sidecar_end_to_end(data_dir):
    """host:port addresses serve over TCP — the cross-host frontend
    posture (frontends on other machines than the device process)."""
    with pysocket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    addr = f"127.0.0.1:{port}"
    url = (f"/webgateway/render_image_region/{IMG}/0/0"
           f"?c=1|0:60000$FF0000&m=g&format=png")

    async def scenario():
        cfg = AppConfig(data_dir=data_dir)
        task = asyncio.create_task(run_sidecar(cfg, addr))
        for _ in range(200):
            if task.done():
                raise AssertionError(
                    f"sidecar died: {task.exception()!r}")
            try:
                r, w = await asyncio.open_connection("127.0.0.1", port)
                w.close()
                break
            except OSError:
                await asyncio.sleep(0.05)
        else:
            raise AssertionError("tcp sidecar never came up")
        app = create_app(_frontend_config(data_dir, addr))
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            r = await client.get(url)
            body = await r.read()
            assert r.status == 200 and body[:4] == b"\x89PNG"
            return True
        finally:
            await client.close()
            task.cancel()
            try:
                await task
            except (asyncio.CancelledError, Exception):
                pass

    assert asyncio.run(scenario())


def test_parse_address_ipv6():
    from omero_ms_image_region_tpu.server.sidecar import parse_address

    assert parse_address("[::1]:8476") == ("tcp", "::1", 8476)
    # Bare IPv6 (multiple colons, no brackets) is NOT mistaken for tcp.
    assert parse_address("::1")[0] == "unix"
    assert parse_address("[::1]")[0] == "unix"


def test_frontend_metrics_include_sidecar_spans(data_dir, tmp_path):
    """/metrics on a frontend merges the device process's span timings
    (where the render actually ran) into its exposition."""
    sock = str(tmp_path / "render.sock")
    url = (f"/webgateway/render_image_region/{IMG}/0/0"
           f"?c=1|0:60000$FF0000&m=g&format=png")

    async def body():
        app = create_app(_frontend_config(data_dir, sock))
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            r = await client.get(url)
            assert r.status == 200
            await r.read()
            m = await (await client.get("/metrics")).text()
            assert 'process="sidecar"' in m
            assert "renderAsPackedInt" in m
            return True
        finally:
            await client.close()

    assert asyncio.run(_with_sidecar(data_dir, sock, body))


def test_session_enforcement_in_split_mode(data_dir, tmp_path):
    """The frontend rejects unresolvable cookies before anything crosses
    the socket; with a cookie, the resolved session key rides the ctx to
    the sidecar (the reference's session-handler placement)."""
    sock = str(tmp_path / "render.sock")
    url = (f"/webgateway/render_image_region/{IMG}/0/0"
           f"?c=1|0:60000$FF0000&m=g&format=png")

    async def body():
        cfg = _frontend_config(data_dir, sock)
        cfg.session_store_type = "static"
        cfg.session_store_required = True
        app = create_app(cfg)
        anon = TestClient(TestServer(app))
        await anon.start_server()
        try:
            r = await anon.get(url)
            assert r.status == 403          # no cookie -> rejected local
        finally:
            await anon.close()
        app2 = create_app(cfg)
        authed = TestClient(TestServer(app2),
                            cookies={"sessionid": "k1"})
        await authed.start_server()
        try:
            r = await authed.get(url)
            assert r.status == 200
            return True
        finally:
            await authed.close()

    assert asyncio.run(_with_sidecar(data_dir, sock, body))


def test_kitchen_sink_ome_tiff_sessions_projection(tmp_path):
    """Round-3 features composed: a multi-file OME-TIFF set served
    through a session-enforcing frontend + sidecar split, including a
    Z-projection — byte-identical to the combined app."""
    from omero_ms_image_region_tpu.io.tiffwrite import write_ome_tiff

    rng = np.random.default_rng(41)
    W, H, Z, C = 64, 64, 3, 2
    planes = rng.integers(0, 60000, size=(C, Z, H, W)).astype(np.uint16)
    names = ["c0.ome.tiff", "c1.ome.tiff"]
    NS = 'xmlns="http://www.openmicroscopy.org/Schemas/OME/2016-06"'
    tds = "".join(
        f'<TiffData FirstZ="0" FirstC="{c}" FirstT="0" IFD="0" '
        f'PlaneCount="{Z}"><UUID FileName="{names[c]}">k{c}</UUID>'
        f'</TiffData>' for c in range(C))
    xml = (f'<?xml version="1.0"?><OME {NS}><Image ID="Image:0">'
           f'<Pixels ID="Pixels:0" DimensionOrder="XYZCT" Type="uint16" '
           f'SizeX="{W}" SizeY="{H}" SizeZ="{Z}" SizeC="{C}" SizeT="1" '
           f'BigEndian="false">{tds}</Pixels></Image></OME>')
    data = tmp_path / "data"
    os.makedirs(data / "6")
    for c in range(C):
        write_ome_tiff(planes[c][None], str(data / "6" / names[c]),
                       tile=(32, 32), n_levels=1, description=xml)

    sock = str(tmp_path / "render.sock")
    urls = [
        "/webgateway/render_image_region/6/1/0"
        "?c=1|0:60000$FF0000,2|0:55000$00FF00&m=c&format=png",
        "/webgateway/render_image_region/6/0/0"
        "?c=1|0:60000$FF0000&m=g&p=intmax|0:2&format=png",
    ]

    def frontend_cfg():
        cfg = AppConfig(data_dir=str(data),
                        sidecar=SidecarConfig(socket=sock,
                                              role="frontend"),
                        session_store_type="static",
                        session_store_required=True)
        return cfg

    async def body():
        app = create_app(frontend_cfg())
        client = TestClient(TestServer(app),
                            cookies={"sessionid": "s1"})
        await client.start_server()
        try:
            out = []
            for u in urls:
                r = await client.get(u)
                assert r.status == 200, u
                out.append(await r.read())
            # No cookie -> rejected before the socket.
            anon = TestClient(TestServer(create_app(frontend_cfg())))
            await anon.start_server()
            try:
                r = await anon.get(urls[0])
                assert r.status == 403
            finally:
                await anon.close()
            return out
        finally:
            await client.close()

    async def run_split():
        cfg = AppConfig(data_dir=str(data))
        task = asyncio.create_task(run_sidecar(cfg, sock))
        try:
            await _wait_socket(sock, task)
            return await body()
        finally:
            task.cancel()
            try:
                await task
            except (asyncio.CancelledError, Exception):
                pass

    split_bodies = asyncio.run(run_split())

    async def combined():
        app = create_app(AppConfig(data_dir=str(data)))
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            return [await (await client.get(u)).read() for u in urls]
        finally:
            await client.close()

    assert split_bodies == asyncio.run(combined())


def test_plane_digest_wire_push(data_dir, tmp_path):
    """Protocol v2 digest-first plane staging: the first push uploads,
    the second (same content, any client) probes resident and ships
    ZERO plane bytes; a digest/content mismatch is rejected before it
    can poison the cache."""
    from omero_ms_image_region_tpu.server.sidecar import SidecarClient

    sock = str(tmp_path / "render.sock")
    rng = np.random.default_rng(5)
    arr = rng.integers(0, 60000, size=(2, 64, 64)).astype(np.uint16)

    async def body():
        client = SidecarClient(sock)
        try:
            digest, resident = await client.stage_plane(arr)
            assert resident is False           # first push: uploaded
            digest2, resident2 = await client.stage_plane(arr.copy())
            assert digest2 == digest
            assert resident2 is True           # probe hit: no upload
            # A second client (another frontend) sees the same residency.
            other = SidecarClient(sock)
            try:
                _, resident3 = await other.stage_plane(arr.copy())
                assert resident3 is True
            finally:
                await other.close()
            # Probe op answers directly too.
            import json as _json
            status, payload = await client.call(
                "plane_probe", {}, extra={"digest": digest})
            assert status == 200
            assert _json.loads(bytes(payload).decode())["resident"]
            # Digest mismatch: 400, nothing cached under the bogus key.
            status, err = await client.call(
                "plane_put", {}, body=arr.tobytes(),
                extra={"digest": "00" * 16, "dtype": str(arr.dtype),
                       "shape": list(arr.shape)})
            assert status == 400 and "mismatch" in str(err)
            # Body/shape disagreement: 400 as well.
            status, err = await client.call(
                "plane_put", {}, body=arr.tobytes()[:-2],
                extra={"digest": digest, "dtype": str(arr.dtype),
                       "shape": list(arr.shape)})
            assert status == 400
            # Negative dims whose product multiplies out positive must
            # still be a 400, never a reshape 500.
            status, err = await client.call(
                "plane_put", {}, body=b"\x00" * (2 * 2 * 64 * 2),
                extra={"digest": digest, "dtype": str(arr.dtype),
                       "shape": [-2, -2, 64]})
            assert status == 400 and "positive" in str(err)
            # Non-numeric dtypes are a 400 too, not a frombuffer 500.
            status, err = await client.call(
                "plane_put", {}, body=b"\x00" * 64,
                extra={"digest": digest, "dtype": "O",
                       "shape": [8]})
            assert status == 400 and "dtype" in str(err)
            return True
        finally:
            await client.close()

    assert asyncio.run(_with_sidecar(data_dir, sock, body))


def test_plane_push_degrades_when_cache_disabled(data_dir, tmp_path):
    """A sidecar without the plane cache (raw-cache disabled) makes
    stage_plane a no-op — (digest, False), nothing uploaded, no error
    surface (the documented mixed-version degrade contract)."""
    from omero_ms_image_region_tpu.server.config import RawCacheConfig
    from omero_ms_image_region_tpu.server.sidecar import SidecarClient

    sock = str(tmp_path / "render.sock")
    arr = np.arange(2 * 16 * 16, dtype=np.uint16).reshape(2, 16, 16)

    async def scenario():
        cfg = AppConfig(data_dir=data_dir,
                        raw_cache=RawCacheConfig(enabled=False))
        task = asyncio.create_task(run_sidecar(cfg, sock))
        client = SidecarClient(sock)
        try:
            await _wait_socket(sock, task)
            digest, resident = await client.stage_plane(arr)
            assert resident is False
            # Still not resident afterwards: nothing was pushed.
            digest2, resident2 = await client.stage_plane(arr)
            assert digest2 == digest and resident2 is False
            return True
        finally:
            await client.close()
            task.cancel()
            try:
                await task
            except (asyncio.CancelledError, Exception):
                pass

    assert asyncio.run(scenario())


def test_wire_pushed_plane_skips_handler_upload(data_dir, tmp_path):
    """A plane pushed over the wire is found by the handler's region
    read through the content-digest index: the read aliases the
    resident HBM buffer instead of re-staging it (the planecache_hits
    counter proves no second upload happened)."""
    import json as _json

    from omero_ms_image_region_tpu.io.store import ChunkedPyramidStore
    from omero_ms_image_region_tpu.server.sidecar import SidecarClient

    sock = str(tmp_path / "render.sock")
    url = (f"/webgateway/render_image_region/{IMG}/0/0"
           f"?c=1|0:60000$FF0000&m=g&format=png")

    async def body():
        # Push exactly the channel plane the handler's full-plane read
        # will produce: channel 0, z 0, t 0.
        src = ChunkedPyramidStore(os.path.join(data_dir, str(IMG)))
        from omero_ms_image_region_tpu.server.region import RegionDef
        plane = src.get_region(0, 0, 0, RegionDef(0, 0, W, H), 0)
        pusher = SidecarClient(sock)
        try:
            _, resident = await pusher.stage_plane(plane)
            assert resident is False
            app = create_app(_frontend_config(data_dir, sock))
            client = TestClient(TestServer(app))
            await client.start_server()
            try:
                r = await client.get(url)
                assert r.status == 200
                await r.read()
                m = await (await client.get("/metrics")).text()
                hits = [line for line in m.splitlines()
                        if line.startswith("imageregion_planecache_hits")]
                assert hits, m
                assert int(hits[0].rsplit(" ", 1)[1]) >= 1
            finally:
                await client.close()
            return True
        finally:
            await pusher.close()

    async def with_device_sidecar():
        # Small test tiles must take the device path (the CPU fallback
        # never touches the raw cache).
        from omero_ms_image_region_tpu.server.config import (
            RendererConfig)
        cfg = AppConfig(data_dir=data_dir,
                        renderer=RendererConfig(cpu_fallback_max_px=0))
        task = asyncio.create_task(run_sidecar(cfg, sock))
        try:
            await _wait_socket(sock, task)
            return await body()
        finally:
            task.cancel()
            try:
                await task
            except (asyncio.CancelledError, Exception):
                pass

    assert asyncio.run(with_device_sidecar())


def test_sidecar_serves_vendor_codec_images(data_dir, tmp_path):
    """The process split composes with the vendor codec paths: a
    JPEG 2000 (Aperio 33005) image and a JPEG-compressed (7) image
    serve through a device-free frontend + render sidecar identically
    to the combined process."""
    import io as _io

    sys.path.insert(0, os.path.dirname(__file__))
    from vendor_tiff import smooth_rgb as _smooth_rgb
    from vendor_tiff import write_jp2k_tiff as _write_jp2k_tiff

    from PIL import Image as PILImage

    arr = _smooth_rgb(96, 96)
    os.makedirs(os.path.join(data_dir, "301"))
    _write_jp2k_tiff(os.path.join(data_dir, "301", "a.tif"), arr,
                     33005, tile=96)
    os.makedirs(os.path.join(data_dir, "302"))
    PILImage.fromarray(arr).save(
        os.path.join(data_dir, "302", "b.tif"),
        compression="jpeg", quality=95)

    sock = str(tmp_path / "render.sock")
    urls = [
        "/webgateway/render_image_region/301/0/0?region=0,0,96,96"
        "&c=1|0:255$FF0000,2|0:255$00FF00,3|0:255$0000FF&m=c"
        "&format=png",
        "/webgateway/render_image_region/302/0/0?region=0,0,96,96"
        "&c=1|0:255$FF0000,2|0:255$00FF00,3|0:255$0000FF&m=c"
        "&format=png",
    ]

    async def fetch(config):
        app = create_app(config)
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            out = []
            for u in urls:
                r = await client.get(u)
                assert r.status == 200, (u, r.status)
                out.append(await r.read())
            return out
        finally:
            await client.close()

    async def split():
        return await _with_sidecar(
            data_dir, sock,
            lambda: fetch(_frontend_config(data_dir, sock)))

    split_bodies = asyncio.run(split())
    combined_bodies = asyncio.run(fetch(AppConfig(data_dir=data_dir)))
    assert split_bodies == combined_bodies
    png = np.asarray(PILImage.open(
        _io.BytesIO(split_bodies[0])).convert("RGB"))
    assert np.abs(png.astype(int) - arr.astype(int)).max() <= 1


def test_bulk_stage_planes_single_probe_roundtrip(data_dir, tmp_path):
    """Bulk digest-first staging (round 6): N planes probe in ONE wire
    round-trip (the per-plane probe RTT was the bulk-upload tax), only
    misses upload, and a repeat of the whole batch ships zero plane
    bytes."""
    from omero_ms_image_region_tpu.server.sidecar import SidecarClient

    sock = str(tmp_path / "render.sock")
    rng = np.random.default_rng(11)
    planes = [rng.integers(0, 60000, size=(1, 64, 64)).astype(np.uint16)
              for _ in range(4)]
    planes.append(planes[0].copy())     # duplicate content in the batch

    async def body():
        client = SidecarClient(sock)
        try:
            results = await client.stage_planes(planes)
            assert len(results) == len(planes)
            digests = [d for d, _ in results]
            assert digests[4] == digests[0]     # content-addressed
            # First batch: the four distinct planes uploaded; the
            # duplicate rode index 0's upload (intra-batch dedup:
            # zero bytes crossed the wire for it).
            assert [r for _, r in results[:4]] == [False] * 4
            assert results[4] == (digests[0], True)
            # Whole batch again: one probe round-trip, all resident,
            # zero plane bytes on the wire.
            results2 = await client.stage_planes(
                [p.copy() for p in planes])
            assert [r for _, r in results2] == [True] * len(planes)
            assert [d for d, _ in results2] == digests
            # The batched probe op itself answers aligned lists.
            import json as _json
            status, payload = await client.call(
                "plane_probe", {},
                extra={"digests": digests + ["ff" * 16]})
            assert status == 200
            doc = _json.loads(bytes(payload).decode())
            assert doc["resident"] == [True] * len(digests) + [False]
            return True
        finally:
            await client.close()

    assert asyncio.run(_with_sidecar(data_dir, sock, body))


def test_bulk_stage_planes_degrades_to_scalar_probes_on_old_peer():
    """Mixed-version posture: a previous-round sidecar knows only the
    scalar plane_probe.  The bulk client must fall back to per-digest
    probes (the old cost) rather than silently re-uploading resident
    planes on every call."""
    import json as _json

    from omero_ms_image_region_tpu.server.sidecar import SidecarClient

    client = SidecarClient("/nonexistent", breaker=None, retry=None)
    calls = []
    device_resident = {}

    async def fake_call(op, ctx, body=b"", extra=None):
        extra = dict(extra or {})
        calls.append((op, extra))
        if op == "plane_probe":
            # Old peer: the batched "digests" key is unknown; it reads
            # the absent scalar "digest" as never-resident.
            d = extra.get("digest", "")
            return 200, _json.dumps({
                "enabled": True,
                "resident": bool(device_resident.get(d)),
            }).encode()
        assert op == "plane_put"
        d = extra["digest"]
        was = bool(device_resident.get(d))
        device_resident[d] = True
        return 200, _json.dumps({"digest": d,
                                 "resident": was}).encode()

    client.call = fake_call
    rng = np.random.default_rng(13)
    arrs = [rng.integers(0, 60000, size=(1, 8, 8)).astype(np.uint16)
            for _ in range(3)]

    first = asyncio.run(client.stage_planes(arrs))
    assert [r for _, r in first] == [False] * 3     # all uploaded once
    n_puts_first = sum(1 for op, _ in calls if op == "plane_put")
    assert n_puts_first == 3
    second = asyncio.run(client.stage_planes(
        [a.copy() for a in arrs]))
    assert [r for _, r in second] == [True] * 3     # dedup survived
    n_puts = sum(1 for op, _ in calls if op == "plane_put")
    assert n_puts == 3                               # zero re-uploads
    # The fallback really probed per digest (scalar form).
    scalar_probes = [e for op, e in calls
                     if op == "plane_probe" and "digest" in e]
    assert len(scalar_probes) == 6                   # 3 per batch
