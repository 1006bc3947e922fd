"""The real service launcher: ``python -m omero_ms_image_region_tpu.server``.

Boots the actual process (socket bind, signal handlers, cleanup path —
the ``io.vertx.core.Launcher`` analogue, ``build.gradle:10``), probes the
OPTIONS feature document over a real TCP connection, and shuts it down
with SIGTERM.
"""

import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.request

import numpy as np
import pytest


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_launcher_serves_and_stops(tmp_path):
    from omero_ms_image_region_tpu.io.store import build_pyramid

    rng = np.random.default_rng(2)
    build_pyramid(rng.integers(0, 60000, (1, 1, 32, 32)).astype(np.uint16),
                  str(tmp_path / "1"), n_levels=1)
    port = _free_port()

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"   # the subprocess must not reach for a chip
    # Log to a file, not a pipe: an undrained pipe buffer would block the
    # server's writes once full and wedge the test.
    log_path = tmp_path / "server.log"
    log_file = open(log_path, "wb")
    proc = subprocess.Popen(
        [sys.executable, "-m", "omero_ms_image_region_tpu.server",
         "--port", str(port), "--data-dir", str(tmp_path)],
        env=env, stdout=log_file, stderr=subprocess.STDOUT)
    try:
        deadline = time.monotonic() + 120
        doc = None
        while time.monotonic() < deadline:
            try:
                req = urllib.request.Request(
                    f"http://127.0.0.1:{port}/", method="OPTIONS")
                with urllib.request.urlopen(req, timeout=5) as resp:
                    doc = json.loads(resp.read())
                break
            except OSError:
                if proc.poll() is not None:
                    out = log_path.read_text(errors="replace")
                    pytest.fail(f"launcher exited rc={proc.returncode}:"
                                f"\n{out[-2000:]}")
                time.sleep(0.5)
        assert doc is not None, "service never came up"
        assert "flip" in doc["features"]

        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/webgateway/render_image_region/1/0/0"
            f"?tile=0,0,0,16,16&format=png&m=c&c=1|0:60000$FF0000",
            timeout=30).read()
        assert body[:4] == b"\x89PNG"

        proc.send_signal(signal.SIGTERM)
        assert proc.wait(timeout=30) == 0
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=10)
        log_file.close()
