"""The server child, its readiness and plain HTTP: copied from
``chip_smoke.py`` (proven on the chip in PR 21), not imported from it, so
that a later PR to the program cannot change the yardstick.

Nothing here imports JAX: the chip belongs to the one server child.
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
READY_TIMEOUT_S = 1100.0
SHUTDOWN_TIMEOUT_S = 60.0


class BenchFailure(Exception):
    """The run cannot give a result: exit non-zero, print no result."""


def check(cond, msg: str) -> None:
    if not cond:
        raise BenchFailure(msg)


def say(msg: str) -> None:
    print(msg, flush=True)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Child:
    """One server process in its own process group, logged to a file."""

    def __init__(self, name: str, argv: list, workdir: str,
                 env: dict | None = None):
        self.name = name
        self.log_path = os.path.join(workdir, f"{name}.log")
        child_env = dict(os.environ)
        child_env["PYTHONPATH"] = os.pathsep.join(
            p for p in (REPO, os.environ.get("PYTHONPATH")) if p)
        child_env.update(env or {})
        self._log = open(self.log_path, "wb")
        self.t_start = time.perf_counter()
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "omero_ms_image_region_tpu.server",
             *argv],
            cwd=workdir, env=child_env, stdout=self._log,
            stderr=subprocess.STDOUT, start_new_session=True)

    def alive(self) -> bool:
        return self.proc.poll() is None

    def log_tail(self, n: int = 40) -> str:
        self._log.flush()
        with open(self.log_path, "rb") as f:
            lines = f.read().decode(errors="replace").splitlines()
        return "\n".join(f"    [{self.name}] {ln}" for ln in lines[-n:])

    def terminate(self) -> float:
        """SIGTERM, and require a clean exit inside the shutdown bound."""
        t0 = time.perf_counter()
        self.proc.send_signal(signal.SIGTERM)
        try:
            code = self.proc.wait(timeout=SHUTDOWN_TIMEOUT_S)
        except subprocess.TimeoutExpired:
            raise BenchFailure(
                f"{self.name} did not exit within {SHUTDOWN_TIMEOUT_S}s "
                f"of SIGTERM\n{self.log_tail()}")
        check(code == 0, f"{self.name} exited {code} after SIGTERM\n"
              f"{self.log_tail()}")
        return time.perf_counter() - t0

    def kill(self) -> None:
        """Unconditional clean-up of the whole group (grandchildren
        included); the checked path is :meth:`terminate`."""
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
        try:
            self.proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            pass
        self._log.close()


def http_get(port: int, path: str, timeout: float = 600.0):
    url = f"http://127.0.0.1:{port}{path}"
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.status, dict(r.headers), r.read()
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), e.read()


def wait_ready(child: Child, port: int, expect_platform: str,
               device_only: bool = False) -> tuple:
    """Poll /readyz until 200; returns (document, seconds since the
    child was started).  The device document is there from the first
    answer (503 while prewarm compiles), so a server that is not on the
    expected platform fails the run in seconds, not minutes —
    ``device_only`` returns as soon as that much is known."""
    doc, device_checked = None, False
    while time.perf_counter() - child.t_start < READY_TIMEOUT_S:
        check(child.alive(), f"{child.name} died during start-up "
              f"(exit {child.proc.returncode})\n{child.log_tail()}")
        try:
            status, _, body = http_get(port, "/readyz", timeout=10.0)
        except (OSError, urllib.error.URLError):
            time.sleep(0.25)
            continue
        doc = json.loads(body)
        device = doc.get("device")
        if device is not None and not device_checked:
            check(device["platform"] == expect_platform,
                  f"{child.name} serves from platform "
                  f"{device['platform']!r} ({device['kind']}), expected "
                  f"{expect_platform!r}")
            device_checked = True
        if status == 200 or (device_only and device_checked):
            check(device_checked, f"/readyz carries no device: {doc}")
            return doc, time.perf_counter() - child.t_start
        time.sleep(0.5)
    raise BenchFailure(f"{child.name} not ready after {READY_TIMEOUT_S}s: "
                       f"{doc}\n{child.log_tail()}")
