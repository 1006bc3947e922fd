"""Test configuration: force an 8-device virtual CPU mesh, and probe
multi-process collective capability.

Tests must not depend on TPU availability; the multi-chip sharding tests run
on XLA's host-platform device virtualization, as the driver's
``dryrun_multichip`` does.

The true multi-PROCESS pod tests (``tests/test_multihost.py``) need more
than virtual devices: the backend must execute computations whose shards
span OS processes.  This image's CPU backend does not —
``jax.device_put`` with a cross-process sharding fails with
``INVALID_ARGUMENT: Multiprocess computations aren't implemented on the
CPU backend`` — so those tests have failed since the seed for an
ENVIRONMENT reason, hiding any real regression inside an
expected-failure count.  ``_multihost_supported`` probes the capability
once per session (two tiny worker processes join via
``jax.distributed`` and run one cross-process sharded reduction); when
the probe fails, every test in ``test_multihost.py`` is SKIPPED with
the probe's verdict as the reason.  On an image whose backend gains the
capability (real TPU slices, a newer CPU collectives build), the probe
passes and the tests run — a regression there fails loudly again.
"""

import os
import socket
import subprocess
import sys

import pytest

# Override (not setdefault): tests and the children they start run on the
# CPU backend, whatever the shell pins.  The chip is reached only through
# chip_smoke.py (see .claude/skills/verify/SKILL.md).
os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()


# ------------------------------------------- multihost capability probe

# Minimal cross-process sharded computation: exactly the operation the
# multihost tests' workers die on when the backend lacks multiprocess
# collectives (device_put with a sharding spanning both processes).
_PROBE_SCRIPT = r"""
import sys
import numpy as np
pid, coord = int(sys.argv[1]), sys.argv[2]
import jax
jax.distributed.initialize(coord, num_processes=2, process_id=pid)
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec
devs = np.array(jax.devices())
mesh = Mesh(devs, ("d",))
arr = jax.device_put(jnp.arange(devs.size),
                     NamedSharding(mesh, PartitionSpec("d")))
print(float(jax.jit(lambda a: a.sum())(arr)))
"""

_MULTIHOST_VERDICT = None   # (supported: bool, reason: str), memoized


def _probe_env() -> dict:
    """One virtual device per worker (the probe needs speed, not
    width), CPU-pinned like the tests' own workers."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    return env


def _multihost_supported():
    global _MULTIHOST_VERDICT
    if _MULTIHOST_VERDICT is not None:
        return _MULTIHOST_VERDICT
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        coord = f"127.0.0.1:{s.getsockname()[1]}"
    env = _probe_env()
    procs = [subprocess.Popen(
        [sys.executable, "-c", _PROBE_SCRIPT, str(pid), coord],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env,
        text=True) for pid in (0, 1)]
    reason = ""
    ok = True
    for pid, proc in enumerate(procs):
        try:
            _out, err = proc.communicate(timeout=120)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            ok, reason = False, "capability probe timed out"
            break
        if proc.returncode != 0:
            ok = False
            tail = [ln for ln in err.strip().splitlines() if ln]
            reason = tail[-1][-200:] if tail else \
                f"probe worker {pid} exited {proc.returncode}"
            break
    _MULTIHOST_VERDICT = (ok, reason)
    return _MULTIHOST_VERDICT


def pytest_collection_modifyitems(config, items):
    multihost = [item for item in items
                 if os.path.basename(str(item.fspath))
                 == "test_multihost.py"]
    if not multihost:
        return
    supported, reason = _multihost_supported()
    if supported:
        return
    marker = pytest.mark.skip(
        reason=f"backend lacks multiprocess collectives "
               f"(env-blocked since seed, not a regression): {reason}")
    for item in multihost:
        item.add_marker(marker)


# ---------------------------------- the JPEG tail's coding pool (PR 37)

@pytest.fixture
def coding_pool(monkeypatch):
    """A pool of three coding threads in the process's place, and its
    counters from zero; yields ``utils.entropypool``."""
    from omero_ms_image_region_tpu.utils import entropypool

    pool = entropypool.EntropyPool(3)
    monkeypatch.setattr(entropypool, "_POOL", pool)
    monkeypatch.setattr(entropypool, "TILES", {"pooled": 0, "inline": 0})
    monkeypatch.setattr(entropypool, "SPLIT_TILES", 0)
    yield entropypool
    pool._executor.shutdown(wait=True)


# ----------------------------------- the renderer's edge-shape warm

@pytest.fixture(autouse=True)
def _edge_warms_end_with_their_test():
    """A test that serves an image's edge tile starts the renderer's
    ``edge-warm`` thread (``BatchingRenderer.warm_edges``); it ends with
    that test, so that its compiles never land in a later test that
    counts them."""
    yield
    import threading
    for thread in threading.enumerate():
        if thread.name == "edge-warm":
            thread.join(timeout=120)
