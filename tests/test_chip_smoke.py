"""CPU rehearsal of ``chip_smoke.py`` at a tiny size, through the same
code: phases A (combined) and B (split), real server children, real
HTTP, the same comparisons with ``refimpl`` and the same counter checks.

Sizes and the expected platform are patched HERE (a wrapper process
imports ``chip_smoke``, sets its module constants and calls ``main()``)
— the script has no option for it.  The children inherit
``JAX_PLATFORMS=cpu`` from this test's environment; the compile cache
goes to a temp directory through ``JAX_COMPILATION_CACHE_DIR``, with
the persistence threshold at zero so the tiny programs are cached at
all (phase B must reach ready on cache hits).
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_WRAPPER = r"""
import sys
sys.path.insert(0, {repo!r})
import chip_smoke as cs
cs.TILE, cs.IMAGE_EDGE, cs.LEVEL_EDGE = 64, 512, 256
cs.ZSTACK_EDGE, cs.ZSTACK_Z, cs.MASK_EDGE = 128, 4, 64
cs.TINY_EDGE, cs.CPU_FALLBACK_MAX_PX = 16, 256
cs.MAX_BATCH, cs.N_COLD, cs.N_WARM, cs.INFLIGHT, cs.N_SPLIT = 4, 16, 4, 8, 8
cs.N_FLEET = 16
cs.READY_TIMEOUT_S = 300.0
{patch}
try:
    code = cs.main({argv!r})
finally:
    print("PARENT_IMPORTED_JAX=%s" % ("jax" in sys.modules), flush=True)
sys.exit(code)
"""


def _run(tmp_path, patch: str, argv=(), xla_flags=None):
    env = dict(os.environ)
    if xla_flags is not None:
        env["XLA_FLAGS"] = xla_flags
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "jax_cache")
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    env["TMPDIR"] = str(tmp_path)
    proc = subprocess.run(
        [sys.executable, "-c",
         _WRAPPER.format(repo=REPO, patch=patch, argv=list(argv))],
        env=env, cwd=str(tmp_path), capture_output=True, text=True,
        timeout=600)
    return proc, [ln for ln in proc.stdout.splitlines() if ln.strip()]


def test_rehearsal_passes_and_names_the_device(tmp_path):
    proc, lines = _run(tmp_path, 'cs.EXPECT_PLATFORM = "cpu"')
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-2000:]
    # The parent never imported JAX (the wrapper prints after main()).
    assert lines[-1] == "PARENT_IMPORTED_JAX=False"
    # main()'s last line is the result, and nothing but the result.
    result = json.loads(lines[-2])
    assert result == {"ok": True, "device": {
        "platform": "cpu", "kind": result["device"]["kind"],
        "count": result["device"]["count"]}}
    assert isinstance(result["device"]["kind"], str)
    assert result["device"]["count"] >= 1
    out = proc.stdout
    # Both phases ran, B on the cache A filled.
    assert "A combined: SIGTERM -> exit 0" in out
    assert "B split: SIGTERM -> exit 0" in out
    assert "host path by design: Renderer.renderAsPackedInt.cpu=1" in out
    assert str(tmp_path / "jax_cache") in out
    # Nothing is left running: the children's sockets are gone with
    # the work directory.
    assert not [p for p in os.listdir(tmp_path)
                if p.startswith("chip_smoke_")]


@pytest.mark.parametrize("patch,needle", [
    # The driver's own sandbox case: the server is on the CPU, the
    # script expects a TPU.  Fails at the first /readyz, in seconds.
    ("", "expected 'tpu'"),
    # A comparison out of bounds fails the phase that made it.
    ('cs.EXPECT_PLATFORM = "cpu"; cs.PNG_MAX_ABS = -1', "PNG differs"),
], ids=["wrong-platform", "comparison-out-of-bounds"])
def test_failing_phase_exits_nonzero_without_a_result(tmp_path, patch,
                                                      needle):
    proc, lines = _run(tmp_path, patch)
    assert proc.returncode != 0
    assert needle in proc.stderr
    assert not [ln for ln in lines if '"ok"' in ln]
    assert lines[-1] == "PARENT_IMPORTED_JAX=False"


def test_four_chip_option_rehearsal(tmp_path):
    """``--chips 4`` on four VIRTUAL CPU devices: the one-chip sidecar,
    the four-sidecar fleet behind the router and the 2x2 mesh, each
    agreeing with the first — the rehearsal the guide asks for before a
    four-chip call (section 2.2).  Each "pinned" sidecar gets one
    virtual device where the real run gives it one chip."""
    one = "--xla_force_host_platform_device_count=1"
    proc, lines = _run(
        tmp_path,
        'cs.EXPECT_PLATFORM = "cpu"; '
        f'cs.chip_env = lambda i: {{"XLA_FLAGS": "{one}"}}',
        argv=["--chips", "4"],
        xla_flags="--xla_force_host_platform_device_count=4")
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-2000:]
    assert lines[-1] == "PARENT_IMPORTED_JAX=False"
    assert json.loads(lines[-2]) == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": 4}}
    out = proc.stdout
    assert out.count("(ii) member m") == 4
    assert "(iii) mesh 2x2 on cpu ids [0, 1, 2, 3]" in out
    # No other phase runs with the option.
    assert "A combined" not in out and "B split" not in out
