"""True multi-process pod simulation: 2 OS processes x 4 virtual CPU
devices join via ``jax.distributed`` and run the mesh-sharded render
step SPMD — the closest this environment gets to a real 2-host TPU pod
(the 8-device single-process tests cannot catch per-process divergence
or a broken cluster join).

Regression anchor: ``cluster.initialize`` used to probe
``jax.process_count()`` first, which initialized the XLA backend and
made every explicit multi-host join fail with "initialize() must be
called before any JAX calls".
"""

import json
import os
import socket
import subprocess
import sys

import numpy as np

_WORKER = os.path.join(os.path.dirname(__file__), "multihost_worker.py")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _clean_env() -> dict:
    """Workers are pinned to the CPU backend, each with its own device
    flags (the outer process's XLA_FLAGS ask for 8 virtual devices)."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    return env


def _run_workers(mode: str, pids) -> dict:
    """One worker subprocess per pid (shared coordinator); returns
    {pid: parsed-json-line} once every worker exits cleanly.  Hang
    protection is the communicate timeout (pytest-timeout is not
    shipped in this image)."""
    coordinator = f"127.0.0.1:{_free_port()}"
    env = _clean_env()
    procs = [
        subprocess.Popen(
            [sys.executable, _WORKER, str(pid), coordinator, mode,
             str(len(pids))],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=env,
            text=True)
        for pid in pids
    ]
    outs = {}
    for p, pid in zip(procs, pids):
        try:
            out, err = p.communicate(timeout=240)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        assert p.returncode == 0, f"worker {pid} failed:\n{err[-3000:]}"
        outs[pid] = json.loads(out.strip().splitlines()[-1])
    return outs


def test_two_process_pod_renders_in_lockstep():
    outs = _run_workers("checksum", (0, 1))
    assert all(o["ok"] for o in outs.values())
    # Every process observed the same all-gathered shard checksums —
    # the SPMD launch sequences stayed in lockstep and the global
    # result is consistent across hosts.
    assert outs[0]["shard_sums"] == outs[1]["shard_sums"]
    assert len(outs[0]["shard_sums"]) == 2
    assert all(np.isfinite(outs[0]["shard_sums"]))


def test_two_process_pod_serves_groups_via_follower_replication():
    """The full multi-host SERVING loop: the leader's MeshRenderer
    replicates each group over the pod broadcast channel, the follower
    replays the identical sharded dispatches (render + huffman JPEG,
    including cap-rescue determinism), and the leader's outputs are
    byte-identical to a single-process mesh render of the same groups
    (the reference runs in its own clean-env subprocess so the outer
    environment's default platform cannot skew the comparison).
    """
    outs = _run_workers("serve", (0, 1))
    leader, follower = outs[0], outs[1]
    assert follower["follower_groups"] == 2
    assert leader["n_jpegs"] == 8

    ref = _run_workers("reference", (0,))[0]
    assert ref["packed_sha"] == leader["packed_sha"]
    assert ref["jpeg_sha"] == leader["jpeg_sha"]


def test_four_process_pod_serves_identically():
    """The pod serving loop at 4 processes x 2 devices: three followers
    replay the leader's dispatches, and the leader's digests still
    equal the single-process 8-device reference — replication and
    lockstep are process-count-independent."""
    outs = _run_workers("serve", (0, 1, 2, 3))
    leader = outs[0]
    for pid in (1, 2, 3):
        assert outs[pid]["follower_groups"] == 2
    assert leader["n_jpegs"] == 8

    ref = _run_workers("reference", (0,))[0]
    assert ref["packed_sha"] == leader["packed_sha"]
    assert ref["jpeg_sha"] == leader["jpeg_sha"]


def test_two_process_pod_overflow_rescue_stays_in_lockstep():
    """Wire-cap overflow across the pod: both processes must launch the
    IDENTICAL sharded program sequence — base caps, the one-shot 2x
    rescue, then the memo-started 2x for the next group — decided
    purely from replicated wire totals (``parallel/serve.py``; a
    host-local divergence here would hang a real pod).  The leader's
    bytes must equal the single-process 8-device reference."""
    outs = _run_workers("serve-overflow", (0, 1))
    leader, follower = outs[0], outs[1]
    assert follower["follower_groups"] == 2
    assert leader["n_jpegs"] == 16

    # Identical launch sequences, and exactly the rescue shape:
    # [base, 2x] for group 1, [2x] (memo) for group 2.
    assert leader["launches"] == follower["launches"]
    caps = [tuple(launch) for launch in leader["launches"]]
    assert len(caps) == 3
    (e0, q0, c0, w0), (e1, q1, c1, w1), (e2, q2, c2, w2) = caps
    assert e0 == e1 == e2 == "huffman" and q0 == q1 == q2 == 85
    assert c1 == 2 * c0 and w1 == 2 * w0
    assert (c2, w2) == (c1, w1)

    ref = _run_workers("reference-overflow", (0,))[0]
    assert ref["jpeg_sha"] == leader["jpeg_sha"]


def test_two_process_pod_replays_mixed_dims_fallback():
    """A ``huffman`` pod codes a group of mixed dims ``sparse`` as a
    whole; the group's engine rides the per-group pod announcement, so
    leader and follower (both configured ``huffman``) launch huffman
    for the exact group and sparse for the mixed one, in lockstep."""
    outs = _run_workers("serve-mixed", (0, 1))
    leader, follower = outs[0], outs[1]
    assert follower["follower_groups"] == 2
    assert leader["last_starts_soi"]
    assert leader["launches"] == follower["launches"]
    engines = [launch[0] for launch in leader["launches"]]
    assert engines == ["huffman", "sparse"]
