"""One process of a simulated multi-host pod (CPU backend).

Launched by ``tests/test_multihost.py`` — NOT a pytest module.  The
pod's process count arrives as argv[4] (2 or 4 in the tests); each
process owns ``8 // nprocs`` virtual CPU devices and ``jax.distributed``
joins them into one 8-device slice over which the mesh-sharded render
step runs SPMD, exactly as an N-host TPU pod would.  Prints one JSON
line with per-process shard checksums (all-gathered, so the test can
assert every process observed the same global result).
"""

import json
import os
import sys


def _make_group(B=8, C=4, H=64, W=64, quality=0):
    """Deterministic batcher group (same on every process/run)."""
    import numpy as np

    from omero_ms_image_region_tpu.flagship import flagship_rdef
    from omero_ms_image_region_tpu.ops.render import pack_settings
    from omero_ms_image_region_tpu.server.batcher import _Pending

    rng = np.random.default_rng(7)
    settings = pack_settings(flagship_rdef(C))
    group = []
    for _ in range(B):
        raw = rng.uniform(0, 60000, (C, H, W)).astype(np.float32)
        group.append(_Pending(raw=raw, settings=settings, h=H, w=W,
                              quality=quality))
    return group


def _make_overflow_group(B=8, C=4, H=64, W=64, quality=85):
    """Deterministic mid-density content whose wire totals land in
    (cap, 2*cap] for every tile (probed: 10 noise columns over a flat
    background, seed 7) — forces the one-shot cap-widening rescue."""
    import numpy as np

    from omero_ms_image_region_tpu.flagship import flagship_rdef
    from omero_ms_image_region_tpu.ops.render import pack_settings
    from omero_ms_image_region_tpu.server.batcher import _Pending

    rng = np.random.default_rng(7)
    settings = pack_settings(flagship_rdef(C))
    group = []
    for _ in range(B):
        raw = np.full((C, H, W), 20000, np.float32)
        raw[:, :, :10] = rng.uniform(0, 60000, (C, H, 10)).astype(
            np.float32)
        group.append(_Pending(raw=raw, settings=settings, h=H, w=W,
                              quality=quality))
    return group


def _spy_jpeg_launches():
    """Class-level instrumentation of every sharded JPEG dispatch:
    returns the list the launches append to (leader and follower alike
    go through MeshRenderer._jpeg_step)."""
    from omero_ms_image_region_tpu.parallel.serve import MeshRenderer

    launches = []
    orig = MeshRenderer._jpeg_step

    def spy(self, quality, cap, engine="sparse", cap_words=None):
        step = orig(self, quality, cap, engine, cap_words)

        def wrapped(*args):
            launches.append([engine, quality, cap, cap_words])
            return step(*args)
        return wrapped

    MeshRenderer._jpeg_step = spy
    return launches


def serve_overflow_mode(pid: int) -> dict:
    """Pod-wide wire-cap overflow: the leader serves two overflowing
    groups (base dispatch -> 2x rescue -> memo-started 2x); the
    follower must replay the IDENTICAL launch sequence from the
    replicated totals alone (``parallel/serve.py`` lockstep memos)."""
    import hashlib

    from omero_ms_image_region_tpu.parallel import cluster
    from omero_ms_image_region_tpu.parallel.serve import (
        MeshRenderer, run_pod_follower)

    launches = _spy_jpeg_launches()
    mesh = cluster.global_mesh(chan_parallel=2)
    if pid != 0:
        groups = run_pod_follower(mesh, jpeg_engine="huffman")
        return {"follower_groups": groups, "launches": launches}
    renderer = MeshRenderer(mesh, jpeg_engine="huffman")
    jpegs1 = renderer._render_group_jpeg(_make_overflow_group())
    jpegs2 = renderer._render_group_jpeg(_make_overflow_group())
    renderer._pod.announce(0)          # shutdown broadcast
    return {
        "launches": launches,
        "jpeg_sha": hashlib.sha256(
            b"".join(jpegs1 + jpegs2)).hexdigest(),
        "n_jpegs": len(jpegs1) + len(jpegs2),
    }


def reference_overflow_mode() -> dict:
    """Single-process 8-device digests for the overflow groups."""
    import hashlib

    from omero_ms_image_region_tpu.parallel.mesh import make_mesh
    from omero_ms_image_region_tpu.parallel.serve import MeshRenderer

    renderer = MeshRenderer(make_mesh(8, chan_parallel=2),
                            jpeg_engine="huffman")
    jpegs1 = renderer._render_group_jpeg(_make_overflow_group())
    jpegs2 = renderer._render_group_jpeg(_make_overflow_group())
    return {
        "jpeg_sha": hashlib.sha256(
            b"".join(jpegs1 + jpegs2)).hexdigest(),
        "n_jpegs": len(jpegs1) + len(jpegs2),
    }


def serve_mixed_mode(pid: int) -> dict:
    """The mixed-dims fall-back, pod-wide: a ``huffman`` deployment
    codes a group with a ragged tile ``sparse`` as a whole, and the
    group's engine rides the per-group announcement — both processes
    must launch huffman for the exact group and sparse for the mixed
    one, though both were configured ``huffman``."""
    import hashlib

    from omero_ms_image_region_tpu.parallel import cluster
    from omero_ms_image_region_tpu.parallel.serve import (
        MeshRenderer, run_pod_follower)

    launches = _spy_jpeg_launches()
    mesh = cluster.global_mesh(chan_parallel=2)
    if pid != 0:
        groups = run_pod_follower(mesh, jpeg_engine="huffman")
        return {"follower_groups": groups, "launches": launches}
    renderer = MeshRenderer(mesh, jpeg_engine="huffman")
    jpegs1 = renderer._render_group_jpeg(_make_group(quality=85))
    mixed = _make_group(quality=85)
    mixed[-1].h, mixed[-1].w = 40, 48    # true dims inside the 64^2 grid
    jpegs2 = renderer._render_group_jpeg(mixed)
    renderer._pod.announce(0)          # shutdown broadcast
    return {
        "launches": launches,
        "last_starts_soi": jpegs2[-1][:2] == b"\xff\xd8",
        "jpeg_sha": hashlib.sha256(
            b"".join(jpegs1 + jpegs2)).hexdigest(),
    }


def serve_mode(pid: int) -> dict:
    """Leader drives a MeshRenderer; followers replay via the pod
    channel.  Returns the leader's output digests."""
    import hashlib

    import numpy as np

    from omero_ms_image_region_tpu.parallel import cluster
    from omero_ms_image_region_tpu.parallel.serve import (
        MeshRenderer, run_pod_follower)

    mesh = cluster.global_mesh(chan_parallel=2)
    if pid != 0:
        groups = run_pod_follower(mesh, jpeg_engine="huffman")
        return {"follower_groups": groups}
    renderer = MeshRenderer(mesh, jpeg_engine="huffman")
    packed = renderer._render_group(_make_group())
    jpegs = renderer._render_group_jpeg(_make_group(quality=85))
    renderer._pod.announce(0)          # shutdown broadcast
    return {
        "packed_sha": hashlib.sha256(
            b"".join(np.ascontiguousarray(p).tobytes()
                     for p in packed)).hexdigest(),
        "jpeg_sha": hashlib.sha256(b"".join(jpegs)).hexdigest(),
        "n_jpegs": len(jpegs),
    }


def reference_mode() -> dict:
    """Single-process 8-device reference for the serve-mode digests
    (run in its own clean-env subprocess: an in-pytest reference would
    see whatever default platform the outer environment registered and
    diverge numerically from the workers)."""
    import hashlib

    import numpy as np

    from omero_ms_image_region_tpu.parallel.mesh import make_mesh
    from omero_ms_image_region_tpu.parallel.serve import MeshRenderer

    renderer = MeshRenderer(make_mesh(8, chan_parallel=2),
                            jpeg_engine="huffman")
    packed = renderer._render_group(_make_group())
    jpegs = renderer._render_group_jpeg(_make_group(quality=85))
    return {
        "packed_sha": hashlib.sha256(
            b"".join(np.ascontiguousarray(p).tobytes()
                     for p in packed)).hexdigest(),
        "jpeg_sha": hashlib.sha256(b"".join(jpegs)).hexdigest(),
        "n_jpegs": len(jpegs),
    }


def main() -> int:
    pid = int(sys.argv[1])
    coordinator = sys.argv[2]
    mode = sys.argv[3] if len(sys.argv) > 3 else "checksum"
    nprocs = int(sys.argv[4]) if len(sys.argv) > 4 else 2
    os.environ["JAX_PLATFORMS"] = "cpu"
    # The global mesh is always 8 devices; each process owns its slice.
    ndev = 8 if mode == "reference" else 8 // nprocs
    os.environ["XLA_FLAGS"] = \
        f"--xla_force_host_platform_device_count={ndev}"
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

    import numpy as np

    import jax

    if mode == "reference":
        out = reference_mode()
        out.update({"pid": pid, "ok": True})
        print(json.dumps(out))
        return 0
    if mode == "reference-overflow":
        out = reference_overflow_mode()
        out.update({"pid": pid, "ok": True})
        print(json.dumps(out))
        return 0
    from omero_ms_image_region_tpu.flagship import flagship_rdef
    from omero_ms_image_region_tpu.ops.render import pack_settings
    from omero_ms_image_region_tpu.parallel import cluster
    from omero_ms_image_region_tpu.parallel.mesh import (
        render_step_sharded_batched, shard_batch_batched)

    cluster.initialize(coordinator_address=coordinator,
                       num_processes=nprocs, process_id=pid)
    assert jax.process_count() == nprocs, jax.process_count()
    assert jax.device_count() == 8, jax.device_count()

    if mode == "serve":
        out = serve_mode(pid)
        out.update({"pid": pid, "ok": True})
        print(json.dumps(out))
        return 0
    if mode == "serve-overflow":
        out = serve_overflow_mode(pid)
        out.update({"pid": pid, "ok": True})
        print(json.dumps(out))
        return 0
    if mode == "serve-mixed":
        out = serve_mixed_mode(pid)
        out.update({"pid": pid, "ok": True})
        print(json.dumps(out))
        return 0

    mesh = cluster.global_mesh(chan_parallel=2)
    rng = np.random.default_rng(0)     # same stream on both processes
    B, C, H, W = 8, 4, 64, 64
    raw = rng.uniform(0, 60000, (B, C, H, W)).astype(np.float32)
    settings = pack_settings(flagship_rdef(C))
    stacked = {
        k: np.stack([settings[k]] * B)
        for k in ("window_start", "window_end", "family",
                  "coefficient", "reverse", "tables")
    }
    stacked["cd_start"] = settings["cd_start"]
    stacked["cd_end"] = settings["cd_end"]
    args = shard_batch_batched(mesh, raw, stacked)
    out = render_step_sharded_batched(mesh)(*args)

    from jax.experimental import multihost_utils
    local_sum = np.float64(sum(
        np.asarray(jax.device_get(s.data)).astype(np.float64).sum()
        for s in out.addressable_shards))
    sums = np.asarray(multihost_utils.process_allgather(local_sum))
    print(json.dumps({"pid": pid, "ok": True,
                      "shard_sums": [float(v) for v in sums.ravel()]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
