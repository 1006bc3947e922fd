"""Mesh-sharded serving: the micro-batcher dispatching over a device mesh.

The reference *serves* from its cluster — worker verticles on every node
consume the same event-bus address (``-cluster``;
``ImageRegionMicroserviceVerticle.java:148-165, 406-424``).  The TPU-native
form: :class:`MeshRenderer` keeps the micro-batcher's queueing/bucketing
contract (drop-in for ``server.handler.Renderer`` / ``BatchingRenderer``)
but runs every coalesced group through the mesh-sharded steps
(``parallel.mesh.render_step_sharded_batched`` /
``render_jpeg_step_sharded_batched``): tiles data-parallel across the
mesh, channels optionally tensor-parallel with the additive composite as
one ``psum`` over ICI.

Group padding makes the fixed mesh shapes hold: the batch pads up to a
multiple of the ``data`` axis (repeating the last tile) and the channel
count pads up to a multiple of the ``chan`` axis with inert channels
(unit window, zero color tables — they contribute nothing to the
composite).

Multi-host pods: only the leader (process 0) has a request stream;
before each dispatch it replicates the group to the followers over the
pod broadcast channel (:class:`_PodChannel`), and every process —
followers via :func:`run_pod_follower` (``--role pod-worker``) — runs
the identical sharded flow.  Step outputs are all-gathered inside the
program (``replicate_output``), so the leader can materialize full
results and overflow decisions are deterministic everywhere.
"""

from __future__ import annotations

import logging
import time
from typing import List

import numpy as np

from ..server.batcher import BatchingRenderer, _Pending, _shape_label
from ..utils import telemetry
from ..utils.stopwatch import stopwatch
from .mesh import (Mesh, render_jpeg_step_sharded_batched,
                   render_step_sharded_batched, shard_batch_batched)

logger = logging.getLogger(__name__)


def _pad_group(raw: np.ndarray, stacked: dict, data: int, chan: int):
    """Pad [B, C, H, W] + stacked settings to the mesh's divisibility."""
    B, C = raw.shape[:2]
    Bp = -(-B // data) * data
    Cp = -(-C // chan) * chan
    if Bp != B:
        reps = [raw[-1:]] * (Bp - B)
        raw = np.concatenate([raw] + reps, axis=0) \
            if isinstance(raw, np.ndarray) else _jnp_cat(raw, reps)
        stacked = {
            k: (np.concatenate([v] + [v[-1:]] * (Bp - B), axis=0)
                if getattr(v, "ndim", 0) else v)
            for k, v in stacked.items()
        }
    if Cp != C:
        pad_c = Cp - C
        xp = np if isinstance(raw, np.ndarray) else _jnp()
        raw = xp.concatenate(
            [raw, xp.zeros(raw.shape[:1] + (pad_c,) + raw.shape[2:],
                           raw.dtype)], axis=1)
        Bp = raw.shape[0]

        def padc(v, fill):
            ext = np.full((Bp, pad_c) + v.shape[2:], fill, v.dtype)
            return np.concatenate([v, ext], axis=1)

        stacked = dict(stacked)
        stacked["window_start"] = padc(stacked["window_start"], 0.0)
        stacked["window_end"] = padc(stacked["window_end"], 1.0)
        stacked["family"] = padc(stacked["family"], 0)
        stacked["coefficient"] = padc(stacked["coefficient"], 1.0)
        stacked["reverse"] = padc(stacked["reverse"], 0)
        stacked["tables"] = padc(stacked["tables"], 0.0)
    return raw, stacked


def _jnp():
    import jax.numpy as jnp
    return jnp


def _jnp_cat(raw, reps):
    jnp = _jnp()
    return jnp.concatenate([raw] + reps, axis=0)


# ------------------------------------------------------ pod replication

# Header words for the pod broadcast protocol (leader -> followers).
_POD_HDR = 16
_POD_SHUTDOWN, _POD_RENDER, _POD_JPEG = 0, 1, 2


class _PodChannel:
    """Group replication for multi-host serving.

    SPMD requires every process of a pod to launch identical sharded
    programs in identical order, but only the leader (process 0) has a
    request stream.  Before each group dispatch the leader broadcasts a
    fixed-size header plus the group's arrays
    (``multihost_utils.broadcast_one_to_all`` — one collective per
    array); followers reconstruct the group and run the IDENTICAL
    dispatch flow, so the pod stays in lockstep without any sidecar
    traffic reaching the followers.
    """

    @staticmethod
    def _bcast(x):
        from jax.experimental import multihost_utils
        return multihost_utils.broadcast_one_to_all(x)

    # ---------------------------------------------------------- leader

    def announce(self, kind: int, raw=None, stacked=None,
                 quality: int = 0, engine_id: int = 0) -> None:
        hdr = np.zeros(_POD_HDR, np.int32)
        hdr[0] = kind
        if kind != _POD_SHUTDOWN:
            B, C, H, W = raw.shape
            hdr[1:5] = (B, C, H, W)
            hdr[5] = quality
            hdr[6] = engine_id
            hdr[7] = np.asarray(stacked["tables"]).ndim
            hdr[8] = int(stacked["cd_start"])
            hdr[9] = int(stacked["cd_end"])
        self._bcast(hdr)
        if kind == _POD_SHUTDOWN:
            return
        for arr, dt in self._payload(raw, stacked):
            self._bcast(np.ascontiguousarray(np.asarray(arr, dt)))

    # -------------------------------------------------------- follower

    def recv(self):
        """Next announced group: (kind, raw, stacked, quality,
        engine_id); raw/stacked are None at shutdown."""
        hdr = np.asarray(self._bcast(np.zeros(_POD_HDR, np.int32)))
        kind = int(hdr[0])
        if kind == _POD_SHUTDOWN:
            return kind, None, None, 0, 0
        B, C, H, W = (int(v) for v in hdr[1:5])
        tables_shape = ((B, C, 3) if int(hdr[7]) == 3
                        else (B, C, 256, 3))
        shapes = self._shapes(B, C, H, W, tables_shape)
        got = [np.asarray(self._bcast(np.zeros(shape, dt)))
               for shape, dt in shapes]
        raw = got[0]
        stacked = {
            "window_start": got[1], "window_end": got[2],
            "family": got[3], "coefficient": got[4], "reverse": got[5],
            "tables": got[6],
            "cd_start": int(hdr[8]), "cd_end": int(hdr[9]),
        }
        return kind, raw, stacked, int(hdr[5]), int(hdr[6])

    # ---------------------------------------------------------- layout

    @staticmethod
    def _payload(raw, stacked):
        return (
            (raw, np.float32),
            (stacked["window_start"], np.float32),
            (stacked["window_end"], np.float32),
            (stacked["family"], np.int32),
            (stacked["coefficient"], np.float32),
            (stacked["reverse"], np.int32),
            (stacked["tables"], np.float32),
        )

    @staticmethod
    def _shapes(B, C, H, W, tables_shape):
        return (
            ((B, C, H, W), np.float32),
            ((B, C), np.float32), ((B, C), np.float32),
            ((B, C), np.int32), ((B, C), np.float32),
            ((B, C), np.int32), (tables_shape, np.float32),
        )


class MeshRenderer(BatchingRenderer):
    """Drop-in renderer serving every group through the sharded steps."""

    # Mesh-sharded programs are topology-bound and run in SPMD
    # lockstep across the whole mesh: this renderer must be its
    # process's FIRST fleet member (the mesh/bulk lane), is never
    # device-pinned narrower than its mesh, and federated builds
    # (parallel.federation.build_federated_members) warn when the
    # manifest order would pin fleet-wide bulk work to another host
    # while this one holds the mesh.
    lockstep = True

    def __init__(self, mesh: Mesh, max_batch: int | None = None,
                 linger_ms: float = 2.0, buckets=None,
                 jpeg_engine: str = "sparse", pipeline_depth: int = 4,
                 max_batch_limit: int = None, planes=()):
        data = mesh.shape["data"]
        if max_batch is None:
            max_batch = max(8, 2 * data)
        if jpeg_engine not in ("sparse", "huffman"):
            raise ValueError(f"mesh jpeg engine must be 'sparse' or "
                             f"'huffman', got {jpeg_engine!r}")
        import jax
        multihost = jax.process_count() > 1
        if multihost and pipeline_depth != 1:
            # On a multi-host global mesh every process must launch the
            # same programs in the same order (SPMD); overlapped group
            # renders make local launch order racy, so pipelining is
            # single-host only.
            logger.warning("multi-host mesh: forcing pipeline_depth=1 "
                           "(was %d) — sharded launches must not "
                           "overlap", pipeline_depth)
            pipeline_depth = 1
        kwargs = {} if buckets is None else {"buckets": buckets}
        if multihost:
            # The two-stage fetch/execute split likewise must not let
            # two groups' sharded launches race a host-local gate order.
            kwargs["device_lanes"] = 1
        super().__init__(max_batch=max_batch, linger_ms=linger_ms,
                         pipeline_depth=pipeline_depth,
                         max_batch_limit=max_batch_limit, planes=planes,
                         **kwargs)
        if multihost:
            # One launch slot shared across ALL bucket keys: without it,
            # two keys' dispatchers would interleave sharded launches in
            # a host-local order.
            import asyncio as _asyncio
            self._shared_slots = _asyncio.Semaphore(1)
            # Host-local queue-pressure batch growth would launch
            # program shapes the other processes never compile (SPMD);
            # the pod serves the configured max_batch only.
            self._growth_enabled = False
            # Likewise a host-local transient-error retry would launch
            # the sharded program a second time on one process only,
            # diverging the pod's lockstep launch sequence.
            self._transient_retry_enabled = False
            # Deadline-expired pendings DO still drop
            # (_deadline_drop_enabled stays True): the drop happens on
            # the LEADER at dispatch pop, before the group rides the
            # pod announcement, so every follower replays the identical
            # post-drop group — unlike growth/retry, no host-local
            # divergence is possible.  The watchdog's stuck-group
            # requeue (server.watchdog) is lockstep-safe for the same
            # reason: it re-enqueues pendings on the LEADER, and the
            # re-dispatch rides a fresh pod announcement like any
            # other group.  Chaos freeze/device-error
            # injection, however, fires on whatever process installed
            # it and would stall or re-launch one process's lockstep
            # sequence only — config load rejects explicit multi-host
            # + fault-injection.seed, and build_services disarms the
            # injector on auto-discovered pods.
        self.mesh = mesh
        # Never the serialized-executable cache (server.execcache):
        # sharded programs are bound to this mesh's topology and, on a
        # pod, to the lockstep compile sequence — a deserialized
        # executable on one host would diverge SPMD launch order.
        # Warm restarts here ride the trace cache
        # (renderer.compilation-cache-dir) and the bring-up dryrun.
        self.exec_cache = None
        self.jpeg_engine = jpeg_engine
        import threading
        # Group renders run on up to pipeline_depth concurrent worker
        # threads; without the lock a cold start would build (and
        # mesh-wide-compile) the same step twice.
        self._steps_lock = threading.Lock()
        self._render_steps: dict = {}
        self._jpeg_steps: dict = {}
        self._multihost = multihost
        # Multi-host: outputs are all-gathered inside the sharded step
        # (replicate_output) so (a) the leader can materialize the full
        # result — a data-sharded global array is not addressable
        # cross-host — and (b) overflow verdicts are computed from
        # identical replicated totals on every process, keeping the
        # cap memos in lockstep with no host collective.  The leader
        # replicates each group to the followers over the pod channel
        # before dispatching (see _PodChannel / run_pod_follower).
        self._replicated = multihost
        self._pod = _PodChannel() if multihost else None

    # ------------------------------------------------------------- steps

    def _render_step(self):
        with self._steps_lock:
            step = self._render_steps.get("render")
            if step is None:
                step = self._render_steps["render"] = \
                    render_step_sharded_batched(
                        self.mesh, replicate_output=self._replicated)
            return step

    def _jpeg_step(self, quality: int, cap: int, engine: str = "sparse",
                   cap_words: int | None = None):
        key = (engine, quality, cap, cap_words)
        with self._steps_lock:
            step = self._jpeg_steps.get(key)
            if step is None:
                step = self._jpeg_steps[key] = \
                    render_jpeg_step_sharded_batched(
                        self.mesh, quality, cap=cap, engine=engine,
                        cap_words=cap_words,
                        replicate_output=self._replicated)
            return step

    # ------------------------------------------------------------ groups

    def _stacked(self, group: List[_Pending]):
        raw, stack = self._group_arrays(group)
        s0 = group[0].settings
        stacked = {
            "window_start": stack("window_start"),
            "window_end": stack("window_end"),
            "family": stack("family"),
            "coefficient": stack("coefficient"),
            "reverse": stack("reverse"),
            "tables": stack("tables"),
            "cd_start": s0["cd_start"],
            "cd_end": s0["cd_end"],
        }
        raw, stacked = _pad_group(
            np.asarray(raw, np.float32) if isinstance(raw, np.ndarray)
            else raw,
            stacked, self.mesh.shape["data"], self.mesh.shape["chan"])
        return raw, stacked

    def _render_group(self, group: List[_Pending]) -> List[np.ndarray]:
        n = len(group)
        # Fetch/stage half outside the device gate: group N+1 stacks
        # and pads while group N executes.  The pod announce stays
        # INSIDE the gate so announce order always equals launch order
        # (single-lane on multi-host).
        t_stage = time.perf_counter()
        with stopwatch("batcher.stage"):
            raw, stacked = self._stacked(group)
        telemetry.add_cost(
            "stage_ms", (time.perf_counter() - t_stage) * 1000.0 / n)
        shape = "mesh:" + _shape_label(raw.shape)
        with self._lane():
            if self._pod is not None:
                self._pod.announce(_POD_RENDER, raw, stacked)
            t0 = time.perf_counter()
            with stopwatch("Renderer.renderAsPackedInt.mesh"):
                host = self._render_wire(raw, stacked)
            exec_ms = (time.perf_counter() - t0) * 1000.0
        telemetry.add_cost("device_ms", exec_ms / n)
        telemetry.SHAPE_COSTS.observe(shape, exec_ms)
        self._count_batch(n, raw.shape[0])
        return [host[i, :p.h, :p.w] for i, p in enumerate(group[:n])]

    def _render_wire(self, raw, stacked) -> np.ndarray:
        """The SPMD-identical half of a packed render: dispatch + full
        result materialization.  Leader and followers both run this."""
        args = shard_batch_batched(self.mesh, raw, stacked)
        return np.asarray(self._render_step()(*args))

    @staticmethod
    def _dense_coefficients(raw, stacked, qy, qc, i):
        """Single-tile dense coefficients on the default device — the
        rare-overflow fallback shared by both wire engines."""
        from ..ops.jpegenc import render_to_jpeg_coefficients

        y, cb, cr = render_to_jpeg_coefficients(
            np.asarray(raw[i:i + 1], np.float32),
            np.asarray(stacked["window_start"][i:i + 1]),
            np.asarray(stacked["window_end"][i:i + 1]),
            np.asarray(stacked["family"][i:i + 1]),
            np.asarray(stacked["coefficient"][i:i + 1]),
            np.asarray(stacked["reverse"][i:i + 1]),
            stacked["cd_start"], stacked["cd_end"],
            np.asarray(stacked["tables"][i:i + 1]), qy, qc)
        return np.asarray(y)[0], np.asarray(cb)[0], np.asarray(cr)[0]

    def _sparse_wire(self, raw, stacked, H, W, quality):
        """Sparse-engine dispatch with the one-shot cap-widening
        rescue; SPMD-identical on leader and followers (with replicated
        outputs every process sees the same totals, so the memo — and
        therefore the launch sequence — stays in lockstep with no host
        collective)."""
        from ..ops.jpegenc import (_CAP_MEMO, default_sparse_cap,
                                   wire_fetcher, wire_header_i32)

        cap = default_sparse_cap(H, W, quality)
        memo_key = ("mesh-sparse", H, W, quality)
        if _CAP_MEMO.get(memo_key):
            cap *= 2
        args = shard_batch_batched(self.mesh, raw, stacked)
        bufs = wire_fetcher(H, W, cap).fetch(
            self._jpeg_step(quality, cap)(*args))
        totals = wire_header_i32(bufs, 0)
        if (memo_key not in _CAP_MEMO
                and ((totals > cap) & (totals <= 2 * cap)).any()):
            _CAP_MEMO[memo_key] = True
            cap *= 2
            bufs = wire_fetcher(H, W, cap).fetch(
                self._jpeg_step(quality, cap)(*args))
        return bufs, cap

    def _huffman_wire(self, raw, stacked, H, W, quality):
        """Huffman-engine dispatch with the one-shot widening; same
        lockstep contract as :meth:`_sparse_wire`."""
        from ..ops.jpegenc import (_CAP_MEMO, default_sparse_cap,
                                   default_words_cap,
                                   huffman_wire_fetcher, wire_header_i32)

        cap = default_sparse_cap(H, W, quality)
        cap_words = default_words_cap(H, W, quality)
        memo_key = ("mesh-huffman", H, W, quality)
        if _CAP_MEMO.get(memo_key):
            cap, cap_words = cap * 2, cap_words * 2
        args = shard_batch_batched(self.mesh, raw, stacked)
        bufs = huffman_wire_fetcher(H, W, cap, cap_words).fetch(
            self._jpeg_step(quality, cap, "huffman", cap_words)(*args))
        totals = wire_header_i32(bufs, 0)
        bits = wire_header_i32(bufs, 1)
        over = (totals > cap) | (bits > cap_words * 32)
        rescuable = ((totals <= 2 * cap)
                     & (bits <= 2 * cap_words * 32))
        if memo_key not in _CAP_MEMO and (over & rescuable).any():
            _CAP_MEMO[memo_key] = True
            cap, cap_words = cap * 2, cap_words * 2
            bufs = huffman_wire_fetcher(H, W, cap, cap_words).fetch(
                self._jpeg_step(quality, cap, "huffman",
                                cap_words)(*args))
        return bufs, cap, cap_words

    def _jpeg_engine_for(self, all_exact: bool) -> str:
        # The packed Huffman stream covers the full (H, W) grid, so the
        # wire-optimal engine applies only when every tile in the group
        # is grid-exact (same policy as ``render_batch_to_jpeg``);
        # mixed groups fall back to the sparse engine as a whole.  The
        # group's engine rides the pod announcement (engine_id), so
        # followers replay the fall-back in lockstep.
        return "huffman" if self.jpeg_engine == "huffman" and all_exact \
            else "sparse"

    def _render_group_jpeg(self, group: List[_Pending]) -> List[bytes]:
        from ..ops.jpegenc import (dense_encoder, finish_huffman_batch,
                                   finish_sparse_to_jpegs, quant_tables)
        from ..utils.stopwatch import REGISTRY

        n = len(group)
        REGISTRY.record("batcher.groupTiles", float(n))
        t_stage = time.perf_counter()
        with stopwatch("batcher.stage"):
            raw, stacked = self._stacked(group)
        telemetry.add_cost(
            "stage_ms", (time.perf_counter() - t_stage) * 1000.0 / n)
        shape = "mesh:" + _shape_label(raw.shape, jpeg=True)
        H, W = raw.shape[-2:]
        quality = group[0].quality
        all_exact = all((p.h + 15) // 16 * 16 == H
                        and (p.w + 15) // 16 * 16 == W for p in group)
        engine = self._jpeg_engine_for(all_exact)
        qy, qc = (np.asarray(t, np.int32) for t in quant_tables(quality))
        dims = [(p.w, p.h) for p in group]
        if engine == "huffman":
            with self._lane():
                if self._pod is not None:
                    self._pod.announce(_POD_JPEG, raw, stacked, quality,
                                       engine_id=1)
                t0 = time.perf_counter()
                with stopwatch("Renderer.renderAsPackedInt.mesh"):
                    bufs, cap, cap_words = self._huffman_wire(
                        raw, stacked, H, W, quality)
                exec_ms = (time.perf_counter() - t0) * 1000.0
            telemetry.add_cost("device_ms", exec_ms / n)
            telemetry.SHAPE_COSTS.observe(shape, exec_ms)
            _dense_encode = dense_encoder()

            def dense_tile(i):
                # Rare cap/bits overflow: dense re-encode of one tile.
                y, cb, cr = self._dense_coefficients(raw, stacked, qy,
                                                     qc, i)
                return _dense_encode(y, cb, cr, group[i].w, group[i].h,
                                     quality)

            jpegs = finish_huffman_batch(
                bufs, dims, H, W, quality, cap, cap_words,
                dense_fallback=dense_tile,
                # First-tile-out is host-side settlement AFTER the
                # lockstep device work — safe on a pod (no launch
                # depends on it).
                on_tile=self._early_settle_cb(group))
        else:
            with self._lane():
                if self._pod is not None:
                    self._pod.announce(_POD_JPEG, raw, stacked, quality,
                                       engine_id=0)
                t0 = time.perf_counter()
                with stopwatch("Renderer.renderAsPackedInt.mesh"):
                    bufs, cap = self._sparse_wire(raw, stacked, H, W,
                                                  quality)
                exec_ms = (time.perf_counter() - t0) * 1000.0
            telemetry.add_cost("device_ms", exec_ms / n)
            telemetry.SHAPE_COSTS.observe(shape, exec_ms)
            jpegs = finish_sparse_to_jpegs(
                bufs, dims, H, W, quality, cap,
                lambda i: self._dense_coefficients(raw, stacked, qy,
                                                   qc, i),
                on_tile=self._early_settle_cb(group))
        self._count_batch(n, raw.shape[0])
        return jpegs

    async def close(self) -> None:
        await super().close()
        if self._pod is not None and jax_process_index() == 0:
            logger.info("pod leader: announcing shutdown")
            self._pod.announce(_POD_SHUTDOWN)
            logger.info("pod leader: shutdown announced")


def jax_process_index() -> int:
    import jax
    return jax.process_index()


def run_pod_follower(mesh: Mesh, jpeg_engine: str = "sparse") -> int:
    """Follower loop for non-leader pod processes.

    Receives each group the leader announces over the pod channel and
    runs the IDENTICAL sharded dispatch flow (including the cap-rescue
    re-dispatches, whose decisions are deterministic from the
    replicated wire totals), keeping the pod's SPMD launch sequence in
    lockstep.  Host-side JFIF finishing is skipped — followers produce
    no responses.  Returns the number of groups served; exits on the
    leader's shutdown announcement.
    """
    renderer = MeshRenderer(mesh, jpeg_engine=jpeg_engine)
    pod = renderer._pod or _PodChannel()
    groups = 0
    while True:
        kind, raw, stacked, quality, engine_id = pod.recv()
        if kind == _POD_SHUTDOWN:
            logger.info("pod follower: shutdown after %d groups", groups)
            return groups
        if kind == _POD_RENDER:
            renderer._render_wire(raw, stacked)
        else:
            H, W = raw.shape[-2:]
            if engine_id == 1:
                renderer._huffman_wire(raw, stacked, H, W, quality)
            else:
                renderer._sparse_wire(raw, stacked, H, W, quality)
        groups += 1
