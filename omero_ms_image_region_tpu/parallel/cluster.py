"""Multi-host deployment: the distributed communication backend.

The reference scales out by joining microservice JVMs into a Hazelcast
cluster over the Vert.x event bus (``-cluster``; SURVEY.md §5 "distributed
communication backend").  The TPU-native equivalent is JAX's distributed
runtime: each host process joins a coordinator (DCN), after which
``jax.devices()`` spans every chip in the slice and a single
``jax.sharding.Mesh`` over the global device list makes the sharded
serving steps (``parallel.mesh``) span hosts — collectives ride ICI
within a slice, DCN across slices, with no application-level cluster
protocol at all.  Cross-instance *state* (tile cache, canRead memo) rides
Redis (``services.cache``), mirroring the reference's split between
cluster transport and shared maps.

Typical multi-host launch (one process per host, same command)::

    from omero_ms_image_region_tpu.parallel import cluster
    cluster.initialize()                 # env-driven (TPU pods: automatic)
    mesh = cluster.global_mesh(chan_parallel=2)
    step = render_jpeg_step_sharded(mesh)
"""

from __future__ import annotations

from typing import Optional

import jax
import numpy as np

from .mesh import Mesh, make_mesh, resolve_devices


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None) -> None:
    """Join the JAX distributed runtime (idempotent).

    On Cloud TPU pods every argument is discovered from the environment;
    elsewhere pass the coordinator explicitly.  Safe to call in
    single-process deployments: with no coordinator configured anywhere it
    leaves the process standalone.
    """
    # Idempotency check WITHOUT touching the backend:
    # jax.process_count() would initialize XLA, after which
    # jax.distributed.initialize() permanently refuses — i.e. the old
    # process_count() probe made every explicit multi-host join fail.
    # (Caught by the 2-process simulated-pod test.)
    if jax.distributed.is_initialized():
        return
    try:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
    except (ValueError, RuntimeError):
        if coordinator_address is not None:
            raise  # explicit cluster config that failed must be loud
        # No cluster environment: standalone single-process service.


def host_identity() -> str:
    """A stable identity for THIS host, for ``federation.host``
    defaults and diagnostics: the JAX distributed process index when a
    cluster is joined (``procN`` — stable across the slice by
    construction), else the OS hostname.  Backend-free unless a
    cluster was already joined (the :func:`initialize` discipline)."""
    if jax.distributed.is_initialized():
        try:
            return f"proc{jax.process_index()}"
        except Exception:
            pass
    import socket
    return socket.gethostname()


def global_mesh(chan_parallel: int = 1,
                n_devices: Optional[int] = None) -> Mesh:
    """A ``(data, chan)`` mesh over every device in the (multi-host) slice.

    With ``jax.distributed`` initialized this spans all hosts; the sharded
    steps built on it (``render_step_sharded`` /
    ``render_jpeg_step_sharded``) then execute one program over the whole
    slice, each host feeding its addressable shard of the batch.

    ``n_devices`` requests a mesh width; a platform with fewer devices
    is an error (``mesh.resolve_devices``), never a quiet substitution.
    """
    devices = np.asarray(resolve_devices(n_devices))
    return make_mesh(len(devices), chan_parallel=chan_parallel,
                     devices=devices)


def local_batch_slice(mesh: Mesh, global_batch: int) -> slice:
    """This process's rows of the global batch (data-axis locality).

    Hosts feed only their addressable shard; the slice maps a global
    [B, ...] workload to the rows this process should stage.
    """
    data_size = mesh.shape["data"]
    if global_batch % data_size:
        raise ValueError(
            f"global batch {global_batch} not divisible by data axis "
            f"{data_size}")
    per_shard = global_batch // data_size
    rows = [i for i, d in enumerate(mesh.devices[:, 0])
            if d.process_index == jax.process_index()]
    if not rows:
        return slice(0, 0)
    if rows != list(range(rows[0], rows[-1] + 1)):
        raise ValueError(
            "this process's data-axis rows are not contiguous "
            f"({rows}); a single slice cannot describe its shard — "
            "reorder the mesh so each process owns a contiguous run "
            "of data rows")
    return slice(rows[0] * per_shard, (rows[-1] + 1) * per_shard)
