"""Mesh-sharded render step (data-parallel tiles x tensor-parallel channels).

The reference's concurrency model is request-level data parallelism over
worker verticles plus cluster scale-out over a Hazelcast event bus
(``ImageRegionMicroserviceVerticle.java:148-165``, SURVEY.md section 2c).
Here that becomes a 2-D ``jax.sharding.Mesh``:

  * ``data`` axis — concurrent tile requests (the micro-batch) are sharded
    across devices: pure DP, no communication.
  * ``chan`` axis — the per-channel pipeline (window/family quantize + LUT
    gather + alpha-weighted contribution) is sharded across channels: each
    device renders its local channel slice and the additive RGB composite
    (``Renderer.renderAsPackedInt``'s sum over active channels) becomes a
    single ``jax.lax.psum`` over the ``chan`` axis — the collective rides
    ICI, replacing the reference's in-JVM accumulation loop.

Everything is expressed with ``shard_map`` so the collective is explicit and
XLA never has to guess the partitioning of the composite.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map as _shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.quantum import quantize


def shard_map(f, mesh, in_specs, out_specs):
    return _shard_map(f, mesh=mesh, in_specs=in_specs,
                      out_specs=out_specs, check_vma=False)


def resolve_devices(n_devices: int | None = None):
    """The default platform's devices for an ``n_devices``-wide mesh.

    Too few devices is an error: a mesh asked for on an accelerator is
    never quietly built from some other platform's devices.  A caller
    that WANTS the virtual host mesh (tests, the compile-check entry)
    runs with ``JAX_PLATFORMS=cpu`` or hands ``make_mesh`` its devices.
    """
    devices = jax.devices()
    if n_devices is not None and len(devices) < n_devices:
        raise ValueError(
            f"requested a {n_devices}-device mesh but platform "
            f"{devices[0].platform!r} has only {len(devices)} device(s)")
    return devices


def make_mesh(n_devices: int | None = None, chan_parallel: int = 1,
              devices=None) -> Mesh:
    """Build a ``(data, chan)`` mesh over the available devices.

    ``chan_parallel`` devices cooperate on one tile's channels; the rest of
    the devices replicate that group over the batch.
    """
    if devices is None:
        devices = resolve_devices(n_devices)
    if n_devices is None:
        n_devices = len(devices)
    if len(devices) < n_devices:
        raise ValueError(
            f"requested a {n_devices}-device mesh but only "
            f"{len(devices)} device(s) are available"
        )
    devices = np.asarray(devices[:n_devices])
    if n_devices % chan_parallel != 0:
        raise ValueError(
            f"n_devices={n_devices} not divisible by "
            f"chan_parallel={chan_parallel}"
        )
    grid = devices.reshape(n_devices // chan_parallel, chan_parallel)
    return Mesh(grid, ("data", "chan"))


def _local_render(raw, window_start, window_end, family, coefficient,
                  reverse, cd_start, cd_end, tables):
    """Per-device block: quantize + gather local channels, partial composite.

    Block shapes (local to one device): raw f32[Bl, Cl, H, W], params [Cl],
    tables f32[Cl, 256, 3].  Returns the *partial* per-component RGB sum
    f32[3, Bl, H, W] (component axis leading — a trailing 3 would pad to
    128 lanes on TPU); the caller psums it over the ``chan`` axis.
    """
    q = quantize(
        raw.reshape((-1,) + raw.shape[-2:]),
        jnp.tile(window_start, raw.shape[0]),
        jnp.tile(window_end, raw.shape[0]),
        jnp.tile(family, raw.shape[0]),
        jnp.tile(coefficient, raw.shape[0]),
        cd_start,
        cd_end,
    ).reshape(raw.shape)  # i32[Bl, Cl, H, W]
    q = jnp.where(
        reverse[None, :, None, None] != 0, cd_start + cd_end - q, q
    )
    if tables.ndim == 2:
        # Ramp weights [Cl, 3]: arithmetic composite (ops.render
        # .composite_ramp_packed) — no per-pixel gather.
        qf = q.astype(jnp.float32)
        comps = [
            jnp.einsum("bchw,c->bhw", qf, tables[:, comp])
            for comp in range(3)
        ]
        return jnp.stack(comps, axis=0)            # [3, Bl, H, W]
    # Per-component flat shared-operand gather with per-channel block
    # offsets (see ops.render.composite_packed for why not table[q]).
    Cl = tables.shape[0]
    flat = tables.reshape(Cl * 256, 3)
    idx = q + (jnp.arange(Cl, dtype=q.dtype) * 256)[None, :, None, None]
    comps = [
        jnp.sum(jnp.take(flat[:, comp], idx, axis=0), axis=1)  # [Bl, H, W]
        for comp in range(3)
    ]
    return jnp.stack(comps, axis=0)                # [3, Bl, H, W]


# One spec per step argument: raw [B, C, H, W], five per-channel setting
# arrays, the two codomain scalars, and tables/weights [C, ...].
_STEP_IN_SPECS = (
    P("data", "chan"), P("chan"), P("chan"), P("chan"), P("chan"),
    P("chan"), P(), P(), P("chan"),
)


def _composite_step(raw, window_start, window_end, family, coefficient,
                    reverse, cd_start, cd_end, tables):
    """Per-shard render + cross-shard composite -> packed u32[Bl, H, W].

    The additive composite across channel shards is the one collective
    (``psum`` over ICI); the shared body of every sharded step variant.
    """
    partial_rgb = _local_render(
        raw, window_start, window_end, family, coefficient, reverse,
        cd_start, cd_end, tables,
    )                                          # f32 [3, Bl, H, W]
    rgb = jax.lax.psum(partial_rgb, axis_name="chan")
    rgb = jnp.clip(jnp.round(rgb), 0.0, 255.0).astype(jnp.uint32)
    return rgb[0] | (rgb[1] << 8) | (rgb[2] << 16) | jnp.uint32(0xFF000000)


def render_step_sharded(mesh: Mesh):
    """Build the jitted mesh-sharded batched render step.

    Returns a function ``step(raw, window_start, window_end, family,
    coefficient, reverse, cd_start, cd_end, tables) -> u32[B, H, W]``
    (packed little-endian R,G,B,A as in ``ops.render.render_tile_packed``)
    with ``raw`` f32[B, C, H, W] sharded ``P('data', 'chan')`` and
    per-channel arrays sharded ``P('chan')``; output sharded ``P('data')``.
    """
    sharded = shard_map(
        _composite_step,
        mesh=mesh,
        in_specs=_STEP_IN_SPECS,
        out_specs=P("data"),
    )
    return jax.jit(sharded)


def render_jpeg_step_sharded(mesh: Mesh, quality: int = 85,
                             cap: int | None = None):
    """The full mesh-sharded serving step: raw tiles -> JPEG wire buffers.

    Composes the sharded render (data-parallel tiles x channel-parallel
    partial composites joined by ``psum``) with the device JPEG front end
    (YCbCr, 4:2:0, blocked DCT, quantize, zigzag, sparse nonzero packing)
    — everything the single-chip serving path runs, expressed over the
    mesh, so a multi-host deployment shards whole requests end to end.
    After the ``psum`` the packed image is replicated across the ``chan``
    group, so the JPEG stage computes redundantly there and the output is
    simply data-sharded.

    Returns ``step(*shard_batch(...)) -> u8[B, wire_bytes]`` sparse
    buffers (``ops.jpegenc.sparse_pack`` layout; finish host-side with
    ``ops.jpegenc.encode_sparse_buffers``).
    """
    from ..ops.jpegenc import (default_sparse_cap, packed_to_jpeg_coefficients,
                               quant_tables, sparse_pack)

    # Keep the quant tables as host numpy and lift them to device constants
    # only inside the traced step: an eager ``jnp.asarray`` here would land
    # on the *default* platform, which may be a different (even broken)
    # backend than the mesh the step runs on.
    qy_h, qc_h = (np.asarray(t, np.int32) for t in quant_tables(quality))

    def step(*args):
        packed = _composite_step(*args)              # u32[Bl, H, W]
        H, W = packed.shape[-2:]
        local_cap = cap if cap is not None else default_sparse_cap(H, W)
        y, cb, cr = packed_to_jpeg_coefficients(
            packed, jnp.asarray(qy_h), jnp.asarray(qc_h))
        return sparse_pack(y, cb, cr, local_cap)

    sharded = shard_map(
        step,
        mesh=mesh,
        in_specs=_STEP_IN_SPECS,
        out_specs=P("data"),
    )
    return jax.jit(sharded)


def _local_render_batched(raw, window_start, window_end, family,
                          coefficient, reverse, cd_start, cd_end, tables):
    """Per-device block with PER-TILE settings: raw f32[Bl, Cl, H, W],
    settings [Bl, Cl], tables [Bl, Cl, ...].  The serving path's form —
    concurrent requests carry their own windows/colors — where
    :func:`_local_render` shares one setting vector across the batch.
    Returns the partial per-component RGB sum f32[3, Bl, H, W]."""
    Bl, Cl = raw.shape[:2]
    q = quantize(
        raw.reshape((-1,) + raw.shape[-2:]),
        window_start.reshape(-1),
        window_end.reshape(-1),
        family.reshape(-1),
        coefficient.reshape(-1),
        cd_start,
        cd_end,
    ).reshape(raw.shape)
    q = jnp.where(reverse[..., None, None] != 0, cd_start + cd_end - q, q)
    if tables.ndim == 3:
        qf = q.astype(jnp.float32)
        comps = [
            jnp.einsum("bchw,bc->bhw", qf, tables[..., comp])
            for comp in range(3)
        ]
        return jnp.stack(comps, axis=0)
    flat = tables.reshape(Bl * Cl * 256, 3)
    offs = (jnp.arange(Bl * Cl, dtype=q.dtype) * 256).reshape(Bl, Cl, 1, 1)
    idx = q + offs
    comps = [
        jnp.sum(jnp.take(flat[:, comp], idx, axis=0), axis=1)
        for comp in range(3)
    ]
    return jnp.stack(comps, axis=0)


# Batched-settings step: every per-channel array gains a leading batch
# dim and shards with the tiles.
_BATCHED_STEP_IN_SPECS = (
    P("data", "chan"), P("data", "chan"), P("data", "chan"),
    P("data", "chan"), P("data", "chan"), P("data", "chan"), P(), P(),
    P("data", "chan"),
)


def _composite_step_batched(raw, window_start, window_end, family,
                            coefficient, reverse, cd_start, cd_end,
                            tables):
    partial_rgb = _local_render_batched(
        raw, window_start, window_end, family, coefficient, reverse,
        cd_start, cd_end, tables)
    rgb = jax.lax.psum(partial_rgb, axis_name="chan")
    rgb = jnp.clip(jnp.round(rgb), 0.0, 255.0).astype(jnp.uint32)
    return rgb[0] | (rgb[1] << 8) | (rgb[2] << 16) | jnp.uint32(0xFF000000)


def render_step_sharded_batched(mesh: Mesh,
                                replicate_output: bool = False):
    """Mesh-sharded render with per-tile settings -> u32[B, H, W].

    ``replicate_output`` finishes with an all-gather over the data axis
    so EVERY process holds the full batch — required on multi-host
    meshes, where a data-sharded global array is not addressable from
    the serving process (the gather rides ICI/DCN once instead of N
    host-to-host fetches)."""
    if replicate_output:
        def fn(*args):
            out = _composite_step_batched(*args)
            return jax.lax.all_gather(out, "data", axis=0, tiled=True)
        out_specs = P()
    else:
        fn = _composite_step_batched
        out_specs = P("data")
    sharded = shard_map(
        fn,
        mesh=mesh,
        in_specs=_BATCHED_STEP_IN_SPECS,
        out_specs=out_specs,
    )
    return jax.jit(sharded)


def render_jpeg_step_sharded_batched(mesh: Mesh, quality: int = 85,
                                     cap: int | None = None,
                                     engine: str = "sparse",
                                     cap_words: int | None = None,
                                     replicate_output: bool = False):
    """Mesh-sharded serving step with per-tile settings: raw tiles ->
    JPEG wire buffers, data-sharded.  The per-request form of
    :func:`render_jpeg_step_sharded`.

    ``engine`` picks the wire format after the ``psum`` composite:
    ``"sparse"`` (18-bit coefficient entries, ``sparse_pack`` layout) or
    ``"huffman"`` (device fixed-table Huffman stream, ``huffman_pack``
    layout — ~3x fewer bytes over DCN/slow links)."""
    from ..ops.jpegenc import (default_sparse_cap, default_words_cap,
                               huffman_pack, huffman_spec_arrays,
                               packed_to_jpeg_coefficients, quant_tables,
                               sparse_pack)

    if engine not in ("sparse", "huffman"):
        raise ValueError(f"mesh jpeg engine must be 'sparse' or "
                         f"'huffman', got {engine!r}")
    qy_h, qc_h = (np.asarray(t, np.int32) for t in quant_tables(quality))
    spec_h = huffman_spec_arrays() if engine == "huffman" else None

    def step(*args):
        packed = _composite_step_batched(*args)
        H, W = packed.shape[-2:]
        local_cap = cap if cap is not None else default_sparse_cap(H, W)
        y, cb, cr = packed_to_jpeg_coefficients(
            packed, jnp.asarray(qy_h), jnp.asarray(qc_h))
        if engine == "huffman":
            local_words = (cap_words if cap_words is not None
                           else default_words_cap(H, W))
            bufs = huffman_pack(
                y, cb, cr, local_cap, local_words,
                *(jnp.asarray(a) for a in spec_h),
                h16=H // 16, w16=W // 16)
        else:
            bufs = sparse_pack(y, cb, cr, local_cap)
        if replicate_output:
            # Multi-host: every process needs the full wire buffers
            # (both to serve and to agree on overflow verdicts without
            # a host collective).
            bufs = jax.lax.all_gather(bufs, "data", axis=0, tiled=True)
        return bufs

    sharded = shard_map(
        step,
        mesh=mesh,
        in_specs=_BATCHED_STEP_IN_SPECS,
        out_specs=P() if replicate_output else P("data"),
    )
    return jax.jit(sharded)


def shard_batch_batched(mesh: Mesh, raw, stacked: dict):
    """Device-put a batch with per-tile stacked settings onto the mesh.

    ``stacked`` holds [B, C] settings arrays and [B, C, ...] tables (the
    ``server.batcher`` group form).  Returns the argument tuple for the
    batched sharded steps."""
    put = jax.device_put
    bc = NamedSharding(mesh, P("data", "chan"))
    rep = NamedSharding(mesh, P())
    return (
        put(raw, bc),
        put(stacked["window_start"], bc),
        put(stacked["window_end"], bc),
        put(stacked["family"], bc),
        put(stacked["coefficient"], bc),
        put(stacked["reverse"], bc),
        put(np.int32(stacked["cd_start"]), rep),
        put(np.int32(stacked["cd_end"]), rep),
        put(stacked["tables"], bc),
    )


def shard_batch(mesh: Mesh, raw, settings):
    """Device-put a host batch + packed settings onto the mesh layout.

    ``settings`` is the dict from ``ops.render.pack_settings`` (with a
    possible channel pad so C divides the chan axis).
    """
    put = jax.device_put
    # Scalars are device_put with a replicated sharding over *this* mesh
    # rather than built with ``jnp.int32`` — an eager jnp constant would be
    # committed to the default platform, which need not be the mesh's.
    rep = NamedSharding(mesh, P())
    args = (
        put(raw, NamedSharding(mesh, P("data", "chan"))),
        put(settings["window_start"], NamedSharding(mesh, P("chan"))),
        put(settings["window_end"], NamedSharding(mesh, P("chan"))),
        put(settings["family"], NamedSharding(mesh, P("chan"))),
        put(settings["coefficient"], NamedSharding(mesh, P("chan"))),
        put(settings["reverse"], NamedSharding(mesh, P("chan"))),
        put(np.int32(settings["cd_start"]), rep),
        put(np.int32(settings["cd_end"]), rep),
        put(settings["tables"], NamedSharding(mesh, P("chan"))),
    )
    return args
