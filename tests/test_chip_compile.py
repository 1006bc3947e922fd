"""The main path's device programs compile for the chip — checked here,
without the chip.

The TPU's compiler is installed in the sandbox and compiles for a v5e
that is DESCRIBED, not attached (on-chip-measurement guide §2.3).  What
it refuses here (a Mosaic op it cannot legalize, a block shape off the
tiling, more VMEM than a core grants) it would refuse on the chip, and
interpret-mode tests never notice.  Nothing runs: a compile that passes
is not a chip run and no time here means anything.

Rules this file keeps (the guide's, and they are load-bearing under the
driver's multi-worker command): the topology is described inside the
module-scoped fixture below and nowhere at import; everything built from
it is built in a fixture or a test; compiles happen in this process;
and these tests live in ONE file, so one worker loads the TPU library.

Widths are the smoke's: 4 channels, 1024^2, uint16.  Batch is the
smallest pad the batcher dispatches (1): the full B=8 programs take ~25 s
each and belong to the rehearsal before a chip run, not to the suite.
"""

import os

import numpy as np
import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

C, H, W = 4, 1024, 1024
QUALITY = 90


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A described-topology compile is written to the persistent cache
    # but cannot be read back without a chip (the next one would warn
    # and recompile): keep the cache out of it.
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def shape(topo):
    """``shape(dims, dtype)`` -> a ShapeDtypeStruct on one described
    chip (there is no device to hold an array)."""
    import jax
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(dims, dtype):
        return jax.ShapeDtypeStruct(dims, np.dtype(dtype),
                                    sharding=one_chip)
    return make


def _render_args(shape, B=1, tables=(3,), raw_dtype="uint16", C=C):
    """``render_tile_batch_packed`` argument order, per-tile settings."""
    return (shape((B, C, H, W), raw_dtype),
            shape((B, C), "float32"), shape((B, C), "float32"),
            shape((B, C), "int32"), shape((B, C), "float32"),
            shape((B, C), "int32"), 0, 255,
            shape((B, C) + tables, "float32"))


def _compiled(lowered):
    compiled = lowered.compile()
    assert compiled.memory_analysis() is not None
    return compiled


def test_packed_render_compiles(shape):
    from omero_ms_image_region_tpu.ops.render import (
        render_tile_batch_packed)
    for tables in ((3,), (256, 3)):          # ramp weights, LUT gather
        _compiled(render_tile_batch_packed.lower(
            *_render_args(shape, tables=tables)))


@pytest.mark.parametrize("engine", ["sparse", "huffman"])
def test_jpeg_wire_program_compiles(shape, engine):
    """The fused render + DCT + wire packer + compaction program the
    batcher dispatches for every JPEG group."""
    from omero_ms_image_region_tpu.ops import jpegenc
    cap = jpegenc.default_sparse_cap(H, W, QUALITY)
    q = (shape((8, 8), "int32"), shape((8, 8), "int32"))
    n_valid = shape((), "int32")
    if engine == "sparse":
        lowered = jpegenc.render_to_jpeg_sparse_compact.lower(
            *_render_args(shape), *q, n_valid, cap=cap)
    else:
        spec = [shape(np.shape(a), np.asarray(a).dtype)
                for a in jpegenc.huffman_spec_arrays()]
        lowered = jpegenc.render_to_jpeg_huffman_compact.lower(
            *_render_args(shape), *q, *spec, n_valid,
            h16=H // 16, w16=W // 16, cap=cap,
            cap_words=jpegenc.default_words_cap(H, W, QUALITY))
    _compiled(lowered)


@pytest.mark.parametrize("shown", [5, 6])
def test_channel_stack_and_the_multiplexed_counts_compile(shape, shown):
    """A multiplexed slide's request (5 or 6 shown of 40 stored
    channels): the stack of its resident channel planes, and the JPEG
    program at that count."""
    from omero_ms_image_region_tpu.ops import jpegenc
    from omero_ms_image_region_tpu.ops.render import stack_channel_planes
    stacked = _compiled(stack_channel_planes.lower(
        *[shape((H, W), "uint16")] * shown))
    assert "stage.channel_stack" in stacked.as_text()
    q = (shape((8, 8), "int32"), shape((8, 8), "int32"))
    _compiled(jpegenc.render_to_jpeg_sparse_compact.lower(
        *_render_args(shape, C=shown), *q, shape((), "int32"),
        cap=jpegenc.default_sparse_cap(H, W, QUALITY)))


@pytest.mark.parametrize("B, shown, edge", [
    (64, 4, 256), (8, 4, 1024), (8, 6, 1024), (8, 3, 2048)])
def test_group_stack_compiles_and_keeps_its_one_scope(shape, B, shown,
                                                      edge):
    """The one program that stacks a group's B x C resident planes, at
    the largest batch shape of each cell's bucket: named
    ``stage.channel_stack`` (``utils.profile_summary`` charges its
    device time to that stage) and nothing else."""
    import re

    from omero_ms_image_region_tpu.ops.render import stack_group_planes
    from omero_ms_image_region_tpu.utils.profile_summary import STAGES
    plane = shape((edge, edge), "uint16")
    text = _compiled(stack_group_planes.lower(
        ((plane,) * shown,) * B)).as_text()
    assert f"u16[{B},{shown},{edge},{edge}]" in text
    scopes = set()
    for op_name in re.findall(r'op_name="([^"]*)"', text):
        scopes.update(op_name.split("/"))
    assert scopes & set(STAGES) == {"stage.channel_stack"}


@pytest.mark.parametrize("B", [1, 8])
def test_a_stated_1080_field_compiles_in_its_1088_bucket(shape, B):
    """A Cell Painting field (5 x uint16 x 1080^2, PR 34): the group's
    one program stacks B x 5 resident planes and edge-replicates them
    to the 1088^2 bucket under a scope of its own, and the served JPEG
    program takes that array (1088 is 8.5 lane tiles: the compiler
    has to accept a minor dimension off 128)."""
    import re

    from omero_ms_image_region_tpu.ops import jpegenc
    from omero_ms_image_region_tpu.ops.render import stack_group_planes
    from omero_ms_image_region_tpu.utils.profile_summary import STAGES
    plane = shape((1080, 1080), "uint16")
    text = _compiled(stack_group_planes.lower(
        ((plane,) * 5,) * B, pad=(1088, 1088))).as_text()
    assert f"u16[{B},5,1088,1088]" in text
    scopes = set()
    for op_name in re.findall(r'op_name="([^"]*)"', text):
        scopes.update(op_name.split("/"))
    assert scopes & set(STAGES) == {"stage.channel_stack",
                                    "stage.pad_mcu"}
    q = (shape((8, 8), "int32"), shape((8, 8), "int32"))
    args = (shape((B, 5, 1088, 1088), "uint16"),) + _render_args(
        shape, B=B, C=5)[1:]
    _compiled(jpegenc.render_to_jpeg_sparse_compact.lower(
        *args, *q, shape((), "int32"),
        cap=jpegenc.default_sparse_cap(1088, 1088, QUALITY)))


@pytest.mark.parametrize("edge", [2048, 1088])
def test_jpeg_front_end_keeps_the_plane_width_minor(shape, edge):
    """The 4:2:0 chroma mean (PR 35): no array of the compiled front
    end has a minor dimension of 2.  The chip lays an array out in
    (8, 128) tiles of its two minor dimensions, and the mean written
    as ``reshape(B, H/2, 2, W/2, 2)`` left ``f32[..., 1024, 2]`` with 2
    of every 128 lanes in use: half of a 2048^2 plane's device time.
    The pooling that replaced it is named for its stage."""
    import re

    from omero_ms_image_region_tpu.ops import jpegenc
    q = (shape((8, 8), "int32"), shape((8, 8), "int32"))
    text = _compiled(jpegenc.packed_to_jpeg_coefficients.lower(
        shape((1, edge, edge), "uint32"), *q)).as_text()
    arrays = re.findall(r"\b[a-z]+\d+\[([\d,]+)\]\{([\d,]+)[:}]", text)
    assert len(arrays) > 50
    for dims, minor_to_major in arrays:
        dims = [int(d) for d in dims.split(",")]
        assert dims[int(minor_to_major.split(",")[0])] != 2, dims
    pools = [line for line in text.splitlines()
             if " reduce-window(" in line]
    assert len(pools) == 2                   # cb and cr
    for line in pools:
        assert f"f32[1,{edge // 2},{edge // 2}]" in line
        assert "jpeg.ycbcr420" in re.search(
            r'op_name="([^"]*)"', line).group(1)


def test_mask_pyramid_projection_programs_compile(shape):
    from omero_ms_image_region_tpu.ops import maskops, projection, pyramid
    _compiled(maskops._rasterize_batch_jit.lower(
        shape((1, H * W // 8), "uint8"), width=W, height=H,
        flip_horizontal=False, flip_vertical=False))
    _compiled(maskops._rasterize_batch_jit.lower(
        shape((8, 512 * 512 // 8), "uint8"), width=512, height=512,
        flip_horizontal=True, flip_vertical=True))
    _compiled(pyramid._mean2_int_jit.lower(
        shape((C, 2048, 2048), "uint16")))
    _compiled(projection._fold_max.lower(
        shape((2048, 2048), "float32"), shape((2048, 2048), "float32")))
    _compiled(projection._fold_chunk.lower(
        shape((2048, 2048), "float32"),
        shape((8, 2048, 2048), "uint16"), alg=0))


def test_mesh_jpeg_step_compiles_with_all_reduce(topo):
    """The (data=2, chan=2) serving step on the described 2x2 mesh: the
    channel composite must be there as a collective."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from omero_ms_image_region_tpu.parallel.mesh import (
        render_jpeg_step_sharded_batched)
    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("data", "chan"))
    bc = NamedSharding(mesh, P("data", "chan"))
    rep = NamedSharding(mesh, P())

    def on(dims, dtype, sharding=bc):
        return jax.ShapeDtypeStruct(dims, np.dtype(dtype),
                                    sharding=sharding)
    B = 2                                    # one tile per data shard
    step = render_jpeg_step_sharded_batched(mesh, quality=QUALITY)
    compiled = _compiled(step.lower(
        on((B, C, H, W), "uint16"), on((B, C), "float32"),
        on((B, C), "float32"), on((B, C), "int32"),
        on((B, C), "float32"), on((B, C), "int32"),
        on((), "int32", rep), on((), "int32", rep),
        on((B, C, 3), "float32")))
    assert "all-reduce" in compiled.as_text()
