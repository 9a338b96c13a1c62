"""Ask the TPU's compiler, without a chip: the selective scan and the gated
delta rule, their plain forms and their kernels.

libtpu is installed here and compiles for a chip that is described, not
attached (`topologies.get_topology_desc`, topology v5e:2x2), so what the
chip's compiler would refuse (a Pallas block the tiling rejects, a program
that does not fit 16 GB of HBM) fails in tier-1 at no chip time. Nothing
runs: a compile that passes says nothing about results or speed, and is never
reported as a chip run. `topo` (skipped where the topology cannot be
described) and `_compile_cache_off` are tests/conftest.py's; the other files
of this kind are tests/test_tpu_compile*.py, one a kind of program so that no
one worker carries them all.
"""

import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

pytestmark = pytest.mark.usefixtures("_compile_cache_off")


def _scan_arguments(topo):
    """ops/selscan.py's arguments at the Phi-4-mini-flash cell's size, as
    shapes on one described chip: x, dt, a (D, N), b, c."""
    from mgwfbp_tpu.models.phi4flash import PHI4FLASH

    t, d, n = 8192, PHI4FLASH.mamba_inner, PHI4FLASH.mamba_state
    one = SingleDeviceSharding(topo.devices[0])

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    return arg, (
        arg((1, t, d), jnp.bfloat16), arg((1, t, d), jnp.float32),
        arg((d, n), jnp.float32), arg((1, t, n), jnp.bfloat16),
        arg((1, t, n), jnp.bfloat16))


def test_selective_scan_keeps_no_whole_sequence_of_states_on_a_v5e(topo):
    """ops/selscan.py's chunked form at the Phi-4-mini-flash cell's size (T
    8,192, 5,120 channels x 16 states, the model's chunk and block), forward
    and backward: it compiles for the chip and ALL its scratch (1.54 GiB: the
    four or five (positions of a block, states, channels) float32 arrays one
    block's backward holds at once, 0.33 GiB each) stays under what ONE
    float32 (T, channels, states) array would take, 2.5 GiB: the states of a
    block's positions live only inside that block's forward and recomputed
    backward."""
    from mgwfbp_tpu.models.phi4flash import PHI4FLASH, Phi4FlashLM
    from mgwfbp_tpu.ops.selscan import chunked_scan

    _, args = _scan_arguments(topo)
    (_, t, d), n = args[0].shape, args[2].shape[1]

    def loss(x, dt, a, b, c):
        y, state = chunked_scan(
            x, dt, a, b, c, chunk=PHI4FLASH.scan_chunk,
            block=Phi4FlashLM.scan_block)
        return jnp.sum(y) + jnp.sum(state)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        *args).compile()
    whole = t * d * n * 4  # 2.5 GiB
    assert compiled.memory_analysis().temp_size_in_bytes < 0.7 * whole
    assert "tpu_custom_call" not in compiled.as_text()  # plain jax.numpy


def test_delta_rule_keeps_no_whole_sequence_of_states_on_a_v5e(topo):
    """ops/deltarule.py at the Qwen3-Next cell's size (2 sequences of 8,192,
    16 key and 32 value heads of 128, the model's chunk and block), forward
    and backward: it compiles for the chip at 0.95 GiB of scratch, and no
    array holds a (keys, values) state for more than the 16 blocks' starts
    or one block's 8 chunks (a state a chunk over the whole sequence would
    be 128 of them a head): a block's states live only inside that block's
    forward and recomputed backward."""
    from mgwfbp_tpu.models.qwen3next import QWEN3NEXT as S, Qwen3NextLM
    from mgwfbp_tpu.ops.deltarule import gated_delta_rule

    one = SingleDeviceSharding(topo.devices[0])
    b, t = 2, 8192

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    qk = arg((b, t, S.linear_key_heads, S.linear_key_dim), jnp.bfloat16)
    v = arg((b, t, S.linear_value_heads, S.linear_value_dim), jnp.bfloat16)
    gate = arg((b, t, S.linear_value_heads), jnp.float32)

    def loss(q, k, v, g, beta):
        o, state = gated_delta_rule(
            q, k, v, g, beta, chunk=S.delta_chunk,
            block=Qwen3NextLM.delta_block)
        return jnp.sum(o.astype(jnp.float32)) + jnp.sum(state)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        qk, qk, v, gate, gate).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1.25 * 2 ** 30
    text = compiled.as_text()
    assert "tpu_custom_call" not in text  # plain jax.numpy
    assert "triangular-solve" not in text  # the inverse is formed by blocks
    one_state = S.linear_key_dim * S.linear_value_dim
    chunks = t // S.delta_chunk
    states = [
        math.prod(int(n) for n in dims.split(",")) // one_state
        for dims in re.findall(
            rf"f32\[([\d,]+),{S.linear_key_dim},{S.linear_value_dim}\]", text)]
    assert states and max(states) <= b * S.linear_value_heads * max(
        Qwen3NextLM.delta_block, chunks // Qwen3NextLM.delta_block)


def test_delta_rule_kernels_keep_a_tiles_matrices_off_hbm_on_a_v5e(topo):
    """ops/deltarule.py's three kernels at the same size and the block the
    shape test gives it, as one layer has them: the rule under
    `jax.checkpoint`, its value and its pull-back. They compile for the chip
    (the blocks, the backward's recomputed states and solves and the
    inverse's slabs fit VMEM), as THREE distinct kernel programs; what the
    forward hands the backward beside the inputs is one state a block of 512
    positions (f32[2,16,32,128,128], 64 MiB): no state a chunk, and the
    chunks' (I + A)^-1 (f32[2,16,2,64,64,128] between the inverse kernel
    and the kernel that reads it) is no residual; the scratch stays under
    0.5 GiB where the plain form's is 0.72. The kernel path is called
    outright: this process traces for the CPU."""
    from mgwfbp_tpu.models.qwen3next import QWEN3NEXT as S
    from mgwfbp_tpu.ops import deltarule

    one = SingleDeviceSharding(topo.devices[0])
    b, t = 2, 8192
    hk, h = S.linear_key_heads, S.linear_value_heads
    dk, dv = S.linear_key_dim, S.linear_value_dim

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    qk, v = arg((b, t, hk, dk), jnp.bfloat16), arg((b, t, h, dv), jnp.bfloat16)
    gate = arg((b, t, h), jnp.float32)
    rows = deltarule._kernel_rows(
        t, hk, h, dk, dv, S.delta_chunk, (jnp.bfloat16,) * 3)
    assert rows is not None

    def rule(*x):
        return deltarule._kernel_rule(*x, S.delta_chunk, rows, False)

    def layer(q, k, v, g, beta, do, dlast):
        out, pull = jax.vjp(jax.checkpoint(rule), q, k, v, g, beta)
        return out, pull((do, dlast))

    compiled = jax.jit(layer).lower(
        qk, qk, v, gate, gate, v, arg((b, h, dk, dv), jnp.float32)).compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert {re.search(r"(gated_delta_rule_\w+)/pallas_call", line).group(1)
            for line in calls} == {
                "gated_delta_rule_inverse", "gated_delta_rule_forward",
                "gated_delta_rule_backward"}
    assert len(calls) in (5, 6)  # the compiler may share one inverse
    # states: the blocks' starts and the final state's cotangent, no more
    blocks = t // rows
    for shape in re.findall(rf"f32\[([\d,]*),{dk},{dv}\]", text):
        assert math.prod(int(n) for n in shape.split(",")) <= b * h * blocks
    assert blocks * 8 == t // S.delta_chunk
    residuals = jax.eval_shape(
        lambda *x: deltarule._kernel_rule_fwd(
            *x, S.delta_chunk, rows, False)[1], qk, qk, v, gate, gate)
    held = sorted(math.prod(x.shape) for x in residuals)
    assert held == sorted([
        *(math.prod(x.shape) for x in (qk, qk, v, gate, gate)),
        b * blocks * h * dk * dv])
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5 * 2 ** 30


@pytest.mark.parametrize("dtype,hk,h,t,rows", [
    (jnp.float32, 2, 8, 384, 128),    # four value heads a key head, one
    # tile a block; the inverse's step takes one tile: lanes no tile fills
    (jnp.float32, 8, 8, 768, 256),    # one value head a key head
    (jnp.float32, 4, 8, 2048, 512),   # the cell's group and block, float32
    (jnp.float32, 2, 8, 2048, 512),   # the most VMEM the kernels are let
    (jnp.bfloat16, 2, 8, 1024, 512),  # groups of four, the solve's 3 passes
    (jnp.bfloat16, 8, 8, 256, 256),   # no group
    (jnp.bfloat16, 4, 8, 640, 128),   # the cell's group, the smallest block
])
def test_delta_rule_kernels_compile_for_v5e_wherever_they_are_chosen(
        topo, dtype, hk, h, t, rows):
    """`_kernel_rows` sends float32 as well as bfloat16, one, two and four
    value heads a key head, blocks of 512, 256 and 128 positions and any
    number of tiles down the kernels, and a shape that Mosaic refused would
    fail the step's compile where the plain form was to be had: interpret
    mode takes shapes the chip's compiler does not (PR 40's SMEM block).
    So each corner is compiled for the described chip, value and pull-back,
    at a short T. Nothing runs: the values are `tests/test_deltarule.py`'s,
    interpreted."""
    from mgwfbp_tpu.ops import deltarule

    one = SingleDeviceSharding(topo.devices[0])

    def arg(shape, of=jnp.float32):
        return jax.ShapeDtypeStruct(shape, of, sharding=one)

    assert deltarule._kernel_rows(t, hk, h, 128, 128, 64, (dtype,) * 3) == rows

    def layer(q, k, v, g, beta, do, dlast):
        out, pull = jax.vjp(
            lambda *x: deltarule._kernel_rule(*x, 64, rows, False),
            q, k, v, g, beta)
        return out, pull((do, dlast))

    qk, v = arg((1, t, hk, 128), dtype), arg((1, t, h, 128), dtype)
    text = jax.jit(layer).lower(
        qk, qk, v, arg((1, t, h)), arg((1, t, h)), v,
        arg((1, h, 128, 128))).compile().as_text()
    assert {"gated_delta_rule_inverse", "gated_delta_rule_forward",
            "gated_delta_rule_backward"} <= set(
                re.findall(r"(gated_delta_rule_\w+)/pallas_call", text))


def test_selective_scan_kernels_keep_the_state_off_hbm_on_a_v5e(topo):
    """ops/selscan.py's two kernels at the same size and the tiles the shape
    test gives it, as one layer has them: the scan under `jax.checkpoint`,
    its value and its pull-back. They compile for the chip (the tiles and
    the block's recomputed states fit VMEM), as TWO distinct kernel programs
    at two or three sites (the forward, its recomputation where the compiler
    keeps it, the backward), no float32 (.., states, channels) array with a
    dimension of positions before it exists in the text (what is saved is
    one state a block of positions: 10 MiB), and the scratch stays under
    0.2 GiB where the chunked form's is 1.54. The kernel path is called
    outright: this process traces for the CPU."""
    from mgwfbp_tpu.ops import selscan

    arg, args = _scan_arguments(topo)
    (_, t, d), n = args[0].shape, args[2].shape[1]
    tiles = selscan._kernel_tiles(
        t, d, n, (args[0].dtype, args[3].dtype, args[4].dtype))
    assert tiles is not None

    def layer(x, dt, a, b, c, dy, dlast):
        out, pull = jax.vjp(
            jax.checkpoint(lambda *v: selscan._kernel_scan(*v, tiles, False)),
            x, dt, a.T, b, c)
        return out, pull((dy, dlast))

    compiled = jax.jit(layer).lower(
        *args, arg((1, t, d), jnp.float32), arg((1, n, d), jnp.float32)
    ).compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert len(calls) in (2, 3)
    assert {re.search(r"(selective_scan_\w+)/pallas_call", line).group(1)
            for line in calls} == {
                "selective_scan_forward", "selective_scan_backward"}
    # f32[1,32,16,5120], the state each block starts from, is there; nothing
    # with as many states as a block has positions, or the sequence has, is
    saved = t // tiles.rows
    for shape in re.findall(rf"f32\[([\d,]*),{n},{d}\]", text):
        assert all(int(size) in (1, saved) for size in shape.split(","))
    assert saved * 8 <= tiles.rows
    assert compiled.memory_analysis().temp_size_in_bytes < 0.2 * 2 ** 30


@pytest.mark.parametrize("dtype,bsz,t,h,p,n,chunk", [
    (jnp.float32, 1, 8192, 64, 64, 128, 256),  # the Granite cell in float32
    (jnp.float32, 2, 512, 4, 128, 256, 128),   # whole lane tiles, two of state
    (jnp.bfloat16, 2, 512, 4, 64, 128, 128),   # half lane tiles, short chunks
    (jnp.bfloat16, 1, 512, 2, 128, 128, 256),  # one head a lane tile
    # the Nemotron 3 Super cell: a tensor share's 16 heads over ONE B/C
    # group, in place at 1,280 columns, the published chunk of 128
    (jnp.bfloat16, 1, 8192, 16, 64, 128, 128),
])
def test_ssd_kernels_compile_for_v5e_wherever_they_are_chosen(
        topo, dtype, bsz, t, h, p, n, chunk):
    """`ops/ssd._kernel_dims` sends float32 as well as bfloat16, heads of a
    whole and of half a lane tile, chunks of 128 and 256 and a state of more
    than one lane tile down the kernels, and a shape that Mosaic refused
    would fail the step's compile where the plain form was to be had. So
    each corner is compiled for the described chip, value and pull-back
    (the cell's own corner, bfloat16, is tests/test_tpu_compile.py's mixer).
    Nothing runs: the values are `tests/test_ssd.py`'s, interpreted."""
    from mgwfbp_tpu.ops import ssd

    one = SingleDeviceSharding(topo.devices[0])

    def arg(shape, of=jnp.float32):
        return jax.ShapeDtypeStruct(shape, of, sharding=one)

    dims = ssd._kernel_dims(t, h, p, n, chunk, (dtype,) * 3)
    assert dims == (h, p, n, chunk)

    def layer(xbc, dt, a, d, dy, dlast):
        out, pull = jax.vjp(
            lambda *v: ssd._kernel_rule(*v, dims, False)[:2], xbc, dt, a, d)
        return out, pull((dy, dlast))

    text = jax.jit(layer).lower(
        arg((bsz, t, h * p + 2 * n), dtype), arg((bsz, t, h)), arg((h,)),
        arg((h,)), arg((bsz, t, h, p)), arg((bsz, h * p, n))
    ).compile().as_text()
    assert {"ssd_scan_forward", "ssd_scan_backward"} == set(
        re.findall(r"(ssd_scan_\w+)/pallas_call", text))
