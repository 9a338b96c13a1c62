"""Ask the TPU's compiler, without a chip.

libtpu is installed here and compiles for a chip that is described, not
attached (`topologies.get_topology_desc`, topology v5e:2x2), so what the
chip's compiler would refuse — a Pallas block the tiling rejects, a step
that does not fit 16 GB of HBM, a collective it cannot partition — fails
in tier-1 at no chip time. Nothing runs: a compile that passes says
nothing about results or speed, and is never reported as a chip run
(`chip_smoke.py` is the run).

Skipped where the topology cannot be described. The persistent compile
cache is off around these tests: an entry written for a described chip
cannot be read back without one, and the next compile would only warn.
"""

import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (
    Mesh,
    NamedSharding,
    PartitionSpec as P,
    SingleDeviceSharding,
)

from mgwfbp_tpu import models as zoo
from mgwfbp_tpu.ops.flashattn import _flash_bhtd
from mgwfbp_tpu.optim import make_optimizer
from mgwfbp_tpu.parallel.allreduce import make_merged_allreduce
from mgwfbp_tpu.parallel.costmodel import lookup_alpha_beta
from mgwfbp_tpu.parallel.mesh import DATA_AXIS
from mgwfbp_tpu.train import create_train_state, make_train_step

HBM_BYTES = 16 * 1024**3  # one v5e chip


# `topo` (the described v5e:2x2) and `_compile_cache_off` are tests/conftest.py's,
# shared with tests/test_tpu_compile_shortconv.py
pytestmark = pytest.mark.usefixtures("_compile_cache_off")


@pytest.mark.parametrize(
    "bh,t,d,dtype",
    [
        (32, 2048, 64, jnp.bfloat16),  # chip_smoke's kernel phase: B4 H8
        (8, 512, 64, jnp.float32),
        (8, 512, 128, jnp.bfloat16),
    ],
)
def test_flash_forward_compiles_for_v5e(topo, bh, t, d, dtype):
    s = SingleDeviceSharding(topo.devices[0])
    x = jax.ShapeDtypeStruct((bh, t, d), dtype, sharding=s)
    compiled = _flash_bhtd.lower(
        x, x, x, causal=True, scale=float(d) ** -0.5, block_q=128,
        block_k=128, interpret=False,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the kernel, compiled


def test_flash_backward_is_not_compilable_yet(topo):
    """The forward-only statement in ops/flashattn.py, pinned: the compiled
    kernel has no custom_vjp, and Pallas refuses to differentiate it. The
    PR that adds a backward turns this test around."""
    s = SingleDeviceSharding(topo.devices[0])
    x = jax.ShapeDtypeStruct((8, 512, 64), jnp.bfloat16, sharding=s)

    def loss(q, k, v):
        out = _flash_bhtd(
            q, k, v, causal=True, scale=0.125, block_q=128, block_k=128,
            interpret=False,
        )
        return out.astype(jnp.float32).sum()

    with pytest.raises(Exception):  # noqa: B017 — AssertionError in Pallas AD
        jax.jit(jax.grad(loss)).lower(x, x, x).compile()


@pytest.mark.parametrize("b,h,hkv,d,window,scale", [
    (2, 32, 4, 128, None, 1.0),
    (2, 32, 4, 128, 1024, 1.0),
    (1, 32, 8, 64, None, 1.0 / 64),
    (1, 64, 8, 128, 512, 1.0),
    (1, 48, 8, 128, None, 1.0),
    (1, 80, 40, 64, None, 0.125),
    (1, 80, 40, 64, 512, 0.125),
    (2, 16, 2, 256, None, 1.0),
], ids=["mellum2-full", "mellum2-window-1024", "granite4h",
        "laguna-xs2-window-512-64-heads", "laguna-xs2-full-48-heads-groups-of-6",
        "phi4flash-full-80-stacked-heads", "phi4flash-window-512-80-stacked-heads",
        "qwen3next-full-head-256-groups-of-8"])
def test_attention_core_compiles_for_v5e_at_the_cells_shapes(
        topo, b, h, hkv, d, window, scale):
    """ops/blockattn.py's fused kernel, forward and backward, at the eight
    call shapes of the language cells (T 8,192, bf16) and the tiles the shape
    test gives them: the tiles fit VMEM and the backward compiles. The kernel
    path is called outright: this process traces for the CPU."""
    from mgwfbp_tpu.ops import blockattn

    t = 8192
    one = SingleDeviceSharding(topo.devices[0])
    q = jax.ShapeDtypeStruct((b, t, h, d), jnp.bfloat16, sharding=one)
    kv = jax.ShapeDtypeStruct((b, t, hkv, d), jnp.bfloat16, sharding=one)
    tiles = blockattn._kernel_tiles(t, d, window)
    assert tiles is not None

    def loss(q, k, v):
        out = blockattn._fused(q, k, v, window, scale, tiles)
        return jnp.sum(out.astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile().as_text()
    # forward, dk/dv and dq (or the two of a fused backward)
    assert text.count("tpu_custom_call") >= 2
    # no float32 array of a whole head's scores leaves the kernel
    assert f"f32[{t},{t}]" not in text and f",{t},{t}]" not in text


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


def test_qwen3next_step_counts_three_rules_through_three_programs(
        topo, monkeypatch):
    """The Qwen3-Next cell's step (four layers, 32 of 512 experts, 18,992
    ids, two sequences of 8,192) traced and lowered for the described chip
    (said so by the test: `traced_for_tpu` asks the default backend, which
    is the CPU here; nothing is compiled: the kernels are above, and the
    whole step takes the chip's compiler two minutes): `delta_program` reads
    3 + 0 and 3, three layers sharing the inverse's, the forward's and the
    backward's program."""
    from mgwfbp_tpu.ops import blockattn

    monkeypatch.setattr(blockattn, "traced_for_tpu", lambda: True)
    mesh = Mesh(np.asarray(topo.devices[:1]), (DATA_AXIS,))
    model, meta = zoo.create_model(
        "qwen3next", num_classes=18992, layers_held=4, experts_held=(0, 32))
    tx = _imagenet_sgd()
    state, batch = _abstract_step_args(model, meta, tx, mesh, 2)
    batch["y"] = jax.ShapeDtypeStruct(
        batch["x"].shape, jnp.int32, sharding=batch["x"].sharding)
    step = make_train_step(
        model, meta, tx, mesh, None, compute_dtype=jnp.bfloat16, donate=True)
    text = step.lower(state, batch).as_text()
    assert step.delta_calls == {"kernel": 3, "plain": 0, "programs": 3}
    assert "gated_delta_rule_backward" in text


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


@pytest.mark.parametrize("m,k,n,groups", [
    (131072, 2304, 896, 16), (131072, 896, 2304, 16),
    (65536, 2048, 512, 32), (65536, 512, 2048, 32),
    (163840, 2048, 512, 32), (163840, 512, 2048, 32),
], ids=["mellum2-gate-up", "mellum2-down", "laguna-xs2-gate-up",
        "laguna-xs2-down", "qwen3next-gate-up", "qwen3next-down"])
def test_grouped_product_compiles_for_v5e_at_the_cells_shapes(
        topo, m, k, n, groups):
    """ops/groupmm.py's tiled kernel with both transposes at the sparse
    cells' call shapes (every assignment's row, bf16) and the tiles the shape
    test gives them: each of the three fits VMEM. The kernel path is called
    outright: this process traces for the CPU."""
    from mgwfbp_tpu.ops import groupmm

    one = SingleDeviceSharding(topo.devices[0])
    lhs = jax.ShapeDtypeStruct((m, k), jnp.bfloat16, sharding=one)
    rhs = jax.ShapeDtypeStruct((groups, k, n), jnp.bfloat16, sharding=one)
    sizes = jax.ShapeDtypeStruct((groups,), jnp.int32, sharding=one)
    tiles = groupmm._kernel_tiles(m, k, n, jnp.bfloat16)
    assert tiles is not None

    def loss(lhs, rhs, sizes):
        out = groupmm._kernel_product(lhs, rhs, sizes, tiles)
        return jnp.sum(out.astype(jnp.float32))

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        lhs, rhs, sizes).compile().as_text()
    assert text.count("tpu_custom_call") == 3  # the product, d lhs, d rhs
    assert "ragged-dot" not in text


# Qwen3-Next's k 10: a block's 256 x 10 scalars are no whole SMEM tiles, which
# Mosaic refuses ("not divisible by tiling"); the kernel pads them (PR 40)
CELLS = [(16384, 8, 2304, 896, 16), (8192, 8, 2048, 512, 32),
         (16384, 10, 2048, 512, 32)]
CELL_IDS = ["mellum2", "laguna-xs2", "qwen3next"]


@pytest.mark.parametrize("n,k,d,f,groups", CELLS, ids=CELL_IDS)
def test_row_permutations_compile_for_v5e_at_the_cells_shapes(
        topo, n, k, d, f, groups):
    """ops/rowperm.py's ways down on the chip with their transposes at the
    sparse cells' shapes (every assignment's row, bf16): `take_rows`, XLA's
    one gather from the (N, D) table, whose transpose is the combine kernel;
    and `combine_rows`' kernel, whose window fits VMEM, with the loop of
    block gathers as its transpose and no gather of all M rows. Called
    outright: this process traces for the CPU."""
    from mgwfbp_tpu.ops import rowperm

    one = SingleDeviceSharding(topo.devices[0])
    m = n * k
    src = jax.ShapeDtypeStruct((n, d), jnp.bfloat16, sharding=one)
    rows = jax.ShapeDtypeStruct((m, d), jnp.bfloat16, sharding=one)
    index = jax.ShapeDtypeStruct((m,), jnp.int32, sharding=one)
    weights = jax.ShapeDtypeStruct((n, k), jnp.float32, sharding=one)
    sizes = jax.ShapeDtypeStruct((groups,), jnp.int32, sharding=one)
    plan = rowperm._kernel_plan(n, k, d, groups, jnp.bfloat16)
    assert plan is not None

    def taken(src, order, inverse, sizes):
        out = rowperm._taken(src, order, inverse, sizes, plan, False)
        return jnp.sum(out.astype(jnp.float32))

    def combined(rows, order, inverse, weights, sizes):
        out = rowperm._combined(
            rows, order, inverse, weights, sizes, plan, False)
        return jnp.sum(out.astype(jnp.float32))

    whole = re.compile(rf"= \w+\[{m},{d}\]\S* gather\(")
    text = jax.jit(jax.value_and_grad(taken)).lower(
        src, index, index, sizes).compile().as_text()
    assert text.count("tpu_custom_call") == 1  # d src: the combine kernel
    assert len(whole.findall(text)) == 1  # the dispatch itself
    text = jax.jit(jax.value_and_grad(combined, argnums=(0, 3))).lower(
        rows, index, index, weights, sizes).compile().as_text()
    assert text.count("tpu_custom_call") == 1  # the value; d rows is a loop
    assert not whole.search(text)


@pytest.mark.parametrize("n,k,d,f,groups", CELLS, ids=CELL_IDS)
def test_held_experts_compiles_for_v5e_with_no_gather_from_all_rows(
        topo, monkeypatch, n, k, d, f, groups):
    """The whole expert block as the models call it (under `jax.checkpoint`),
    value and gradients, at the sparse cells' shapes, traced as for a TPU:
    its `tpu_custom_call`s are the three grouped products' (forward, again in
    the recomputation, and two transposes each) and the combine's (forward
    and as d `u`): 3 + 3 + 6 + 2, no `ragged-dot`, and the only `gather`s
    that produce an (M, D) array are the dispatch's own from the (N, D)
    table, forward and recomputed (the parent's program held six)."""
    from mgwfbp_tpu.models import mellum
    from mgwfbp_tpu.ops import blockattn

    monkeypatch.setattr(blockattn, "traced_for_tpu", lambda: True)
    one = SingleDeviceSharding(topo.devices[0])
    bf = jnp.bfloat16
    m = n * k

    def shape(dims, dtype=bf):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)

    def loss(u, weights, ws, idx):
        y, _, dropped = jax.checkpoint(
            mellum.held_experts, static_argnums=6)(u, idx, weights, *ws, 0)
        return jnp.sum(y.astype(jnp.float32)) + dropped

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        shape((n, d)), shape((n, k), jnp.float32),
        (shape((groups, d, f)), shape((groups, d, f)), shape((groups, f, d))),
        shape((n, k), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 3 + 3 + 6 + 2
    assert "ragged-dot" not in text
    assert 1 <= len(
        re.findall(rf"= \w+\[{m},{d}\]\S* gather\(", text)) <= 2
    memory = compiled.memory_analysis()
    assert (memory.temp_size_in_bytes + memory.argument_size_in_bytes
            < HBM_BYTES)


def _abstract_step_args(model, meta, tx, mesh, per_device_batch):
    """(state, batch) as ShapeDtypeStructs sharded the way the Trainer
    places them: state replicated, batch split over the data axis. A
    described device holds no array, so nothing is materialized."""
    example = jnp.zeros((1,) + tuple(meta.input_shape), meta.input_dtype)
    state = jax.eval_shape(
        lambda: create_train_state(jax.random.PRNGKey(0), model, example, tx)
    )
    rep = NamedSharding(mesh, P())
    state = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep), state
    )
    gb = per_device_batch * mesh.devices.size
    split = NamedSharding(mesh, P(None, DATA_AXIS))
    batch = {
        "x": jax.ShapeDtypeStruct(
            (1, gb) + tuple(meta.input_shape), meta.input_dtype,
            sharding=split,
        ),
        "y": jax.ShapeDtypeStruct((1, gb), jnp.int32, sharding=split),
    }
    return state, batch


def _imagenet_sgd():
    return make_optimizer(
        0.01, momentum=0.9, weight_decay=1e-4, lr_schedule="const",
        dataset="imagenet", num_batches_per_epoch=1,
    )[0]


def test_merged_allreduce_step_compiles_on_4_chip_mesh(topo):
    """The product's multi-chip program — jitted step + merged bucket
    collectives over a `data` mesh of four described chips — at CIFAR
    width so the compile stays in seconds."""
    mesh = Mesh(np.asarray(topo.devices).reshape(4), (DATA_AXIS,))
    model, meta = zoo.create_model("resnet20")
    tx = _imagenet_sgd()
    state, batch = _abstract_step_args(model, meta, tx, mesh, 32)
    n_leaves = len(jax.tree_util.tree_leaves(state.params))
    reducer = make_merged_allreduce(
        state.params, axis_name=DATA_AXIS, policy="mgwfbp",
        tb=[1.1e-5] * n_leaves, cost_model=lookup_alpha_beta("ici", 4),
    )
    step = make_train_step(
        model, meta, tx, mesh, reducer, compute_dtype=jnp.bfloat16,
        donate=True,
    )
    compiled = step.lower(state, batch).compile()
    assert "all-reduce" in compiled.as_text()
    assert reducer.schedule.num_groups >= 1


def test_resnet50_bf16_b128_step_fits_one_v5e_chip(topo):
    """chip_smoke's train phase, asked of the compiler: ResNet-50, bf16
    compute, per-chip batch 128, one chip, no reducer (the Trainer drops it
    at world size 1), donated state — compiles and fits 16 GB of HBM. The
    one long compile of this file (~40 s); tier-1 has the room for it."""
    mesh = Mesh(np.asarray(topo.devices[:1]), (DATA_AXIS,))
    model, meta = zoo.create_model("resnet50")
    tx = _imagenet_sgd()
    state, batch = _abstract_step_args(model, meta, tx, mesh, 128)
    step = make_train_step(
        model, meta, tx, mesh, None, compute_dtype=jnp.bfloat16, donate=True,
    )
    mem = step.lower(state, batch).compile().memory_analysis()
    need = mem.temp_size_in_bytes + mem.argument_size_in_bytes
    assert need < HBM_BYTES, f"{need / 1e9:.1f} GB does not fit one chip"


def _four_chip_mlp_step_text(topo):
    """Compiled text of a data-parallel step over four described chips:
    three dense layers wide enough that a gradient is worth hiding, `wfbp`
    (one all-reduce a leaf), bf16 compute. Compiles in seconds."""
    import flax.linen as nn

    from mgwfbp_tpu.models import ModelMeta

    width = 2048

    class MLP(nn.Module):
        @nn.compact
        def __call__(self, x, train=False):
            for _ in range(3):
                x = nn.relu(nn.Dense(width)(x))
            return nn.Dense(10)(x)

    mesh = Mesh(np.asarray(topo.devices).reshape(4), (DATA_AXIS,))
    model, meta = MLP(), ModelMeta("mlp", "synthetic", 10, (width,))
    tx = _imagenet_sgd()
    state, batch = _abstract_step_args(model, meta, tx, mesh, 64)
    n_leaves = len(jax.tree_util.tree_leaves(state.params))
    reducer = make_merged_allreduce(
        state.params, axis_name=DATA_AXIS, policy="wfbp",
        tb=[1e-3] * n_leaves, cost_model=lookup_alpha_beta("ici", 4),
    )
    step = make_train_step(
        model, meta, tx, mesh, reducer, compute_dtype=jnp.bfloat16,
        donate=True, health_stats=True,
    )
    assert reducer.schedule.num_groups == 8
    return step.lower(state, batch).compile().as_text()


@pytest.mark.parametrize("with_options", [True, False])
def test_four_chip_step_gets_asynchronous_allreduces(
    topo, monkeypatch, with_options
):
    """ISSUE 29, asked of the chip's compiler: a data-parallel step over
    four described chips is built with `ASYNC_COLLECTIVE_OPTIONS` (the
    mesh's reduction axis spans four TPU devices) and the compiled program
    then holds asynchronous all-reduces; built without them, as before
    PR 29, every all-reduce is synchronous. Either way there is one
    all-reduce per merge group and the metrics' own: the ordering token
    keeps the compiler's combiner off the buckets."""
    import mgwfbp_tpu.train.step as stepmod
    from mgwfbp_tpu.profiling import hlo_collective_counts

    mesh = Mesh(np.asarray(topo.devices).reshape(4), (DATA_AXIS,))
    assert stepmod.async_collective_options(mesh, (DATA_AXIS,)) == dict(
        stepmod.ASYNC_COLLECTIVE_OPTIONS
    )
    assert stepmod.async_collective_options(
        Mesh(np.asarray(topo.devices[:1]), (DATA_AXIS,)), (DATA_AXIS,)
    ) == {}
    if not with_options:
        monkeypatch.setattr(
            stepmod, "async_collective_options", lambda mesh, axes: {}
        )
    counts = hlo_collective_counts(_four_chip_mlp_step_text(topo))
    assert counts["collectives"] == 8 + 1
    if with_options:
        # the three 16 MB kernels' all-reduces at least; the biases' and the
        # metrics' scalar are too short to be worth a start/done pair
        assert counts["async_collectives"] >= 3
    else:
        assert counts["async_collectives"] == 0


def _gradient_kernels_fed_by_a_reduced_bucket(text):
    """(n, fed): of the compiled entry computation's `n` kernels that
    compute a gradient (a fusion under `transpose(jvp(...))/.../dot_general`)
    the names of those with an operand that depends on a reduced bucket (a
    gradient all-reduce's result or an async collective's `-done`). The
    state an `async-collective-start` hands to the kernels that carry its
    steps is the collective running, not a reduced value, and is not
    followed."""
    import re

    lines = text.split("\n")
    at = next(i for i, line in enumerate(lines) if line.startswith("ENTRY"))
    fed: dict[str, bool] = {}
    kernels, hit = 0, []
    for line in lines[at + 1:]:
        if line.startswith("}"):
            break
        m = re.match(r"^\s*(?:ROOT )?%([\w.\-]+) = .*? ([\w\-]+)\((.*)$", line)
        if m is None:
            continue
        name, opcode, rest = m.groups()
        op_name = re.search(r'op_name="([^"]*)"', line)
        op_name = op_name.group(1) if op_name else ""
        if name.startswith("async-collective-done") or (
            opcode in ("all-reduce", "all-reduce-done")
            and "mgwfbp_group" in op_name
        ):
            fed[name] = True
            continue
        if name.startswith("async-collective-start"):
            fed[name] = False
            continue
        operands = re.findall(r"%([\w.\-]+)", rest.split("), ")[0])
        fed[name] = any(fed.get(o, False) for o in operands)
        if (opcode == "fusion" and "transpose(jvp" in op_name
                and "dot_general" in op_name):
            kernels += 1
            if fed[name]:
                hit.append(name)
    return kernels, hit


def test_no_gradient_kernel_waits_for_a_reduced_bucket(topo, monkeypatch):
    """What the one-element token is for, read off the chip compiler's
    program: no kernel of the backward pass has an operand that depends on
    a reduced bucket, so an asynchronous all-reduce holds up nothing but
    the next all-reduce. Under the whole-bucket token (the parent's) the
    compiler fuses the token's add into the kernels that compute the
    weight gradients and they do wait (four-chip VGG-16: 15 of 31 gradient
    kernels, PERF.md, PR 29)."""
    from mgwfbp_tpu.parallel import allreduce

    kernels, hit = _gradient_kernels_fed_by_a_reduced_bucket(
        _four_chip_mlp_step_text(topo))
    assert kernels >= 6 and hit == []
    monkeypatch.setattr(allreduce, "_order_after", allreduce._chain_token)
    kernels, hit = _gradient_kernels_fed_by_a_reduced_bucket(
        _four_chip_mlp_step_text(topo))
    assert kernels >= 6 and len(hit) >= 2
