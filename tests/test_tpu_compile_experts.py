"""Ask the TPU's compiler, without a chip: the experts' grouped products,
their row permutations and the whole of `held_experts`.

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

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

HBM_BYTES = 16 * 1024**3  # one v5e chip

pytestmark = pytest.mark.usefixtures("_compile_cache_off")


@pytest.mark.parametrize("m,k,n,groups", [
    (131072, 2304, 896, 16), (131072, 896, 2304, 16),
    (65536, 2048, 512, 32), (65536, 512, 2048, 32),
    (163840, 2048, 512, 32), (163840, 512, 2048, 32),
    # 8,192 tokens x the 8 experts held of the 22 a token chooses (the rows
    # that can be in a group: `lm_parts._grouped_experts`); N 2,688 is 21
    # lane tiles, which `_fitted` halves to 1,408 with a remainder tile of
    # 1,280
    (65536, 1024, 2688, 8), (65536, 2688, 1024, 8),
], ids=["mellum2-gate-up", "mellum2-down", "laguna-xs2-gate-up",
        "laguna-xs2-down", "qwen3next-gate-up", "qwen3next-down",
        "nemotron3s-up-from-the-latent", "nemotron3s-down-to-the-latent"])
def test_grouped_product_compiles_for_v5e_at_the_cells_shapes(
        topo, m, k, n, groups):
    """ops/groupmm.py's tiled kernel with both transposes at the sparse
    cells' call shapes (a row for every assignment that can be held, bf16)
    and the tiles the shape test gives them: each of the three fits VMEM.
    The kernel path is called outright: this process traces for the CPU."""
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
# Nemotron 3 Super's k 22 over 8 experts held, in the latent of 1,024: the
# permutations are called at min(k, held) = 8 choices a token, 65,536 rows
CELLS = [(16384, 8, 2304, 896, 16), (8192, 8, 2048, 512, 32),
         (16384, 10, 2048, 512, 32), (8192, 22, 1024, 2688, 8)]
CELL_IDS = ["mellum2", "laguna-xs2", "qwen3next", "nemotron3s"]


@pytest.mark.parametrize("n,k,d,f,groups", CELLS, ids=CELL_IDS)
def test_row_permutations_compile_for_v5e_at_the_cells_shapes(
        topo, n, k, d, f, groups):
    """ops/rowperm.py's ways down on the chip with their transposes at the
    sparse cells' shapes (a row for every assignment that can be held,
    bf16): `take_rows`, XLA's one gather from the (N, D) table, whose
    transpose is the combine kernel; and `combine_rows`' kernel, whose
    window fits VMEM, with the loop of block gathers as its transpose and no
    gather of all M rows. Called outright: this process traces for the
    CPU."""
    from mgwfbp_tpu.ops import rowperm

    one = SingleDeviceSharding(topo.devices[0])
    k = min(k, groups)  # as `lm_parts._grouped_experts` calls them
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
    the recomputation, and two transposes each; two products where the
    experts have no gate: `held_relu2_experts` at k 22) and the combine's
    (forward and as d `u`): 3 + 3 + 6 + 2, no `ragged-dot`, and the only
    `gather`s
    that produce an (M, D) array are the dispatch's own from the (N, D)
    table, forward and recomputed (the parent's program held six). M is N x
    min(k, experts held): at k 22 over 8 held no array of the N x 22 =
    180,224 rows is left in the compiled text."""
    from mgwfbp_tpu.models import lm_parts
    from mgwfbp_tpu.ops import programs

    monkeypatch.setattr(programs, "traced_for_tpu", lambda: True)
    one = SingleDeviceSharding(topo.devices[0])
    bf = jnp.bfloat16
    m = n * min(k, groups)

    def shape(dims, dtype=bf):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)

    products = 2 if k == 22 else 3  # experts of relu(u W_up)^2 W_down
    experts = lm_parts.held_experts if products == 3 \
        else lm_parts.held_relu2_experts

    def loss(u, weights, ws, idx):
        y, _, dropped, *_ = jax.checkpoint(
            experts, static_argnums=3 + products)(u, idx, weights, *ws, 0)
        return jnp.sum(y.astype(jnp.float32)) + dropped

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        shape((n, d)), shape((n, k), jnp.float32),
        (*(shape((groups, d, f)) for _ in range(products - 1)),
         shape((groups, f, d))),
        shape((n, k), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 4 * products + 2
    assert "ragged-dot" not in text
    assert 1 <= len(
        re.findall(rf"= \w+\[{m},{d}\]\S* gather\(", text)) <= 2
    if k > groups:
        assert not re.search(rf"\[{n * k}[,\]]", text)
    memory = compiled.memory_analysis()
    assert (memory.temp_size_in_bytes + memory.argument_size_in_bytes
            < HBM_BYTES)
