"""ops/groupmm.py. Its tiled kernel (the installed megablox `gmm` / `tgmm`
under this module's custom VJP, here in `interpret` mode on the CPU) against
a per-group dense product in float32: forward, d lhs and d rhs, at group
sizes no tile divides, with empty and one-row groups, with the rows past the
last group poisoned, at both sparse cells' (K, N). The test of platform and
shape that chooses between the kernel and `lax.ragged_dot`; and the count of
distinct kernel programs in a module lowered for a TPU. (The count a step
program leaves on the telemetry is held where a Trainer runs each preset
anyway: tests/test_<family>_trainer.py, tests/test_telemetry.py.)"""

import collections
import functools
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mgwfbp_tpu.ops import groupmm, programs

Case = collections.namedtuple(
    "Case", "m k n sizes dtype tiles", defaults=(None,))
CASES = {
    # a group of one row, an empty group, and 303 rows past the last group
    "mellum2_gate_up": Case(1024, 2304, 896, (300, 1, 0, 420), "bfloat16"),
    # tiles the entry point does not choose: K 2,304 over a tile of 1,024
    # leaves a remainder tile of 256 in the product and in d rhs, and d lhs's
    # output has one; then the same with K and N changing places
    "k_with_a_remainder_tile": Case(
        1024, 2304, 896, (5, 600, 0, 100), "bfloat16", groupmm.Tiles(
            product=(512, 1024, 896), d_lhs=(512, 896, 1024),
            d_rhs=(512, 1024, 896))),
    "n_with_a_remainder_tile": Case(
        1024, 896, 2304, (5, 600, 0, 100), "bfloat16", groupmm.Tiles(
            product=(512, 896, 1024), d_lhs=(512, 1024, 896),
            d_rhs=(512, 896, 1024))),
    "mellum2_down": Case(1024, 896, 2304, (0, 511, 2, 200), "bfloat16"),
    "laguna_gate_up": Case(1024, 2048, 512, (130, 0, 0, 57, 1, 300, 7, 129),
                           "bfloat16"),
    "laguna_down": Case(1024, 512, 2048, (64, 64, 64, 64, 100, 1, 0, 3),
                        "bfloat16"),
    # float32 operands: the masks and the walk over the tiles, to rounding
    "empty_groups_only_at_the_ends": Case(
        1024, 256, 128, (0, 0, 513, 5, 0), "float32"),
    "one_row_groups": Case(512, 128, 256, (1, 1, 1, 1, 1, 1), "float32"),
    "no_tile_divides_a_group": Case(1536, 384, 128, (517, 3, 611, 299),
                                    "float32"),
    "every_row_in_a_group": Case(1024, 128, 128, (1, 510, 2, 511), "float32"),
    "no_row_in_a_group": Case(512, 128, 128, (0, 0, 0), "float32"),
}


def dense_by_group(lhs, rhs, g, sizes):
    """The product and both transposes, a group at a time, in float32; rows
    past the last group are zero."""
    lhs, rhs, g = (np.asarray(a, np.float32) for a in (lhs, rhs, g))
    out = np.zeros((lhs.shape[0], rhs.shape[2]), np.float32)
    d_lhs, d_rhs = np.zeros_like(lhs), np.zeros_like(rhs)
    start = 0
    for i, size in enumerate(sizes):
        rows = slice(start, start + size)
        out[rows] = lhs[rows] @ rhs[i]
        d_lhs[rows] = g[rows] @ rhs[i].T
        d_rhs[i] = lhs[rows].T @ g[rows]
        start += size
    return {"product": out, "d_lhs": d_lhs, "d_rhs": d_rhs}


@functools.lru_cache(maxsize=None)
def kernel_against_dense(name: str):
    """{which: (got, want)} for one case, the kernel run once. The rows past
    the last group hold NaN on the way in and in the cotangent."""
    case = CASES[name]
    dtype = jnp.dtype(case.dtype)
    sizes = jnp.asarray(case.sizes, jnp.int32)
    held = sum(case.sizes)
    keys = jax.random.split(jax.random.PRNGKey(len(name)), 3)
    lhs = jax.random.normal(keys[0], (case.m, case.k)).astype(dtype)
    rhs = (jax.random.normal(keys[1], (len(case.sizes), case.k, case.n))
           / np.sqrt(case.k)).astype(dtype)
    g = jax.random.normal(keys[2], (case.m, case.n)).astype(dtype)
    want = dense_by_group(lhs, rhs, g, case.sizes)
    past = jnp.arange(case.m)[:, None] >= held
    lhs, g = jnp.where(past, jnp.nan, lhs), jnp.where(past, jnp.nan, g)
    tiles = case.tiles or groupmm._kernel_tiles(
        case.m, case.k, case.n, dtype)
    assert tiles is not None
    out, vjp = jax.vjp(
        lambda a, b: groupmm._kernel_product(a, b, sizes, tiles, True),
        lhs, rhs)
    d_lhs, d_rhs = vjp(g)
    assert out.dtype == d_lhs.dtype == d_rhs.dtype == dtype
    got = {"product": out, "d_lhs": d_lhs, "d_rhs": d_rhs}
    # rows past the last group hold anything: never compared
    return {
        which: (np.asarray(a, np.float32)[:held] if which != "d_rhs"
                else np.asarray(a, np.float32),
                want[which][:held] if which != "d_rhs" else want[which])
        for which, a in got.items()}


@pytest.mark.parametrize("which", ["product", "d_lhs", "d_rhs"])
@pytest.mark.parametrize("name", list(CASES))
def test_kernel_against_a_dense_product_a_group(name, which):
    got, want = kernel_against_dense(name)[which]
    assert np.isfinite(got).all()  # no poisoned row reached a row in a group
    # one rounding of a float32 sum to the operands' dtype
    eps = 2.0 ** -8 if CASES[name].dtype == "bfloat16" else 1e-5
    scale = np.abs(want).max(initial=0.0)
    assert np.abs(got - want).max(initial=0.0) <= eps * scale
    if which == "d_rhs":  # an empty group's block is zero, not left alone
        for i, size in enumerate(CASES[name].sizes):
            if size == 0:
                assert not got[i].any()


def lowered_since(before):
    """The experts' record: the grouped products' fields, the rows' and the
    expert blocks'."""
    made = programs.lowered_since(before)
    return {**made["experts"], **made["rows"], **made["groups"]}


def traced_ways(fn, *args):
    before = programs.LOWERED.copy()
    # a fresh function each time: a cached trace calls nothing and counts none
    jaxpr = jax.make_jaxpr(lambda *a: fn(*a))(*args)
    return lowered_since(before), "pallas_call" in str(jaxpr)


def test_falls_back_to_ragged_dot_off_the_tpu_and_on_a_shape_that_misfits(
        monkeypatch):
    """The choice is read off the platform traced for and the shape: on this
    CPU every call is `lax.ragged_dot`; traced as for a TPU, a shape the tiles
    divide takes the kernel, and rows the row tile does not divide, a K or N
    that is no whole number of lane tiles, or integer operands do not."""
    sizes = jnp.asarray([5, 7], jnp.int32)

    def product(lhs, rhs):
        return groupmm.grouped_product(lhs, rhs, sizes)

    def operands(m, k, n, dtype=jnp.bfloat16):
        return jnp.ones((m, k), dtype), jnp.ones((2, k, n), dtype)

    def ways(kernel, ragged, programs):  # no row permutation beside them
        return {"kernel": kernel, "ragged": ragged, "programs": programs,
                "rows_held": 0, "rows_all": 0, "rows_programs": 0,
                "bounded": 0, "whole": 0}

    ragged = (ways(0, 1, 0), False)
    assert not programs.traced_for_tpu()
    assert traced_ways(product, *operands(512, 128, 128)) == ragged
    monkeypatch.setattr(programs, "traced_for_tpu", lambda: True)
    # K = N: the product and d lhs are one program, d rhs another
    assert traced_ways(product, *operands(512, 128, 128)) == (
        ways(1, 0, 2), True)
    assert traced_ways(product, *operands(512, 256, 128)) == (
        ways(1, 0, 3), True)
    assert traced_ways(product, *operands(384, 128, 128)) == ragged
    assert traced_ways(product, *operands(512, 96, 128)) == ragged
    assert traced_ways(product, *operands(512, 128, 200)) == ragged
    assert traced_ways(product, *operands(512, 128, 128, jnp.int8)) == ragged


def expert_blocks(layers: int):
    """Forward + gradient of `layers` checkpointed expert blocks as the
    models call them, at a size the products' tiles and the permutations'
    blocks divide: 256 tokens, 8 choices each over 4 experts held: the 256 x
    4 = 1,024 rows that can be in a group."""
    from mgwfbp_tpu.models import lm_parts

    tokens, d, f, experts, k = 256, 256, 128, 4, 8
    bf = jnp.bfloat16
    shape = jax.ShapeDtypeStruct

    def loss(ws, u, idx, weights):
        for w_gate, w_up, w_down in ws:
            y, _, _ = programs.counted(jax.checkpoint(
                lm_parts.held_experts, static_argnums=6))(
                    u, idx, weights, w_gate, w_up, w_down, 0)
            u = u + y.astype(u.dtype)
        return jnp.sum(u.astype(jnp.float32))

    ws = [(shape((experts, d, f), bf), shape((experts, d, f), bf),
           shape((experts, f, d), bf)) for _ in range(layers)]
    args = (ws, shape((tokens, d), bf), shape((tokens, k), jnp.int32),
            shape((tokens, k), jnp.float32))
    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1))), args


def test_four_layers_lower_no_more_kernel_programs_than_one(monkeypatch):
    """Lowered for a TPU, here: the distinct `tpu_custom_call` programs of
    four expert blocks, forward + gradient, are those of one block, and no
    more than the entry points' docstrings state (four of the grouped
    products, two of the row permutations round them); every product and
    every permutation of every layer is counted all the same."""
    from mgwfbp_tpu.ops import rowperm

    monkeypatch.setattr(programs, "traced_for_tpu", lambda: True)
    # the docstrings' own numbers, so that the two cannot part
    assert "at most FOUR distinct programs" in groupmm.__doc__
    assert "**One kernel program a step.**" in rowperm.__doc__
    stated = 4, 1
    seen = {}
    for layers in (1, 4):
        fn, args = expert_blocks(layers)
        before = programs.LOWERED.copy()
        text = fn.trace(*args).lower(lowering_platforms=("tpu",)).as_text()
        assert "ragged_dot" not in text
        counted = lowered_since(before)
        assert (counted["kernel"], counted["ragged"]) == (3 * layers, 0)
        # a layer's combine through its kernel; its dispatch is XLA's gather
        assert (counted["rows_held"], counted["rows_all"]) == (layers, layers)
        assert (counted["bounded"], counted["whole"]) == (layers, 0)
        distinct = {
            hashlib.sha256(config.encode()).hexdigest() for config in
            re.findall(r'backend_config = "([^"]*)"', text)}
        sites = text.count("stablehlo.custom_call @tpu_custom_call")
        assert len(distinct) == (
            counted["programs"] + counted["rows_programs"])
        seen[layers] = (
            counted["programs"], counted["rows_programs"], sites)
    assert seen[1] == seen[4]
    assert 0 < seen[1][0] <= stated[0] and 0 < seen[1][1] <= stated[1]
