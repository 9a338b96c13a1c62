"""ops/rowperm.py. Its ways down on the chip (`combine_rows`' kernel, here in
`interpret` mode on the CPU, which is also `take_rows`' transpose; the loop of
block gathers that is `combine_rows`' transpose, as it is) against the plain
gathers: value and every gradient (`src`, `rows`, `weights`), with `valid` at
nothing, one row, a block's edge, a third of M and all of M, under a skew
that sends every token to one expert, in bfloat16 and float32, at Mellum 2's
and Laguna-XS.2's (D, k); the rows past `valid` poisoned with NaN on the way
in and in the cotangent. The kernel's lists against their definition.
`held_experts` that way against `held_experts` through the plain gathers. The
test of platform and shape that chooses between them."""

import collections
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mgwfbp_tpu.ops import groupmm, programs, rowperm

Case = collections.namedtuple("Case", "n k d groups experts held dtype")
EDGE = 1024  # the loop's block in the cases that stop at a block's edge
# `held`: how the assignments fall. A number: the share of them that lands on
# a held group (0: none, 1: all); "one_row": a single assignment is held;
# "block_edge": exactly one turn of the transpose's loop (1,024 rows); "skew": every
# token chooses the same k experts, of which two are held
CASES = {
    "mellum2_a_third": Case(256, 8, 2304, 4, 16, 0.35, "bfloat16"),
    "laguna_a_fifth": Case(256, 8, 2048, 8, 64, 0.2, "bfloat16"),
    "none_held": Case(512, 8, 256, 4, 16, 0.0, "bfloat16"),
    "one_row_held": Case(512, 8, 256, 4, 16, "one_row", "bfloat16"),
    "a_blocks_edge": Case(512, 8, 256, 4, 16, "block_edge", "bfloat16"),
    "float32_a_blocks_edge": Case(256, 4, 128, 4, 8, "block_edge", "float32"),
    "all_held": Case(256, 8, 256, 8, 8, 1.0, "bfloat16"),
    "skew_to_one_expert": Case(512, 8, 256, 4, 16, "skew", "bfloat16"),
    "float32_a_third": Case(512, 4, 128, 4, 16, 0.35, "float32"),
    "float32_all_held": Case(256, 4, 256, 4, 4, 1.0, "float32"),
    "float32_skew": Case(512, 4, 128, 2, 8, "skew", "float32"),
    # Qwen3-Next's k: a block's 256 x 10 scalars are no whole SMEM tiles
    "qwen3next_k10_a_fifth": Case(512, 10, 256, 4, 64, 0.2, "bfloat16"),
    "qwen3next_k10_all_held": Case(256, 10, 128, 16, 16, 1.0, "float32"),
}


def assignments(case: Case, key) -> jax.Array:
    """idx (n, k): the experts each token chose, distinct a token."""
    n, k, groups, experts = case.n, case.k, case.groups, case.experts
    if case.held == "skew":
        # experts 0 and 1 held, the token's other choices not
        chosen = jnp.concatenate([jnp.arange(2), groups + jnp.arange(k - 2)])
        return jnp.tile(chosen[None, :], (n, 1))
    if case.held in ("one_row", "block_edge"):
        idx = groups + jnp.argsort(
            jax.random.uniform(key, (n, experts - groups)), axis=1)[:, :k]
        if case.held == "one_row":
            return idx.at[n // 3, 2].set(1)
        # EDGE assignments held: every token's first choices, its held
        # experts distinct
        per = EDGE // n
        held = (jnp.arange(n)[:, None] + jnp.arange(per)[None, :]) % groups
        return idx.at[:, :per].set(held)
    share = float(case.held)
    logit = jnp.where(
        jnp.arange(experts) < groups, jnp.log(max(share, 1e-9) / groups),
        jnp.log(max(1 - share, 1e-9) / max(experts - groups, 1)))
    noise = jax.random.gumbel(key, (n, experts))
    return jnp.argsort(-(noise + logit[None, :]), axis=1)[:, :k]


@functools.lru_cache(maxsize=None)
def operands(name: str):
    case = CASES[name]
    dtype = jnp.dtype(case.dtype)
    keys = jax.random.split(jax.random.PRNGKey(len(name)), 6)
    idx = assignments(case, keys[0])
    held = idx < case.groups
    sort_keys = jnp.where(held, idx, case.groups).reshape(-1)
    order = jnp.argsort(sort_keys, stable=True).astype(jnp.int32)
    inverse = jnp.argsort(order).astype(jnp.int32)
    sizes = jnp.sum(
        sort_keys[:, None] == jnp.arange(case.groups)[None, :], axis=0,
        dtype=jnp.int32)
    m = case.n * case.k
    src = jax.random.normal(keys[1], (case.n, case.d)).astype(dtype)
    rows = jax.random.normal(keys[2], (m, case.d)).astype(dtype)
    weights = jax.random.uniform(keys[3], (case.n, case.k), jnp.float32)
    g_rows = jax.random.normal(keys[4], (m, case.d)).astype(dtype)
    g_y = jax.random.normal(keys[5], (case.n, case.d)).astype(dtype)
    plan = rowperm._kernel_plan(
        case.n, case.k, case.d, case.groups, dtype)
    assert plan is not None
    if case.held == "block_edge":
        plan = plan._replace(rows=EDGE)
    return dict(
        src=src, rows=rows, weights=weights, g_rows=g_rows, g_y=g_y,
        order=order, inverse=inverse, sizes=sizes, plan=plan,
        valid=int(jnp.sum(sizes)))


def poisoned(rows, valid: int):
    return jnp.where(jnp.arange(rows.shape[0])[:, None] < valid, rows, jnp.nan)


@functools.lru_cache(maxsize=None)
def both_ways(name: str):
    """{which: (the chip's way, plain gathers')} for one case, each way run
    once: the chip's way sees NaN in every row past `valid`, of `rows` and of
    the cotangent of `take_rows`' result; the plain gathers see zeros."""
    o = operands(name)
    order, inverse, sizes, valid = o["order"], o["inverse"], o["sizes"], o["valid"]

    def run(plan, interpret, rows, g_rows):
        taken, take_vjp = jax.vjp(
            lambda src: rowperm._taken(
                src, order, inverse, sizes, plan, interpret), o["src"])
        (d_src,) = take_vjp(g_rows)
        y, combine_vjp = jax.vjp(
            lambda rows, weights: rowperm._combined(
                rows, order, inverse, weights, sizes, plan, interpret),
            rows, o["weights"])
        d_rows, d_weights = combine_vjp(o["g_y"])
        assert taken.dtype == d_src.dtype == y.dtype == d_rows.dtype == rows.dtype
        assert d_weights.dtype == jnp.float32
        # rows past `valid` hold anything: never compared
        return {"taken": taken[:valid], "d_src": d_src, "combined": y,
                "d_rows": d_rows[:valid], "d_weights": d_weights}

    zeroed = lambda a: jnp.where(  # noqa: E731
        jnp.arange(a.shape[0])[:, None] < valid, a, 0)
    got = run(o["plan"], True, poisoned(o["rows"], valid),
              poisoned(o["g_rows"], valid))
    want = run(None, False, zeroed(o["rows"]), zeroed(o["g_rows"]))
    return {which: (np.asarray(got[which], np.float32),
                    np.asarray(want[which], np.float32)) for which in got}


@pytest.mark.parametrize(
    "which", ["taken", "d_src", "combined", "d_rows", "d_weights"])
@pytest.mark.parametrize("name", list(CASES))
def test_the_chips_way_against_the_plain_gathers(name, which):
    got, want = both_ways(name)[which]
    assert got.shape == want.shape
    assert np.isfinite(got).all()  # no poisoned row reached a result
    if which in ("taken", "d_rows"):
        # a copy, and a copy times a weight rounded once: the same bits
        assert np.array_equal(got, want)
        return
    # a float32 sum of at most k terms in the same order, rounded once
    eps = 2.0 ** -8 if CASES[name].dtype == "bfloat16" else 1e-5
    assert np.abs(got - want).max(initial=0.0) <= eps * np.abs(want).max(
        initial=0.0)


@pytest.mark.parametrize("name", list(CASES))
def test_the_cases_are_what_their_names_say(name):
    case, o = CASES[name], operands(name)
    m = case.n * case.k
    expected = {
        "one_row": (1, 1), "block_edge": (EDGE, EDGE),
        "skew": (2 * case.n, 2 * case.n), 0.0: (0, 0), 1.0: (m, m),
    }.get(case.held, (m // 8, m // 2))
    assert expected[0] <= o["valid"] <= expected[1]
    if case.held == "skew":
        assert sorted(np.asarray(o["sizes"]))[-2:] == [case.n, case.n]


def test_window_lists_name_every_row_an_assignment_needs():
    """The combine kernel's lists, against the definition: the chunk that
    holds an assignment's row is in its block's list at the window position
    the assignment is given, and no block lists more chunks than the plan
    has room for."""
    for name in CASES:
        o = operands(name)
        case, plan = CASES[name], o["plan"]
        chunks, count, where = (np.asarray(a) for a in rowperm._window_lists(
            o["inverse"].reshape(case.n, case.k), o["sizes"], plan))
        chunks = chunks.reshape(-1, plan.chunks)
        assert count.max(initial=0) <= plan.chunks
        index = np.asarray(o["inverse"])
        held = index < o["valid"]
        # an assignment in no group is given the window's rows of zeros
        assert ((where < plan.chunks * rowperm._CHUNK) == held).all()
        assert (where[~held] == plan.chunks * rowperm._CHUNK).all()
        block = np.arange(index.shape[0]) // (plan.tokens * case.k)
        slot, lane = np.divmod(where[held], rowperm._CHUNK)
        assert (slot < count[block[held]]).all()
        assert (chunks[block[held], slot] * rowperm._CHUNK + lane
                == index[held]).all()


def experts_block(d, f, experts, k, n, dtype, seed=0):
    """`held_experts`' arguments at a size the kernels' blocks divide."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    u = jax.random.normal(keys[0], (n, d)).astype(dtype)
    idx = jnp.argsort(
        jax.random.uniform(keys[1], (n, 4 * experts)), axis=1)[:, :k]
    weights = jax.nn.softmax(jax.random.normal(keys[2], (n, k)), axis=-1)
    scale = 1.0 / np.sqrt(d)
    ws = [(jax.random.normal(key, shape) * scale).astype(dtype)
          for key, shape in zip(keys[3:], ((experts, d, f), (experts, d, f),
                                           (experts, f, d)))]
    return u, idx.astype(jnp.int32), weights, ws


@functools.lru_cache(maxsize=None)
def held_experts_both_ways():
    """(through the kernel and the loop, through the plain gathers): value
    and gradients of one expert block, each way run once."""
    from mgwfbp_tpu.models import lm_parts

    u, idx, weights, ws = experts_block(256, 128, 4, 8, 256, jnp.bfloat16)

    def loss(u, weights, ws):
        y, sizes, dropped = lm_parts.held_experts(u, idx, weights, *ws, 0)
        return jnp.sum(y.astype(jnp.float32) * jnp.cos(
            jnp.arange(y.size).reshape(y.shape))), (y, dropped)

    def run():
        before = programs.LOWERED.copy()
        (_, (y, dropped)), (d_u, d_weights, d_ws) = jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True)(u, weights, ws)
        assert int(dropped) == 0
        made = programs.lowered_since(before)["rows"]
        return {"y": y, "d_u": d_u, "d_weights": d_weights,
                "d_w_down": d_ws[2]}, (
                    made["rows_held"], made["rows_all"], made["rows_programs"])

    want, ways = run()
    assert ways == (0, 2, 0)
    combine = rowperm._combine_kernel
    with pytest.MonkeyPatch.context() as patch:
        # the choice as on a TPU, the kernel interpreted; the grouped
        # products stay `lax.ragged_dot`, as on both sides here
        patch.setattr(programs, "traced_for_tpu", lambda: True)
        patch.setattr(groupmm, "_kernel_tiles", lambda *a: None)
        patch.setattr(
            rowperm, "_combine_kernel",
            lambda *a, interpret=False, **kw: combine(
                *a, interpret=True, **kw))
        got, ways = run()
    assert ways == (1, 1, 1)
    return got, want


@pytest.mark.parametrize("which", ["y", "d_u", "d_weights", "d_w_down"])
def test_held_experts_through_the_kernel_as_through_the_plain_gathers(which):
    """The whole expert block, value and gradients, with the combine's kernel
    (interpreted) and its transpose's loop where the plain gathers were; the
    grouped products stay `lax.ragged_dot` on both sides, whose rows past the
    last group are zero on the CPU."""
    got, want = held_experts_both_ways()
    a, b = np.asarray(got[which], np.float32), np.asarray(
        want[which], np.float32)
    assert np.isfinite(a).all()
    assert np.abs(a - b).max() <= 2.0 ** -7 * np.abs(b).max()


def traced_ways(fn, *args):
    """((moving only the rows held, moving all rows, kernel programs), the
    primitives of the traced program that tell the ways apart)."""
    before = programs.LOWERED.copy()
    text = str(jax.make_jaxpr(lambda *a: fn(*a))(*args))
    made = programs.lowered_since(before)["rows"]
    way = ("pallas_call" if "pallas_call" in text
           else "while" if "while" in text else "gather")
    return (made["rows_held"], made["rows_all"], made["rows_programs"]), way


def test_falls_back_to_the_plain_gathers_off_the_tpu_and_on_a_shape_that_misfits(
        monkeypatch):
    """The choice is read off the platform traced for and the shape: on this
    CPU both permutations are the plain gathers; traced as for a TPU, at a
    shape the blocks divide `combine_rows` is the kernel (and `take_rows`,
    XLA's gather there too, traces its transpose's kernel program under
    `eval_shape`), and a D that is no whole number of lane tiles, an N no
    block of tokens divides, an M no block of rows divides, integer rows, or
    a window VMEM cannot hold leave both plain."""

    def take(n, k, d, dtype=jnp.bfloat16, groups=4):
        index = jax.ShapeDtypeStruct((n * k,), jnp.int32)
        return traced_ways(
            rowperm.take_rows, jax.ShapeDtypeStruct((n, d), dtype), index,
            index, jax.ShapeDtypeStruct((groups,), jnp.int32))

    def combine(n, k, d, dtype=jnp.bfloat16, groups=4):
        index = jax.ShapeDtypeStruct((n * k,), jnp.int32)
        return traced_ways(
            rowperm.combine_rows, jax.ShapeDtypeStruct((n * k, d), dtype),
            index, index, jax.ShapeDtypeStruct((n, k), jnp.float32),
            jax.ShapeDtypeStruct((groups,), jnp.int32))

    plain = ((0, 1, 0), "gather")
    assert not programs.traced_for_tpu()
    assert take(256, 8, 256) == combine(256, 8, 256) == plain
    monkeypatch.setattr(programs, "traced_for_tpu", lambda: True)
    # `take_rows` is XLA's gather there too; its transpose's kernel program
    # is traced with it (under `eval_shape`) and counted
    assert take(256, 8, 256) == ((0, 1, 1), "gather")
    assert combine(256, 8, 256) == ((1, 0, 1), "pallas_call")
    assert take(256, 8, 128, jnp.float32) == ((0, 1, 1), "gather")
    assert combine(256, 8, 128, jnp.float32) == ((1, 0, 1), "pallas_call")
    for misfit in (
            (256, 8, 200),  # D: no whole number of lane tiles
            (192, 8, 256),  # N: no whole block of tokens
            (256 * 33, 1, 256),  # M: no whole block of rows
            (256, 8, 256, jnp.int32),
            (256, 8, 256, jnp.bfloat16, 2048),  # windows of 138 MB
    ):
        assert take(*misfit) == combine(*misfit) == plain


@pytest.mark.parametrize("n,k,d,groups", [
    (16384, 8, 2304, 16), (8192, 8, 2048, 32), (16384, 10, 2048, 32)],
    ids=["mellum2", "laguna_xs2", "qwen3next"])
def test_the_cells_shapes_take_the_chips_way(n, k, d, groups):
    plan = rowperm._kernel_plan(n, k, d, groups, jnp.bfloat16)
    assert plan is not None
    assert (n * k) % plan.rows == 0 and n % plan.tokens == 0
    assert plan.chunks * rowperm._CHUNK >= plan.tokens * k
