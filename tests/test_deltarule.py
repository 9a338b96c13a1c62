"""ops/deltarule.py against the literal recurrence S' = exp(g_t) S_{t-1},
S_t = S' + k_t (beta_t (v_t - S'^T k_t))^T, o_t = S_t^T q_t, one position at a
time, down both of its ways: the plain chunked form (what `gated_delta_rule`
is on the CPU) and the three kernels with a tile's matrices and the state in
VMEM (Pallas `interpret` mode, called outright). Outputs, final state and all
five inputs' gradients; T a multiple of the chunk and of the block and not,
one block of positions and several; key heads that serve one, two and four
value heads; decays near 0 and near -20 a token; bfloat16 inputs within a
stated tolerance; a cotangent on the final state alone; the inverse of both
ways against the triangular solve; each of the rule's parts shown to matter
(beta, the erasure); and the test of platform and shape that chooses between
the two, with what each call notes (`ops/programs.py`)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mgwfbp_tpu.ops import deltarule, programs
from mgwfbp_tpu.ops.deltarule import gated_delta_rule

HI = jax.lax.Precision.HIGHEST
ALL = (0, 1, 2, 3, 4)


def literal(q, k, v, g, beta, erase=True):
    """(o (B, T, H, V), final state (B, H, K, V)) by the recurrence; a key
    head serves H / Hk adjacent value heads."""
    bsz, t, hk, dk = q.shape
    h, dv = v.shape[2], v.shape[3]
    q, k = (jnp.repeat(x, h // hk, axis=2) for x in (q, k))

    def step(s, inp):
        qt, kt, vt, gt, bt = inp  # (B, H, K) x 2, (B, H, V), (B, H) x 2
        s = jnp.exp(gt)[..., None, None] * s
        held = jnp.einsum("bhkv,bhk->bhv", s, kt, precision=HI)
        u = bt[..., None] * (vt - held if erase else vt)
        s = s + jnp.einsum("bhk,bhv->bhkv", kt, u, precision=HI)
        return s, jnp.einsum("bhkv,bhk->bhv", s, qt, precision=HI)

    s, o = jax.lax.scan(step, jnp.zeros((bsz, h, dk, dv)), tuple(
        jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), s


def draws(seed, t, bsz=2, hk=2, h=4, dk=6, dv=5, decay=1.0):
    key = jax.random.split(jax.random.PRNGKey(seed), 5)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(jax.random.normal(key[0], (bsz, t, hk, dk))) * dk ** -0.5
    k = unit(jax.random.normal(key[1], (bsz, t, hk, dk)))
    v = jax.random.normal(key[2], (bsz, t, h, dv))
    g = -decay * jax.nn.softplus(jax.random.normal(key[3], (bsz, t, h)))
    beta = jax.nn.sigmoid(jax.random.normal(key[4], (bsz, t, h)))
    return q, k, v, g, beta


# what `draws` makes for a path: the kernels take keys and values of one lane
# tile a head, and eight (key head, value head) pairs a step of the inverse
WIDTHS = {"plain": dict(hk=2, h=4), "kernel": dict(
    bsz=1, hk=4, h=8, dk=128, dv=128)}


def rule(path, sizes):
    """`gated_delta_rule`'s signature down one way: the chunked form at
    `sizes` = (chunk, block), or the kernels, interpreted, at (chunk,
    positions a block)."""
    if path == "plain":
        return lambda *x: gated_delta_rule(*x, chunk=sizes[0], block=sizes[1])
    return lambda *x: deltarule._kernel_rule(*x, *sizes, True)


def weighted(fn, args, seed=9):
    """Scalar of fn's outputs under fixed random weights, so that one
    gradient exercises o and the final state together."""
    o, s = fn(*args)
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return (jnp.sum(o.astype(jnp.float32) * jax.random.normal(k1, o.shape))
            + jnp.sum(s * jax.random.normal(k2, s.shape)))


def both(args, fn):
    """((o, state, five gradients) of fn, of the recurrence)."""
    with jax.default_matmul_precision("highest"):
        got = (*fn(*args), *jax.grad(
            lambda *x: weighted(fn, x), argnums=ALL)(*args))
        want = (*literal(*args), *jax.grad(
            lambda *x: weighted(literal, x), argnums=ALL)(*args))
    return got, want


def agree(got, want, path="plain"):
    """rtol 1e-4 and atol 2e-5 down the plain form, as before there were
    kernels. The kernels' heads are 128 wide and their gradients reach 40:
    there the 2e-5 is of each array's own scale where that is above one."""
    for a, b in zip(got, want):
        scale = float(jnp.abs(b).max()) if path == "kernel" else 1.0
        np.testing.assert_allclose(
            a, b, rtol=1e-4, atol=2e-5 * max(1.0, scale))


@pytest.mark.parametrize("path,t,sizes", [
    ("plain", 32, (8, 2)),   # whole chunks, two blocks of two
    ("plain", 29, (8, 8)),   # a short last chunk, one block
    ("plain", 40, (16, 2)),  # three chunks: the block shrinks to one that divides
    ("plain", 7, (16, 4)),   # shorter than one chunk
    ("plain", 48, (8, 4)),   # six chunks: blocks of three
    ("kernel", 256, (64, 128)),  # two blocks of one tile of two chunks
    ("kernel", 256, (64, 256)),  # one block of two tiles: the first is the last
    ("kernel", 128, (32, 128)),  # four chunks a tile
])
def test_float32_matches_the_recurrence_forward_state_and_five_gradients(
        path, t, sizes):
    args = draws(t, t, **WIDTHS[path])
    got, want = both(args, rule(path, sizes))
    (bsz, _, h, dv), dk = args[2].shape, args[0].shape[3]
    assert got[0].shape == args[2].shape and got[1].shape == (bsz, h, dk, dv)
    agree(got, want, path)


@pytest.mark.parametrize("path,heads,sizes", [
    ("plain", dict(hk=4, h=4), (8, 2)), ("plain", dict(hk=1, h=4), (8, 2)),
    ("kernel", dict(hk=8, h=8), (64, 128)),
    ("kernel", dict(hk=2, h=8), (64, 128))])
def test_one_key_head_for_every_value_head_and_a_group_of_four(
        path, heads, sizes):
    args = draws(3, 24 if path == "plain" else 128, **{
        **WIDTHS[path], **heads})
    agree(*both(args, rule(path, sizes)), path)
    with pytest.raises(ValueError, match="do not divide"):
        gated_delta_rule(*draws(3, 8, hk=3), chunk=8)


@pytest.mark.parametrize("decay", [1e-4, 20.0], ids=["near-0", "near-minus-20"])
@pytest.mark.parametrize("path,sizes", [
    ("plain", (64, 1)), ("kernel", (64, 128))])
def test_decays_near_zero_and_near_minus_twenty_a_token(path, sizes, decay):
    """g near 0: nothing is forgotten and the erasure carries the whole
    rule. g near -20 a token: a chunk's sum passes -1,000, exp(-G_j) is inf
    in float32 and exp(G_i) * exp(-G_j) nan; the difference form is exact to
    rounding, forward and backward, with no overflow and no NaN."""
    args = draws(5, 128, decay=decay, **WIDTHS[path])
    if decay > 1:
        bsz, _, h = args[3].shape
        cum = jnp.cumsum(args[3].reshape(bsz, 2, 64, h), axis=2)
        assert float(cum.min()) < -500
        assert not bool(jnp.all(jnp.isfinite(jnp.exp(-cum))))
    got, want = both(args, rule(path, sizes))
    assert all(bool(jnp.all(jnp.isfinite(a))) for a in got)
    agree(got, want, path)


@pytest.mark.parametrize("path,t,sizes", [
    ("plain", 48, (16, 2)), ("kernel", 128, (64, 128))])
def test_bfloat16_inputs_keep_float32_decays_solve_and_state(path, t, sizes):
    """bf16 q, k, v (g and beta stay float32, as the mixer hands them over)
    against the float32 recurrence on the same rounded inputs: the products'
    operands are rounded to bf16 (2^-9 relative each), accumulation, decays,
    solve and state are float32, so outputs agree to a few per cent of their
    scale."""
    q, k, v, g, beta = draws(11, t, **WIDTHS[path])
    lo = [x.astype(jnp.bfloat16) for x in (q, k, v)]
    fn = jax.jit(rule(path, sizes))
    o, s = fn(*lo, g, beta)
    assert o.dtype == jnp.bfloat16 and s.dtype == jnp.float32
    want_o, want_s = literal(*(x.astype(jnp.float32) for x in lo), g, beta)
    assert float(jnp.abs(o.astype(jnp.float32) - want_o).max()) \
        < 0.03 * float(jnp.abs(want_o).max())
    assert float(jnp.abs(s - want_s).max()) \
        < 0.03 * float(jnp.abs(want_s).max())
    grads = jax.jit(jax.grad(lambda *x: jnp.sum(
        fn(*x)[0].astype(jnp.float32)), argnums=ALL))(*lo, g, beta)
    assert [x.dtype for x in grads] == [jnp.bfloat16] * 3 + [jnp.float32] * 2
    assert all(bool(jnp.all(jnp.isfinite(x))) for x in grads)


@pytest.mark.parametrize("path,t,sizes", [
    ("plain", 24, (8, 2)), ("kernel", 256, (64, 128))])
def test_a_cotangent_on_the_final_state_alone(path, t, sizes):
    """The loss reads the state after the last position and nothing of o:
    every gradient then comes from dS entering after the last position, and
    q, which only o reads, gets none."""
    args = draws(13, t, **WIDTHS[path])
    h, dv = args[2].shape[2:]
    w = jax.random.normal(
        jax.random.PRNGKey(2), (args[0].shape[0], h, args[0].shape[3], dv))

    def of(fn):
        with jax.default_matmul_precision("highest"):
            return jax.grad(
                lambda *x: jnp.sum(fn(*x)[1] * w), argnums=ALL)(*args)

    got, want = of(rule(path, sizes)), of(literal)
    assert float(jnp.abs(got[0]).max()) == 0 == float(jnp.abs(want[0]).max())
    agree(got, want, path)
    assert all(float(jnp.abs(x).max()) > 0 for x in got[1:])


def test_the_kernel_saves_the_state_each_block_starts_from():
    """Two blocks of 128 positions: what the forward kernel hands the
    backward one is the literal recurrence's state before each block's first
    position, zero before the first."""
    args = draws(17, 256, **WIDTHS["kernel"])
    with jax.default_matmul_precision("highest"):
        o, last, starts = deltarule._forward_kernel(
            *args, chunk=64, rows=128, interpret=True)
        _, want = literal(*(x[:, :128] for x in args))
        _, want_last = literal(*args)
    assert starts.shape == (1, 2, 8, 128, 128)
    np.testing.assert_array_equal(starts[:, 0], 0.0)
    np.testing.assert_allclose(starts[:, 1], want, rtol=1e-4, atol=2e-5)
    np.testing.assert_allclose(last, want_last, rtol=1e-4, atol=2e-5)


def _all_but_equal_keys(shape, same):
    k = jax.random.normal(jax.random.PRNGKey(shape[-2]), shape)
    if same:
        k = k[..., :1, :] + 0.05 * k
    return k / jnp.linalg.norm(k, axis=-1, keepdims=True)


def test_the_inverse_by_blocks_against_the_triangular_solve():
    """(I + A)^-1 by blocks against `solve_triangular`, on keys that are all
    but equal with beta one (the entries of A near one, the inverse's terms
    as large as they get) and on random ones, at 8, 16 and 64 rows."""
    from jax.scipy.linalg import solve_triangular

    for c, same in ((8, False), (16, True), (64, False), (64, True)):
        k = _all_but_equal_keys((3, c, 12), same)
        a = jnp.tril(jnp.einsum("bik,bjk->bij", k, k), -1)
        with jax.default_matmul_precision("highest"):
            got = deltarule._unit_lower_inverse(a)
            want = solve_triangular(
                jnp.eye(c) + a, jnp.broadcast_to(jnp.eye(c), a.shape),
                lower=True, unit_diagonal=True)
        scale = float(jnp.abs(want).max())
        assert float(jnp.abs(got - want).max()) < 2e-5 * scale, (c, same)
        assert float(jnp.abs(jnp.triu(got, 1)).max()) == 0.0
    with pytest.raises(ValueError, match="no power of two"):
        gated_delta_rule(*draws(0, 24), chunk=12)


@pytest.mark.parametrize("same", [False, True], ids=["random", "all-but-equal"])
def test_the_inverse_kernel_against_the_triangular_solve(same):
    """The kernels' (I + A)^-1, forward substitution a row at a time with
    the systems along the lanes, against `solve_triangular` at the plain
    inverse's tolerance: chunks of 64, g zero and beta one (A is the keys'
    Gram matrix under its diagonal), keys random and all but equal; two
    tiles of eight (key head, value head) pairs, each pair's two chunks
    side by side."""
    from jax.scipy.linalg import solve_triangular

    t, hk, c = 256, 8, 64
    k = _all_but_equal_keys((1, hk, t // c, c, 128), same)  # a chunk's alike
    k = k.transpose(0, 2, 3, 1, 4).reshape(1, t, hk, 128)
    vec = deltarule._decay_vectors(
        jnp.zeros((1, t, hk)), jnp.ones((1, t, hk)), c, hk)
    got = deltarule._inverse_kernel(k, vec, chunk=c, interpret=True)
    assert got.shape == (1, hk, 1, t // 128, c, 128)
    # (B, Hk, r, tiles, i, (m, j)) -> (B, Hk, chunks, i, j)
    got = got[:, :, 0].reshape(1, hk, t // 128, c, 2, c).transpose(
        0, 1, 2, 4, 3, 5).reshape(1, hk, t // c, c, c)
    kc = k.reshape(1, t // c, c, hk, 128)
    with jax.default_matmul_precision("highest"):
        a = jnp.tril(jnp.einsum("bnihd,bnjhd->bhnij", kc, kc), -1)
        want = solve_triangular(
            jnp.eye(c) + a, jnp.broadcast_to(jnp.eye(c), a.shape),
            lower=True, unit_diagonal=True)
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(got - want).max()) < 2e-5 * scale
    assert float(jnp.abs(jnp.triu(got, 1)).max()) == 0.0


def test_beta_and_the_erasure_are_the_rule():
    """With beta forced to one, or with the erasure term dropped (plain
    decayed linear attention), the output is another function's."""
    q, k, v, g, beta = draws(2, 32)
    with jax.default_matmul_precision("highest"):
        o, _ = gated_delta_rule(q, k, v, g, beta, chunk=8)
        ones, _ = gated_delta_rule(q, k, v, g, jnp.ones_like(beta), chunk=8)
        plain, _ = literal(q, k, v, g, beta, erase=False)
    scale = float(jnp.abs(o).max())
    assert float(jnp.abs(o - ones).max()) > 0.1 * scale
    assert float(jnp.abs(o - plain).max()) > 0.1 * scale


def test_the_kernels_block_follows_the_shape():
    bf16, f32 = jnp.bfloat16, jnp.float32
    # the Qwen3-Next cell's rule: 8,192 positions, 16 / 32 heads of 128
    rows = deltarule._kernel_rows(8192, 16, 32, 128, 128, 64, (bf16,) * 3)
    assert rows == 512 and 8192 % rows == 0
    assert deltarule._kernel_rows(384, 8, 8, 128, 128, 64, (f32,) * 3) == 128
    # T no whole number of tiles; keys, values that are no lane tile; a
    # chunk the kernels are not chosen for; value heads a key head that are
    # no 1, 2 or 4, or fewer than eight pairs of them; dtypes that differ
    # or have no tile
    fits = (8192, 16, 32, 128, 128, 64, (bf16,) * 3)
    for at, other in ((0, 8192 + 64), (3, 64), (4, 256), (5, 32), (5, 16),
                      (1, 4), (1, 2), (6, (bf16, bf16, f32)),
                      (6, (jnp.float16,) * 3)):
        assert deltarule._kernel_rows(
            *fits[:at], other, *fits[at + 1:]) is None, (at, other)


SHAPES = {  # what `lowered_since` says of ONE call of each, traced for a TPU
    "fits": (dict(hk=8, h=8, dk=128, dv=128), 256, 64,
             {"kernel": 1, "plain": 0, "programs": 3}),
    "narrow-keys": (dict(hk=8, h=8, dk=64, dv=128), 256, 64,
                    {"kernel": 0, "plain": 1, "programs": 0}),
    "padded-T": (dict(hk=8, h=8, dk=128, dv=128), 200, 64,
                 {"kernel": 0, "plain": 1, "programs": 0}),
    "other-chunk": (dict(hk=8, h=8, dk=128, dv=128), 256, 16,
                    {"kernel": 0, "plain": 1, "programs": 0}),
    "few-pairs": (dict(hk=2, h=4, dk=128, dv=128), 256, 64,
                  {"kernel": 0, "plain": 1, "programs": 0}),
}


@pytest.mark.parametrize("tpu", [False, True], ids=["cpu", "traced-for-tpu"])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_lowered_since_counts_kernel_plain_and_programs(
        monkeypatch, shape, tpu):
    """Off a TPU every call is the plain form, whatever its shape (what was
    `test_every_call_is_counted_as_plain`). Traced for a TPU (said so by the
    test: this process traces for the CPU; nothing runs, a kernel traced for
    a TPU cannot on the CPU), a shape that fits goes down the kernels and is
    counted `kernel` with its three programs, once however many rules of
    that shape there are; a shape the kernels refuse falls to the plain
    form."""
    widths, t, chunk, want = SHAPES[shape]
    if tpu:
        monkeypatch.setattr(programs, "traced_for_tpu", lambda: True)
    else:
        want = {"kernel": 0, "plain": 1, "programs": 0}
    args = draws(0, t, bsz=1, **widths)
    before = programs.LOWERED.copy()
    jaxpr = jax.make_jaxpr(
        lambda *x: gated_delta_rule(*x, chunk=chunk))(*args)
    assert programs.lowered_since(before)["delta"] == want
    assert ("pallas_call" in str(jaxpr)) == bool(want["kernel"])
    before = programs.LOWERED.copy()
    (o, s), _ = jax.eval_shape(lambda *x: (
        gated_delta_rule(*x, chunk=chunk),
        gated_delta_rule(*x, chunk=chunk)), *args)
    assert o.shape == args[2].shape and s.shape == (
        1, widths["h"], widths["dk"], widths["dv"])
    assert programs.lowered_since(before)["delta"] == {
        **{way: 2 * n for way, n in want.items()},
        "programs": want["programs"]}


def test_a_shape_the_kernels_refuse_falls_to_the_plain_form(monkeypatch):
    """... and computes the rule there: traced for a TPU, narrow keys and a
    T that is padded give the recurrence's values through the plain form."""
    monkeypatch.setattr(programs, "traced_for_tpu", lambda: True)
    for widths, t in ((dict(hk=2, h=4, dk=64, dv=128), 128),
                      (dict(hk=8, h=8, dk=128, dv=128), 72)):
        args = draws(t, t, bsz=1, **widths)
        before = programs.LOWERED.copy()
        with jax.default_matmul_precision("highest"):
            got = gated_delta_rule(*args, chunk=64)
            want = literal(*args)
        assert programs.lowered_since(before)["delta"] == {
            "kernel": 0, "plain": 1, "programs": 0}
        agree(got, want)
