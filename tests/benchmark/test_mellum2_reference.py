"""The Mellum 2 program (models/mellum.py, ops/blockattn.py) against its plain
reference (benchmarks/references/mellum2_share.py) at the tiny size: hidden
64, 4 query / 2 key-value heads of 16, 8 experts top 2 of width 32, window
16, T 64, four layers (window, window, window, full), float32 on the CPU.

Tolerance 1e-5 relative (of a leaf's norm, or of the number): both sides
compute in float32 on the CPU, where a float32 product is a float32 product,
so they differ only by the order of their sums (blocks against whole rows,
grouped against per-expert products); a wrong mask, a wrong frequency or a
lost token moves a number by 1e-2 or more.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_paths import load

from mgwfbp_tpu.models import create_model, mellum
from mgwfbp_tpu.ops.blockattn import blockwise_attention, key_range

RTOL = 1e-5
T, VOCAB = 64, 256
SHAPE = mellum.MELLUM2_TINY


@pytest.fixture(scope="module")
def ref():
    """The reference module at the tiny shape (the wrapper's own copy)."""
    return load("references/mellum2_share_tiny.py").full


def flat(tree) -> dict:
    return {
        "/".join(str(k.key) for k in path): np.asarray(leaf)
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]
    }


def rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def program(experts_held, seed=0):
    model, _ = create_model(
        "mellum2_tiny", num_classes=VOCAB, experts_held=experts_held)
    model = model.clone(attn_block=24, loss_block=32)
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randint(0, VOCAB, (2, T)), jnp.int32)
    y = jnp.asarray(rng.randint(0, VOCAB, (2, T)), jnp.int32)
    params = model.init(
        {"params": jax.random.PRNGKey(seed)}, x[:1], train=False)["params"]
    # norms away from one, so that a dropped scale shows
    params = jax.tree_util.tree_map(
        lambda a: a + 0.1 * jnp.sin(jnp.arange(a.size, dtype=jnp.float32))
        if a.ndim == 1 else a, params)
    return model, params, x, y


@pytest.mark.parametrize("experts_held", [(0, 8), (2, 2)],
                         ids=["all-experts", "a-quarter"])
def test_program_matches_reference_logits_loss_and_every_gradient_leaf(
        ref, experts_held):
    model, params, x, y = program(experts_held)
    first = experts_held[0]
    host = flat(params)

    got_logits = model.apply({"params": params}, x)
    for row in range(2):
        want = ref.logits(host, x[row], first=first)
        assert rel(got_logits[row], want) < RTOL

    def program_loss(p):
        per_token, stats = model.apply(
            {"params": p}, x, targets=y, train=True)
        return per_token.mean(), stats

    (loss, stats), grads = jax.value_and_grad(
        program_loss, has_aux=True)(params)

    def reference_loss(p):
        return sum(
            ref.sequence_loss(p, x[r], y[r], first=first) for r in range(2)
        ) / 2

    want_loss, want_grads = jax.value_and_grad(reference_loss)(
        {k: jnp.asarray(v) for k, v in host.items()})
    assert abs(float(loss) - float(want_loss)) / float(want_loss) < RTOL
    got = flat(grads)
    assert set(got) == set(want_grads)
    for name, want in want_grads.items():
        assert rel(got[name], want) < RTOL, name
    # the counters: every assignment of a held expert counted, none dropped
    tokens = np.asarray(stats[mellum.MOE_TOKENS_KEY])
    assert tokens.shape == (4, experts_held[1])
    assert float(stats[mellum.MOE_DROPPED_KEY]) == 0.0
    if experts_held == (0, 8):
        assert (tokens.sum(axis=1) == 2 * T * 2).all()


def test_the_four_shares_add_up_to_the_uncut_layer(ref):
    """What ties the share to the model: the sparse block's outputs of the
    shares 0:2, 2:2, 4:2, 6:2 (each routing over all 8 experts, computing
    its own two), added, are the uncut reference's block output."""
    model, params, _, _ = program((0, 8), seed=3)
    p = params["layer_1"]
    u = jax.random.normal(jax.random.PRNGKey(5), (2, T, SHAPE.hidden_size))
    total = 0.0
    for first in (0, 2, 4, 6):
        share = {
            **p, **{k: p[k][first:first + 2]
                    for k in ("w_gate", "w_up", "w_down")}}
        y, tokens, dropped = mellum.sparse_block(share, u, SHAPE, first)
        assert float(dropped) == 0.0 and tokens.shape == (2,)
        total = total + y
    host = {k: np.asarray(v) for k, v in p.items()}
    for row in range(2):
        want = ref.sparse_block(host, u[row], ref.SHAPE, 0)
        assert rel(total[row], want) < RTOL


def dense_attention(q, k, v, window):
    """(B, T, H, D) attention under the mask written out, one (T, T) array."""
    groups = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, groups, axis=2), jnp.repeat(v, groups, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    i, j = jnp.arange(q.shape[1])[:, None], jnp.arange(q.shape[1])[None, :]
    mask = j <= i if window is None else (j <= i) & (i - j < window)
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("t", [64, 50], ids=["T64", "T50-no-multiple"])
@pytest.mark.parametrize("window", [None, 16], ids=["full", "window"])
def test_blockwise_attention_value_and_gradient_against_dense(window, t):
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    q = jax.random.normal(keys[0], (2, t, 4, 16))
    k = jax.random.normal(keys[1], (2, t, 2, 16))
    v = jax.random.normal(keys[2], (2, t, 2, 16))
    w = jax.random.normal(keys[3], (2, t, 4, 16))

    def through(fn):
        return jax.value_and_grad(
            lambda q, k, v: jnp.sum(fn(q, k, v) * w), argnums=(0, 1, 2))

    got, got_grads = through(
        lambda q, k, v: blockwise_attention(
            q, k, v, window=window, block=24))(q, k, v)
    want, want_grads = through(
        lambda q, k, v: dense_attention(q, k, v, window))(q, k, v)
    assert abs(float(got) - float(want)) < RTOL * abs(float(want))
    for g, wg in zip(got_grads, want_grads):
        assert rel(g, wg) < RTOL


def test_needed_pairs_and_key_ranges_cover_the_triangle_and_the_band(ref):
    """The reference's count of needed pairs is the mask's; the program's
    static key ranges cover every needed key and, on a window layer, no
    more than the band plus a block and the alignment."""
    i, j = np.arange(64)[:, None], np.arange(64)[None, :]
    assert ref.needed_pairs(64, None) == int((j <= i).sum()) == 64 * 65 // 2
    assert ref.needed_pairs(64, 16) == int(((j <= i) & (i - j < 16)).sum())
    # at T 8,192 a window layer needs about a quarter of a full layer's pairs
    assert 0.23 < ref.needed_pairs(8192, 1024) / ref.needed_pairs(8192, None) \
        < 0.25
    for window, block in ((None, 512), (1024, 256), (1024, 512)):
        computed = 0
        for start in range(0, 8192, block):
            lo, hi = key_range(start, start + block, window)
            assert hi == start + block and lo % 128 == 0
            assert lo <= max(start - (window or 8192) + 1, 0)
            computed += block * (hi - lo)
        needed = ref.needed_pairs(8192, window)
        assert needed <= computed <= needed * (1.07 if window is None else 1.5)


def test_rotary_frequencies_against_numbers_computed_by_hand(ref):
    """head_dim 128, theta 500,000; YaRN factor 16 over 8,192 positions,
    beta_fast 32, beta_slow 1: c(32) = 18.08 and c(1) = 34.98, so the ramp
    runs from dimension 18 to 35. base_i = 500000^(-2i/128):
    base_10 = 0.128687373, base_26 = 0.00483942135, base_40 = 0.000274248176.
    Below 18 the frequency is kept, above 35 divided by 16, and at 26 the
    ramp is 8/17: 0.00483942135 x (9/17 + 8/17/16) = 0.00270438252."""
    plain, one = mellum.rope_inv_freq(mellum.MELLUM2, mellum.SLIDING)
    yarn, factor = mellum.rope_inv_freq(mellum.MELLUM2, mellum.FULL)
    assert one == 1.0 and factor == pytest.approx(1.2772588722239782)
    assert factor == pytest.approx(0.1 * np.log(16.0) + 1.0)
    hand_plain = {0: 1.0, 10: 0.12868737343, 26: 0.0048394213457,
                  40: 0.00027424817568, 63: 2.4551407911e-06}
    hand_yarn = {0: 1.0, 10: 0.12868737343, 17: float(plain[17]),
                 26: 0.0027043825167, 40: 1.7140510980e-05,
                 63: 1.5344629945e-07}
    for i, want in hand_plain.items():
        assert float(plain[i]) == pytest.approx(want, rel=1e-5)
    for i, want in hand_yarn.items():
        assert float(yarn[i]) == pytest.approx(want, rel=1e-5)
    # low and high themselves: 18 is still kept whole, 35 already divided
    assert float(yarn[18]) == pytest.approx(float(plain[18]), rel=1e-6)
    assert float(yarn[35]) == pytest.approx(float(plain[35]) / 16, rel=1e-5)
    assert float(yarn[34]) > float(plain[34]) / 16 * 1.01
    # the reference states the same formulas on its own
    full = load("references/mellum2_share.py")
    ref_plain, _ = full.inv_freq(full.SHAPE, full.SLIDING)
    ref_yarn, ref_factor = full.inv_freq(full.SHAPE, full.FULL)
    np.testing.assert_allclose(ref_plain, plain, rtol=1e-5)
    np.testing.assert_allclose(ref_yarn, yarn, rtol=1e-5)
    assert ref_factor == factor


def test_dropless_under_skew_one_expert_takes_every_token(ref):
    """A router whose expert 3 wins every token (its column is large and the
    inputs are positive): the held share 2:2 gets all 2 x 64 tokens on one
    expert, computes every one of them, and matches the reference."""
    model, params, _, _ = program((2, 2), seed=4)
    p = dict(params["layer_0"])
    p["router"] = p["router"].at[:, 3].set(1.0)
    u = jnp.abs(jax.random.normal(jax.random.PRNGKey(9), (2, T, 64))) + 0.1
    y, tokens, dropped = mellum.sparse_block(p, u, SHAPE, 2)
    assert tokens.tolist()[1] == 2 * T  # expert 3 is the share's second
    assert float(dropped) == 0.0
    host = {k: np.asarray(v) for k, v in p.items()}
    for row in range(2):
        want = ref.sparse_block(host, u[row], ref.SHAPE, 2)
        assert rel(y[row], want) < RTOL
        assert float(jnp.min(jnp.linalg.norm(y[row], axis=-1))) > 0.0


def test_forward_macs_counts_needed_pairs_and_expected_expert_work():
    full = load("references/mellum2_share.py")
    t, d = 8192, 2304
    proj = d * 4096 * 2 + d * 512 * 2
    per_layer = t * (proj + d * 64 + 2 * 3 * d * 896)
    pairs = 3 * full.needed_pairs(t, 1024) + full.needed_pairs(t, None)
    want = 4 * per_layer + pairs * 32 * 128 * 2 + t * d * 24576
    assert full.forward_macs((8192,), 24576) == want
    # 2.06e12 multiply-accumulates a sequence: 251 M a token
    assert 2.0e12 < want < 2.1e12


def test_rows_past_the_last_group_are_never_trusted(ref, monkeypatch):
    """On the chip a grouped product leaves the rows past its last group
    unwritten, in the forward pass and in the transposes (on the CPU they
    read zero, which hid a gradient norm of 1e7 on the chip). With a grouped
    product that poisons those rows both ways, the program still matches the
    reference in value and in every gradient leaf."""
    real = jax.lax.ragged_dot

    def poison(rows, sizes):
        valid = jnp.arange(rows.shape[0])[:, None] < jnp.sum(sizes)
        return jnp.where(valid, rows, jnp.nan)

    @jax.custom_vjp
    def poisoned(lhs, rhs, sizes):
        return poison(real(lhs, rhs, sizes), sizes)

    def fwd(lhs, rhs, sizes):
        return poisoned(lhs, rhs, sizes), (lhs, rhs, sizes)

    def bwd(res, g):
        lhs, rhs, sizes = res
        valid = jnp.arange(lhs.shape[0])[:, None] < jnp.sum(sizes)
        _, vjp = jax.vjp(
            lambda a, b: real(a, b, sizes), jnp.where(valid, lhs, 0), rhs)
        d_lhs, d_rhs = vjp(jnp.where(valid, g, 0))
        return poison(d_lhs, sizes), d_rhs, None

    poisoned.defvjp(fwd, bwd)
    monkeypatch.setattr(mellum.lax, "ragged_dot", poisoned)
    model, params, x, y = program((2, 2), seed=6)

    def program_loss(p):
        per_token, _ = model.apply({"params": p}, x, targets=y, train=True)
        return per_token.mean()

    loss, grads = jax.value_and_grad(program_loss)(params)
    host = flat(params)
    want_loss, want_grads = jax.value_and_grad(lambda p: sum(
        ref.sequence_loss(p, x[r], y[r], first=2) for r in range(2)) / 2)(
            {k: jnp.asarray(v) for k, v in host.items()})
    assert abs(float(loss) - float(want_loss)) / float(want_loss) < RTOL
    for name, got in flat(grads).items():
        assert np.isfinite(got).all(), name
        assert rel(got, want_grads[name]) < RTOL, name
