"""The Nemotron 3 Super program (models/nemotronh.py: lm_parts' Mamba-2 mixer
with groups of B and C and a gated norm by group, its sigmoid router with a
selection bias, its sorted experts of two products, ops/blockattn.py) against
its plain reference (benchmarks/references/nemotron3s_share.py, whose scan is
the literal recurrence and whose experts are a loop) at the tiny size: hidden
32, seven layers of one mixer each (M E M * E M E), 8 Mamba heads of 8 over 4
B/C groups of state 8, chunk 16, 8 query heads over 2 key-value heads of 8, 8
routed relu^2 experts top 3 of width 24 in a latent of 16, a shared expert of
48, T 64, float32 on the CPU.

Tolerance 3e-5 relative (of a leaf's norm, or of the number): both sides
compute in float32 on the CPU, so they differ only by the order of their sums
(chunks against single positions, blocks against whole rows, grouped against
per-expert products); a wrong group, a lost chunk boundary, a norm over all
channels at once or a gate left in moves a number by 1e-3 or more, and the
same program computing in bfloat16 fails it by a hundred times (a case
below). A tiny decoder is trained in tests/test_nemotronh_trainer.py, not
here.
"""

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "benchmark"))
from bench_paths import load  # noqa: E402

from mgwfbp_tpu.models import create_model, lm_parts, nemotronh  # noqa: E402
from mgwfbp_tpu.models.nemotronh import ATTENTION, MAMBA, MOE  # noqa: E402

RTOL = 3e-5
T, VOCAB = 64, 256
SHAPE = nemotronh.NEMOTRON3S_TINY
KINDS = (MAMBA, MOE, ATTENTION)


@pytest.fixture(scope="module")
def ref():
    """The reference module at the tiny shape (the wrapper's own copy)."""
    return load("references/nemotron3s_share_tiny.py").full


def flat(tree) -> dict:
    return {
        "/".join(str(k.key) for k in path): np.asarray(leaf)
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]
    }


def rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def program(experts_held=(0, 8), tensor_share=(0, 1), layers_held=None,
            vocab=VOCAB, seed=0):
    model, _ = create_model(
        "nemotron3s_tiny", num_classes=vocab, experts_held=experts_held,
        layers_held=layers_held, tensor_share=tensor_share)
    model = model.clone(attn_block=24, loss_block=40, scan_block=3)
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randint(0, vocab, (2, T)), jnp.int32)
    y = jnp.asarray(rng.randint(0, vocab, (2, T)), jnp.int32)
    params = model.init(
        {"params": jax.random.PRNGKey(seed)}, x[:1], train=False)["params"]
    # norms and the skip away from one, so that a dropped scale shows; the
    # Mamba out-projection back at the other matrices' scale (over sqrt(88)
    # it would hide the mixer behind the residual)
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: a + 0.1 * jnp.sin(jnp.arange(a.size, dtype=jnp.float32)
                                          ).reshape(a.shape)
        if path[-1].key.endswith("norm") or path[-1].key == "d"
        else a * 88 ** 0.5 if path[-1].key == "out_proj" else a, params)
    return model, params, x, y


def loss_and_grads(model, params, x, y):
    def loss(p):
        per_token, stats = model.apply({"params": p}, x, targets=y, train=True)
        return per_token.mean(), stats

    return jax.jit(jax.value_and_grad(loss, has_aux=True))(params)


def reference_loss_and_grads(ref, host, x, y, first):
    """The reference a sequence at a time (it is written for one), the rows
    under `vmap` so that its graph is traced once."""
    def loss(p):
        return jnp.mean(jax.vmap(
            lambda xi, yi: ref.sequence_loss(p, xi, yi, first=first))(x, y))

    return jax.jit(jax.value_and_grad(loss))(
        {k: jnp.asarray(v) for k, v in host.items()})


def whole_layer(kind: str, seed=0) -> dict:
    """A whole layer's leaves of `kind` at the tiny size, seeded, every
    leaf of order one where a draw of 0.02 would hide it."""
    rng = np.random.RandomState(seed)
    share = nemotronh.held(SHAPE, (0, 1))
    out = {}
    for name, shape, _ in nemotronh.layer_leaves(
            kind, SHAPE.num_experts, SHAPE, share):
        if name == "a_log":
            leaf = np.log(rng.uniform(1.0, 16.0, shape))
        elif name == "router_bias":
            leaf = rng.uniform(-0.02, 0.02, shape)
        elif name in ("dt_bias", "conv_b"):
            leaf = rng.uniform(-0.5, 0.5, shape)
        elif name.endswith("norm") or name == "d":
            leaf = 1.0 + 0.1 * rng.standard_normal(shape)
        else:
            leaf = rng.standard_normal(shape) * shape[-2] ** -0.5
        out[name] = jnp.asarray(leaf, jnp.float32)
    return out


@pytest.mark.parametrize("of", [1, 2], ids=["whole", "a-share-of-two"])
@pytest.mark.parametrize("kind", KINDS, ids=["mamba", "moe", "attention"])
def test_each_kind_of_layer_matches_the_reference_with_its_gradients(
        ref, kind, of):
    """One layer of each kind alone, on a residual stream of order one:
    output and the gradient of a seeded cotangent in every leaf, whole and
    as member 1 of 2 chips' share (2 B/C groups of 4, 4 query heads over
    key-value head 1, 24 shared columns) with experts 2 to 5 of 8."""
    share = (of - 1, of)
    first, count = (0, 8) if of == 1 else (2, 4)
    h = nemotronh.held(SHAPE, share)
    p = nemotronh.share_leaves(whole_layer(kind), kind, SHAPE, share)
    if kind == MOE:
        p.update(w_up=p["w_up"][first:first + count],
                 w_down=p["w_down"][first:first + count])
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.standard_normal((2, T, 32)), jnp.float32)
    ct = jnp.asarray(rng.standard_normal((2, T, 32)), jnp.float32)

    def got(p):
        out, _ = nemotronh.layer(p, x, kind, SHAPE, h, first, 24, 3)
        return jnp.sum(out * ct), out

    def want(p):
        out = jax.vmap(lambda row: ref.layer(
            p, row, kind, ref.SHAPE, first))(x)
        return jnp.sum(out * ct), out

    (_, out), grads = jax.jit(jax.value_and_grad(got, has_aux=True))(p)
    (_, want_out), want_grads = jax.jit(
        jax.value_and_grad(want, has_aux=True))(p)
    assert rel(out - x, want_out - x) < RTOL  # the mixer's own output
    for name in p:
        if name == "router_bias":  # the choice carries no gradient
            assert not np.asarray(grads[name]).any()
            continue
        assert rel(grads[name], want_grads[name]) < RTOL, name


@pytest.fixture(scope="module")
def cell_share(ref):
    """The tiny cell's share (layers 1 to 5 under their published names,
    experts 2 to 5, member 1 of 2 chips' heads, half the vocabulary's rows)
    with the reference's loss and gradients of it."""
    model, params, x, y = program(
        (2, 4), (1, 2), layers_held=(1, 5), vocab=128)
    return model, params, x, y, reference_loss_and_grads(
        ref, flat(params), x, y, 2)


@pytest.mark.parametrize("share", ["whole", "the-tiny-cells-share"])
def test_whole_model_matches_reference_loss_and_every_gradient_leaf(
        ref, share, request):
    if share == "whole":  # a layer of every kind (M E M *), 4 groups, 8 / 2
        # heads, all columns, all experts
        first, layers, vocab = 0, range(4), VOCAB
        model, params, x, y = program(layers_held=(0, 4))
        want_loss, want_grads = reference_loss_and_grads(
            ref, flat(params), x, y, first)
    else:
        first, layers, vocab = 2, range(1, 6), 128
        model, params, x, y, (want_loss, want_grads) = \
            request.getfixturevalue("cell_share")
    host = flat(params)
    assert {k.split("/")[0] for k in host} == {
        "embed", "out", *(f"layer_{i}" for i in layers)}
    assert host["out/head"].shape == (32, vocab)
    groups, heads = (4, 8) if share == "whole" else (2, 4)
    assert host["layer_2/in_proj"].shape == (
        32, 2 * heads * 8 + 2 * groups * 8 + heads)
    assert host["layer_3/wk"].shape == (32, 16 if share == "whole" else 8)
    assert host["layer_1/router"].shape == (32, 8)  # all 8 scored
    assert host["layer_1/shared_up"].shape == (
        32, 48 if share == "whole" else 24)
    (loss, stats), grads = loss_and_grads(model, params, x, y)
    assert abs(float(loss) - float(want_loss)) / float(want_loss) < RTOL
    got = flat(grads)
    assert set(got) == set(want_grads)
    for name, want in want_grads.items():
        if name.endswith("router_bias"):
            assert not got[name].any() and not np.asarray(want).any()
            continue
        assert rel(got[name], want) < RTOL, name
    logits = model.apply({"params": params}, x)
    for row in range(2):
        assert rel(logits[row], ref.logits(host, x[row], first=first)) < RTOL
    # the counters: one row a Mamba layer, one an `E` layer
    mambas = sum(SHAPE.pattern[i] == MAMBA for i in layers)
    sparse = sum(SHAPE.pattern[i] == MOE for i in layers)
    assert stats["health/ssm_state"].shape == (mambas,)
    assert stats["health/moe_tokens"].shape == (
        sparse, 8 if share == "whole" else 4)
    assert float(stats["health/moe_dropped"]) == 0.0
    assert stats["health/moe_latent_rms"].shape == (sparse,)
    active = np.asarray(stats["health/moe_relu2_active"])
    assert active.shape == (sparse,) and ((0.3 < active) & (active < 0.7)).all()
    if share == "whole":  # every assignment is to a held expert
        assert float(stats["health/moe_tokens"].sum()) == sparse * 2 * T * 3


def test_the_same_program_in_bfloat16_fails_the_float32_tolerance(cell_share):
    """The tolerance holds the precision: with the weights and the stream in
    bfloat16 the loss or a gradient leaf is off by a hundred times RTOL."""
    model, params, x, y, (_, want_grads) = cell_share
    low = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), params)
    _, grads = loss_and_grads(model, low, x, y)
    got = flat(grads)
    gaps = [rel(got[name].astype(np.float32), want)
            for name, want in want_grads.items()
            if not name.endswith("router_bias")]
    assert max(gaps) > 100 * RTOL


def test_the_shares_add_up_to_the_uncut_layer(ref):
    """Section 4's test: the four tensor shares' partial outputs of a Mamba
    layer (2 heads over ONE B/C group each, a gated norm over its own 16
    channels), of the attention layer (2 query heads each; key-value head 0
    for members 0 and 1, head 1 for members 2 and 3) and of the shared
    expert (12 columns each), and the four expert shares' parts through
    W_up (2 experts each), with what every chip computes alike (the norm,
    the router, both latent projections) counted once, sum to the uncut
    reference's layer. (With the gated norm over all 64 channels at once the
    Mamba shares would not add up: the reading the program holds.)"""
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.standard_normal((2, T, 32)), jnp.float32)
    for kind in KINDS:
        whole = whole_layer(kind, seed=3)
        total = 0.0
        for member in range(4):
            share = (member, 4)
            h = nemotronh.held(SHAPE, share)
            assert h == (2, 1, 2, 1, 12)
            p = nemotronh.share_leaves(whole, kind, SHAPE, share)
            if kind == MOE:  # expert share `member` beside tensor share
                p.update(w_up=p["w_up"][2 * member:2 * member + 2],
                         w_down=p["w_down"][2 * member:2 * member + 2])
            out, _ = jax.jit(
                nemotronh.layer, static_argnums=(2, 3, 4, 5, 6, 7))(
                    p, x, kind, SHAPE, h, 2 * member, 24, 3)
            total = total + (out - x)  # a member's partial mixer output
        want = jax.vmap(lambda row: ref.layer(
            whole, row, kind, ref.SHAPE, 0))(x) - x
        assert rel(total, want) < RTOL, kind
    # the key-value mapping: members 2 and 3 hold key-value head 1
    attention = whole_layer(ATTENTION, seed=3)
    for member, head in ((0, 0), (1, 0), (2, 1), (3, 1)):
        p = nemotronh.share_leaves(attention, ATTENTION, SHAPE, (member, 4))
        np.testing.assert_array_equal(
            p["wk"], attention["wk"][:, 8 * head:8 * head + 8])
        np.testing.assert_array_equal(
            p["wq"], attention["wq"][:, 16 * member:16 * member + 16])


def dense_experts(u, idx, weights, first, act, *stacks):
    """Every held expert over every token under a mask: the plain loop."""
    y = 0.0
    for e in range(stacks[0].shape[0]):
        w_e = jnp.sum(jnp.where(idx == first + e, weights, 0.0), axis=-1)
        y = y + w_e[:, None] * act(u, *(w[e] for w in stacks))
    return y


def test_held_experts_of_two_products_against_a_dense_loop_and_gated_unchanged():
    """`lm_parts.held_relu2_experts` (relu(u W_up)^2 W_down, no gate) and
    `held_experts` (SwiGLU, as the four sparse models call it) share the
    permutation and the combine; each against a loop over the held experts
    under a mask, with the gradients of u and of the stacks, holding experts
    3 to 5 of 8; the share of hidden units relu left on against numpy."""
    rng = np.random.RandomState(4)
    n, d, f, k, first, count = 96, 16, 24, 3, 3, 3
    u = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    idx = jnp.asarray(np.stack([rng.permutation(8)[:k] for _ in range(n)]))
    weights = jnp.asarray(rng.uniform(0.1, 1.0, (n, k)), jnp.float32)
    w_gate, w_up = (jnp.asarray(rng.standard_normal((count, d, f)) * 0.3,
                                jnp.float32) for _ in range(2))
    w_down = jnp.asarray(rng.standard_normal((count, f, d)) * 0.3, jnp.float32)
    ct = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)

    def relu2(v, up, down):
        return jnp.square(jax.nn.relu(jnp.dot(v, up, precision="highest"))) \
            @ down

    def swiglu(v, gate, up, down):
        return (jax.nn.silu(v @ gate) * (v @ up)) @ down

    def two(u, w_up, w_down):
        y, sizes, dropped, active = lm_parts.held_relu2_experts(
            u, idx, weights, w_up, w_down, first)
        return jnp.sum(y * ct), (y, sizes, dropped, active)

    def three(u, w_gate, w_up, w_down):
        y, sizes, dropped = lm_parts.held_experts(
            u, idx, weights, w_gate, w_up, w_down, first)
        return jnp.sum(y * ct), (y, sizes, dropped)

    for fn, act, stacks in ((two, relu2, (w_up, w_down)),
                            (three, swiglu, (w_gate, w_up, w_down))):
        args = tuple(range(1 + len(stacks)))
        (_, (y, sizes, dropped, *rest)), grads = jax.jit(
            jax.value_and_grad(fn, argnums=args, has_aux=True))(u, *stacks)
        want_y = dense_experts(u, idx, weights, first, act, *stacks)
        want_grads = jax.grad(lambda u, *w: jnp.sum(dense_experts(
            u, idx, weights, first, act, *w) * ct), argnums=args)(u, *stacks)
        assert rel(y, want_y) < RTOL
        for got, want in zip(grads, want_grads):
            assert rel(got, want) < RTOL
        held = np.asarray(idx) - first
        np.testing.assert_array_equal(
            sizes, [(held == e).sum() for e in range(count)])
        assert int(dropped) == 0
        if rest:  # the share of hidden units above zero, rows in a group
            rows = np.concatenate([
                np.asarray(u)[(held == e).any(axis=1)] @ np.asarray(w_up[e])
                for e in range(count)])
            assert float(rest[0]) == pytest.approx((rows > 0).mean(), abs=1e-6)


# --- k > experts held: the grouped arrays sized by the rows that CAN be in a
# group (`lm_parts._grouped_experts`, `_held_first`). `tiny-nemotron3s-f32`,
# the tiny cell above and in tests/test_nemotronh_trainer.py, routes 3 over 4
# held and does NOT exercise the bound; these do: 16 experts, 5 a token,
# experts 6 to 8 held, so at most 3 of a token's 5 choices are held.
B_N, B_D, B_F, B_K, B_FIRST, B_COUNT = 64, 16, 24, 5, 6, 3
PARTS = ("y", "sizes", "dropped", "d_u", "d_weights", "d_w_up", "d_w_down")
# (experts of two products or of three, what is compared): with the share of
# hidden units relu left on where there is one, the gate's gradient where not
KIND_PARTS = [
    (kind, part) for kind, more in (("relu2", "active"), ("swiglu", "d_w_gate"))
    for part in (*PARTS, more)]


def grouped_whole(u, idx, weights, count, first, experts):
    """`lm_parts._grouped_experts` as PR 47 had it: every one of the N x k
    assignments a sorted row, whatever number of them can be held."""
    from mgwfbp_tpu.ops import rowperm

    local = idx - first
    held = (local >= 0) & (local < count)
    keys = jnp.where(held, local, count).reshape(-1)
    order = jnp.argsort(keys, stable=True)
    inverse = jnp.argsort(order)
    sizes = jnp.sum(
        keys[:, None] == jnp.arange(count)[None, :], axis=0, dtype=jnp.int32)
    rows = rowperm.take_rows(u, order, inverse, sizes)
    out, stat = experts(rows, sizes)
    y = rowperm.combine_rows(out, order, inverse, weights, sizes)
    return y, sizes, jnp.sum(held) - jnp.sum(sizes), stat


def bounded_operands():
    rng = np.random.RandomState(48)
    idx = np.stack([rng.permutation(16)[:B_K] for _ in range(B_N)])
    idx[0] = [7, 1, 8, 6, 2]  # all three held, out of their order
    idx[1] = [0, 1, 7, 2, 3]  # fewer held than slots: one, in the middle
    idx[2] = [0, 1, 2, 3, 4]  # none held
    idx[3] = [9, 10, 11, 8, 6]  # two held, last
    u = jnp.asarray(rng.standard_normal((B_N, B_D)), jnp.float32)
    weights = jnp.asarray(rng.uniform(0.1, 1.0, (B_N, B_K)), jnp.float32)
    w_gate, w_up = (jnp.asarray(
        rng.standard_normal((B_COUNT, B_D, B_F)) * 0.3, jnp.float32)
        for _ in range(2))
    w_down = jnp.asarray(
        rng.standard_normal((B_COUNT, B_F, B_D)) * 0.3, jnp.float32)
    ct = jnp.asarray(rng.standard_normal((B_N, B_D)), jnp.float32)
    return jnp.asarray(idx), u, weights, w_gate, w_up, w_down, ct


@functools.lru_cache(maxsize=None)
def bounded_block(kind: str, form: str) -> dict:
    """Value, counters and gradients of one expert block at k 5 over 3 held,
    `form` "bounded" (the program), "whole" (the N x k form in the program's
    place) or "dense" (a loop over the held experts under a mask)."""
    idx, u, weights, w_gate, w_up, w_down, ct = bounded_operands()

    def relu2(v, up, down):
        return jnp.square(jax.nn.relu(v @ up)) @ down

    def swiglu(v, gate, up, down):
        return (jax.nn.silu(v @ gate) * (v @ up)) @ down

    stacks = (w_up, w_down) if kind == "relu2" else (w_gate, w_up, w_down)

    def block(u, weights, *stacks):
        if form == "dense":
            y = dense_experts(u, idx, weights, B_FIRST,
                              relu2 if kind == "relu2" else swiglu, *stacks)
            return jnp.sum(y * ct), (y,)
        fn = lm_parts.held_relu2_experts if kind == "relu2" \
            else lm_parts.held_experts
        y, *rest = fn(u, idx, weights, *stacks, B_FIRST)
        return jnp.sum(y * ct), (y, *rest)

    with pytest.MonkeyPatch.context() as patch:
        if form == "whole":
            patch.setattr(lm_parts, "_grouped_experts", grouped_whole)
        (_, (y, *rest)), grads = jax.value_and_grad(
            block, argnums=tuple(range(2 + len(stacks))), has_aux=True)(
                u, weights, *stacks)
    names = ("d_u", "d_weights", *(("d_w_gate",) if kind == "swiglu" else ()),
             "d_w_up", "d_w_down")
    out = {"y": y, **dict(zip(names, grads)),
           **dict(zip(("sizes", "dropped", "active"), rest))}
    return {name: np.asarray(a) for name, a in out.items()}


def test_the_bounded_cases_hold_what_their_comments_say():
    held = np.asarray(bounded_operands()[0]) - B_FIRST
    held = ((held >= 0) & (held < B_COUNT)).sum(axis=1)
    assert list(held[:4]) == [3, 1, 0, 2]
    assert set(held) == {0, 1, 2, 3} and B_K > B_COUNT


@pytest.mark.parametrize("kind,part", KIND_PARTS)
def test_more_choices_than_experts_held_against_a_dense_loop(kind, part):
    """`held_relu2_experts` and `held_experts` at k > count, experts 6 to 8
    of 16 held: value, tokens per held expert, nothing dropped, the share of
    hidden units relu left on, and the gradients of u, the weights and the
    stacks against a loop over the held experts under a mask; an unheld
    choice's weight gets exactly 0."""
    got = bounded_block(kind, "bounded")
    want = bounded_block(kind, "dense")
    idx, u, _, _, w_up, _, _ = (np.asarray(a) for a in bounded_operands())
    if part == "sizes":
        np.testing.assert_array_equal(
            got["sizes"],
            [(idx == B_FIRST + e).sum() for e in range(B_COUNT)])
    elif part == "dropped":
        assert int(got["dropped"]) == 0
    elif part == "active":  # over the rows in a group
        rows = np.concatenate([
            u[(idx == B_FIRST + e).any(axis=1)] @ w_up[e]
            for e in range(B_COUNT)])
        assert float(got["active"]) == pytest.approx(
            (rows > 0).mean(), abs=1e-6)
    else:
        assert got[part].shape == want[part].shape
        assert rel(got[part], want[part]) < RTOL
    if part == "d_weights":
        unheld = (idx < B_FIRST) | (idx >= B_FIRST + B_COUNT)
        assert (got[part][unheld] == 0).all()
        assert (got[part][~unheld] != 0).all()


@pytest.mark.parametrize("kind,part", KIND_PARTS)
def test_more_choices_than_experts_held_is_the_whole_form_bit_for_bit(
        kind, part):
    """The same arithmetic in the same order: the N x count form's value,
    counters and every gradient EQUAL the N x k form's on the CPU (the rows
    in a group are the same rows at the same offsets; a token's held terms
    are added in the order j, and what the N x k form adds beside them is
    weight x 0)."""
    got, want = bounded_block(kind, "bounded"), bounded_block(kind, "whole")
    assert got.keys() == want.keys()
    np.testing.assert_array_equal(got[part], want[part])


@pytest.mark.parametrize("k,count,rows", [
    (5, 3, 64 * 3), (8, 2, 64 * 2), (3, 3, 64 * 3), (2, 4, 64 * 2)],
    ids=["k5-of-3-held", "k8-of-2-held", "k3-of-3-held", "k2-of-4-held"])
def test_the_grouped_arrays_have_a_row_for_each_choice_that_can_be_held(
        k, count, rows):
    """N x min(k, count) rows reach `experts`, by the shapes alone; where k
    <= count the traced block holds no compaction: no `cumsum`, no (N, k,
    count) array; where k > count it holds both."""
    from mgwfbp_tpu.ops import programs

    seen = []

    def experts(rows, sizes):
        seen.append(rows.shape)
        return rows, None

    before = programs.LOWERED.copy()
    text = str(jax.make_jaxpr(
        lambda u, idx, weights: lm_parts._grouped_experts(
            u, idx, weights, count, B_FIRST, experts))(
        jax.ShapeDtypeStruct((64, B_D), jnp.float32),
        jax.ShapeDtypeStruct((64, k), jnp.int32),
        jax.ShapeDtypeStruct((64, k), jnp.float32)))
    assert seen == [(rows, B_D)]
    bounded = k > count
    assert programs.lowered_since(before)["groups"] == {
        "bounded": int(bounded), "whole": int(not bounded)}
    assert ("cumsum" in text) == bounded
    assert (f"[64,{k},{count}]" in text) == bounded


@pytest.mark.parametrize("share,message", [
    ((0, 3), "do not divide the model's 4 B/C groups"),
    ((4, 4), "names no member of a group of 4"),
    ((0, 8), "do not divide the model's 4 B/C groups"),
], ids=["three-chips", "member-out-of-range", "more-chips-than-groups"])
def test_a_tensor_share_that_cannot_be_held_says_why(share, message):
    with pytest.raises(ValueError, match=message):
        nemotronh.held(SHAPE, share)


def test_the_published_share_holds_what_the_issue_counts():
    """Member 0 of 8 at the published sizes: 16 Mamba heads over ONE group,
    4 query heads over 1 key-value head, 672 shared columns; with layers 26
    to 36, 8 of 512 experts and 16,384 rows: 508,189,680 parameters."""
    h = nemotronh.held(nemotronh.NEMOTRON3S, (0, 8))
    assert h == (16, 1, 4, 1, 672)
    model, _ = create_model(
        "nemotron3s", num_classes=16384, layers_held="26:11",
        experts_held=(0, 8), tensor_share="0:8")
    shapes = jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)},
                           jnp.zeros((1, 8), jnp.int32), train=False))
    leaves = {
        "/".join(str(k.key) for k in path): leaf.shape for path, leaf
        in jax.tree_util.tree_flatten_with_path(shapes["params"])[0]}

    def count(*prefixes):
        return sum(int(np.prod(v)) for k, v in leaves.items()
                   if k.startswith(prefixes))

    assert [nemotronh.NEMOTRON3S.pattern[i] for i in range(26, 37)] \
        == list("EMEMEMEMEM*")
    assert count("layer_27/") == 13_708_592
    assert count("layer_36/") == 5_246_976
    assert count("layer_26/") == 60_035_584
    assert count("embed/", "out/") == 134_221_824
    assert count("") == 508_189_680
    assert leaves["layer_27/in_proj"] == (4096, 1024 + 1280 + 16)
    assert leaves["layer_26/w_up"] == (8, 1024, 2688)
    assert leaves["layer_26/router"] == (4096, 512)
