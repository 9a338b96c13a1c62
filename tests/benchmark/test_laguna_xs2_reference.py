"""The Laguna-XS.2 program (models/laguna.py, ops/blockattn.py, Mellum 2's
sorted experts and blocked loss) against its plain reference
(benchmarks/references/laguna_xs2_share.py) at the tiny size: hidden 64, 6 | 8
query heads over 2 key-value heads of 16, 16 routed experts top 2 of width 32
and a shared one of 24, dense MLP 96, window 16, T 64, five layers (full +
dense, window, window, window, full), float32 on the CPU.

Tolerance 1e-5 relative (of a leaf's norm, or of the number): both sides
compute in float32 on the CPU, so they differ only by the order of their sums
(blocks against whole rows, grouped against per-expert products); a wrong
mask, a wrong frequency, a lost gate or a lost token moves a number by 1e-3 or
more.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_paths import BENCH, load

from mgwfbp_tpu.models import create_model, laguna, mellum

RTOL = 1e-5
T, VOCAB = 64, 256
SHAPE = laguna.LAGUNA_XS2_TINY
CONFIG = "laguna-xs2-l5-e32of256-v12544-t8192-bf16"


@pytest.fixture(scope="module")
def ref():
    """The reference module at the tiny shape (the wrapper's own copy)."""
    return load("references/laguna_xs2_share_tiny.py").full


def flat(tree) -> dict:
    return {
        "/".join(str(k.key) for k in path): np.asarray(leaf)
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]
    }


def rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def program(experts_held=(0, 16), seed=0, vocab=VOCAB, layers_held=None):
    model, _ = create_model(
        "laguna_xs2_tiny", num_classes=vocab, experts_held=experts_held,
        layers_held=layers_held)
    model = model.clone(attn_block=24, loss_block=32)
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randint(0, vocab, (2, T)), jnp.int32)
    y = jnp.asarray(rng.randint(0, vocab, (2, T)), jnp.int32)
    params = model.init(
        {"params": jax.random.PRNGKey(seed)}, x[:1], train=False)["params"]
    # norms away from one, so that a dropped scale shows
    params = jax.tree_util.tree_map(
        lambda a: a + 0.1 * jnp.sin(jnp.arange(a.size, dtype=jnp.float32))
        if a.ndim == 1 else a, params)
    return model, params, x, y


def loss_and_grads(model, params, x, y):
    def loss(p):
        per_token, stats = model.apply({"params": p}, x, targets=y, train=True)
        return per_token.mean(), stats

    return jax.jit(jax.value_and_grad(loss, has_aux=True))(params)


def reference_loss_and_grads(ref, host, x, y, first):
    def loss(p):
        return sum(
            ref.sequence_loss(p, x[r], y[r], first=first)
            for r in range(x.shape[0])) / x.shape[0]

    return jax.jit(jax.value_and_grad(loss))(
        {k: jnp.asarray(v) for k, v in host.items()})


@pytest.fixture(scope="module")
def seeded(ref):
    """Seed 0's draws, all 16 experts held, and the reference's loss and
    gradient on them."""
    model, params, x, y = program()
    host = flat(params)
    return model, params, x, y, host, reference_loss_and_grads(
        ref, host, x, y, 0)


@pytest.mark.parametrize("experts_held", [(0, 16), (2, 4)],
                         ids=["all-experts", "a-quarter"])
def test_program_matches_reference_logits_loss_and_every_gradient_leaf(
        ref, seeded, experts_held):
    if experts_held == (0, 16):
        model, params, x, y, host, (want_loss, want_grads) = seeded
    else:
        model, params, x, y = program(experts_held)
        host = flat(params)
        want_loss, want_grads = reference_loss_and_grads(
            ref, host, x, y, experts_held[0])
    first, count = experts_held
    # leaves that differ by layer: the head count, a dense MLP or experts
    assert host["layer_0/wq"].shape == (64, 6 * 16)
    assert host["layer_1/wq"].shape == (64, 8 * 16)
    assert host["layer_0/wg"].shape == (64, 6)
    assert host["layer_3/wg"].shape == (64, 8)
    assert host["layer_4/wo"].shape == (6 * 16, 64)
    assert host["layer_0/mlp_gate"].shape == (64, 96)
    assert "layer_0/router" not in host and "layer_1/mlp_gate" not in host
    assert host["layer_1/router"].shape == (64, 16)  # all 16 experts scored
    assert host["layer_2/shared_up"].shape == (64, 24)
    assert host["layer_4/w_down"].shape == (count, 32, 64)
    got_logits = jax.jit(lambda p: model.apply({"params": p}, x))(params)
    want_logits = jax.jit(lambda p: jnp.stack(
        [ref.logits(p, x[row], first=first) for row in range(2)]))(host)
    for row in range(2):
        assert rel(got_logits[row], want_logits[row]) < RTOL
    (loss, stats), grads = loss_and_grads(model, params, x, y)
    assert abs(float(loss) - float(want_loss)) / float(want_loss) < RTOL
    got = flat(grads)
    assert set(got) == set(want_grads)
    for name, want in want_grads.items():
        assert rel(got[name], want) < RTOL, name
    # the counters: routing counts of the four sparse layers, none dropped;
    # one gate mean a layer, one score sum a sparse layer
    tokens = np.asarray(stats[mellum.MOE_TOKENS_KEY])
    assert tokens.shape == (4, count)
    assert float(stats[mellum.MOE_DROPPED_KEY]) == 0.0
    if count == 16:
        assert (tokens.sum(axis=1) == 2 * T * 2).all()
    gates = np.asarray(stats[laguna.ATTN_GATE_KEY])
    assert gates.shape == (5,) and (np.abs(gates - 0.5) < 0.05).all()
    sums = np.asarray(stats[laguna.MOE_SCORE_SUM_KEY])
    assert sums.shape == (4,) and ((0.5 < sums) & (sums < 2.0)).all()
    counters = model.step_counters(
        {k: np.asarray(v, np.float64) for k, v in stats.items()}, tokens=2 * T)
    assert set(counters) == {
        "moe_here", "moe_load_max", "moe_load_mean", "moe_dropped",
        "attn_gate_mean", "moe_score_sum"}
    assert counters["moe_here"] == pytest.approx(
        tokens.sum(axis=1).mean() / (2 * T * 2))
    assert counters["attn_gate_mean"] == pytest.approx(gates.mean())
    assert counters["moe_score_sum"] == pytest.approx(sums.mean())


def test_the_score_sum_and_the_gate_mean_are_the_references_own(ref):
    """Layer 1's counters against the reference's router and gate on layer
    1's own inputs, computed from the reference's functions alone."""
    model, params, x, _ = program(seed=2)
    host = flat(params)
    s = ref.SHAPE
    tree = ref._tree(host)
    h = tree["embed"]["embedding"][x[0]]
    h = ref.layer(tree["layer_0"], h, 0, s, 0)
    p = tree["layer_1"]
    u = ref.rms_norm(h, p["attn_norm"], s["rms_norm_eps"])
    want_gate = float(jnp.mean(ref.attention_gate(u, p["wg"])))
    # the layer up to its MLP norm, by the reference's own pieces
    freqs, factor = ref.inv_freq(s, s["layer_types"][1])
    q = ref.rope((u @ p["wq"]).reshape(T, 8, 16), freqs, factor)
    k = ref.rope((u @ p["wk"]).reshape(T, 2, 16), freqs, factor)
    a = ref.attention(q, k, (u @ p["wv"]).reshape(T, 2, 16), 16)
    a = a * ref.attention_gate(u, p["wg"])[:, :, None]
    v2 = ref.rms_norm(h + a.reshape(T, -1) @ p["wo"], p["mlp_norm"],
                      s["rms_norm_eps"])
    scores = jax.nn.sigmoid(v2 @ p["router"])
    want_sum = float(jnp.mean(jnp.sum(jax.lax.top_k(scores, 2)[0], axis=-1)))
    _, stats = model.apply({"params": params}, x[:1], targets=x[:1])
    assert float(stats[laguna.ATTN_GATE_KEY][1]) == pytest.approx(
        want_gate, rel=1e-5)
    assert float(stats[laguna.MOE_SCORE_SUM_KEY][0]) == pytest.approx(
        want_sum, rel=1e-5)


def test_the_eight_shares_add_up_to_the_uncut_layer(ref):
    """What ties the share to the model: the ROUTED parts of the eight shares
    0:2, 2:2, ... 14:2 (each routing over all 16 experts, computing its own
    two), added, with the shared expert, which every share computes alike,
    counted ONCE, are the uncut reference's whole sparse block."""
    model, params, _, _ = program(seed=3)
    p = params["layer_2"]
    u = jax.random.normal(jax.random.PRNGKey(5), (2, T, SHAPE.hidden_size))
    flat_u = u.reshape(2 * T, -1)
    shared = laguna.swiglu(
        flat_u, p["shared_gate"], p["shared_up"], p["shared_down"])
    routed = 0.0
    taken = 0.0
    for first in range(0, 16, 2):
        share = {
            **p, **{k: p[k][first:first + 2]
                    for k in ("w_gate", "w_up", "w_down")}}
        y, tokens, dropped, _ = laguna.sparse_block(share, u, SHAPE, first)
        assert float(dropped) == 0.0 and tokens.shape == (2,)
        taken += float(tokens.sum())
        # a share's block holds the shared expert entire
        routed = routed + (y.reshape(2 * T, -1) - shared)
    assert taken == 2 * T * 2  # every assignment landed on exactly one share
    total = (routed + shared).reshape(2, T, -1)
    host = {k: np.asarray(v) for k, v in p.items()}
    for row in range(2):
        want = ref.sparse_block(host, u[row], ref.SHAPE, 0)
        assert rel(total[row], want) < RTOL
        # and the shared expert counted eight times is another layer
        eightfold = total[row] + 7 * shared.reshape(2, T, -1)[row]
        assert rel(eightfold, want) > 1e-2
        # the reference's own routed part is what the shares add up to
        assert rel(routed.reshape(2, T, -1)[row],
                   ref.routed_experts(host, u[row], ref.SHAPE, 0)) < RTOL


def test_a_share_of_layers_experts_and_rows_in_program_and_reference(ref):
    """Three of five layers (the dense one first), experts 6 to 8 and half
    the rows: ids, logits and loss over the slice on both sides."""
    model, params, x, y = program((6, 3), seed=1, vocab=128, layers_held=3)
    host = flat(params)
    assert host["embed/embedding"].shape == (128, SHAPE.hidden_size)
    assert host["out/head"].shape == (SHAPE.hidden_size, 128)
    assert "layer_0/mlp_up" in host and host["layer_2/w_up"].shape[0] == 3
    assert not [k for k in host if k.startswith(("layer_3", "layer_4"))]
    assert model.apply({"params": params}, x).shape == (2, T, 128)
    (loss, stats), grads = loss_and_grads(model, params, x, y)
    assert np.asarray(stats[mellum.MOE_TOKENS_KEY]).shape == (2, 3)
    assert np.asarray(stats[laguna.ATTN_GATE_KEY]).shape == (3,)
    want_loss, want_grads = reference_loss_and_grads(ref, host, x, y, 6)
    assert abs(float(loss) - float(want_loss)) / float(want_loss) < RTOL
    for name, want in want_grads.items():
        assert rel(flat(grads)[name], want) < RTOL, name


def test_the_dense_layer_alone_has_no_routing_counters():
    """`--layers-held 1` holds the dense layer only: no expert leaf, the
    gate's counter and no routing counter."""
    model, params, x, y = program(layers_held=1)
    assert set(params) == {"embed", "layer_0", "out"}
    (loss, stats), _ = loss_and_grads(model, params, x, y)
    assert np.isfinite(float(loss))
    counters = model.step_counters(
        {k: np.asarray(v, np.float64) for k, v in stats.items()}, tokens=2 * T)
    assert set(counters) == {"attn_gate_mean"}


def _without(part):
    """The program with one part of the architecture taken out."""
    s = SHAPE
    if part == "routed-scaling":
        return dict(shape=dataclasses.replace(s, routed_scaling_factor=1.0))
    if part == "partial-rotary":
        return dict(shape=dataclasses.replace(s, full_rotary_factor=1.0))
    if part == "yarn-attention-factor":
        return dict(shape=dataclasses.replace(s, yarn_attention_factor=1.0))
    if part == "window":
        return dict(shape=dataclasses.replace(s, sliding_window=T))
    return {}


@pytest.mark.parametrize("part", [
    "gate", "routed-scaling", "partial-rotary", "yarn-attention-factor",
    "window", "head-count", "shared-expert", "sigmoid-router"])
def test_each_part_taken_out_fails_the_comparison(
        seeded, monkeypatch, part):
    """The program with the gate at one, the routed scaling at 1, the rotary
    over the whole head of a full layer, the YaRN factor at 1, no window, the
    heads regrouped as 8 | 6 (the two counts swapped over the same leaves'
    columns), no shared expert, or a softmax router is another model: its
    loss or a gradient leaf leaves the tolerance by a wide margin."""
    model, params, x, y, _, (want_loss, want_grads) = seeded
    model = model.clone(**_without(part))
    if part == "gate":
        monkeypatch.setattr(
            laguna, "attention_gate",
            lambda u, w: jnp.ones((*u.shape[:-1], w.shape[-1]), jnp.float32)
            + 0.0 * jnp.dot(u, w))
    elif part == "shared-expert":
        real = laguna.swiglu
        monkeypatch.setattr(
            laguna, "swiglu",
            lambda v, g, u, d: real(v, g, u, d) * (0.0 if g.shape[-1] == 24
                                                   else 1.0))
    elif part == "sigmoid-router":
        def softmax_route(u, router, top_k, scaling):
            idx, w = mellum.route(u, router, top_k)
            return idx, w * scaling, jnp.ones((u.shape[0],), jnp.float32)
        monkeypatch.setattr(laguna, "route", softmax_route)
    elif part == "head-count":
        # the key heads serve 3 | 4 query heads each: regrouped as 2 kv x
        # (4 | 3) the same columns belong to other key heads
        real = laguna.blockwise_attention

        def regrouped(q, k, v, **kw):
            b, t, h, d = q.shape
            q = q.reshape(b, t, h // 2, 2, d).swapaxes(2, 3).reshape(q.shape)
            return real(q, k, v, **kw)
        monkeypatch.setattr(laguna, "blockwise_attention", regrouped)
    (loss, _), grads = loss_and_grads(model, params, x, y)
    gaps = [abs(float(loss) - float(want_loss)) / float(want_loss)] + [
        rel(flat(grads)[name], want) for name, want in want_grads.items()]
    assert max(gaps) > 100 * RTOL


def test_rotary_over_half_a_head_against_numbers_computed_by_hand(ref):
    """Full layers: 64 of 128 dimensions rotate, theta 500,000, YaRN factor 64
    over 4,096 positions, beta_fast 64, beta_slow 1. Over dim 64:
    c(64) = 64 ln(4096 / (128 pi)) / (2 ln 500000) = 5.66 and c(1) = 15.80, so
    the ramp runs from pair 5 to pair 16. base_i = 500000^(-2i/64):
    base_3 = 0.292227823, base_10 = 0.0165604401, base_20 = 2.74248176e-4. Pairs up to
    5 are kept, from 16 on divided by 64, and at 10 the ramp is 5/11:
    0.0165604401 x (6/11 + 5/11/64) = 0.00915058408. Window layers: all 128
    dimensions, theta 10,000, plain."""
    yarn, factor = laguna.rope_inv_freq(laguna.LAGUNA_XS2, laguna.FULL)
    plain, one = laguna.rope_inv_freq(laguna.LAGUNA_XS2, laguna.SLIDING)
    assert yarn.shape == (32,) and plain.shape == (64,)
    assert one == 1.0 and factor == pytest.approx(0.1 * np.log(64.0) + 1.0)
    hand = {0: 1.0, 3: 0.292227823, 5: 500000 ** (-10 / 64),
            10: 0.00915058408, 16: 500000 ** (-32 / 64) / 64,
            20: 2.74248176e-4 / 64,
            31: 500000 ** (-62 / 64) / 64}
    for i, want in hand.items():
        assert float(yarn[i]) == pytest.approx(want, rel=2e-5), i
    assert float(plain[10]) == pytest.approx(10000 ** (-20 / 128), rel=1e-5)
    # the reference states the same formulas on its own
    full = load("references/laguna_xs2_share.py")
    ref_yarn, ref_factor = full.inv_freq(full.SHAPE, full.FULL)
    ref_plain, _ = full.inv_freq(full.SHAPE, full.SLIDING)
    np.testing.assert_allclose(ref_yarn, yarn, rtol=1e-5)
    np.testing.assert_allclose(ref_plain, plain, rtol=1e-5)
    assert ref_factor == factor
    # the dimensions past the rotary ones pass through, scaled and no more
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 2, 128))
    out = laguna.partial_rope(x, yarn, factor, 0.25)
    np.testing.assert_allclose(out[..., 64:], 0.25 * x[..., 64:], rtol=1e-6)
    np.testing.assert_allclose(  # position 0 turns by no angle
        out[:, 0, :, :64], 0.25 * factor * x[:, 0, :, :64], rtol=1e-6)
    assert float(jnp.max(jnp.abs(
        out[:, 5, :, :64] - 0.25 * factor * x[:, 5, :, :64]))) > 1e-2


def test_mellum2s_frequencies_are_what_they_were():
    """`mellum.rope_inv_freq` now goes through the width-taking helpers that
    models/laguna.py shares: Mellum 2's numbers by hand (PR 26's test) still
    hold, to the last bit of the parent's formula."""
    plain, _ = mellum.rope_inv_freq(mellum.MELLUM2, mellum.SLIDING)
    yarn, factor = mellum.rope_inv_freq(mellum.MELLUM2, mellum.FULL)
    base = 500000.0 ** (-jnp.arange(0, 128, 2, dtype=jnp.float32) / 128)
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(base))
    ramp = jnp.clip((jnp.arange(64, dtype=jnp.float32) - 18) / (35 - 18), 0, 1)
    np.testing.assert_array_equal(
        np.asarray(yarn), np.asarray((1 - ramp) * base + ramp * base / 16.0))
    assert factor == 1.2772588722239782


def _leaf_shapes(name, **share):
    model, _ = create_model(name, **share)
    shapes = jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)},
                           jnp.zeros((1, 8), jnp.int32), train=False))
    return {
        "/".join(str(k.key) for k in path): leaf.shape for path, leaf
        in jax.tree_util.tree_flatten_with_path(shapes["params"])[0]}


def test_forward_macs_and_the_parameters_held():
    """The published widths: 691,623,936 parameters in the share (layer 0
    79,794,176; a window layer 142,217,216; layer 4 133,795,840; embedding
    and head 51,380,224; final norm 2,048); the MACs of a sequence by hand:
    400.9 M a token, 19.7 TFLOP a step."""
    full = load("references/laguna_xs2_share.py")
    leaves = _leaf_shapes(
        "laguna_xs2", num_classes=12544, layers_held=5, experts_held=(0, 32))

    def held(prefix):
        return sum(int(np.prod(v)) for k, v in leaves.items()
                   if k.startswith(prefix))

    assert held("") == 691623936
    assert (held("layer_0/"), held("layer_1/"), held("layer_3/"),
            held("layer_4/")) == (79794176, 142217216, 142217216, 133795840)
    assert held("embed/") + held("out/head") == 51380224
    assert leaves["layer_1/w_gate"] == (32, 2048, 512)
    assert leaves["layer_1/wg"] == (2048, 64)
    assert leaves["layer_4/wg"] == (2048, 48)
    assert leaves["layer_2/router"] == (2048, 256)
    s = laguna.LAGUNA_XS2
    assert list(s.layer_types) == full.SHAPE["layer_types"]
    assert list(s.mlp_layer_types) == full.SHAPE["mlp_layer_types"]
    assert list(s.heads_per_layer) \
        == full.SHAPE["num_attention_heads_per_layer"]
    t, d = 8192, 2048
    triangle, band = t * (t + 1) // 2, 512 * 513 // 2 + (t - 512) * 512
    assert full.needed_pairs(t, None) == triangle
    assert full.needed_pairs(t, 512) == band
    attn48 = t * (d * 48 * 128 * 2 + d * 1024 * 2 + d * 48)
    attn64 = t * (d * 64 * 128 * 2 + d * 1024 * 2 + d * 64)
    sparse = t * (d * 256 + 3 * d * 512 + 1 * 3 * d * 512)  # 8 x 32 / 256 = 1
    want = (
        attn48 + triangle * 48 * 128 * 2 + t * 3 * d * 8192
        + 3 * (attn64 + band * 64 * 128 * 2 + sparse)
        + attn48 + triangle * 48 * 128 * 2 + sparse + t * d * 12544)
    assert full.forward_macs((t,), 12544) == want
    assert 400.8e6 < want / t < 401.0e6
    assert 19.6e12 < 6 * want < 19.8e12
    # attention with its projections and gate: about three quarters of it
    attention = 2 * (attn48 + triangle * 48 * 128 * 2) \
        + 3 * (attn64 + band * 64 * 128 * 2)
    assert 0.73 < attention / want < 0.76


def test_the_published_total_from_the_configuration_files_own_keys():
    """33.44 B parameters from the keys of the configuration file and its
    `published` counts, with one gate scalar a head: what `described_as`
    says (33.4B); a gate a channel would make it 34.07 B. And the whole
    model as the program declares it."""
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        c = json.load(f)
    d, hd, kv = c["hidden_size"], c["head_dim"], c["num_key_value_heads"]
    pub = c["published"]
    heads = [48, 64, 64, 64] * (pub["num_hidden_layers"] // 4)
    assert heads[:5] == c["num_attention_heads_per_layer"]

    def total(gate_columns):
        n = 2 * pub["vocab_size"] * d + d
        for i, h in enumerate(heads):
            n += 2 * d * h * hd + 2 * d * kv * hd + 2 * d + d * gate_columns(h)
            if i == 0:
                n += 3 * d * c["intermediate_size"]
            else:
                n += d * pub["num_experts"] \
                    + 3 * d * c["shared_expert_intermediate_size"] \
                    + pub["num_experts"] * 3 * d * c["moe_intermediate_size"]
        return n

    assert total(lambda h: h) == pub["parameters"] == 33442596864
    assert total(lambda h: 0) == 33437681664
    assert total(lambda h: h * hd) == 34066827264
    assert round(total(lambda h: h) / 1e9, 1) == 33.4
    assert round(total(lambda h: h * hd) / 1e9, 1) == 34.1
    whole = _leaf_shapes("laguna_xs2")
    assert sum(int(np.prod(v)) for v in whole.values()) == pub["parameters"]
    # ten leaves in the dense layer, fourteen in each sparse one
    assert len([k for k in whole if k.startswith("layer_")]) == 10 + 39 * 14
