"""The Xing4.0 program (models/xing4.py, ops/blockattn.py at a value width
other than the score width, lm_parts' sorted experts and blocked loss) against
its plain reference (benchmarks/references/xing4_share.py) at the tiny size:
hidden 32, four residual streams, 4 latent-attention heads scoring over 16 + 8
and summing values of 16, 8 routed experts top 2 of width 16 and a shared one,
dense MLP 48, T 64, four layers (dense, dense, sparse, sparse), float32 on the
CPU.

Tolerance 3e-5 relative (of a leaf's norm, or of the number): both sides
compute in float32 on the CPU, so they differ only by the order of their sums
(blocks against whole rows, grouped against per-expert products, four products
with phi's rows against one); the three alphas are scalars whose gradient is a
sum over every token and channel of terms of either sign and read up to 6e-6.
A wrong iteration count, a lost bias or a wrong width moves a number by 1e-2
or more: tests/benchmark/test_xing4_knockouts.py patches the program so, one
part a case (a file of its own, so that neither passes two minutes alone).
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_paths import BENCH, load

from mgwfbp_tpu.models import create_model, lm_parts, xing4

RTOL = 3e-5
T, VOCAB = 64, 256
SHAPE = xing4.XING4_TINY
CONFIG = "xing4-l5-e8of64-v16384-t8192-bf16"


@pytest.fixture(scope="module")
def ref():
    """The reference module at the tiny shape (the wrapper's own copy)."""
    return load("references/xing4_share_tiny.py").full


def flat(tree) -> dict:
    return {
        "/".join(str(k.key) for k in path): np.asarray(leaf)
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]
    }


def rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def program(experts_held=(0, 8), seed=0, vocab=VOCAB, layers_held=None):
    model, _ = create_model(
        "xing4_tiny", num_classes=vocab, experts_held=experts_held,
        layers_held=layers_held)
    model = model.clone(attn_block=24, loss_block=32)
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randint(0, vocab, (2, T)), jnp.int32)
    y = jnp.asarray(rng.randint(0, vocab, (2, T)), jnp.int32)
    params = model.init(
        {"params": jax.random.PRNGKey(seed)}, x[:1], train=False)["params"]
    # norms away from one, so that a dropped scale shows
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: a + 0.1 * jnp.sin(jnp.arange(a.size, dtype=jnp.float32))
        if path[-1].key.endswith("norm") else a, params)
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


def gaps(loss, grads, want_loss, want_grads) -> dict:
    got = flat(grads)
    assert set(got) == set(want_grads)
    return {
        "loss": abs(float(loss) - float(want_loss)) / float(want_loss),
        **{name: rel(got[name], want) for name, want in want_grads.items()
           if not name.endswith("router_bias")}}


@pytest.mark.parametrize("share", ["both-kinds-of-layer-all-experts",
                                   "the-tiny-cells-share"])
def test_program_matches_reference_logits_loss_and_every_gradient_leaf(
        ref, share):
    if share == "both-kinds-of-layer-all-experts":  # layers 1 and 2
        first, count, layers, vocab = 0, 8, (1, 2), VOCAB
        model, params, x, y = program(layers_held=(1, 2))
    else:  # layers 1 to 3 of 4 under their published names, experts 2 to 5,
        # half the rows of the embedding and of the head
        first, count, layers, vocab = 2, 4, (1, 2, 3), 128
        model, params, x, y = program(
            (first, count), vocab=vocab, layers_held=(1, 3))
    host = flat(params)
    assert host["embed/embedding"].shape == (vocab, SHAPE.hidden_size)
    assert host["out/head"].shape == (SHAPE.hidden_size, vocab)
    want_loss, want_grads = reference_loss_and_grads(ref, host, x, y, first)
    assert {k.split("/")[0] for k in host} == {
        "embed", "out", *(f"layer_{i}" for i in layers)}
    assert host["layer_1/attn_phi"].shape == (4 * 32, 24)
    assert host["layer_1/mlp_b"].shape == (24,)
    assert host["layer_1/mlp_alpha"].shape == (3,)
    assert host["layer_1/w_uq"].shape == (24, 4 * (16 + 8))
    assert host["layer_1/w_dkv"].shape == (32, 16 + 8)  # ONE rotary key
    assert host["layer_1/w_ukv"].shape == (16, 4 * (16 + 16))
    assert host["layer_1/wo"].shape == (4 * 16, 32)
    assert host["layer_1/mlp_gate"].shape == (32, 48)
    assert "layer_1/router" not in host and "layer_2/mlp_gate" not in host
    assert host["layer_2/router"].shape == (32, 8)  # all 8 experts scored
    assert host["layer_2/router_bias"].shape == (8,)
    assert host["layer_2/w_down"].shape == (count, 16, 32)
    (loss, stats), grads = loss_and_grads(model, params, x, y)
    if share == "the-tiny-cells-share":  # the logits too, once
        got_logits = jax.jit(lambda p: model.apply({"params": p}, x))(params)
        want_logits = jax.jit(jax.vmap(
            lambda xi: ref.logits(host, xi, first=first)))(x)
        assert got_logits.shape == (2, T, vocab)
        for row in range(2):
            assert rel(got_logits[row], want_logits[row]) < RTOL
        # the held vocabulary's loss is the reference's over the same slice,
        # by hand from the reference's logits
        by_hand = -jnp.mean(jnp.take_along_axis(
            jax.nn.log_softmax(want_logits, axis=-1), y[..., None], axis=-1))
        assert float(loss) == pytest.approx(float(by_hand), rel=RTOL)
    for name, gap in gaps(loss, grads, want_loss, want_grads).items():
        assert gap < RTOL, name
    # no gradient reaches the selection bias, on either side
    for name, leaf in flat(grads).items():
        if name.endswith("router_bias"):
            assert not leaf.any() and not np.asarray(want_grads[name]).any()
    # the counters: two mappings a layer, one latent a layer, the routing
    # counts and the swapped share of the two sparse layers
    tokens = np.asarray(stats[lm_parts.MOE_TOKENS_KEY])
    sparse = len(layers) - 1
    assert tokens.shape == (sparse, count)
    assert float(stats[lm_parts.MOE_DROPPED_KEY]) == 0.0
    if count == 8:
        assert (tokens.sum(axis=1) == 2 * T * 2).all()
    gap = np.asarray(stats[xing4.MHC_GAP_KEY])
    offdiag = np.asarray(stats[xing4.MHC_OFFDIAG_KEY])
    assert gap.shape == offdiag.shape == (2 * len(layers),)
    assert (gap < 5e-3).all() and (gap > 0).all()
    assert ((0.2 < offdiag) & (offdiag < 0.45)).all()
    latent = np.asarray(stats[xing4.MLA_LATENT_KEY])
    assert latent.shape == (len(layers),) and (latent > 0.01).all()
    swapped = np.asarray(stats[xing4.MOE_SWAP_KEY])
    assert swapped.shape == (sparse,)
    assert ((0.02 < swapped) & (swapped < 0.6)).all()
    counters = model.step_counters(
        {k: np.asarray(v, np.float64) for k, v in stats.items()}, tokens=2 * T)
    assert set(counters) == {
        "moe_here", "moe_load_max", "moe_load_mean", "moe_dropped",
        "mhc_res_gap", "mhc_res_offdiag", "mla_kv_latent_rms",
        "moe_bias_swap_share"}
    assert counters["moe_here"] == pytest.approx(
        tokens.sum(axis=1).mean() / (2 * T * 2))
    assert counters["mhc_res_gap"] == pytest.approx(gap.mean())
    assert counters["moe_bias_swap_share"] == pytest.approx(swapped.mean())


def test_the_counters_are_the_references_own(ref):
    """Layer 2's counters (its first mapping's H_res, its latent, its swapped
    choices) from the reference's functions alone on layer 2's own inputs."""
    model, params, x, _ = program(seed=2, layers_held=(2, 1))
    host = flat(params)
    s = ref.SHAPE

    @jax.jit
    def by_the_reference(host):
        p = ref._tree(host)["layer_2"]
        emb = host["embed/embedding"][x[0]]
        h = jnp.broadcast_to(emb[:, None, :], (T, 4, 32))
        pre, _, res = ref.stream_maps(p, "attn", h, s)
        gap = jnp.maximum(
            jnp.max(jnp.abs(res.sum(axis=2) - 1)),
            jnp.max(jnp.abs(res.sum(axis=1) - 1)))
        offdiag = jnp.mean(
            res.sum(axis=(1, 2)) - jnp.trace(res, axis1=1, axis2=2)) / 4
        u = ref.rms_norm(jnp.einsum("tn,tnc->tc", pre, h), p["attn_norm"],
                         s["rms_norm_eps"])
        rms = jnp.sqrt(jnp.mean(jnp.square((u @ p["w_dkv"])[:, :16])))
        h = ref.attention_sub_layer(p, h, s)
        pre = ref.stream_maps(p, "mlp", h, s)[0]
        u = ref.rms_norm(jnp.einsum("tn,tnc->tc", pre, h), p["mlp_norm"],
                         s["rms_norm_eps"])
        scores = jax.nn.sigmoid(u @ p["router"])
        chosen, _ = ref.route(u, p["router"], p["router_bias"], 2, 2.0)
        plain = jax.lax.top_k(scores, 2)[1]
        swapped = jnp.mean(jnp.all(
            chosen[:, :, None] != plain[:, None, :], axis=-1))
        return gap, offdiag, rms, swapped

    want_gap, want_offdiag, want_rms, want_swapped = (
        float(v) for v in by_the_reference(
            {k: jnp.asarray(v) for k, v in host.items()}))
    _, stats = jax.jit(lambda p: model.apply(
        {"params": p}, x[:1], targets=x[:1]))(params)
    assert float(stats[xing4.MHC_GAP_KEY][0]) == pytest.approx(
        want_gap, rel=0.05, abs=1e-6)
    assert float(stats[xing4.MHC_OFFDIAG_KEY][0]) == pytest.approx(
        want_offdiag, rel=1e-4)
    assert float(stats[xing4.MLA_LATENT_KEY][0]) == pytest.approx(
        want_rms, rel=1e-4)
    assert want_swapped > 0  # the seeded bias changes some choices
    assert float(stats[xing4.MOE_SWAP_KEY][0]) == pytest.approx(
        want_swapped, abs=1e-6)


def test_the_eight_shares_add_up_to_the_uncut_layer(ref):
    """What ties the share to the model: the ROUTED parts of the eight shares
    0:1, 1:1, ... 7:1 (each routing over all 8 experts with the selection
    bias, computing its own one), added, with the shared expert, which every
    share computes alike, counted ONCE, are the uncut reference's whole sparse
    block."""
    _, params, _, _ = program(seed=3)
    p = params["layer_2"]
    u = jax.random.normal(jax.random.PRNGKey(5), (2, T, SHAPE.hidden_size))
    flat_u = u.reshape(2 * T, -1)
    shared = lm_parts.swiglu(
        flat_u, p["shared_gate"], p["shared_up"], p["shared_down"])
    routed = 0.0
    taken = 0.0
    for first in range(8):
        share = {
            **p, **{k: p[k][first:first + 1]
                    for k in ("w_gate", "w_up", "w_down")}}
        y, tokens, dropped, _ = xing4.sparse_block(share, u, SHAPE, first)
        assert float(dropped) == 0.0 and tokens.shape == (1,)
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


def test_a_dense_layer_alone_has_no_routing_counters():
    """`--layers-held 1:1` holds layer 1, dense: no expert leaf, the
    mappings' and the latent's counters and no routing counter."""
    model, params, x, y = program(layers_held=(1, 1))
    assert set(params) == {"embed", "layer_1", "out"}
    per_token, stats = model.apply({"params": params}, x, targets=y)
    assert np.isfinite(float(per_token.mean()))
    counters = model.step_counters(
        {k: np.asarray(v, np.float64) for k, v in stats.items()}, tokens=2 * T)
    assert set(counters) == {
        "mhc_res_gap", "mhc_res_offdiag", "mla_kv_latent_rms"}
    with pytest.raises(ValueError, match="not among the model's 4"):
        program(layers_held=(3, 2))[0].apply({"params": params}, x)
    with pytest.raises(ValueError, match="not among the model's 8"):
        program(experts_held=(6, 3))


def test_rotary_frequencies_and_the_scores_factor_by_hand(ref):
    """YaRN over the 64 rotary dimensions: theta 10,000, factor 64 over 4,096
    positions, beta_fast 32, beta_slow 1. c(32) = 64 ln(4096 / (64 pi)) /
    (2 ln 10000) = 10.47 and c(1) = 22.51, so the ramp runs from pair 10 to
    pair 23. base_i = 10000^(-2i/64): pairs up to 10 are kept, from 23 on
    divided by 64, and at 16 the ramp is 6/13. m = 0.1 ln 64 + 1 =
    1.4158883; the scores' factor 192^-1/2 x m^2 = 0.1446796; cos and sin
    times 1."""
    s = xing4.XING4
    freqs = xing4.rope_inv_freq(s)
    assert freqs.shape == (32,)
    hand = {0: 1.0, 5: 10000 ** (-10 / 64), 10: 10000 ** (-20 / 64),
            16: 10000 ** (-32 / 64) * (7 / 13 + 6 / 13 / 64),
            23: 10000 ** (-46 / 64) / 64, 31: 10000 ** (-62 / 64) / 64}
    for i, want in hand.items():
        assert float(freqs[i]) == pytest.approx(want, rel=2e-5), i
    m = 0.1 * np.log(64.0) + 1.0
    assert s.score_scale == pytest.approx(192 ** -0.5 * m * m)
    assert s.score_scale == pytest.approx(0.1446796, rel=1e-6)
    assert s.rope_factor == 1.0 and s.score_dim == 192 and s.map_width == 24
    # the reference states the same formulas on its own
    full = load("references/xing4_share.py")
    ref_freqs, ref_factor = full.inv_freq(full.SHAPE)
    np.testing.assert_allclose(ref_freqs, freqs, rtol=1e-5)
    assert ref_factor == 1.0
    assert full.score_scale(full.SHAPE) == pytest.approx(s.score_scale)


def test_sinkhorn_against_numbers_computed_by_hand():
    """One iteration on [[0, ln 3], [0, 0]] (exp: [[1, 3], [1, 1]]): rows
    [[1/4, 3/4], [1/2, 1/2]], then columns over (3/4, 5/4): [[1/3, 3/5],
    [2/3, 2/5]]. Twenty iterations leave every row and column sum within 1e-6
    of one; the matrix lies first index TO, second FROM."""
    m = jnp.asarray([[0.0, np.log(3.0)], [0.0, 0.0]])[:, :, None]
    one = np.asarray(xing4.sinkhorn(m, 1, 0.0))[:, :, 0]
    np.testing.assert_allclose(one, [[1 / 3, 3 / 5], [2 / 3, 2 / 5]], rtol=1e-6)
    twenty = np.asarray(xing4.sinkhorn(m, 20, 1e-6))[:, :, 0]
    assert np.abs(twenty.sum(axis=0) - 1).max() < 2e-6
    assert np.abs(twenty.sum(axis=1) - 1).max() < 2e-6
    # the write-back reads it [to, from]: x'[0] = H[0, 0] x[0] + H[0, 1] x[1]
    x = jnp.asarray([[[[1.0]]], [[[10.0]]]])  # (n 2, B 1, T 1, C 1)
    res = jnp.asarray(one)[:, :, None, None]
    out = xing4.write_streams(
        x, res, jnp.zeros((2, 1, 1)), jnp.zeros((1, 1, 1)))
    np.testing.assert_allclose(
        np.asarray(out)[:, 0, 0, 0], [1 / 3 + 6.0, 2 / 3 + 4.0], rtol=1e-6)


def _leaf_shapes(name, **share):
    model, _ = create_model(name, **share)
    shapes = jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)},
                           jnp.zeros((1, 8), jnp.int32), train=False))
    return {
        "/".join(str(k.key) for k in path): leaf.shape for path, leaf
        in jax.tree_util.tree_flatten_with_path(shapes["params"])[0]}


def test_forward_macs_and_the_parameters_held():
    """The published widths: 759,346,446 parameters in the share, by the
    issue's table (latent attention 28,411,136; a stream mapping 344,091; the
    dense layer 128,196,918; an expert 11,010,048; a sparse layer outside its
    routed experts 40,345,974; embedding, head and final norm 117,444,096);
    the MACs of a sequence by hand: 581 M a token, 28.5 TFLOP a step."""
    full = load("references/xing4_share.py")
    leaves = _leaf_shapes(
        "xing4", num_classes=16384, layers_held=(1, 5), experts_held=(0, 8))

    def held(*prefixes):
        return sum(int(np.prod(v)) for k, v in leaves.items()
                   if k.startswith(prefixes))

    assert held("") == 759346446
    assert {k.split("/")[0] for k in leaves} == {
        "embed", "out", "layer_1", "layer_2", "layer_3", "layer_4", "layer_5"}
    attention = held(*(f"layer_1/{n}" for n in (
        "w_dq", "q_norm", "w_uq", "w_dkv", "kv_norm", "w_ukv", "wo")))
    assert attention == 28411136
    assert held("layer_1/attn_phi", "layer_1/attn_b", "layer_1/attn_alpha") \
        == 344091
    assert held("layer_1/") == 128196918
    assert held("layer_2/") == held("layer_5/") == 128426358
    assert int(np.prod(leaves["layer_3/w_gate"][1:])) * 3 == 11010048
    assert held("layer_2/") - 8 * 11010048 == 40345974
    assert held("embed/", "out/") == 117444096
    assert leaves["layer_2/router"] == (3584, 64)
    assert leaves["layer_2/w_gate"] == (8, 3584, 1024)
    assert leaves["layer_1/w_uq"] == (768, 32 * 192)
    assert leaves["layer_1/w_ukv"] == (512, 32 * 256)
    t, d = 8192, 3584
    triangle = t * (t + 1) // 2
    projections = t * (d * 768 + 768 * 6144 + d * 576 + 512 * 8192 + 4096 * d)
    core = triangle * 32 * (192 + 128)
    streams = t * (4 * d * 24 + 4 * d + 16 * d + 4 * d)
    assert full.mhc_macs(t) == streams
    sparse = t * (d * 64 + 3 * d * 1024 + 0.5 * 3 * d * 1024)  # 4 x 8 / 64
    want = int(5 * (projections + core + 2 * streams)
               + t * 3 * d * 9216 + 4 * sparse + t * d * 16384)
    assert full.forward_macs((t,), 16384) == want
    assert 580e6 < want / t < 582e6
    assert 28.4e12 < 6 * want < 28.7e12
    # the two by-hand roofline counts: the streams' bytes, the core's FLOPs
    streams_bill = full.mhc_flops_and_bytes(t)
    assert streams_bill["bytes"] == 2 * (
        10 * t * (7 * 4 * d + 4 * d) + 2 * t * 5 * d)
    assert streams_bill["flops"] == 2 * 3 * 10 * streams
    assert streams_bill["flops"] / streams_bill["bytes"] < 20  # bandwidth's
    core_bill = full.mla_core_flops_and_bytes(t)
    assert core_bill["flops"] == 2 * 3 * 5 * core
    assert core_bill["flops"] / core_bill["bytes"] > 1000  # the MXU's


def test_the_published_total_from_the_configuration_files_own_keys():
    """29,505,505,264 parameters from the keys of the configuration file and
    its `published` counts, without the prediction module: the 29B of the
    model's name. And the whole model as the program declares it."""
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        c = json.load(f)
    pub = c["published"]
    d, h = c["hidden_size"], c["num_attention_heads"]
    n = c["hc_mult"]
    attention = (
        d * c["q_lora_rank"] + c["q_lora_rank"]
        + c["q_lora_rank"] * h * (c["qk_nope_head_dim"] + c["qk_rope_head_dim"])
        + d * (c["kv_lora_rank"] + c["qk_rope_head_dim"]) + c["kv_lora_rank"]
        + c["kv_lora_rank"] * h * (c["qk_nope_head_dim"] + c["v_head_dim"])
        + h * c["v_head_dim"] * d)
    mapping = n * d * (n * n + 2 * n) + (n * n + 2 * n) + 3
    expert = 3 * d * c["moe_intermediate_size"]
    dense = attention + 3 * d * c["intermediate_size"] + 2 * mapping + 2 * d
    sparse = (attention + d * pub["n_routed_experts"] + pub["n_routed_experts"]
              + c["n_shared_experts"] * expert + 2 * mapping + 2 * d)
    total = (2 * pub["vocab_size"] * d + d
             + pub["first_k_dense_replace"] * dense
             + (pub["num_hidden_layers"] - pub["first_k_dense_replace"])
             * (sparse + pub["n_routed_experts"] * expert))
    assert total == pub["parameters"] == 29505505264
    assert round(total / 1e9, 1) == 29.5
    assert (attention, mapping, dense, expert, sparse) == (
        28411136, 344091, 128196918, 11010048, 40345974)
    assert c["parameters_held"] == dense + 4 * (sparse + 8 * expert) \
        + 2 * c["vocab_size"] * d + d == 759346446
    whole = _leaf_shapes("xing4")
    assert sum(int(np.prod(v)) for v in whole.values()) == pub["parameters"]
