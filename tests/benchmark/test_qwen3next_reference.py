"""The Qwen3-Next program (models/qwen3next.py, ops/deltarule.py,
ops/blockattn.py, Mellum 2's router, sorted experts and blocked loss) against
its plain reference (benchmarks/references/qwen3next_share.py, the delta rule
as the literal recurrence) at the tiny size: hidden 32, 2 key / 4 value
delta-rule heads of 8, 4 query heads over 1 key head of 16 with rotary over 4,
16 routed experts top 3 of width 16 and a gated shared one, T 64, float32 on
the CPU; the 8-layer list (two periods) and the 4-layer share with 4 of 16
experts.

Tolerance 2e-5 relative (of a leaf's norm, or of the number): both sides
compute in float32 on the CPU, so they differ only by the order of their sums
(chunks and a triangular solve against the recurrence, blocks against whole
rows, grouped against per-expert products); a lost gate, a wrong norm or a
lost erasure moves a number by 1e-3 or more.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_paths import BENCH, load

from mgwfbp_tpu.models import create_model, mellum, qwen3next

RTOL = 2e-5
T, VOCAB = 64, 256
SHAPE = qwen3next.QWEN3NEXT_TINY
CONFIG = "qwen3next-l4-e32of512-v18992-t8192-bf16"


@pytest.fixture(scope="module")
def ref():
    """The reference module at the tiny shape (the wrapper's own copy)."""
    return load("references/qwen3next_share_tiny.py").full


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
        "qwen3next_tiny", num_classes=vocab, experts_held=experts_held,
        layers_held=layers_held)
    # T 64 in chunks of 16, blocks of 3 shrink to 2; attention and loss
    # blocks that do not divide T
    model = model.clone(attn_block=24, loss_block=32, delta_block=3)
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randint(0, vocab, (2, T)), jnp.int32)
    y = jnp.asarray(rng.randint(0, vocab, (2, T)), jnp.int32)
    params = model.init(
        {"params": jax.random.PRNGKey(seed)}, x[:1], train=False)["params"]
    # norms away from their start, so that a dropped or misread scale shows
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: a + 0.1 * jnp.sin(
            jnp.arange(a.size, dtype=jnp.float32) + 1.0)
        if "norm" in str(path[-1].key) else a, params)
    return model, params, x, y


def loss_and_grads(model, params, x, y):
    def loss(p):
        per_token, stats = model.apply({"params": p}, x, targets=y, train=True)
        return per_token.mean(), (per_token, stats)

    return jax.jit(jax.value_and_grad(loss, has_aux=True))(params)


def reference_loss_and_grads(ref, host, x, y, first):
    def loss(p):
        per_token = jnp.stack([
            ref.token_losses(p, x[r], y[r], first=first)
            for r in range(x.shape[0])])
        return per_token.mean(), per_token

    return jax.jit(jax.value_and_grad(loss, has_aux=True))(
        {k: jnp.asarray(v) for k, v in host.items()})


@pytest.fixture(scope="module")
def seeded(ref):
    """Seed 0's draws, all 8 layers and 16 experts held, and the reference's
    per-token loss and gradient on them."""
    model, params, x, y = program()
    host = flat(params)
    return model, params, x, y, host, reference_loss_and_grads(
        ref, host, x, y, 0)


@pytest.mark.parametrize("layers_held, experts_held", [
    (None, (0, 16)), (4, (4, 4))], ids=["two-periods", "the-share"])
def test_program_matches_reference_per_token_loss_and_every_gradient_leaf(
        ref, seeded, layers_held, experts_held):
    if layers_held is None:
        model, params, x, y, host, ((_, want_losses), want_grads) = seeded
    else:
        model, params, x, y = program(experts_held, layers_held=layers_held)
        host = flat(params)
        (_, want_losses), want_grads = reference_loss_and_grads(
            ref, host, x, y, experts_held[0])
    first, count = experts_held
    layers = 8 if layers_held is None else layers_held
    # the leaves: three delta-rule layers to one attention layer, experts in
    # every one, the router over all 16
    assert model.layer_kinds() == (
        (qwen3next.GDN,) * 3 + (qwen3next.FULL,)) * (layers // 4)
    assert host["layer_0/w_qkvz"].shape == (32, 2 * 16 + 2 * 32)
    assert host["layer_1/w_ba"].shape == (32, 8)
    assert host["layer_2/conv_w"].shape == (4, 64)
    assert host["layer_0/a_log"].shape == host["layer_0/dt_bias"].shape == (4,)
    assert host["layer_3/wq"].shape == (32, 2 * 4 * 16)
    assert host["layer_3/wk"].shape == (32, 16)
    assert host["layer_3/q_norm"].shape == (16,)
    assert "layer_3/w_qkvz" not in host and "layer_0/wq" not in host
    assert host["layer_3/router"].shape == (32, 16)
    assert host["layer_1/shared_gate_w"].shape == (32,)
    assert host["layer_2/w_down"].shape == (count, 16, 32)
    assert f"layer_{layers - 1}/wo" in host and f"layer_{layers}/wo" not in host
    got_logits = jax.jit(lambda p: model.apply({"params": p}, x))(params)
    want_logits = jax.jit(lambda p: jnp.stack(
        [ref.logits(p, x[row], first=first) for row in range(2)]))(host)
    assert rel(got_logits, want_logits) < RTOL
    (loss, (losses, stats)), grads = loss_and_grads(model, params, x, y)
    assert losses.shape == (2, T)
    assert rel(losses, want_losses) < RTOL
    got = flat(grads)
    assert set(got) == set(want_grads)
    for name, want in want_grads.items():
        assert rel(got[name], want) < RTOL, name
    # the counters: routing counts of every layer, none dropped; a state rms
    # and a mean beta a delta-rule layer; a shared gate's mean a layer
    tokens = np.asarray(stats[mellum.MOE_TOKENS_KEY])
    assert tokens.shape == (layers, count)
    assert float(stats[mellum.MOE_DROPPED_KEY]) == 0.0
    if count == 16:
        assert (tokens.sum(axis=1) == 2 * T * 3).all()
    assert np.asarray(stats[qwen3next.DELTA_STATE_KEY]).shape \
        == (layers // 4 * 3,)
    betas = np.asarray(stats[qwen3next.DELTA_BETA_KEY])
    assert (np.abs(betas - 0.5) < 0.1).all()
    gates = np.asarray(stats[qwen3next.SHARED_GATE_KEY])
    assert gates.shape == (layers,) and (np.abs(gates - 0.5) < 0.1).all()
    counters = model.step_counters(
        {k: np.asarray(v, np.float64) for k, v in stats.items()}, tokens=2 * T)
    assert set(counters) == {
        "moe_here", "moe_load_max", "moe_load_mean", "moe_dropped",
        "delta_state_rms", "delta_beta_mean", "shared_gate_mean"}
    assert counters["moe_here"] == pytest.approx(
        tokens.sum(axis=1).mean() / (2 * T * 3))
    assert counters["delta_beta_mean"] == pytest.approx(betas.mean())
    assert counters["shared_gate_mean"] == pytest.approx(gates.mean())


def test_the_three_counters_are_the_references_own(ref):
    """Layer 0's counters against the reference's recurrence, write gate and
    shared gate on layer 0's own inputs, from the reference's functions."""
    model, params, x, _ = program(seed=2)
    host = flat(params)
    s = ref.SHAPE
    tree = ref._tree(host)
    p = tree["layer_0"]
    h = tree["embed"]["embedding"][x[0]]
    u = ref.rms_norm0(h, p["attn_norm"], s["rms_norm_eps"])
    y, state, beta = ref.delta_mixer(p, u, s)
    assert state.shape == (4, 8, 8)
    v = ref.rms_norm0(h + y, p["moe_norm"], s["rms_norm_eps"])
    _, stats = model.apply({"params": params}, x[:1], targets=x[:1])
    assert float(stats[qwen3next.DELTA_STATE_KEY][0]) == pytest.approx(
        float(jnp.sqrt(jnp.mean(state ** 2))), rel=1e-4)
    assert float(stats[qwen3next.DELTA_BETA_KEY][0]) == pytest.approx(
        float(jnp.mean(beta)), rel=1e-5)
    assert float(stats[qwen3next.SHARED_GATE_KEY][0]) == pytest.approx(
        float(jnp.mean(ref.shared_gate(p, v))), rel=1e-5)


def test_the_four_shares_add_up_to_the_uncut_block(ref):
    """What ties the share to the model: the ROUTED parts of the four shares
    0:4, 4:4, 8:4, 12:4 (each routing over all 16 experts, computing its own
    four), added, with the shared expert under its gate, which every share
    computes alike, counted ONCE, are the uncut reference's whole sparse
    block."""
    model, params, _, _ = program(seed=3)
    p = params["layer_2"]
    u = jax.random.normal(jax.random.PRNGKey(5), (2, T, SHAPE.hidden_size))
    flat_u = u.reshape(2 * T, -1)
    shared = qwen3next.shared_gate(flat_u, p["shared_gate_w"])[:, None] \
        * qwen3next.swiglu(
            flat_u, p["shared_gate"], p["shared_up"], p["shared_down"])
    routed, taken = 0.0, 0.0
    for first in range(0, 16, 4):
        share = {
            **p, **{k: p[k][first:first + 4]
                    for k in ("w_gate", "w_up", "w_down")}}
        y, tokens, dropped, _ = qwen3next.sparse_block(share, u, SHAPE, first)
        assert float(dropped) == 0.0 and tokens.shape == (4,)
        taken += float(tokens.sum())
        # a share's block holds the gated shared expert entire
        routed = routed + (y.reshape(2 * T, -1) - shared)
    assert taken == 2 * T * 3  # every assignment landed on exactly one share
    total = (routed + shared).reshape(2, T, -1)
    host = {k: np.asarray(v) for k, v in p.items()}
    for row in range(2):
        want = ref.sparse_block(host, u[row], ref.SHAPE, 0)
        assert rel(total[row], want) < RTOL
        # and the shared expert counted four times is another layer
        fourfold = total[row] + 3 * shared.reshape(2, T, -1)[row]
        assert rel(fourfold, want) > 1e-2
        # the reference's own routed part is what the shares add up to
        assert rel(routed.reshape(2, T, -1)[row],
                   ref.routed_experts(host, u[row], ref.SHAPE, 0)) < RTOL


def test_logits_over_the_held_rows_are_those_columns_of_the_uncut_ones(ref):
    """A sliced vocabulary is a smaller vocabulary: with the first 128 rows
    of the embedding and the head, on ids under 128, the logits are columns
    0 to 127 of the uncut model's; the reference agrees on the slice."""
    model, params, x, _ = program(seed=1)
    x = x % 128
    half, _ = create_model("qwen3next_tiny", num_classes=128)
    half = half.clone(attn_block=24, loss_block=32, delta_block=3)
    sliced = {
        **params,
        "embed": {"embedding": params["embed"]["embedding"][:128]},
        "out": {"norm": params["out"]["norm"],
                "head": params["out"]["head"][:, :128]}}
    whole = jax.jit(lambda p: model.apply({"params": p}, x))(params)
    held = jax.jit(lambda p: half.apply({"params": p}, x))(sliced)
    assert held.shape == (2, T, 128)
    assert rel(held, whole[..., :128]) < 1e-6
    assert rel(held[0], ref.logits(flat(sliced), x[0], first=0)) < RTOL


def _plain_decayed(q, k, v, g, beta, **_):
    """Linear attention with the decay and the write gate but NO erasure:
    S_t = exp(g_t) S_{t-1} + k_t (beta_t v_t)^T."""
    r = v.shape[2] // q.shape[2]
    q, k = (jnp.repeat(a, r, axis=2) for a in (q, k))

    def step(s, inp):
        q_t, k_t, v_t, g_t, b_t = inp
        s = jnp.exp(g_t)[..., None, None] * s + jnp.einsum(
            "bhk,bhv->bhkv", k_t, b_t[..., None] * v_t)
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t)

    s, o = jax.lax.scan(
        step, jnp.zeros((*v.shape[:1], *v.shape[2:3], q.shape[-1],
                         v.shape[-1])),
        tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), s


@pytest.mark.parametrize("part", [
    "beta", "erasure", "attention-gate", "shared-gate", "zero-centred-norm"])
def test_each_mechanism_taken_out_fails_the_comparison(
        seeded, monkeypatch, part):
    """The program with beta forced to one, with the erasure term dropped
    (plain decayed linear attention), with the attention's output gate at
    one, with the shared expert's gate at one, or with the zero-centred norm
    read as a plain one is another model: its loss or a gradient leaf leaves
    the tolerance by a wide margin."""
    model, params, x, y, _, ((_, want_losses), want_grads) = seeded
    real = qwen3next.deltarule.gated_delta_rule
    real_norm = qwen3next.rms_norm0
    if part == "beta":
        monkeypatch.setattr(
            qwen3next.deltarule, "gated_delta_rule",
            lambda q, k, v, g, beta, **kw: real(
                q, k, v, g, jnp.ones_like(beta) + 0.0 * beta, **kw))
    elif part == "erasure":
        monkeypatch.setattr(
            qwen3next.deltarule, "gated_delta_rule", _plain_decayed)
    elif part == "attention-gate":
        monkeypatch.setattr(
            qwen3next, "output_gate",
            lambda gate: jnp.ones(gate.shape, jnp.float32) + 0.0 * gate)
    elif part == "shared-gate":
        monkeypatch.setattr(
            qwen3next, "shared_gate",
            lambda u, w: jnp.ones(u.shape[:1], jnp.float32)
            + 0.0 * jnp.dot(u, w))
    else:
        monkeypatch.setattr(
            qwen3next, "rms_norm0",
            lambda a, w, eps: real_norm(a, w - 1.0, eps))
    # the layers' traces are cached by (function, static arguments, shapes)
    jax.clear_caches()
    try:
        (_, (losses, _)), grads = loss_and_grads(model, params, x, y)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    gaps = [rel(losses, want_losses)] + [
        rel(flat(grads)[name], want) for name, want in want_grads.items()
        if float(np.linalg.norm(want)) > 0]
    assert max(gaps) > 100 * RTOL


def test_rotary_over_the_first_quarter_of_a_head(ref):
    """64 of 256 dimensions rotate (theta 1e7, half-split inside them), the
    other 192 pass through; the reference states the same on its own."""
    s = qwen3next.QWEN3NEXT
    dims = int(s.head_dim * s.partial_rotary_factor)
    assert dims == 64
    inv_freq = mellum.plain_inv_freq(dims, s.rope_theta)
    assert inv_freq.shape == (32,)
    assert float(inv_freq[1]) == pytest.approx(1e7 ** (-2 / 64), rel=1e-5)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 2, 256))
    out = qwen3next.partial_rope(x, inv_freq, 1.0, 0.25)
    np.testing.assert_allclose(out[..., 64:], 0.25 * x[..., 64:], rtol=1e-6)
    np.testing.assert_allclose(
        out[:, 0, :, :64], 0.25 * x[:, 0, :, :64], rtol=1e-6)
    assert float(jnp.max(jnp.abs(
        out[:, 5, :, :64] - 0.25 * x[:, 5, :, :64]))) > 1e-2
    full = load("references/qwen3next_share.py")
    want = full.rope(x[0], 64, full.SHAPE["rope_theta"])
    np.testing.assert_allclose(out[0], 0.25 * want, rtol=1e-4, atol=1e-5)


def _leaf_shapes(name, **share):
    model, _ = create_model(name, **share)
    shapes = jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)},
                           jnp.zeros((1, 8), jnp.int32), train=False))
    return {
        "/".join(str(k.key) for k in path): leaf.shape for path, leaf
        in jax.tree_util.tree_flatten_with_path(shapes["params"])[0]}


def test_forward_macs_and_the_parameters_held():
    """The published widths: 625,667,136 parameters in the share (a Gated
    DeltaNet layer 138,582,208, the full layer 132,127,232, embedding and
    head 77,791,232, final norm 2,048); the MACs of a sequence by hand:
    about 230 M a token, 22.6 TFLOP a step of two sequences."""
    full = load("references/qwen3next_share.py")
    leaves = _leaf_shapes(
        "qwen3next", num_classes=18992, layers_held=4, experts_held=(0, 32))

    def held(prefix):
        return sum(int(np.prod(v)) for k, v in leaves.items()
                   if k.startswith(prefix))

    assert held("") == 625667136
    assert (held("layer_0/"), held("layer_1/"), held("layer_2/"),
            held("layer_3/")) == (138582208,) * 3 + (132127232,)
    assert held("embed/") + held("out/head") == 2 * 18992 * 2048
    assert held("out/norm") == 2048
    mixer = ("attn_norm", "w_qkvz", "w_ba", "conv_w", "dt_bias", "a_log",
             "gate_norm", "w_out")
    assert sum(held(f"layer_0/{name}") for name in mixer) == 33718464 + 2048
    assert sum(held(f"layer_3/{name}") for name in (
        "wq", "wk", "wv", "q_norm", "k_norm", "wo")) == 27263488
    assert sum(int(np.prod(leaves[f"layer_3/{name}"])) for name in (
        "router", "shared_gate", "shared_up", "shared_down",
        "shared_gate_w")) == 4196352
    assert leaves["layer_1/w_gate"] == (32, 2048, 512)
    assert leaves["layer_2/router"] == (2048, 512)  # all 512 experts scored
    assert leaves["layer_0/a_log"] == leaves["layer_0/dt_bias"] == (32,)
    assert leaves["layer_3/wq"] == (2048, 2 * 16 * 256)
    s, r = qwen3next.QWEN3NEXT, full.SHAPE
    assert [s.kind(i) for i in range(8)] \
        == [full.layer_kind(i, r) for i in range(8)] \
        == [qwen3next.GDN] * 3 + [qwen3next.FULL] \
        + [qwen3next.GDN] * 3 + [qwen3next.FULL]
    t, d = 8192, 2048
    delta = t * (d * 12288 + d * 64 + 4 * 8192 + 4096 * d) \
        + 3 * t * 32 * 128 * 128
    assert full.delta_macs(t) == 3 * t * 32 * 128 * 128
    attn = t * (d * 8192 + 2 * d * 512 + 4096 * d) \
        + t * (t + 1) // 2 * 4096 * 2
    sparse = t * (d * 512 + d + 3 * d * 512 + 0.625 * 3 * d * 512)
    want = int(3 * delta + attn + 4 * sparse + t * d * 18992)
    assert full.forward_macs((t,), 18992) == want
    assert 229e6 < want / t < 232e6
    assert 22.4e12 < 2 * 6 * want < 22.9e12
    # the delta rule's least operations and bytes, by hand: a layer, two
    # sequences, forward and backward
    need = full.delta_flops_and_bytes(t, 2, 2)
    assert need["flops"] == 2 * 3 * 2 * 3 * t * 32 * 128 * 128
    inputs = t * (2 * 16 * 128 + 32 * 128) * 2 + t * 2 * 32 * 4
    o = t * 32 * 128 * 2
    assert need["bytes"] == 2 * ((inputs + o) + (inputs + o + inputs))


def test_the_published_total_from_the_configuration_files_own_keys():
    """79.67 B parameters from the keys of the configuration file and its
    `published` counts: the published 80 B less the multi-token-prediction
    module, which config.json does not declare. And the whole model as the
    program declares it."""
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        c = json.load(f)
    d, pub = c["hidden_size"], c["published"]
    key_dim = c["linear_num_key_heads"] * c["linear_key_head_dim"]
    value_dim = c["linear_num_value_heads"] * c["linear_value_head_dim"]
    delta = d * (2 * key_dim + 2 * value_dim) \
        + d * 2 * c["linear_num_value_heads"] \
        + c["linear_conv_kernel_dim"] * (2 * key_dim + value_dim) \
        + 2 * c["linear_num_value_heads"] + c["linear_value_head_dim"] \
        + value_dim * d
    dq = c["num_attention_heads"] * c["head_dim"]
    dkv = c["num_key_value_heads"] * c["head_dim"]
    attn = d * 2 * dq + 2 * d * dkv + 2 * c["head_dim"] + dq * d
    sparse = d * pub["num_experts"] + d \
        + 3 * d * c["shared_expert_intermediate_size"] \
        + pub["num_experts"] * 3 * d * c["moe_intermediate_size"] + 2 * d
    assert (delta, attn) == (33718464, 27263488)
    layers, every = pub["num_hidden_layers"], c["full_attention_interval"]
    total = (layers - layers // every) * (delta + sparse) \
        + layers // every * (attn + sparse) + 2 * pub["vocab_size"] * d + d
    assert total == pub["parameters"] == 79674391296
    assert round(total / 1e9, 2) == 79.67
    whole = _leaf_shapes("qwen3next")
    assert sum(int(np.prod(v)) for v in whole.values()) == pub["parameters"]
