"""The Granite 4.0-H program (models/granite.py, ops/ssd.py, ops/blockattn.py)
against its plain reference (benchmarks/references/granite4h_share.py, whose
scan is the literal recurrence) at the tiny size: hidden 32, 4 Mamba heads of
16, state 8, chunk 16, 4 query / 2 key-value heads of 8, T 64, four layers
(mamba, mamba, attention, mamba), float32 on the CPU.

Tolerance 2e-5 relative (of a leaf's norm, or of the number): both sides
compute in float32 on the CPU, so they differ only by the order of their sums
(chunks against single positions, blocks against whole rows); a wrong mask, a
lost chunk boundary or a multiplier left out moves a number by 1e-3 or more.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_paths import load

from mgwfbp_tpu.models import create_model, granite

RTOL = 2e-5
T, VOCAB = 64, 256
SHAPE = granite.GRANITE4H_TINY


@pytest.fixture(scope="module")
def ref():
    """The reference module at the tiny shape (the wrapper's own copy)."""
    return load("references/granite4h_share_tiny.py").full


def flat(tree) -> dict:
    return {
        "/".join(str(k.key) for k in path): np.asarray(leaf)
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]
    }


def rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def program(seed=0, vocab=VOCAB, layers_held=None):
    model, _ = create_model(
        "granite4h_tiny", num_classes=vocab, layers_held=layers_held)
    # T 64: query blocks of 24 and loss blocks of 32, chunks 2 to a block
    model = model.clone(attn_block=24, loss_block=32, scan_block=2)
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randint(0, vocab, (2, T)), jnp.int32)
    y = jnp.asarray(rng.randint(0, vocab, (2, T)), jnp.int32)
    params = model.init(
        {"params": jax.random.PRNGKey(seed)}, x[:1], train=False)["params"]
    # norms, `D`, time-step and decay leaves away from their initial values,
    # so that a dropped scale or bias shows
    params = jax.tree_util.tree_map(
        lambda a: a + 0.1 * jnp.sin(jnp.arange(a.size, dtype=jnp.float32))
        if a.ndim == 1 else a, params)
    return model, params, x, y


def loss_and_grads(model, params, x, y):
    def loss(p):
        per_token, stats = model.apply({"params": p}, x, targets=y, train=True)
        return per_token.mean(), stats

    return jax.jit(jax.value_and_grad(loss, has_aux=True))(params)


def reference_loss_and_grads(ref, host, x, y):
    def loss(p):
        return sum(
            ref.sequence_loss(p, x[r], y[r]) for r in range(x.shape[0])
        ) / x.shape[0]

    return jax.jit(jax.value_and_grad(loss))(
        {k: jnp.asarray(v) for k, v in host.items()})


@pytest.fixture(scope="module")
def seeded(ref):
    """Seed 0's draws and the reference's loss and gradient on them (the
    initial draws do not depend on a multiplier)."""
    model, params, x, y = program()
    host = flat(params)
    return model, params, x, y, host, reference_loss_and_grads(ref, host, x, y)


def test_program_matches_reference_logits_loss_and_every_gradient_leaf(
        ref, seeded):
    model, params, x, y, host, (want_loss, want_grads) = seeded
    assert set(host) >= {
        "embed/embedding", "out/norm", "layer_0/in_proj", "layer_0/conv_w",
        "layer_0/conv_b", "layer_0/dt_bias", "layer_0/a_log", "layer_0/d",
        "layer_0/gate_norm", "layer_0/out_proj", "layer_2/wq", "layer_2/wo",
        "layer_3/w1", "layer_3/w2"}
    assert "out/head" not in host  # tied: the embedding is the head
    got_logits = model.apply({"params": params}, x)
    want_logits = jax.jit(lambda p, xs: jnp.stack(
        [ref.logits(p, xs[row]) for row in range(2)]))(host, x)
    assert rel(got_logits, want_logits) < RTOL
    (loss, stats), grads = loss_and_grads(model, params, x, y)
    assert abs(float(loss) - float(want_loss)) / float(want_loss) < RTOL
    got = flat(grads)
    assert set(got) == set(want_grads)
    for name, want in want_grads.items():
        assert rel(got[name], want) < RTOL, name
    # the counters: one entry a Mamba layer; the recurrence's own final state
    assert np.asarray(stats[granite.SSM_STATE_KEY]).shape == (3,)
    assert np.asarray(stats[granite.SSM_LOG_DECAY_KEY]).shape == (3,)
    assert (np.asarray(stats[granite.SSM_LOG_DECAY_KEY]) < 0).all()
    counters = model.step_counters(
        {k: np.asarray(v, np.float64) for k, v in stats.items()}, tokens=2 * T)
    assert set(counters) == {"ssm_state_rms", "ssm_log_decay_min"}
    assert counters["ssm_state_rms"] > 0


def test_final_state_counter_is_the_recurrences_state(ref):
    """`ssm_state_rms` of layer 0 against the literal recurrence's state
    after the last position, on layer 0's own inputs."""
    model, params, x, _ = program(seed=2)
    host = flat(params)
    p = {k.split("/")[1]: jnp.asarray(v) for k, v in host.items()
         if k.startswith("layer_0/")}
    s = ref.SHAPE
    h = s["embedding_multiplier"] * jnp.asarray(host["embed/embedding"])[x[0]]
    u = ref.rms_norm(h, p["norm"], s["rms_norm_eps"])
    inner, n = SHAPE.mamba_inner, SHAPE.mamba_state
    zxbcdt = u @ p["in_proj"]
    xbc = jax.nn.silu(ref.causal_conv(
        zxbcdt[:, inner:2 * inner + 2 * n], p["conv_w"], p["conv_b"]))
    _, state = ref.recurrence(
        xbc[:, :inner].reshape(T, SHAPE.mamba_heads, SHAPE.mamba_head_dim),
        jax.nn.softplus(zxbcdt[:, 2 * inner + 2 * n:] + p["dt_bias"]),
        -jnp.exp(p["a_log"]), xbc[:, inner:inner + n], xbc[:, inner + n:])
    _, stats = model.apply({"params": params}, x[:1], targets=x[:1])
    want = float(jnp.sqrt(jnp.mean(jnp.square(state))))
    assert float(stats[granite.SSM_STATE_KEY][0]) == pytest.approx(
        want, rel=1e-4)


def test_the_tied_leaf_gets_the_sum_of_its_two_uses():
    """The embedding's gradient is what the lookup gives it plus what the
    head gives it: the model with the two uses fed by separate copies."""
    model, params, x, y = program(seed=4)
    _, grads = loss_and_grads(model, params, x, y)
    s = SHAPE
    layers = [params[f"layer_{i}"] for i in range(4)]

    def untied(lookup, head):
        h = s.embedding_multiplier * lookup[x]
        for p, kind in zip(layers, s.layer_types):
            h, _, _ = granite.layer(p, h, kind, s, 24, 2)
        h = granite.rms_norm(h, params["out"]["norm"], s.rms_norm_eps)
        logits = (h @ head.T) / s.logits_scaling
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, y[..., None], axis=-1))

    e = params["embed"]["embedding"]
    by_lookup, by_head = jax.jit(jax.grad(untied, argnums=(0, 1)))(e, e)
    assert float(jnp.linalg.norm(by_lookup)) > 0
    assert float(jnp.linalg.norm(by_head)) > 0
    assert rel(grads["embed"]["embedding"], by_lookup + by_head) < RTOL
    # rows no id of the batch names get nothing from the lookup
    unseen = np.setdiff1d(np.arange(VOCAB), np.asarray(x).ravel())
    assert unseen.size and not np.asarray(by_lookup)[unseen].any()


def test_a_vocabulary_slice_is_a_smaller_vocabulary(ref):
    """Half the rows held: ids, logits and loss are over the slice, in the
    program and in the reference alike; so are the first two layers held."""
    model, params, x, y = program(seed=1, vocab=128, layers_held=2)
    host = flat(params)
    assert host["embed/embedding"].shape == (128, SHAPE.hidden_size)
    assert not [k for k in host if k.startswith(("layer_2", "layer_3"))]
    assert model.apply({"params": params}, x).shape == (2, T, 128)
    (loss, _), grads = loss_and_grads(model, params, x, y)
    want_loss, want_grads = reference_loss_and_grads(ref, host, x, y)
    assert abs(float(loss) - float(want_loss)) / float(want_loss) < RTOL
    for name, want in want_grads.items():
        assert rel(flat(grads)[name], want) < RTOL, name


@pytest.mark.parametrize("multiplier", [
    "embedding_multiplier", "residual_multiplier", "attention_multiplier",
    "logits_scaling"])
def test_each_multiplier_set_to_one_fails_the_comparison(seeded, multiplier):
    """The program with one published multiplier at 1 is another model: its
    loss or a gradient leaf leaves the tolerance by a wide margin."""
    model, params, x, y, _, (want_loss, want_grads) = seeded
    model = model.clone(shape=dataclasses.replace(SHAPE, **{multiplier: 1.0}))
    (loss, _), grads = loss_and_grads(model, params, x, y)
    gaps = [abs(float(loss) - float(want_loss)) / float(want_loss)] + [
        rel(flat(grads)[name], want) for name, want in want_grads.items()]
    assert max(gaps) > 100 * RTOL


def test_forward_macs_and_the_parameters_held():
    """The published widths: 772,160,448 parameters in ten layers and an
    eighth of the tied vocabulary; the MACs of a sequence by hand."""
    full = load("references/granite4h_share.py")
    model, _ = create_model("granite4h", num_classes=12544, layers_held=10)
    shapes = jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)},
                           jnp.zeros((1, 8), jnp.int32), train=False))
    leaves = {
        "/".join(str(k.key) for k in path): leaf for path, leaf
        in jax.tree_util.tree_flatten_with_path(shapes["params"])[0]}
    assert sum(int(np.prod(v.shape)) for v in leaves.values()) == 772160448
    mamba = sum(int(np.prod(v.shape)) for k, v in leaves.items()
                if k.startswith("layer_0/"))
    attn = sum(int(np.prod(v.shape)) for k, v in leaves.items()
               if k.startswith("layer_5/"))
    assert (mamba, attn) == (76182976, 60821504)
    assert list(granite.GRANITE4H.layer_types) == full.SHAPE["layer_types"]
    t = 8192
    scan = full.scan_macs(t)
    assert scan == 32 * (256 * 257 // 2) * (128 + 4096) + 2 * t * 4096 * 128
    assert 1.58e6 < scan / t < 1.60e6
    per_mamba = t * (2048 * 8512 + 4 * 4352 + 4096 * 2048) + scan
    per_attn = t * 2048 * 64 * 80 + t * (t + 1) // 2 * 32 * 64 * 2
    mlp = t * 3 * 2048 * 8192
    assert full.forward_macs((t,), 12544) == (
        9 * per_mamba + per_attn + 10 * mlp + t * 2048 * 12544)
    roof = full.scan_flops_and_bytes(t, 1)
    assert roof["flops"] == 6 * scan and roof["bytes"] > 0
