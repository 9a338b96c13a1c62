"""The Phi-4-mini-flash program (models/phi4flash.py, ops/selscan.py,
ops/blockattn.py) against its plain reference
(benchmarks/references/phi4flash_share.py, whose scan is the literal
recurrence and whose attention is four written-out softmax products a pair)
at the tiny size: hidden 32, 4 query / 2 key-value heads of 8, window 16,
state 4, T 64, float32 on the CPU, at two lists of layers: the 8 the
publisher's rule gives (mamba, window, mamba, window, mamba*, full*, gmu,
cross) and the stage 14..19 of the 32-layer rule (the benchmark's cut).

Tolerance 2e-5 relative (of a leaf's norm, or of the number): both sides
compute in float32 on the CPU, so they differ only by the order of their sums
(chunks against single positions, blocks against whole rows); a wrong mask, a
lost chunk boundary, a memory or keys not wired moves a number by 1e-3 or
more.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_paths import load

from mgwfbp_tpu.models import create_model, phi4flash

RTOL = 2e-5
T, VOCAB = 64, 256
TINY = phi4flash.PHI4FLASH_TINY
LISTS = {
    # name: (layers of the rule, (first, count) held)
    "eight": (8, None),
    "stage-14-19": (32, (14, 6)),
}


@pytest.fixture(scope="module")
def ref():
    """The reference module at the tiny shape (the wrapper's own copy)."""
    return load("references/phi4flash_share_tiny.py").full


def flat(tree) -> dict:
    return {
        "/".join(str(k.key) for k in path): np.asarray(leaf)
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]
    }


def rel(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def leaf_rtol(leaf: str) -> float:
    """A lambda's gradient is ONE sum over every token, pair and channel of
    its layer, of terms of both signs that nearly cancel at seeded weights
    (1e-7 to 1e-6 in all, beside 1e-3 for the sub-norm's leaf of the same
    layer): the order of a float32 sum shows at 3.3e-5 of it on
    `layer_1/lambda_k1` and at 1.0e-3 on `layer_3/lambda_k1`, where a matrix's
    leaf reads 1e-6. 5e-3 there: a wrong sign, a lost exponential or a lambda
    of another layer moves it by its whole size."""
    return 5e-3 if "/lambda_" in leaf else RTOL


def program(layers=8, held=None, seed=0, vocab=VOCAB):
    model, _ = create_model(
        "phi4flash_tiny", num_classes=vocab, layers_held=held)
    # T 64: query blocks of 24 and loss blocks of 32, chunks 3 to a block
    # (8 chunks of 8: the last block is shorter)
    model = model.clone(
        shape=dataclasses.replace(TINY, num_layers=layers), attn_block=24,
        loss_block=32, scan_block=3)
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randint(0, vocab, (2, T)), jnp.int32)
    y = jnp.asarray(rng.randint(0, vocab, (2, T)), jnp.int32)
    params = model.init(
        {"params": jax.random.PRNGKey(seed)}, x[:1], train=False)["params"]
    # norms, biases, lambdas, `D` and time-step leaves away from their
    # initial values, so that a dropped scale or bias shows
    params = jax.tree_util.tree_map(
        lambda a: a + 0.1 * jnp.sin(jnp.arange(a.size, dtype=jnp.float32))
        if a.ndim == 1 else a, params)
    return model, params, x, y


def loss_and_grads(model, params, x, y):
    def loss(p):
        per_token, stats = model.apply({"params": p}, x, targets=y, train=True)
        return per_token.mean(), (per_token, stats)

    return jax.jit(jax.value_and_grad(loss, has_aux=True))(params)


def reference_loss_and_grads(ref, host, x, y, shape):
    def loss(p):
        return sum(
            ref.sequence_loss(p, x[r], y[r], shape=shape)
            for r in range(x.shape[0])) / x.shape[0]

    return jax.jit(jax.value_and_grad(loss))(
        {k: jnp.asarray(v) for k, v in host.items()})


@pytest.fixture(scope="module", params=list(LISTS))
def seeded(request, ref):
    """Seed 0's draws at one list of layers and the reference's loss and
    gradient on them."""
    layers, held = LISTS[request.param]
    model, params, x, y = program(layers, held)
    host = flat(params)
    shape = {**ref.SHAPE, "num_hidden_layers": layers}
    return (request.param, model, params, x, y, host, shape,
            reference_loss_and_grads(ref, host, x, y, shape))


def test_program_matches_reference_logits_loss_and_every_gradient_leaf(
        ref, seeded):
    name, model, params, x, y, host, shape, (want_loss, want_grads) = seeded
    first = 0 if name == "eight" else 14
    mamba, full, gmu, cross = (
        f"layer_{first + i}" for i in ((4, 5, 6, 7) if name == "eight"
                                       else (2, 3, 4, 5)))
    assert set(host) >= {
        "embed/embedding", "out/norm", "out/norm_b",
        f"{mamba}/in_proj", f"{mamba}/conv_w", f"{mamba}/conv_b",
        f"{mamba}/x_proj", f"{mamba}/dt_proj", f"{mamba}/dt_bias",
        f"{mamba}/a_log", f"{mamba}/d", f"{mamba}/out_proj",
        f"{full}/wqkv", f"{full}/bqkv", f"{full}/wo", f"{full}/bo",
        f"{full}/lambda_q1", f"{full}/lambda_k2", f"{full}/sub_norm",
        f"{gmu}/w_g", f"{gmu}/w_o", f"{cross}/wq", f"{cross}/bq",
        f"{cross}/w1", f"{cross}/w2", f"{cross}/norm_b"}
    assert "out/head" not in host  # tied: the embedding is the head
    assert f"{cross}/wqkv" not in host and f"{gmu}/in_proj" not in host
    got_logits = jax.jit(lambda p: model.apply({"params": p}, x))(params)
    want_logits = jax.jit(lambda p, xs: jnp.stack(
        [ref.logits(p, xs[row], shape=shape) for row in range(2)]))(host, x)
    assert rel(got_logits, want_logits) < RTOL
    (loss, (per_token, stats)), grads = loss_and_grads(model, params, x, y)
    assert abs(float(loss) - float(want_loss)) / float(want_loss) < RTOL
    # per token: -log softmax(reference logits)[y]
    want_per_token = -jnp.take_along_axis(
        jax.nn.log_softmax(want_logits, axis=-1), y[..., None], axis=-1)[..., 0]
    assert rel(per_token, want_per_token) < RTOL
    got = flat(grads)
    assert set(got) == set(want_grads)
    for leaf, want in want_grads.items():
        assert rel(got[leaf], want) < leaf_rtol(leaf), leaf
    # the leaves whose gradient waits for every later reader, by name
    for leaf in (f"{mamba}/in_proj", f"{mamba}/a_log", f"{mamba}/x_proj",
                 f"{full}/wqkv", f"{full}/bqkv"):
        assert float(np.linalg.norm(got[leaf])) > 0
        assert rel(got[leaf], want_grads[leaf]) < RTOL, leaf
    # the counters: one entry a layer of the kind
    kinds = [model.shape.kind(i) for i in model.layer_indices()]
    assert np.asarray(stats[phi4flash.SEL_SCAN_STATE_KEY]).shape \
        == (kinds.count(phi4flash.MAMBA),)
    assert np.asarray(stats[phi4flash.GMU_GATE_KEY]).shape == (1,)
    assert np.asarray(stats[phi4flash.DIFF_LAMBDA_KEY]).shape \
        == (len(kinds) - kinds.count(phi4flash.MAMBA) - 1,)
    counters = model.step_counters(
        {k: np.asarray(v, np.float64) for k, v in stats.items()}, tokens=2 * T)
    assert set(counters) == {
        "sel_scan_state_rms", "gmu_gate_rms", "diff_lambda_mean"}
    assert counters["sel_scan_state_rms"] > 0 and counters["gmu_gate_rms"] > 0
    # lam of the last layer held: exp(lq1 . lk1) - exp(lq2 . lk2) + lam0
    p = {k.split("/")[1]: v for k, v in host.items()
         if k.startswith(cross + "/")}
    index = int(cross.split("_")[1])
    lam = np.exp(p["lambda_q1"] @ p["lambda_k1"]) \
        - np.exp(p["lambda_q2"] @ p["lambda_k2"]) \
        + 0.8 - 0.6 * np.exp(-0.3 * index)
    assert float(stats[phi4flash.DIFF_LAMBDA_KEY][-1]) == pytest.approx(
        lam, rel=1e-5)


def test_final_state_counter_is_the_recurrences_state(ref):
    """`sel_scan_state_rms` of layer 0 against the literal recurrence's state
    after the last position, on layer 0's own inputs."""
    model, params, x, _ = program(seed=2)
    host = flat(params)
    p = {k.split("/")[1]: jnp.asarray(v) for k, v in host.items()
         if k.startswith("layer_0/")}
    s = ref.SHAPE
    h = jnp.asarray(host["embed/embedding"])[x[0]]
    u = ref.layer_norm(h, p["norm"], p["norm_b"], s["layer_norm_eps"])
    _, _, state = jax.jit(lambda p, u: ref.mamba_mixer(p, u, s))(p, u)
    _, stats = jax.jit(lambda p: model.apply(
        {"params": p}, x[:1], targets=x[:1]))(params)
    want = float(jnp.sqrt(jnp.mean(jnp.square(state))))
    assert float(stats[phi4flash.SEL_SCAN_STATE_KEY][0]) == pytest.approx(
        want, rel=1e-4)


def _knocked_out(part, monkeypatch):
    if part == "no-lambda":
        monkeypatch.setattr(
            phi4flash, "differential_lambda", lambda p, lam0: jnp.zeros(()))
    elif part == "no-memory":
        real = phi4flash.gated_memory
        monkeypatch.setattr(
            phi4flash, "gated_memory",
            lambda p, u, m: real(p, u, jnp.ones_like(m)))
    elif part == "own-keys":
        # the cross layer attends to its OWN projection (the even half of
        # its query heads in the keys' place) and not to the full layer's
        real = phi4flash.differential_attention

        def own(p, q, k, v, s, kind, index, block):
            if kind == phi4flash.CROSS:
                k = q[:, :, : k.shape[2]]
            return real(p, q, k, v, s, kind, index, block)
        monkeypatch.setattr(phi4flash, "differential_attention", own)


@pytest.mark.parametrize("seeded", ["stage-14-19"], indirect=True)
@pytest.mark.parametrize("part", ["no-lambda", "no-memory", "own-keys"])
def test_each_mechanism_taken_out_fails_the_comparison(
        seeded, monkeypatch, part):
    """The program with lam forced to 0 (no subtraction), with the GMU's
    memory replaced by ones, or with the cross layer attending to its own
    projection instead of the full layer's keys, is another model: its loss
    or a gradient leaf leaves the tolerance by a wide margin (at the
    benchmark's stage, which has every kind of layer)."""
    _, model, params, x, y, _, _, (want_loss, want_grads) = seeded
    _knocked_out(part, monkeypatch)
    # `layer` is traced under jax.checkpoint, whose cache knows nothing of
    # the patched names
    jax.clear_caches()
    try:
        (loss, _), grads = loss_and_grads(model, params, x, y)
    finally:
        jax.clear_caches()
    gaps = [abs(float(loss) - float(want_loss)) / float(want_loss)] + [
        rel(flat(grads)[name], want) for name, want in want_grads.items()]
    assert max(gaps) > 100 * RTOL


def test_a_vocabulary_slice_is_those_columns_of_the_uncut_logits():
    """Half the rows held: the logits over the slice are those columns of the
    uncut vocabulary's logits, for ids of the slice."""
    model, params, x, _ = program(seed=1)
    x = x % 128
    half, _ = create_model("phi4flash_tiny", num_classes=128)
    half = half.clone(attn_block=24, loss_block=32, scan_block=3)
    sliced = {**params, "embed": {
        "embedding": params["embed"]["embedding"][:128]}}
    whole = jax.jit(lambda p: model.apply({"params": p}, x))(params)
    held = jax.jit(lambda p: half.apply({"params": p}, x))(sliced)
    assert held.shape == (2, T, 128)
    np.testing.assert_allclose(held, whole[..., :128], rtol=1e-5, atol=1e-6)


def test_the_stage_is_layers_14_to_19_of_the_uncut_model(ref):
    """The six-layer stage fed the uncut 32-layer model's residual stream at
    layer 14's input gives the uncut model's stream after layer 19: the
    uncut stream from the reference run over all 32 layers; the stage through
    the program's normal path, its lookup handing position t the stream's row
    t (ids 0..T-1, one embedding row a position)."""
    uncut, params, _, _ = program(layers=32, seed=3)
    host = flat(params)
    shape = {**ref.SHAPE, "num_hidden_layers": 32}
    tree = ref._tree(host)
    ids = jnp.arange(T) * 3 % VOCAB

    @jax.jit
    def uncut_stream(tree):
        h = tree["embed"]["embedding"][ids]
        reads, stream = {ref.GMU: None, ref.CROSS: None}, {}
        for index in range(32):
            stream[index] = h
            kind = ref.layer_kind(index, shape)
            h, published = ref.layer(
                tree[f"layer_{index}"], h, reads.get(kind), index=index,
                shape=shape)
            if published is not None:
                reads[ref.GMU if kind == ref.MAMBA else ref.CROSS] = published
        return stream[14], stream[20]

    into_14, after_19 = uncut_stream(tree)
    stage, _, _, _ = program(layers=32, held=(14, 6), seed=3)
    assert stage.layer_indices() == (14, 15, 16, 17, 18, 19)
    rows = jnp.zeros((VOCAB, TINY.hidden_size)).at[:T].set(into_14)
    held = {k: v for k, v in params.items()
            if k in {f"layer_{i}" for i in range(14, 20)} | {"out"}}
    got = jax.jit(lambda p: stage.apply({"params": p}, jnp.arange(T)[None]))(
        {**held, "embed": {"embedding": rows}})
    s = ref.SHAPE
    want = ref.layer_norm(
        after_19, tree["out"]["norm"], tree["out"]["norm_b"],
        s["layer_norm_eps"]) @ rows.T
    assert rel(got[0], want) < RTOL
    # and the kinds of the stage, by the published index
    assert [stage.shape.kind(i) for i in range(14, 20)] == [
        phi4flash.MAMBA, phi4flash.WINDOW, phi4flash.MAMBA, phi4flash.FULL,
        phi4flash.GMU, phi4flash.CROSS]


@pytest.mark.parametrize("held,names", [
    ((18, 2), ("layer 18", "memory", "layer 16")),
    ((17, 3), ("layer 18", "memory", "layer 16")),
    ((19, 1), ("layer 19", "keys and values", "layer 17")),
    ((30, 4), ("not among", "32")),
])
def test_a_stage_with_nothing_to_read_is_refused_by_name(held, names):
    model, _ = create_model("phi4flash", layers_held=held)
    with pytest.raises(ValueError) as refused:
        model.layer_indices()
    for said in names:
        assert said in str(refused.value)


def test_forward_macs_and_the_parameters_held():
    """The published widths: 697,094,272 parameters in the six layers round
    the hinge and an eighth of the tied vocabulary; each kind's count, the
    published 3.853 B, and the MACs of a sequence by hand."""
    full = load("references/phi4flash_share.py")
    model, _ = create_model(
        "phi4flash", num_classes=25008, layers_held="14:6")
    shapes = jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)},
                           jnp.zeros((1, 8), jnp.int32), train=False))
    leaves = {
        "/".join(str(k.key) for k in path): leaf for path, leaf
        in jax.tree_util.tree_flatten_with_path(shapes["params"])[0]}
    assert sum(int(np.prod(v.shape)) for v in leaves.values()) == 697094272
    by_layer = {
        i: sum(int(np.prod(v.shape)) for k, v in leaves.items()
               if k.startswith(f"layer_{i}/")) for i in range(14, 20)}
    mamba, attn, gmu, cross = 119895040, 98322304, 104867840, 91766144
    assert by_layer == {14: mamba, 15: attn, 16: mamba, 17: attn, 18: gmu,
                        19: cross}
    assert 9 * mamba + 9 * attn + 7 * gmu + 7 * cross \
        + 200064 * 2560 + 2 * 2560 == 3852562944
    s = phi4flash.PHI4FLASH
    kinds = [s.kind(i) for i in range(32)]
    assert kinds == [phi4flash.MAMBA, phi4flash.WINDOW] * 8 + [
        phi4flash.MAMBA, phi4flash.FULL] + [
        phi4flash.GMU, phi4flash.CROSS] * 7
    assert kinds == [full.layer_kind(i, full.SHAPE) for i in range(32)]
    assert full.SHARE == {"first_layer": 14, "layers": 6}
    for index in (15, 17, 19):
        assert s.lambda_init(index) == pytest.approx(
            0.8 - 0.6 * np.exp(-0.3 * index))
    t = 8192
    scan = full.scan_macs(t)
    assert scan == 3 * t * 5120 * 16
    per_mamba = t * (2560 * 10240 + 4 * 5120 + 5120 * 192 + 160 * 5120
                     + 5120 * 2560) + scan
    band = 512 * 513 // 2 + (t - 512) * 512
    triangle = t * (t + 1) // 2
    assert full.causal_pairs(t, 512) == band
    assert full.causal_pairs(t) == triangle
    proj = t * (2560 * 5120 + 2560 * 2560)
    per_window = proj + band * 40 * 64 * 3
    per_full = proj + triangle * 40 * 64 * 3
    per_cross = t * 2 * 2560 * 2560 + triangle * 40 * 64 * 3
    per_gmu = t * 2 * 2560 * 5120
    mlp = t * 3 * 2560 * 10240
    assert full.forward_macs((t,), 25008) == (
        2 * per_mamba + per_window + per_full + per_gmu + per_cross
        + 6 * mlp + t * 2560 * 25008)
    roof = full.scan_flops_and_bytes(t, 1)
    assert roof["flops"] == 6 * scan
    inputs = t * (5120 + 32) * 2 + t * 5120 * 4
    assert roof["bytes"] == 3 * inputs + 2 * t * 5120 * 2
