"""Model zoo tests: tracing/shape correctness for every registered model and
real forward passes for the small ones.

The reference has no test suite (SURVEY.md §4); shape checks replace its
commented-out manual `test()` functions (reference models/vgg.py:41-47,
resnet.py:118-123). Big ImageNet models are checked with `jax.eval_shape`
(abstract tracing — catches shape/structure bugs without CPU-minutes of
compute).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mgwfbp_tpu import models as zoo


def _example_input(meta, batch=2):
    return jnp.zeros((batch,) + meta.input_shape, dtype=meta.input_dtype)


ALL_IMAGE_MODELS = [
    n for n in zoo.model_names()
    if n not in ("lstm", "lstman4", "transformer", "mellum2", "mellum2_tiny",
                 "granite4h", "granite4h_tiny", "laguna_xs2",
                 "laguna_xs2_tiny", "phi4flash", "phi4flash_tiny",
                 "qwen3next", "qwen3next_tiny", "xing4", "xing4_tiny",
                 "nemotron3s", "nemotron3s_tiny")
]


@pytest.mark.parametrize("name", ALL_IMAGE_MODELS)
def test_image_model_traces(name):
    model, meta = zoo.create_model(name)
    x = _example_input(meta)
    rngs = {"params": jax.random.PRNGKey(0)}
    variables = jax.eval_shape(lambda: model.init(rngs, x, train=False))
    assert "params" in variables
    out = jax.eval_shape(
        lambda v: model.apply(v, x, train=False), variables
    )
    assert out.shape == (2, meta.num_classes)


@pytest.mark.parametrize("name,share,want", [
    # the whole model: 28 x 417.8 M + 2 x 98,304 x 2,304, "12B" by name
    ("mellum2", {}, 12_149_915_904),
    # one chip's share: 4 layers, 16 of 64 experts, a quarter of the ids
    ("mellum2", dict(num_classes=24576, layers_held=4,
                     experts_held=(0, 16)), 595_153_152),
    ("mellum2_tiny", dict(experts_held=(2, 2)), None),
])
def test_mellum2_traces_and_counts_its_parameters(name, share, want):
    model, meta = zoo.create_model(name, **share)
    assert meta.task == "lm" and not meta.has_carry and meta.fused_loss
    x = _example_input(meta)
    variables = jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)}, x, train=False))
    n = sum(int(np.prod(leaf.shape))
            for leaf in jax.tree_util.tree_leaves(variables["params"]))
    if want is not None:
        assert n == want
    layers = [k for k in variables["params"] if k.startswith("layer_")]
    assert len(layers) == (share.get("layers_held") or 28 if want else 4)
    if name == "mellum2_tiny":
        logits = jax.eval_shape(lambda v: model.apply(v, x), variables)
        assert logits.shape == (2, 64, meta.num_classes)
        per_token, stats = jax.eval_shape(
            lambda v: model.apply(v, x, targets=x, train=True), variables)
        assert per_token.shape == (2, 64)
        assert stats["health/moe_tokens"].shape == (4, 2)


@pytest.mark.parametrize("name,share,want,layers", [
    # the whole model: "33.4B" by name, one gate scalar a head
    ("laguna_xs2", {}, 33_442_596_864, 40),
    # one chip's share: the dense layer and four sparse ones, 32 of 256
    # routed experts, an eighth of the ids
    ("laguna_xs2", dict(num_classes=12544, layers_held=5,
                        experts_held=(0, 32)), 691_623_936, 5),
    ("laguna_xs2_tiny", dict(experts_held=(2, 4)), None, 5),
])
def test_laguna_xs2_traces_and_counts_its_parameters(
        name, share, want, layers):
    model, meta = zoo.create_model(name, **share)
    assert meta.task == "lm" and not meta.has_carry and meta.fused_loss
    assert meta.dataset == "tokens"
    x = _example_input(meta)
    variables = jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)}, x, train=False))
    params = variables["params"]
    if want is not None:
        assert sum(int(np.prod(leaf.shape))
                   for leaf in jax.tree_util.tree_leaves(params)) == want
    assert len([k for k in params if k.startswith("layer_")]) == layers
    assert set(params["out"]) == {"norm", "head"}
    # the leading layer dense, every later one sparse beside a shared expert
    assert "mlp_gate" in params["layer_0"] and "router" not in params["layer_0"]
    assert {"router", "shared_gate", "w_gate"} <= set(params["layer_1"])
    if name == "laguna_xs2_tiny":
        logits = jax.eval_shape(lambda v: model.apply(v, x), variables)
        assert logits.shape == (2, 64, meta.num_classes)
        per_token, stats = jax.eval_shape(
            lambda v: model.apply(v, x, targets=x, train=True), variables)
        assert per_token.shape == (2, 64)
        assert stats["health/moe_tokens"].shape == (4, 4)  # sparse layers
        assert stats["health/attn_gate"].shape == (5,)
        assert stats["health/moe_score_sum"].shape == (4,)


@pytest.mark.parametrize("name,share,want,layers", [
    # the whole model: 36 Mamba and 4 attention layers, the tied embedding
    ("granite4h", {}, 3_191_396_096, 40),
    # one chip's share: one period of the pattern, an eighth of the ids
    ("granite4h", dict(num_classes=12544, layers_held=10), 772_160_448, 10),
    ("granite4h_tiny", {}, None, 4),
])
def test_granite4h_traces_and_counts_its_parameters(name, share, want, layers):
    model, meta = zoo.create_model(name, **share)
    assert meta.task == "lm" and not meta.has_carry and meta.fused_loss
    x = _example_input(meta)
    variables = jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)}, x, train=False))
    params = variables["params"]
    if want is not None:
        assert sum(int(np.prod(leaf.shape))
                   for leaf in jax.tree_util.tree_leaves(params)) == want
    assert len([k for k in params if k.startswith("layer_")]) == layers
    assert set(params["out"]) == {"norm"}  # no head: the embedding is tied
    if name == "granite4h_tiny":
        logits = jax.eval_shape(lambda v: model.apply(v, x), variables)
        assert logits.shape == (2, 64, meta.num_classes)
        per_token, stats = jax.eval_shape(
            lambda v: model.apply(v, x, targets=x, train=True), variables)
        assert per_token.shape == (2, 64)
        assert stats["health/ssm_state"].shape == (3,)  # the Mamba layers
        assert stats["health/ssm_log_decay_min"].shape == (3,)


@pytest.mark.parametrize("name,share,want,layers", [
    # the whole model: 9 Mamba, 9 attention, 7 GMU and 7 cross layers, the
    # tied embedding: the published 3.8 B
    ("phi4flash", {}, 3_852_562_944, 32),
    # one chip's share: the stage round the hinge, an eighth of the ids
    ("phi4flash", dict(num_classes=25008, layers_held="14:6"),
     697_094_272, 6),
    ("phi4flash_tiny", {}, None, 8),
])
def test_phi4flash_traces_and_counts_its_parameters(name, share, want, layers):
    model, meta = zoo.create_model(name, **share)
    assert meta.task == "lm" and not meta.has_carry and meta.fused_loss
    assert meta.dataset == "tokens"
    x = _example_input(meta)
    variables = jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)}, x, train=False))
    params = variables["params"]
    if want is not None:
        assert sum(int(np.prod(leaf.shape))
                   for leaf in jax.tree_util.tree_leaves(params)) == want
    held = sorted(int(k.split("_")[1]) for k in params if k.startswith("layer_"))
    first = 14 if "layers_held" in share else 0
    assert held == list(range(first, first + layers))  # published indices
    assert set(params["out"]) == {"norm", "norm_b"}  # no head: tied
    if name == "phi4flash_tiny":
        logits = jax.eval_shape(lambda v: model.apply(v, x), variables)
        assert logits.shape == (2, 64, meta.num_classes)
        per_token, stats = jax.eval_shape(
            lambda v: model.apply(v, x, targets=x, train=True), variables)
        assert per_token.shape == (2, 64)
        assert stats["health/sel_scan_state"].shape == (3,)  # Mamba layers
        assert stats["health/gmu_gate"].shape == (1,)
        assert stats["health/diff_lambda"].shape == (4,)  # 2 window, full, cross


@pytest.mark.parametrize("name,share,want,layers", [
    # the whole model: 36 Gated DeltaNet and 12 full-attention layers, 512
    # experts in every one, the untied vocabulary: the published 80 B less
    # the multi-token-prediction module
    ("qwen3next", {}, 79_674_391_296, 48),
    # one chip's share: the first period, 32 of 512 experts, an eighth of
    # the ids
    ("qwen3next", dict(num_classes=18992, layers_held=4,
                       experts_held=(0, 32)), 625_667_136, 4),
    ("qwen3next_tiny", dict(experts_held=(4, 4)), None, 8),
])
def test_qwen3next_traces_and_counts_its_parameters(name, share, want, layers):
    model, meta = zoo.create_model(name, **share)
    assert meta.task == "lm" and not meta.has_carry and meta.fused_loss
    assert meta.dataset == "tokens"
    x = _example_input(meta)
    variables = jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)}, x, train=False))
    params = variables["params"]
    if want is not None:
        assert sum(int(np.prod(leaf.shape))
                   for leaf in jax.tree_util.tree_leaves(params)) == want
    assert sum(k.startswith("layer_") for k in params) == layers
    assert set(params["out"]) == {"norm", "head"}  # untied
    # every fourth layer is the full-attention one
    assert [i for i in range(layers) if "wq" in params[f"layer_{i}"]] \
        == list(range(3, layers, 4))
    if name == "qwen3next_tiny":
        logits = jax.eval_shape(lambda v: model.apply(v, x), variables)
        assert logits.shape == (2, 64, meta.num_classes)
        per_token, stats = jax.eval_shape(
            lambda v: model.apply(v, x, targets=x, train=True), variables)
        assert per_token.shape == (2, 64)
        assert stats["health/delta_state"].shape == (6,)  # the delta layers
        assert stats["health/delta_beta"].shape == (6,)
        assert stats["health/shared_gate"].shape == (8,)
        assert stats["health/moe_tokens"].shape == (8, 4)


@pytest.mark.parametrize("name,share,want,layers", [
    # the whole model: two dense layers, 38 sparse ones of 64 experts, the
    # untied vocabulary: the published 29.5 B less the multi-token-prediction
    # module
    ("xing4", {}, 29_505_505_264, tuple(range(40))),
    # one chip's share: layers 1 to 5 of the stage behind the embedding, 8 of
    # 64 experts, an eighth of the ids
    ("xing4", dict(num_classes=16384, layers_held="1:5",
                   experts_held=(0, 8)), 759_346_446, (1, 2, 3, 4, 5)),
    ("xing4_tiny", dict(layers_held=(1, 3), experts_held=(2, 4)), None,
     (1, 2, 3)),
])
def test_xing4_traces_and_counts_its_parameters(name, share, want, layers):
    model, meta = zoo.create_model(name, **share)
    assert meta.task == "lm" and not meta.has_carry and meta.fused_loss
    assert meta.dataset == "tokens"
    x = _example_input(meta)
    variables = jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)}, x, train=False))
    params = variables["params"]
    if want is not None:
        assert sum(int(np.prod(leaf.shape))
                   for leaf in jax.tree_util.tree_leaves(params)) == want
    # a stage's layers keep their published numbers
    assert {k for k in params if k.startswith("layer_")} \
        == {f"layer_{i}" for i in layers}
    assert set(params["out"]) == {"norm", "head"}  # untied
    # the leading layers are dense, every later one sparse
    first_sparse = 2
    assert [i for i in layers if "router_bias" in params[f"layer_{i}"]] \
        == [i for i in layers if i >= first_sparse]
    if name == "xing4_tiny":
        logits = jax.eval_shape(lambda v: model.apply(v, x), variables)
        assert logits.shape == (2, 64, meta.num_classes)
        per_token, stats = jax.eval_shape(
            lambda v: model.apply(v, x, targets=x, train=True), variables)
        assert per_token.shape == (2, 64)
        assert stats["health/mhc_res_gap"].shape == (6,)  # two a layer
        assert stats["health/mhc_res_offdiag"].shape == (6,)
        assert stats["health/mla_kv_latent_rms"].shape == (3,)
        assert stats["health/moe_bias_swap"].shape == (2,)  # sparse layers
        assert stats["health/moe_tokens"].shape == (2, 4)


@pytest.mark.parametrize("name,share,want,layers", [
    # the whole model: 40 Mamba-2, 40 LatentMoE and 8 attention layers of one
    # mixer each, the untied vocabulary: the published 120.67 B less the
    # multi-token-prediction module
    ("nemotron3s", {}, 120_668_707_840, tuple(range(88))),
    # one chip's share: the first whole period (layers 26 to 36), 8 of 512
    # experts, member 0 of 8 chips' heads, an eighth of the ids
    ("nemotron3s", dict(num_classes=16384, layers_held="26:11",
                        experts_held=(0, 8), tensor_share="0:8"),
     508_189_680, tuple(range(26, 37))),
    ("nemotron3s_tiny", dict(layers_held=(1, 5), experts_held=(2, 4),
                             tensor_share=(1, 2)), None, (1, 2, 3, 4, 5)),
])
def test_nemotron3s_traces_and_counts_its_parameters(name, share, want, layers):
    model, meta = zoo.create_model(name, **share)
    assert meta.task == "lm" and not meta.has_carry and meta.fused_loss
    assert meta.dataset == "tokens"
    x = _example_input(meta)
    variables = jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)}, x, train=False))
    params = variables["params"]
    if want is not None:
        assert sum(int(np.prod(leaf.shape))
                   for leaf in jax.tree_util.tree_leaves(params)) == want
    # a stage's layers keep their published numbers
    assert {k for k in params if k.startswith("layer_")} \
        == {f"layer_{i}" for i in layers}
    assert set(params["out"]) == {"norm", "head"}  # untied
    # a layer is ONE mixer behind ONE norm, its kind the pattern's letter
    pattern = model.shape.pattern
    for i in layers:
        leaves = set(params[f"layer_{i}"])
        assert {"M": "in_proj", "E": "router", "*": "wq"}[pattern[i]] in leaves
        assert len(leaves & {"in_proj", "router", "wq"}) == 1
        assert [k for k in leaves if k.endswith("norm")] in (
            ["norm"], ["gate_norm", "norm"], ["norm", "gate_norm"])
    if name == "nemotron3s_tiny":
        logits = jax.eval_shape(lambda v: model.apply(v, x), variables)
        assert logits.shape == (2, 64, meta.num_classes)
        per_token, stats = jax.eval_shape(
            lambda v: model.apply(v, x, targets=x, train=True), variables)
        assert per_token.shape == (2, 64)
        assert stats["health/ssm_state"].shape == (2,)  # Mamba layers held
        assert stats["health/moe_tokens"].shape == (2, 4)  # `E` layers held
        assert stats["health/moe_bias_swap"].shape == (2,)
        assert stats["health/moe_latent_rms"].shape == (2,)
        assert stats["health/moe_relu2_active"].shape == (2,)


@pytest.mark.parametrize(
    "name,lo,hi",
    [
        ("resnet20", 0.2e6, 0.4e6),
        ("resnet50", 23e6, 28e6),
        ("resnet152", 55e6, 65e6),
        ("densenet121", 6e6, 10e6),
        ("vgg16i", 130e6, 145e6),
        ("alexnet", 55e6, 65e6),
        ("vgg16", 14e6, 16e6),
    ],
)
def test_param_counts(name, lo, hi):
    model, meta = zoo.create_model(name)
    x = _example_input(meta, batch=1)
    variables = jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)}, x, train=False)
    )
    n = sum(
        int(np.prod(l.shape))
        for l in jax.tree_util.tree_leaves(variables["params"])
    )
    assert lo <= n <= hi, f"{name}: {n} params outside [{lo}, {hi}]"


@pytest.mark.parametrize("name", ["mnistnet", "lenet", "resnet20", "caffe_cifar", "fcn5net", "lr"])
def test_small_model_forward(name):
    model, meta = zoo.create_model(name)
    x = jnp.asarray(
        np.random.RandomState(0).randn(2, *meta.input_shape), jnp.float32
    )
    variables = model.init({"params": jax.random.PRNGKey(0)}, x, train=False)
    out = model.apply(variables, x, train=False)
    assert out.shape == (2, meta.num_classes)
    assert np.isfinite(np.asarray(out)).all()


def test_batchnorm_mutable_train_step():
    model, meta = zoo.create_model("resnet20")
    x = jnp.ones((2,) + meta.input_shape)
    variables = model.init({"params": jax.random.PRNGKey(0)}, x, train=False)
    out, updates = model.apply(
        variables, x, train=True,
        mutable=["batch_stats"],
        rngs={"dropout": jax.random.PRNGKey(1)},
    )
    assert "batch_stats" in updates
    assert out.shape == (2, 10)


def test_googlenet_aux_heads():
    model, meta = zoo.create_model("googlenet", num_classes=10)
    x = jnp.zeros((1, 224, 224, 3))
    variables = jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)}, x, train=True)
    )
    outs = jax.eval_shape(
        lambda v: model.apply(
            v, x, train=True, mutable=["batch_stats"],
            rngs={"dropout": jax.random.PRNGKey(1)},
        ),
        variables,
    )
    (logits, aux1, aux2), _ = outs
    assert logits.shape == aux1.shape == aux2.shape == (1, 10)


def test_ptb_lstm_carry():
    model, meta = zoo.create_model("lstm", num_classes=200)  # tiny vocab
    tokens = jnp.zeros((2, 7), dtype=jnp.int32)
    variables = model.init(
        {"params": jax.random.PRNGKey(0)}, tokens, train=False
    )
    logits, carry = model.apply(variables, tokens, train=False)
    assert logits.shape == (2, 7, 200)
    assert len(carry) == 2  # two layers
    # carry round-trips
    logits2, carry2 = model.apply(variables, tokens, carry=carry, train=False)
    assert logits2.shape == logits.shape
    c0 = np.asarray(carry[0][0])
    assert np.isfinite(c0).all()


def test_deepspeech_forward():
    from mgwfbp_tpu.models.deepspeech import DeepSpeech

    model = DeepSpeech(num_classes=29, hidden_size=32, num_layers=2)
    spect = jnp.asarray(
        np.random.RandomState(0).randn(2, 40, 161), jnp.float32
    )
    lengths = jnp.asarray([40, 25], jnp.int32)
    variables = model.init(
        {"params": jax.random.PRNGKey(0)}, spect, lengths, train=False
    )
    logits, out_lengths = model.apply(variables, spect, lengths, train=False)
    assert logits.shape[0] == 2 and logits.shape[2] == 29
    # Reference conv geometry: time downsampled 2x (kernel 11, strides 2,1)
    # -> 40 frames become 20; freq 161 -> 81 -> 41 (kernels 41/21 stride 2).
    assert logits.shape[1] == 20
    assert int(out_lengths[0]) == 20
    assert int(out_lengths[0]) >= int(out_lengths[1])
    assert np.isfinite(np.asarray(logits)).all()


def test_deepspeech_rnn_feature_width_matches_reference():
    # After the conv stack, freq 161 -> 41 bins x 32 channels = 1312 features
    # (reference lstm_models.py rnn_input_size arithmetic).
    from mgwfbp_tpu.models.deepspeech import DeepSpeech

    model = DeepSpeech(num_classes=29, hidden_size=16, num_layers=1)
    spect = jnp.zeros((1, 8, 161))
    variables = jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)}, spect, train=False)
    )
    cell = variables["params"]["rnn_0"]["OptimizedLSTMCell_0"]
    assert cell["ii"]["kernel"].shape[0] == 41 * 32


def test_aux_head_structure_mode_independent():
    # init(train=False) must still create aux params so a later train-mode
    # apply finds them (structure can't depend on the runtime mode).
    model, _ = zoo.create_model("googlenet", num_classes=10)
    x = jnp.zeros((1, 224, 224, 3))
    variables = jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)}, x, train=False)
    )
    assert "aux1" in variables["params"] and "aux2" in variables["params"]
    outs = jax.eval_shape(
        lambda v: model.apply(
            v, x, train=True, mutable=["batch_stats"],
            rngs={"dropout": jax.random.PRNGKey(1)},
        ),
        variables,
    )
    (logits, aux1, aux2), _ = outs
    assert logits.shape == aux1.shape == aux2.shape == (1, 10)


def test_dataset_override_retargets_input_shape():
    _, meta = zoo.create_model("resnet50", dataset="cifar10")
    assert meta.input_shape == (32, 32, 3)
    assert meta.num_classes == 10


def test_registry_dataset_override():
    model, meta = zoo.create_model("resnet20", dataset="cifar10")
    assert meta.num_classes == 10
    model, meta = zoo.create_model("vgg16", num_classes=100)
    assert meta.num_classes == 100


def _param_count(name):
    model, meta = zoo.create_model(name)
    x = jnp.zeros((1,) + tuple(meta.input_shape), meta.input_dtype)
    v = model.init({"params": jax.random.PRNGKey(0)}, x, train=False)
    return sum(int(a.size) for a in jax.tree_util.tree_leaves(v["params"]))


def test_parameter_counts_match_canonical_cifar():
    """Parameter counts pinned to the canonical architecture sizes — a
    wrong block layout / channel width / head count moves these immediately
    (reference models/resnet.py CifarResNet). Cheap CIFAR family only; the
    big ImageNet/LSTM inits live in the slow-marked sibling."""
    for name, want in {
        "resnet20": 272_474,
        "resnet56": 855_770,
        "resnet110": 1_730_714,
    }.items():
        assert _param_count(name) == want, name


@pytest.mark.slow
def test_parameter_counts_match_canonical_imagenet():
    """Canonical counts for the heavyweight models (torchvision
    resnet50/alexnet/densenet, googlenet-with-aux, PTB 2x1500 LSTM)."""
    for name, want in {
        "resnet50": 25_557_032,
        "densenet121": 7_978_856,
        "googlenet": 13_385_816,
        "alexnet": 61_100_840,
        "lstm": 66_022_000,
    }.items():
        assert _param_count(name) == want, name


def test_deepspeech_default_is_unidirectional_lookahead():
    """The reference's an4 config runs create_net defaults
    (models/lstman4.py:8: bidirectional=False), i.e. the unidirectional +
    Lookahead variant; the registry default must match, with bidirectional
    selectable."""
    from mgwfbp_tpu.models.deepspeech import DeepSpeech

    m = DeepSpeech(num_classes=29, hidden_size=8, num_layers=1)
    assert m.bidirectional is False
    x = jnp.zeros((2, 32, 161), jnp.float32)
    v = m.init({"params": jax.random.PRNGKey(0)}, x, train=False)
    # lookahead layer present in the unidirectional param tree
    names = " ".join(
        jax.tree_util.keystr(kp)
        for kp, _ in jax.tree_util.tree_flatten_with_path(v["params"])[0]
    )
    assert "Lookahead" in names
    bi = DeepSpeech(num_classes=29, hidden_size=8, num_layers=1,
                    bidirectional=True)
    vb = bi.init({"params": jax.random.PRNGKey(0)}, x, train=False)
    bnames = " ".join(
        jax.tree_util.keystr(kp)
        for kp, _ in jax.tree_util.tree_flatten_with_path(vb["params"])[0]
    )
    assert "Lookahead" not in bnames
