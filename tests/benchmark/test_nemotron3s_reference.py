"""The plain reference of the Nemotron 3 Super cell
(benchmarks/references/nemotron3s_share.py) held to itself: it imports
nothing of the program, its literal recurrence, its written-out attention and
its loop over the experts are what they say (each against numbers computed by
hand or by a second plain form), the float8 control moves its numbers, and
its counts (`forward_macs`, the `*_flops_and_bytes` of the by-hand rooflines,
`SHARE`) are the configuration file's. The program against it, per layer
kind and whole, and the shares adding up, are in tests/test_nemotronh.py;
the configuration file against the catalog in test_nemotron3s_harness.py."""

import ast
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_paths import BENCH, load

CONFIG = "nemotron3s-l11-tp8-e8of512-v16384-t8192-bf16"


@pytest.fixture(scope="module")
def ref():
    """The reference module at the tiny shape (the wrapper's own copy)."""
    return load("references/nemotron3s_share_tiny.py").full


@pytest.fixture(scope="module")
def full():
    return load("references/nemotron3s_share.py")


@pytest.mark.parametrize("name", [
    "nemotron3s_share.py", "nemotron3s_share_tiny.py"])
def test_the_reference_imports_nothing_of_the_program(name):
    with open(os.path.join(BENCH, "references", name)) as f:
        tree = ast.parse(f.read())
    found = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            found |= {a.name for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            found.add(node.module or "")
    assert not {m for m in found if m.startswith(("mgwfbp_tpu", "ops"))}
    assert found <= {"__future__", "functools", "importlib.util", "os",
                     "jax", "jax.numpy"}


def test_the_recurrence_against_a_loop_in_numpy(ref):
    """S_t = exp(dt_t a) S_{t-1} + dt_t x_t B_t^T, y_t = S_t C_t, in float64
    numpy one position after another; T 64 over blocks of 24: two whole
    blocks and a short one."""
    rng = np.random.RandomState(0)
    t, h, p, n = 64, 2, 4, 8
    xs = rng.standard_normal((t, h, p))
    dt = rng.uniform(0.01, 0.2, (t, h))
    a = -rng.uniform(1.0, 8.0, h)
    b, c = rng.standard_normal((t, n)), rng.standard_normal((t, n))
    s = np.zeros((h, p, n))
    want = np.zeros((t, h, p))
    for i in range(t):
        s = np.exp(dt[i] * a)[:, None, None] * s \
            + (dt[i][:, None] * xs[i])[:, :, None] * b[i][None, None, :]
        want[i] = s @ c[i]
    got = ref.recurrence(*(jnp.asarray(v, jnp.float32)
                           for v in (xs, dt, a, b, c)))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


def test_the_gated_norm_is_by_group_and_the_gate_comes_first(ref):
    """Two groups of two channels: v = y silu(z), each pair over the root of
    its own mean square; over all four at once the numbers differ."""
    y = jnp.asarray([[1.0, 3.0, 10.0, 30.0]])
    z = jnp.asarray([[0.0, 0.0, 0.0, 0.0]])  # silu(0) = 0 ...
    assert not np.asarray(ref.gated_group_norm(
        y, z, jnp.ones(4), 2, 1e-5)).any()  # ... so the gate comes first
    z = jnp.full((1, 4), 50.0)  # silu(50) = 50
    got = np.asarray(ref.gated_group_norm(
        y, z, jnp.asarray([1.0, 1.0, 2.0, 2.0]), 2, 0.0))[0]
    r = np.sqrt(5.0)  # a pair (a, 3a) over its rms a sqrt(5)
    np.testing.assert_allclose(got, [1 / r, 3 / r, 2 / r, 6 / r], rtol=1e-6)
    at_once = np.asarray(ref.gated_group_norm(
        y, z, jnp.ones(4), 1, 0.0))[0]
    assert abs(at_once[0] - 1 / r) > 0.3


def test_the_router_chooses_by_score_plus_bias_and_weighs_by_score(ref):
    """Four experts, two a token: scores sigmoid(0.2, 0.1, 0.0, -0.1); the
    bias lifts expert 3 over expert 1; the weights are the chosen SCORES over
    their sum (+ 1e-20), times the factor."""
    u = jnp.asarray([[1.0]])
    router = jnp.asarray([[0.2, 0.1, 0.0, -0.1]])
    bias = jnp.asarray([0.0, 0.0, 0.0, 0.1])
    idx, w = ref.route(u, router, bias, 2, 5.0)
    assert sorted(np.asarray(idx)[0]) == [0, 3]
    s = 1 / (1 + np.exp(-np.asarray([0.2, -0.1])))
    got = dict(zip(np.asarray(idx)[0], np.asarray(w)[0]))
    np.testing.assert_allclose(
        [got[0], got[3]], 5.0 * s / s.sum(), rtol=1e-6)
    unbiased, _ = ref.route(u, router, jnp.zeros(4), 2, 5.0)
    assert sorted(np.asarray(unbiased)[0]) == [0, 1]


def test_an_expert_has_two_products_and_no_gate(ref):
    """relu(u W1)^2 W2 by hand: u = (1, -1), W1 = [[1, 2], [3, 1]] -> (-2,
    1) -> relu^2 (0, 1) -> W2's second row."""
    u = jnp.asarray([[1.0, -1.0]])
    w1 = jnp.asarray([[1.0, 2.0], [3.0, 1.0]])
    w2 = jnp.asarray([[5.0, 7.0], [11.0, 13.0]])
    np.testing.assert_allclose(ref.relu2_mlp(u, w1, w2), [[11.0, 13.0]])


def test_the_float8_control_moves_loss_and_gradient(ref):
    """The control's operands rounded to e4m3: the share's loss moves by
    1e-5 or more and its gradient norm by a tenth or more, as
    `configs/tiny-nemotron3s-f32.json`'s limits need."""
    from mgwfbp_tpu.models import create_model

    model, _ = create_model(
        "nemotron3s_tiny", num_classes=256, layers_held=(1, 5),
        experts_held=(2, 4), tensor_share=(1, 2))
    rng = np.random.RandomState(0)
    x = rng.randint(0, 256, (2, 64))
    y = rng.randint(0, 256, (2, 64))
    params = model.init(
        {"params": jax.random.PRNGKey(0)}, jnp.asarray(x[:1]),
        train=False)["params"]
    host = {
        "/".join(str(k.key) for k in path): np.asarray(leaf)
        for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]}
    tiny = load("references/nemotron3s_share_tiny.py")
    sound = tiny.first_step(host, x, y, seed=0, shards=1)
    low = tiny.first_step(host, x, y, seed=0, shards=1, dtype="float8_e4m3fn")
    assert abs(low["loss"] - sound["loss"]) / sound["loss"] > 1e-5
    assert abs(low["grad_norm"] - sound["grad_norm"]) / sound["grad_norm"] \
        > 0.1
    assert 5.0 < sound["loss"] < 6.0  # ln 256 = 5.55 at seeded weights


def test_forward_macs_and_the_by_hand_roofline_counts(full):
    """The published widths at the share: 235 M multiply-accumulates a token
    forward, by the issue's split (Mamba projections 29%, the five expert
    blocks 38% of which the held experts 4 points, the head 29%, attention
    4); 15 TFLOP a step with the recomputation; the three `*_flops_and_bytes`
    by hand."""
    t, d = 8192, 4096
    assert full.held() == {
        "mamba_heads": 16, "groups": 1, "heads": 4, "kv_heads": 1,
        "shared_columns": 672, "evaluations": 22 * 8 / 512}
    pairs = full.causal_pairs(t)
    assert pairs == t * (t + 1) // 2
    chunk_pairs = (t // 128) * (128 * 129 // 2)
    scan = chunk_pairs * (128 + 1024) + 2 * t * 1024 * 128
    assert full.scan_macs(t) == scan
    mamba = t * d * (1024 + 1280 + 16) + t * 4 * 1280 + scan + t * 1024 * d
    attention = t * d * 128 * (2 * 4 + 2) + pairs * 4 * 128 * 2
    experts = t * (22 * 8 / 512) * 2 * 1024 * 2688
    moe = t * (d * 512 + 2 * d * 1024 + 2 * d * 672) + experts
    head = t * d * 16384
    want = int(5 * mamba + attention + 5 * moe + head)
    assert full.forward_macs((t,), 16384) == want
    assert 234e6 < want / t < 238e6
    assert 0.28 < 5 * (mamba - scan) / want < 0.30
    assert 0.37 < 5 * moe / want < 0.39
    assert 0.03 < 5 * experts / want < 0.05
    assert 0.28 < head / want < 0.30
    assert 0.03 < attention / want < 0.05
    assert 14e12 < 8 * want < 16e12  # forward, recomputed forward, backward
    # a held expert's even load: 352 rows of the 8,192 tokens' 180,224
    assert t * 22 // 512 == 352 and t * 22 == 180224
    bill = full.latent_moe_flops_and_bytes(t)
    assert bill["flops"] == 3 * 2 * 2816 * 2 * 1024 * 2688
    assert bill["bytes"] == 2 * 3 * (
        8 * 2 * 1024 * 2688 + 2816 * (2 * 1024 + 2 * 2688))
    # 352 rows an expert: the two products sit AT the chip's ridge (197
    # TFLOP/s over 819 GB/s = 240 FLOPs a byte), the weights' traffic as
    # dear as the arithmetic
    assert 230 < bill["flops"] / bill["bytes"] < 250
    scan_bill = full.scan_flops_and_bytes(t)
    assert scan_bill["flops"] == 6 * scan
    assert scan_bill["bytes"] == 3 * (t * 1280 * 2 + t * 16 * 4) \
        + 2 * t * 1024 * 2
    core = full.attention_core_flops_and_bytes(t)
    assert core["flops"] == 2 * 3 * pairs * 4 * 128 * 2
    assert core["flops"] / core["bytes"] > 1000


def test_the_share_and_the_shape_are_the_configuration_files(full):
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        config = json.load(f)
    share = full.SHARE
    flags = config["train_cli"]

    def flag(name):
        return tuple(int(v) for v in flags[flags.index(name) + 1].split(":"))

    assert (share["first_layer"], share["layers"]) == flag("--layers-held")
    assert (share["first_expert"], share["experts"]) == flag("--experts-held")
    assert share["tensor"] == flag("--tensor-share")
    first = share["first_layer"]
    assert full.SHAPE["hybrid_override_pattern"][
        first:first + share["layers"]] == config["hybrid_override_pattern"]
    for key in ("hidden_size", "mamba_head_dim", "ssm_state_size",
                "conv_kernel", "chunk_size", "head_dim",
                "num_experts_per_tok", "moe_intermediate_size",
                "moe_latent_size", "moe_shared_expert_intermediate_size",
                "routed_scaling_factor", "layer_norm_epsilon"):
        assert full.SHAPE[key] == config[key], key
    held = full.held()
    assert held["mamba_heads"] == config["mamba_num_heads"]
    assert held["groups"] == config["n_groups"]
    assert held["heads"] == config["num_attention_heads"]
    assert held["kv_heads"] == config["num_key_value_heads"]
    assert held["shared_columns"] == config["moe_shared_expert_columns_held"]


def test_the_published_total_from_the_configuration_files_own_keys():
    """120,668,707,840 parameters from the keys of the configuration file and
    its `published` counts, without the prediction module: the issue's table
    (M 109,640,064; * 35,655,680; E outside its routed experts 54,530,560; a
    routed expert 5,505,024)."""
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        c = json.load(f)
    pub = c["published"]
    d, n = c["hidden_size"], c["ssm_state_size"]
    heads, groups = pub["mamba_num_heads"], pub["n_groups"]
    inner = heads * c["mamba_head_dim"]
    assert inner == c["expand"] * d
    channels = inner + 2 * groups * n
    mamba = (d + d * (inner + channels + heads)
             + (c["conv_kernel"] + 1) * channels + 3 * heads + inner
             + inner * d)
    q = pub["num_attention_heads"] * c["head_dim"]
    kv = pub["num_key_value_heads"] * c["head_dim"]
    attention = d + d * q + 2 * d * kv + q * d
    expert = 2 * c["moe_latent_size"] * c["moe_intermediate_size"]
    moe = (d + d * pub["n_routed_experts"] + pub["n_routed_experts"]
           + 2 * d * c["moe_latent_size"]
           + 2 * d * c["moe_shared_expert_intermediate_size"])
    assert (mamba, attention, moe, expert) == (
        109640064, 35655680, 54530560, 5505024)
    pattern = pub["hybrid_override_pattern"]
    assert len(pattern) == pub["num_hidden_layers"] == 88
    total = (2 * pub["vocab_size"] * d + d + pattern.count("M") * mamba
             + pattern.count("*") * attention
             + pattern.count("E") * (moe + pub["n_routed_experts"] * expert))
    assert total == pub["parameters"] == 120668707840
    assert round(total / 1e9, 2) == 120.67
    # the nine stages that each end at an attention layer but the last
    runs = [len(r) + 1 for r in pattern.split("*")]
    runs[-1] -= 1
    assert runs == [8, 9, 9, 11, 11, 11, 11, 9, 9]
    assert pattern[26:37] == c["hybrid_override_pattern"] == "EMEMEMEMEM*"
    assert sum(runs[:3]) == 26
