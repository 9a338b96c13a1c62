"""The harness takes the Qwen3-Next configuration without an edit:
`run.run_once` driven on the CPU mesh with the tiny configuration file ends
`correct`; the float8 reference in the program's place does not. The new
cell's entries in BENCHMARK.json (AFTER Phi-4-mini-flash's, wherever later
entries put them in their lists), the configuration file against the
catalog's row, and the three readers on hand-made `step` events."""

import json
import os

import pytest

from bench_paths import BENCH, load

CELL = "qwen3next-plain-1chip"
CONFIG = "qwen3next-l4-e32of512-v18992-t8192-bf16"
NEW_METRICS = ("delta_state_rms", "delta_beta_mean", "shared_gate_mean")
SOURCE = ("https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/"
          "blob/main/config.json")
OTHERS = ("ssm_state_rms", "ssm_log_decay_min", "attn_gate_mean",
          "moe_score_sum", "sel_scan_state_rms", "gmu_gate_rms",
          "diff_lambda_mean")


@pytest.fixture(scope="module")
def run_module():
    return load("run.py")


@pytest.fixture
def restored_environment():
    """`run.apply_env` writes the cell's environment into this process for
    good; a later test file of the same worker would train on a set sized
    for this one."""
    before = dict(os.environ)
    yield
    for key in set(os.environ) - set(before):
        del os.environ[key]
    os.environ.update(before)


def tiny_spec(run_module) -> dict:
    return {
        "bench": run_module.load_json(run_module.BENCHMARK_FILE),
        "cell": {"name": "tiny-qwen3next", "config": "tiny-qwen3next-f32",
                 "traffic": "tiny", "chips": 8},
        "config": run_module.load_json(
            os.path.join(BENCH, "configs", "tiny-qwen3next-f32.json")),
        "traffic": run_module.load_json(
            os.path.join(BENCH, "traffic", "tiny.json")),
        "home": BENCH,
    }


def test_run_once_ends_correct_and_the_float8_reference_does_not(
        run_module, tmp_path, restored_environment):
    spec = tiny_spec(run_module)
    result, compared = run_module.run_once(
        spec, 4000000021, 0.5, False, str(tmp_path / "out"),
        spec["config"]["controls"]["ref-fp8"], run_module.CompileCounter(),
        rehearsal=True)
    print(json.dumps(compared))
    assert set(result) == {"correct", "attempted", "failed", "metrics", "device"}
    assert compared["sound"]["correct"] is True
    assert result["failed"] == 0 and result["attempted"] >= 3
    assert set(result["metrics"]) == {"samples_per_s", "peak_hbm_gib", "setup_s"}
    # the control: the reference in float8 is not correct, by the gradient
    # and by the loss
    assert result["correct"] is False
    for name in ("first_grad_norm_rel", "first_loss_rel"):
        limit = spec["config"]["limits"][name]["max"]
        assert compared["checks"][name] > 3 * limit
        assert compared["sound"]["checks"][name] < limit / 3
    # the stream the readers read: the counters on the step records
    stream = run_module.read_stream(os.path.join(
        str(tmp_path / "out"), "logs",
        os.listdir(str(tmp_path / "out" / "logs"))[0], "telemetry.jsonl"))
    steps = [e for e in stream
             if e["event"] == "step" and "delta_state_rms" in e]
    assert len(steps) >= 3
    run = {"window_steps": steps}
    assert 0 < load("layer_metrics/delta_state_rms.py").read(run) < 1.0
    assert 0.3 < load("layer_metrics/delta_beta_mean.py").read(run) < 0.7
    assert 0.4 < load("layer_metrics/shared_gate_mean.py").read(run) < 0.6
    # the routing counters every `held_experts` model has: a quarter of the
    # experts held, none dropped
    assert 0.1 < load("layer_metrics/moe_here_share.py").read(run) < 50
    assert load("layer_metrics/moe_dropped.py").read(run) == 0
    # no other model's counter on this model's records
    assert not set(OTHERS) & set().union(*steps)
    for other in OTHERS:
        assert load(f"layer_metrics/{other}.py").read(run) is None, other


def test_the_new_cell_resolves_and_reports_its_counters(run_module):
    spec = run_module.load_cell(CELL)
    assert spec["cell"] == {
        "name": CELL, "config": CONFIG, "traffic": "plain", "chips": 1,
        "why": spec["cell"]["why"]}
    for said in ("2 x 8,192 tokens", "closed loop", "AdamW", "delta-rule",
                 "D-256", "top-10", "320 tokens", "2,560", "16x"):
        assert said in spec["cell"]["why"], said
    assert len(spec["cell"]["why"]) <= 200
    per_layer = {m["name"] for m in run_module.cell_metrics(spec, "per_layer")}
    assert {*NEW_METRICS, "step_mfu", "step_device_ms", "device_idle"} \
        <= per_layer
    # none of the other models' counters, nor the cells' own lists
    assert not {"step_ms_p95", "boundary_ms", "exposed_comm_ms", *OTHERS,
                "moe_here_share", "moe_load_imbalance", "moe_dropped",
                "moe_group_rows"} & per_layer
    assert {m["name"] for m in run_module.cell_metrics(spec, "end_to_end")} \
        == {"samples_per_s", "peak_hbm_gib", "setup_s"}
    for old in ("resnet50-plain-1chip", "mellum2-plain-1chip",
                "granite4h-plain-1chip", "laguna-xs2-plain-1chip",
                "phi4flash-plain-1chip"):
        assert not set(NEW_METRICS) & {
            m["name"] for m in run_module.cell_metrics(
                run_module.load_cell(old), "per_layer")}
    config = spec["config"]
    assert config["image_hw"] == [8192] and config["num_classes"] == 18992
    assert config["train_cli"] == [
        "--dnn", "qwen3next", "--dataset", "tokens", "--layers-held", "4",
        "--experts-held", "0:32", "--vocab-size", "18992", "--num-steps",
        "8192", "--batch-size", "2", "--dtype", "bfloat16", "--max-epochs",
        "40", "--synthetic", "--telemetry"]
    # Mellum 2's flags but for the model and the share
    mellum = run_module.load_cell("mellum2-plain-1chip")["config"]
    changed = {"--dnn", "--experts-held", "--vocab-size"}
    flags, theirs = config["train_cli"], mellum["train_cli"]
    assert [f for f in flags if f.startswith("--")] \
        == [f for f in theirs if f.startswith("--")]
    assert all(a == b or theirs[i - 1] in changed
               for i, (a, b) in enumerate(zip(flags, theirs)))
    reference = load("references/" + config["reference"] + ".py")
    assert reference.SHARE == {
        "layers": 4, "first_expert": 0, "experts": 32, "vocab": 18992}
    assert reference.forward_macs(
        tuple(config["image_hw"]), config["num_classes"]) > 1.8e12
    # the entries: one configuration, one cell, three metrics that list it
    # alone, each AFTER Phi-4-mini-flash's (a later PR's entries may follow
    # them: nothing here says they are last)
    bench = spec["bench"]
    names = [c["name"] for c in bench["configs"]]
    assert names.count(CONFIG) == 1
    assert names.index(CONFIG) > names.index("phi4flash-l6-v25008-t8192-bf16")
    cells = [w["name"] for w in bench["workloads"]]
    assert cells.index(CELL) > cells.index("phi4flash-plain-1chip")
    assert [w["name"] for w in bench["workloads"] if w["config"] == CONFIG] \
        == [CELL]
    # nine cells at least, this one on one chip; a quarter may ask for four
    assert len(cells) >= 9
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= len(cells) // 4
    metrics = [m["name"] for m in bench["per_layer"]]
    assert all(metrics.index(n) > metrics.index("diff_lambda_mean")
               for n in NEW_METRICS)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name, layer, unit, better in (
            ("delta_state_rms", "linear attention", "rms", "lower"),
            ("delta_beta_mean", "linear attention", "ratio", "higher"),
            ("shared_gate_mean", "experts", "ratio", "higher")):
        assert by_name[name] == {
            "name": name, "unit": unit, "better": better,
            "source": "program_counter", "layer": layer,
            "moves": "samples_per_s", "workloads": [CELL]}
        assert os.path.isfile(
            os.path.join(BENCH, "layer_metrics", name + ".py"))
    # the older cells' own counters still list their cells alone
    assert by_name["sel_scan_state_rms"]["workloads"] \
        == ["phi4flash-plain-1chip"]
    assert by_name["attn_gate_mean"]["workloads"] == ["laguna-xs2-plain-1chip"]
    assert by_name["moe_here_share"]["workloads"] == ["mellum2-plain-1chip"]
    entry = bench["configs"][names.index(CONFIG)]
    assert entry["source"] == SOURCE
    assert entry["reduced"] == config["reduced"]
    assert len(entry["why"]) <= 200


def catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        return None
    with open(path) as f:
        rows = [json.loads(line) for line in f
                if '"name": "Qwen3-Next-80B-A3B-Instruct"' in line]
    return rows[0] if rows else None


def test_configuration_file_keeps_every_published_number():
    """Every key of the catalog's `config` under the same key and with the
    same value, but the keys `reduced` names; no width among those; the
    limits have their why; `parameters_held` is the leaves' count."""
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        config = json.load(f)
    published = {
        "decoder_sparse_step": 1, "full_attention_interval": 4,
        "head_dim": 256, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
        "linear_key_head_dim": 128, "linear_num_key_heads": 16,
        "linear_num_value_heads": 32, "linear_value_head_dim": 128,
        "max_position_embeddings": 262144, "mlp_only_layers": [],
        "model_type": "qwen3_next", "moe_intermediate_size": 512,
        "norm_topk_prob": True, "num_attention_heads": 16, "num_experts": 512,
        "num_experts_per_tok": 10, "num_hidden_layers": 48,
        "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 10000000,
        "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
        "use_sliding_window": False, "vocab_size": 151936,
    }
    row = catalog_row()
    if row is not None:  # the catalog beside the guide, where it is there
        assert row["source_url"] == SOURCE and SOURCE in config["source"]
        assert row["config"] == published
    held = {"num_hidden_layers": 4, "num_experts": 32, "vocab_size": 18992}
    for key, value in published.items():
        if key in held:
            assert key in config["reduced"]
            assert config[key] == held[key]
            assert config["published"][key] == value
        else:
            assert config[key] == value, key
    assert config["layer_types"] == ["linear_attention"] * 3 \
        + ["full_attention"]
    assert config["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size",
        "train_set_sequences"]
    assert not [k for k in config["reduced"]
                if k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"]
    for name in ("delta_rule", "convolution", "norms", "attention", "experts",
                 "module_left_out", "initial_weights", "optimizer", "data",
                 "memory"):
        assert name in config["assumed"], name
    for said in ("uniform draw in (0, 16)", "[1e-3, 1e-1]", "1e-6",
                 "grouped by key head", "chunk of 64"):
        assert said in config["assumed"]["delta_rule"], said
    assert "uniform in +-0.5" in config["assumed"]["convolution"]
    for said in ("start at zero", "starts at one"):
        assert said in config["assumed"]["norms"], said
    assert "multi-token-prediction" in config["assumed"]["module_left_out"]
    for said in ("twelve pipeline stages of four", "sixteen chips share",
                 "sixteen ways", "eight ways", "layers 0 to 3",
                 "experts 0 to 31", "0 to 18,991", "320 tokens", "2,560",
                 "1.03 B", "1.17 B"):
        assert said in config["deployment"], said
    for said in ("L2 norms", "both sigmoid gates", "softplus",
                 "triangular solve and carried state in float32", "AdamW",
                 "router product"):
        assert said in config["precision"], said
    assert config["published"]["parameters"] == 79674391296
    assert "LEFT OUT" in config["published"]["parameters_note"]
    for name in ("first_grad_norm_rel", "update_rel", "loss_ratio"):
        assert len(config["limits"][name]["why"]) > 40
    # the float8 control's loss reads under the sound runs' largest: the
    # number has no upper reading and is left out BY NAME, with the readings
    assert "first_loss_rel" not in config["limits"]
    left_out = config["limits_left_out"]["first_loss_rel"]
    for said in ("2.6e-5", "8.9e-6", "no upper reading",
                 "first_grad_norm_rel"):
        assert said in left_out, said
    assert "618 rows" in config["deployment"]  # the load the window ran at
    assert config["controls"]["ref-fp8"]["reference_dtype"] == "float8_e4m3fn"
    assert config["per_device_batch"] == 2
    assert config["tokens_per_step"] == 16384
    # the program's shape and the reference's state the same widths, and the
    # leaves the program declares are `parameters_held`
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mgwfbp_tpu.models import create_model
    from mgwfbp_tpu.models.qwen3next import QWEN3NEXT as S

    ref = load("references/qwen3next_share.py").SHAPE
    for mine, key in (
            (S.hidden_size, "hidden_size"), (S.num_heads, "num_attention_heads"),
            (S.num_kv_heads, "num_key_value_heads"), (S.head_dim, "head_dim"),
            (S.partial_rotary_factor, "partial_rotary_factor"),
            (S.rope_theta, "rope_theta"),
            (S.full_attention_interval, "full_attention_interval"),
            (S.linear_key_heads, "linear_num_key_heads"),
            (S.linear_value_heads, "linear_num_value_heads"),
            (S.linear_key_dim, "linear_key_head_dim"),
            (S.linear_value_dim, "linear_value_head_dim"),
            (S.linear_conv, "linear_conv_kernel_dim"),
            (S.experts_per_token, "num_experts_per_tok"),
            (S.expert_width, "moe_intermediate_size"),
            (S.shared_expert_width, "shared_expert_intermediate_size"),
            (S.rms_norm_eps, "rms_norm_eps")):
        assert mine == ref[key] == config[key], key
    assert S.num_layers == ref["num_hidden_layers"] \
        == config["published"]["num_hidden_layers"]
    assert S.num_experts == ref["num_experts"] \
        == config["published"]["num_experts"]
    assert S.vocab_size == config["published"]["vocab_size"]
    flags = config["train_cli"]
    first, count = flags[flags.index("--experts-held") + 1].split(":")
    model, _ = create_model(
        "qwen3next", num_classes=config["vocab_size"],
        layers_held=flags[flags.index("--layers-held") + 1],
        experts_held=(int(first), int(count)))
    shapes = jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)},
                           jnp.zeros((1, 8), jnp.int32), train=False))
    assert sum(int(np.prod(leaf.shape)) for leaf in
               jax.tree_util.tree_leaves(shapes)) \
        == config["parameters_held"] == 625667136
    assert list(model.layer_kinds()) == config["layer_types"]


@pytest.mark.parametrize("loss_gap, norm_gap, ratio, holds", [
    (2.6e-5, 2.97e-4, 0.9962, True),   # the sound runs' largest readings
    (2.1e-4, 2.97e-4, 0.9982, True),   # the loss alone refuses nothing ...
    (8.9e-6, 0.9325, 0.9975, False),   # ... the float8 control fails by the norm
    (1.0e-5, 5.0e-5, 1.02, False),     # a loss that rises
])
def test_the_limits_hold_the_sound_readings_and_refuse_the_control(
        run_module, capsys, loss_gap, norm_gap, ratio, holds):
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        limits = json.load(f)["limits"]
    checks = {"first_loss_rel": loss_gap, "first_grad_norm_rel": norm_gap,
              "loss_ratio": ratio, "update_rel": 9.65e-4}
    assert run_module.judge(checks, limits) is holds
    assert "first_loss_rel" in capsys.readouterr().out.split(
        "(no limit: informational)")[0]


@pytest.mark.parametrize("name,events,want", [
    ("delta_state_rms",
     [{"delta_state_rms": 0.02}, {"delta_state_rms": 0.04}, {"step": 3}],
     0.03),
    ("delta_beta_mean",
     [{"delta_beta_mean": 0.5}, {"delta_beta_mean": 0.52}, {"step": 3}], 0.51),
    ("shared_gate_mean",
     [{"shared_gate_mean": 0.49}, {"shared_gate_mean": 0.51}, {"step": 3}],
     0.50),
    ("delta_state_rms", [{"step": 1, "sel_scan_state_rms": 0.1}], None),
    ("delta_beta_mean", [{"step": 1, "moe_here": 0.25}], None),
    ("shared_gate_mean", [{"step": 1, "attn_gate_mean": 0.5}], None),
    ("shared_gate_mean", [], None),
])
def test_new_counter_readers_on_hand_made_step_events(name, events, want):
    """A program without the counters (the parent commit, another model)
    gives a reader nothing to read: None, no exception."""
    value = load(f"layer_metrics/{name}.py").read({"window_steps": events})
    assert value == (None if want is None else pytest.approx(want))
