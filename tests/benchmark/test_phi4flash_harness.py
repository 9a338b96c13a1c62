"""The harness takes the Phi-4-mini-flash configuration without an edit:
`run.run_once` driven on the CPU mesh with the tiny configuration file ends
`correct`; the float8 reference in the program's place does not. The new
cell's entries in BENCHMARK.json (wherever later entries put them in their
lists), the configuration file against the catalog's row, and the three
readers on hand-made `step` events."""

import json
import os

import pytest

from bench_paths import BENCH, load

CELL = "phi4flash-plain-1chip"
CONFIG = "phi4flash-l6-v25008-t8192-bf16"
NEW_METRICS = ("sel_scan_state_rms", "gmu_gate_rms", "diff_lambda_mean")
SOURCE = ("https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/"
          "blob/main/config.json")


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
        "cell": {"name": "tiny-phi4flash", "config": "tiny-phi4flash-f32",
                 "traffic": "tiny", "chips": 8},
        "config": run_module.load_json(
            os.path.join(BENCH, "configs", "tiny-phi4flash-f32.json")),
        "traffic": run_module.load_json(
            os.path.join(BENCH, "traffic", "tiny.json")),
        "home": BENCH,
    }


def test_run_once_ends_correct_and_the_float8_reference_does_not(
        run_module, tmp_path, restored_environment):
    spec = tiny_spec(run_module)
    result, compared = run_module.run_once(
        spec, 3000000021, 0.5, False, str(tmp_path / "out"),
        spec["config"]["controls"]["ref-fp8"], run_module.CompileCounter(),
        rehearsal=True)
    print(json.dumps(compared))
    assert set(result) == {"correct", "attempted", "failed", "metrics", "device"}
    assert compared["sound"]["correct"] is True
    assert result["failed"] == 0 and result["attempted"] >= 3
    assert set(result["metrics"]) == {"samples_per_s", "peak_hbm_gib", "setup_s"}
    # the control: the reference in float8 is not correct, by the gradient
    assert result["correct"] is False
    limit = spec["config"]["limits"]["first_grad_norm_rel"]["max"]
    assert compared["checks"]["first_grad_norm_rel"] > 3 * limit
    assert compared["sound"]["checks"]["first_grad_norm_rel"] < limit / 3
    # the stream the readers read: the counters on the step records
    stream = run_module.read_stream(os.path.join(
        str(tmp_path / "out"), "logs",
        os.listdir(str(tmp_path / "out" / "logs"))[0], "telemetry.jsonl"))
    steps = [e for e in stream
             if e["event"] == "step" and "sel_scan_state_rms" in e]
    assert len(steps) >= 3
    run = {"window_steps": steps}
    assert 0 < load("layer_metrics/sel_scan_state_rms.py").read(run) < 1.0
    assert 0 < load("layer_metrics/gmu_gate_rms.py").read(run) < 1.0
    # lam0 of the tiny model's layers 1, 3, 5, 7: 0.36, 0.56, 0.67, 0.73
    assert 0.4 < load("layer_metrics/diff_lambda_mean.py").read(run) < 0.75
    # no other model's counter on this model's records
    assert not {"ssm_state_rms", "ssm_log_decay_min", "attn_gate_mean",
                "moe_here", "moe_score_sum"} & set().union(*steps)
    for other in ("ssm_state_rms", "ssm_log_decay_min", "attn_gate_mean",
                  "moe_here_share", "moe_score_sum", "moe_group_rows"):
        assert load(f"layer_metrics/{other}.py").read(run) is None


def test_the_new_cell_resolves_and_reports_its_counters(run_module):
    spec = run_module.load_cell(CELL)
    assert spec["cell"] == {
        "name": CELL, "config": CONFIG, "traffic": "plain", "chips": 1,
        "why": spec["cell"]["why"]}
    for said in ("1 sequence of 8,192 tokens", "closed loop", "AdamW",
                 "five of six mixers", "differential combine",
                 "samples_per_s counts sequences"):
        assert said in spec["cell"]["why"]
    assert len(spec["cell"]["why"]) <= 200
    per_layer = {m["name"] for m in run_module.cell_metrics(spec, "per_layer")}
    assert {*NEW_METRICS, "step_mfu", "step_device_ms", "device_idle"} \
        <= per_layer
    assert not {"step_ms_p95", "boundary_ms", "exposed_comm_ms",
                "ssm_state_rms", "ssm_log_decay_min", "attn_gate_mean",
                "moe_here_share", "moe_load_imbalance", "moe_dropped",
                "moe_score_sum", "moe_group_rows"} & per_layer
    assert {m["name"] for m in run_module.cell_metrics(spec, "end_to_end")} \
        == {"samples_per_s", "peak_hbm_gib", "setup_s"}
    for old in ("resnet50-plain-1chip", "mellum2-plain-1chip",
                "granite4h-plain-1chip", "laguna-xs2-plain-1chip"):
        assert not set(NEW_METRICS) & {
            m["name"] for m in run_module.cell_metrics(
                run_module.load_cell(old), "per_layer")}
    config = spec["config"]
    assert config["image_hw"] == [8192] and config["num_classes"] == 25008
    assert config["train_cli"] == [
        "--dnn", "phi4flash", "--dataset", "tokens", "--layers-held", "14:6",
        "--vocab-size", "25008", "--num-steps", "8192", "--batch-size", "1",
        "--dtype", "bfloat16", "--max-epochs", "40", "--synthetic",
        "--telemetry"]
    granite = run_module.load_cell("granite4h-plain-1chip")["config"]
    changed = {"--dnn", "--layers-held", "--vocab-size"}
    flags, theirs = config["train_cli"], granite["train_cli"]
    assert [f for f in flags if f.startswith("--")] \
        == [f for f in theirs if f.startswith("--")]
    assert all(a == b or theirs[i - 1] in changed
               for i, (a, b) in enumerate(zip(flags, theirs)))
    reference = load("references/" + config["reference"] + ".py")
    assert reference.SHARE == {"first_layer": 14, "layers": 6}
    assert reference.forward_macs(
        tuple(config["image_hw"]), config["num_classes"]) > 5e12
    # the entries: one configuration, one cell, three metrics that list it
    # alone, each after everything PR 37's benchmark had (a later PR's
    # entries may follow them)
    bench = spec["bench"]
    names = [c["name"] for c in bench["configs"]]
    assert names.count(CONFIG) == 1
    assert names.index(CONFIG) > names.index(
        "laguna-xs2-l5-e32of256-v12544-t8192-bf16")
    cells = [w["name"] for w in bench["workloads"]]
    assert cells.index(CELL) > cells.index("laguna-xs2-plain-1chip")
    assert [w["name"] for w in bench["workloads"] if w["config"] == CONFIG] \
        == [CELL]
    metrics = [m["name"] for m in bench["per_layer"]]
    assert all(metrics.index(n) > metrics.index("setup_compiles")
               for n in NEW_METRICS)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name, layer, unit, better in (
            ("sel_scan_state_rms", "state space", "rms", "lower"),
            ("gmu_gate_rms", "state space", "rms", "higher"),
            ("diff_lambda_mean", "attention", "ratio", "higher")):
        assert by_name[name] == {
            "name": name, "unit": unit, "better": better,
            "source": "program_counter", "layer": layer,
            "moves": "samples_per_s", "workloads": [CELL]}
        assert os.path.isfile(
            os.path.join(BENCH, "layer_metrics", name + ".py"))
    # the older cells' own counters still list their cells alone
    assert by_name["ssm_state_rms"]["workloads"] == ["granite4h-plain-1chip"]
    assert by_name["attn_gate_mean"]["workloads"] == ["laguna-xs2-plain-1chip"]
    entry = bench["configs"][names.index(CONFIG)]
    assert entry["source"] == SOURCE
    assert entry["reduced"] == config["reduced"]


def catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        return None
    with open(path) as f:
        rows = [json.loads(line) for line in f
                if '"Phi-4-mini-flash-reasoning"' in line]
    return rows[0] if rows else None


def test_configuration_file_keeps_every_published_number():
    """Every key of the catalog's `config` under the same key and with the
    same value, but the keys `reduced` names; no width among those; the
    limits have their why; `parameters_held` is the leaves' count."""
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        config = json.load(f)
    published = {
        "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
        "intermediate_size": 10240, "layer_norm_eps": 1e-05,
        "max_position_embeddings": 262144, "mb_per_layer": 2,
        "model_type": "phi4flash", "num_attention_heads": 40,
        "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
        "sliding_window": 512, "tie_word_embeddings": True,
        "mlp_bias": False, "lm_head_bias": False, "vocab_size": 200064,
    }
    row = catalog_row()
    if row is not None:  # the catalog beside the guide, where it is there
        assert row["source_url"] == SOURCE and SOURCE in config["source"]
        assert row["config"] == published
    held = {"num_hidden_layers": 6, "vocab_size": 25008}
    for key, value in published.items():
        if key in held:
            assert key in config["reduced"]
            assert config[key] == held[key]
            assert config["published"][key] == value
        else:
            assert config[key] == value, key
    assert config["layer_types"] == [
        "mamba", "sliding_attention", "mamba", "full_attention", "gmu",
        "cross_attention"]
    assert config["first_layer"] == 14
    assert config["reduced"] == [
        "num_hidden_layers", "layer_types", "vocab_size",
        "train_set_sequences"]
    assert not [k for k in config["reduced"]
                if k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"]
    for name in ("mamba", "attention", "initial_weights", "optimizer", "data",
                 "memory"):
        assert name in config["assumed"]
    for said in ("d_state 16", "d_conv 4", "expand 2", "dt_rank 160"):
        assert said in config["assumed"]["mamba"], said
    for said in ("even / odd", "0.8 - 0.6 exp(-0.3 l)", "eps 1e-5", "biases"):
        assert said in config["assumed"]["attention"], said
    for said in ("two four-chip v5e hosts", "eight ways", "layers 14 to 19",
                 "0 to 25,007", "every kind of layer", "915 M"):
        assert said in config["deployment"], said
    for said in ("LayerNorm statistics", "differential combine", "softplus",
                 "the scan entirely in float32", "AdamW"):
        assert said in config["precision"], said
    for name in ("first_grad_norm_rel", "update_rel", "loss_ratio"):
        assert len(config["limits"][name]["why"]) > 40
    # the float8 control's loss reads under the sound runs' largest: the
    # number has no upper reading and is left out BY NAME, with the readings
    assert "first_loss_rel" not in config["limits"]
    left_out = config["limits_left_out"]["first_loss_rel"]
    for said in ("7.0e-5", "5.1e-6", "no upper reading",
                 "first_grad_norm_rel"):
        assert said in left_out, said
    assert config["controls"]["ref-fp8"]["reference_dtype"] == "float8_e4m3fn"
    # the program's shape and the reference's state the same widths, and the
    # leaves the program declares are `parameters_held`
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mgwfbp_tpu.models import create_model
    from mgwfbp_tpu.models.phi4flash import PHI4FLASH as S

    ref = load("references/phi4flash_share.py").SHAPE
    assert S.hidden_size == ref["hidden_size"] == config["hidden_size"]
    assert S.intermediate_size == ref["intermediate_size"] \
        == config["intermediate_size"]
    assert S.num_heads == ref["num_attention_heads"] \
        == config["num_attention_heads"]
    assert S.num_kv_heads == ref["num_key_value_heads"] \
        == config["num_key_value_heads"]
    assert S.head_dim == ref["head_dim"] == 64
    assert S.sliding_window == ref["sliding_window"] == config["sliding_window"]
    assert S.mb_per_layer == ref["mb_per_layer"] == config["mb_per_layer"]
    assert S.layer_norm_eps == ref["layer_norm_eps"] == config["layer_norm_eps"]
    assert S.num_layers == ref["num_hidden_layers"] \
        == config["published"]["num_hidden_layers"]
    assert S.vocab_size == config["published"]["vocab_size"]
    assert (S.mamba_state, S.mamba_conv, S.mamba_expand, S.mamba_dt_rank) == (
        ref["mamba_d_state"], ref["mamba_d_conv"], ref["mamba_expand"],
        ref["mamba_dt_rank"]) == (16, 4, 2, 160)
    flags = config["train_cli"]
    model, _ = create_model(
        "phi4flash", num_classes=config["vocab_size"],
        layers_held=flags[flags.index("--layers-held") + 1])
    shapes = jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)},
                           jnp.zeros((1, 8), jnp.int32), train=False))
    assert sum(int(np.prod(leaf.shape)) for leaf in
               jax.tree_util.tree_leaves(shapes)) == config["parameters_held"]
    assert [S.kind(i) for i in model.layer_indices()] == config["layer_types"]


@pytest.mark.parametrize("loss_gap, norm_gap, ratio, holds", [
    (7.0e-5, 8.3e-5, 0.959, True),    # the sound runs' largest readings
    (2.7e-4, 8.3e-5, 0.992, True),    # the loss alone refuses nothing ...
    (6.3e-5, 0.935, 0.987, False),    # ... the float8 control fails by the norm
    (1.0e-5, 5.0e-5, 1.02, False),    # a loss that rises
])
def test_the_limits_hold_the_sound_readings_and_refuse_the_control(
        run_module, capsys, loss_gap, norm_gap, ratio, holds):
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        limits = json.load(f)["limits"]
    checks = {"first_loss_rel": loss_gap, "first_grad_norm_rel": norm_gap,
              "loss_ratio": ratio, "update_rel": 5.2e-4}
    assert run_module.judge(checks, limits) is holds
    assert "first_loss_rel" in capsys.readouterr().out.split(
        "(no limit: informational)")[0]


@pytest.mark.parametrize("name,events,want", [
    ("sel_scan_state_rms",
     [{"sel_scan_state_rms": 0.02}, {"sel_scan_state_rms": 0.04},
      {"step": 3}], 0.03),
    ("gmu_gate_rms",
     [{"gmu_gate_rms": 0.5}, {"gmu_gate_rms": 0.7}, {"step": 3}], 0.6),
    ("diff_lambda_mean",
     [{"diff_lambda_mean": 0.79}, {"diff_lambda_mean": 0.81}, {"step": 3}],
     0.80),
    ("sel_scan_state_rms", [{"step": 1, "ssm_state_rms": 0.1}], None),
    ("gmu_gate_rms", [{"step": 1, "moe_here": 0.25}], None),
    ("diff_lambda_mean", [{"step": 1, "attn_gate_mean": 0.5}], None),
    ("diff_lambda_mean", [], None),
])
def test_new_counter_readers_on_hand_made_step_events(name, events, want):
    """A program without the counters (the parent commit, another model)
    gives a reader nothing to read: None, no exception."""
    value = load(f"layer_metrics/{name}.py").read({"window_steps": events})
    assert value == (None if want is None else pytest.approx(want))
