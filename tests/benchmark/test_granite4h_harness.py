"""The harness takes the hybrid state-space configuration without an edit:
`run.run_once` driven on the CPU mesh with the tiny Granite 4.0-H
configuration file ends `correct`; the float8 reference in the program's
place and a step that returns its state unchanged do not. The new cell's
entries in BENCHMARK.json, the configuration file against the catalog's
numbers, and the two readers of the scan's counters on hand-made `step`
events."""

import json
import os

import pytest

from bench_paths import BENCH, load

CELL = "granite4h-plain-1chip"
CONFIG = "granite4h-micro-l10-v12544-t8192-bf16"


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
        "cell": {"name": "tiny-granite4h", "config": "tiny-granite4h-f32",
                 "traffic": "tiny", "chips": 8},
        "config": run_module.load_json(
            os.path.join(BENCH, "configs", "tiny-granite4h-f32.json")),
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
    # the stream the readers read: the scan's counters on the step records
    stream = run_module.read_stream(os.path.join(
        str(tmp_path / "out"), "logs",
        os.listdir(str(tmp_path / "out" / "logs"))[0], "telemetry.jsonl"))
    steps = [e for e in stream
             if e["event"] == "step" and "ssm_state_rms" in e]
    assert len(steps) >= 3
    run = {"window_steps": steps}
    assert load("layer_metrics/ssm_state_rms.py").read(run) > 0.0
    assert load("layer_metrics/ssm_log_decay_min.py").read(run) < 0.0


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        run_module, tmp_path, restored_environment):
    """`update_rel` reads 0 under its floor when the optimizer moves
    nothing (a learning rate of zero for the whole warm-up)."""
    spec = tiny_spec(run_module)
    spec["config"] = {
        **spec["config"],
        "train_cli": [*spec["config"]["train_cli"], "--lr", "0"]}
    result, compared = run_module.run_once(
        spec, 7, 0.0, False, str(tmp_path / "out"), {},
        run_module.CompileCounter(), rehearsal=True)
    assert compared["checks"]["update_rel"] == 0.0
    assert result["correct"] is False
    # everything else the check compares still holds
    assert compared["checks"]["first_grad_norm_rel"] \
        < spec["config"]["limits"]["first_grad_norm_rel"]["max"]


def test_the_new_cell_resolves_and_reports_the_scan_counters(run_module):
    spec = run_module.load_cell(CELL)
    assert spec["cell"] == {
        "name": CELL, "config": CONFIG, "traffic": "plain", "chips": 1,
        "why": spec["cell"]["why"]}
    per_layer = {m["name"] for m in run_module.cell_metrics(spec, "per_layer")}
    assert {"ssm_state_rms", "ssm_log_decay_min", "step_mfu",
            "step_device_ms", "device_idle"} <= per_layer
    assert not {"step_ms_p95", "boundary_ms", "exposed_comm_ms",
                "moe_here_share", "moe_dropped"} & per_layer
    assert {m["name"] for m in run_module.cell_metrics(spec, "end_to_end")} \
        == {"samples_per_s", "peak_hbm_gib", "setup_s"}
    for old in ("resnet50-plain-1chip", "mellum2-plain-1chip"):
        assert not {"ssm_state_rms", "ssm_log_decay_min"} & {
            m["name"] for m in run_module.cell_metrics(
                run_module.load_cell(old), "per_layer")}
    config = spec["config"]
    assert config["image_hw"] == [8192] and config["num_classes"] == 12544
    flags = config["train_cli"]
    assert flags[flags.index("--dnn") + 1] == "granite4h"
    assert flags[flags.index("--layers-held") + 1] == "10"
    assert flags[flags.index("--vocab-size") + 1] == str(config["vocab_size"])
    assert flags[flags.index("--batch-size") + 1] == "1"
    reference = load("references/" + config["reference"] + ".py")
    assert reference.SHARE == {"layers": 10}
    # the entries are the last of their lists: nothing before them moved
    bench = spec["bench"]
    assert bench["configs"][-1]["name"] == CONFIG
    assert bench["workloads"][-1]["name"] == CELL
    assert [m["name"] for m in bench["per_layer"][-2:]] == [
        "ssm_state_rms", "ssm_log_decay_min"]
    for metric in bench["per_layer"][-2:]:
        assert metric["workloads"] == [CELL]
        assert metric["layer"] == "state space"
        assert metric["moves"] == "samples_per_s"
        assert metric["source"] == "program_counter"


def test_configuration_file_keeps_every_published_number():
    """Every number of the catalog's `config` under the same key, but the
    keys `reduced` names; no width among those; the limits have their why."""
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        config = json.load(f)
    published = {
        "attention_multiplier": 0.015625, "embedding_multiplier": 12,
        "hidden_size": 2048, "intermediate_size": 8192, "logits_scaling": 8,
        "mamba_chunk_size": 256, "mamba_d_conv": 4, "mamba_d_head": 64,
        "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1,
        "mamba_n_heads": 64, "max_position_embeddings": 131072,
        "num_attention_heads": 32, "num_experts_per_tok": 0,
        "num_hidden_layers": 40, "num_key_value_heads": 8,
        "num_local_experts": 0, "residual_multiplier": 0.22,
        "rms_norm_eps": 1e-05, "rope_theta": 10000,
        "shared_intermediate_size": 8192, "vocab_size": 100352,
    }
    held = {"num_hidden_layers": 10, "vocab_size": 12544}
    for key, value in published.items():
        if key in held:
            assert key in config["reduced"]
            assert config[key] == held[key]
            assert config["published"][key] == value
        else:
            assert config[key] == value, key
    assert config["layer_types"] == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert config["tie_word_embeddings"] is True
    assert config["position_embedding_type"] == "nope"
    assert config["mamba_conv_bias"] is True and not config["mamba_proj_bias"]
    assert config["reduced"] == [
        "num_hidden_layers", "layer_types", "vocab_size", "train_set_sequences"]
    assert not [k for k in config["reduced"]
                if k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"]
    assert config["parameters_held"] == 772160448
    for name in ("initial_weights", "optimizer", "data", "memory"):
        assert name in config["assumed"]
    for name in ("first_loss_rel", "first_grad_norm_rel", "update_rel",
                 "loss_ratio"):
        assert len(config["limits"][name]["why"]) > 40
    assert config["controls"]["ref-fp8"]["reference_dtype"] == "float8_e4m3fn"
    # the program's shape and the reference's state the same widths
    from mgwfbp_tpu.models.granite import GRANITE4H

    ref = load("references/granite4h_share.py").SHAPE
    assert GRANITE4H.hidden_size == ref["hidden_size"] == config["hidden_size"]
    assert GRANITE4H.intermediate_size == ref["shared_intermediate_size"] \
        == config["shared_intermediate_size"]
    assert GRANITE4H.mamba_state == ref["mamba_d_state"] \
        == config["mamba_d_state"]
    assert GRANITE4H.mamba_chunk == ref["mamba_chunk_size"] \
        == config["mamba_chunk_size"]
    assert (GRANITE4H.mamba_heads, GRANITE4H.mamba_head_dim) == (
        ref["mamba_n_heads"], ref["mamba_d_head"]) == (
        config["mamba_n_heads"], config["mamba_d_head"])
    assert GRANITE4H.mamba_inner == config["mamba_expand"] * config["hidden_size"]
    assert GRANITE4H.head_dim == ref["attention_head_dim"] \
        == config["hidden_size"] // config["num_attention_heads"]
    for name in ("embedding_multiplier", "residual_multiplier",
                 "attention_multiplier", "logits_scaling"):
        assert getattr(GRANITE4H, name) == ref[name] == config[name]


@pytest.mark.parametrize("name,events,want", [
    ("ssm_state_rms",
     [{"ssm_state_rms": 0.5}, {"ssm_state_rms": 0.7}, {"step": 3}], 0.6),
    ("ssm_log_decay_min",
     [{"ssm_log_decay_min": -40.0}, {"ssm_log_decay_min": -95.5},
      {"step": 3}], -95.5),
    ("ssm_state_rms", [{"step": 1}], None),
    ("ssm_log_decay_min", [], None),
])
def test_scan_counter_readers_on_hand_made_step_events(name, events, want):
    """A program without the counters (the parent commit, a model without a
    scan) gives a reader nothing to read: None, no exception."""
    value = load(f"layer_metrics/{name}.py").read({"window_steps": events})
    assert value == (None if want is None else pytest.approx(want))
