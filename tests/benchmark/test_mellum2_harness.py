"""The harness takes a token configuration without an edit: `run.run_once`
driven on the CPU mesh with the tiny Mellum 2 configuration file (a spec built
as `load_cell` builds one) ends `correct`, and the float8 reference in the
program's place does not. And the three readers of the routing counters, on
hand-made `step` events."""

import json
import os

import pytest

from bench_paths import BENCH, ROOT, load


@pytest.fixture(scope="module")
def run_module():
    return load("run.py")


@pytest.fixture
def restored_environment():
    """`run.apply_env` writes the cell's environment (the train set's size
    among it) into this process for good; a later test file of the same
    worker would train on a set sized for this one."""
    before = dict(os.environ)
    yield
    for key in set(os.environ) - set(before):
        del os.environ[key]
    os.environ.update(before)


def tiny_spec(run_module) -> dict:
    return {
        "bench": run_module.load_json(run_module.BENCHMARK_FILE),
        "cell": {"name": "tiny-mellum2", "config": "tiny-mellum2-f32",
                 "traffic": "tiny", "chips": 8},
        "config": run_module.load_json(
            os.path.join(BENCH, "configs", "tiny-mellum2-f32.json")),
        "traffic": run_module.load_json(
            os.path.join(BENCH, "traffic", "tiny.json")),
        "home": BENCH,
    }


def test_run_once_takes_the_token_configuration_and_ends_correct(
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
    # the stream the readers read: routing counters on the step records
    stream = run_module.read_stream(os.path.join(
        str(tmp_path / "out"), "logs",
        os.listdir(str(tmp_path / "out" / "logs"))[0], "telemetry.jsonl"))
    steps = [e for e in stream if e["event"] == "step" and "moe_here" in e]
    assert len(steps) >= 3
    assert all(e["moe_dropped"] == 0.0 for e in steps)
    run = {"window_steps": steps}
    assert 5.0 < load("layer_metrics/moe_here_share.py").read(run) < 60.0
    assert load("layer_metrics/moe_load_imbalance.py").read(run) >= 1.0
    assert load("layer_metrics/moe_dropped.py").read(run) == 0.0


def test_the_new_cell_resolves_and_reports_the_routing_metrics(run_module):
    spec = run_module.load_cell("mellum2-plain-1chip")
    assert spec["cell"]["chips"] == 1 and spec["cell"]["traffic"] == "plain"
    per_layer = {m["name"] for m in run_module.cell_metrics(spec, "per_layer")}
    assert {"moe_here_share", "moe_load_imbalance", "moe_dropped",
            "step_mfu", "step_device_ms", "device_idle"} <= per_layer
    assert not {"step_ms_p95", "boundary_ms", "exposed_comm_ms"} & per_layer
    old = run_module.load_cell("resnet50-plain-1chip")
    assert not {"moe_here_share", "moe_load_imbalance", "moe_dropped"} & {
        m["name"] for m in run_module.cell_metrics(old, "per_layer")}
    config = spec["config"]
    assert config["image_hw"] == [8192] and config["num_classes"] == 24576
    reference = load("references/" + config["reference"] + ".py")
    assert reference.SHARE == {"layers": 4, "first_expert": 0, "experts": 16}
    flags = config["train_cli"]
    assert flags[flags.index("--experts-held") + 1] == "0:16"
    assert flags[flags.index("--layers-held") + 1] == "4"
    assert flags[flags.index("--vocab-size") + 1] == str(config["vocab_size"])


def test_configuration_file_keeps_every_published_number():
    """Every number of the catalog's `config` under the same key, but the
    keys `reduced` names; no width among those."""
    with open(os.path.join(
            BENCH, "configs", "mellum2-l4-e16of64-v24576-t8192-bf16.json")) as f:
        config = json.load(f)
    published = {
        "head_dim": 128, "hidden_size": 2304, "intermediate_size": 7168,
        "max_position_embeddings": 131072, "max_window_layers": 0,
        "moe_intermediate_size": 896, "num_attention_heads": 32,
        "num_experts": 64, "num_experts_per_tok": 8, "num_hidden_layers": 28,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "sliding_window": 1024, "vocab_size": 98304,
    }
    held = {"num_experts": 16, "num_hidden_layers": 4, "vocab_size": 24576}
    for key, value in published.items():
        if key in held:
            assert key in config["reduced"]
            assert config[key] == held[key]
            assert config["published"][key] == value
        else:
            assert config[key] == value, key
    assert config["rope_parameters"]["full_attention"] == {
        "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 8192, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782}
    assert config["layer_types"] == ["sliding_attention"] * 3 + ["full_attention"]
    assert not [k for k in config["reduced"]
                if k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"]
    for name in ("qk_norm_and_router_bias", "load_balancing_loss",
                 "multi_token_prediction", "optimizer", "initial_weights"):
        assert name in config["assumed"]
    # the program's shape and the reference's state the same widths
    from mgwfbp_tpu.models.mellum import MELLUM2

    reference = load("references/mellum2_share.py")
    assert MELLUM2.hidden_size == reference.SHAPE["hidden_size"] \
        == config["hidden_size"]
    assert MELLUM2.expert_width == reference.SHAPE["moe_intermediate_size"] \
        == config["moe_intermediate_size"]
    assert MELLUM2.sliding_window == reference.SHAPE["sliding_window"] \
        == config["sliding_window"]
    assert list(MELLUM2.layer_types) == reference.SHAPE["layer_types"]


@pytest.mark.parametrize("name,events,want", [
    ("moe_here_share",
     [{"moe_here": 0.25}, {"moe_here": 0.27}, {"step": 3}], 26.0),
    ("moe_load_imbalance",
     [{"moe_load_max": 2400.0, "moe_load_mean": 2000.0},
      {"moe_load_max": 2200.0, "moe_load_mean": 2000.0}, {"step": 3}], 1.15),
    ("moe_dropped",
     [{"moe_dropped": 0.0}, {"moe_dropped": 3.0}, {"step": 3}], 3.0),
    ("moe_here_share", [{"step": 1}], None),
    ("moe_load_imbalance", [{"step": 1}], None),
    ("moe_dropped", [], None),
])
def test_routing_readers_on_hand_made_step_events(name, events, want):
    """A program without the counters (the parent commit, a model without
    experts) gives a reader nothing to read: None, no exception."""
    value = load(f"layer_metrics/{name}.py").read({"window_steps": events})
    assert value == (None if want is None else pytest.approx(want))
