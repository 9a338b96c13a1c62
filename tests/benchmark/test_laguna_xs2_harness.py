"""The harness takes the Laguna-XS.2 configuration without an edit:
`run.run_once` driven on the CPU mesh with the tiny configuration file ends
`correct`; the float8 reference in the program's place and a step that
returns its state unchanged do not. The new cell's entries in BENCHMARK.json
(wherever later entries put them in their lists), the configuration file
against the catalog's row, and the three readers on hand-made `step`
events."""

import json
import os

import pytest

from bench_paths import BENCH, load

CELL = "laguna-xs2-plain-1chip"
CONFIG = "laguna-xs2-l5-e32of256-v12544-t8192-bf16"
NEW_METRICS = ("attn_gate_mean", "moe_score_sum", "moe_group_rows")


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
        "cell": {"name": "tiny-laguna-xs2", "config": "tiny-laguna-xs2-f32",
                 "traffic": "tiny", "chips": 8},
        "config": run_module.load_json(
            os.path.join(BENCH, "configs", "tiny-laguna-xs2-f32.json")),
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
             if e["event"] == "step" and "attn_gate_mean" in e]
    assert len(steps) >= 3
    run = {"window_steps": steps}
    assert 0.4 < load("layer_metrics/attn_gate_mean.py").read(run) < 0.6
    # two of sixteen sigmoid scores a token
    assert 0.5 < load("layer_metrics/moe_score_sum.py").read(run) < 2.0
    # 4 of 16 experts held, 2 x 64 tokens a device choosing 2: 16 a group
    assert 8 < load("layer_metrics/moe_group_rows.py").read(run) < 32
    # Mellum 2's readers read this model's counters as they are
    assert 15 < load("layer_metrics/moe_here_share.py").read(run) < 35
    assert load("layer_metrics/moe_dropped.py").read(run) == 0


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


def test_the_new_cell_resolves_and_reports_its_counters(run_module):
    spec = run_module.load_cell(CELL)
    assert spec["cell"] == {
        "name": CELL, "config": CONFIG, "traffic": "plain", "chips": 1,
        "why": spec["cell"]["why"]}
    # the load the window runs at is said beside the load at the start
    for said in ("256 tokens at first", "520 in the window", "2,048",
                 "8-chip", "shared expert", "samples_per_s counts sequences"):
        assert said in spec["cell"]["why"]
    assert len(spec["cell"]["why"]) <= 200
    per_layer = {m["name"] for m in run_module.cell_metrics(spec, "per_layer")}
    assert {*NEW_METRICS, "step_mfu", "step_device_ms", "device_idle"} \
        <= per_layer
    assert not {"step_ms_p95", "boundary_ms", "exposed_comm_ms",
                "ssm_state_rms", "moe_here_share", "moe_dropped"} & per_layer
    assert {m["name"] for m in run_module.cell_metrics(spec, "end_to_end")} \
        == {"samples_per_s", "peak_hbm_gib", "setup_s"}
    for old in ("resnet50-plain-1chip", "mellum2-plain-1chip",
                "granite4h-plain-1chip"):
        assert not set(NEW_METRICS) & {
            m["name"] for m in run_module.cell_metrics(
                run_module.load_cell(old), "per_layer")}
    config = spec["config"]
    assert config["image_hw"] == [8192] and config["num_classes"] == 12544
    flags = config["train_cli"]
    assert flags[flags.index("--dnn") + 1] == "laguna_xs2"
    assert flags[flags.index("--layers-held") + 1] == "5"
    assert flags[flags.index("--experts-held") + 1] == "0:32"
    assert flags[flags.index("--vocab-size") + 1] == str(config["vocab_size"])
    assert flags[flags.index("--batch-size") + 1] == "1"
    assert flags[flags.index("--num-steps") + 1] == "8192"
    reference = load("references/" + config["reference"] + ".py")
    assert reference.SHARE == {"layers": 5, "first_expert": 0, "experts": 32}
    # the entries: one configuration, one cell, three metrics that list it
    # alone, each after everything PR 31's benchmark had (a later PR's
    # entries may follow them)
    bench = spec["bench"]
    names = [c["name"] for c in bench["configs"]]
    assert names.count(CONFIG) == 1
    assert names.index(CONFIG) > names.index(
        "granite4h-micro-l10-v12544-t8192-bf16")
    cells = [w["name"] for w in bench["workloads"]]
    assert cells.index(CELL) > cells.index("granite4h-plain-1chip")
    assert [w["name"] for w in bench["workloads"] if w["config"] == CONFIG] \
        == [CELL]
    metrics = [m["name"] for m in bench["per_layer"]]
    assert all(metrics.index(n) > metrics.index("ssm_log_decay_min")
               for n in NEW_METRICS)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name, layer, unit in (("attn_gate_mean", "attention", "ratio"),
                              ("moe_score_sum", "experts", "ratio"),
                              ("moe_group_rows", "experts", "count")):
        assert by_name[name] == {
            "name": name, "unit": unit, "better": "higher",
            "source": "program_counter", "layer": layer,
            "moves": "samples_per_s", "workloads": [CELL]}
        assert os.path.isfile(
            os.path.join(BENCH, "layer_metrics", name + ".py"))
    # the three `moe_*` metrics that were there still list Mellum 2 alone
    for name in ("moe_here_share", "moe_load_imbalance", "moe_dropped"):
        assert by_name[name]["workloads"] == ["mellum2-plain-1chip"]
    entry = bench["configs"][names.index(CONFIG)]
    assert entry["source"] \
        == "https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json"
    assert entry["reduced"] == config["reduced"]


def catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        return None
    with open(path) as f:
        rows = [json.loads(line) for line in f if '"Laguna-XS.2"' in line]
    return rows[0] if rows else None


@pytest.mark.parametrize("loss_gap, norm_gap, holds", [
    (6.7e-5, 1.6e-4, True),    # the sound runs' largest readings
    (9.2e-4, 1.6e-4, True),    # the loss alone refuses nothing ...
    (7.4e-5, 0.788, False),    # ... the float8 control fails by the norm
])
def test_the_loss_left_out_by_name_is_printed_and_not_judged(
        run_module, capsys, loss_gap, norm_gap, holds):
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        limits = json.load(f)["limits"]
    checks = {"first_loss_rel": loss_gap, "first_grad_norm_rel": norm_gap}
    assert run_module.judge(checks, limits) is holds
    assert "first_loss_rel" in capsys.readouterr().out.split(
        "(no limit: informational)")[0]


def test_configuration_file_keeps_every_published_number():
    """Every key of the catalog's `config` under the same key and with the
    same value, but the keys `reduced` names; no width among those; the
    nested rotary group whole; the limits have their why."""
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        config = json.load(f)
    published = {
        "model_type": "laguna", "vocab_size": 100352, "hidden_size": 2048,
        "intermediate_size": 8192, "num_hidden_layers": 40,
        "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
        "max_position_embeddings": 262144, "attention_bias": False,
        "rms_norm_eps": 1e-06, "num_experts": 256, "num_experts_per_tok": 8,
        "moe_intermediate_size": 512, "shared_expert_intermediate_size": 512,
        "tie_word_embeddings": False, "gating": True, "sliding_window": 512,
        "moe_apply_router_weight_on_input": False,
        "partial_rotary_factor": 0.5, "moe_routed_scaling_factor": 2.5,
    }
    row = catalog_row()
    if row is not None:  # the catalog beside the guide, where it is there
        assert row["source_url"] in config["source"]
        for key, value in row["config"].items():
            if key not in config["reduced"]:
                assert config[key] == value, key
        assert {k: row["config"][k] for k in published} == published
    held = {"num_hidden_layers": 5, "num_experts": 32, "vocab_size": 12544}
    for key, value in published.items():
        if key in held:
            assert key in config["reduced"]
            assert config[key] == held[key]
            assert config["published"][key] == value
        else:
            assert config[key] == value, key
    full, window = "full_attention", "sliding_attention"
    assert config["layer_types"] == [full, window, window, window, full]
    assert config["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert config["num_attention_heads_per_layer"] == [48, 64, 64, 64, 48]
    assert config["rope_parameters"] == {
        full: {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
               "original_max_position_embeddings": 4096, "beta_slow": 1,
               "beta_fast": 64, "attention_factor": 1.4158883083359672,
               "partial_rotary_factor": 0.5},
        window: {"rope_type": "default", "rope_theta": 10000,
                 "partial_rotary_factor": 1},
        "original_max_position_embeddings": 4096}
    assert config["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size", "layer_types",
        "mlp_layer_types", "num_attention_heads_per_layer",
        "train_set_sequences"]
    assert not [k for k in config["reduced"]
                if k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"]
    assert config["parameters_held"] == 691623936
    for name in ("gating", "router_score", "qk_norm_and_router_bias",
                 "load_balancing_loss", "optimizer", "initial_weights",
                 "data", "memory"):
        assert name in config["assumed"]
    # both open readings are named, with the count that decides the first
    assert "per channel" in config["assumed"]["gating"]
    assert "33.4B-A3B" in config["assumed"]["gating"]
    assert "softmax" in config["assumed"]["router_score"]
    for said in ("eight pipeline stages", "eight ways", "0 to 31",
                 "0 to 12,543", "last stage", "256 tokens",
                 "does NOT stay at 256", "absent columns", "1,083"):
        assert said in config["deployment"], said
    for name in ("first_grad_norm_rel", "update_rel", "loss_ratio"):
        assert len(config["limits"][name]["why"]) > 40
    # the float8 control's loss reads 1.1 times the sound runs' largest: the
    # number has no upper reading and is left out BY NAME, with the readings
    assert "first_loss_rel" not in config["limits"]
    left_out = config["limits_left_out"]["first_loss_rel"]
    for said in ("6.7e-5", "7.4e-5", "no upper reading",
                 "first_grad_norm_rel"):
        assert said in left_out, said
    assert config["controls"]["ref-fp8"]["reference_dtype"] == "float8_e4m3fn"
    # the program's shape and the reference's state the same widths
    from mgwfbp_tpu.models.laguna import LAGUNA_XS2 as S

    ref = load("references/laguna_xs2_share.py").SHAPE
    assert S.hidden_size == ref["hidden_size"] == config["hidden_size"]
    assert S.intermediate_size == ref["intermediate_size"] \
        == config["intermediate_size"]
    assert S.head_dim == ref["head_dim"] == config["head_dim"]
    assert S.num_kv_heads == ref["num_key_value_heads"] \
        == config["num_key_value_heads"]
    assert S.num_experts == ref["num_experts"] \
        == config["published"]["num_experts"]
    assert S.experts_per_token == ref["num_experts_per_tok"] \
        == config["num_experts_per_tok"]
    assert S.expert_width == ref["moe_intermediate_size"] \
        == config["moe_intermediate_size"]
    assert S.shared_expert_width == ref["shared_expert_intermediate_size"] \
        == config["shared_expert_intermediate_size"]
    assert S.routed_scaling_factor == ref["moe_routed_scaling_factor"] \
        == config["moe_routed_scaling_factor"]
    assert S.sliding_window == ref["sliding_window"] == config["sliding_window"]
    assert S.vocab_size == config["published"]["vocab_size"]
    rope = config["rope_parameters"]
    assert (S.full_rope_theta, S.yarn_factor, S.yarn_original_len,
            S.yarn_beta_fast, S.yarn_beta_slow, S.yarn_attention_factor,
            S.full_rotary_factor) == tuple(rope[full][k] for k in (
                "rope_theta", "factor", "original_max_position_embeddings",
                "beta_fast", "beta_slow", "attention_factor",
                "partial_rotary_factor"))
    assert (S.sliding_rope_theta, S.sliding_rotary_factor) == (
        rope[window]["rope_theta"], rope[window]["partial_rotary_factor"])
    assert list(S.heads_per_layer[:5]) \
        == config["num_attention_heads_per_layer"]


@pytest.mark.parametrize("name,events,want", [
    ("attn_gate_mean",
     [{"attn_gate_mean": 0.5}, {"attn_gate_mean": 0.46}, {"step": 3}], 0.48),
    ("moe_score_sum",
     [{"moe_score_sum": 7.0}, {"moe_score_sum": 7.5}, {"step": 3}], 7.25),
    ("moe_group_rows",
     [{"moe_load_mean": 250.0, "moe_load_max": 300.0},
      {"moe_load_mean": 262.0}, {"step": 3}], 256.0),
    ("attn_gate_mean", [{"step": 1, "moe_here": 0.25}], None),
    ("moe_score_sum", [{"step": 1, "moe_here": 0.25}], None),
    ("moe_group_rows", [{"step": 1, "ssm_state_rms": 0.1}], None),
    ("moe_group_rows", [], None),
])
def test_new_counter_readers_on_hand_made_step_events(name, events, want):
    """A program without the counters (the parent commit, a model without a
    gate or experts) gives a reader nothing to read: None, no exception."""
    value = load(f"layer_metrics/{name}.py").read({"window_steps": events})
    assert value == (None if want is None else pytest.approx(want))
