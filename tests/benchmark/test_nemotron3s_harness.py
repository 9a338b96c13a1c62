"""The harness takes the Nemotron 3 Super configuration without an edit:
`run.run_once` driven on the CPU mesh with the tiny configuration file ends
`correct`; the float8 reference in the program's place does not. The new
cell's entries in BENCHMARK.json (found BY NAME, wherever later entries put
them in their lists), the configuration file against the catalog's row, and
the two readers on hand-made `step` events."""

import json
import os

import pytest

from bench_paths import BENCH, load

CELL = "nemotron3s-plain-1chip"
CONFIG = "nemotron3s-l11-tp8-e8of512-v16384-t8192-bf16"
NEW_METRICS = ("moe_latent_rms", "moe_relu2_active")
SOURCE = ("https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-"
          "BF16/blob/main/config.json")
# the other models' counters: none of them on this model's records
OTHERS = ("attn_gate_mean", "moe_score_sum", "sel_scan_state_rms",
          "gmu_gate_rms", "diff_lambda_mean", "delta_state_rms",
          "delta_beta_mean", "shared_gate_mean", "mhc_res_gap",
          "mhc_res_offdiag", "mla_kv_latent_rms")
OLDER_CELLS = (
    "resnet50-plain-1chip", "resnet50-augment-1chip", "vgg16-plain-4chip",
    "vgg16-plain-1chip", "mellum2-plain-1chip", "granite4h-plain-1chip",
    "laguna-xs2-plain-1chip", "phi4flash-plain-1chip",
    "qwen3next-plain-1chip", "xing4-plain-1chip")
HELD = {"num_hidden_layers": 11, "hybrid_override_pattern": "EMEMEMEMEM*",
        "n_routed_experts": 8, "mamba_num_heads": 16, "n_groups": 1,
        "num_attention_heads": 4, "num_key_value_heads": 1,
        "vocab_size": 16384, "num_nextn_predict_layers": 0}


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
        "cell": {"name": "tiny-nemotron3s", "config": "tiny-nemotron3s-f32",
                 "traffic": "tiny", "chips": 8},
        "config": run_module.load_json(
            os.path.join(BENCH, "configs", "tiny-nemotron3s-f32.json")),
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
             if e["event"] == "step" and "moe_latent_rms" in e]
    assert len(steps) >= 3
    run = {"window_steps": steps}
    assert 0.01 < load("layer_metrics/moe_latent_rms.py").read(run) < 2.0
    assert 30 < load("layer_metrics/moe_relu2_active.py").read(run) < 70
    # the counters the shared code already gives ride on the same records:
    # the scan's, the routing's (half of the experts held, none dropped) and
    # the selection bias's
    assert load("layer_metrics/ssm_state_rms.py").read(run) > 0
    assert load("layer_metrics/ssm_log_decay_min.py").read(run) < 0
    assert 10 < load("layer_metrics/moe_here_share.py").read(run) < 90
    assert load("layer_metrics/moe_dropped.py").read(run) == 0
    assert load("layer_metrics/moe_group_rows.py").read(run) > 0
    assert 1 < load("layer_metrics/moe_bias_swap_share.py").read(run) < 60
    # no other model's counter on this model's records
    assert not set(OTHERS) & set().union(*steps)
    for other in OTHERS:
        assert load(f"layer_metrics/{other}.py").read(run) is None, other


def test_the_new_cell_resolves_and_reports_its_counters(run_module):
    spec = run_module.load_cell(CELL)
    assert spec["cell"] == {
        "name": CELL, "config": CONFIG, "traffic": "plain", "chips": 1,
        "why": spec["cell"]["why"]}
    for said in ("1 x 8,192 tokens", "closed loop", "AdamW", "352",
                 "180,224", "2,816", "64-chip", "one chip of 8"):
        assert said in spec["cell"]["why"], said
    assert len(spec["cell"]["why"]) <= 200
    per_layer = {m["name"] for m in run_module.cell_metrics(spec, "per_layer")}
    assert {*NEW_METRICS, "step_mfu", "step_device_ms", "device_idle"} \
        <= per_layer
    # none of the other models' counters, nor the cells' own lists (the
    # scan's and the routing's list the cells they were accepted with: to
    # list this one too is a benchmark issue's)
    assert not {"step_ms_p95", "boundary_ms", "exposed_comm_ms", *OTHERS,
                "ssm_state_rms", "ssm_log_decay_min", "moe_here_share",
                "moe_load_imbalance", "moe_dropped", "moe_group_rows",
                "moe_bias_swap_share"} & per_layer
    assert {m["name"] for m in run_module.cell_metrics(spec, "end_to_end")} \
        == {"samples_per_s", "peak_hbm_gib", "setup_s"}
    for old in OLDER_CELLS:
        assert not set(NEW_METRICS) & {
            m["name"] for m in run_module.cell_metrics(
                run_module.load_cell(old), "per_layer")}, old
    config = spec["config"]
    assert config["image_hw"] == [8192] and config["num_classes"] == 16384
    assert config["train_cli"] == [
        "--dnn", "nemotron3s", "--dataset", "tokens", "--layers-held",
        "26:11", "--experts-held", "0:8", "--tensor-share", "0:8",
        "--vocab-size", "16384", "--num-steps", "8192", "--batch-size", "1",
        "--dtype", "bfloat16", "--max-epochs", "40", "--synthetic",
        "--telemetry"]
    # Xing4.0's flags but for the model, the shares and the new one
    xing4 = run_module.load_cell("xing4-plain-1chip")["config"]["train_cli"]
    flags = config["train_cli"]
    at = flags.index("--tensor-share")
    rest = flags[:at] + flags[at + 2:]
    changed = {"--dnn", "--layers-held", "--experts-held"}
    assert [f for f in rest if f.startswith("--")] \
        == [f for f in xing4 if f.startswith("--")]
    assert all(a == b or xing4[i - 1] in changed
               for i, (a, b) in enumerate(zip(rest, xing4)))
    reference = load("references/" + config["reference"] + ".py")
    assert reference.SHARE == {
        "first_layer": 26, "layers": 11, "first_expert": 0, "experts": 8,
        "tensor": (0, 8)}
    assert 1.9e12 < reference.forward_macs(
        tuple(config["image_hw"]), config["num_classes"]) < 2.0e12
    # the entries, each found by its name: one configuration, one cell, two
    # metrics that list it alone (later PRs' entries may follow them)
    bench = spec["bench"]
    entries = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert len(entries) == 1
    assert [w["name"] for w in bench["workloads"] if w["config"] == CONFIG] \
        == [CELL]
    cells = [w["name"] for w in bench["workloads"]]
    assert cells.count(CELL) == 1 and len(cells) >= 11
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= len(cells) // 4
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name, unit, better in (("moe_latent_rms", "rms", "lower"),
                               ("moe_relu2_active", "%", "higher")):
        assert by_name[name] == {
            "name": name, "unit": unit, "better": better,
            "source": "program_counter", "layer": "experts",
            "moves": "samples_per_s", "workloads": [CELL]}
        assert os.path.isfile(
            os.path.join(BENCH, "layer_metrics", name + ".py"))
    # no accepted metric's list gained the cell
    assert [m["name"] for m in bench["per_layer"]
            if CELL in m.get("workloads", ())] == list(NEW_METRICS)
    entry = entries[0]
    assert entry["source"] == SOURCE
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert entry["reduced"] == config["reduced"]
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200


def catalog_row():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        return None
    with open(path) as f:
        rows = [json.loads(line) for line in f
                if '"name": "NVIDIA-Nemotron-3-Super-120B-A12B-BF16"' in line]
    return rows[0] if rows else None


def test_configuration_file_keeps_every_published_number():
    """Every key of the catalog's `config` under the same key and with the
    same value, but the keys `reduced` names; no width among those; the
    limits have their why; `parameters_held` is the leaves' count."""
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        config = json.load(f)
    published = {
        "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
        "expand": 2, "head_dim": 128, "hidden_size": 4096,
        "hybrid_override_pattern": (
            "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
            "EMEMEMEMEM*EMEMEMEM*EMEMEMEME"),
        "intermediate_size": 2688, "layer_norm_epsilon": 1e-05,
        "mamba_head_dim": 64, "mamba_hidden_act": "silu",
        "mamba_num_heads": 128, "mamba_proj_bias": False,
        "max_position_embeddings": 262144, "mlp_bias": False,
        "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
        "moe_intermediate_size": 2688, "moe_latent_size": 1024,
        "moe_shared_expert_intermediate_size": 5376,
        "moe_shared_expert_overlap": False,
        "mtp_hybrid_override_pattern": "*E", "n_group": 1, "n_groups": 8,
        "n_routed_experts": 512, "n_shared_experts": 1, "norm_eps": 1e-05,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 22, "num_hidden_layers": 88,
        "num_key_value_heads": 2, "num_logits_to_keep": 1,
        "num_nextn_predict_layers": 1, "partial_rotary_factor": 1,
        "rescale_prenorm_residual": True, "residual_in_fp32": False,
        "rope_theta": 10000, "routed_scaling_factor": 5,
        "sliding_window": None, "ssm_state_size": 128,
        "tie_word_embeddings": False, "time_step_floor": 0.0001,
        "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1,
        "use_bias": False, "use_conv_bias": True, "use_mamba_kernels": True,
        "vocab_size": 131072,
    }
    row = catalog_row()
    if row is not None:  # the catalog beside the guide, where it is there
        assert row["source_url"] == SOURCE and SOURCE in config["source"]
        assert row["config"] == published
    for key, value in published.items():
        if key in HELD:
            assert key in config["reduced"]
            assert config[key] == HELD[key]
            assert config["published"][key] == value
            assert len(config["reduced_why"][key]) > 40
        else:
            assert config[key] == value, key
    assert config["reduced"] == [*HELD, "train_set_sequences"]
    assert len(config["reduced"]) <= 16
    # no width among them: sizes of heads, states, latents and hidden layers
    # stay the published ones; what is cut is a count
    assert not [k for k in config["reduced"]
                if k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"]
    assert not {"expand", "num_experts_per_tok", "chunk_size", "conv_kernel",
                "head_dim", "n_group", "topk_group"} & set(config["reduced"])
    assert config["moe_shared_expert_columns_held"] == 672 == 5376 // 8
    assert config["tensor_share"]["of"] == 8
    for name in ("gated_norm", "attention", "time_step", "router",
                 "initial_weights", "optimizer", "load_balancing_loss",
                 "data", "memory"):
        assert name in config["assumed"], name
    for said in ("by GROUP", "1,024", "before the norm", "would not add up"):
        assert said in config["assumed"]["gated_norm"], said
    for said in ("no rotary", "rope_theta", "partial_rotary_factor"):
        assert said in config["assumed"]["attention"], said
    for said in ("sqrt(88)", "out_proj alone", "uniform +-0.02"):
        assert said in config["assumed"]["initial_weights"], said
    for said in ("s + bias", "1e-20", "no gradient reaches", "in no key"):
        assert said in config["assumed"]["router"], said
    for said in ("nine pipeline stages", "8, 9, 9, 11, 11, 11, 11, 9 and 9",
                 "sixteen four-chip v5e hosts", "eight-chip tensor-parallel",
                 "published layers 26 to 36", "experts 0 to 7",
                 "0 to 16,383", "352 rows", "2,816",
                 "13.4 GiB", "without its all-reduce",
                 "without its all-to-all"):
        assert said in config["deployment"], said
    for said in ("last pipeline stage", "in no key", "worse than none"):
        assert said in config["reduced_why"]["num_nextn_predict_layers"], said
    for said in ("router product", "AdamW", "relu^2", "carried state"):
        assert said in config["precision"], said
    assert config["published"]["parameters"] == 120668707840
    for name in ("first_grad_norm_rel", "update_rel"):
        assert len(config["limits"][name]["why"]) > 40
    assert {"first_grad_norm_rel", "update_rel",
            "steps_without_health_record", "bad_step_events",
            "nonfinite_losses"} <= set(config["limits"])
    # the float8 control's loss reads INSIDE the sound runs' range, and the
    # loss falls by 2.4% in the eight warm-up steps, which the accepted
    # cells' band leaves no three times of room: both numbers are left out BY
    # NAME, with their readings
    assert set(config["limits"]) == {
        "first_grad_norm_rel", "update_rel", "steps_without_health_record",
        "bad_step_events", "nonfinite_losses"}
    left_out = config["limits_left_out"]
    assert set(left_out) == {"first_loss_rel", "loss_ratio"}
    for said in ("no limit: informational", "first_grad_norm_rel", "INSIDE"):
        assert said in left_out["first_loss_rel"], said
    for said in ("0.97", "three times", "update_rel"):
        assert said in left_out["loss_ratio"], said
    assert config["controls"]["ref-fp8"]["reference_dtype"] == "float8_e4m3fn"
    assert config["per_device_batch"] == 1
    assert config["tokens_per_step"] == 8192
    # the program's shape and the reference's state the same widths, and the
    # leaves the program declares are `parameters_held`
    import jax
    import jax.numpy as jnp
    import numpy as np

    from mgwfbp_tpu.models import create_model
    from mgwfbp_tpu.models.nemotronh import NEMOTRON3S as S

    ref = load("references/nemotron3s_share.py").SHAPE
    for mine, key in (
            (S.hidden_size, "hidden_size"),
            (S.mamba_head_dim, "mamba_head_dim"),
            (S.mamba_state, "ssm_state_size"),
            (S.mamba_chunk, "chunk_size"),
            (S.head_dim, "head_dim"),
            (S.experts_per_token, "num_experts_per_tok"),
            (S.expert_width, "moe_intermediate_size"),
            (S.latent_size, "moe_latent_size"),
            (S.shared_expert_width, "moe_shared_expert_intermediate_size"),
            (S.routed_scaling_factor, "routed_scaling_factor"),
            (S.rms_norm_eps, "layer_norm_epsilon")):
        assert mine == ref[key] == config[key], key
    for mine, key in (
            (S.pattern, "hybrid_override_pattern"),
            (S.num_layers, "num_hidden_layers"),
            (S.mamba_heads, "mamba_num_heads"), (S.mamba_groups, "n_groups"),
            (S.num_heads, "num_attention_heads"),
            (S.num_kv_heads, "num_key_value_heads"),
            (S.num_experts, "n_routed_experts"),
            (S.vocab_size, "vocab_size")):
        assert mine == config["published"][key], key
        assert key == "num_hidden_layers" or key == "vocab_size" \
            or mine == ref[key], key
    assert S.mamba_heads * S.mamba_head_dim \
        == config["expand"] * S.hidden_size
    flags = config["train_cli"]

    def flag(name):
        return flags[flags.index(name) + 1]

    first, count = flag("--experts-held").split(":")
    model, _ = create_model(
        "nemotron3s", num_classes=config["vocab_size"],
        layers_held=flag("--layers-held"),
        experts_held=(int(first), int(count)),
        tensor_share=flag("--tensor-share"))
    shapes = jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)},
                           jnp.zeros((1, 8), jnp.int32), train=False))
    assert sum(int(np.prod(leaf.shape)) for leaf in
               jax.tree_util.tree_leaves(shapes)) \
        == config["parameters_held"] == 508189680
    assert model.layer_indices() == tuple(range(26, 37))
    assert "".join(S.pattern[i] for i in model.layer_indices()) \
        == config["hybrid_override_pattern"]
    share = config["tensor_share"]
    assert model.tensor_share == (share["index"], share["of"])
    from mgwfbp_tpu.models.nemotronh import held

    assert held(S, model.tensor_share) == (
        config["mamba_num_heads"], config["n_groups"],
        config["num_attention_heads"], config["num_key_value_heads"],
        config["moe_shared_expert_columns_held"])


@pytest.mark.parametrize("loss_gap, norm_gap, ratio, update, holds", [
    (3.3e-5, 3.04e-4, 0.9723, 1.333e-3, True),  # the sound runs' largest
    (7.0e-4, 1.2e-4, 0.96, 1.23e-3, True),     # loss and ratio refuse nothing
    (1.2e-5, 0.8515, 0.9765, 1.23e-3, False),  # the float8 control's smallest
    (1.0e-5, 1.2e-4, 0.98, 0.0, False),        # a step that changes nothing
    (1.0e-5, 1.2e-4, 0.98, 0.02, False),       # a missing warm-up
])
def test_the_limits_hold_the_sound_readings_and_refuse_the_control(
        run_module, capsys, loss_gap, norm_gap, ratio, update, holds):
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        limits = json.load(f)["limits"]
    checks = {"first_loss_rel": loss_gap, "first_grad_norm_rel": norm_gap,
              "loss_ratio": ratio, "update_rel": update}
    assert run_module.judge(checks, limits) is holds
    said = capsys.readouterr().out
    for name in ("first_loss_rel", "loss_ratio"):  # printed, held to nothing
        assert any(line.startswith(f"[correct] {name} ")
                   and line.endswith("(no limit: informational)")
                   for line in said.splitlines()), name


@pytest.mark.parametrize("name,events,want", [
    ("moe_latent_rms",
     [{"moe_latent_rms": 0.5}, {"moe_latent_rms": 0.7}, {"step": 3}], 0.6),
    ("moe_relu2_active",  # a share of one on the record, per cent here
     [{"moe_relu2_active": 0.50}, {"moe_relu2_active": 0.48}, {"step": 3}],
     49.0),
    ("moe_latent_rms", [{"step": 1, "mla_kv_latent_rms": 0.1}], None),
    ("moe_relu2_active", [{"step": 1, "moe_here": 0.25}], None),
    ("moe_relu2_active", [], None),
])
def test_new_counter_readers_on_hand_made_step_events(name, events, want):
    """A program without the counters (the parent commit, another model)
    gives a reader nothing to read: None, no exception."""
    value = load(f"layer_metrics/{name}.py").read({"window_steps": events})
    assert value == (None if want is None else pytest.approx(want))
