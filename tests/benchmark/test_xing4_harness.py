"""The harness takes the Xing4.0 configuration without an edit: `run.run_once`
driven on the CPU mesh with the tiny configuration file ends `correct`; the
float8 reference in the program's place does not. The new cell's entries in
BENCHMARK.json (AFTER Qwen3-Next's, wherever later entries put them in their
lists), the configuration file against the catalog's row, and the four
readers on hand-made `step` events."""

import json
import os

import pytest

from bench_paths import BENCH, load

CELL = "xing4-plain-1chip"
CONFIG = "xing4-l5-e8of64-v16384-t8192-bf16"
NEW_METRICS = ("mhc_res_gap", "mhc_res_offdiag", "mla_kv_latent_rms",
               "moe_bias_swap_share")
SOURCE = ("https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B/"
          "blob/main/config.json")
OTHERS = ("ssm_state_rms", "ssm_log_decay_min", "attn_gate_mean",
          "moe_score_sum", "sel_scan_state_rms", "gmu_gate_rms",
          "diff_lambda_mean", "delta_state_rms", "delta_beta_mean",
          "shared_gate_mean")
OLDER_CELLS = (
    "resnet50-plain-1chip", "resnet50-augment-1chip", "vgg16-plain-4chip",
    "vgg16-plain-1chip", "mellum2-plain-1chip", "granite4h-plain-1chip",
    "laguna-xs2-plain-1chip", "phi4flash-plain-1chip",
    "qwen3next-plain-1chip")


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
        "cell": {"name": "tiny-xing4", "config": "tiny-xing4-f32",
                 "traffic": "tiny", "chips": 8},
        "config": run_module.load_json(
            os.path.join(BENCH, "configs", "tiny-xing4-f32.json")),
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
    steps = [e for e in stream if e["event"] == "step" and "mhc_res_gap" in e]
    assert len(steps) >= 3
    run = {"window_steps": steps}
    assert 0 < load("layer_metrics/mhc_res_gap.py").read(run) < 5e-3
    assert 0.2 < load("layer_metrics/mhc_res_offdiag.py").read(run) < 0.45
    assert 0.01 < load("layer_metrics/mla_kv_latent_rms.py").read(run) < 1.0
    assert 1 < load("layer_metrics/moe_bias_swap_share.py").read(run) < 60
    # the routing counters every `held_experts` model has: half of the
    # experts held, none dropped
    assert 10 < load("layer_metrics/moe_here_share.py").read(run) < 90
    assert load("layer_metrics/moe_dropped.py").read(run) == 0
    assert load("layer_metrics/moe_group_rows.py").read(run) > 0
    # no other model's counter on this model's records
    assert not set(OTHERS) & set().union(*steps)
    for other in OTHERS:
        assert load(f"layer_metrics/{other}.py").read(run) is None, other


def test_the_new_cell_resolves_and_reports_its_counters(run_module):
    spec = run_module.load_cell(CELL)
    assert spec["cell"] == {
        "name": CELL, "config": CONFIG, "traffic": "plain", "chips": 1,
        "why": spec["cell"]["why"]}
    for said in ("1 x 8,192 tokens", "closed loop", "AdamW", "512 rows",
                 "4,096", "8-chip", "8x"):
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
    for old in OLDER_CELLS:
        assert not set(NEW_METRICS) & {
            m["name"] for m in run_module.cell_metrics(
                run_module.load_cell(old), "per_layer")}, old
    config = spec["config"]
    assert config["image_hw"] == [8192] and config["num_classes"] == 16384
    assert config["train_cli"] == [
        "--dnn", "xing4", "--dataset", "tokens", "--layers-held", "1:5",
        "--experts-held", "0:8", "--vocab-size", "16384", "--num-steps",
        "8192", "--batch-size", "1", "--dtype", "bfloat16", "--max-epochs",
        "40", "--synthetic", "--telemetry"]
    # Laguna-XS.2's flags but for the model and the share
    laguna = run_module.load_cell("laguna-xs2-plain-1chip")["config"]
    changed = {"--dnn", "--layers-held", "--experts-held", "--vocab-size"}
    flags, theirs = config["train_cli"], laguna["train_cli"]
    assert [f for f in flags if f.startswith("--")] \
        == [f for f in theirs if f.startswith("--")]
    assert all(a == b or theirs[i - 1] in changed
               for i, (a, b) in enumerate(zip(flags, theirs)))
    reference = load("references/" + config["reference"] + ".py")
    assert reference.SHARE == {
        "first_layer": 1, "layers": 5, "first_expert": 0, "experts": 8}
    assert 4.7e12 < reference.forward_macs(
        tuple(config["image_hw"]), config["num_classes"]) < 4.8e12
    # the entries: one configuration, one cell, four metrics that list it
    # alone, each AFTER Qwen3-Next's (a later PR's entries may follow them:
    # nothing here says they are last)
    bench = spec["bench"]
    names = [c["name"] for c in bench["configs"]]
    assert names.count(CONFIG) == 1
    assert names.index(CONFIG) > names.index(
        "qwen3next-l4-e32of512-v18992-t8192-bf16")
    cells = [w["name"] for w in bench["workloads"]]
    assert cells.index(CELL) > cells.index("qwen3next-plain-1chip")
    assert [w["name"] for w in bench["workloads"] if w["config"] == CONFIG] \
        == [CELL]
    # ten cells at least, this one on one chip; a quarter may ask for four
    assert len(cells) >= 10
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= len(cells) // 4
    metrics = [m["name"] for m in bench["per_layer"]]
    assert all(metrics.index(n) > metrics.index("shared_gate_mean")
               for n in NEW_METRICS)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name, layer, unit, better in (
            ("mhc_res_gap", "residual streams", "ratio", "lower"),
            ("mhc_res_offdiag", "residual streams", "ratio", "higher"),
            ("mla_kv_latent_rms", "attention", "rms", "lower"),
            ("moe_bias_swap_share", "experts", "%", "lower")):
        assert by_name[name] == {
            "name": name, "unit": unit, "better": better,
            "source": "program_counter", "layer": layer,
            "moves": "samples_per_s", "workloads": [CELL]}
        assert os.path.isfile(
            os.path.join(BENCH, "layer_metrics", name + ".py"))
    # the older cells' own counters still list their cells alone
    assert by_name["delta_state_rms"]["workloads"] == ["qwen3next-plain-1chip"]
    assert by_name["attn_gate_mean"]["workloads"] == ["laguna-xs2-plain-1chip"]
    assert by_name["moe_here_share"]["workloads"] == ["mellum2-plain-1chip"]
    assert by_name["moe_group_rows"]["workloads"] == ["laguna-xs2-plain-1chip"]
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
                if '"name": "Xing4.0-29B-A4B"' in line]
    return rows[0] if rows else None


def test_configuration_file_keeps_every_published_number():
    """Every key of the catalog's `config` under the same key and with the
    same value, but the keys `reduced` names; no width among those; the
    limits have their why; `parameters_held` is the leaves' count."""
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        config = json.load(f)
    published = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2,
        "hidden_act": "silu", "hidden_size": 3584, "intermediate_size": 9216,
        "kv_lora_rank": 512, "max_position_embeddings": 262144,
        "model_type": "xing4_0", "moe_intermediate_size": 1024,
        "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
        "n_shared_experts": 1, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 4,
        "num_hidden_layers": 40, "num_key_value_heads": 32,
        "num_nextn_predict_layers": 1, "hc_mult": 4, "hc_sinkhorn_iters": 20,
        "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30,
        "mhc_h_res_clamp_max": 30, "q_lora_rank": 768,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
        "rope_theta": 10000,
        "rope_scaling": {
            "beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
            "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
            "type": "yarn"},
        "routed_scaling_factor": 2, "scoring_func": "sigmoid",
        "tie_word_embeddings": False, "topk_group": 1,
        "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 131072,
    }
    row = catalog_row()
    if row is not None:  # the catalog beside the guide, where it is there
        assert row["source_url"] == SOURCE and SOURCE in config["source"]
        assert row["config"] == published
    held = {"num_hidden_layers": 5, "first_k_dense_replace": 1,
            "n_routed_experts": 8, "vocab_size": 16384,
            "num_nextn_predict_layers": 0}
    for key, value in published.items():
        if key in held:
            assert key in config["reduced"]
            assert config[key] == held[key]
            assert config["published"][key] == value
            assert len(config["reduced_why"][key]) > 40
        else:
            assert config[key] == value, key
    assert config["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers", "train_set_sequences"]
    assert not [k for k in config["reduced"]
                if k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"]
    for name in ("streams_in_and_out", "mapping", "latent_attention", "router",
                 "initial_weights", "optimizer", "load_balancing_loss", "data",
                 "memory"):
        assert name in config["assumed"], name
    for said in ("rows before columns", "eps on the sums",
                 "clamp on the exponent", "no learned scale", "rows first"):
        assert said in config["assumed"]["mapping"], said
    for said in ("4 copies", "summed before the final norm"):
        assert said in config["assumed"]["streams_in_and_out"], said
    for said in ("(nC)^-1/2", "0.5", "2 on the diagonal", "uniform +-0.1"):
        assert said in config["assumed"]["initial_weights"], said
    for said in ("s + bias", "no gradient reaches", "in no key"):
        assert said in config["assumed"]["router"], said
    for said in ("eight chips share each layer", "eight ways",
                 "published layers 1 to 5", "experts 0 to 7", "0 to 16,383",
                 "layers 6 to 40", "512 rows", "4,096",
                 "two sequences do not fit"):
        assert said in config["deployment"], said
    for said in ("last pipeline stage", "FOUR residual streams",
                 "worse than none"):
        assert said in config["reduced_why"]["num_nextn_predict_layers"], said
    for said in ("Sinkhorn", "router product", "AdamW", "round each stream"):
        assert said in config["precision"], said
    assert config["published"]["parameters"] == 29505505264
    for name in ("first_grad_norm_rel", "update_rel"):
        assert len(config["limits"][name]["why"]) > 40
    # the float8 control's loss reads within three times the sound runs'
    # largest, and the loss falls by 2% in the eight warm-up steps, which the
    # accepted cells' band leaves no three times of room: both numbers are
    # left out BY NAME, with their readings
    assert set(config["limits"]) == {
        "first_grad_norm_rel", "update_rel", "steps_without_health_record",
        "bad_step_events", "nonfinite_losses"}
    left_out = config["limits_left_out"]
    assert set(left_out) == {"first_loss_rel", "loss_ratio"}
    for said in ("no limit: informational", "first_grad_norm_rel"):
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
    from mgwfbp_tpu.models.xing4 import XING4 as S

    ref = load("references/xing4_share.py").SHAPE
    for mine, key in (
            (S.hidden_size, "hidden_size"),
            (S.intermediate_size, "intermediate_size"),
            (S.num_heads, "num_attention_heads"),
            (S.q_lora_rank, "q_lora_rank"), (S.kv_lora_rank, "kv_lora_rank"),
            (S.qk_nope_head_dim, "qk_nope_head_dim"),
            (S.qk_rope_head_dim, "qk_rope_head_dim"),
            (S.v_head_dim, "v_head_dim"),
            (S.experts_per_token, "num_experts_per_tok"),
            (S.expert_width, "moe_intermediate_size"),
            (S.routed_scaling_factor, "routed_scaling_factor"),
            (S.rms_norm_eps, "rms_norm_eps"), (S.rope_theta, "rope_theta"),
            (S.hc_mult, "hc_mult"),
            (S.hc_sinkhorn_iters, "hc_sinkhorn_iters"),
            (S.hc_eps, "hc_eps")):
        assert mine == ref[key] == config[key], key
    assert S.hc_res_clamp == (
        config["mhc_h_res_clamp_min"], config["mhc_h_res_clamp_max"]) == (
        ref["mhc_h_res_clamp_min"], ref["mhc_h_res_clamp_max"])
    for mine, key in ((S.yarn_factor, "factor"),
                      (S.yarn_original_len, "original_max_position_embeddings"),
                      (S.yarn_beta_fast, "beta_fast"),
                      (S.yarn_beta_slow, "beta_slow"),
                      (S.yarn_mscale, "mscale"),
                      (S.yarn_mscale_all_dim, "mscale_all_dim")):
        assert mine == ref["rope_scaling"][key] \
            == config["rope_scaling"][key], key
    assert S.num_layers == config["published"]["num_hidden_layers"]
    assert S.first_k_dense == ref["first_k_dense_replace"] \
        == config["published"]["first_k_dense_replace"]
    assert S.num_experts == ref["n_routed_experts"] \
        == config["published"]["n_routed_experts"]
    assert S.vocab_size == config["published"]["vocab_size"]
    flags = config["train_cli"]
    first, count = flags[flags.index("--experts-held") + 1].split(":")
    model, _ = create_model(
        "xing4", num_classes=config["vocab_size"],
        layers_held=flags[flags.index("--layers-held") + 1],
        experts_held=(int(first), int(count)))
    shapes = jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)},
                           jnp.zeros((1, 8), jnp.int32), train=False))
    assert sum(int(np.prod(leaf.shape)) for leaf in
               jax.tree_util.tree_leaves(shapes)) \
        == config["parameters_held"] == 759346446
    assert model.layer_indices() == (1, 2, 3, 4, 5)
    assert [S.kind(i) for i in model.layer_indices()] == [
        "dense", "sparse", "sparse", "sparse", "sparse"]


@pytest.mark.parametrize("loss_gap, norm_gap, ratio, update, holds", [
    (1.0e-4, 1.05e-4, 0.9784, 1.045e-3, True),  # the sound runs' largest
    (7.0e-4, 1.05e-4, 0.96, 1.018e-3, True),   # loss and ratio refuse nothing
    (2.5e-4, 0.4146, 0.9885, 1.018e-3, False),  # the float8 control's smallest
    (1.3e-5, 2.5e-5, 0.98, 0.0, False),        # a step that changes nothing
    (1.3e-5, 2.5e-5, 0.98, 0.02, False),       # a missing warm-up
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
    ("mhc_res_gap",
     [{"mhc_res_gap": 2e-5}, {"mhc_res_gap": 4e-5}, {"step": 3}], 3e-5),
    ("mhc_res_offdiag",
     [{"mhc_res_offdiag": 0.30}, {"mhc_res_offdiag": 0.32}, {"step": 3}],
     0.31),
    ("mla_kv_latent_rms",
     [{"mla_kv_latent_rms": 1.0}, {"mla_kv_latent_rms": 1.2}, {"step": 3}],
     1.1),
    ("moe_bias_swap_share",  # a share of one on the record, per cent here
     [{"moe_bias_swap_share": 0.10}, {"moe_bias_swap_share": 0.20},
      {"step": 3}], 15.0),
    ("mhc_res_gap", [{"step": 1, "delta_state_rms": 0.1}], None),
    ("mhc_res_offdiag", [{"step": 1, "moe_here": 0.25}], None),
    ("mla_kv_latent_rms", [{"step": 1, "attn_gate_mean": 0.5}], None),
    ("moe_bias_swap_share", [{"step": 1, "moe_score_sum": 0.5}], None),
    ("moe_bias_swap_share", [], None),
])
def test_new_counter_readers_on_hand_made_step_events(name, events, want):
    """A program without the counters (the parent commit, another model)
    gives a reader nothing to read: None, no exception."""
    value = load(f"layer_metrics/{name}.py").read({"window_steps": events})
    assert value == (None if want is None else pytest.approx(want))
