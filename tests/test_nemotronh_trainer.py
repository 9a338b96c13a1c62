"""Nemotron 3 Super (models/nemotronh.py) on the Trainer's path at the tiny
size, data parallel over four virtual devices, through `train_cli`'s flags: a
stage that starts at layer 1 (`--layers-held 1:5`: E M * E M under their
published names) with experts 2 to 5 of 8 and member 1 of 2 chips' heads
(`--tensor-share 1:2`) trains under `--policy wfbp`, the scan's and the
routing's counters (Granite's, Mellum 2's and Xing4.0's names) and the two new
ones ride on the `step` records, the leaves (a (count, latent, width) expert
stack beside a Mamba layer's vectors and a selection bias no gradient reaches)
are reduced like `lax.pmean`'s, and the jaxpr verifier finds the step clean.
ONE training a file (module scope), read by every test that needs what it
left. The equations are held against the plain reference in
tests/test_nemotronh.py."""

import jax
import jax.numpy as jnp
import numpy as np
import program_records
import pytest
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from mgwfbp_tpu import train_cli
from mgwfbp_tpu.parallel.mesh import DATA_AXIS, MeshSpec, make_mesh
from mgwfbp_tpu.telemetry.events import events_of
from mgwfbp_tpu.train.step import make_loss_fn
from mgwfbp_tpu.train.trainer import Trainer

WORLD = 4
FLAGS = [
    "--dnn", "nemotron3s_tiny", "--dataset", "tokens", "--layers-held", "1:5",
    "--experts-held", "2:4", "--tensor-share", "1:2", "--vocab-size", "256",
    "--num-steps", "64", "--batch-size", "2", "--lr", "0.01", "--lr-schedule",
    "const", "--synthetic", "--telemetry", "--no-profile-backward",
    "--num-batches-per-epoch", "6", "--max-epochs", "2", "--seed", "5",
]
LEAVES = 2 * 9 + 2 * 9 + 5 + 3  # two `E`, two Mamba, the attention; embed, out


def build(tmp_path, name, *extra, world=WORLD, flags=FLAGS):
    args = train_cli.build_parser().parse_args(
        [*flags, "--logdir", str(tmp_path / name), *extra])
    cfg = train_cli.config_from_args(args)
    mesh = make_mesh(MeshSpec(data=world, seq=1), devices=jax.devices()[:world])
    return cfg, Trainer(
        cfg, mesh=mesh, profile_backward=not args.no_profile_backward,
        synthetic_data=True if args.synthetic else None)


@pytest.mark.parametrize("layers_held,tensor_share,indices", [
    (None, None, tuple(range(7))), ("1:5", "1:2", (1, 2, 3, 4, 5)),
    ("3", "0:4", (0, 1, 2)), ("3:1", "3:4", (3,)),
], ids=["all-seven-whole", "the-stage-of-the-tiny-cell",
        "a-bare-n-a-quarter-of-the-heads", "the-attention-layer-alone"])
def test_preset_and_flags_reach_the_factory_and_layers_keep_their_names(
        tmp_path, monkeypatch, layers_held, tensor_share, indices):
    """`--layers-held FIRST:COUNT` holds a stage anywhere in the model, under
    the published layer numbers; `--tensor-share INDEX:OF` sizes every
    layer's heads, groups and shared columns; router, latent projections and
    norms stay whole."""
    monkeypatch.setenv("MGWFBP_SYNTH_TRAIN_N", "64")
    flags = [f for f in FLAGS
             if f not in ("--layers-held", "1:5", "--tensor-share", "1:2")]
    extra = [] if layers_held is None else ["--layers-held", layers_held]
    extra += [] if tensor_share is None else ["--tensor-share", tensor_share]
    cfg, trainer = build(tmp_path, "a", *extra, world=1, flags=flags)
    try:
        assert cfg.optimizer == "adamw" and cfg.adam_b2 == 0.95
        assert cfg.weight_decay == 0.1 and cfg.norm_clip == 1.0
        model = trainer.model
        assert model.layer_indices() == indices
        assert model.experts_held == (2, 4)
        of = model.tensor_share[1]
        assert model.tensor_share == tuple(
            int(v) for v in (tensor_share or "0:1").split(":"))
        assert model.vocab_size == trainer.meta.num_classes == 256
        assert trainer.meta.input_shape == (64,) and trainer.meta.fused_loss
        params = trainer.state.params
        assert set(params) == {
            "embed", "out", *(f"layer_{i}" for i in indices)}
        assert set(params["out"]) == {"norm", "head"}  # untied
        for i in indices:
            leaves = params[f"layer_{i}"]
            kind = "MEM*EME"[i]
            assert ("in_proj" in leaves, "wq" in leaves, "router" in leaves) \
                == (kind == "M", kind == "*", kind == "E")
            assert leaves["norm"].shape == (32,)
            if kind == "M":
                heads, groups = 8 // of, 4 // of
                assert leaves["in_proj"].shape == (
                    32, 2 * heads * 8 + 2 * groups * 8 + heads)
                assert leaves["conv_w"].shape == (
                    4, heads * 8 + 2 * groups * 8)
                assert leaves["gate_norm"].shape == (heads * 8,)
                assert leaves["out_proj"].shape == (heads * 8, 32)
                # `rescale_prenorm_residual`: over the root of the seven
                assert 0.3 < np.std(leaves["out_proj"]) / (0.02 / 7 ** 0.5) \
                    < 1.7
            elif kind == "*":
                assert leaves["wq"].shape == (32, 8 // of * 8)
                assert leaves["wk"].shape == (32, max(2 // of, 1) * 8)
            else:
                assert leaves["router"].shape == (32, 8)  # all 8 scored
                assert leaves["router_bias"].shape == (8,)
                assert leaves["latent_down"].shape == (32, 16)  # whole
                assert leaves["latent_up"].shape == (16, 32)
                assert leaves["shared_up"].shape == (32, 48 // of)
                assert leaves["w_up"].shape == (4, 16, 24)
                assert leaves["w_down"].shape == (4, 24, 16)
                assert np.abs(leaves["router_bias"]).max() <= 0.02
                assert np.asarray(leaves["router_bias"]).any()
    finally:
        trainer.close()


def trained(tmp_path, patch, policy):
    """Two epochs under `policy`, after every leaf was seen to reduce like
    `lax.pmean`'s: what `program_records.read_run` reads of them."""
    patch.setenv("MGWFBP_SYNTH_TRAIN_N", str(6 * 2 * WORLD))
    patch.setenv("MGWFBP_SYNTH_VAL_N", "8")
    cfg, trainer = build(tmp_path, policy, "--policy", policy)
    try:
        reducer = trainer.reducer
        assert reducer is not None and trainer.data_size == WORLD
        with_paths = jax.tree_util.tree_flatten_with_path(
            trainer.state.params)[0]
        names = [jax.tree_util.keystr(kp) for kp, _ in with_paths]
        shapes = {leaf.shape for _, leaf in with_paths}
        # what the solver and the buckets are handed
        assert {(4, 16, 24), (4, 24, 16), (32, 8), (8,), (32, 16), (16, 32),
                (32, 24), (24, 32), (32, 100), (4, 64), (64,), (4,), (32, 32),
                (32, 8)} <= shapes
        assert len(names) == LEAVES
        assert sorted(i for g in reducer.layout.groups for i in g) \
            == list(range(len(names)))
        assert reducer.schedule.num_groups == len(names)

        # the real per-device gradients, reduced both ways in one program
        loss_fn = make_loss_fn(trainer.model, trainer.meta)
        x, y = trainer.bundle.train.inner.load_batch(0, 0)
        assert x.shape == (2 * WORLD, 64)

        def body(params, xb, yb):
            grads = jax.grad(
                lambda p: loss_fn(
                    p, {}, {"x": xb, "y": yb}, jax.random.PRNGKey(0), None,
                )[0])(params)
            return reducer(grads), lax.pmean(grads, DATA_AXIS)

        reduced, plain = jax.jit(shard_map(
            body, mesh=trainer.mesh,
            in_specs=(P(), P(DATA_AXIS), P(DATA_AXIS)),
            out_specs=(P(), P()), check_vma=False,
        ))(trainer.state.params, jnp.asarray(x), jnp.asarray(y))
        for got, want, name in zip(
                jax.tree_util.tree_leaves(reduced),
                jax.tree_util.tree_leaves(plain), names):
            # no gradient reaches the selection bias: the choice carries none
            assert (float(jnp.linalg.norm(want)) > 0) \
                == ("router_bias" not in name), name
            np.testing.assert_allclose(
                got, want, rtol=1e-6, atol=1e-9, err_msg=name)

        bias_0 = {
            name: np.asarray(leaf) for name, leaf in zip(
                names, jax.tree_util.tree_leaves(trainer.state.params))
            if "router_bias" in name}
        trainer.fit(2)
        assert trainer.iteration == 12
        # ... and the window does not update it
        after = dict(zip(
            names, jax.tree_util.tree_leaves(trainer.state.params)))
        assert len(bias_0) == 2
        for name, before in bias_0.items():
            np.testing.assert_array_equal(np.asarray(after[name]), before)
    finally:
        trainer.close()
    return program_records.read_run(str(tmp_path / policy), cfg, trainer)


@pytest.fixture(scope="module")
def wfbp_run(tmp_path_factory):
    """The file's one training under `wfbp` with the telemetry on, for every
    test that reads what it left."""
    with pytest.MonkeyPatch.context() as patch:
        return trained(tmp_path_factory.mktemp("wfbp"), patch, "wfbp")


def test_trains_with_counters_and_every_leaf_reduces_like_pmean(wfbp_run):
    _, records, _ = wfbp_run
    steps = events_of(records, "step")
    health = {h["step"]: h for h in events_of(records, "health")}
    assert [s["step"] for s in steps] == list(range(1, 13))
    assert set(health) == set(range(1, 13))
    assert health[12]["loss"] < health[1]["loss"] - 0.05
    assert all(np.isfinite(h["grad_norm"]) and h["grad_norm"] > 0
               for h in health.values())
    assert events_of(records, "bad_step") == []
    with_counters = [s for s in steps if "moe_latent_rms" in s]
    assert len(with_counters) >= 10
    for s in with_counters:
        assert s["moe_dropped"] == 0.0 and "stats_ready" in s
        assert 0.0 < s["moe_here"] < 1.0
        assert s["moe_load_max"] >= s["moe_load_mean"] > 0.0
        # four of eight experts held, 2 x 64 tokens a device choosing 3
        assert s["moe_load_mean"] * 4 <= 2 * 64 * 3
        assert 0.0 < s["moe_bias_swap_share"] < 0.6
        assert 0.01 < s["moe_latent_rms"] < 2.0
        assert 0.3 < s["moe_relu2_active"] < 0.7
        assert s["ssm_state_rms"] > 0.0 and s["ssm_log_decay_min"] < 0.0
    assert not [k for s in steps for k in s if k.startswith("health/")]
    # the report reads the scan's and the routing's counters as it reads
    # Granite's and Mellum 2's, and the new ones on a line of their own
    import telemetry_report

    report = telemetry_report.format_report(records)
    assert "expert routing" in report
    assert "state-space scan" in report
    assert "latent experts" in report
    for said in ("latent's rms", "relu left on", "selection bias"):
        assert said in report, said


@pytest.mark.parametrize("op,want", [
    # one attention layer's core; each layer under a `jax.checkpoint` whose
    # cached trace `counted` counts again; the two `E` layers 2 grouped
    # products and 2 permutations each; the two Mamba layers a convolution
    # each and, at two B/C groups a share, two scans each
    ("attention", {"kernel": 0, "blocks": 1}),
    ("experts", {"kernel": 0, "ragged": 4, "programs": 0}),
    ("rows", {"rows_held": 0, "rows_all": 4, "rows_programs": 0}),
    ("groups", {"bounded": 0, "whole": 2}),
    ("scan", {"kernel": 0, "plain": 0, "programs": 0}),
    ("delta", {"kernel": 0, "plain": 0, "programs": 0}),
    ("conv", {"kernel": 0, "plain": 2, "programs": 0}),
    ("streams", {"kernel": 0, "plain": 0, "programs": 0}),
    ("ssd", {"kernel": 0, "plain": 4, "programs": 0}),
], ids=program_records.OPS)
def test_the_step_program_leaves_its_records(wfbp_run, op, want):
    program_records.holds(wfbp_run, op, want)


def test_the_step_verifies_and_the_counters_add_no_collective():
    from mgwfbp_tpu.analysis.jaxpr_check import (
        trace_train_step,
        verify_health_stats_footprint,
        verify_train_step,
    )

    assert verify_train_step("nemotron3s_tiny", "wfbp", batch_size=8) == []
    assert verify_health_stats_footprint("nemotron3s_tiny", "wfbp") == []
    _, reducer, leaves = trace_train_step(
        "nemotron3s_tiny", "wfbp", batch_size=8)
    stacked = [leaf for leaf in leaves if leaf.ndim == 3]
    assert len(stacked) == 2 * 3 and len(leaves) == 3 * 9 + 3 * 9 + 5 + 3
    assert sorted(i for g in reducer.layout.groups for i in g) \
        == list(range(len(leaves)))


@pytest.mark.parametrize("dnn,flag,share,message", [
    ("nemotron3s_tiny", "--experts-held", "6:4", "not among the model's 8"),
    ("nemotron3s_tiny", "--layers-held", "5:3", "not among the model's 7"),
    ("nemotron3s_tiny", "--tensor-share", "0:3",
     "do not divide the model's 4 B/C groups"),
    ("nemotron3s_tiny", "--tensor-share", "2:2", "names no member"),
    ("nemotron3s_tiny", "--tensor-share", "half", "is not INDEX:OF"),
    ("xing4_tiny", "--tensor-share", "0:2", "holds every layer's heads whole"),
    ("lenet", "--tensor-share", "0:2", "cannot be held in part"),
], ids=["experts-out-of-range", "layers-out-of-range", "three-chips",
        "member-out-of-range", "no-integers", "a-model-without-the-share",
        "a-model-held-whole"])
def test_a_share_that_cannot_be_held_fails_with_the_families_message(
        tmp_path, monkeypatch, dnn, flag, share, message):
    monkeypatch.setenv("MGWFBP_SYNTH_TRAIN_N", "16")
    dataset = ["--dataset", "tokens", "--vocab-size", "256"] \
        if dnn != "lenet" else []
    args = train_cli.build_parser().parse_args([
        "--dnn", dnn, *dataset, flag, share, "--synthetic",
        "--no-profile-backward", "--logdir", str(tmp_path)])
    cfg = train_cli.config_from_args(args)
    with pytest.raises(ValueError, match=message):
        Trainer(cfg, profile_backward=False, synthetic_data=True).close()
