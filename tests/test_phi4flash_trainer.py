"""Phi-4-mini-flash (models/phi4flash.py) on the Trainer's path at the tiny
size, data parallel over four virtual devices, through `train_cli`'s flags:
`--layers-held FIRST:COUNT` reaches the factory (and is refused where a stage
has nothing to read, or a family holds its first layers only), it trains
under `--policy mgwfbp` and `wfbp`, the three counters ride on the `step`
records, it resumes from a checkpoint bitwise, and every leaf is reduced like
`lax.pmean`'s of the same per-device gradients in the order the schedule
assumes: the leaves of the Mamba layer whose memory and of the full layer
whose keys and values later layers read are complete only after every reader's
backward, which the reverse order of the layers already is. The equations are
held against the plain reference in
tests/benchmark/test_phi4flash_reference.py."""

import jax
import jax.numpy as jnp
import numpy as np
import program_records
import pytest
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from mgwfbp_tpu import train_cli
from mgwfbp_tpu.models import create_model, parse_layers_held
from mgwfbp_tpu.parallel.mesh import DATA_AXIS, MeshSpec, make_mesh
from mgwfbp_tpu.telemetry.events import events_of
from mgwfbp_tpu.train.step import make_loss_fn
from mgwfbp_tpu.train.trainer import Trainer
from mgwfbp_tpu.utils.faults import Preempted

WORLD = 4
# the tiny model's stage round its hinge, as the benchmark's cell holds
# layers 14 to 19 of 32: [mamba, window, mamba*, full*, gmu, cross] of 8
STAGE = "2:6"
FLAGS = [
    "--dnn", "phi4flash_tiny", "--dataset", "tokens", "--vocab-size", "256",
    "--num-steps", "64", "--batch-size", "2", "--lr", "0.01",
    "--lr-schedule", "const", "--synthetic", "--telemetry",
    "--no-profile-backward", "--num-batches-per-epoch", "6",
    "--max-epochs", "2", "--seed", "5", "--layers-held", STAGE,
]


def build(tmp_path, name, *extra, flags=FLAGS):
    args = train_cli.build_parser().parse_args(
        [*flags, "--logdir", str(tmp_path / name), *extra])
    cfg = train_cli.config_from_args(args)
    mesh = make_mesh(MeshSpec(data=WORLD, seq=1), devices=jax.devices()[:WORLD])
    return cfg, Trainer(
        cfg, mesh=mesh, profile_backward=not args.no_profile_backward,
        synthetic_data=True if args.synthetic else None)


def test_preset_and_flags_reach_the_factory_and_the_optimizer(
        tmp_path, monkeypatch):
    monkeypatch.setenv("MGWFBP_SYNTH_TRAIN_N", "64")
    cfg, trainer = build(tmp_path, "a")
    try:
        assert cfg.optimizer == "adamw" and cfg.adam_b2 == 0.95
        assert cfg.weight_decay == 0.1 and cfg.norm_clip == 1.0
        assert cfg.layers_held == STAGE
        assert trainer.model.layers_held == (2, 6)
        assert trainer.model.layer_indices() == (2, 3, 4, 5, 6, 7)
        assert trainer.model.vocab_size == trainer.meta.num_classes == 256
        assert trainer.meta.input_shape == (64,) and trainer.meta.fused_loss
        params = trainer.state.params
        # a held layer keeps its published index
        assert set(params) == {
            "embed", "out", *(f"layer_{i}" for i in range(2, 8))}
        assert set(params["out"]) == {"norm", "norm_b"}  # no head leaf: tied
        assert params["layer_2"]["in_proj"].shape == (32, 128)
        assert params["layer_4"]["a_log"].shape == (64, 4)
        assert params["layer_4"]["x_proj"].shape == (64, 2 + 8)
        assert params["layer_3"]["wqkv"].shape == (32, 32 + 2 * 16)
        assert params["layer_5"]["sub_norm"].shape == (16,)
        assert set(params["layer_6"]) == {
            "w_g", "w_o", "norm", "norm_b", "mlp_norm", "mlp_norm_b",
            "w1", "w2"}
        assert "wqkv" not in params["layer_7"]
        assert params["layer_7"]["wq"].shape == (32, 32)
        np.testing.assert_allclose(
            -np.exp(np.asarray(params["layer_2"]["a_log"])),
            -np.broadcast_to(np.arange(1.0, 5.0), (64, 4)), rtol=1e-6)
        dt = np.log1p(np.exp(np.asarray(params["layer_4"]["dt_bias"])))
        assert ((dt >= 1e-3 * 0.999) & (dt <= 1e-1 * 1.001)).all()
    finally:
        trainer.close()


@pytest.mark.parametrize("value,want", [
    (None, None), (3, (0, 3)), ("3", (0, 3)), ("0:3", (0, 3)),
    ("14:6", (14, 6)), ((14, 6), (14, 6)),
])
def test_layers_held_is_n_or_first_and_count(value, want):
    assert parse_layers_held(value) == want


@pytest.mark.parametrize("name,held,said", [
    # a stage that holds a reader without what it reads
    ("phi4flash_tiny", "6:2", ("layer 6", "memory", "layer 4")),
    ("phi4flash_tiny", "7:1", ("layer 7", "keys and values", "layer 5")),
    ("phi4flash_tiny", "5:4", ("not among", "8")),
    ("phi4flash_tiny", "two", ("neither N nor FIRST:COUNT",)),
    # the three older families hold their first layers only
    ("granite4h_tiny", "1:2", ("granite4h_tiny", "FIRST other than 0")),
    ("mellum2_tiny", "2:2", ("mellum2_tiny", "FIRST other than 0")),
    ("laguna_xs2_tiny", "1:3", ("laguna_xs2_tiny", "FIRST other than 0")),
])
def test_a_share_that_cannot_be_held_is_refused_by_name(name, held, said):
    with pytest.raises(ValueError) as refused:
        model, _ = create_model(name, layers_held=held)
        model.init({"params": jax.random.PRNGKey(0)},
                   jnp.zeros((1, 8), jnp.int32), train=False)
    for part in said:
        assert part in str(refused.value)


@pytest.mark.parametrize("name,n,share", [
    ("granite4h_tiny", 3, {}),
    ("mellum2_tiny", 2, {"experts_held": (2, 2)}),
    ("laguna_xs2_tiny", 3, {"experts_held": (2, 4)}),
])
def test_a_bare_n_is_the_program_it_was(name, n, share):
    """`--layers-held N`, `"N"` and `0:N` build the same module (the count as
    the older families always took it) and lower to the same program."""
    modules = [create_model(name, layers_held=v, **share)[0]
               for v in (n, str(n), f"0:{n}")]
    assert modules[0].layers_held == n
    assert modules[0] == modules[1] == modules[2]
    x = jnp.zeros((1, 64), jnp.int32)

    def lowered(module):
        params = jax.eval_shape(
            lambda: module.init({"params": jax.random.PRNGKey(0)}, x))
        return jax.jit(jax.grad(lambda p: jnp.mean(
            module.apply(p, x, targets=x, train=True)[0]))).lower(
                params).as_text()

    assert lowered(modules[0]) == lowered(modules[2])


def trained(tmp_path, patch, policy):
    """Two epochs under `policy`, after every leaf was seen to reduce like
    `lax.pmean`'s: what `program_records.read_run` reads of them."""
    patch.setenv("MGWFBP_SYNTH_TRAIN_N", str(6 * 2 * WORLD))
    patch.setenv("MGWFBP_SYNTH_VAL_N", "8")
    cfg, trainer = build(tmp_path, policy, "--policy", policy)
    try:
        reducer = trainer.reducer
        assert reducer is not None and trainer.data_size == WORLD
        names = [jax.tree_util.keystr(kp) for kp, _ in
                 jax.tree_util.tree_flatten_with_path(trainer.state.params)[0]]
        # arrival order: the readers' leaves (layers 6, 7) before the leaves
        # of the layers they read (4, 5), the tied embedding last
        arrival = [names[j] for j in reducer.perm]
        assert arrival[-1] == "['embed']['embedding']"
        layer_of = [int(n.split("_")[1].split("'")[0])
                    for n in arrival if n.startswith("['layer_")]
        assert layer_of == sorted(layer_of, reverse=True)
        assert set(layer_of) == set(range(2, 8))
        assert sorted(i for g in reducer.layout.groups for i in g) \
            == list(range(len(names)))
        if policy == "wfbp":
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
            return reducer(grads), lax.pmean(grads, DATA_AXIS), \
                grads["layer_5"]["wqkv"][None]

        reduced, plain, local = jax.jit(shard_map(
            body, mesh=trainer.mesh,
            in_specs=(P(), P(DATA_AXIS), P(DATA_AXIS)),
            out_specs=(P(), P(), P(DATA_AXIS)), check_vma=False,
        ))(trainer.state.params, jnp.asarray(x), jnp.asarray(y))
        local = np.asarray(local)  # (WORLD, hidden, q + k + v columns)
        assert np.abs(local[0] - local[1]).max() > 0  # ranks differ
        for got, want, name in zip(
                jax.tree_util.tree_leaves(reduced),
                jax.tree_util.tree_leaves(plain), names):
            assert float(jnp.linalg.norm(want)) > 0, name
            np.testing.assert_allclose(
                got, want, rtol=1e-6, atol=1e-9, err_msg=name)
        # the full layer's key and value columns hold what its own core AND
        # the cross layer's send back: reduced like the rest
        np.testing.assert_allclose(
            reduced["layer_5"]["wqkv"], local.mean(axis=0),
            rtol=1e-5, atol=1e-8)

        trainer.fit(2)
        assert trainer.iteration == 12
    finally:
        trainer.close()
    return program_records.read_run(str(tmp_path / policy), cfg, trainer)


@pytest.fixture(scope="module")
def wfbp_run(tmp_path_factory):
    """The file's one training under `wfbp` with the telemetry on, for every
    test that reads what it left."""
    with pytest.MonkeyPatch.context() as patch:
        return trained(tmp_path_factory.mktemp("wfbp"), patch, "wfbp")


@pytest.mark.parametrize("policy", ["mgwfbp", "wfbp"])
def test_trains_with_counters_and_every_leaf_reduces_like_pmean(
        tmp_path, monkeypatch, request, policy):
    _, records, _ = (
        request.getfixturevalue("wfbp_run") if policy == "wfbp"
        else trained(tmp_path, monkeypatch, policy))
    steps = events_of(records, "step")
    health = {h["step"]: h for h in events_of(records, "health")}
    assert [s["step"] for s in steps] == list(range(1, 13))
    assert set(health) == set(range(1, 13))
    # the first and the twelfth loss are the parent commit's to the last
    # digit, under either policy (read there on the same seeds, PR 42: the
    # convolution's plain form moved to ops/shortconv.py, it did not change)
    assert (health[1]["loss"], health[12]["loss"]) == (5.552222728729248, 4.329502582550049)
    assert health[12]["loss"] < health[1]["loss"] - 0.05
    assert all(np.isfinite(h["grad_norm"]) and h["grad_norm"] > 0
               for h in health.values())
    assert events_of(records, "bad_step") == []
    with_counters = [s for s in steps if "sel_scan_state_rms" in s]
    assert len(with_counters) >= 10
    for s in with_counters:
        assert s["sel_scan_state_rms"] > 0.0 and s["gmu_gate_rms"] > 0.0
        # mean of lam over layers 3, 5, 7 near the mean of their lam0
        assert 0.3 < s["diff_lambda_mean"] < 1.0
        assert "stats_ready" in s
    assert not [k for s in steps for k in s if k.startswith("health/")]


@pytest.mark.parametrize("op,want", [
    # one stacked core a layer (window, full, cross), the stage's two Mamba
    # layers' scans and convolutions; every layer is a trace of its own (its
    # index is a static argument), and where another test of this process
    # traced it before, `counted` notes what that trace noted
    ("attention", {"kernel": 0, "blocks": 3}),
    ("experts", {"kernel": 0, "ragged": 0, "programs": 0}),
    ("rows", {"rows_held": 0, "rows_all": 0, "rows_programs": 0}),
    ("groups", {"bounded": 0, "whole": 0}),
    ("scan", {"kernel": 0, "plain": 2, "programs": 0}),
    ("delta", {"kernel": 0, "plain": 0, "programs": 0}),
    ("conv", {"kernel": 0, "plain": 2, "programs": 0}),
    ("streams", {"kernel": 0, "plain": 0, "programs": 0}),
    ("ssd", {"kernel": 0, "plain": 0, "programs": 0}),
], ids=program_records.OPS)
def test_the_step_program_leaves_its_records(wfbp_run, op, want):
    program_records.holds(wfbp_run, op, want)


def test_exact_step_resume_is_bitwise(tmp_path, monkeypatch):
    monkeypatch.setenv("MGWFBP_SYNTH_TRAIN_N", "96")
    monkeypatch.delenv("MGWFBP_FAULT_PLAN", raising=False)
    _, whole = build(tmp_path, "whole")
    try:
        whole.fit(1)
        want = jax.tree_util.tree_map(np.asarray, (
            whole.state.params, whole.state.opt_state))
        assert whole.iteration == 6
    finally:
        whole.close()
    ckpt = ["--checkpoint-dir", str(tmp_path / "ckpt"),
            "--ckpt-every-steps", "2"]
    monkeypatch.setenv("MGWFBP_FAULT_PLAN", "preempt@step=3")
    _, cut = build(tmp_path, "cut", *ckpt)
    try:
        with pytest.raises(Preempted) as exc:
            cut.fit(1)
        assert exc.value.iteration == 3
    finally:
        cut.close()
    monkeypatch.delenv("MGWFBP_FAULT_PLAN")
    _, resumed = build(tmp_path, "cut", *ckpt)
    try:
        assert resumed.iteration == 3 and resumed.start_epoch == 0
        assert resumed.model.layers_held == (2, 6)
        resumed.fit(1)
        assert resumed.iteration == 6
        got = jax.tree_util.tree_map(np.asarray, (
            resumed.state.params, resumed.state.opt_state))
    finally:
        resumed.close()
    for a, b in zip(jax.tree_util.tree_leaves(want),
                    jax.tree_util.tree_leaves(got)):
        np.testing.assert_array_equal(a, b)


def test_the_step_verifies_and_the_counters_add_no_collective():
    from mgwfbp_tpu.analysis.jaxpr_check import (
        verify_health_stats_footprint,
        verify_train_step,
    )

    assert verify_train_step("phi4flash_tiny", "wfbp", batch_size=8) == []
    assert verify_train_step(
        "phi4flash_tiny", "mgwfbp", batch_size=8, norm_clip=1.0) == []
    assert verify_health_stats_footprint("phi4flash_tiny", "wfbp") == []
