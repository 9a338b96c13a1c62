"""Trainer/CLI/profiling/checkpoint integration tests on the 8-device CPU
mesh — the reference's "multi-node without a cluster" strategy (SURVEY.md §4)
with real assertions instead of oracle A/B runs."""

import glob
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mgwfbp_tpu.config import make_config
from mgwfbp_tpu.train.trainer import Trainer


def _cfg(dnn="mnistnet", **kw):
    base = dict(
        lr=0.01, max_epochs=2, logdir="", checkpoint_dir=None, seed=3,
        batch_size=8,
    )
    base.update(kw)
    return make_config(dnn, **base)


def test_trainer_end_to_end_mnist(tmp_path):
    cfg = _cfg(checkpoint_dir=str(tmp_path / "ckpt"))
    t = Trainer(cfg, synthetic_data=True)
    assert t.reducer is not None and t.reducer.schedule.num_groups >= 1
    metrics = t.fit(2)
    assert "eval" in metrics
    assert np.isfinite(metrics["train"]["loss"])
    assert metrics["eval"]["top1"] >= 0.0

    # resume: a fresh trainer picks up from the checkpoint
    t2 = Trainer(cfg, synthetic_data=True, profile_backward=False)
    assert t2.start_epoch == 2
    assert int(t2.state.step) == int(t.state.step)


@pytest.mark.slow
def test_trainer_policies_same_loss():
    # wfbp / single / none must agree numerically given the same seed. Over
    # a SHORT horizon the comparison is tight; a full epoch lets ULP-level
    # rounding differences of the packed single-bucket reduction compound
    # chaotically (exact per-application parity is pinned in
    # tests/test_allreduce.py).
    losses = {}
    for policy in ("wfbp", "single", "auto", "none"):
        cfg = _cfg(policy=policy, num_batches_per_epoch=5)
        t = Trainer(cfg, synthetic_data=True, profile_backward=False)
        m = t.train_epoch(0)
        losses[policy] = m["loss"]
    vals = list(losses.values())
    assert max(vals) - min(vals) < 1e-5, losses


def test_evaluate_indivisible_val_set_counts_every_sample():
    """Val set whose size is NOT divisible by the 8-device data axis: every
    sample must be evaluated (reference dl_trainer.py:854-937), with top1
    matching a hand computation over the same samples."""
    cfg = _cfg()
    t = Trainer(cfg, synthetic_data=True, profile_backward=False)
    rs = np.random.RandomState(11)
    n = 21  # 21 % 8 != 0; also indivisible tail within each batch of 8
    x = rs.randn(n, 28, 28, 1).astype(np.float32)
    y = rs.randint(0, 10, size=(n,)).astype(np.int32)
    t.bundle.val = [
        (x[:8], y[:8]), (x[8:16], y[8:16]), (x[16:], y[16:])
    ]
    out = t.evaluate()
    assert out["count"] == n
    logits = t.model.apply(
        {"params": t.state.params, "batch_stats": t.state.batch_stats},
        jnp.asarray(x), train=False,
    )
    want_top1 = float((np.argmax(np.asarray(logits), -1) == y).mean())
    assert out["top1"] == pytest.approx(want_top1, abs=1e-6)


def test_trainer_gradient_accumulation_runs():
    cfg = _cfg(nsteps_update=2)
    t = Trainer(cfg, synthetic_data=True, profile_backward=False)
    m = t.train_epoch(0)
    assert np.isfinite(m["loss"])


def test_trainer_lstm_carry_epoch(monkeypatch):
    # full-size PTB LSTM (1500-d, 10k vocab) is CPU-prohibitive; swap in a
    # tiny one through the registry — the trainer path is what's under test
    from mgwfbp_tpu import models as zoo
    from mgwfbp_tpu.models import ModelMeta
    from mgwfbp_tpu.models.lstm import PTBLSTM

    def tiny_lstm(nc):
        nc = nc or 10000
        return (
            PTBLSTM(vocab_size=nc, hidden_size=16, num_layers=2, dropout=0.0),
            ModelMeta(name="lstm", dataset="ptb", num_classes=nc,
                      input_shape=(35,), input_dtype=jnp.int32, task="lm",
                      has_carry=True),
        )

    monkeypatch.setitem(zoo._REGISTRY, "lstm", tiny_lstm)
    cfg = _cfg("lstm", batch_size=1, max_epochs=1)
    t = Trainer(cfg, synthetic_data=True, profile_backward=False)
    m = t.train_epoch(0)
    assert "perplexity" in m
    ev = t.evaluate()
    assert "perplexity" in ev


@pytest.mark.slow
def test_trainer_ctc_wer_eval(monkeypatch):
    from mgwfbp_tpu import models as zoo
    from mgwfbp_tpu.models import ModelMeta
    from mgwfbp_tpu.models.deepspeech import DeepSpeech

    def tiny_ds(nc):
        nc = nc or 29
        return (
            DeepSpeech(num_classes=nc, hidden_size=16, num_layers=1),
            ModelMeta(name="lstman4", dataset="an4", num_classes=nc,
                      input_shape=(201, 161), task="ctc"),
        )

    monkeypatch.setitem(zoo._REGISTRY, "lstman4", tiny_ds)
    cfg = _cfg("lstman4", batch_size=1, max_epochs=1)
    t = Trainer(cfg, synthetic_data=True, profile_backward=False)
    m = t.train_epoch(0)
    assert np.isfinite(m["loss"])
    ev = t.evaluate()
    assert 0.0 <= ev["wer"]


def test_cli_print_config(capsys):
    from mgwfbp_tpu.train_cli import main

    rc = main(["--dnn", "resnet20", "--policy", "wfbp", "--print-config"])
    assert rc == 0
    out = json.loads(capsys.readouterr().out)
    assert out["dnn"] == "resnet20" and out["policy"] == "wfbp"
    assert out["dataset"] == "cifar10" and out["batch_size"] == 32


@pytest.mark.slow
def test_cli_end_to_end(capsys):
    from mgwfbp_tpu.train_cli import main

    rc = main([
        "--dnn", "mnistnet", "--batch-size", "8", "--lr", "0.01",
        "--epochs", "1", "--synthetic", "--no-profile-backward",
        "--logdir", "",
    ])
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert "train" in out


def test_profile_allreduce_fits(mesh8):
    from mgwfbp_tpu.profiling import profile_allreduce

    prof = profile_allreduce(
        mesh8, sizes=(1024, 8192, 65536), warmup=1, iters=3
    )
    assert prof.model.alpha >= 0 and prof.model.beta >= 0
    assert len(prof.times_s) == 3


def test_benchmark_backward_distributes_total():
    from mgwfbp_tpu.profiling import benchmark_backward

    def loss(p, x):
        return jnp.sum(p["a"] * x) ** 2 + jnp.sum(p["b"]) ** 2

    params = {"a": jnp.ones((100,)), "b": jnp.ones((900,))}
    tb = benchmark_backward(loss, params, (jnp.ones((100,)),), [0, 1],
                            warmup=1, iters=5)
    assert len(tb) == 2
    assert all(t >= 0 for t in tb)
    # weight proportional to numel: b (900) gets ~9x a's share
    assert tb[1] > tb[0]


@pytest.mark.slow
def test_accumulation_lr_schedule_counts_optimizer_steps():
    # nsteps_update=2 halves optimizer steps per epoch; warmup must still
    # complete in the same number of wall epochs
    from mgwfbp_tpu.optim.schedules import as_step_fn, resolve

    cfg2 = _cfg(nsteps_update=2)
    t2 = Trainer(cfg2, synthetic_data=True, profile_backward=False)
    loader_batches = t2.bundle.num_batches_per_epoch
    # after one epoch the step counter is loader_batches // 2
    t2.train_epoch(0)
    assert int(t2.state.step) == loader_batches // 2
    # the schedule seen inside the optimizer treats that as epoch ~1.0
    sched = resolve("auto", cfg2.lr, dataset=cfg2.dataset)
    step_fn = as_step_fn(sched, loader_batches // 2)
    lr_after_epoch1 = float(step_fn(int(t2.state.step)))
    assert lr_after_epoch1 == pytest.approx(float(sched(1.0)))


@pytest.mark.slow
def test_fit_epochs_relative_to_resume(tmp_path):
    cfg = _cfg(checkpoint_dir=str(tmp_path / "c2"))
    t = Trainer(cfg, synthetic_data=True, profile_backward=False)
    t.fit(1)
    t.checkpointer.wait()
    t2 = Trainer(cfg, synthetic_data=True, profile_backward=False)
    assert t2.start_epoch == 1
    steps_before = int(t2.state.step)
    t2.fit(1)  # one MORE epoch, not zero
    assert int(t2.state.step) > steps_before


def test_logger_swaps_file_handler(tmp_path):
    import logging

    from mgwfbp_tpu.utils.logging import get_logger

    f1 = str(tmp_path / "a" / "run.log")
    f2 = str(tmp_path / "b" / "run.log")
    log = get_logger("mgwfbp.test.swap", logfile=f1)
    log.info("one")
    log = get_logger("mgwfbp.test.swap", logfile=f2)
    log.info("two")
    assert "one" in open(f1).read()
    content2 = open(f2).read()
    assert "two" in content2 and "one" not in content2


def test_pretrain_initializes_from_other_run(tmp_path):
    cfg = _cfg(checkpoint_dir=str(tmp_path / "runA"))
    t = Trainer(cfg, synthetic_data=True, profile_backward=False)
    t.fit(1)
    t.checkpointer.wait()
    run_a_dir = t.checkpointer._dir
    t.close()

    cfg_b = _cfg(pretrain=run_a_dir, seed=4)
    t2 = Trainer(cfg_b, synthetic_data=True, profile_backward=False)
    # weights and counters came from run A
    assert t2.start_epoch == 1
    a = jax.tree_util.tree_leaves(t.state.params)[0]
    b = jax.tree_util.tree_leaves(t2.state.params)[0]
    np.testing.assert_allclose(np.asarray(a), np.asarray(b))
    t2.close()


def test_pretrain_missing_raises(tmp_path):
    cfg = _cfg(pretrain=str(tmp_path / "nope"))
    with pytest.raises(FileNotFoundError):
        Trainer(cfg, synthetic_data=True, profile_backward=False)


def test_checkpoint_dirs_distinct_per_policy(tmp_path):
    cfg1 = _cfg(checkpoint_dir=str(tmp_path), policy="mgwfbp")
    cfg2 = _cfg(checkpoint_dir=str(tmp_path), policy="none")
    t1 = Trainer(cfg1, synthetic_data=True, profile_backward=False)
    t2 = Trainer(cfg2, synthetic_data=True, profile_backward=False)
    assert t1.checkpointer._dir != t2.checkpointer._dir
    t1.close()
    t2.close()


def test_evaluate_cli_offline(tmp_path, capsys):
    cfg = _cfg(checkpoint_dir=str(tmp_path))
    t = Trainer(cfg, synthetic_data=True, profile_backward=False)
    t.fit(1)
    t.checkpointer.wait()
    run_dir = t.checkpointer._dir
    t.close()

    from mgwfbp_tpu.evaluate import main as eval_main

    rc = eval_main([
        "--dnn", "mnistnet", "--checkpoint-dir", run_dir,
        "--batch-size", "8", "--synthetic",
    ])
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["epoch"] == 0 and "top1" in out

    # --all-epochs: one JSON line per saved epoch (scripts/eval.sh loop)
    rc = eval_main([
        "--dnn", "mnistnet", "--checkpoint-dir", run_dir,
        "--batch-size", "8", "--synthetic", "--all-epochs",
    ])
    assert rc == 0
    lines = [
        json.loads(l)
        for l in capsys.readouterr().out.strip().splitlines()
        if l.startswith("{")
    ]
    # last line is the running-best summary (reference evaluate.py:47-57)
    assert lines[-1]["best"]["epoch"] == 0 and "top1" in lines[-1]["best"]
    lines = lines[:-1]
    assert [m["epoch"] for m in lines] == [0]
    assert all("top1" in m for m in lines)


def test_calibrate_cli(tmp_path, capsys):
    from mgwfbp_tpu.calibrate import main as cal_main

    out_path = str(tmp_path / "prof.json")
    rc = cal_main(["--out", out_path, "--min-log2", "10", "--max-log2", "13",
                   "--iters", "2", "--warmup", "1"])
    assert rc == 0
    from mgwfbp_tpu.parallel.costmodel import load_profile

    model = load_profile(out_path)
    assert model.alpha >= 0 and model.beta >= 0


def test_update_nworker_elastic_resize():
    """Elastic resize (reference update_nworker, dl_trainer.py:545-566):
    shrink the data axis 8 -> 4 mid-training, then grow back. The merge
    schedule must be re-solved for the new world size, state must stay
    replicated, and training must keep running with the resized loaders."""
    cfg = _cfg(num_batches_per_epoch=3)
    t = Trainer(cfg, synthetic_data=True)
    assert t.data_size == 8
    m8 = t.train_epoch(0)
    assert np.isfinite(m8["loss"])
    groups8 = t.reducer.schedule.num_groups
    batch8 = t.process_batch

    t.update_nworker(4)
    assert t.data_size == 4 and t.config.nworkers == 4
    assert t.process_batch == batch8 // 2  # weak scaling: per-device fixed
    assert t.mesh.devices.size == 4
    assert t.reducer is not None and t.reducer.schedule.num_groups >= 1
    m4 = t.train_epoch(1)
    assert np.isfinite(m4["loss"])

    t.update_nworker(8)
    assert t.process_batch == batch8
    assert t.reducer.schedule.num_groups == groups8  # same tb, same solver
    m8b = t.train_epoch(2)
    assert np.isfinite(m8b["loss"])


def test_update_nworker_rejects_bad_sizes():
    cfg = _cfg()
    t = Trainer(cfg, synthetic_data=True, profile_backward=False)
    with pytest.raises(ValueError):
        t.update_nworker(0)
    with pytest.raises(ValueError):
        t.update_nworker(16)  # only 8 virtual devices


def test_scalar_writer_events(tmp_path):
    """The TensorBoard seam (reference dist_trainer.py:136-137, disabled
    there) streams train/eval scalars to a JSONL event file."""
    from mgwfbp_tpu.utils.summary import read_events

    cfg = _cfg(logdir=str(tmp_path), tensorboard=True, num_batches_per_epoch=12)
    t = Trainer(cfg, synthetic_data=True, profile_backward=False)
    t.fit(1)
    t.close()
    path = os.path.join(str(tmp_path), cfg.tag(), "events.jsonl")
    events = read_events(path)
    tags = {e["tag"] for e in events}
    assert "train/loss" in tags and "train/sec_per_iter" in tags
    assert "epoch/loss" in tags and "eval/top1" in tags
    for e in events:
        assert np.isfinite(e["value"]) and e["step"] >= 0


def test_update_nworker_lr_schedule_continues():
    """The LR schedule must CONTINUE from its epoch position across a resize
    (re-deriving epoch = step/new_nbpe from the carried-over step count
    would jump it discontinuously)."""
    from mgwfbp_tpu.optim.schedules import as_step_fn

    sched = lambda e: 0.1 * (e + 1.0)  # strictly epoch-dependent
    old = as_step_fn(sched, 10)
    # at step 30 the old conversion stands at epoch 3.0; the resized one
    # (20 batches/epoch) anchored there must agree exactly at the seam...
    new = as_step_fn(sched, 20, step_offset=30, epoch_offset=3.0)
    assert float(new(30)) == pytest.approx(float(old(30)))
    # ...and advance at the NEW rate afterwards: +20 steps = +1 epoch
    assert float(new(50)) == pytest.approx(float(sched(4.0)))

    cfg = _cfg(num_batches_per_epoch=3)
    t = Trainer(cfg, synthetic_data=True, profile_backward=False)
    t.train_epoch(0)
    nbpe = max(t._steps_per_epoch(), 1)
    steps = int(t.state.step)
    t.update_nworker(4)
    assert t._sched_step_offset == steps
    assert t._sched_epoch_offset == pytest.approx(steps / nbpe)


def test_logdir_and_events_share_run_tag(tmp_path):
    """train.log and events.jsonl must land in the SAME tagged run dir (the
    tag reflects the actual device count, so the logger must be built after
    nworkers is known)."""
    cfg = _cfg(logdir=str(tmp_path), tensorboard=True, num_batches_per_epoch=10)
    t = Trainer(cfg, synthetic_data=True, profile_backward=False)
    t.fit(1)
    t.close()
    rundir = os.path.join(str(tmp_path), cfg.tag())
    assert "-n8-" in cfg.tag()
    assert os.path.exists(os.path.join(rundir, "train.log"))
    assert os.path.exists(os.path.join(rundir, "events.jsonl"))


def test_evaluate_model_average(tmp_path, capsys):
    """--average-dirs evaluates the elementwise mean of several runs' weights
    (reference model_average, evaluate.py:10-18, disabled there at :36).
    Averaging two DIFFERENT runs must produce a valid eval, and averaging a
    run with itself must reproduce that run's own eval exactly."""
    runs = []
    for seed in (3, 4):
        cfg = _cfg(checkpoint_dir=str(tmp_path / f"s{seed}"), seed=seed,
                   num_batches_per_epoch=8)
        t = Trainer(cfg, synthetic_data=True, profile_backward=False)
        t.fit(1)
        t.checkpointer.wait()
        runs.append(t.checkpointer._dir)
        t.close()

    from mgwfbp_tpu.evaluate import evaluate, main as eval_main, \
        model_average_evaluate

    solo = evaluate("mnistnet", runs[0], synthetic=True, batch_size=8)
    self_avg = model_average_evaluate(
        "mnistnet", [runs[0], runs[0]], synthetic=True, batch_size=8,
    )
    assert self_avg["top1"] == pytest.approx(solo["top1"], abs=1e-6)
    assert self_avg["averaged_over"] == 2

    rc = eval_main([
        "--dnn", "mnistnet", "--average-dirs", runs[0], runs[1],
        "--batch-size", "8", "--synthetic",
    ])
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["averaged_over"] == 2 and 0.0 <= out["top1"] <= 1.0


def test_update_nworker_repoints_checkpoint_dir(tmp_path):
    """After a resize the run tag changes; checkpoints must land under the
    NEW tag so a relaunch at the new size resumes them."""
    cfg = _cfg(checkpoint_dir=str(tmp_path), num_batches_per_epoch=2)
    t = Trainer(cfg, synthetic_data=True, profile_backward=False)
    assert "-n8-" in t.checkpointer._dir
    t.train_epoch(0)
    t.update_nworker(4)
    assert "-n4-" in t.checkpointer._dir
    t.save(0)
    t.checkpointer.wait()
    t.close()
    t2 = Trainer(cfg, synthetic_data=True, profile_backward=False,
                 mesh=__import__("mgwfbp_tpu.parallel.mesh", fromlist=["x"])
                 .make_mesh(
                     __import__("mgwfbp_tpu.parallel.mesh", fromlist=["x"])
                     .MeshSpec(data=4), devices=jax.devices()[:4]))
    assert t2.start_epoch == 1  # resumed from the -n4- checkpoint
    t2.close()


def test_model_average_rejects_mismatched_epochs(tmp_path):
    from mgwfbp_tpu.evaluate import model_average_evaluate

    dirs = []
    for seed, epochs in ((5, 1), (6, 2)):
        cfg = _cfg(checkpoint_dir=str(tmp_path / f"e{seed}"), seed=seed,
                   num_batches_per_epoch=2)
        t = Trainer(cfg, synthetic_data=True, profile_backward=False)
        t.fit(epochs)
        t.checkpointer.wait()
        dirs.append(t.checkpointer._dir)
        t.close()
    with pytest.raises(ValueError, match="different epochs"):
        model_average_evaluate("mnistnet", dirs, synthetic=True, batch_size=8)


def test_preset_optimizer_constants_match_reference():
    """Per-dataset SGD constants (reference dl_trainer.py:216-229): imagenet
    momentum 0.875 / wd 2*3.0517578125e-05, ptb momentum 0 / wd 0, everything
    else momentum 0.9 / wd 1e-4 (the an4 wd-zeroing there is commented out)."""
    from mgwfbp_tpu.config import PRESETS

    imagenet_models = [
        n for n, p in PRESETS.items() if p.get("dataset") == "imagenet"
    ]
    assert len(imagenet_models) >= 9
    for name in imagenet_models:
        cfg = make_config(name)
        assert cfg.momentum == 0.875, name
        assert cfg.weight_decay == pytest.approx(2 * 3.0517578125e-05), name
    lstm = make_config("lstm")
    assert lstm.momentum == 0.0 and lstm.weight_decay == 0.0
    an4 = make_config("lstman4")
    assert an4.momentum == 0.9 and an4.weight_decay == pytest.approx(1e-4)
    for name in ("resnet20", "vgg16", "mnistnet", "lenet"):
        cfg = make_config(name)
        assert cfg.momentum == 0.9, name
        assert cfg.weight_decay == pytest.approx(1e-4), name


def test_auto_density():
    """--density 0 = auto: the cost-model chooser picks a density or
    concludes dense wins and disables compression. The chooser's decision
    logic is covered in test_costmodel; this test covers the TRAINER wiring
    only — whatever was chosen, the reducer builds and training runs."""
    cfg = _cfg(compressor="topk", density=0.0,
               comm_profile="profiles/cpu8_mesh.json", num_batches_per_epoch=2)
    t = Trainer(cfg, synthetic_data=True, profile_backward=False)
    # mnistnet on the calibrated cpu8 link: whatever the chooser decided,
    # the reducer must exist and training must run
    assert t.reducer is not None
    comp = t.reducer.compressor
    if comp is not None:
        assert 0.0 < comp.density < 1.0
    m = t.train_epoch(0)
    assert np.isfinite(m["loss"])


def test_trainer_multislice_dcn():
    """--dcn-slices 2 on 8 devices: a (dcn=2, data=4) mesh, two-level cost
    model, mgwfbp schedule, and (with --comm-op hier) the explicit
    hierarchical lowering. Same seed + same global batch as the flat 8-way
    mesh must give the same loss."""
    # lenet: dropout-free, so per-device rng folding (which legitimately
    # differs between mesh layouts) cannot move the loss
    flat = _cfg("lenet", num_batches_per_epoch=3, batch_size=8)
    t_flat = Trainer(flat, synthetic_data=True, profile_backward=False)
    m_flat = t_flat.train_epoch(0)

    for comm_op in ("all_reduce", "hier"):
        cfg = _cfg("lenet", num_batches_per_epoch=3, batch_size=8,
                   dcn_slices=2, comm_op=comm_op)
        t = Trainer(cfg, synthetic_data=True, profile_backward=False)
        assert t.dcn_size == 2 and t.ici_size == 4 and t.data_size == 8
        assert t.config.nworkers == 8
        assert t.reducer is not None
        # the two-level ICI+DCN model must drive the solver on a
        # multi-slice mesh
        from mgwfbp_tpu.parallel.costmodel import TwoLevelAlphaBeta

        assert isinstance(t.cost_model, TwoLevelAlphaBeta)
        assert t.cost_model.ici_size == 4 and t.cost_model.dcn_size == 2
        assert t.reducer.schedule.num_groups >= 1
        assert t.reducer.comm_op == comm_op
        m = t.train_epoch(0)
        assert m["loss"] == pytest.approx(m_flat["loss"], abs=1e-5), comm_op


def test_trainer_hier_requires_multislice():
    cfg = _cfg(comm_op="hier")
    with pytest.raises(ValueError, match="dcn-slices"):
        Trainer(cfg, synthetic_data=True, profile_backward=False)


def test_fused_wer_matches_second_pass_decode(monkeypatch):
    """VERDICT r3 #9 pin: the single-pass WER (decode inputs folded out of
    the loss forward) must equal the old two-pass re-forward decode on the
    same model and val set."""
    from mgwfbp_tpu import models as zoo
    from mgwfbp_tpu.models import ModelMeta
    from mgwfbp_tpu.models.deepspeech import DeepSpeech

    def tiny_ds(nc):
        nc = nc or 29
        return (
            DeepSpeech(num_classes=nc, hidden_size=16, num_layers=1),
            ModelMeta(name="lstman4", dataset="an4", num_classes=nc,
                      input_shape=(201, 161), task="ctc"),
        )

    monkeypatch.setitem(zoo._REGISTRY, "lstman4", tiny_ds)
    cfg = _cfg("lstman4", batch_size=1, max_epochs=1)
    t = Trainer(cfg, synthetic_data=True, profile_backward=False)
    ev = t.evaluate()  # fused path (single process)
    two_pass = t._evaluate_wer()  # the old re-forward decode
    assert ev["wer"] == pytest.approx(two_pass["wer"], abs=1e-9)


# --------------------------------------------------------------------------
# README.md against the programs: every flag and variable, both ways
# --------------------------------------------------------------------------

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# flags the README quotes from programs that are not this repo's
_FOREIGN_FLAGS = {
    "--xla_force_host_platform_device_count",  # XLA_FLAGS
    "--oversubscribe",  # the reference's mpirun
}


def _read(*parts):
    with open(os.path.join(_REPO, *parts)) as f:
        return f.read()


def _sources(*roots):
    """The text of every .py file under the named directories and files."""
    out = []
    for root in roots:
        path = os.path.join(_REPO, root)
        files = (
            sorted(glob.glob(os.path.join(path, "**", "*.py"), recursive=True))
            if os.path.isdir(path) else [path]
        )
        out.extend(_read(f) for f in files)
    return "\n".join(out)


def _declared_flags(src):
    out = set()
    for m in re.finditer(r'add_argument\(\s*((?:"-{1,2}[\w-]+",?\s*)+)', src):
        out.update(re.findall(r'"(--[\w-]+)"', m.group(1)))
    return out


def _readme_flags(readme):
    return set(re.findall(r"(?<![\w-])--[a-z][a-z0-9_-]*", readme))


def test_readme_names_every_flag_and_variable():
    """A flag of the three launchers, or a variable the package reads as
    a quoted name, that README.md does not name is undocumented."""
    readme = _read("README.md")
    flags = _declared_flags(_sources(
        "mgwfbp_tpu/train_cli.py", "mgwfbp_tpu/runtime/supervise.py",
        "mgwfbp_tpu/calibrate.py",
    ))
    assert len(flags) > 60  # the extraction itself still finds them
    assert sorted(flags - _readme_flags(readme)) == []
    # a name built at run time ends in "_": any README name with that
    # prefix documents it
    names = set(re.findall(
        r"""["'](MGWFBP_[A-Z0-9_]+)""", _sources("mgwfbp_tpu")
    ))
    assert len(names) > 40
    assert sorted(n for n in names if n not in readme) == []


def test_readme_names_nothing_the_programs_do_not_read():
    """A flag or MGWFBP_* name in README.md that no program of the repo
    declares or mentions was removed, renamed or never existed."""
    readme = _read("README.md")
    src = _sources(
        "mgwfbp_tpu", "tools", "benchmarks", "chip_smoke.py",
        "__graft_entry__.py",
    )
    known = _declared_flags(src) | _FOREIGN_FLAGS
    assert sorted(_readme_flags(readme) - known) == []
    readme_names = set(re.findall(r"MGWFBP_[A-Z0-9_]+", readme))
    assert sorted(n for n in readme_names if n not in src) == []


@pytest.mark.parametrize("module,argv", [
    ("mgwfbp_tpu.train_cli", ["--dnn", "lenet", "--serve" "-shadow"]),
    ("mgwfbp_tpu.runtime.supervise",
     ["--processes", "1", "--serve" "-replicas", "1"]),
])
def test_retired_flags_are_unknown_arguments(module, argv, capsys):
    """The serving plane's flags left with it (PR 28): argparse refuses
    them by name instead of a launcher accepting and ignoring them."""
    import importlib

    parser = importlib.import_module(module).build_parser()
    with pytest.raises(SystemExit) as exc:
        parser.parse_args(argv)
    assert exc.value.code == 2
    assert "unrecognized arguments" in capsys.readouterr().err
