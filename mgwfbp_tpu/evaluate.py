"""Offline evaluation over saved checkpoints.

Parity target (SURVEY.md §3.4): reference evaluate.py (:20-57 — rebuild the
trainer from hyperparameters encoded in the checkpoint dir name, load each
epoch's checkpoint, run test(): top1/top5 for CNNs, perplexity for PTB, WER
for AN4) and scripts/eval.sh. Here the checkpoint directory is the
config-tagged dir the Trainer writes; model/dataset come from CLI flags
(explicit beats dir-name parsing).

Usage:
  python -m mgwfbp_tpu.evaluate --dnn resnet20 --checkpoint-dir ckpts/... \
      [--epoch N] [--synthetic]
"""

from __future__ import annotations

import argparse
import json
from typing import Optional

from mgwfbp_tpu.config import make_config


def _install_and_eval(trainer, state) -> dict:
    """Re-replicate a restored train state over the trainer's mesh (the
    reference's post-load broadcast_parameters, dist_trainer.py:66) and run
    the eval loop. Single seam shared by the per-epoch and model-average
    paths."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    trainer.state = jax.device_put(
        state, NamedSharding(trainer.mesh, PartitionSpec())
    )
    return trainer.evaluate()


def _restore_or_raise(
    ckpt, root: str, template, epoch: Optional[int], carry_template=None
):
    if epoch is None:
        # prefer the newest epoch BOUNDARY: with --ckpt-every-steps the
        # raw latest snapshot may be mid-epoch, and evaluation semantics
        # are per-epoch; fall back to the latest of any kind for dirs
        # holding only step checkpoints
        epoch = ckpt.latest_epoch()
    snap = ckpt.restore(template, epoch=epoch, carry_template=carry_template)
    if snap is None:
        raise FileNotFoundError(
            f"no checkpoint under {root!r}"
            + (f" at epoch {epoch}" if epoch is not None else "")
        )
    return snap


def _eval_snapshots(
    dnn: str,
    checkpoint_root: str,
    pick_epochs,
    synthetic: Optional[bool] = None,
    **config_overrides,
):
    """Shared driver: build ONE trainer, then restore + re-replicate +
    evaluate each epoch `pick_epochs(ckpt)` selects, yielding metrics
    incrementally (a failure at epoch k does not discard earlier results)."""
    from mgwfbp_tpu.checkpoint import Checkpointer
    from mgwfbp_tpu.train.trainer import Trainer

    cfg = make_config(dnn, checkpoint_dir=None, **config_overrides)
    trainer = Trainer(cfg, profile_backward=False, synthetic_data=synthetic)
    ckpt = Checkpointer(checkpoint_root)
    try:
        epochs = pick_epochs(ckpt)
        for e in epochs:
            snap = _restore_or_raise(
                ckpt, checkpoint_root, trainer.state, e,
                carry_template=trainer._carry_template(),
            )
            metrics = _install_and_eval(trainer, snap.state)
            metrics["epoch"] = snap.epoch
            yield metrics
    finally:
        ckpt.close()
        trainer.close()


def evaluate(
    dnn: str,
    checkpoint_root: str,
    epoch: Optional[int] = None,
    synthetic: Optional[bool] = None,
    **config_overrides,
) -> dict:
    """Evaluate one checkpoint (latest by default); returns metrics dict."""
    for metrics in _eval_snapshots(
        dnn, checkpoint_root, lambda ckpt: [epoch],
        synthetic=synthetic, **config_overrides,
    ):
        return metrics
    raise FileNotFoundError(f"no checkpoint under {checkpoint_root!r}")


def evaluate_all(
    dnn: str,
    checkpoint_root: str,
    synthetic: Optional[bool] = None,
    **config_overrides,
):
    """Yield metrics for EVERY saved epoch in a run dir, in order (the
    reference's scripts/eval.sh + evaluate.py loop over per-epoch
    checkpoints)."""

    def pick(ckpt):
        epochs = ckpt.all_epochs()
        if not epochs:
            raise FileNotFoundError(
                f"no checkpoints under {checkpoint_root!r}"
            )
        return epochs

    yield from _eval_snapshots(
        dnn, checkpoint_root, pick, synthetic=synthetic, **config_overrides
    )


def model_average_evaluate(
    dnn: str,
    checkpoint_roots: list[str],
    epoch: Optional[int] = None,
    synthetic: Optional[bool] = None,
    **config_overrides,
) -> dict:
    """Average model weights across several runs' checkpoints, then evaluate
    the averaged model (reference evaluate.py:10-18 `model_average` —
    elementwise state-dict mean over per-rank checkpoints, shipped there
    behind a disabled branch at :36; live here).

    Each root is one run's tagged checkpoint directory. All roots must hold
    a checkpoint at the SAME epoch — with epoch=None each root's latest is
    restored and a mismatch (runs of different lengths, or one root's epoch
    pruned by retention) raises instead of silently averaging weights from
    different training stages."""
    import jax
    import jax.numpy as jnp

    from mgwfbp_tpu.checkpoint import Checkpointer
    from mgwfbp_tpu.train.trainer import Trainer

    if not checkpoint_roots:
        raise ValueError("model_average_evaluate: no checkpoint dirs given")
    cfg = make_config(dnn, checkpoint_dir=None, **config_overrides)
    trainer = Trainer(cfg, profile_backward=False, synthetic_data=synthetic)
    try:
        snaps = []
        for root in checkpoint_roots:
            ckpt = Checkpointer(root)
            try:
                snaps.append(
                    _restore_or_raise(
                        ckpt, root, trainer.state, epoch,
                        carry_template=trainer._carry_template(),
                    )
                )
            finally:
                ckpt.close()
        epochs = sorted({s.epoch for s in snaps})
        if len(epochs) > 1:
            raise ValueError(
                "model_average_evaluate: checkpoint roots are at different "
                f"epochs {epochs}; pass --epoch to pick a common one"
            )
        n = float(len(snaps))

        def mean(*leaves):
            acc = leaves[0].astype(jnp.float32)
            for x in leaves[1:]:
                acc = acc + x.astype(jnp.float32)
            return (acc / n).astype(leaves[0].dtype)

        params = jax.tree_util.tree_map(
            mean, *[s.state.params for s in snaps]
        )
        batch_stats = jax.tree_util.tree_map(
            mean, *[s.state.batch_stats for s in snaps]
        )
        metrics = _install_and_eval(
            trainer,
            trainer.state.replace(params=params, batch_stats=batch_stats),
        )
        metrics["epoch"] = snaps[0].epoch
        metrics["averaged_over"] = len(snaps)
        return metrics
    finally:
        trainer.close()


def main(argv: Optional[list[str]] = None) -> int:
    p = argparse.ArgumentParser(prog="mgwfbp-evaluate")
    p.add_argument("--dnn", required=True)
    p.add_argument("--checkpoint-dir", dest="checkpoint_dir", default=None,
                   help="the run's tagged checkpoint directory (required "
                        "unless --average-dirs is used)")
    p.add_argument("--epoch", type=int, default=None,
                   help="epoch to evaluate (default: latest)")
    p.add_argument("--all-epochs", action="store_true",
                   help="evaluate every saved epoch (one JSON line each, "
                        "then a final {\"best\": ...} summary line); "
                        "mutually exclusive with --epoch")
    p.add_argument("--average-dirs", dest="average_dirs", nargs="+",
                   default=None,
                   help="average weights across these runs' checkpoints "
                        "before evaluating (reference model_average)")
    p.add_argument("--dataset", default=None)
    p.add_argument("--data-dir", dest="data_dir", default=None)
    p.add_argument("--batch-size", dest="batch_size", type=int, default=None)
    p.add_argument("--synthetic", action="store_true")
    args = p.parse_args(argv)
    from mgwfbp_tpu.utils.platform import (
        apply_platform_overrides,
        enable_compile_cache,
    )

    apply_platform_overrides()
    enable_compile_cache()
    overrides = {
        k: getattr(args, k)
        for k in ("dataset", "data_dir", "batch_size")
        if getattr(args, k) is not None
    }
    if args.all_epochs and args.epoch is not None:
        p.error("--all-epochs and --epoch are mutually exclusive")
    if args.average_dirs and args.all_epochs:
        p.error("--average-dirs and --all-epochs are mutually exclusive")
    if not args.average_dirs and not args.checkpoint_dir:
        p.error("--checkpoint-dir is required (or use --average-dirs)")
    if args.average_dirs:
        metrics = model_average_evaluate(
            args.dnn,
            args.average_dirs,
            epoch=args.epoch,
            synthetic=True if args.synthetic else None,
            **overrides,
        )
        print(json.dumps(metrics))
        return 0
    if args.all_epochs:
        # running best across epochs (reference evaluate.py:47-57: higher is
        # better for accuracy, lower for lstm perplexity / an4 WER)
        best = None
        best_epoch = None
        key = lower_better = None
        for metrics in evaluate_all(
            args.dnn,
            args.checkpoint_dir,
            synthetic=True if args.synthetic else None,
            **overrides,
        ):
            print(json.dumps(metrics))
            if key is None:
                # the metric key is a property of the MODEL TASK, fixed for
                # the whole run; deriving it per line would let one epoch
                # with a missing key (e.g. failed WER decode) relabel the
                # final best summary (ADVICE r3)
                if "wer" in metrics:
                    key, lower_better = "wer", True
                elif "perplexity" in metrics:
                    key, lower_better = "perplexity", True
                else:
                    key, lower_better = "top1", False
            v = metrics.get(key)
            if v is not None and (
                best is None or (v < best if lower_better else v > best)
            ):
                best, best_epoch = v, metrics.get("epoch")
        if best is not None:
            print(json.dumps(
                {"best": {key: best, "epoch": best_epoch}}
            ))
        return 0
    metrics = evaluate(
        args.dnn,
        args.checkpoint_dir,
        epoch=args.epoch,
        synthetic=True if args.synthetic else None,
        **overrides,
    )
    print(json.dumps(metrics))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
