"""Optimizers: SGD with momentum and decay/no-decay parameter groups.

Parity target: reference dl_trainer.py:216-248 — per-dataset momentum /
weight-decay constants and the bn/bias exclusion (:231-241: params with
ndim == 1, i.e. batch-norm scales/offsets and biases, get weight_decay=0).
Expressed as an optax chain so it composes with the MG-WFBP merged
all-reduce (which runs on raw grads BEFORE this transform — reductions are
about communication, the optimizer is local math).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Union

import jax
import jax.numpy as jnp
import optax

from mgwfbp_tpu.optim import schedules
from mgwfbp_tpu.optim.schedules import EpochSchedule, as_step_fn, resolve

ScalarOrSchedule = Union[float, Callable[[jnp.ndarray], jnp.ndarray]]


@dataclasses.dataclass(frozen=True)
class OptimSpec:
    """Declarative description of an ELEMENTWISE optimizer chain.

    The optax transforms this repo composes (`sgd` below, optax.adam/adamw)
    are opaque closures: nothing can re-run their math on a flattened,
    1/world shard of a merge-group bucket, which is exactly what the
    rs_opt_ag lowering needs (`parallel.allreduce.ShardedOptimStep`). The
    spec is the transparent twin — `make_tx()` builds the optax chain for
    the replicated path, and the sharded path interprets the SAME fields on
    flat buffers, so the two paths cannot drift apart on hyperparameters.

    Field semantics mirror the optax transforms bit for bit:
      * kind 'sgd': optional coupled weight decay (added to the grad BEFORE
        momentum, torch semantics), optax.trace momentum, lr scaling.
      * kind 'adam': optax.scale_by_adam (b1/b2/eps, bias correction by
        count), optional DECOUPLED decay (added to the update AFTER the
        preconditioner — optax.adamw), lr scaling.
      * mask_ndim_gt1: the bn/bias decay exclusion (`decay_mask`).
      * norm_clip: optax.clip_by_global_norm threshold, ALREADY scaled by
        sqrt(1/P) when distributed (`clip_by_global_norm` below does the
        scaling; store the scaled value here).
      * lr: float or optax-style `step -> lr` schedule (`as_step_fn`).
    """

    lr: ScalarOrSchedule
    kind: str = "sgd"  # sgd | adam
    momentum: float = 0.0
    nesterov: bool = False
    weight_decay: float = 0.0
    decoupled_wd: bool = False  # adamw-style (after the preconditioner)
    mask_ndim_gt1: bool = True
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    norm_clip: Optional[float] = None

    def __post_init__(self):
        if self.kind not in ("sgd", "adam"):
            raise ValueError(f"unknown OptimSpec.kind {self.kind!r}")
        if self.kind == "sgd" and self.decoupled_wd:
            raise ValueError("decoupled weight decay requires kind='adam'")

    def learning_rate(self, count):
        """lr at optimizer step `count` (traced or concrete)."""
        return self.lr(count) if callable(self.lr) else self.lr

    def make_tx(self) -> optax.GradientTransformation:
        """The equivalent replicated optax chain (the all_reduce path's
        optimizer; also the checkpoint interchange structure both paths
        save/restore through)."""
        mask = decay_mask if self.mask_ndim_gt1 else None
        if self.kind == "sgd":
            tx = sgd(
                self.lr,
                momentum=self.momentum,
                weight_decay=self.weight_decay,
                nesterov=self.nesterov,
                mask_ndim_gt1=self.mask_ndim_gt1,
            )
        elif self.decoupled_wd or self.weight_decay:
            tx = optax.adamw(
                self.lr, b1=self.b1, b2=self.b2, eps=self.eps,
                weight_decay=self.weight_decay, mask=mask,
            )
        else:
            tx = optax.adam(self.lr, b1=self.b1, b2=self.b2, eps=self.eps)
        if self.norm_clip is not None:
            tx = optax.chain(optax.clip_by_global_norm(self.norm_clip), tx)
        return tx

    @property
    def num_slots(self) -> int:
        """Params-shaped state buffers this chain carries (momentum trace;
        Adam first/second moments) — the leaves the sharded path packs."""
        if self.kind == "adam":
            return 2
        return 1 if self.momentum else 0


def decay_mask(params: Any) -> Any:
    """True for params that SHOULD get weight decay: ndim > 1 (conv/dense
    kernels, embeddings). 1-d params (bn scale/offset, biases) are excluded
    (reference dl_trainer.py:231-241)."""
    return jax.tree_util.tree_map(lambda p: jnp.ndim(p) > 1, params)


def sgd(
    learning_rate: ScalarOrSchedule,
    momentum: float = 0.9,
    weight_decay: float = 0.0,
    nesterov: bool = False,
    mask_ndim_gt1: bool = True,
) -> optax.GradientTransformation:
    """SGD + momentum + masked (coupled) weight decay, matching
    torch.optim.SGD semantics: decay is added to the gradient before the
    momentum buffer update."""
    parts = []
    if weight_decay:
        wd = optax.add_decayed_weights(weight_decay)
        parts.append(optax.masked(wd, decay_mask) if mask_ndim_gt1 else wd)
    if momentum:
        parts.append(optax.trace(decay=momentum, nesterov=nesterov))
    parts.append(
        optax.scale_by_learning_rate(learning_rate)  # handles schedules too
    )
    return optax.chain(*parts)


def scaled_clip_threshold(max_norm: float, world_size: int = 1) -> float:
    """The distributed clip threshold: max_norm scaled by sqrt(1/P)
    (reference distributed_optimizer.py:380-387 — worker-averaged gradients
    have ~sqrt(1/P) the noise norm, so the threshold tightens to match).
    The single source of the scaling rule for both `clip_by_global_norm`
    and `make_optimizer`/OptimSpec."""
    if world_size > 1:
        return float(jnp.sqrt(1.0 / world_size)) * max_norm
    return float(max_norm)


def clip_by_global_norm(max_norm: float, world_size: int = 1):
    """Gradient clipping transform (reference clip_grad_norm_ for the RNN
    workloads, dist_trainer.py:56-60,89-94: lstm 0.25, lstman4 400).

    When distributed, the threshold is scaled by sqrt(1/P) — the reference's
    distributed clip rule (`scaled_clip_threshold`). Known delta
    (PARITY.md): the reference applies that threshold to each MERGED
    GROUP's norm separately (a per-bucket approximation of the global clip
    its single-process path uses); here the principled global-norm clip
    keeps single/multi-worker semantics identical.
    """
    return optax.clip_by_global_norm(
        scaled_clip_threshold(max_norm, world_size)
    )


def make_optimizer(
    base_lr: float,
    *,
    momentum: float = 0.9,
    weight_decay: float = 1e-4,  # reference default (dl_trainer.py:216)
    lr_schedule: str = "auto",
    dataset: str = "cifar10",
    max_epochs: int = 141,
    warmup_epochs: int = 5,
    num_batches_per_epoch: int = 1,
    norm_clip: Optional[float] = None,
    step_offset: int = 0,
    epoch_offset: float = 0.0,
    world_size: int = 1,
    return_spec: bool = False,
    optimizer: str = "sgd",
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
):
    """Build the full optimizer chain + its epoch schedule (for logging).

    optimizer: 'sgd' (momentum + coupled masked decay, the reference's) or
    'adamw' (optax.adamw with b1/b2/eps and `weight_decay` DECOUPLED, on
    parameters of more than one dimension only; `momentum` is unused).

    step_offset/epoch_offset anchor the step->epoch conversion so an elastic
    resize continues the schedule from its current position (as_step_fn).
    world_size scales the norm-clip threshold by sqrt(1/P) (reference
    distributed clip rule, distributed_optimizer.py:380-387).

    return_spec=True appends the `OptimSpec` describing the same chain —
    the transparent form `ShardedOptimStep` re-runs on flat bucket shards
    (rs_opt_ag). Built from the same locals as the optax chain so the two
    representations cannot drift."""
    epoch_schedule = resolve(
        lr_schedule, base_lr, dataset=dataset, max_epochs=max_epochs,
        warmup_epochs=warmup_epochs,
    )
    step_fn = as_step_fn(
        epoch_schedule, num_batches_per_epoch,
        step_offset=step_offset, epoch_offset=epoch_offset,
    )
    if optimizer == "sgd":
        tx = sgd(step_fn, momentum=momentum, weight_decay=weight_decay)
    elif optimizer == "adamw":
        tx = optax.adamw(
            step_fn, b1=b1, b2=b2, eps=eps, weight_decay=weight_decay,
            mask=decay_mask,
        )
    else:
        raise ValueError(f"unknown optimizer {optimizer!r} (sgd | adamw)")
    scaled_clip = None
    if norm_clip is not None:
        scaled_clip = scaled_clip_threshold(norm_clip, world_size)
        tx = optax.chain(optax.clip_by_global_norm(scaled_clip), tx)
    if not return_spec:
        return tx, epoch_schedule
    if optimizer == "sgd":
        spec = OptimSpec(
            lr=step_fn,
            kind="sgd",
            momentum=momentum,
            weight_decay=weight_decay,
            norm_clip=scaled_clip,
        )
    else:
        spec = OptimSpec(
            lr=step_fn, kind="adam", b1=b1, b2=b2, eps=eps,
            weight_decay=weight_decay, decoupled_wd=True,
            norm_clip=scaled_clip,
        )
    return tx, epoch_schedule, spec


__all__ = [
    "OptimSpec",
    "decay_mask",
    "sgd",
    "make_optimizer",
    "clip_by_global_norm",
    "scaled_clip_threshold",
    "schedules",
    "resolve",
    "as_step_fn",
]
