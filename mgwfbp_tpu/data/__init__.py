"""Data subsystem: dataset dispatch + sharded loading.

`data_prepare` is the analogue of the reference's per-dataset prepare methods
and dispatcher (reference dl_trainer.py:317-539): it resolves a dataset name
to sharded train/val loaders. Real files under `data_dir` are used when
present; otherwise a deterministic synthetic twin with identical
shapes/cardinalities is served (no-egress container — see data/datasets.py).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from mgwfbp_tpu.data.datasets import (
    CIFAR_MEAN,
    CIFAR_STD,
    IMAGENET_MEAN,
    IMAGENET_STD,
    MNIST_MEAN,
    MNIST_STD,
    load_cifar10,
    load_imagenet_hdf5,
    load_mnist,
    synthetic_images,
)
from mgwfbp_tpu.data.loader import (
    ArrayDataset,
    PrefetchLoader,
    ShardedLoader,
    infinite_batches,
    normalize_images,
)
from mgwfbp_tpu.data.sharding import ShardInfo
from mgwfbp_tpu.telemetry.phases import setup_span


def _wrap_prefetch(train_loader):
    """Background prefetch for the TRAIN path (reference DataLoader
    num_workers + pin_memory, dl_trainer.py:353). MGWFBP_DATA_WORKERS
    tunes the pool (0 disables and returns the bare loader);
    MGWFBP_DATA_DEVICE_PUT=1 additionally commits batches to device from
    the worker threads (pin_memory analogue) — OPT-IN: host-side
    assembly-ahead alone already overlaps the load with compute, the
    actual transfer is async under jax dispatch, and the knob's effect on
    the attached chip is not measured yet (ROADMAP S6)."""
    import os

    workers = int(os.environ.get("MGWFBP_DATA_WORKERS", "2"))
    if workers <= 0:
        return train_loader
    return PrefetchLoader(
        train_loader,
        workers=workers,
        device_put=os.environ.get("MGWFBP_DATA_DEVICE_PUT", "0") == "1",
    )

# Synthetic sizes: big enough for stable throughput measurement and smoke
# convergence, small enough to build instantly. MGWFBP_SYNTH_TRAIN_N /
# MGWFBP_SYNTH_VAL_N override them (full-cardinality convergence runs), and
# MGWFBP_SYNTH_MODE=hard swaps the trivial twin for the held-out
# generalization generator (datasets.synthetic_images_hard) — the honest
# convergence substitute in this no-egress container.
_SYNTH_TRAIN = {"mnist": 4096, "cifar10": 4096, "imagenet": 512, "ptb": 512,
                "tokens": 64}  # tokens: sequences
_SYNTH_VAL = {"mnist": 512, "cifar10": 512, "imagenet": 128, "ptb": 64,
              "tokens": 8}


def _synth_size(split: str, name: str) -> int:
    import os

    table = _SYNTH_TRAIN if split == "train" else _SYNTH_VAL
    env = os.environ.get(f"MGWFBP_SYNTH_{split.upper()}_N")
    return int(env) if env else table[name]


@dataclasses.dataclass
class DataBundle:
    train: ShardedLoader
    val: ShardedLoader
    num_classes: int
    synthetic: bool
    # batches per epoch over the GLOBAL batch (reference dl_trainer.py:539
    # divides by batch_size * nworkers)
    num_batches_per_epoch: int


def data_prepare(
    dataset: str,
    data_dir: str = "./data",
    batch_size: int = 32,
    shard: ShardInfo = ShardInfo(),
    seed: int = 0,
    image_hw: Optional[tuple[int, int]] = None,
    synthetic: Optional[bool] = None,
    augment: bool = True,
    num_steps: Optional[int] = None,
    vocab_size: Optional[int] = None,
) -> DataBundle:
    """Build sharded train/val loaders for a dataset name.

    batch_size is PER PROCESS (weak scaling, reference dl_trainer.py:153-156).
    `synthetic=True` forces the synthetic twin; None auto-detects files.
    `image_hw` overrides the image size (inceptions need 299x299).
    `augment=False` disables training-time augmentation (benchmarking).
    `num_steps` overrides the LM window length (default: the reference's
    35-token BPTT window; seq-parallel transformers need a length divisible
    by the seq mesh extent); for `tokens` it is the sequence length.
    `vocab_size` states the `tokens` vocabulary (data/tokens.py).

    The data sets' construction (files read, or the synthetic twin drawn)
    is the `dataset` span of the set-up record (telemetry/phases.py), apart
    from the loaders and the prefetch pool's start.
    """
    name = dataset.lower()
    if name in ("mnist", "cifar10", "imagenet"):
        hw_default = {"mnist": (28, 28), "cifar10": (32, 32), "imagenet": (224, 224)}
        h, w = image_hw or hw_default[name]
        c = 1 if name == "mnist" else 3
        mean, std = {
            "mnist": (MNIST_MEAN, MNIST_STD),
            "cifar10": (CIFAR_MEAN, CIFAR_STD),
            "imagenet": (IMAGENET_MEAN, IMAGENET_STD),
        }[name]
        train = val = None
        if not synthetic:
            loader_fn = {
                "mnist": load_mnist,
                "cifar10": load_cifar10,
                "imagenet": load_imagenet_hdf5,
            }[name]
            with setup_span("dataset"):
                train = loader_fn(data_dir, "train")
                val = loader_fn(
                    data_dir, "val" if name == "imagenet" else "test")
        is_synth = train is None or val is None
        if is_synth:
            if synthetic is False:
                raise FileNotFoundError(
                    f"real {name} data not found under {data_dir!r}"
                )
            import os as _os

            nc = 1000 if name == "imagenet" else 10
            gen = synthetic_images
            if _os.environ.get("MGWFBP_SYNTH_MODE", "easy") == "hard":
                from mgwfbp_tpu.data.datasets import synthetic_images_hard

                gen = synthetic_images_hard
            with setup_span("dataset"):
                train = gen(_synth_size("train", name), (h, w, c), nc, seed)
                val = gen(_synth_size("val", name), (h, w, c), nc, seed + 1)
        else:
            real_hw = tuple(train.data.shape[1:3])
            if image_hw is not None and real_hw != tuple(image_hw):
                raise ValueError(
                    f"requested image_hw {image_hw} but real {name} files "
                    f"under {data_dir!r} store {real_hw} images; rebuild the "
                    "dataset at the requested size (scripts/create_hdf5)"
                )
        normalize = normalize_images(mean, std)
        # train-split-only augmentation (reference dl_trainer.py:331-336,
        # 381-385: RandomCrop+flip for CIFAR, RandomResizedCrop+flip for
        # ImageNet; eval uses only normalize)
        train_tf = normalize
        # fused crop + flip + normalize: one pass over the uint8 batch via
        # the native C++ kernels (NumPy fallbacks are bit-identical)
        if augment and name == "cifar10":
            from mgwfbp_tpu.data.augment import FusedCropFlipNormalize

            train_tf = FusedCropFlipNormalize(mean, std, pad=4)
        elif augment and name == "imagenet":
            from mgwfbp_tpu.data.augment import FusedResizedCropFlipNormalize

            train_tf = FusedResizedCropFlipNormalize(mean, std)
        train_loader = ShardedLoader(
            train, batch_size, shard, shuffle=True, seed=seed,
            transform=train_tf,
        )
        val_loader = ShardedLoader(
            val, batch_size, shard, shuffle=False, seed=seed,
            drop_last=False, transform=normalize,
        )
        return DataBundle(
            train=_wrap_prefetch(train_loader),
            val=val_loader,
            num_classes=train.num_classes,
            synthetic=is_synth,
            # per-rank loader length already divides by nranks, so this is
            # dataset_size / (batch_size * nranks) — the reference's formula
            num_batches_per_epoch=len(train_loader),
        )
    if name == "ptb":
        from mgwfbp_tpu.data.ptb import (
            NUM_STEPS,
            VOCAB_SIZE,
            carry_layout,
            load_ptb_stream,
            synthetic_ptb_stream,
        )

        nsteps = num_steps or NUM_STEPS
        with setup_span("dataset"):
            streams = None
            if not synthetic:
                streams = (load_ptb_stream(data_dir, "train"),
                           load_ptb_stream(data_dir, "valid"))
                if streams[0] is None or streams[1] is None:
                    streams = None
            is_synth = streams is None
            if is_synth:
                if synthetic is False:
                    raise FileNotFoundError(
                        f"PTB files not found under {data_dir!r}")
                vocab_size = VOCAB_SIZE
                train_stream = synthetic_ptb_stream(
                    _SYNTH_TRAIN["ptb"], seed=seed)
                val_stream = synthetic_ptb_stream(
                    _SYNTH_VAL["ptb"], seed=seed + 1)
            else:
                (train_stream, vocab_size), (val_stream, _) = streams
            # Stateful-BPTT layout: contiguous sub-streams per batch element
            # and per rank (see ptb.carry_layout); NO shuffling, NO
            # sample-sharding — the carry must see textually consecutive
            # windows each step.
            train = carry_layout(
                train_stream, nsteps, batch_size, shard.rank, shard.nranks,
                vocab_size,
            )
            val = carry_layout(
                val_stream, nsteps, batch_size, shard.rank, shard.nranks,
                vocab_size,
            )
        train_loader = ShardedLoader(train, batch_size, shuffle=False, seed=seed)
        val_loader = ShardedLoader(val, batch_size, shuffle=False, seed=seed)
        return DataBundle(
            train=_wrap_prefetch(train_loader),
            val=val_loader,
            num_classes=vocab_size,
            synthetic=is_synth,
            num_batches_per_epoch=len(train_loader),
        )
    if name == "tokens":
        from mgwfbp_tpu.data import tokens as tok
        from mgwfbp_tpu.models import DATASET_CLASSES

        seq_len = num_steps or tok.SEQ_LEN
        with setup_span("dataset"):
            streams = None
            if not synthetic:
                streams = (tok.load_token_stream(data_dir, "train"),
                           tok.load_token_stream(data_dir, "valid"))
                if streams[0] is None or streams[1] is None:
                    streams = None
            is_synth = streams is None
            if is_synth:
                if synthetic is False:
                    raise FileNotFoundError(
                        f"tokens/train.npy and tokens/valid.npy not found "
                        f"under {data_dir!r}")
                vocab = vocab_size or DATASET_CLASSES["tokens"]
                streams = tuple(
                    tok.synthetic_token_stream(n, seq_len, vocab, seed + i)
                    for i, n in enumerate((
                        _synth_size("train", "tokens"),
                        _synth_size("val", "tokens"))))
            else:
                vocab = vocab_size or int(max(s.max() for s in streams)) + 1
            train, val = (
                tok.sequence_dataset(s, seq_len, vocab) for s in streams)
        train_loader = ShardedLoader(
            train, batch_size, shard, shuffle=True, seed=seed)
        val_loader = ShardedLoader(
            val, batch_size, shard, shuffle=False, seed=seed, drop_last=False)
        return DataBundle(
            train=_wrap_prefetch(train_loader),
            val=val_loader,
            num_classes=vocab,
            synthetic=is_synth,
            num_batches_per_epoch=len(train_loader),
        )
    if name == "an4":
        from mgwfbp_tpu.data.audio import an4_prepare

        with setup_span("dataset"):  # the loaders too: made in one call
            bundle = an4_prepare(data_dir, batch_size, shard, seed, synthetic)
        bundle.train = _wrap_prefetch(bundle.train)
        return bundle
    raise ValueError(f"unknown dataset {dataset!r}")


__all__ = [
    "ArrayDataset",
    "DataBundle",
    "ShardInfo",
    "ShardedLoader",
    "data_prepare",
    "infinite_batches",
]
