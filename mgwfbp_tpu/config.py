"""Unified configuration.

The reference splits config across three tiers — compile-time globals
(settings.py), per-model env-var conf files (exp_configs/*.conf), and argparse
CLIs (dist_trainer.py:105-122) — per SURVEY.md §5. Here it is one dataclass
with per-model presets mirroring exp_configs, env-var overrides, and CLI
plumbing in train_cli.py.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional


@dataclasses.dataclass
class TrainConfig:
    # model/data (exp_configs/*.conf fields)
    dnn: str = "resnet20"
    dataset: str = "cifar10"
    data_dir: str = "./data"
    batch_size: int = 32  # per-worker batch (weak scaling, dl_trainer.py:153-156)
    lr: float = 0.1
    max_epochs: int = 141
    nsteps_update: int = 1  # gradient accumulation micro-steps (dist_trainer.py:77-88)
    augment: bool = True  # train-split augmentation (dl_trainer.py:331-336,381-385)

    # distributed
    nworkers: int = 1
    seq_parallel: int = 1  # sequence-parallel mesh extent (TPU extension)
    dcn_slices: int = 1  # multi-slice pod: outer data-parallel level whose
    # collectives cross the data-center network (two-level cost model;
    # --comm-op hier lowers the hierarchy explicitly)
    num_steps: Optional[int] = None  # LM window length override (default 35;
    # seq-parallel transformers need num_steps % seq_parallel == 0)
    # the part of a model this chip holds (models that can be held in part:
    # the mellum2, granite4h, laguna_xs2, phi4flash, qwen3next, xing4 and
    # nemotron3s families).
    # None = all
    layers_held: Optional[str] = None  # "N" the first N layers, or
    # "FIRST:COUNT" a stage anywhere (models.parse_layers_held)
    experts_held: Optional[str] = None  # "first:count" of each layer's experts
    tensor_share: Optional[str] = None  # "index:of": member INDEX of the OF
    # chips that share each layer's heads (models.parse_tensor_share)
    vocab_size: Optional[int] = None  # `tokens` dataset: ids 0..n-1, and so
    # the rows of the embedding and the head (the dataset's num_classes)

    # MG-WFBP scheduler
    policy: str = "auto"  # auto | mgwfbp | threshold | single | wfbp | none
    # `auto` simulates every candidate schedule (wfbp/single/mgwfbp/threshold
    # sweep/isolate-bigs) under the calibrated cost model and picks the argmin
    # — the adaptive policy IS the product, matching the reference's
    # ADAPTIVE_MERGE default (distributed_optimizer.py:267-270). `none` is the
    # XLA-fusion oracle (no explicit bucketing).
    threshold: int = 0  # elements, for policy='threshold' (batch_dist_mpi.sh grid)
    connection: str = "ici"  # cost-model link class (settings.py CONNECTION)
    comm_profile: Optional[str] = None  # path to calibrated alpha-beta json

    # closed-loop schedule autotuner (parallel/autotune.py): race verified
    # candidate schedules for warmup+k REAL steps each on the live jitted
    # step, refit the cost model from the measurements, commit the measured
    # argmin, persist it in the schedule cache
    autotune: bool = False
    autotune_steps: int = 3  # timed steps per candidate (k; +1 warmup/compile)
    autotune_candidates: int = 6  # frontier cap (incumbent always raced too)
    schedule_cache: Optional[str] = None  # cache dir; default
    # profiles/schedule_cache (keyed by model/world/comm_op/dtype)

    # gradient compression seam (reference compression.py, --compressor/--density)
    compressor: str = "none"  # none | topk
    density: float = 1.0  # kept fraction for sparsifying compressors
    comm_op: str = "all_reduce"  # all_reduce | rs_ag (DeAR-style RS+AG per
    # bucket) | hier (two-level ICI+DCN lowering; needs dcn_slices > 1) |
    # rs_opt_ag (ZeRO-1-style: optimizer update runs on the 1/world bucket
    # shard between reduce-scatter and a param all-gather; opt state stays
    # device-sharded between steps — needs a bucketing policy, no
    # compressor) | rs_fwd_ag (cross-step pipelining: rs_opt_ag whose
    # per-group all-gather is DEFERRED into the NEXT step's forward, so
    # comm hides behind forward compute too; params carried as 1/world
    # shards between steps — same constraints as rs_opt_ag; multi-host
    # capable since the shard-native checkpoint/interchange seam)

    # numerics
    dtype: str = "float32"  # param/compute dtype
    comm_dtype: Optional[str] = None  # wire dtype (settings.FP16 analog -> 'bfloat16')
    # reference defaults (dl_trainer.py:216-229): wd 1e-4 / momentum 0.9,
    # with per-dataset overrides carried by the PRESETS below
    weight_decay: float = 1e-4
    momentum: float = 0.9
    norm_clip: Optional[float] = None  # lstm 0.25 / lstman4 400 (dist_trainer.py:56-60)
    optimizer: str = "sgd"  # sgd | adamw (decoupled `weight_decay` on
    # matrices, b1 0.9, eps 1e-8; OptimSpec kind 'adam')
    adam_b2: float = 0.999

    # schedule
    lr_schedule: str = "auto"  # auto | step | cosine | ptb | anneal | vgg | const
    warmup_epochs: int = 5

    # io / bookkeeping
    logdir: str = "./logs"
    tensorboard: bool = False  # scalar event stream (reference's disabled
    # tensorboardX seam, dist_trainer.py:136-137 — live here as JSONL)
    telemetry: bool = False  # structured run observability (telemetry/):
    # step spans, per-group comm spans + overlap-efficiency snapshots,
    # autotune/resize/checkpoint/watchdog events — one schema-versioned
    # JSONL per run, rendered by tools/telemetry_report.py
    telemetry_dir: Optional[str] = None  # events dir; default <logdir>/<tag>
    metrics_port: Optional[int] = None  # live observability plane
    # (telemetry/serve.py): per-process HTTP server exposing /metrics
    # (Prometheus, live), /healthz (watchdog-wired liveness), /status
    # (run JSON). None = off; 0 = ephemeral port (logged); a multi-host
    # group serves port + process_index per process. Env:
    # MGWFBP_METRICS_PORT (the generic MGWFBP_<field> override)
    checkpoint_dir: Optional[str] = None
    checkpoint_every_epochs: int = 1
    ckpt_format: str = "sharded"  # sharded | replicated (ISSUE 13):
    # 'sharded' writes the shard-native format — each process saves only
    # its own shard rows plus a manifest, so sharded comm paths
    # (rs_opt_ag / rs_fwd_ag) never gather world-sized state to save,
    # and a restore re-shards onto any world size / merge schedule.
    # 'replicated' is the escape hatch: the legacy orbax payload in the
    # gathered interchange form, for interchange with pre-ISSUE-13
    # consumers. Both formats RESTORE transparently regardless of this
    # setting (it selects the save side only).
    ckpt_async: bool = True  # background shard-native payload writer
    # (ISSUE 16): mid-epoch --ckpt-every-steps saves snapshot the shard
    # rows at the step boundary and hand the np.save to a writer thread;
    # the commit (group barriers + manifest) lands on the step-loop
    # thread at the preemption agree-interval cadence. False = every
    # save blocks the step loop (pre-ISSUE-16 behavior). Epoch-boundary
    # and drain (wait=True) saves are always synchronous.
    # resilience layer (ISSUE 5)
    ckpt_every_steps: int = 0  # mid-epoch step-indexed checkpoints every N
    # optimizer steps (0 = epoch boundaries only); a SIGTERM/SIGINT drain
    # always writes one regardless, so preemption loses at most one step
    grad_guard: bool = True  # non-finite-gradient guard in the jitted step:
    # drop the update on NaN/inf grads (bad_step telemetry, zero host syncs)
    health_stats: bool = True  # in-jit training-health statistics (ISSUE
    # 12): per-merge-group grad L2 norms + update/param ratio riding the
    # EXISTING metrics psum (no extra collectives — jaxpr rule SCH010);
    # effective only with telemetry on (the stats exist to be streamed —
    # `health` records, the online detector in telemetry/health.py, the
    # flight recorder; without the stream the step compiles without them)
    bad_step_limit: int = 3  # consecutive bad steps before rolling back to
    # the last checkpoint (0 disables rollback; skipping still applies)
    pretrain: Optional[str] = None
    seed: int = 0
    num_batches_per_epoch: Optional[int] = None
    eval_every_epochs: int = 1

    def tag(self) -> str:
        from mgwfbp_tpu.utils.logging import run_tag

        return run_tag(dataclasses.asdict(self))


# Per-model presets — parity with exp_configs/*.conf (values cited in
# BASELINE.md "Headline training configs" and reference exp_configs/).
# Dataset-keyed SGD constants (the reference selects them by DATASET,
# dl_trainer.py:216-229); make_config fills them for any model trained
# on that dataset unless the preset or caller overrides.
_DATASET_SGD: dict[str, dict] = {
    "imagenet": dict(momentum=0.875, weight_decay=2 * 3.0517578125e-05),
    "ptb": dict(momentum=0.0, weight_decay=0.0),
}
# Mellum 2's recipe, which every decoder LM takes (nothing of it is
# published): AdamW as sparse language models are usually trained, 2 x 8,192
# tokens a device and step, cosine after the program's warm-up
_LM = dict(dataset="tokens", batch_size=2, num_steps=8192, lr=3e-4,
           max_epochs=40, lr_schedule="cosine", optimizer="adamw",
           adam_b2=0.95, weight_decay=0.1, norm_clip=1.0)
_LM_TINY = dict(_LM, num_steps=64, lr=3e-3, vocab_size=256)
PRESETS: dict[str, dict] = {
    "mnistnet": dict(dataset="mnist", batch_size=64, lr=0.01, max_epochs=10),
    "lenet": dict(dataset="mnist", batch_size=64, lr=0.01, max_epochs=10),
    "resnet20": dict(dataset="cifar10", batch_size=32, lr=0.1, max_epochs=141),
    "resnet56": dict(dataset="cifar10", batch_size=32, lr=0.1, max_epochs=141),
    "resnet110": dict(dataset="cifar10", batch_size=32, lr=0.1, max_epochs=141),
    "vgg16": dict(dataset="cifar10", batch_size=128, lr=0.1, max_epochs=141,
                  lr_schedule="vgg"),
    "resnet50": dict(dataset="imagenet", batch_size=128, lr=0.01, max_epochs=70),
    "resnet152": dict(dataset="imagenet", batch_size=32, lr=0.01, max_epochs=70),
    "densenet121": dict(dataset="imagenet", batch_size=64, lr=0.01, max_epochs=70),
    "densenet161": dict(dataset="imagenet", batch_size=32, lr=0.01, max_epochs=70),
    "densenet201": dict(dataset="imagenet", batch_size=64, lr=0.01, max_epochs=70),
    "googlenet": dict(dataset="imagenet", batch_size=64, lr=0.01, max_epochs=70),
    "inceptionv3": dict(dataset="imagenet", batch_size=64, lr=0.01, max_epochs=70),
    "inceptionv4": dict(dataset="imagenet", batch_size=64, lr=0.01, max_epochs=70),
    "alexnet": dict(dataset="imagenet", batch_size=128, lr=0.01, max_epochs=70),
    "lstm": dict(dataset="ptb", batch_size=20, lr=22.0, max_epochs=40,
                 lr_schedule="ptb", norm_clip=0.25),
    # TPU long-context extension (no reference analogue): windowed LM with
    # ring attention; 64-token windows divide by seq extents 2/4/8
    "transformer": dict(dataset="ptb", batch_size=16, lr=1.0, max_epochs=40,
                        lr_schedule="cosine", weight_decay=1e-5, momentum=0.9,
                        num_steps=64),
    # an4 keeps the defaults (the reference's an4 wd-zeroing is commented
    # out, dl_trainer.py:219-222: wd stays 1e-4, momentum 0.9)
    "lstman4": dict(dataset="an4", batch_size=4, lr=2e-4, max_epochs=100,
                    lr_schedule="anneal", norm_clip=400.0),
    # the decoder LMs share ONE recipe (`_LM`, `_LM_TINY`), none of it
    # published; a family says what differs: sparse (models/mellum.py), hybrid
    # state-space (models/granite.py), decoder-hybrid-decoder
    # (models/phi4flash.py), sparse with a dense first layer, a shared expert
    # and a head count by layer (models/laguna.py), and hybrid
    # linear-attention sparse (models/qwen3next.py), and latent attention
    # under four residual streams with bias-selected experts
    # (models/xing4.py), and layers of one mixer each, Mamba-2, LatentMoE or
    # attention (models/nemotronh.py). `batch_size`: two sequences of 8,192
    # tokens a device and step, or one
    "mellum2": dict(_LM),
    "mellum2_tiny": dict(_LM_TINY),
    "granite4h": dict(_LM, batch_size=1),
    "granite4h_tiny": dict(_LM_TINY),
    "phi4flash": dict(_LM, batch_size=1),
    "phi4flash_tiny": dict(_LM_TINY),
    "laguna_xs2": dict(_LM, batch_size=1),
    "laguna_xs2_tiny": dict(_LM_TINY),
    "qwen3next": dict(_LM),
    "qwen3next_tiny": dict(_LM_TINY),
    "xing4": dict(_LM, batch_size=1),
    "xing4_tiny": dict(_LM_TINY),
    "nemotron3s": dict(_LM, batch_size=1),
    "nemotron3s_tiny": dict(_LM_TINY),
    "fcn5net": dict(dataset="mnist", batch_size=64, lr=0.05, max_epochs=10),
    "lr": dict(dataset="mnist", batch_size=64, lr=0.01, max_epochs=10),
}


def make_config(dnn: str, **overrides) -> TrainConfig:
    """Config for a model with its preset applied, then env-var and kwarg
    overrides (the reference's `${var:-default}` shell pattern,
    exp_configs/resnet20.conf:1-8)."""
    base = dict(PRESETS.get(dnn, {}))
    base["dnn"] = dnn
    for field in dataclasses.fields(TrainConfig):
        if field.name == "dnn":
            # dnn selected the preset above; letting a lingering MGWFBP_DNN
            # env var override it here would mix one model's name with
            # another's hyperparameters. Model choice comes from the caller.
            continue
        env = os.environ.get(f"MGWFBP_{field.name.upper()}")
        if env is not None:
            base[field.name] = _coerce(env, field.type)
    base.update({k: v for k, v in overrides.items() if v is not None})
    # dataset-keyed SGD constants fill any key no preset/env/caller set
    for k, v in _DATASET_SGD.get(base.get("dataset", "cifar10"), {}).items():
        base.setdefault(k, v)
    return TrainConfig(**base)


def _coerce(value: str, typ) -> object:
    s = str(typ)
    if "int" in s:
        return int(value)
    if "float" in s:
        return float(value)
    if "bool" in s:
        return value.lower() in ("1", "true", "yes")
    return value
