"""Trainer runtime: the DLTrainer + distributed-driver of this framework.

Parity targets (SURVEY.md §2.2, §2.3): reference `DLTrainer`
(dl_trainer.py:140-276 construction, :736-852 train, :854-937 test) and the
distributed driver `mgwfbp()` (dist_trainer.py:29-102: offline backward
benchmark feeding the merge solver, optimizer wrap, epoch/iter loop with
sec/iter + images/s logging, gradient accumulation, RNN norm clip, resume).

TPU shape of the same pipeline:
  bootstrap -> mesh over local devices (+ multi-host axis via process shards)
  data_prepare -> per-process sharded loaders (weak scaling: batch_size is
      PER DEVICE, reference dl_trainer.py:153-156)
  benchmark_trainer_backward -> tb (arrival order)     [one-shot, offline]
  cost model (calibrated profile or built-in table)    [costmodel]
  make_merged_allreduce -> merge schedule + buckets    [solver]
  make_train_step -> ONE jitted program per iteration  [step]
  fit() -> epoch loop with eval, checkpointing, logs
"""

from __future__ import annotations

import dataclasses
import functools
import os
import signal as _signal
import threading
import time
from collections import deque
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from mgwfbp_tpu import models as zoo
from mgwfbp_tpu.checkpoint import (
    Checkpointer,
    CheckpointRestoreError,
    Snapshot,
)
from mgwfbp_tpu.config import TrainConfig
from mgwfbp_tpu.data import ShardInfo, data_prepare
from mgwfbp_tpu.ops import programs
from mgwfbp_tpu.optim import make_optimizer
from mgwfbp_tpu.parallel.allreduce import make_merged_allreduce
from mgwfbp_tpu.parallel.costmodel import load_profile, lookup_alpha_beta
from mgwfbp_tpu.parallel.mesh import DATA_AXIS, SEQ_AXIS, MeshSpec, make_mesh
from mgwfbp_tpu.profiling import benchmark_trainer_backward
from mgwfbp_tpu.runtime import ResizeUnsupported
from mgwfbp_tpu.runtime import coordination as coord
from mgwfbp_tpu.telemetry import phases
from mgwfbp_tpu.telemetry.phases import NO_SPAN, PhaseRecorder, no_span
from mgwfbp_tpu.train.step import (
    create_train_state,
    make_eval_step,
    make_train_step,
)
from mgwfbp_tpu.utils.faults import FaultPlan, Preempted
from mgwfbp_tpu.utils.platform import env_int
from mgwfbp_tpu.utils.logging import get_logger


def derive_agree_interval(step_s: float, grace_s: float = 30.0) -> int:
    """Drain-agreement cadence from a measured step time (ROADMAP PR-6
    follow-up b): the group consults `agree_any` every N-th step, so a
    preemption drain lags by at most N steps — budget HALF the preemption
    grace window for that lag (the other half covers the in-flight step
    plus the drain checkpoint itself). Clamped to [1, 1000]; explicit
    MGWFBP_AGREE_INTERVAL values are always authoritative over this."""
    if step_s <= 0.0:
        return 1
    return int(min(max(grace_s * 0.5 / step_s, 1.0), 1000.0))


def _elastic_resume_enabled() -> bool:
    """True when a relaunch may resume from a SIBLING tag directory
    written at a different world size (re-sharding the state onto the
    new layout). The supervisor exports MGWFBP_ELASTIC_RESUME=1 for the
    groups it launches — a resize-by-relaunch must find the old world's
    checkpoints; standalone runs keep the exact-tag-only behavior unless
    the operator opts in."""
    raw = (os.environ.get("MGWFBP_ELASTIC_RESUME") or "").strip().lower()
    return raw in ("1", "true", "yes")


class _RollbackRequested(Exception):
    """Internal: K consecutive non-finite steps — unwind train_epoch so
    _fit_epochs can restore the last checkpoint and continue from there."""

    def __init__(self, bad_steps: int):
        super().__init__(f"{bad_steps} consecutive non-finite steps")
        self.bad_steps = bad_steps


def _poison_batch(batch: Any) -> tuple[Any, bool]:
    """NaN-fill every floating leaf of a stacked batch (fault injection:
    NaN inputs make every post-allreduce gradient non-finite without
    touching the compiled step). Returns (batch, poisoned?) — an all-int
    batch (token LMs) has nothing to poison."""
    poisoned = False

    def fill(v):
        nonlocal poisoned
        if hasattr(v, "dtype") and jnp.issubdtype(v.dtype, jnp.floating):
            poisoned = True
            return jnp.full_like(v, jnp.nan)
        return v

    out = jax.tree_util.tree_map(fill, batch)
    return (out if poisoned else batch), poisoned


def _describe_args(args: Any) -> Any:
    """`args` as ShapeDtypeStructs with the shardings the arrays were
    COMMITTED to: what a dispatch was traced and lowered for, still there
    after it has donated the arrays themselves. An uncommitted array (one
    device's batch) is lowered with no sharding of its own, and described
    with one it would be lowered anew and miss every cache the dispatch
    filled (`profiling.step_map` then compiles the step a second time: 23
    to 103 s a cell, my chip runs, PR 49)."""
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype,
            sharding=a.sharding if getattr(a, "committed", False) else None,
        ),
        args,
    )


class Trainer:
    def __init__(
        self,
        config: TrainConfig,
        mesh=None,
        profile_backward: bool = True,
        synthetic_data: Optional[bool] = None,
    ):
        # set-up as spans (telemetry/phases.py): the constructor and what
        # preceded it, written as one `setup` record once the first step's
        # results are read. With telemetry off nothing is opened, no clock
        # read, and the buffer the process opened with is dropped
        self._setup: Optional[phases.SetupRecorder] = None
        self.telemetry = None  # _build_run_sinks opens it
        if config.telemetry or config.metrics_port is not None:
            self._setup = phases.begin_setup()
        else:
            self._drop_setup()
        with self._setup_span("init"):
            self._construct(config, mesh, profile_backward, synthetic_data)

    def _setup_span(self, name: str):
        return NO_SPAN if self._setup is None else self._setup.span(name)

    def _drop_setup(self) -> None:
        """No `setup` record will be written: nothing is kept from here on."""
        self._setup = None
        phases.drop_setup()

    def _construct(
        self, config: TrainConfig, mesh, profile_backward: bool,
        synthetic_data: Optional[bool],
    ) -> None:
        span = self._setup_span
        self.config = config
        with span("mesh"):
            # graft: group-uniform -- the mesh derives from config + the global device set, identical on every process
            self.mesh = mesh if mesh is not None else make_mesh(
                MeshSpec(
                    data=-1, seq=config.seq_parallel, dcn=config.dcn_slices,
                )
            )
        from mgwfbp_tpu.parallel.mesh import DCN_AXIS

        self.dcn_size = self.mesh.shape.get(DCN_AXIS, 1)
        self.ici_size = self.mesh.shape[DATA_AXIS]
        # total data-parallel membership (weak scaling, cost-model world
        # size, eval quantum): inner ICI extent x outer DCN slices
        self.data_size = self.ici_size * self.dcn_size
        # data-dimension mesh axes, ALWAYS a tuple, inner first (the hier
        # lowering convention); every consumer takes it verbatim
        self.data_axes = (
            (DATA_AXIS, DCN_AXIS) if self.dcn_size > 1 else (DATA_AXIS,)
        )
        # reflect the actual worker count into the config BEFORE anything
        # consumes config.tag(): run tags / log dirs / checkpoint dirs must
        # all distinguish 1-device from N-device runs, consistently
        config.nworkers = self.data_size
        self.log = get_logger(
            "mgwfbp.trainer",
            logfile=os.path.join(config.logdir, config.tag(), "train.log")
            if config.logdir
            else None,
        )
        self.shard = ShardInfo(jax.process_index(), jax.process_count())
        # weak scaling: per-device batch (reference per-worker batch) times
        # the local extent of the data axis = this process's loader batch
        local_data_devices = max(
            self.data_size // jax.process_count(), 1
        )
        self.process_batch = config.batch_size * local_data_devices
        # mixed-precision compute policy (config.dtype; the reference's
        # apex FP16 O2 analogue — bf16 on TPU, no loss scaling)
        self.compute_dtype = (
            jnp.dtype(config.dtype)
            if config.dtype not in (None, "", "float32", "f32")
            else None
        )
        with span("model"):
            # graft: group-uniform -- model + metadata derive from config alone
            self.model, self.meta = self._create_model()
            self._apply_lm_window()
        # sequence parallelism (ring attention): shard the lm time dim over
        # the mesh's seq axis. Only carry-free lm models expose a seq_axis
        # attribute (models/transformer.py). self.model stays axis-free
        # (init / host-side apply run outside shard_map); the sharded steps
        # get a seq-bound clone below.
        self.seq_size = self.mesh.shape.get(SEQ_AXIS, 1)
        self.seq_axis = None
        if self.seq_size > 1:
            if not hasattr(self.model, "seq_axis") or self.meta.has_carry:
                raise ValueError(
                    f"model {config.dnn!r} does not support sequence "
                    "parallelism (needs a carry-free lm model with a "
                    "seq_axis attribute, e.g. 'transformer')"
                )
            t = self.meta.input_shape[0]
            if t % self.seq_size != 0:
                raise ValueError(
                    f"sequence length {t} not divisible by seq mesh extent "
                    f"{self.seq_size}"
                )
            self.seq_axis = SEQ_AXIS
        image_hw = None
        if self.meta.task == "classify" and self.meta.input_shape[0] >= 256:
            image_hw = self.meta.input_shape[:2]  # inception 299
        self._image_hw = image_hw
        self._synthetic_data = synthetic_data
        with span("data"):
            self.bundle = self._build_loaders()
        if self.bundle.num_classes != self.meta.num_classes:
            with span("model"):
                # graft: group-uniform -- model + metadata derive from config alone
                self.model, self.meta = self._create_model(
                    self.bundle.num_classes
                )
                # the rebuild reset meta/model to registry defaults;
                # re-apply the window-length override
                self._apply_lm_window()
        # schedule anchor: epoch position the step->lr conversion continues
        # from (moves only on elastic resizes, see update_nworker)
        self._sched_step_offset = 0
        self._sched_epoch_offset = 0.0
        with span("optimizer"):
            self._build_optimizer()
            # on the mesh from birth, like every state the step returns: an
            # uncommitted initial state gives the second call other input
            # shardings than the first, and the whole step compiles twice
            self.state = self._replicate_onto_mesh(create_train_state(
                jax.random.PRNGKey(config.seed),
                self.model,
                self._example_input(),
                self.tx,
            ))
            # canonical param pytree shapes/dtypes: the shape source for
            # layer specs, reducer builds, and checkpoint templates — on the
            # cross-step (rs_fwd_ag) path the live state.params is the
            # sharded carry and no longer LOOKS like the model's param tree
            self._params_template = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                self.state.params,
            )
        self._tb_cache = None  # measured backward profile, reused on resize
        self._tf_cache = None  # measured forward profile (rs_fwd_ag)
        # trace-attributed per-group comm seconds (layout order) for the
        # LIVE schedule, when a profiler trace has measured them (autotune,
        # or the opt-in MGWFBP_TELEMETRY_TRACE snapshot); telemetry's
        # overlap accounting prefers these over cost-model predictions
        self._measured_group_times = None
        # first-dispatch flags: the initial call of each step program
        # compiles (long, silent); the watchdog gets an extended deadline
        # for exactly that phase (ADVICE r4 #3)
        self._train_step_compiled = False
        self._eval_step_compiled = False
        self._profile_backward_enabled = profile_backward
        with span("reducer"):
            # graft: group-uniform -- the merge schedule solves from broadcast-identical profiles; later swaps ride group-agreed commits
            self.reducer = self._build_reducer(profile_backward)
        if self._sharded_opt or self._cross_step:
            # rs_opt_ag / rs_fwd_ag: the optimizer state lives as 1/world
            # bucket shards on each device from here on; it only returns
            # to the replicated optax form at checkpoint boundaries
            # (gather) and elastic resizes (gather -> re-scatter on the
            # new layout)
            self.state = self.state.replace(
                opt_state=self.reducer.optim.init()
            )
            self.log.info(
                "sharded optimizer (%s): opt-state %d B/device vs "
                "%d B replicated (%.2fx reduction over %d workers)",
                self.reducer.comm_op,
                self.reducer.optim.state_bytes_per_device(),
                self.reducer.optim.replicated_state_bytes(),
                self.reducer.optim.replicated_state_bytes()
                / max(self.reducer.optim.state_bytes_per_device(), 1),
                self.reducer.optim.world,
            )
        if self._cross_step:
            # rs_fwd_ag: params too become the cross-step carry — per-group
            # 1/world shards whose all-gather lands in the NEXT step's
            # forward; the canonical replicated tree exists only at
            # checkpoint/eval boundaries (gather) from here on
            self.state = self.state.replace(
                params=self.reducer.optim.scatter_params(self.state.params)
            )
            self.log.info(
                "cross-step pipelining (rs_fwd_ag): %d group gather(s) "
                "deferred into the next step's forward",
                self.reducer.layout.num_groups,
            )
        if self.reducer is not None:
            detail = self.reducer.schedule.policy_detail
            self.log.info(
                "merge schedule: %d groups over %d tensors "
                "(policy=%s%s, predicted nonoverlap %.3g s)",
                self.reducer.schedule.num_groups,
                len(self.reducer.schedule.layer_names),
                config.policy,
                f" -> {detail}" if detail else "",
                self.reducer.schedule.predicted_nonoverlap_time,
            )
        self._build_steps()
        with span("sinks"):
            self._build_run_sinks()
        if self.telemetry is None:
            # asked for and not to be had (no directory): nothing to write to
            self._drop_setup()
        self.start_epoch = 0
        self.iteration = 0  # graft: group-uniform -- the step counter advances in lockstep; resume/rollback targets are broadcast-agreed
        self.carry = None
        # graft: group-uniform -- set by autotune(): race winners ride all_argmin, cache hits agree_all
        self.autotune_report = None  # set by autotune() (cache hit or race)
        # resilience layer (ISSUE 5): deterministic fault plan, graceful
        # preemption drain, non-finite-step bookkeeping, mid-epoch resume
        # for_incarnation: the supervisor exports MGWFBP_INCARNATION per
        # (re)launch; HARD chaos kinds (kill/wedge, ISSUE 20) key on it
        # so a healed relaunch does not re-fire the fault it died of
        self._faults = (
            FaultPlan.from_env()
            .for_process(jax.process_index())
            .for_incarnation(env_int("MGWFBP_INCARNATION", 0))
        )
        if self._faults:
            self.log.info("fault plan armed: %s", self._faults.describe())
        # live observability plane (ISSUE 9): online cost-model drift
        # detection + multi-host straggler probe (telemetry/drift.py).
        # Pure host arithmetic at the logging cadence — the step loop
        # gains zero device syncs from any of it. The straggler probe and
        # the drift-reautotune agreement are COLLECTIVES, so their gates
        # read only group-uniform state (env-derived config, the lockstep
        # iteration counter).
        from mgwfbp_tpu.telemetry.drift import (
            DriftConfig,
            DriftDetector,
            StragglerDetector,
            reautotune_enabled,
        )

        # graft: group-uniform -- MGWFBP_* detector thresholds parse the one supervisor-exported environment
        self._drift_cfg = DriftConfig.from_env()
        self._drift_detector = (
            DriftDetector(self._drift_cfg) if config.telemetry else None
        )
        self._straggler_detector = StragglerDetector(
            self._drift_cfg.straggler_band, self._drift_cfg.hysteresis,
            self._drift_cfg.straggler_min_excess_s,
        )
        self._straggler_enabled = (
            config.telemetry and self._drift_cfg.straggler_band > 0
        )
        # graft: group-uniform -- MGWFBP_DRIFT_REAUTOTUNE is group-uniform env
        self._drift_reautotune_enabled = reautotune_enabled()
        self._drift_reautotune_pending = False
        # training-health telemetry (ISSUE 12): the jitted step packs
        # per-group grad norms / update ratio into its metrics psum
        # (config.health_stats); the trainer strips them, starts their
        # copies to the host, and reads them one step LATE through this
        # deque (the PR-5 guard idiom — finished arrays read on the host,
        # no device program and no device_get on the dispatch path),
        # streams `health` records, and feeds the online detector
        # (telemetry/health.py), whose alarm edges trip the flight
        # recorder (telemetry/recorder.py, wired in _build_run_sinks).
        from mgwfbp_tpu.telemetry.health import (
            HealthConfig,
            HealthDetector,
            health_enabled,
        )

        self._health_cfg = HealthConfig.from_env()
        self._health_detector = (
            HealthDetector(self._health_cfg)
            if config.telemetry and config.health_stats and health_enabled()
            else None
        )
        # the running epoch's span recorder (telemetry/phases.py), here for
        # the watchdog's abort, which writes the record it holds back, and
        # the health drain's `stats_ready`; None between epochs and with
        # telemetry off
        self._phase_rec: Optional[PhaseRecorder] = None
        self._pending_health: deque = deque()  # graft: group-uniform -- fills at the deterministic step cadence; identical length everywhere
        # straggler probe bookkeeping: synchronous SGD equalizes
        # END-TO-END step walls across the group (everyone waits for the
        # straggler inside the collectives — on the CPU mesh even the
        # dispatch call blocks there), so the probe gathers each
        # process's LOCAL busy seconds per step — loader/batch prep and
        # injected stalls, ending BEFORE the dispatch — the share that
        # actually differs on a slow host
        self._local_busy_s = 0.0
        self._probe_iter = 0  # last probed iteration
        self._probe_busy = 0.0  # _local_busy_s at the last probe
        self._preempt_signal: Optional[str] = None
        # multi-host: how often (in optimizer steps) the group runs the
        # tiny agree_any collective that turns ONE host's preemption
        # signal into a GROUP drain. Every step by default (drain latency
        # = 1 step); the collective syncs the dispatch pipeline, so
        # latency-sensitive real-chip runs raise it — drain then lags by
        # at most N steps. Must be identical across the group (the
        # supervisor exports one env); single-host runs never consult it.
        raw_interval = (
            os.environ.get("MGWFBP_AGREE_INTERVAL") or ""
        ).strip()
        try:
            self._agree_interval = max(int(raw_interval or "1"), 1)
        except ValueError:
            raise ValueError(
                f"MGWFBP_AGREE_INTERVAL={raw_interval!r} is not an integer"
            ) from None
        # unset -> auto: once a step time has been measured, derive the
        # interval from it vs the MGWFBP_PREEMPT_GRACE_S budget (default
        # 30 s) and broadcast process 0's choice — the cadence gates a
        # COLLECTIVE, so it must be bit-identical across the group, and
        # per-process wall clocks are not. Explicit values stay
        # authoritative (no derivation runs).
        self._agree_interval_auto = not raw_interval
        raw_grace = (os.environ.get("MGWFBP_PREEMPT_GRACE_S") or "").strip()
        try:
            self._preempt_grace_s = float(raw_grace or "30")
        except ValueError:
            raise ValueError(
                f"MGWFBP_PREEMPT_GRACE_S={raw_grace!r} is not a number"
            ) from None
        self._signals_armed = False
        self._resume_epoch: Optional[int] = None  # mid-epoch resume target
        self._resume_skip_steps = 0  # optimizer steps already done there
        self._resume_carry = None
        self._bad_streak = 0  # consecutive non-finite steps observed
        # guard flags are read LATE (deque), so checking them never stalls
        # the dispatch pipeline and adds no device_get/block_until_ready.
        # Cadence: every step by default; MGWFBP_GUARD_CHECK_INTERVAL=N
        # batches N steps' flags into ONE stacked pull (detection lags by
        # at most N steps; the in-jit skip protects the params either
        # way). On the chip a read of a finished flag takes 0.5 to 0.9 ms;
        # it is the loop's first read of step k-1's outputs, so it is
        # where the host, one step ahead, waits for the busy chip
        # (PERF.md, PR 24 and PR 25; ROADMAP S1 for the N > 1 stack)
        self._pending_guard: deque = deque()  # graft: group-uniform -- fills at the deterministic step cadence; identical length everywhere
        self._guard_interval = max(
            int(os.environ.get("MGWFBP_GUARD_CHECK_INTERVAL", "1")), 1
        )
        # rollback livelock detection: a second rollback with NO finite
        # step observed since the first means the NaN source is
        # deterministic — abort instead of looping
        self._last_rollback_iteration: Optional[int] = None
        self._good_step_since_rollback = True
        with span("resume"):
            self._maybe_resume()

    # ------------------------------------------------------------------
    @property
    def _sharded_opt(self) -> bool:
        """True when the optimizer state is device-sharded (rs_opt_ag)."""
        return (
            getattr(self, "reducer", None) is not None
            and self.reducer.comm_op == "rs_opt_ag"
        )

    @property
    def _cross_step(self) -> bool:
        """True when params AND opt state are device-sharded between steps
        (rs_fwd_ag: the cross-step carry — each group's all-gather lands in
        the next step's forward)."""
        return (
            getattr(self, "reducer", None) is not None
            and self.reducer.comm_op == "rs_fwd_ag"
        )

    def _template_params(self):
        """Full replicated zeros matching the canonical param pytree (the
        interchange form's param template when the live params are carried
        as cross-step shards)."""
        return jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), self._params_template
        )

    def _replicated_template_state(self):
        """TrainState in checkpoint-interchange form: full replicated
        params + the replicated optax opt_state structure every comm path
        saves/restores through."""
        if not (self._sharded_opt or self._cross_step):
            return self.state
        state = self.state
        if self._cross_step:
            state = state.replace(params=self._template_params())
        return state.replace(opt_state=self.tx.init(state.params))

    def _to_checkpoint_state(self, state):
        """Gather sharded state (opt state; cross-step also params) into
        the replicated interchange form."""
        if not (self._sharded_opt or self._cross_step):
            return state
        if self._cross_step:
            state = state.replace(
                params=self.reducer.optim.gather_params(
                    state.params, self._params_template
                )
            )
        return state.replace(
            opt_state=self.reducer.optim.gather(
                state.opt_state, self.tx, state.params
            )
        )

    def _from_checkpoint_state(self, state):
        """Scatter a replicated interchange state onto the current layout
        (opt state first — its scatter reads the still-full params)."""
        if not (self._sharded_opt or self._cross_step):
            return state
        state = state.replace(
            opt_state=self.reducer.optim.scatter(
                state.opt_state, state.params
            )
        )
        if self._cross_step:
            state = state.replace(
                params=self.reducer.optim.scatter_params(state.params)
            )
        return state

    # -- multi-host-capable interchange (ISSUE 13) ----------------------
    # `_to/_from_checkpoint_state` pack and unpack on the HOST, which
    # needs every buffer locally addressable — single-process only. These
    # twins route through the collective seam (`ShardedOptimStep.
    # replicate` all-gathers the shards into replicated global arrays;
    # `scatter_*_onto` re-shards host buffers as global arrays) so the
    # replicated interchange form exists wherever it is GENUINELY needed
    # (eval, autotune hot-swaps, the --ckpt-format replicated escape
    # hatch) at pod scale too. Checkpoints themselves no longer pass
    # through here — the shard-native format saves/restores per-process
    # shards directly.

    def _to_interchange_state(self, state):
        if not (self._sharded_opt or self._cross_step):
            return state
        if jax.process_count() == 1:
            return self._to_checkpoint_state(state)
        optim = self.reducer.optim
        if self._cross_step:
            state = state.replace(
                params=optim.gather_params(
                    optim.replicate(state.params), self._params_template
                )
            )
        return state.replace(
            opt_state=optim.gather(
                optim.replicate(state.opt_state), self.tx, state.params
            )
        )

    def _from_interchange_state(self, state):
        if not (self._sharded_opt or self._cross_step):
            return state
        if jax.process_count() == 1:
            return self._from_checkpoint_state(state)
        optim = self.reducer.optim
        state = state.replace(
            opt_state=optim.scatter_onto(
                state.opt_state, state.params, self.mesh
            )
        )
        if self._cross_step:
            state = state.replace(
                params=optim.scatter_params_onto(state.params, self.mesh)
            )
        return state

    def _gathered_params(self, shards):
        """Canonical replicated params from the cross-step carry — the
        collective route on a multi-host mesh, the host unpack otherwise
        (bitwise identical either way)."""
        optim = self.reducer.optim
        if jax.process_count() > 1:
            shards = optim.replicate(shards)
        return optim.gather_params(shards, self._params_template)

    # ------------------------------------------------------------------
    def _create_model(self, num_classes: Optional[int] = None):
        """(module, meta) for the configuration: the model, and the part of
        it this chip holds (--layers-held, --experts-held, --tensor-share)."""
        config = self.config
        experts = None
        if config.experts_held:
            try:
                first, count = (int(v) for v in config.experts_held.split(":"))
            except ValueError:
                raise ValueError(
                    f"--experts-held {config.experts_held!r} is not "
                    "FIRST:COUNT (two integers)"
                ) from None
            experts = (first, count)
        return zoo.create_model(
            config.dnn, dataset=config.dataset, num_classes=num_classes,
            layers_held=config.layers_held, experts_held=experts,
            tensor_share=config.tensor_share,
        )

    def _build_loaders(self):
        """Sharded data loaders at the current process batch (shared by
        __init__ and update_nworker so the two can never drift)."""
        bundle = data_prepare(
            self.config.dataset,
            data_dir=self.config.data_dir,
            batch_size=self.process_batch,
            shard=self.shard,
            seed=self.config.seed,
            image_hw=self._image_hw,
            synthetic=self._synthetic_data,
            augment=self.config.augment,
            num_steps=self.config.num_steps,
            vocab_size=self.config.vocab_size,
        )
        # eval batch is decoupled from the train batch (MGWFBP_EVAL_BATCH):
        # eval cost is per-batch dispatch + transfer, and carry-free eval
        # has no batch-size semantics
        eval_bs = os.environ.get("MGWFBP_EVAL_BATCH")
        if eval_bs and not self.meta.has_carry:
            bundle.val.set_batch_size(max(int(eval_bs), 1))
        return bundle

    def _build_optimizer(self) -> None:
        """(Re)build tx + the epoch LR schedule. The step->epoch conversion
        inside the schedule is baked from the CURRENT loader length, so this
        must rerun whenever the loaders change (e.g. update_nworker); the
        (_sched_step_offset, _sched_epoch_offset) anchor makes the schedule
        CONTINUE from its pre-resize position instead of re-deriving the
        epoch from the carried-over step count with the new divisor."""
        config = self.config
        # the OptimSpec twin rides along for the rs_opt_ag path: the
        # sharded update interprets the same fields the optax chain was
        # built from, so the two representations cannot drift
        self.tx, self.epoch_schedule, self.optim_spec = make_optimizer(
            config.lr,
            return_spec=True,
            momentum=config.momentum,
            weight_decay=config.weight_decay,
            lr_schedule=config.lr_schedule,
            dataset=config.dataset,
            max_epochs=config.max_epochs,
            warmup_epochs=config.warmup_epochs,
            # the optimizer step counter ticks once per nsteps_update
            # micro-batches, so convert loader batches -> optimizer steps;
            # config.num_batches_per_epoch caps the epoch (smoke runs)
            num_batches_per_epoch=max(
                self._steps_per_epoch(), 1,
            ),
            norm_clip=config.norm_clip,
            step_offset=self._sched_step_offset,
            epoch_offset=self._sched_epoch_offset,
            # reference distributed clip rule: threshold scales by sqrt(1/P)
            # (re-baked on elastic resize since _build_optimizer reruns)
            world_size=self.data_size,
            optimizer=config.optimizer,
            b2=config.adam_b2,
        )

    def _build_steps(self) -> None:
        """(Re)build the jitted train/eval steps from the current
        model/tx/mesh/reducer (shared by __init__ and update_nworker)."""
        if self._setup is None and self.telemetry is not None:
            # a rebuild in a running job (update_nworker, autotune's swap):
            # what the recompile costs goes out as a `setup` record of its
            # own, at the new step's first dispatch
            self._setup = phases.begin_setup(rebuild=True)
        step_model = (
            self.model.clone(seq_axis=self.seq_axis)
            if self.seq_axis
            else self.model
        )
        with self._setup_span("steps"):
            self.train_step = make_train_step(
                step_model, self.meta, self.tx, self.mesh, self.reducer,
                nsteps_update=self.config.nsteps_update,
                axis_name=self.data_axes, seq_axis=self.seq_axis,
                compute_dtype=self.compute_dtype,
                grad_guard=self.config.grad_guard,
                # the statistics exist to be STREAMED: without the telemetry
                # stream they would be computed, popped, and discarded every
                # step — so the stream gates them (and every non-telemetry
                # run compiles the plain step)
                health_stats=(
                    self.config.health_stats and self.config.telemetry
                ),
            )
            self.eval_step = make_eval_step(
                step_model, self.meta, self.mesh, axis_name=self.data_axes,
                seq_axis=self.seq_axis, compute_dtype=self.compute_dtype,
            )
        # fresh programs recompile on first dispatch (update_nworker
        # rebuilds mid-run) — restore the watchdog's compile allowance
        self._train_step_compiled = False
        self._eval_step_compiled = False
        # the count of the OLD program's collectives (_note_step_program);
        # its map (profiling.step_map) goes when the new one is dispatched
        self._step_program = None
        # a one-device step has no gradient collective to count
        self._step_program_noted = self.data_size * self.seq_size <= 1
        self._traced_programs_noted = False

    def _build_run_sinks(self) -> None:
        """(Re)bind every tag-addressed output — log file, checkpoint dir,
        scalar event stream — to the CURRENT config.tag(). Runs at init and
        again whenever the tag changes (update_nworker changes nworkers),
        so checkpoints/events never keep landing under a stale tag that a
        relaunch at the new size would not look in."""
        config = self.config
        self.log = get_logger(
            "mgwfbp.trainer",
            logfile=os.path.join(config.logdir, config.tag(), "train.log")
            if config.logdir
            else None,
        )
        old_ckpt = getattr(self, "checkpointer", None)
        if old_ckpt is not None:
            old_ckpt.close()
        self.checkpointer = None
        if config.checkpoint_dir:
            # full config tag (dnn/dataset/bs/lr/policy/threshold/seed) so
            # distinct experiments never share a resume directory
            # graft: group-uniform -- checkpointer presence is config-derived (--checkpoint-dir)
            self.checkpointer = Checkpointer(
                os.path.join(config.checkpoint_dir, config.tag())
            )
        old_writer = getattr(self, "writer", None)
        if old_writer is not None:
            old_writer.close()
        old_tel = getattr(self, "telemetry", None)
        if old_tel is not None:
            old_tel.close()
        # telemetry event stream (telemetry/events.py): one schema-
        # versioned JSONL PER PROCESS per tagged run (single-process keeps
        # the historical telemetry.jsonl name) — step spans, overlap
        # snapshots, resizes, checkpoints, watchdog stalls all land here;
        # tools/telemetry_merge.py reassembles a multi-host group's
        # streams into one global timeline + straggler table
        self.telemetry = None
        if config.metrics_port is not None and not config.telemetry:
            # the live plane's aggregator is fed by the event stream —
            # a metrics port implies the stream, exactly like the CLI
            config.telemetry = True
        tel_dir = config.telemetry_dir or (
            os.path.join(config.logdir, config.tag())
            if config.logdir
            else None
        )
        run_meta = {
            "model": config.dnn,
            "dataset": config.dataset,
            "world": self.data_size * self.seq_size,
            "comm_op": config.comm_op,
            "policy": config.policy,
            "tag": config.tag(),
            "process_index": jax.process_index(),
            "process_count": jax.process_count(),
        }
        if config.telemetry:
            if tel_dir is None:
                self.log.warning(
                    "--telemetry requested but neither --telemetry-dir nor "
                    "--logdir is set; telemetry disabled"
                )
            else:
                from mgwfbp_tpu.telemetry import EventWriter, stream_filename

                self.telemetry = EventWriter(
                    os.path.join(tel_dir, stream_filename(
                        jax.process_index(), jax.process_count()
                    )),
                    run=run_meta,
                )
        # live observability plane (ISSUE 9): one in-memory aggregator +
        # HTTP server per process, created once and kept across resize
        # rebinds (the port must not churn mid-run); the NEW writer is
        # tee'd into the same aggregator. The server thread reads host
        # state only — the zero-sync contract holds with it enabled.
        if (
            config.metrics_port is not None
            and getattr(self, "_metrics_agg", None) is None
        ):
            from mgwfbp_tpu.telemetry.serve import (
                MetricsAggregator,
                start_metrics_server,
            )

            self._metrics_agg = MetricsAggregator(run=run_meta)
            self._metrics_server = start_metrics_server(
                self._metrics_agg, config.metrics_port, jax.process_index()
            )
        agg = getattr(self, "_metrics_agg", None)
        # anomaly-triggered flight recorder (ISSUE 12): a bounded event
        # ring tee'd off the SAME validated stream the aggregator reads;
        # any alarm (drift/straggler/health/bad_step/watchdog) dumps an
        # atomic postmortem bundle under <tag dir>/postmortems/NNNN.
        # Rebuilt with the writer on resize rebinds (the bundle sequence
        # under a re-used tag continues — the recorder scans the dir).
        self._recorder = None
        if self.telemetry is not None and tel_dir is not None:
            from mgwfbp_tpu.telemetry.recorder import (
                FlightRecorder,
                recorder_enabled,
            )

            if recorder_enabled():
                self._recorder = FlightRecorder(
                    tel_dir,
                    status_provider=(
                        agg.status if agg is not None else None
                    ),
                    schedule_provider=self._schedule_state_doc,
                    profile_armer=(
                        agg.arm_profile if agg is not None else None
                    ),
                    event_sink=self.telemetry.emit,
                    # a multi-host group shares the tag dir: per-process
                    # bundle names, no rename races on the same index
                    suffix=(
                        f".p{jax.process_index()}"
                        if jax.process_count() > 1 else ""
                    ),
                )
        if self.telemetry is not None and (
            agg is not None or self._recorder is not None
        ):
            from mgwfbp_tpu.telemetry.recorder import tee_observers

            self.telemetry.observer = tee_observers(
                agg.observe if agg is not None else None,
                self._recorder.observe
                if self._recorder is not None else None,
            )
        if agg is not None:
            # a live trainer is attached: /profile?steps=N requests now
            # have a consumer (the step loop polls for armed windows)
            agg.enable_profile()
        self._sync_schedule_gauge()
        # scalar event stream (reference's tensorboardX seam, live):
        # process 0 only, like the reference's rank-gated writer. With
        # telemetry on, the ScalarWriter is a thin view over the SAME
        # stream (scalar records), so one file holds the whole run.
        self.writer = None
        if config.tensorboard and config.logdir and jax.process_index() == 0:
            from mgwfbp_tpu.utils.summary import ScalarWriter

            self.writer = ScalarWriter(
                os.path.join(config.logdir, config.tag()),
                stream=self.telemetry,
            )

    # ------------------------------------------------------------------
    # Telemetry (mgwfbp_tpu/telemetry/): every emission below is host-only
    # arithmetic over already-host data — the step loop gains ZERO device
    # syncs from telemetry (enforced by tests/test_telemetry.py's guard and
    # lint rule JIT006 on the jitted side).
    # ------------------------------------------------------------------

    def _emit_event(self, event: str, **fields) -> None:
        """Append one telemetry record; schema misuse (unknown event,
        missing field, device value) raises — that is a bug — but I/O
        failure only disables the stream, never the training run."""
        if self.telemetry is None:
            return
        try:
            self.telemetry.emit(event, **fields)
        except (TypeError, ValueError):
            raise
        except Exception as e:  # noqa: BLE001 — disk full / fs gone
            self.log.warning("telemetry write failed (%s); disabling", e)
            self.telemetry = None

    def _layer_specs(self) -> list:
        """Arrival-ordered LayerSpecs of the live reducer's layer set
        (shared by the autotuner's frontier and the overlap tb prior).
        Shapes come from the canonical param TEMPLATE — the live
        state.params may be the cross-step sharded carry."""
        from mgwfbp_tpu.parallel.solver import LayerSpec

        leaves = jax.tree_util.tree_leaves(self._params_template)
        arr = [leaves[j] for j in self.reducer.perm]
        return [
            LayerSpec(
                name=nm,
                size=int(np.prod(l.shape)) if l.shape else 1,
                itemsize=jnp.dtype(l.dtype).itemsize,
            )
            for nm, l in zip(self.reducer.schedule.layer_names, arr)
        ]

    def _overlap_tb(self) -> Optional[list]:
        """Arrival-ordered per-layer backward seconds for the overlap
        replay: the measured profile when one exists, else the same
        size-prior the solver fell back to (so accounting and schedule
        always reason from the same timeline)."""
        if self._tb_cache is not None:
            return list(self._tb_cache)
        from mgwfbp_tpu.parallel.solver import size_prior_tb

        return size_prior_tb(
            self._layer_specs(), getattr(self, "cost_model", None)
        )

    def _emit_overlap_snapshot(
        self, step_s: float, step: int, epoch: int
    ) -> None:
        """Overlap-efficiency accounting for the current schedule regime:
        one aggregate `overlap` record plus one `comm_group` record per
        merge group (exposed vs hidden comm — README 'Telemetry')."""
        if self.telemetry is None or self.reducer is None:
            return
        cost_model = getattr(self, "cost_model", None)
        if cost_model is None or step_s <= 0.0:
            return
        from mgwfbp_tpu import telemetry as tel

        measured = self._measured_group_times
        if measured is not None and len(measured) != (
            self.reducer.layout.num_groups
        ):
            measured = None  # traced under a since-replaced schedule
        tf = (
            list(self._tf_cache)
            if self._cross_step and self._tf_cache is not None
            else None  # summarize falls back to the tb/2 forward prior
        )
        summary = tel.summarize(
            self.reducer, cost_model, self._overlap_tb(), step_s,
            measured=measured, tf=tf,
        )
        self._emit_event(
            "overlap", step=int(step), epoch=int(epoch),
            **summary.to_event_fields(),
        )
        for fields in summary.group_event_fields(int(step)):
            self._emit_event("comm_group", **fields)
        self.log.info(
            "overlap snapshot (%s): %.4g s comm/step = %.4g hidden + %.4g "
            "exposed -> efficiency %.3f",
            summary.attribution, summary.comm_s, summary.hidden_s,
            summary.exposed_s, summary.efficiency,
        )

    def _measure_group_times_live(self, iters: int = 2) -> None:
        """Opt-in (MGWFBP_TELEMETRY_TRACE=1) trace attribution of per-group
        comm from a couple of live steps. This DOES sync the device, so it
        runs once before the epoch loop — never inside it; on backends
        whose traces drop the name stack (CPU mesh) it yields nothing and
        overlap accounting stays on the cost model."""
        if self.reducer is None:
            return
        from mgwfbp_tpu.profiling import trace_group_times

        batch_iter = self._autotune_batches()

        def run():
            for _ in range(iters):
                self.state = self._apply_train_step(
                    self.state, next(batch_iter)
                )
            jax.block_until_ready(self.state)

        wd = getattr(self, "_watchdog", None)
        if wd is not None and not self._train_step_compiled:
            from mgwfbp_tpu.utils.watchdog import COMPILE_ALLOW_S

            wd.beat("telemetry group trace", allow_s=COMPILE_ALLOW_S)
        try:
            measured = trace_group_times(
                run, self.reducer.layout.num_groups, iters=iters
            )
        except Exception as e:  # noqa: BLE001 — observability must never
            # kill the run it observes
            self.log.info("telemetry group trace failed (%s)", e)
            return
        self.iteration += iters
        self._train_step_compiled = True
        if measured is not None:
            self._measured_group_times = measured
            self.log.info(
                "telemetry: trace attributed %d group comm time(s)",
                len(measured),
            )

    # ------------------------------------------------------------------
    # On-demand deep profiling (ISSUE 10): /profile?steps=N arms a
    # bounded jax.profiler.trace window on the LIVE job. The HTTP handler
    # only flips host state (telemetry/serve.MetricsAggregator); the step
    # loop consumes it here. The window itself deliberately SYNCS the
    # device (like the startup MGWFBP_TELEMETRY_TRACE snapshot and the
    # autotune race) — it runs on demand only; the DISARMED check is one
    # lock acquire, so the step loop's zero-sync contract holds whenever
    # no window is armed (pinned by the zero-sync guard test).
    # ------------------------------------------------------------------

    def _maybe_profile_window(self) -> None:
        """Consume an armed /profile request at a step boundary.

        Single-process: checked every step (the "next N steps" promise).
        Multi-host: the window's steps are lockstep collective steps, so
        EVERY process must enter it together — at every agree-interval
        step the group gathers its locally-armed step counts (the gate
        reads only group-uniform config, so agreement participation never
        depends on the local request) and runs the agreed max. Each
        process traces locally; the per-group device times are then
        gathered so any process's /profile answer shows the whole
        group."""
        if self.config.metrics_port is None:
            return
        agg = getattr(self, "_metrics_agg", None)
        if coord.process_count() == 1:
            req = agg.take_profile_request() if agg is not None else None
            if req:
                self._run_profile_window(int(req))
            return
        if self.iteration % self._agree_interval != 0:
            return
        local = float(
            agg.take_profile_request() or 0
        ) if agg is not None else 0.0
        steps = int(max(coord.gather_values(local)))
        if steps > 0:
            self._run_profile_window(steps)

    def _run_profile_window(self, steps: int) -> None:
        """Trace `steps` live training steps (state carried — genuine
        optimizer steps, nothing replayed or lost), write the Chrome-trace
        slice next to the run's logs, reduce the trace by the step's map
        (`profiling.split_trace`: device time by scope and pass, by merge
        group, the exchange's carriers and waits; the `step_scopes`
        record), gather the groups' times across processes, and feed the
        drift detector's ABSOLUTE per-group residual channel — a straggler
        whose slowness is purely device-side becomes visible live, not
        only post-hoc."""
        from mgwfbp_tpu import profiling
        from mgwfbp_tpu.telemetry.serve import PROFILE_MAX_STEPS

        steps = max(1, min(int(steps), PROFILE_MAX_STEPS))
        agg = getattr(self, "_metrics_agg", None)
        num_groups = (
            self.reducer.layout.num_groups
            if self.reducer is not None else 0
        )
        trace_dir = None
        if self.config.logdir:
            trace_dir = os.path.join(
                self.config.logdir, self.config.tag(), "profile",
                f"iter{self.iteration:08d}",
            )
            try:
                os.makedirs(trace_dir, exist_ok=True)
            except OSError as e:
                # a full/read-only logdir must degrade (temp-dir trace,
                # discarded after attribution), never kill the run
                self.log.warning(
                    "profile: cannot create %s (%s); trace slice will "
                    "not be persisted", trace_dir, e,
                )
                trace_dir = None
        self.log.info(
            "profile window: tracing %d live step(s) at iter %d%s",
            steps, self.iteration,
            f" -> {trace_dir}" if trace_dir else "",
        )
        wd = getattr(self, "_watchdog", None)
        if wd is not None:
            # BEFORE the HLO lower/compile below: the AOT compile of the
            # live step is itself a legitimately long silent phase
            from mgwfbp_tpu.utils.watchdog import COMPILE_ALLOW_S

            wd.beat(f"profile window ({steps} steps)",
                    allow_s=COMPILE_ALLOW_S)
        batch_iter = self._autotune_batches()
        # the join key of trace events to scopes and groups: a backend whose
        # trace drops the jax name stack (the CPU mesh) still names each
        # event after the instruction it ran. Built here where no reader
        # asked before (a window syncs the device anyway)
        step_map = profiling.step_map()

        def run():
            # annotated like train_epoch's iterations (telemetry/phases.py),
            # so the slice shows the host's spans above the device ops; the
            # tuning feed waits for a batch and places it in one generator.
            # The profiler keeps its default options: with the host tracer
            # at annotations only (level 1) a TPU v5e's runtime still writes
            # 12.9 million host events per 32-step ResNet-50 epoch and the
            # chip idles 89% against 39% (PERF.md, PR 24)
            for _ in range(steps):
                with jax.profiler.TraceAnnotation("wait,place"):
                    batch = next(batch_iter)
                with jax.profiler.StepTraceAnnotation(
                    "train", step_num=self.iteration + 1
                ):
                    self.state = self._apply_train_step(self.state, batch)
                # count each applied step as it happens: the traced steps
                # are genuine optimizer steps, and on a failure below the
                # group-uniform iteration counter (every agree-interval
                # gate reads it) must still reflect every step that ran
                self.iteration += 1
            jax.block_until_ready(self.state)

        # the steps already dispatched run to their end first, so that the
        # trace holds `steps` executions of every instruction and no tail of
        # an earlier one (the split divides by them)
        jax.block_until_ready(self.state)
        t0 = time.perf_counter()
        try:
            split = profiling.trace_step_split(
                run, step_map, steps, logdir=trace_dir
            )
        except Exception as e:  # noqa: BLE001 — observability must never
            # kill the run it observes
            self.log.warning("profile window failed (%s)", e)
            if agg is not None:
                agg.fail_profile(str(e))
            return
        finally:
            if wd is not None:
                wd.beat("profile window done")
        wall_s = time.perf_counter() - t0
        self._train_step_compiled = True
        measured = None
        if split is not None:
            self._emit_event(
                "step_scopes", step=int(self.iteration), steps=int(steps),
                **{k: split[k] for k in (
                    "scopes", "layers", "groups", "exchange", "top",
                    "total_ms", "events")},
            )
            self.log.info("step by scope: %s", profiling.split_summary(split))
            groups_s = [ms * 1e-3 for ms in split["groups"][:num_groups]]
            if num_groups and len(groups_s) == num_groups and all(groups_s):
                measured = groups_s  # partial attribution is worse than none
        attribution = "trace" if measured is not None else "none"
        groups_doc: list[dict] = []
        if self.reducer is not None:
            layout = self.reducer.layout
            cost_model = getattr(self, "cost_model", None)
            predicted = None
            if cost_model is not None:
                # device_s comes from group-scope attribution, so the
                # predicted column must be scope-comparable (ICI legs
                # only on hier — see _scope_comparable_predictions)
                predicted = self._scope_comparable_predictions(cost_model)
            for gi in range(num_groups):
                row = {
                    "group": gi,
                    "nbytes": int(layout.group_sizes[gi])
                    * int(np.dtype(layout.dtypes[gi]).itemsize),
                }
                if predicted is not None:
                    row["predicted_s"] = float(predicted[gi])
                if measured is not None:
                    row["device_s"] = float(measured[gi])
                groups_doc.append(row)
        # fixed-length gather: attribution is host/backend dependent, so
        # a process whose trace attributed nothing contributes zeros —
        # the lockstep shape (num_groups is group-uniform) never varies
        per_process = None
        if coord.process_count() > 1 and num_groups:
            row = (
                [float(t) for t in measured]
                if measured is not None and len(measured) == num_groups
                else [0.0] * num_groups
            )
            per_process = coord.gather_vectors(row)
        if (
            measured is not None
            and self.reducer is not None
            and len(measured) == num_groups
        ):
            # the drift detector's comm channel reads these: from the
            # next log window on it checks each group ABSOLUTELY
            # (predicted vs device-attributed) instead of the
            # baseline-relative aggregate — mid-run, no restart
            self._measured_group_times = [float(t) for t in measured]
        result = {
            "steps": int(steps),
            "iteration": int(self.iteration),
            "wall_s": float(wall_s),
            "attribution": attribution,
            "trace_dir": trace_dir,
            "groups": groups_doc,
        }
        if per_process is not None:
            result["per_process_device_s"] = {
                str(pi): [float(t) for t in vec]
                for pi, vec in enumerate(per_process)
            }
        if agg is not None:
            agg.set_profile_result(result)
        self._emit_event(
            "profile", step=int(self.iteration), steps=int(steps),
            attribution=attribution,
            device_s=(
                [float(t) for t in measured] if measured is not None
                else []
            ),
            trace_dir=trace_dir or "",
        )
        self.log.info(
            "profile window done: %d step(s) in %.3g s, attribution=%s"
            "%s", steps, wall_s, attribution,
            (
                " (" + ", ".join(
                    f"g{r['group']}={r.get('device_s', 0.0):.4g}s"
                    for r in groups_doc
                ) + ")"
            ) if measured is not None else "",
        )

    def _scope_comparable_predictions(self, cost_model):
        """Per-group predicted seconds COMPARABLE to group-scope
        (``mgwfbp_groupNNNN``) trace attribution. On the hier lowering
        the DCN collectives live under their own ``mgwfbp_dcngroupNNNN``
        scopes, which per-group attribution does not collect — so the
        comparable prediction is the ICI legs (RS + AG) alone; a
        full-predict comparison there raises a comm_residual alarm of
        ~(ici+dcn)/ici on a perfectly calibrated model (and, with
        MGWFBP_DRIFT_REAUTOTUNE=1, an endless forced re-race loop).
        Every other lowering's group scopes cover the whole collective,
        so the plain group_comm_times predictions apply."""
        from mgwfbp_tpu.telemetry import group_comm_times

        predicted, nbytes, _ = group_comm_times(self.reducer, cost_model)
        if self.reducer.comm_op == "hier":
            from mgwfbp_tpu.parallel.solver import (
                is_two_level,
                two_level_leg_costs,
            )

            if is_two_level(cost_model):
                rs_c, _, ag_c = two_level_leg_costs(cost_model)
                predicted = [rs_c(b) + ag_c(b) for b in nbytes]
        return predicted

    def _on_watchdog_stall(
        self, phase: str, idle_s: float, timeout_s: float, abort: bool
    ) -> None:
        """Watchdog stall/abort -> structured event in the run's stream
        (post-mortems of a wedged device grep ONE file, not stderr). The
        event also flips /healthz unhealthy through the aggregator tee —
        BEFORE an rc-86 abort kills the process, so a prober sees 503,
        not a reset connection."""
        rec = self._phase_rec
        if abort and rec is not None:
            # rc 86 follows and the loop's thread is stuck: the record of
            # the step in flight, held back until the next dispatch, goes
            # out first, with the spans the loop got through
            rec.flush()
        self._emit_event(
            "watchdog_stall", phase=str(phase), idle_s=float(idle_s),
            timeout_s=float(timeout_s), abort=bool(abort),
        )

    def _sync_schedule_gauge(self) -> None:
        """Push the committed schedule into the /status aggregator (at
        build, autotune commit / hot swap, and elastic resize)."""
        agg = getattr(self, "_metrics_agg", None)
        if agg is None:
            return
        reducer = getattr(self, "reducer", None)
        if reducer is None:
            agg.set_schedule("none", 0, self.config.policy)
        else:
            agg.set_schedule(
                reducer.comm_op,
                reducer.layout.num_groups,
                reducer.schedule.policy_detail or self.config.policy,
                float(reducer.schedule.predicted_nonoverlap_time),
            )

    def _note_first_dispatch(
        self, step_args, rec: Optional[PhaseRecorder]
    ) -> None:
        """The first dispatch of a newly built step program has returned:
        the open set-up takes that step's span from its record (`rec` holds
        it), a rebuild's set-up, which waits for no result, is written, and
        the program is read (`program_read`). What it takes to map the
        compiled program later is kept for the process
        (`profiling.note_step`: the jitted step, its arguments as shapes and
        shardings, the scopes the model and the step declare, the log
        directory; no device buffer): nothing is built until a profile
        window or a reader of a trace asks `profiling.step_map()`."""
        setup = self._setup if rec is not None else None
        if setup is not None and setup.first_step is None:
            setup.dispatched(self.iteration, *rec.dispatch_span())
            if setup.rebuild:
                self._write_setup()
        with self._setup_span("program_read"):
            if step_args is not None:
                from mgwfbp_tpu import profiling
                from mgwfbp_tpu.train.step import STEP_SCOPES

                profiling.note_step(
                    self.train_step, step_args,
                    {**getattr(self.model, "scopes", {}),
                     **dict.fromkeys(STEP_SCOPES, profiling.UPDATE_LAYER)},
                    self.config.logdir,
                )
                if not self._step_program_noted:
                    self._note_step_program()
            self._note_traced_programs()

    def _setup_results_read(self, step: int) -> None:
        """The host holds the results of `step`: where that is the open
        set-up's first step (or a later one), set-up is over. The record
        follows that step's own `step` record, which an epoch of one step
        still holds back here: train_epoch writes both at its end."""
        setup = self._setup
        if setup.first_step is None or step < setup.first_step:
            return
        setup.results_read()
        rec = self._phase_rec
        if rec is None or not rec.holds(setup.first_step):
            self._write_setup()

    def _write_setup(self) -> None:
        if self.telemetry is None:  # the stream failed meanwhile
            self._drop_setup()
            return
        setup, self._setup = self._setup, None
        record = setup.finish(self.telemetry.clock_of)
        self._emit_event("setup", **record)
        self.log.info("%s", phases.setup_line(record))

    def _note_step_program(self) -> None:
        """Once per step-program build, after its first dispatch: count the
        compiled program's collectives and how many of them the compiler
        made asynchronous (profiling.collective_counts), for the log, the
        `step_program` telemetry record and _schedule_state_doc. Only a
        program with gradient collectives is read (_build_steps marks a
        one-device step as noted: it has none); the read fills the
        process's step map (`profiling.step_map()`, the `step_map` set-up
        span), which a one-device run builds only when asked.

        No second compilation: the noted arguments describe the dispatch
        that just built the program, so the lowering is jax's cached one,
        and the executable is the one in memory, or, for a step built with
        compile options (train/step.py: jax keeps no executable in memory
        for those), a read of the persistent compile cache that the
        dispatch just wrote."""
        self._step_program_noted = True
        from mgwfbp_tpu import profiling
        from mgwfbp_tpu.train.step import async_collective_options

        red_axes = tuple(self.data_axes) + (
            (self.seq_axis,) if self.seq_axis else ()
        )
        options = sorted(async_collective_options(self.mesh, red_axes))
        if options and not jax.config.jax_compilation_cache_dir:
            # nothing to read back: compiling again is all that is left
            self.log.info(
                "merge schedule: compiled step not read (compile options "
                "%s and no persistent compile cache)", ", ".join(options),
            )
            return
        with self._setup_span("step_map"):
            step_map = profiling.step_map()
        if step_map is None:  # the compiled text cannot be had (logged)
            return
        self._step_program = {
            **profiling.collective_counts(step_map.instructions),
            "compiler_options": options,
        }
        self.log.info(
            "merge schedule: the compiled step issues %d collectives, %d of "
            "them asynchronous (compile options: %s)",
            self._step_program["collectives"],
            self._step_program["async_collectives"],
            ", ".join(options) or "none",
        )
        self._emit_event(
            "step_program", step=int(self.iteration), **self._step_program
        )

    def _note_traced_programs(self) -> None:
        """Once per step-program build, after its first dispatch, what
        make_train_step noted while the step was traced: which way each call
        of the ops' entry points went down, by platform and shape, and the
        distinct kernel programs those calls need (ops/programs.py has the
        records and their log lines). Nothing is compiled or read from the
        device."""
        self._traced_programs_noted = True
        calls = getattr(self.train_step, "traced_programs", None)
        if not calls:  # not traced through make_train_step's own wrapper
            return
        for record, ops, line in programs.RECORDS:
            fields = {
                name: int(n) for op in ops for name, n in calls[op].items()}
            self.log.info(line, fields)
            self._emit_event(record, step=int(self.iteration), **fields)

    def _schedule_state_doc(self) -> dict:
        """The committed schedule + cost-model state, JSON-able — the
        flight recorder snapshots this into every postmortem bundle so
        'what schedule was live when it broke' survives the run."""
        doc: dict = {"iteration": int(self.iteration)}
        reducer = getattr(self, "reducer", None)
        if reducer is not None:
            doc["schedule"] = {
                "comm_op": str(reducer.comm_op),
                "num_groups": int(reducer.layout.num_groups),
                "groups": [list(g) for g in reducer.layout.groups],
                "dcn_groups": [
                    list(d) for d in reducer.schedule.dcn_groups
                ],
                "policy_detail": str(
                    reducer.schedule.policy_detail or self.config.policy
                ),
                "predicted_nonoverlap_s": float(
                    reducer.schedule.predicted_nonoverlap_time
                ),
            }
        cost_model = getattr(self, "cost_model", None)
        if cost_model is not None:
            from mgwfbp_tpu.parallel import autotune as at

            doc["cost_model"] = at.model_summary(cost_model)
        measured = getattr(self, "_measured_group_times", None)
        if measured is not None:
            doc["measured_group_times"] = [float(t) for t in measured]
        program = getattr(self, "_step_program", None)
        if program is not None:
            doc["step_program"] = dict(program)
        return doc

    # ------------------------------------------------------------------
    # Training-health telemetry (ISSUE 12): the jitted step's health/*
    # metrics leave the chip as copies of the step's own output arrays,
    # started when the step is dispatched and read one step LATE (the
    # PR-5 deque idiom) into `health` events + the online detector; alarm
    # edges become `health_alarm` events, which the flight recorder tee
    # turns into postmortem bundles. The drain dispatches no device
    # program (a program would queue behind the step in flight, and the
    # host would wait that step out: PERF.md, PR 25) and calls no
    # device_get/block_until_ready (tests/test_health.py's zero-sync
    # guard counts those two; tests/test_health_drain.py pins "no
    # program"). Reading an array does wait for the step that made it:
    # where the guard ran first it has already waited, `stats_ready` on
    # the step record says so.
    # ------------------------------------------------------------------

    def _note_health_stats(self, epoch: int, metrics) -> None:
        """Strip this step's health/* statistics from the metrics dict
        (they are telemetry plumbing, not log-line metrics), start their
        copies to the host and queue them; drain all but the newest
        step's."""
        if not isinstance(metrics, dict):
            return
        from mgwfbp_tpu.train.step import HEALTH_PREFIX

        keys = [k for k in metrics if k.startswith(HEALTH_PREFIX)]
        if not keys:
            return
        vals = {k: metrics.pop(k) for k in keys}
        if self.telemetry is None:
            return
        vals["loss"] = metrics["loss"]
        for v in vals.values():
            v.copy_to_host_async()
        self._pending_health.append((self.iteration, epoch, vals))
        if len(self._pending_health) <= self._guard_interval:
            return
        self._drain_health([
            self._pending_health.popleft()
            for _ in range(len(self._pending_health) - 1)
        ])

    def _drain_health_flags(self) -> None:
        items = list(self._pending_health)
        self._pending_health.clear()
        self._drain_health(items)

    def _drain_health(self, items: list) -> None:
        """One `health` event per queued step, in step order. Each item
        decodes with its own keys (an autotune commit or a resize changes
        the per-group key set between two steps), on the host."""
        if not items:
            return
        from mgwfbp_tpu.train.step import HEALTH_PREFIX

        if self._phase_rec is not None:
            # of a replicated array the read below takes the first
            # addressable replica; the others may finish a moment later
            self._phase_rec.stats_ready(all(
                v.addressable_data(0).is_ready()
                for _, _, d in items for v in d.values()
            ))
        g_prefix = f"{HEALTH_PREFIX}gnorm_g"
        c_prefix = f"{HEALTH_PREFIX}comp_err_g"
        # a model's own statistics (routing counts, a scan's state): the
        # keys it declares, arrays among them, and no part of the `health`
        # record; the model turns them into counters on the step record
        own_keys = getattr(self.model, "health_keys", ())
        for it, ep, d in items:
            own = {
                k: np.asarray(d[k], dtype=np.float64)
                for k in own_keys if k in d
            }
            if own:
                d = {k: v for k, v in d.items() if k not in own}
                if self._phase_rec is not None:
                    self._phase_rec.counters(self.model.step_counters(
                        own,
                        tokens=self.config.batch_size
                        * self.meta.input_shape[0],
                    ))
            vals = {
                k: float(np.asarray(d[k], dtype=np.float32))
                for k in sorted(d)
            }
            group_norms = [
                v for k, v in vals.items() if k.startswith(g_prefix)
            ]
            comp = [v for k, v in vals.items() if k.startswith(c_prefix)]
            fields = {
                "step": int(it),
                "epoch": int(ep),
                "loss": vals["loss"],
                "grad_norm": vals.get(
                    f"{HEALTH_PREFIX}grad_norm", float("nan")
                ),
                "update_ratio": vals.get(
                    f"{HEALTH_PREFIX}update_ratio", float("nan")
                ),
            }
            if group_norms:
                fields["group_norms"] = group_norms
            if comp:
                fields["compression_error"] = comp
            self._emit_event("health", **fields)
            det = self._health_detector
            if det is None:
                continue
            for a in det.observe(
                loss=fields["loss"],
                grad_norm=fields["grad_norm"],
                compression_errors=comp or None,
            ):
                self.log.warning(
                    "health %s: %s alarm (value %.3g vs band %.3g) at "
                    "iter %d",
                    "RAISED" if a.active else "cleared", a.kind,
                    a.value, a.band, it,
                )
                self._emit_event(
                    "health_alarm", kind=a.kind, step=int(it),
                    value=float(a.value), band=float(a.band),
                    active=bool(a.active), group=int(a.group),
                )
        if self._setup is not None:
            self._setup_results_read(items[-1][0])

    def _reset_health_detector(self) -> None:
        """Resolve raised health alarms and forget learned baselines —
        called after a rollback restores an older model (the baselines
        describe statistics the restored model does not produce)."""
        self._pending_health.clear()
        det = self._health_detector
        if det is None:
            return
        for a in det.clear_alarms():
            self._emit_event(
                "health_alarm", kind=a.kind, step=int(self.iteration),
                value=float(a.value), band=float(a.band),
                active=False, group=int(a.group),
            )
        det.reset()

    def _observe_drift_window(self, step_s: float) -> None:
        """Feed one measured log-window step time to the drift detector
        and emit any alarm edges (telemetry/drift.py). Host arithmetic
        only. A raised alarm arms the re-autotune trigger when
        MGWFBP_DRIFT_REAUTOTUNE=1 (fired at a deterministic step
        boundary; multi-host rides agree_any so the race is lockstep)."""
        det = self._drift_detector
        if det is None or step_s <= 0.0:
            return
        if not getattr(self, "_drift_window_seen", False):
            # the run's FIRST log window amortizes the one-off XLA
            # compile; feeding it would poison every baseline the
            # detector learns
            self._drift_window_seen = True
            return
        alarms = list(det.observe_step_window(step_s))
        cost_model = getattr(self, "cost_model", None)
        if self.reducer is not None and cost_model is not None:
            from mgwfbp_tpu.telemetry import group_comm_times

            measured = self._measured_group_times
            if measured is not None and len(measured) == (
                self.reducer.layout.num_groups
            ):
                # measured is group-scope trace attribution: compare it
                # against scope-COMPARABLE predictions (on hier the DCN
                # collectives ride their own scopes and are not in it)
                predicted = self._scope_comparable_predictions(cost_model)
                alarms += det.observe_comm(predicted, measured_s=measured)
            elif self._tb_cache is not None:
                # whole-step fallback: the full (both-link) predictions
                # are the right comparison for a step-delta aggregate
                predicted, _, _ = group_comm_times(
                    self.reducer, cost_model
                )
                # aggregate upper bound: the non-backward share of the
                # measured step (the autotune step-delta attribution) —
                # needs a MEASURED tb (the size-prior tb is itself a comm
                # prediction and would corrupt the residual)
                measured_total = step_s - float(sum(self._tb_cache))
                if measured_total > 0.0:
                    alarms += det.observe_comm(
                        predicted, measured_total_s=measured_total
                    )
        for a in alarms:
            self.log.warning(
                "drift %s: %s alarm (residual %.3g vs band %.3g%s)",
                "RAISED" if a.active else "cleared", a.kind, a.residual,
                a.band, f", group {a.group}" if a.group >= 0 else "",
            )
            self._emit_event(
                "drift_alarm", kind=a.kind, step=int(self.iteration),
                residual=float(a.residual), band=float(a.band),
                active=bool(a.active), group=int(a.group),
            )
            if a.active and self._drift_reautotune_enabled:
                self._drift_reautotune_pending = True

    def _maybe_drift_reautotune(self) -> None:
        """Fire the armed drift re-autotune at a deterministic step
        boundary. Multi-host: EVERY process runs the agree_any at every
        agree-interval step (the gate reads only group-uniform state), so
        one process's local alarm pulls the whole group into the same
        lockstep candidate race the startup autotune runs."""
        if not self._drift_reautotune_enabled:
            return
        if coord.process_count() == 1:
            if self._drift_reautotune_pending:
                self._drift_reautotune()
            return
        if self.iteration % self._agree_interval != 0:
            return
        if coord.agree_any(self._drift_reautotune_pending):
            self._drift_reautotune()

    def _drift_reautotune(self) -> None:
        """Re-race the schedule frontier on the live job through the
        existing hot-swap seam (`autotune(force=True)` ->
        `_swap_reducer`): the race re-measures, the refit corrects the
        cost model, and the measured argmin replaces the drifted
        schedule. The detector resets afterwards — its residuals
        described the OLD model."""
        self._drift_reautotune_pending = False
        if self.reducer is None:
            return
        self.log.warning(
            "cost-model drift: re-autotuning the merge schedule on the "
            "live job (MGWFBP_DRIFT_REAUTOTUNE=1)"
        )
        self.autotune(force=True)
        self._reset_drift_baselines()

    def _reset_drift_baselines(self) -> None:
        """Resolve any raised drift alarms and forget the detector's
        baselines — called whenever the regime they described changes out
        from under them (a drift re-autotune installed a corrected
        model, a hot schedule swap, an elastic resize changed the world
        size). Also skips the NEXT log window: it amortizes the swap's
        recompile and would poison the fresh baselines exactly like the
        run's first compile window."""
        det = self._drift_detector
        if det is None:
            return
        for a in det.clear_alarms():
            self._emit_event(
                "drift_alarm", kind=a.kind, step=int(self.iteration),
                residual=float(a.residual), band=float(a.band),
                active=False, group=int(a.group),
            )
        det.reset()
        self._drift_window_seen = False

    def _maybe_straggler_probe(self) -> None:
        """Live multi-host straggler probe: at every agree-interval step
        the group gathers its per-process LOCAL busy seconds per step
        (coordination.gather_values — one tiny lockstep collective, the
        same cost class as the preempt agree_any at the same cadence) and
        the hysteresis detector names a process consistently slower than
        the fastest by more than MGWFBP_STRAGGLER_BAND. Local busy time
        (not the end-to-end step wall, which the group's collectives
        equalize) is what a slow host actually inflates. Every process
        emits the identical agreed row into its own stream;
        tools/telemetry_merge.py shows them alongside its post-hoc
        table."""
        if not self._straggler_enabled or coord.process_count() == 1:
            return
        if self.iteration % self._agree_interval != 0:
            return
        steps = self.iteration - self._probe_iter
        if steps <= 0:
            return
        local = (self._local_busy_s - self._probe_busy) / steps
        self._probe_iter = self.iteration
        self._probe_busy = self._local_busy_s
        times = coord.gather_values(local)
        alarm = self._straggler_detector.observe(times)
        if alarm is None:
            return
        self.log.warning(
            "straggler %s: process %d is %.4g s/step slower than the "
            "fastest (%.4g vs %.4g)",
            "RAISED" if alarm.active else "cleared", alarm.slow_process,
            alarm.excess_s, alarm.step_s_max, alarm.step_s_min,
        )
        self._emit_event(
            "straggler", step=int(self.iteration),
            slow_process=int(alarm.slow_process),
            excess_s=float(alarm.excess_s),
            step_s_max=float(alarm.step_s_max),
            step_s_min=float(alarm.step_s_min),
            active=bool(alarm.active),
        )

    def _cached_schedule_entry(self):
        """(entry, path) of a committed autotune schedule for the CURRENT
        (model, world, ...) cache key whose layer set matches the live
        model, else None — the elastic-resize seam consults this before
        settling for the freshly solved schedule."""
        from mgwfbp_tpu.parallel import autotune as at

        if self.reducer is None:
            return None
        cfg = self.config
        cache_dir = cfg.schedule_cache or os.path.join(
            "profiles", "schedule_cache"
        )
        key = at.cache_key(
            cfg.dnn, self.data_size * self.seq_size, cfg.comm_op, cfg.dtype,
            comm_dtype=cfg.comm_dtype,
            compressor=cfg.compressor, density=cfg.density,
            batch_size=cfg.batch_size, nsteps_update=cfg.nsteps_update,
            dcn_slices=self.dcn_size,
        )
        path = at.entry_path(cache_dir, key)
        try:
            entry = at.load_cache_entry(path)
        except ValueError as e:
            self.log.warning("schedule cache entry unreadable: %s", e)
            return None
        if entry is None:
            return None
        if entry.get("layer_names") != list(
            self.reducer.schedule.layer_names
        ):
            return None
        return entry, path

    def _steps_per_epoch(self) -> int:
        """Optimizer steps per epoch: loader batches / nsteps_update, capped
        by config.num_batches_per_epoch when set (smoke/CI runs)."""
        steps = self.bundle.num_batches_per_epoch // max(
            self.config.nsteps_update, 1
        )
        if self.config.num_batches_per_epoch:
            steps = min(steps, self.config.num_batches_per_epoch)
        return steps

    def update_nworker(self, nworkers: int) -> None:
        """Elastic worker-count resize (reference `update_nworker`,
        dl_trainer.py:545-566: re-rank + rebuild DistributedSampler/loaders
        for a changed worker count — defined there but never called).

        On TPU the worker count is the data-axis extent, so a resize is a
        real reconfiguration, not just a sampler rebuild: the mesh shrinks or
        grows over the local devices, the train state re-replicates onto the
        new mesh, the data loaders re-shard (weak scaling keeps the
        PER-DEVICE batch constant, so the process batch changes with the
        extent), and — unlike the reference — the MG-WFBP merge schedule is
        RE-SOLVED, because the α-β communication constants depend on the
        world size. The measured backward profile is reused (per-device work
        is unchanged under weak scaling).
        """
        if nworkers == self.data_size:
            return
        if self.dcn_size > 1:
            raise ResizeUnsupported(
                "update_nworker cannot re-mesh a multi-slice (dcn) run in "
                "place; relaunch with new --dcn-slices",
                nworkers,
            )
        if jax.process_count() > 1:
            # Cross-host elastic resize needs a coordinated device subset
            # on every host plus loader re-ranking; the SUPPORTED path is
            # resize-by-relaunch — drain, then relaunch the whole group at
            # the new size under the supervisor (the structured error
            # carries the recipe; README "Multi-host runtime").
            raise ResizeUnsupported(
                "update_nworker supports single-process (multi-device) "
                "runs; a multi-host process group cannot re-mesh in place",
                nworkers,
            )
        n_devices = nworkers * self.seq_size
        avail = len(jax.devices())
        if nworkers < 1 or n_devices > avail:
            raise ValueError(
                f"update_nworker({nworkers}): need {n_devices} devices "
                f"(seq={self.seq_size}), have {avail}"
            )
        old = self.data_size
        # sharded opt state (rs_opt_ag) is laid out for the OLD (world,
        # merge schedule); gather it to the replicated interchange form
        # while the old reducer still describes it — re-scattered onto the
        # new layout after the reducer is re-solved below
        self.state = self._to_checkpoint_state(self.state)
        # advance the LR-schedule anchor to the CURRENT epoch position under
        # the OLD loader length before anything is rebuilt, so the schedule
        # continues smoothly across the resize instead of jumping when the
        # step->epoch divisor changes
        old_nbpe = max(self._steps_per_epoch(), 1)
        step_now = int(self.state.step)
        self._sched_epoch_offset += (
            step_now - self._sched_step_offset
        ) / old_nbpe
        self._sched_step_offset = step_now
        self.mesh = make_mesh(
            MeshSpec(data=nworkers, seq=self.seq_size),
            devices=jax.devices()[:n_devices],
        )
        self.data_size = nworkers
        self.ici_size = nworkers  # single-slice resize (dcn guarded above)
        self.config.nworkers = nworkers
        self.process_batch = self.config.batch_size * nworkers
        # re-replicate state onto the new mesh (the reference's post-resize
        # re-broadcast, expressed as a sharding constraint)
        from mgwfbp_tpu.parallel.mesh import replicated_sharding

        self.state = jax.device_put(self.state, replicated_sharding(self.mesh))
        self.bundle = self._build_loaders()
        # loader length changed with the process batch, so the LR schedule's
        # step->epoch conversion must be re-baked; the optax chain structure
        # is unchanged, so the existing opt_state (momentum) carries over
        self._build_optimizer()
        self.reducer = self._build_reducer(self._profile_backward_enabled)
        self._measured_group_times = None  # traced under the old schedule
        # a tuned entry for the NEW world size beats the fresh solve: the
        # autotuner measured it on a live job at exactly this key, so
        # consult the schedule cache before settling for the solver
        schedule_source = "solver"
        cached = self._cached_schedule_entry()
        if cached is not None:
            entry, path = cached
            try:
                self.reducer = self._reducer_for(
                    tuple(tuple(int(i) for i in g) for g in entry["groups"]),
                    entry["comm_op"],
                    detail=f"schedule-cache:{entry.get('winner', 'winner')}",
                    dcn_groups=tuple(
                        tuple(int(i) for i in d)
                        for d in entry.get("dcn_groups") or ()
                    ) or None,
                )
            except Exception as e:  # noqa: BLE001 — a stale/corrupt entry
                # must degrade to the solved schedule, not kill the resize
                self.log.warning(
                    "schedule cache entry %s failed to build (%s); "
                    "keeping the solved schedule", path, e,
                )
            else:
                schedule_source = "schedule-cache"
                self.log.info(
                    "update_nworker: tuned schedule loaded from %s "
                    "(%d groups, comm_op=%s)", path,
                    self.reducer.layout.num_groups, self.reducer.comm_op,
                )
        self.state = self._from_checkpoint_state(self.state)
        self._build_steps()
        # the run tag changed with nworkers: re-point log/checkpoint/event
        # sinks so post-resize output is found by a relaunch at the new size
        self._build_run_sinks()
        self._emit_event(
            "resize", old_world=int(old), new_world=int(nworkers),
            schedule_source=schedule_source if self.reducer is not None
            else "none",
            num_groups=(
                self.reducer.layout.num_groups
                if self.reducer is not None else 0
            ),
        )
        self.carry = None  # old carry is sized for the old process batch
        # step times and comm predictions both changed with the world
        # size; stale drift baselines would raise alarms that never clear
        self._reset_drift_baselines()
        self.log.info(
            "update_nworker: resized data axis %d -> %d (process batch %d%s)",
            old, nworkers, self.process_batch,
            "" if self.reducer is None
            else f", merge schedule {schedule_source}: "
                 f"{self.reducer.schedule.num_groups} groups",
        )

    # ------------------------------------------------------------------
    # Closed-loop schedule autotuning (ISSUE 3). parallel/autotune.py owns
    # the pure parts (frontier, cache, step-delta observations); these
    # methods own the live pieces — the jitted step, the train state, the
    # data stream, and the hot-swap through the elastic-resize seam.
    # ------------------------------------------------------------------

    def autotune(
        self,
        steps_per_candidate: Optional[int] = None,
        force: bool = False,
    ):
        """Close the solver's loop on the live job.

        Races verified candidate schedules for warmup + k REAL training
        steps each (state carried through — no step is paused or lost),
        refits the cost model from the measurements, re-solves once, and
        commits the measured argmin, persisting it in the schedule cache
        keyed by the schedule-cache key (authoritative field list:
        `parallel.autotune.cache_key`). A later run with the same
        key skips the race and cold-starts on the committed schedule.

        Returns the report dict (also kept as self.autotune_report), or
        None when there is nothing to tune (no merged reducer).

        ``force=True`` re-races even when a committed cache entry matches
        (the drift re-autotune path: the entry describes a model the
        detector just called stale); the new winner overwrites it. The
        flag must be group-uniform on multi-host — the drift trigger
        rides agree_any before calling, so it is.
        """
        import itertools

        from mgwfbp_tpu.parallel import autotune as at
        from mgwfbp_tpu.parallel.costmodel import refit_from_observations
        from mgwfbp_tpu.parallel.solver import build_schedule, size_prior_tb

        cfg = self.config
        if self.reducer is None:
            self.log.info(
                "autotune: nothing to tune (no merged reducer: policy %r "
                "or single device)", cfg.policy,
            )
            return None
        if jax.process_count() > 1:
            # multi-host race protocol (ISSUE 6): candidates derive from
            # broadcast-identical inputs (tb, cost model, layer specs), so
            # every process races the SAME sequence of schedules in
            # lockstep; only the WALL-CLOCK timings are per-process. Those
            # are reduced to one agreed vector (each candidate at its
            # slowest process — coordination.all_argmin) before anything
            # commits, so divergent schedules can never be installed.
            self.log.info(
                "autotune: multi-host race — per-candidate timings will "
                "be reduced to a cross-process argmin before commit"
            )
        world = self.data_size * self.seq_size
        cache_dir = cfg.schedule_cache or os.path.join(
            "profiles", "schedule_cache"
        )
        key = at.cache_key(
            cfg.dnn, world, cfg.comm_op, cfg.dtype,
            comm_dtype=cfg.comm_dtype,
            compressor=cfg.compressor, density=cfg.density,
            batch_size=cfg.batch_size, nsteps_update=cfg.nsteps_update,
            dcn_slices=self.dcn_size,
        )
        path = at.entry_path(cache_dir, key)
        entry = at.load_cache_entry(path)
        names_now = list(self.reducer.schedule.layer_names)
        cache_hit = (
            not force
            and entry is not None
            and entry.get("layer_names") == names_now
        )
        if coord.process_count() > 1:
            # the cache is filesystem state: without a shared FS one host
            # can hold the entry while another misses. A split decision is
            # a split schedule, so the hit counts only when EVERY process
            # has it; otherwise all re-race together.
            cache_hit = coord.agree_all(cache_hit)
        if cache_hit:
            groups = tuple(tuple(int(i) for i in g) for g in entry["groups"])
            entry_dcn = tuple(
                tuple(int(i) for i in d)
                for d in entry.get("dcn_groups") or ()
            ) or None
            if not self._reducer_is_live(
                groups, entry["comm_op"], entry_dcn
            ):
                self._swap_reducer(self._reducer_for(
                    groups, entry["comm_op"],
                    detail=f"autotune-cache:{entry.get('winner', 'winner')}",
                    dcn_groups=entry_dcn,
                ))
            self.log.info(
                "autotune: cache hit %s — committed schedule loaded "
                "(%d groups, comm_op=%s), race skipped",
                path, len(groups), entry["comm_op"],
            )
            mgt = entry.get("measured_group_times")
            if mgt:
                # the entry's trace-attributed group times describe the
                # schedule just installed; telemetry's overlap accounting
                # can use them instead of cost-model predictions
                self._measured_group_times = [float(t) for t in mgt]
            self._emit_event(
                "autotune_commit", winner=str(entry.get("winner")),
                comm_op=str(entry["comm_op"]), num_groups=len(groups),
                source="cache",
            )
            self.autotune_report = {
                "source": "cache", "cache_path": path,
                "comm_op": entry["comm_op"],
                "groups": [list(g) for g in groups],
                "dcn_groups": [list(d) for d in entry_dcn or ()],
                "winner": entry.get("winner"),
            }
            return self.autotune_report
        if entry is not None:
            if force:
                self.log.info(
                    "autotune: forced re-race — committed entry %s will "
                    "be overwritten by the new winner", path,
                )
            else:
                self.log.warning(
                    "autotune: cache entry %s was tuned for a different "
                    "parameter set; re-tuning", path,
                )

        # ---- frontier ------------------------------------------------
        specs = self._layer_specs()
        cost_model = getattr(self, "cost_model", None)
        tb = (
            list(self._tb_cache)
            if self._tb_cache is not None
            else size_prior_tb(specs, cost_model)
        )
        tf = list(self._tf_cache) if self._tf_cache is not None else None
        # "both comm_op lowerings where state permits": a sparsifying
        # compressor replaces the bucket collective, so only the configured
        # all_reduce path is raceable under it
        comm_ops = (
            ("all_reduce",)
            if self._compressor is not None
            # hier candidates need the (ici, dcn) mesh — and not the seq
            # axis, which the hier lowering does not compose with yet
            else at.allowed_comm_ops(
                cfg.comm_op,
                multi_slice=self.dcn_size > 1 and self.seq_axis is None,
            )
        )
        candidates = at.build_candidates(
            specs, tb, cost_model, comm_ops,
            tf=tf,
            max_candidates=max(int(cfg.autotune_candidates), 1),
            incumbent=(
                self.reducer.schedule.groups, cfg.comm_op,
                self.reducer.schedule.dcn_groups,
            ),
        )
        steps = int(
            steps_per_candidate
            if steps_per_candidate is not None
            else cfg.autotune_steps
        )
        steps = max(steps, 1)
        self.log.info(
            "autotune: racing %d candidate(s), %d timed step(s) each "
            "(cache key %s)", len(candidates), steps, key,
        )

        original = self.reducer
        batch_iter = self._autotune_batches()
        sample_batch = next(batch_iter)
        batch_iter = itertools.chain([sample_batch], batch_iter)
        # burn-in on the incumbent: the process's first real steps carry
        # one-off host-side warmup (loader pipeline, dispatch pools) that
        # would bias whichever candidate happens to race first; these are
        # still genuine training steps — nothing is discarded
        for _ in range(2):
            self.state = self._apply_train_step(self.state, next(batch_iter))
        jax.block_until_ready(self.state)
        self.iteration += 2
        self._train_step_compiled = True
        entries = []
        raced_shapes: set = set()
        for c in candidates:
            e = self._race_candidate(c, batch_iter, sample_batch, steps)
            entries.append(e)
            # record BOTH the requested shape and the issued (post-layout)
            # shape: the refit re-solve emits pre-layout groups, and on
            # dtype-mixed models the two differ — deduping on only one
            # side would re-race an already-timed schedule
            raced_shapes.add((
                c.comm_op, tuple(map(tuple, c.groups)),
                tuple(map(tuple, c.dcn_groups)),
            ))
            raced_shapes.add((
                e.comm_op, tuple(map(tuple, e.groups)),
                tuple(map(tuple, e.dcn_groups)),
            ))
        # multi-host: per-process wall clocks disagree; reduce every
        # candidate's timing to the group-agreed value (its slowest
        # process) BEFORE anything downstream reads them, so the refit
        # inputs and the argmin are identical everywhere
        self._sync_entry_times(entries)

        # ---- refit from observations + one re-solve ------------------
        refit_info = None
        measured_groups = None
        timed = [e for e in entries if e.measured_step_s is not None]
        if timed and cost_model is not None:
            best = min(timed, key=lambda e: e.measured_step_s)
            if not self._reducer_is_live(
                best.groups, best.comm_op, best.dcn_groups or None
            ):
                self._swap_reducer(self._reducer_for(
                    best.groups, best.comm_op,
                    detail=f"autotune:{best.label}",
                    dcn_groups=best.dcn_groups or None,
                ))
            total_bytes = float(sum(s.nbytes for s in specs))
            (
                obs, obs_source, measured_groups, dcn_obs,
            ) = self._group_observations(
                batch_iter, entries, total_bytes, float(sum(tb))
            )
            # the trace timed THIS schedule; remember whose groups the
            # per-group seconds belong to (the refit candidate may win
            # with a different grouping, and the cache must not pair its
            # groups with another schedule's measurements)
            traced_schedule = (
                self.reducer.comm_op,
                tuple(map(tuple, self.reducer.layout.groups)),
                tuple(map(tuple, self.reducer.schedule.dcn_groups)),
            )
            if len(obs) >= 2:
                from mgwfbp_tpu.parallel.solver import (
                    is_two_level as _is_two_level,
                )

                try:
                    if _is_two_level(cost_model):
                        # a two-level model must stay two-level: the flat
                        # refit would silently collapse the per-link
                        # constants into one line and unsolve the nested
                        # schedule. Whether TRACE observations are
                        # ICI-only depends on the lowering the trace ran
                        # over (the LIVE reducer, not the model's type):
                        # the hier lowering keeps its DCN collectives
                        # under their own mgwfbp_dcngroupNNNN scopes, so
                        # its group-scoped times are the ICI legs alone
                        # and refit the ICI link; a FLAT lowering's one
                        # scoped pmean crosses BOTH axes, so its times —
                        # like step deltas — are whole-collective and
                        # rescale both links by the common drift factor.
                        from mgwfbp_tpu.parallel.costmodel import (
                            refit_two_level_from_observations,
                        )

                        if (
                            obs_source == "trace"
                            and self.reducer.comm_op == "hier"
                        ):
                            # trace-separated legs: the group scopes refit
                            # the ICI link, and — when the dcngroup scopes
                            # attributed too — the DCN link refits from
                            # its OWN samples instead of inheriting a
                            # common drift factor (hier follow-up b)
                            new_model = refit_two_level_from_observations(
                                cost_model, [], ici_observations=obs,
                                dcn_observations=dcn_obs,
                            )
                        else:
                            new_model = refit_two_level_from_observations(
                                cost_model, obs
                            )
                    else:
                        new_model = refit_from_observations(
                            cost_model, obs, cfg.comm_op
                        )
                except ValueError as e:
                    self.log.info("autotune: refit skipped (%s)", e)
                else:
                    refit_info = {
                        "before": at.model_summary(cost_model),
                        "after": at.model_summary(new_model),
                        "source": obs_source,
                        "observations": [
                            [float(b), float(t)] for b, t in obs
                        ],
                    }
                    self.cost_model = new_model
                    resolved = build_schedule(
                        specs, tb, tf=tf, policy="auto",
                        cost_model=new_model, comm_op=cfg.comm_op,
                    )
                    shape = tuple(tuple(g) for g in resolved.groups)
                    dcn_shape = tuple(
                        tuple(d) for d in resolved.dcn_groups
                    )
                    if (cfg.comm_op, shape, dcn_shape) not in raced_shapes:
                        cand = at.Candidate(
                            label=(
                                f"{cfg.comm_op}:refit->"
                                f"{resolved.policy_detail or 'auto'}"
                            ),
                            groups=shape,
                            comm_op=cfg.comm_op,
                            predicted_total_s=float(
                                resolved.predicted_total_time
                            ),
                            dcn_groups=dcn_shape,
                        )
                        entries.append(self._race_candidate(
                            cand, batch_iter, sample_batch, steps
                        ))
        # the refit re-solve may have raced one more candidate; agree on
        # its timing too before the winner is chosen (idempotent for the
        # already-reduced entries, no-op single-process)
        self._sync_entry_times(entries)
        timed = [e for e in entries if e.measured_step_s is not None]

        # ---- commit the measured argmin + persist --------------------
        if not timed:
            self.log.warning(
                "autotune: no candidate survived verification/racing; "
                "keeping the solved schedule"
            )
            if self.reducer is not original:
                self._swap_reducer(original)
            for e in entries:
                self._emit_event("autotune_race", **e.to_json())
            self.autotune_report = {
                "source": "race", "cache_path": None,
                "race": [e.to_json() for e in entries],
            }
            return self.autotune_report
        winner = min(timed, key=lambda e: e.measured_step_s)
        if measured_groups is not None and traced_schedule != (
            winner.comm_op, tuple(map(tuple, winner.groups)),
            tuple(map(tuple, winner.dcn_groups)),
        ):
            measured_groups = None  # traced a different schedule's groups
        if not self._reducer_is_live(
            winner.groups, winner.comm_op, winner.dcn_groups or None
        ):
            self._swap_reducer(self._reducer_for(
                winner.groups, winner.comm_op,
                detail=f"autotune:{winner.label}",
                dcn_groups=winner.dcn_groups or None,
            ))
        cache_entry = {
            "key": key,
            "model": cfg.dnn,
            "world": world,
            "comm_op": winner.comm_op,
            "dtype": cfg.dtype,
            "layer_names": names_now,
            "winner": winner.label,
            "groups": [list(g) for g in winner.groups],
            # hier winners round-trip their nested DCN partition too; []
            # for flat lowerings (and old entries load as one outer
            # collective per group)
            "dcn_groups": [list(d) for d in winner.dcn_groups],
            "measured_step_s": winner.measured_step_s,
            "tb_source": (
                getattr(self._tb_cache, "source", "volume-prior")
                if self._tb_cache is not None
                else "size-prior"
            ),
            "race": [e.to_json() for e in entries],
            "refit": refit_info,
            "solved_group_times": [
                [int(b), float(t)]
                for b, t in self.reducer.schedule.predicted_group_times
            ],
            "measured_group_times": measured_groups,
        }
        if coord.is_primary():  # graft: noqa[RUN004] -- the schedule cache is best-effort persistence: a miss simply re-races, and cache hits require agree_all on every process
            # one writer: the cache file is shared state (and on a shared
            # FS two processes racing the rename could tear it)
            at.save_cache_entry(path, cache_entry)
        # trace-attributed group times (when the backend supplied any)
        # describe the NOW-LIVE winner; hand them to the overlap accounting
        self._measured_group_times = (
            [float(t) for t in measured_groups]
            if measured_groups is not None
            else None
        )
        # race rows land in the stream too, so tools/autotune_report.py and
        # tools/telemetry_report.py tell the same story
        for e in entries:
            self._emit_event("autotune_race", **e.to_json())
        self._emit_event(
            "autotune_commit", winner=winner.label,
            comm_op=winner.comm_op, num_groups=len(winner.groups),
            source="race",
        )
        self.log.info(
            "autotune: committed %s (%d groups, comm_op=%s, %.4g s/step) "
            "-> %s", winner.label, len(winner.groups), winner.comm_op,
            winner.measured_step_s, path,
        )
        self.autotune_report = {
            "source": "race",
            "cache_path": path,
            **{
                k: cache_entry[k]
                for k in (
                    "winner", "groups", "dcn_groups", "comm_op",
                    "measured_step_s", "race", "refit",
                )
            },
        }
        return self.autotune_report

    def _sync_entry_times(self, entries) -> None:
        """Multi-host: replace each race entry's measured step time with
        the group-agreed value — the MAX across processes (a synchronous
        group runs at its straggler's pace), with unmeasured-anywhere
        reducing to None — so every process's `min(timed)` argmin, refit
        observations, and cache entry are bitwise identical. No-op
        single-process and on an empty race."""
        if coord.process_count() == 1 or not entries:
            return
        idx, reduced = coord.all_argmin(
            [e.measured_step_s for e in entries]
        )
        for e, t in zip(entries, reduced):
            e.measured_step_s = float(t) if np.isfinite(t) else None
        self.log.info(
            "autotune: cross-process argmin -> candidate %d (%s)",
            idx, entries[idx].label,
        )

    def _reducer_for(
        self, groups, comm_op: str, detail: str = "", dcn_groups=None,
    ):
        """A MergedAllreduce for an EXPLICIT grouping (autotune candidates,
        cache hits), sharing the live cost model / tb / axes / compressor
        wiring with `_build_reducer`. For comm_op='hier', `dcn_groups` is
        the candidate's nested DCN partition (None = one outer collective
        per group)."""
        cfg = self.config
        axes = self.data_axes
        if self.seq_axis is not None:
            axes = axes + (self.seq_axis,)
        comm_dtype = jnp.dtype(cfg.comm_dtype) if cfg.comm_dtype else None
        return make_merged_allreduce(
            self._params_template,
            axis_name=axes,
            policy="auto",  # only sets the tb fallback; `groups` wins
            groups=groups,
            dcn_groups=dcn_groups if comm_op == "hier" else None,
            policy_detail=detail,
            tb=self._tb_cache,
            tf=self._tf_cache,
            cost_model=getattr(self, "cost_model", None),
            comm_dtype=comm_dtype,
            compressor=self._compressor,
            comm_op=comm_op,
            optim_spec=(
                self.optim_spec
                if comm_op in ("rs_opt_ag", "rs_fwd_ag")
                else None
            ),
            world_size=self.data_size * self.seq_size,
        )

    def _reducer_is_live(self, groups, comm_op: str, dcn_groups=None) -> bool:
        """True when the live reducer already issues exactly this schedule
        — skipping the rebuild avoids the tuning phase's dominant cost (a
        fresh XLA compile) plus a sharded opt-state round trip. A hier
        candidate must also match the live NESTED (DCN) partition: same
        inner groups under a different outer merge is a different
        program."""
        live = self.reducer
        shape = tuple(tuple(int(i) for i in g) for g in groups)
        if comm_op != live.comm_op or shape not in (
            tuple(map(tuple, live.layout.groups)),
            tuple(map(tuple, live.schedule.groups)),
        ):
            return False
        if comm_op == "hier" and dcn_groups is not None:
            want = tuple(tuple(int(i) for i in d) for d in dcn_groups)
            from mgwfbp_tpu.parallel.solver import singleton_dcn_groups

            live_dcn = live.schedule.dcn_groups or tuple(
                tuple(d) for d in singleton_dcn_groups(len(shape))
            )
            if want != live_dcn:
                return False
        return True

    def _swap_reducer(self, reducer) -> None:
        """Hot-swap the live merge schedule mid-run — the elastic-resize
        re-solve seam (`update_nworker`) without the resize: gather any
        sharded opt state to the replicated interchange form while the OLD
        reducer still describes its layout, install the new reducer,
        re-scatter onto its layout, rebuild the jitted steps.

        Transactional: if installing the NEW reducer fails (e.g. its
        scatter OOMs), the old reducer is restored and the opt state
        re-scattered under its layout before the error propagates — a
        half-installed swap would corrupt every later gather."""
        old = self.reducer
        self.state = self._to_interchange_state(self.state)
        self._measured_group_times = None  # traced under the old schedule
        self.reducer = reducer
        scattered = False
        try:
            self.state = self._from_interchange_state(self.state)
            scattered = True
            self._build_steps()
        except Exception:
            if scattered:
                # the new layout's scatter succeeded before the failure;
                # gather back to the interchange form under the NEW
                # reducer before the old one re-scatters it
                self.state = self._to_interchange_state(self.state)
            self.reducer = old
            self.state = self._from_interchange_state(self.state)
            self._build_steps()
            raise
        self._sync_schedule_gauge()
        # the detector's baselines described the OLD schedule's regime
        self._reset_drift_baselines()

    def _apply_train_step(self, state, batch):
        """One live train step (autotune race path), carry-aware."""
        if self.meta.has_carry:
            if self.carry is None:
                self.carry = self._globalize(
                    self.model.initial_carry(self.process_batch), axes=0
                )
            state, _, self.carry = self.train_step(state, batch, self.carry)
        else:
            state, _ = self.train_step(state, batch)
        return state

    def _autotune_batches(self):
        """Endless stream of stacked train batches for the tuning phase —
        real data, exactly what train_epoch would feed (every raced step is
        a genuine training step). The shuffle epoch starts in a reserved
        range far above any training epoch: the tuning steps must be EXTRA
        passes over the data, not a replay of epoch 0's exact batch
        sequence (train_epoch(0) re-seeds set_epoch(0) afterwards and
        would otherwise double-step the same minibatches)."""
        def gen():
            epoch = 1 << 20  # reserved shuffle-seed range for tuning
            nsteps = self.config.nsteps_update
            while True:
                self.bundle.train.set_epoch(epoch)
                micro: list[dict] = []
                for raw in self.bundle.train:
                    micro.append(self._to_model_batch(raw))
                    if len(micro) == nsteps:
                        yield self._stack_micro(micro)
                        micro = []
                epoch += 1

        return gen()

    def _verify_live_step(self, sample_batch) -> list:
        """Trace the LIVE jitted step abstractly and run the jaxpr
        schedule verifier (analysis.jaxpr_check, SCH001..SCH007) against
        the live reducer — the gate every autotune candidate must pass
        before it may race a single real step."""
        from mgwfbp_tpu.analysis.jaxpr_check import (
            verify_jaxpr_against_reducer,
        )

        args = [self.state, sample_batch]
        if self.meta.has_carry:
            if self.carry is None:
                self.carry = self._globalize(
                    self.model.initial_carry(self.process_batch), axes=0
                )
            args.append(self.carry)
        closed = jax.make_jaxpr(self.train_step)(*args)
        leaves = jax.tree_util.tree_leaves(self._params_template)
        arr = [leaves[j] for j in self.reducer.perm]
        tag = self.reducer.schedule.policy_detail or self.config.policy
        return verify_jaxpr_against_reducer(
            closed, self.reducer, arr, expect_donation=True,
            expect_finite_guard=self.config.grad_guard,
            file=f"<live step {tag}>",
        )

    def _race_candidate(self, cand, batch_iter, sample_batch, steps: int):
        """Verify one candidate, then give it warmup + `steps` real
        training steps on the live job and record the measured step time.
        Candidates the verifier rejects never run a step."""
        from mgwfbp_tpu.analysis.rules import ERROR
        from mgwfbp_tpu.parallel import autotune as at
        from mgwfbp_tpu.profiling import time_carried_steps

        pred = float(cand.predicted_total_s)
        entry = at.RaceEntry(
            label=cand.label,
            comm_op=cand.comm_op,
            num_groups=len(cand.groups),
            predicted_total_s=None if pred != pred else pred,
            groups=cand.groups,
        )
        is_live = self._reducer_is_live(
            cand.groups, cand.comm_op, cand.dcn_groups or None
        )
        if is_live:
            # the incumbent is already installed, burned in, and compiled —
            # rebuilding it would waste the tuning phase's dominant cost
            # (one XLA compile) plus a sharded-opt-state round trip
            reducer = self.reducer
        else:
            try:
                reducer = self._reducer_for(
                    cand.groups, cand.comm_op,
                    detail=f"autotune:{cand.label}",
                    dcn_groups=cand.dcn_groups or None,
                )
            except Exception as e:  # noqa: BLE001 — a bad candidate must
                # not take down the tuning phase; recorded and skipped
                self.log.warning(
                    "autotune: candidate %s failed to build: %s",
                    cand.label, e,
                )
                return entry
        # build_layout may split dtype-mixed groups; race what is issued
        entry.groups = reducer.layout.groups
        entry.num_groups = reducer.layout.num_groups
        entry.dcn_groups = reducer.schedule.dcn_groups
        wd = getattr(self, "_watchdog", None)
        if wd is not None:
            from mgwfbp_tpu.utils.watchdog import COMPILE_ALLOW_S

            wd.beat(f"autotune candidate {cand.label}",
                    allow_s=COMPILE_ALLOW_S)
        try:
            if not is_live:
                self._swap_reducer(reducer)
            findings = self._verify_live_step(sample_batch)
        except Exception as e:  # noqa: BLE001 — same contract as above
            self.log.warning(
                "autotune: candidate %s failed to swap/trace: %s",
                cand.label, e,
            )
            return entry
        errors = [f for f in findings if f.severity == ERROR]
        if errors:
            self.log.warning(
                "autotune: candidate %s REJECTED by the schedule verifier "
                "(%s)", cand.label,
                "; ".join(f"{f.rule_id}: {f.message}" for f in errors[:3]),
            )
            return entry
        entry.verified = True

        def step_once(state):
            return self._apply_train_step(state, next(batch_iter))

        try:
            self.state, dt = time_carried_steps(
                step_once, self.state, steps, warmup=1
            )
        except Exception as e:  # noqa: BLE001 — a candidate that cannot
            # execute (e.g. its compile or first dispatch fails) is
            # skipped, not fatal: the job trains fine without it
            deleted = any(
                getattr(l, "is_deleted", lambda: False)()
                for l in jax.tree_util.tree_leaves(self.state)
            )
            if deleted:
                # the failing step already consumed the DONATED state
                # buffers: there is nothing to continue training from, so
                # skipping would only defer a confusing 'Array has been
                # deleted' crash — fail here with the real cause attached
                raise RuntimeError(
                    f"autotune: candidate {cand.label} failed mid-step "
                    "after consuming the donated train state; cannot "
                    "continue this run"
                ) from e
            self.log.warning(
                "autotune: candidate %s failed during its timed steps "
                "(%s); skipping", cand.label, e,
            )
            return entry
        self._train_step_compiled = True
        self.iteration += steps + 1
        entry.measured_step_s = float(dt)
        self.log.info(
            "autotune: %s — %d group(s), measured %.4g s/step"
            "%s", cand.label, entry.num_groups, dt,
            (
                f" (predicted {entry.predicted_total_s:.4g})"
                if entry.predicted_total_s
                else ""
            ),
        )
        return entry

    def _group_observations(
        self, batch_iter, entries, total_bytes: float, tb_total: float
    ):
        """(observations, source, measured_group_times, dcn_observations)
        for the cost-model refit. Primary path: a profiler trace of a
        couple more live steps, attributing wall-clock to each
        `mgwfbp_groupNNNN` scope (profiling.trace_group_times — real TPU
        traces keep the scope in op metadata); on the hier lowering the
        SAME trace additionally attributes the `mgwfbp_dcngroupNNNN`
        scopes, so the DCN leg's (bytes, seconds) samples come back
        separated and a drifted DCN link refits ALONE
        (costmodel.refit_two_level_from_observations' dcn_observations —
        ROADMAP hier follow-up b). Fallback: step-time deltas across the
        raced schedules (autotune.step_delta_observations — the CPU-mesh
        regime, where traces drop the name stack; dcn_observations is
        then None and the refit falls back to the common drift factor)."""
        from mgwfbp_tpu.parallel import autotune as at
        from mgwfbp_tpu.profiling import trace_group_times

        num_groups = self.reducer.layout.num_groups
        iters = 2

        def run():
            for _ in range(iters):
                self.state = self._apply_train_step(
                    self.state, next(batch_iter)
                )
            jax.block_until_ready(self.state)

        measured = None
        dcn_measured = None
        hier = self.reducer.comm_op == "hier"
        # one derivation for BOTH the traced DCN-group count and the byte
        # attribution below — same singleton fallback as the hier lowering
        dcn_part = (
            [list(d) for d in self.reducer.schedule.dcn_groups]
            or [[gi] for gi in range(num_groups)]
        ) if hier else []
        if coord.process_count() > 1:
            # per-process profiler traces diverge (attribution is
            # backend/host dependent), and a divergent refit means a
            # divergent re-solve -> mismatched collectives. The step-delta
            # fallback reads the group-AGREED entry times instead, so the
            # refit is identical everywhere by construction.
            self.log.info(
                "autotune: multi-host — trace attribution skipped, "
                "refitting from agreed step deltas"
            )
        else:
            try:
                if hier:
                    from mgwfbp_tpu.profiling import (
                        trace_two_level_group_times,
                    )

                    measured, dcn_measured = trace_two_level_group_times(
                        run, num_groups, len(dcn_part), iters=iters,
                    )
                else:
                    measured = trace_group_times(
                        run, num_groups, iters=iters
                    )
                self.iteration += iters
            except Exception as e:  # noqa: BLE001 — profiling must never
                # kill the tuning phase; the step-delta fallback applies
                self.log.info(
                    "autotune: group trace failed (%s); using step deltas",
                    e,
                )
        dcn_obs = None
        if hier and dcn_measured is not None:
            from mgwfbp_tpu.profiling import dcn_shard_nbytes

            dcn_bytes = dcn_shard_nbytes(
                self.reducer.layout, dcn_part, self.ici_size,
                getattr(self.reducer, "comm_dtype", None),
            )
            dcn_obs = list(zip(dcn_bytes, dcn_measured))
            self.log.info(
                "autotune: trace separated %d DCN leg time(s) — the DCN "
                "link refits from its own observations", len(dcn_obs),
            )
        if measured is not None and num_groups >= 2:
            layout = self.reducer.layout
            nbytes = [
                int(layout.group_sizes[gi])
                * np.dtype(layout.dtypes[gi]).itemsize
                for gi in range(num_groups)
            ]
            return list(zip(nbytes, measured)), "trace", measured, dcn_obs
        # a single-group schedule yields one trace observation — not enough
        # for a 2-parameter fit; the raced entries span several group
        # counts, so fall through to the step-delta pseudo-observations
        # (measured per-group times, when any, still ride to the cache)
        if self._tb_cache is None:
            # step deltas subtract the backward-compute total from each
            # measured step; the size-prior tb is a COMM prediction (the
            # time to all-reduce the model once), not compute — subtracting
            # it would bias the refit. Trace observations don't need tb,
            # so only this fallback is gated on a measured profile.
            self.log.info(
                "autotune: refit skipped — step-delta observations need a "
                "measured backward profile (run without "
                "--no-profile-backward)"
            )
            return [], "step-deltas", measured, dcn_obs
        return (
            at.step_delta_observations(entries, total_bytes, tb_total),
            "step-deltas",
            measured,
            dcn_obs,
        )

    def _apply_lm_window(self) -> None:
        """Windowed-LM length override (--num-steps): retarget the model's
        position table and the meta the batches are built from."""
        config = self.config
        if not (
            config.num_steps
            and self.meta.task == "lm"
            and not self.meta.has_carry
        ):
            return
        import dataclasses as _dc

        self.meta = _dc.replace(self.meta, input_shape=(config.num_steps,))
        if hasattr(self.model, "max_len"):
            self.model = self.model.clone(
                max_len=max(self.model.max_len, config.num_steps)
            )

    def _example_input(self) -> Any:
        meta = self.meta
        shape = (1,) + tuple(meta.input_shape)
        if meta.task == "ctc":
            return jnp.zeros(shape, jnp.float32)
        return jnp.zeros(shape, meta.input_dtype)

    def _build_reducer(self, profile_backward: bool):
        cfg = self.config
        self._compressor = None  # set below; reused by autotune candidates
        if cfg.comm_op == "hier" and (
            self.dcn_size <= 1 or self.seq_axis is not None
        ):
            # fail fast: this needs only config + mesh shape, so don't burn
            # the offline backward benchmark on a config error
            raise ValueError(
                "--comm-op hier needs a multi-slice mesh "
                "(--dcn-slices > 1) and no sequence parallelism; "
                f"got dcn={self.dcn_size}, seq={self.seq_size}"
            )
        if cfg.policy in ("none", "xla"):
            if cfg.comm_op in ("rs_opt_ag", "rs_fwd_ag"):
                # the sharded optimizer NEEDS the bucketed lowering (it
                # runs inside the per-group RS/AG seam); silently falling
                # back to replicated updates would misreport memory wins
                raise ValueError(
                    f"--comm-op {cfg.comm_op} requires a merge policy "
                    "(mgwfbp/auto/threshold/single/wfbp); policy "
                    f"{cfg.policy!r} issues no bucket collectives"
                )
            # the ORIGINAL_HOROVOD-style oracle: one pmean per grad leaf
            # fused at XLA's discretion (reference settings.py:34 A/B switch)
            return None
        if self.data_size * self.seq_size == 1:
            # single device: no communication exists to schedule — the
            # reference's single-process path runs WITHOUT the distributed
            # optimizer (dl_trainer.py train_with_single, :956-984); a
            # merge schedule here would only add no-op collective dispatch
            # (rs_opt_ag falls back to the replicated optimizer too: with
            # world == 1 a "shard" IS the full state, nothing is saved)
            self.log.info(
                "single device: skipping merged-allreduce scheduling "
                "(policy %s inert, reference single-path parity)", cfg.policy,
            )
            return None
        if cfg.comm_op in ("rs_opt_ag", "rs_fwd_ag") and cfg.compressor not in (
            None, "", "none"
        ):
            raise ValueError(
                f"--comm-op {cfg.comm_op} cannot combine with --compressor "
                "(the shard update needs the dense reduction)"
            )
        if cfg.comm_profile:
            from mgwfbp_tpu.parallel.costmodel import resolve_profile

            # family profiles (P-sweep calibrations) pin to this run's
            # data-parallel extent; flat/two-level load as-is
            cost_model = resolve_profile(
                load_profile(cfg.comm_profile), self.data_size
            )
            from mgwfbp_tpu.parallel.costmodel import TwoLevelAlphaBeta as _TL

            if self.dcn_size > 1 and not isinstance(cost_model, _TL):
                # ADVICE r3: a flat single-slice calibration silently
                # mispricing the ICI+DCN hierarchy skews the merge solve
                self.log.warning(
                    "--comm-profile %s is a FLAT alpha-beta model but the "
                    "mesh is multi-slice (dcn=%d): the profile prices the "
                    "DCN hop as ICI. Calibrate a two-level profile (kind="
                    "'two_level') for trustworthy merge schedules.",
                    cfg.comm_profile, self.dcn_size,
                )
        elif self.dcn_size > 1:
            # multi-slice: two-level model — ICI within a slice, DCN across
            from mgwfbp_tpu.parallel.costmodel import TwoLevelAlphaBeta

            cost_model = TwoLevelAlphaBeta(
                ici=lookup_alpha_beta("ici", self.ici_size),
                dcn=lookup_alpha_beta("dcn", self.dcn_size),
                ici_size=self.ici_size,
                dcn_size=self.dcn_size,
            )
        else:
            cost_model = lookup_alpha_beta(cfg.connection, self.data_size)
        self.cost_model = cost_model  # introspection (logs, tests)
        tb = None
        tf = None
        if cfg.policy in ("mgwfbp", "auto") and profile_backward:
            if self._tb_cache is None:
                with self._setup_span("profile_backward"):
                    self._tb_cache = self._profile_backward()
            # tb is per-device backward time at the per-device batch, which
            # weak scaling holds constant — reusable across worker resizes
            tb = self._tb_cache
            if cfg.comm_op == "rs_fwd_ag":
                # the cross-step simulate prices deferred all-gathers
                # against the FORWARD timeline; only this comm_op ever
                # consumes it — allowed_comm_ops adds rs_fwd_ag candidates
                # to a race only when it IS the configured lowering, so
                # other runs must not pay the extra benchmark (falls back
                # to solver.forward_prior_tf when the benchmark fails)
                if self._tf_cache is None:
                    with self._setup_span("profile_forward"):
                        self._tf_cache = self._profile_forward()
                tf = self._tf_cache
        comm_dtype = (
            jnp.dtype(cfg.comm_dtype) if cfg.comm_dtype else None
        )
        from mgwfbp_tpu.parallel.compression import make_compressor

        density = cfg.density
        if cfg.compressor not in (None, "", "none") and density <= 0:
            # --density 0 = auto: model-driven chooser (the reference's
            # predict_density_with_size_and_computation is hardwired to
            # 0.001, utils.py:119-149; ours prices topk + sparse allgather
            # against the dense all-reduce with the active cost model)
            from mgwfbp_tpu.parallel.costmodel import choose_density

            n_elems = sum(
                int(np.prod(v.shape)) if v.shape else 1
                for v in jax.tree_util.tree_leaves(self._params_template)
            )
            density = choose_density(
                n_elems, self.data_size * self.seq_size, cost_model
            )
            self.log.info(
                "auto density: %g for %d params over %d workers",
                density, n_elems, self.data_size * self.seq_size,
            )
            if density >= 1.0:
                # the model says dense wins: drop the compressor entirely
                self.log.info(
                    "auto density: dense all-reduce predicted cheaper than "
                    "top-k + allgather on this link; compression disabled"
                )
                density = 1.0
                cfg = dataclasses.replace(cfg, compressor="none")
        compressor = make_compressor(cfg.compressor, density)
        self._compressor = compressor
        if compressor is not None:
            self.log.info(
                "gradient compression: %s density=%g",
                cfg.compressor, density,
            )
        # with sequence parallelism every (data, seq) member computes a
        # partial gradient; the merged buckets reduce over ALL those axes
        # (and over dcn on a multi-slice mesh)
        axes = self.data_axes
        if self.seq_axis is not None:
            axes = axes + (self.seq_axis,)
        with self._setup_span("solve"):
            return make_merged_allreduce(
                self._params_template,
                axis_name=axes,
                policy=cfg.policy,
                tb=tb,
                tf=tf,
                cost_model=cost_model,
                threshold=cfg.threshold,
                comm_dtype=comm_dtype,
                compressor=compressor,
                comm_op=cfg.comm_op,
                optim_spec=(
                    self.optim_spec
                    if cfg.comm_op in ("rs_opt_ag", "rs_fwd_ag")
                    else None
                ),
                world_size=self.data_size * self.seq_size,
            )

    def _profile_backward(self) -> Optional[list[float]]:
        """Offline layer-wise backward benchmark (reference benchmark(trainer),
        dist_trainer.py:44-51). Measured wall-clock differs per process, so
        like the reference's mpi4py bcast the times are broadcast from
        process 0 — every process MUST derive the identical merge schedule or
        the per-host XLA programs get mismatched collectives."""
        from mgwfbp_tpu.parallel.allreduce import arrival_order

        try:
            batch = self._peek_batch()
        except StopIteration:
            return None
        # benchmark at the PER-DEVICE batch the sharded step will see;
        # timing the whole per-process batch on one device would inflate tb
        # by the local device count and under-merge the schedule
        per_device = max(self.config.batch_size, 1)
        batch = {k: v[:per_device] for k, v in batch.items()}
        if self.seq_axis is not None:
            # same inflation on the TIME dim: each seq member's backward
            # covers T / seq_size tokens, so benchmark that slice
            batch = {
                k: (v[:, : v.shape[1] // self.seq_size] if v.ndim >= 2 else v)
                for k, v in batch.items()
            }
        paths = jax.tree_util.tree_flatten_with_path(self.state.params)[0]
        names = [jax.tree_util.keystr(kp) for kp, _ in paths]
        perm = arrival_order(len(names), names=names)
        tb = benchmark_trainer_backward(
            self.model, self.meta, self.state.params, self.state.batch_stats,
            batch, perm, warmup=2, iters=10, names=names,
            compute_dtype=self.compute_dtype,
        )
        self._persist_tb(tb, names, perm)
        source = getattr(tb, "source", "volume-prior")
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils
            from mgwfbp_tpu.profiling import TbProfile

            tb_arr = multihost_utils.broadcast_one_to_all(
                np.asarray(tb, np.float64)
            )
            tb = TbProfile((float(t) for t in tb_arr), source=source)
        self.log.info(
            "backward benchmark: %.3g s total over %d tensors, "
            "per-layer source=%s", sum(tb), len(tb), source,
        )
        return tb

    def _profile_forward(self) -> Optional[list[float]]:
        """Layer-wise FORWARD benchmark (the backward benchmark's twin):
        arrival-ordered per-layer forward seconds, feeding the cross-step
        solver's AG-before-first-use deadlines. Broadcast from process 0
        like tb, for the same schedule-divergence reason."""
        from mgwfbp_tpu.parallel.allreduce import arrival_order
        from mgwfbp_tpu.profiling import benchmark_trainer_forward

        try:
            batch = self._peek_batch()
        except StopIteration:
            return None
        per_device = max(self.config.batch_size, 1)
        batch = {k: v[:per_device] for k, v in batch.items()}
        if self.seq_axis is not None:
            batch = {
                k: (v[:, : v.shape[1] // self.seq_size] if v.ndim >= 2 else v)
                for k, v in batch.items()
            }
        paths = jax.tree_util.tree_flatten_with_path(self._params_template)[0]
        names = [jax.tree_util.keystr(kp) for kp, _ in paths]
        perm = arrival_order(len(names), names=names)
        params = self.state.params
        from mgwfbp_tpu.parallel.allreduce import ShardedParams

        if isinstance(params, ShardedParams):
            # the benchmark forwards the canonical tree on ONE device
            params = self._gathered_params(params)
        try:
            tf = benchmark_trainer_forward(
                self.model, self.meta, params, self.state.batch_stats,
                batch, perm, warmup=2, iters=10, names=names,
                compute_dtype=self.compute_dtype,
            )
        except Exception as e:  # noqa: BLE001 — the forward profile is an
            # input to a cost MODEL; the solver's tf prior (tb/2) is the
            # documented fallback, not a crash
            self.log.warning(
                "forward benchmark failed (%s); rs_fwd_ag schedules fall "
                "back to the tb/2 forward prior", e,
            )
            return None
        source = getattr(tf, "source", "volume-prior")
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils
            from mgwfbp_tpu.profiling import TbProfile

            tf_arr = multihost_utils.broadcast_one_to_all(
                np.asarray(tf, np.float64)
            )
            tf = TbProfile((float(t) for t in tf_arr), source=source)
        self._persist_tb(
            self._tb_cache if self._tb_cache is not None else [],
            names, perm, tf=tf,
        )
        self.log.info(
            "forward benchmark: %.3g s total over %d tensors, "
            "per-layer source=%s", sum(tf), len(tf), source,
        )
        return tf

    def _persist_tb(self, tb, names, perm, tf=None) -> None:
        """Persist the measured layer-wise backward (and, when measured,
        forward) profile next to the run's logs (the comm profile's
        sibling — reference persists nothing, but its measured
        layerwise_times are the solver's primary input,
        dist_trainer.py:44-51, so ours are auditable on disk). Stamped
        schema_version=2 (tf_s added); `profiling.load_layer_profile`
        migrates unstamped v1 files."""
        if not self.config.logdir:
            return
        import json

        from mgwfbp_tpu.profiling import LAYER_PROFILE_SCHEMA_VERSION

        path = os.path.join(
            self.config.logdir, self.config.tag(), "tb_profile.json"
        )
        os.makedirs(os.path.dirname(path), exist_ok=True)
        doc = {
            "schema_version": LAYER_PROFILE_SCHEMA_VERSION,
            "tb_s": list(tb),
            "arrival_names": [names[j] for j in perm],
            "total_s": sum(tb),
            # which path produced the numbers: 'trace' (profiler
            # attribution) or 'volume-prior' (numel-weight split)
            "source": getattr(tb, "source", "volume-prior"),
        }
        if tf is not None:
            doc["tf_s"] = list(tf)
            doc["tf_total_s"] = sum(tf)
            doc["tf_source"] = getattr(tf, "source", "volume-prior")
        with open(path, "w") as f:
            json.dump(doc, f)

    def _peek_batch(self) -> dict:
        self.bundle.train.set_epoch(0)
        it = iter(self.bundle.train)
        raw = next(it)
        return self._to_model_batch(raw)

    def _to_model_batch(self, raw) -> dict:
        if isinstance(raw, dict):
            return {k: jnp.asarray(v) for k, v in raw.items()}
        x, y = raw
        return {"x": jnp.asarray(x), "y": jnp.asarray(y)}

    def _to_host_batch(self, raw) -> dict:
        """Batch dict as HOST numpy arrays (for pre-device-put padding)."""
        if isinstance(raw, dict):
            return {k: np.asarray(v) for k, v in raw.items()}
        x, y = raw
        return {"x": np.asarray(x), "y": np.asarray(y)}

    def _stack_micro(self, batches: list[dict]) -> dict:
        """Stack nsteps_update micro-batches on a leading scan axis, then
        (multi-host) assemble the per-process shards into global arrays."""
        stacked = {k: jnp.stack([b[k] for b in batches]) for k in batches[0]}
        return self._globalize(stacked, axes=1)

    def _globalize(self, tree, axes: int):
        """Place a per-process batch on the mesh, split over the data axis
        (dim `axes`). Multi-host: per-process loader slices are the LOCAL
        shards of one global batch, assembled into jax global arrays.
        Single process, several devices: the local batch IS the global
        one; it is put on the mesh here, or it would sit whole on the
        first device until the jitted step split it. One device: identity."""
        if self.mesh.devices.size == 1:
            return tree
        from jax.sharding import NamedSharding, PartitionSpec

        def put(a):
            spec = [None] * a.ndim
            spec[axes] = self.data_axes  # str, or (data, dcn) multi-slice
            sharding = NamedSharding(self.mesh, PartitionSpec(*spec))
            if jax.process_count() == 1:
                return jax.device_put(a, sharding)
            return jax.make_array_from_process_local_data(
                sharding, np.asarray(a)
            )

        return jax.tree_util.tree_map(put, tree)

    # ------------------------------------------------------------------
    def train_epoch(self, epoch: int) -> dict:
        """One epoch of the train loop. With telemetry on, every part of an
        iteration is a span on the stream's clock and in the profiler's
        trace (telemetry/phases.py); with it off no clock is read."""
        if self.telemetry is None:
            return self._run_epoch(epoch, None)
        rec = self._phase_rec = PhaseRecorder(
            self.telemetry.now, functools.partial(self._emit_event, "step"),
        )
        try:
            return self._run_epoch(epoch, rec)
        finally:
            # a rollback or a drain that unwinds the loop still leaves the
            # last dispatched step's record behind
            self._phase_rec = None
            rec.flush()
            if self._setup is not None and self._setup.first_step is not None:
                # the epoch read every result it had (or unwound)
                self._write_setup()

    def _run_epoch(self, epoch: int, rec: Optional[PhaseRecorder]) -> dict:
        entered_s = rec.now() if rec is not None else 0.0
        span = rec.span if rec is not None else no_span
        cfg = self.config
        loader = self.bundle.train
        loader.set_epoch(epoch)
        nsteps = cfg.nsteps_update
        micro: list[dict] = []
        t_epoch = time.time()
        t_window = time.time()
        window_iters = 0
        epoch_steps = 0
        max_steps = (
            cfg.num_batches_per_epoch if cfg.num_batches_per_epoch else None
        )
        # each metrics log pulls device scalars to the host and so drains
        # the dispatch pipeline; long runs raise the interval
        log_interval = int(os.environ.get("MGWFBP_LOG_INTERVAL", "10"))
        metrics: dict = {}
        # mid-epoch resume (preemption / rollback): (epoch, epoch_step)
        # fully names the deterministic loader's position, so skipping the
        # first epoch_step * nsteps_update micro-batches replays the run
        # bit-for-bit from the checkpointed step
        skip_micro = 0
        epoch_pos = 0  # optimizer-step position within the epoch
        resume_carry = None
        if self._resume_epoch is not None and epoch == self._resume_epoch:
            skip_micro = self._resume_skip_steps * nsteps
            epoch_pos = self._resume_skip_steps
            resume_carry = self._resume_carry
            self.log.info(
                "epoch %d: resuming mid-epoch at step %d (skipping %d "
                "micro-batch(es))", epoch, epoch_pos, skip_micro,
            )
        self._resume_epoch = None
        self._resume_skip_steps = 0
        self._resume_carry = None
        if self.meta.has_carry:
            # fresh hidden state each epoch (reference init_hidden per
            # epoch) — unless a mid-epoch checkpoint carried one
            self.carry = self._globalize(
                resume_carry
                if resume_carry is not None
                else self.model.initial_carry(self.process_batch),
                axes=0,
            )
        wd = getattr(self, "_watchdog", None)
        wd_phase = f"train epoch {epoch}"
        # straggler probe: LOCAL busy window — loader fetch/convert,
        # batch assembly, injected stalls; anchored here and re-anchored
        # at the END of each step body so the accumulation below covers
        # everything up to the dispatch but nothing after it — the
        # dispatch (and the guard reads / agreements behind it) can block
        # inside the group's collectives waiting for the slowest peer,
        # and sync SGD equalizes exactly the signal a straggler probe
        # must not average away
        t_anchor = time.perf_counter()
        for raw in (
            loader if rec is None else rec.batches(loader, entered_s)
        ):
            if skip_micro > 0:
                skip_micro -= 1
                continue
            with span("place"):
                micro.append(self._to_model_batch(raw))
                if len(micro) == nsteps:
                    batch = self._stack_micro(micro)
            if len(micro) < nsteps:
                continue
            micro = []
            stall_s = self._faults.stall_secs("train", self.iteration + 1)
            if stall_s > 0:
                self.log.warning(
                    "fault injection: stalling %.3g s before step %d",
                    stall_s, self.iteration + 1,
                )
                time.sleep(stall_s)
            wedge_s = self._faults.wedge_secs(self.iteration + 1)
            if wedge_s > 0:
                self._wedge(wedge_s)
            if self._faults.nan_at(self.iteration + 1):
                batch, poisoned = _poison_batch(batch)
                if poisoned:
                    self.log.warning(
                        "fault injection: NaN batch for step %d",
                        self.iteration + 1,
                    )
                else:
                    self.log.warning(
                        "fault injection: nan@step=%d requested but the "
                        "batch has no floating leaves to poison",
                        self.iteration + 1,
                    )
            if wd is not None and not self._train_step_compiled:
                # the first dispatch traces+compiles the step program — a
                # legitimately long silent phase the per-step timeout must
                # not hard-exit (ADVICE r4 #3)
                from mgwfbp_tpu.utils.watchdog import COMPILE_ALLOW_S

                wd.beat(f"compile train step (epoch {epoch})",
                        allow_s=COMPILE_ALLOW_S)
            self._local_busy_s += time.perf_counter() - t_anchor
            # a fresh step program's arguments, described before the
            # dispatch donates them (_note_first_dispatch keeps them for
            # whoever reads the compiled program this dispatch builds)
            step_args = (
                None if self._traced_programs_noted
                else _describe_args(
                    (self.state, batch, self.carry) if self.meta.has_carry
                    else (self.state, batch)
                )
            )
            # step span: host wall-clock around the ASYNC dispatch, taken
            # outside jit — no block_until_ready, no device_get (the span
            # itself syncs nothing; once the dispatch pipeline fills, span
            # cadence equals realized step throughput)
            with (
                jax.profiler.StepTraceAnnotation(
                    "train", step_num=self.iteration + 1
                ) if rec is not None else NO_SPAN
            ):
                span0 = rec.now() if rec is not None else 0.0
                if self.meta.has_carry:
                    # graft: group-uniform -- step outputs are SPMD-replicated; metrics ride the global psum
                    self.state, metrics, self.carry = self.train_step(
                        self.state, batch, self.carry
                    )
                else:
                    # graft: group-uniform -- step outputs are SPMD-replicated; metrics ride the global psum
                    self.state, metrics = self.train_step(self.state, batch)
            self._train_step_compiled = True
            if wd is not None:
                wd.beat(wd_phase)
            self.iteration += 1
            epoch_pos += 1
            if rec is not None:
                rec.dispatched(
                    self.iteration, epoch, span0, rec.now() - span0
                )
            if not self._traced_programs_noted:
                # once per step-program build, after its first dispatch
                self._note_first_dispatch(step_args, rec)
            window_iters += 1
            epoch_steps += 1
            # non-finite guard bookkeeping (one step LATE via the deque, so
            # the dispatch pipeline never stalls); may raise
            # _RollbackRequested after bad_step_limit consecutive bad steps
            with span("guard"):
                self._note_guard_flag(epoch, metrics)
            # training-health statistics drain on the same late-deque
            # contract (and strip their keys from the log-facing metrics)
            with span("health"):
                self._note_health_stats(epoch, metrics)
            with span("tail"):
                if (
                    cfg.ckpt_every_steps
                    and self.checkpointer is not None
                    and epoch_pos % cfg.ckpt_every_steps == 0
                ):
                    if wd is not None:
                        from mgwfbp_tpu.utils.watchdog import CHECKPOINT_ALLOW_S

                        wd.beat(f"step checkpoint iter {self.iteration}",
                                allow_s=CHECKPOINT_ALLOW_S)
                    self.save_step(epoch, epoch_pos)
                    if wd is not None:
                        wd.beat(wd_phase)
                # retire a completed async shard save. Multi-host this is a
                # collective vote, so it runs on the SAME deterministic
                # cadence as preemption agreement (every _agree_interval-th
                # step, every process) — never gated on the local slot state
                if self.checkpointer is not None and (
                    coord.process_count() == 1
                    or self.iteration % self._agree_interval == 0
                ):
                    self._poll_async_ckpt()
                sig = self._faults.preempt_signal_after(self.iteration)
                if sig is not None:
                    self._deliver_preempt(sig)
                if self._faults.kill_after(self.iteration):
                    # chaos (ISSUE 20): a drain-less HARD crash — no
                    # checkpoint barrier, no telemetry flush, nothing. The
                    # supervisor's healer is what recovers the group.
                    self.log.warning(
                        "fault injection: SIGKILL self after step %d "
                        "(drain-less hard crash)", self.iteration,
                    )
                    os.kill(os.getpid(), _signal.SIGKILL)
                if self._agreed_preempt():
                    self._graceful_drain(epoch, epoch_pos)  # raises Preempted
                # live observability (ISSUE 9): straggler probe + armed drift
                # re-autotune, both at deterministic (group-uniform) steps;
                # ISSUE 10 adds the armed /profile deep-trace window on the
                # same cadence contract (disarmed = one lock read, zero sync)
                self._maybe_straggler_probe()
                self._maybe_drift_reautotune()
                self._maybe_profile_window()
            if max_steps is not None and epoch_pos >= max_steps:
                break
            if self.iteration % log_interval == 0:
                with span("log"):
                    metrics = {k: float(v) for k, v in metrics.items()}
                    dt = (time.time() - t_window) / max(window_iters, 1)
                    self._maybe_derive_agree_interval(dt)
                    self._observe_drift_window(dt)
                    global_batch = cfg.batch_size * self.data_size * nsteps
                    shown = {
                        k: v for k, v in metrics.items()
                        if k not in ("loss", "grads_nonfinite")
                    }
                    self.log.info(
                        "epoch %d iter %d: loss %.4f%s | %.4f s/iter, %.1f samples/s",
                        epoch, self.iteration, metrics.get("loss", float("nan")),
                        "".join(f", {k} {v:.4f}" for k, v in shown.items()),
                        dt, global_batch / dt,
                    )
                    if self.writer is not None:
                        self.writer.add_scalars("train", shown | {
                            "loss": metrics.get("loss", float("nan")),
                        }, self.iteration)
                        self.writer.add_scalar(
                            "train/sec_per_iter", dt, self.iteration
                        )
                        self.writer.add_scalar(
                            "train/samples_per_sec", global_batch / dt,
                            self.iteration,
                        )
                    t_window = time.time()
                    window_iters = 0
            # re-anchor the local-busy window: everything between the
            # pre-dispatch accumulation above and here (guard reads,
            # agreements, checkpoints, metric pulls) is group-coupled
            # and must stay OUT of the straggler signal
            t_anchor = time.perf_counter()
        if micro:
            # trailing micro-batches short of a full nsteps_update group are
            # dropped; say so (SURVEY "no silent caps")
            self.log.info(
                "epoch %d: dropped %d trailing micro-batch(es) "
                "(loader length %% nsteps_update=%d != 0)",
                epoch, len(micro), nsteps,
            )
        # drain the guard deque: every dispatched step's flag has a value
        # by epoch end (the conversion below syncs anyway); a tail of bad
        # steps can still trigger the rollback here
        with span("drain"):
            self._drain_guard_flags()
            self._drain_health_flags()
        if self.telemetry is not None and epoch_steps > 0:
            with span("snapshot"):
                epoch_dur = time.time() - t_epoch
                self._emit_event(
                    "epoch", epoch=int(epoch), steps=int(epoch_steps),
                    dur_s=float(epoch_dur),
                )
                # overlap-efficiency snapshot for this epoch's schedule
                # regime (pure host arithmetic: measured step cadence +
                # per-group comm times — trace-attributed when available,
                # cost-model otherwise)
                self._emit_overlap_snapshot(
                    step_s=epoch_dur / epoch_steps,
                    step=int(self.iteration), epoch=int(epoch),
                )
        metrics = {k: float(v) for k, v in metrics.items()}
        metrics.pop("grads_nonfinite", None)  # guard plumbing, not a metric
        self.log.info(
            "epoch %d done in %.1f s (lr %.5f)",
            epoch, time.time() - t_epoch,
            float(self.epoch_schedule(jnp.asarray(float(epoch)))),
        )
        return metrics

    # ------------------------------------------------------------------
    # Resilience layer (ISSUE 5): graceful preemption drain, non-finite
    # guard bookkeeping, rollback. utils/faults.py owns the deterministic
    # injection plan; these methods own the live handling policy.
    # ------------------------------------------------------------------

    def _maybe_derive_agree_interval(self, step_s: float) -> None:
        """One-shot MGWFBP_AGREE_INTERVAL auto-derivation from the first
        measured step-time window (multi-host only — single-process runs
        never consult the interval). Process 0's derivation is broadcast:
        the cadence gates a collective (`_agreed_preempt`'s agree_any), so
        it must be bit-identical across the group and per-process wall
        clocks are not. Fires at the first log window, which lands at the
        same iteration on every process (MGWFBP_LOG_INTERVAL, like every
        MGWFBP_* cadence var, must be group-uniform — the supervisor
        exports one environment)."""
        if not self._agree_interval_auto or coord.process_count() == 1:
            return
        self._agree_interval_auto = False  # one-shot
        iv = derive_agree_interval(step_s, self._preempt_grace_s)
        iv = int(coord.broadcast_flag(float(iv)))
        self._agree_interval = max(iv, 1)
        self.log.info(
            "MGWFBP_AGREE_INTERVAL auto-derived: %d (measured %.4g s/step "
            "vs %.3g s preemption grace; set MGWFBP_AGREE_INTERVAL to "
            "override)",
            self._agree_interval, step_s, self._preempt_grace_s,
        )

    def _arm_signals(self) -> None:
        """SIGTERM/SIGINT -> graceful drain: finish the in-flight step,
        write a step-indexed checkpoint, emit `preempt`, exit rc 75 (see
        train_cli). Main thread only — signal.signal refuses elsewhere."""
        if threading.current_thread() is not threading.main_thread():
            return
        try:
            self._prev_handlers = {
                s: _signal.signal(s, self._on_preempt_signal)
                for s in (_signal.SIGTERM, _signal.SIGINT)
            }
        except ValueError:  # non-main interpreter contexts
            return
        self._signals_armed = True

    def _disarm_signals(self) -> None:
        if not self._signals_armed:
            return
        for s, h in self._prev_handlers.items():
            try:
                _signal.signal(s, h)
            except ValueError:
                pass
        # graft: thread-safe -- GIL-atomic bool store; the signal context
        # only ever flips it False, so the worst interleaving with the
        # main-thread arm/disarm pair is one redundant disarm
        self._signals_armed = False

    def _on_preempt_signal(self, signum, frame) -> None:
        # async-signal context: just set the flag; the step loop drains at
        # the next step boundary (the in-flight dispatch completes first)
        name = _signal.Signals(signum).name
        if self._preempt_signal is not None:
            # second signal before the drain reached a step boundary (a
            # wedged step, or a slow drain checkpoint): escalate instead
            # of silently re-setting the flag — disarm so a THIRD signal
            # gets the default disposition (hard kill), and interrupt any
            # Python-level wait now
            self._disarm_signals()
            raise KeyboardInterrupt(
                f"second {name} during preemption drain — escalating "
                "(next signal kills outright)"
            )
        # graft: thread-safe -- one-word flag store is GIL-atomic; the
        # async-signal context is the only concurrent writer and the step
        # loop consumes the flag at boundaries, so a lost re-set at worst
        # delays the drain by the one step the escalation path covers
        self._preempt_signal = name

    def _wedge(self, secs: float) -> None:
        """Chaos (ISSUE 20): stop stepping for `secs` — the liveness
        monitor's wedge signature (frozen /status step) while /healthz
        and /status keep serving from their daemon thread. Sliced sleep
        so a delivered preempt signal (the supervisor's heal SIGTERM)
        interrupts the wedge and the normal drain path takes over; no
        watchdog beat on purpose (a real wedge would not beat either)."""
        self.log.warning(
            "fault injection: wedging for %.3g s before step %d "
            "(stepping stops; HTTP keeps serving)",
            secs, self.iteration + 1,
        )
        deadline = time.monotonic() + secs
        while time.monotonic() < deadline:
            if self._preempt_signal is not None:
                self.log.warning(
                    "wedge interrupted by %s; resuming the step loop "
                    "(drain takes over at the boundary)",
                    self._preempt_signal,
                )
                return
            time.sleep(min(0.2, max(deadline - time.monotonic(), 0.0)))

    def _deliver_preempt(self, sig: int) -> None:
        """Fault-plan preemption: deliver the real signal when our handler
        is armed (exercising the production path), else set the flag
        directly (train_epoch called outside fit, e.g. unit tests)."""
        name = _signal.Signals(sig).name
        if (
            self._signals_armed
            and threading.current_thread() is threading.main_thread()
        ):
            self.log.warning("fault injection: delivering %s to self", name)
            os.kill(os.getpid(), sig)
        else:
            self.log.warning("fault injection: simulating %s", name)
            self._preempt_signal = name

    def _agreed_preempt(self, at_boundary: bool = False) -> bool:
        """Should the WHOLE group drain now?

        Single-process: the local flag, checked every step (today's
        behavior). Multi-host: one host's SIGTERM must drain every
        process — whoever keeps stepping blocks forever in its next
        collective against peers that left — so the group runs a tiny
        `agree_any` collective over the local flags. It runs at
        deterministic points only (every `_agree_interval`-th step, and
        at epoch boundaries): agreement participation may NEVER depend on
        the local flag itself, or the signaled process would issue a
        collective its peers don't. A process drained by a peer's signal
        records the drain as signal 'PEER'."""
        local = self._preempt_signal is not None
        if coord.process_count() == 1:
            return local
        if not at_boundary and self.iteration % self._agree_interval != 0:
            return False
        agreed = coord.agree_any(local)
        if agreed and not local:
            self._preempt_signal = "PEER"  # drained by a peer's signal
        return agreed

    def _graceful_drain(self, epoch: int, epoch_pos: int) -> None:
        """The in-flight step is done; checkpoint the exact position and
        unwind with Preempted (train_cli converts it to rc 75)."""
        name = self._preempt_signal or "SIGTERM"
        self._pending_guard.clear()  # a drain outranks bad-step policy
        self._pending_health.clear()  # ... and health bookkeeping
        if self.checkpointer is not None:
            wd = getattr(self, "_watchdog", None)
            if wd is not None:
                from mgwfbp_tpu.utils.watchdog import CHECKPOINT_ALLOW_S

                wd.beat("preemption drain checkpoint",
                        allow_s=CHECKPOINT_ALLOW_S)
            self.save_step(epoch, epoch_pos, wait=True)
        else:
            self.log.warning(
                "preempted without --checkpoint-dir: progress NOT saved"
            )
        self._emit_event(
            "preempt", signal=str(name), epoch=int(epoch),
            iteration=int(self.iteration),
        )
        self.log.warning(
            "preemption (%s): drained at epoch %d step %d (iter %d); "
            "exiting restart-friendly", name, epoch, epoch_pos,
            self.iteration,
        )
        raise Preempted(name, epoch, self.iteration)

    def _graceful_drain_boundary(self, epoch: int) -> None:
        """Preemption landing between epochs (eval/checkpoint phases):
        write/refresh the boundary checkpoint and unwind."""
        name = self._preempt_signal or "SIGTERM"
        if self.checkpointer is not None:
            self.save(epoch)
            self.checkpointer.wait()
        self._emit_event(
            "preempt", signal=str(name), epoch=int(epoch),
            iteration=int(self.iteration),
        )
        self.log.warning(
            "preemption (%s): drained at epoch %d boundary (iter %d)",
            name, epoch, self.iteration,
        )
        raise Preempted(name, epoch, self.iteration)

    def _note_guard_flag(self, epoch: int, metrics) -> None:
        """Queue this step's `grads_nonfinite` metric and examine the one
        from the PREVIOUS step (already computed by now — reading it stalls
        nothing and issues no device_get/block_until_ready, preserving the
        PR-4 zero-sync contract)."""
        if not self.config.grad_guard or not isinstance(metrics, dict):
            return
        flag = metrics.get("grads_nonfinite")
        if flag is None:
            return
        self._pending_guard.append((self.iteration, epoch, flag))
        if len(self._pending_guard) <= self._guard_interval:
            return
        # drain all but the newest (whose step may still be in flight):
        # stacked into ONE device->host pull, so an interval of N costs
        # one RTT per N steps instead of one per step
        items = [
            self._pending_guard.popleft()
            for _ in range(len(self._pending_guard) - 1)
        ]
        self._check_guard_batch(items)

    def _drain_guard_flags(self) -> None:
        items = list(self._pending_guard)
        self._pending_guard.clear()
        self._check_guard_batch(items)

    def _check_guard_batch(self, items: list) -> None:
        if not items:
            return
        if len(items) == 1:
            values = [float(items[0][2])]
        else:
            values = np.asarray(jnp.stack([f for _, _, f in items]))
        if self._setup is not None:
            self._setup_results_read(items[-1][0])
        for (it, ep, _), v in zip(items, values):
            self._check_guard_value(it, ep, float(v))

    def _check_guard_value(self, it: int, epoch: int, flag) -> None:
        # graft: group-uniform -- the nonfinite count is a globally-psum'd metric
        nonfinite = float(flag)
        if nonfinite <= 0:
            self._bad_streak = 0
            self._good_step_since_rollback = True
            return
        self._bad_streak += 1
        self.log.warning(
            "non-finite gradients at iter %d (%g element(s)): update "
            "dropped by the step guard (bad streak %d)",
            it, nonfinite, self._bad_streak,
        )
        self._emit_event(
            "bad_step", step=int(it), epoch=int(epoch),
            nonfinite=float(nonfinite),
        )
        limit = self.config.bad_step_limit
        if not limit or self._bad_streak < limit:
            return
        can_rollback = (
            self.checkpointer is not None
            and self.checkpointer.latest_step() is not None
        )
        if coord.process_count() > 1:
            # the streak itself is identical everywhere (the nonfinite
            # count rides the globally-psum'd metrics and the guard
            # cadence is deterministic), so every process reaches this
            # point at the same step — but whether a checkpoint EXISTS is
            # host-local state (e.g. a host with a torn local dir). One
            # process rolling back while another keeps stepping is a
            # distributed hang, so the group agrees: roll back only when
            # EVERY process can.
            can_rollback = coord.agree_all(can_rollback)
        if can_rollback:
            raise _RollbackRequested(self._bad_streak)
        if not getattr(self, "_warned_no_rollback", False):
            self._warned_no_rollback = True
            self.log.error(
                "%d consecutive non-finite steps but no checkpoint to "
                "roll back to (--checkpoint-dir unset or nothing saved); "
                "continuing under the skip-step policy", self._bad_streak,
            )

    def _rollback(self, rb: _RollbackRequested) -> int:
        """Restore the last checkpoint after K consecutive bad steps;
        returns the epoch to continue from."""
        # an in-flight async save snapshots the suspect regime and its
        # step key may be re-reached after the replay: abandon it
        # uncommitted (local-only; uniform because the rollback decision
        # is broadcast-agreed below)
        dropped = self.checkpointer.abandon_async()
        if dropped is not None:
            self.log.warning(
                "rollback: abandoned in-flight async checkpoint of "
                "step %d", dropped,
            )
        step = self.checkpointer.latest_step()
        if coord.process_count() > 1:
            # every process must replay from the SAME snapshot; latest_step
            # is host-local filesystem state, so process 0's choice is the
            # group's choice (broadcast, like the tb profile)
            step = int(coord.broadcast_flag(
                float(step if step is not None else -1)
            ))
            step = None if step < 0 else step
        snap = self._restore_step(self.checkpointer, step)
        if snap is None:  # GC'd between check and restore — give up cleanly
            raise RuntimeError(
                "rollback requested but the checkpoint vanished"
            ) from rb
        if self._last_rollback_iteration is not None and (
            snap.iteration == self._last_rollback_iteration
            # mid-epoch saves during an all-bad streak advance the
            # checkpoint ITERATION while the params stay frozen, so
            # "different iteration" alone is not progress — a finite step
            # must have been OBSERVED since the last rollback
            or not self._good_step_since_rollback
        ):
            # the previous rollback's replay produced K consecutive bad
            # steps again with no good step in between: the NaNs are
            # persistent (lr/data/config), not transient — loop
            # detection beats a silent forever-rollback livelock
            raise RuntimeError(
                f"persistent non-finite gradients: rollback to iter "
                f"{snap.iteration} follows a rollback to iter "
                f"{self._last_rollback_iteration} with no finite step "
                f"observed in between ({rb.bad_steps} consecutive bad "
                "steps again) — the NaN source is deterministic (check "
                "lr, input pipeline, precision config); aborting instead "
                "of looping"
            ) from rb
        self._last_rollback_iteration = snap.iteration
        self._good_step_since_rollback = False
        self._bad_streak = 0
        self._pending_guard.clear()
        # the restored model's statistics invalidate the health
        # detector's learned baselines; resolve raised alarms first
        self._reset_health_detector()
        self._warned_no_rollback = False
        self._apply_snapshot(snap, "rolled back", emit_resume=False)
        self._emit_event(
            "rollback", bad_steps=int(rb.bad_steps),
            restored_iteration=int(snap.iteration),
            restored_epoch=int(snap.epoch),
        )
        self.log.warning(
            "rollback: %d consecutive non-finite steps -> restored iter %d "
            "(epoch %d%s)", rb.bad_steps, snap.iteration, snap.epoch,
            f" step {snap.epoch_step}" if snap.mid_epoch else " boundary",
        )
        return self.start_epoch

    def _eval_params(self):
        """The canonical replicated params for host/eval consumers: the
        live tree, or the cross-step carry gathered back into it (a
        collective all-gather on a multi-host mesh — the one place the
        replicated view is genuinely needed). When the CURRENT iteration
        already committed a shard-native checkpoint, the gathered view is
        sitting on disk — read it off the manifest instead of issuing the
        collective (ROADMAP shard-native follow-up (b); pinned bitwise
        against the gathered path in tests/test_shard_ckpt.py)."""
        if not self._cross_step:
            return self.state.params
        params = self._manifest_eval_params()
        if params is not None:
            self._eval_params_source = "manifest"
            return params
        self._eval_params_source = "gather"
        return self._gathered_params(self.state.params)

    def _manifest_eval_params(self):
        """Replicated params rebuilt leaf-by-leaf from the committed
        shard-native checkpoint of the current iteration, or None when no
        such checkpoint exists (mid-cadence, async commit still pending,
        orbax format) — the caller falls back to the gather. Single
        process only: the gather it replaces is a collective, so skipping
        it must be group-uniform, and one process cannot know its
        siblings see the same committed manifest."""
        if coord.process_count() != 1 or self.checkpointer is None:
            return None
        step = int(self.iteration)
        try:
            if self.checkpointer.entry_format(step) != "sharded":
                return None
            src = self.checkpointer.open_sharded(step)
        except CheckpointRestoreError:
            return None
        if src.section_kind("params") == "none":
            return None
        template = jax.tree_util.tree_leaves(self._params_template)
        docs = src.section_docs("params")
        if len(docs) != len(template):
            return None
        from jax.sharding import NamedSharding, PartitionSpec

        sharding = NamedSharding(self.mesh, PartitionSpec())
        leaves = []
        for j, ref in enumerate(template):
            doc = docs[j]
            if tuple(doc.get("shape", ())) != tuple(ref.shape) or (
                jnp.dtype(doc.get("dtype", "float32"))
                != jnp.dtype(ref.dtype)
            ):
                return None
            leaves.append(
                jax.device_put(src.read_leaf("params", j), sharding)
            )
        return jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(self._params_template), leaves
        )

    def _eval_state(self):
        """State view eval steps consume: replicated params (gathered from
        the cross-step carry when needed); opt state is stripped by the
        eval step itself."""
        if not self._cross_step:
            return self.state
        return self.state.replace(params=self._eval_params(), opt_state=())

    def evaluate(self) -> dict:
        """Eval over the val loader (reference test(), dl_trainer.py:854-937).

        Every sample is evaluated — the reference iterates the full val set —
        so an indivisible tail batch is PADDED up to data-axis divisibility
        (edge-replicating real samples) with a per-sample ``valid`` mask
        zeroing the padding's contribution. `eval_step` returns psum'd GLOBAL
        sums per metric plus ``count``; accumulation here is plain addition
        and one final divide by the summed count.
        """
        stall_s = self._faults.stall_secs("eval", self.iteration)
        if stall_s > 0:
            self.log.warning(
                "fault injection: stalling %.3g s in eval", stall_s
            )
            time.sleep(stall_s)
        # cross-step carry: eval consumes the canonical replicated params;
        # gather the shards ONCE per evaluate() (the jitted eval step's
        # in-spec is replicated P())
        eval_state = self._eval_state()
        loader = self.bundle.val
        sums: dict[str, float] = {}
        wer_total, wer_n = 0.0, 0
        wd = getattr(self, "_watchdog", None)
        # single-process ctc: decode inputs come OUT of the loss forward
        # (step.py per_device_ctc), so WER costs no second pass over the val
        # set; multi-host logits are not fully addressable on one process,
        # so that path keeps the separate local-shard decode pass.
        fused_wer = self.meta.task == "ctc" and jax.process_count() == 1
        carry = (
            self._globalize(
                self.model.initial_carry(self.process_batch), axes=0
            )
            if self.meta.has_carry
            else None
        )
        # each process's local batch must split evenly over its local extent
        # of the data axis for the global assembly to shard cleanly
        quantum = max(self.data_size // jax.process_count(), 1)
        for raw in loader:
            batch = self._to_host_batch(raw)
            b = next(iter(batch.values())).shape[0]
            if self.meta.has_carry:
                # carry pins the batch extent; loaders for carry models use
                # drop_last so every batch is full-size already
                target = self.process_batch
                if b != target:
                    self.log.warning(
                        "evaluate: skipping %d-sample batch (carry model "
                        "requires fixed batch %d)", b, target,
                    )
                    continue
            else:
                target = -(-b // quantum) * quantum
            valid = np.ones((b,), np.float32)
            if b < target:
                # pad on the HOST (edge-replicate) before any device put
                pad = target - b
                batch = {
                    k: np.concatenate(
                        [v, np.repeat(v[:1], pad, axis=0)], axis=0
                    )
                    for k, v in batch.items()
                }
                valid = np.concatenate([valid, np.zeros((pad,), np.float32)])
            batch["valid"] = valid
            batch = self._globalize(
                {k: jnp.asarray(v) for k, v in batch.items()}, axes=0
            )
            if wd is not None and not self._eval_step_compiled:
                from mgwfbp_tpu.utils.watchdog import COMPILE_ALLOW_S

                wd.beat("compile eval step", allow_s=COMPILE_ALLOW_S)
            if self.meta.has_carry:
                metrics, carry = self.eval_step(eval_state, batch, carry)
            elif self.meta.task == "ctc":
                metrics, logits, out_lengths = self.eval_step(
                    eval_state, batch
                )
                if fused_wer:
                    w, n = self._decode_wer_batch(
                        np.asarray(logits), np.asarray(out_lengths), batch
                    )
                    wer_total += w
                    wer_n += n
            else:
                metrics = self.eval_step(eval_state, batch)
            self._eval_step_compiled = True
            for k, v in metrics.items():
                # device-side accumulation: a float() here would pull one
                # scalar PER BATCH to the host and drain the dispatch
                # pipeline; keep the adds async and pull once at the end
                sums[k] = sums.get(k, 0.0) + v
            if wd is not None:
                wd.beat("evaluate")
        sums = {k: float(v) for k, v in sums.items()}
        count = sums.pop("count", 0.0)
        out = {k: v / max(count, 1.0) for k, v in sums.items()}
        # seq-sharded eval counts each sample once per sequence shard (the
        # loss sums carry the same factor, so the means above are exact);
        # report true samples-evaluated
        out["count"] = count / self.seq_size
        if self.meta.task == "lm":
            # reference reports per-token perplexity (dl_trainer.py:927-929)
            out["perplexity"] = float(np.exp(out.get("loss", 0.0)))
        if self.meta.task == "ctc":
            if fused_wer:
                out["wer"] = wer_total / max(wer_n, 1)
            else:
                out.update(self._evaluate_wer())
        return out

    def _decode_wer_batch(
        self, logits: np.ndarray, out_lengths: np.ndarray, batch: dict
    ) -> tuple[float, int]:
        """Greedy-decode one already-computed eval batch; padded samples
        (valid == 0) are skipped. Returns (sum of per-utterance WER, n)."""
        from mgwfbp_tpu.data.audio import greedy_decode, ids_to_text, wer

        valid = np.asarray(batch.get("valid", np.ones(len(logits))))
        ys = np.asarray(batch["y"])
        lab_lens = np.asarray(batch["label_lengths"])
        hyps = greedy_decode(logits, out_lengths)
        total, n = 0.0, 0
        for j, hyp in enumerate(hyps):
            if valid[j] == 0.0:
                continue
            ref = ids_to_text(ys[j][: int(lab_lens[j])])
            total += wer(hyp, ref)
            n += 1
        return total, n

    def _evaluate_wer(self, max_batches: Optional[int] = None) -> dict:
        """Host-side greedy decode + WER over the FULL validation set
        (reference dl_trainer.py:891-910 decodes every val batch);
        max_batches caps it for smoke runs only."""
        from mgwfbp_tpu.data.audio import greedy_decode, ids_to_text, wer

        if not hasattr(self, "_decode_forward"):
            # jitted decode forward — eager per-op dispatch of the conv+RNN
            # stack is orders of magnitude slower than one compiled call
            self._decode_forward = jax.jit(
                lambda params, bstats, x, lens: self.model.apply(
                    {"params": params, "batch_stats": bstats},
                    x, lens, train=False,
                )
            )
        total, n = 0.0, 0
        decode_params = self._eval_params()
        for bi, raw in enumerate(self.bundle.val):
            if max_batches is not None and bi >= max_batches:
                break
            batch = self._to_model_batch(raw)
            logits, out_lengths = self._decode_forward(
                decode_params, self.state.batch_stats,
                batch["x"], batch["input_lengths"],
            )
            hyps = greedy_decode(np.asarray(logits), np.asarray(out_lengths))
            for j, hyp in enumerate(hyps):
                ref = ids_to_text(
                    np.asarray(batch["y"][j])[: int(batch["label_lengths"][j])]
                )
                total += wer(hyp, ref)
                n += 1
        return {"wer": total / max(n, 1)}

    def save(self, epoch: int) -> None:
        """Epoch-boundary checkpoint (step-indexed key = the iteration the
        epoch ended on; the sidecar index marks it a boundary)."""
        if self.checkpointer is None:
            return
        stats = self._save_snapshot(epoch, epoch_step=0, mid_epoch=False)
        if stats is None:  # async submission: event lands at commit
            return
        self._emit_event(
            "checkpoint", epoch=int(epoch),
            iteration=int(self.iteration), mid_epoch=False, **stats,
        )

    def save_step(
        self, epoch: int, epoch_step: int, wait: bool = False
    ) -> None:
        """Mid-epoch step-indexed checkpoint (--ckpt-every-steps and the
        preemption drain): carries the data-iterator position — the
        deterministic loader makes (epoch, epoch_step) the complete
        iterator state — and the BPTT carry for stateful models, so a
        restart resumes from the EXACT step, bitwise — multi-host
        included (the shard-native format writes each process's carry
        block; the replicated escape hatch all-gathers it)."""
        if self.checkpointer is None:
            return
        stats = self._save_snapshot(
            epoch, epoch_step=epoch_step, mid_epoch=True, wait=wait,
        )
        if stats is None:  # async submission: event lands at commit
            return
        self._emit_event(
            "checkpoint", epoch=int(epoch), iteration=int(self.iteration),
            mid_epoch=True, epoch_step=int(epoch_step), **stats,
        )

    # -- snapshot writers (shard-native by default) ----------------------
    def _ckpt_sharded(self) -> bool:
        """Shard-native format unless the --ckpt-format replicated escape
        hatch (interchange with pre-ISSUE-13 consumers) is armed."""
        return getattr(self.config, "ckpt_format", "sharded") != "replicated"

    def _poll_async_ckpt(
        self, block: bool = False, durable: bool = False
    ) -> None:
        """Retire a completed in-flight async shard save (ISSUE 16): the
        collective commit (payload barrier + p0 manifest + success vote)
        runs HERE on the step-loop thread — the writer thread never
        issues a group op — and the checkpoint event carries the real
        submit-to-commit span plus the commit iteration, so the report
        tool can tell how many steps each save overlapped."""
        ck = self.checkpointer
        if ck is None:
            return
        evt = ck.poll_async(block=block, durable=durable)
        if evt is None:
            return
        meta = evt.get("meta") or {}
        self._emit_event(
            "checkpoint",
            epoch=int(meta.get("epoch", 0)),
            iteration=int(evt["step"]),
            mid_epoch=bool(meta.get("mid_epoch", True)),
            epoch_step=int(meta.get("epoch_step", 0)),
            duration_s=float(evt["duration_s"]),
            bytes=int(evt["bytes"]),
            format="sharded",
            commit_iteration=int(self.iteration),
            **{"async": True},
        )

    def _save_snapshot(
        self, epoch: int, epoch_step: int, mid_epoch: bool,
        wait: bool = False,
    ) -> dict:
        """Write one snapshot in the configured format; returns the
        telemetry fields for the `checkpoint` event (save duration +
        bytes this process wrote — the flight recorder and report tool
        surface checkpoint-cost regressions from them)."""
        carry = None
        if self.meta.has_carry and self.carry is not None and mid_epoch:
            carry = self.carry
        if self._ckpt_sharded():
            # retire any in-flight async save FIRST, from here (not from
            # the checkpointer-internal drain), so its checkpoint event
            # lands in the telemetry stream before the new save's; the
            # preempt drain (wait=True) also upgrades that commit to the
            # fsync'd rc-75 durability contract
            self._poll_async_ckpt(block=True, durable=wait)
            manifest, files = self._shard_payload(
                epoch, epoch_step, mid_epoch, carry
            )
            # graft: group-uniform -- mid_epoch/wait are literal args at collective call sites; ckpt_async is static config
            if (
                mid_epoch and not wait
                and getattr(self.config, "ckpt_async", True)
            ):
                # async path (ISSUE 16): the step-boundary snapshot is
                # `files` itself — fresh host copies, handed over to the
                # writer thread; only the group-agreed preamble runs here
                stats = self.checkpointer.submit_sharded(manifest, files)
                if stats is None:
                    return None  # in flight; event lands at commit time
            else:
                stats = self.checkpointer.save_sharded(
                    manifest, files, wait=wait
                )
            return {
                "duration_s": float(stats["duration_s"]),
                "bytes": int(stats["bytes"]),
                "format": "sharded",
            }
        # --ckpt-format replicated: the legacy orbax payload (gathered
        # interchange form; duration measures the submit — orbax commits
        # asynchronously unless wait=True)
        t0 = time.perf_counter()
        host_carry = None
        if carry is not None:
            host_carry = jax.tree_util.tree_map(
                np.asarray, self._replicated_view(carry)
            )
        state = self._to_interchange_state(self.state)
        nbytes = int(sum(
            np.dtype(leaf.dtype).itemsize
            * (int(np.prod(leaf.shape)) if leaf.shape else 1)
            for leaf in jax.tree_util.tree_leaves(state)
            if hasattr(leaf, "dtype")
        ))
        self.checkpointer.save(
            Snapshot(
                state=state,
                epoch=epoch,
                iteration=self.iteration,
                epoch_step=epoch_step,
                mid_epoch=mid_epoch,
                carry=host_carry,
            ),
            wait=wait,
        )
        return {
            "duration_s": float(time.perf_counter() - t0),
            "bytes": nbytes,
            "format": "replicated",
        }

    def _replicated_view(self, tree):
        """A fully-addressable (replicated) view of a data-sharded pytree
        — identity on one process, a cached jitted all-gather on a
        multi-host mesh (the collective twin of np.asarray; shared
        implementation in `mesh.gather_replicated`)."""
        if jax.process_count() == 1:
            return tree
        from mgwfbp_tpu.parallel.mesh import gather_replicated

        return gather_replicated(
            tree, self.mesh, self.__dict__.setdefault("_rep_progs", {})
        )

    # -- shard-native payload builders (ISSUE 13) ------------------------
    def _tree_leaf_docs(self, tree) -> list[dict]:
        from mgwfbp_tpu.checkpoint import _leaf_doc

        return [
            _leaf_doc(jax.tree_util.keystr(kp), leaf)
            for kp, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]
        ]

    def _shard_rows_by_process(self) -> dict[int, list[int]]:
        """Global shard-row ownership: row -> lowest-index process whose
        devices hold it (the save-side dedup rule; identical on every
        process — it derives from the mesh alone)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        optim = self.reducer.optim
        sharding = NamedSharding(self.mesh, P(optim.axes))
        owners: dict[int, int] = {}
        for dev, idx in sharding.devices_indices_map(
            (optim.world, 1)
        ).items():
            r = int(idx[0].start or 0)
            p = int(dev.process_index)
            if r not in owners or p < owners[r]:
                owners[r] = p
        rows: dict[int, list[int]] = {}
        for r, p in owners.items():
            rows.setdefault(p, []).append(r)
        return {p: sorted(v) for p, v in rows.items()}

    def _local_needed_rows(self) -> list[int]:
        """Shard rows this process's devices materialize at restore time
        (the superset of its save-side owned rows when an axis outside
        the shard spec replicates them)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        optim = self.reducer.optim
        sharding = NamedSharding(self.mesh, P(optim.axes))
        rows = set()
        for _, idx in sharding.addressable_devices_indices_map(
            (optim.world, 1)
        ).items():
            rows.add(int(idx[0].start or 0))
        return sorted(rows)

    @staticmethod
    def _rows_block(arr, rows: list[int]) -> np.ndarray:
        """Stack the requested global rows of a (world, shard) array from
        this process's addressable shards — only those rows' bytes are
        touched."""
        want = set(rows)
        have: dict[int, np.ndarray] = {}
        for sh in arr.addressable_shards:
            start = int(sh.index[0].start or 0)
            nrows = int(sh.data.shape[0])
            if want.intersection(range(start, start + nrows)):
                data = np.asarray(sh.data)
                for k in range(nrows):
                    if start + k in want:
                        have[start + k] = data[k]
        return np.stack([have[r] for r in rows])

    def _carry_runs_by_process(
        self, rows: int
    ) -> dict[int, list[list[int]]]:
        """EXACT batch-row runs each process's devices own on the carry's
        dim-0 data sharding (lowest-index owner dedup, adjacent runs
        merged). A process's rows need not be contiguous — a multi-slice
        (dcn) data sharding interleaves them — so both the manifest
        (save) and the restore-side block assembly use this run list
        verbatim; a contiguous-block assumption would silently assign
        hidden-state rows to the wrong batch elements."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        # only dim 0 is sharded; a 1-D probe shape yields the same runs
        # for every carry leaf regardless of its rank
        sharding = NamedSharding(self.mesh, P(self.data_axes))
        owners: dict[int, tuple[int, int]] = {}  # start -> (proc, stop)
        for dev, idx in sharding.devices_indices_map((rows,)).items():
            a = int(idx[0].start or 0)
            b = int(idx[0].stop if idx[0].stop is not None else rows)
            p = int(dev.process_index)
            if a not in owners or p < owners[a][0]:
                owners[a] = (p, b)
        runs: dict[int, list[list[int]]] = {}
        for a in sorted(owners):
            p, b = owners[a]
            mine = runs.setdefault(p, [])
            if mine and mine[-1][1] == a:
                mine[-1][1] = b  # merge adjacent
            else:
                mine.append([a, b])
        return runs

    @staticmethod
    def _carry_block(leaf, runs: list[list[int]]) -> np.ndarray:
        """This process's carry rows, run-concatenated in manifest order
        — every requested row must be locally addressable."""
        have: list[tuple[int, int, Any]] = []
        for sh in leaf.addressable_shards:
            a = int(sh.index[0].start or 0)
            have.append((a, a + int(sh.data.shape[0]), sh))
        pieces = []
        for start, stop in runs:
            pos = start
            while pos < stop:
                hit = None
                for a, b, sh in have:
                    if a <= pos < b:
                        hit = (a, b, sh)
                        break
                if hit is None:
                    raise RuntimeError(
                        f"carry row {pos} is not addressable on this "
                        "process — carry sharding drifted from the "
                        "manifest convention"
                    )
                a, b, sh = hit
                hi = min(b, stop)
                pieces.append(np.asarray(sh.data)[pos - a : hi - a])
                pos = hi
        return np.concatenate(pieces) if len(pieces) > 1 else np.array(
            pieces[0]
        )

    def _shard_payload(
        self, epoch: int, epoch_step: int, mid_epoch: bool, carry,
    ) -> tuple[dict, dict]:
        """(manifest, this process's files) for one shard-native save.

        Sharded sections (the rs_opt_ag opt slots, the rs_fwd_ag param
        carry, the BPTT carry) contribute ONLY this process's shard rows;
        replicated sections (params on in-step lowerings, batch stats,
        the optax tree on unsharded runs, rng) are written once by
        process 0."""
        from mgwfbp_tpu.checkpoint import SHARD_FORMAT_VERSION
        from mgwfbp_tpu.parallel.allreduce import (
            _map_count_leaves,
            _map_params_subtrees,
        )

        state = self.state
        primary = coord.is_primary()
        files: dict[str, np.ndarray] = {}
        sharded = self._sharded_opt or self._cross_step
        manifest: dict = {
            "format_version": SHARD_FORMAT_VERSION,
            "step": int(self.iteration),
            "world": int(
                self.reducer.optim.world if sharded
                else self.data_size * self.seq_size
            ),
            "process_count": int(jax.process_count()),
            "mesh_axes": {
                str(k): int(v) for k, v in self.mesh.shape.items()
            },
            "comm_op": str(self.config.comm_op),
            "leaves": self._tree_leaf_docs(self._params_template),
            "rng": [int(x) for x in np.asarray(state.rng).reshape(-1)],
            "meta": {
                "epoch": int(epoch),
                "iteration": int(self.iteration),
                "epoch_step": int(epoch_step),
                "mid_epoch": bool(mid_epoch),
                "train_step": int(np.asarray(state.step)),
                "steps_per_epoch": int(max(self._steps_per_epoch(), 1)),
                "sched_step_offset": int(self._sched_step_offset),
                "sched_epoch_offset": float(self._sched_epoch_offset),
            },
        }
        rows_by_proc = None
        if sharded:
            optim = self.reducer.optim
            rows_by_proc = self._shard_rows_by_process()
            manifest["layout"] = optim.manifest_layout()
            manifest["processes"] = {
                str(p): {"rows": rows} for p, rows in rows_by_proc.items()
            }
            my_rows = rows_by_proc.get(jax.process_index(), [])
            for s, groups in enumerate(state.opt_state.slots):
                for gi, buf in enumerate(groups):
                    files[f"opt.s{s}.g{gi}"] = self._rows_block(
                        buf, my_rows
                    )
            manifest["opt"] = {
                "kind": "sharded", "slots": int(optim.num_slots),
            }
            manifest["meta"]["opt_count"] = int(
                np.asarray(state.opt_state.count)
            )
        if self._cross_step:
            my_rows = rows_by_proc.get(jax.process_index(), [])
            for gi, buf in enumerate(state.params.groups):
                files[f"params.g{gi}"] = self._rows_block(buf, my_rows)
            manifest["params"] = {"kind": "sharded"}
        else:
            manifest["params"] = {"kind": "replicated"}
            if primary:
                for j, leaf in enumerate(
                    jax.tree_util.tree_leaves(state.params)
                ):
                    files[f"params.l{j}"] = np.asarray(leaf)
        if not sharded:
            opt_docs = self._tree_leaf_docs(state.opt_state)
            # slot s of params-tree leaf j -> flat optax leaf index, so a
            # SHARDED restore target can re-slice this replicated source
            # without reconstructing the optax tree
            n_opt = len(opt_docs)
            idx_tree = jax.tree_util.tree_unflatten(
                jax.tree_util.tree_structure(state.opt_state),
                list(range(n_opt)),
            )
            slot_leaf_index: list[list[int]] = []
            _map_params_subtrees(
                idx_tree, state.params,
                lambda sub: slot_leaf_index.append(
                    [int(i) for i in jax.tree_util.tree_leaves(sub)]
                ) or sub,
            )
            counts: list[int] = []
            _map_count_leaves(
                state.opt_state,
                lambda leaf: counts.append(int(np.asarray(leaf))) or leaf,
            )
            manifest["opt"] = {
                "kind": "replicated",
                "leaves": opt_docs,
                "slot_leaf_index": slot_leaf_index,
            }
            manifest["meta"]["opt_count"] = int(counts[0]) if counts else 0
            if primary:
                for j, leaf in enumerate(
                    jax.tree_util.tree_leaves(state.opt_state)
                ):
                    files[f"opt.l{j}"] = np.asarray(leaf)
        manifest["batch_stats"] = {
            "kind": "replicated",
            "leaves": self._tree_leaf_docs(state.batch_stats),
        }
        if primary:
            for j, leaf in enumerate(
                jax.tree_util.tree_leaves(state.batch_stats)
            ):
                files[f"batch_stats.l{j}"] = np.asarray(leaf)
        if carry is not None:
            carry_leaves = jax.tree_util.tree_leaves(carry)
            runs = self._carry_runs_by_process(
                int(carry_leaves[0].shape[0])
            )
            manifest["carry"] = {
                "leaves": self._tree_leaf_docs(carry),
                # exact row runs per process, manifest-ordered — the
                # reader maps any global row straight to (process,
                # offset within that process's run-concatenated file)
                "runs": {
                    str(p): [[int(a), int(b)] for a, b in r]
                    for p, r in runs.items()
                },
            }
            mine = runs.get(jax.process_index())
            if mine:
                for li, leaf in enumerate(carry_leaves):
                    files[f"carry.l{li}"] = self._carry_block(leaf, mine)
        return manifest, files

    def close(self) -> None:
        if self._setup is not None:
            # built and never stepped: no set-up to report
            self._drop_setup()
        if self.checkpointer is not None:
            if coord.process_count() == 1:
                # land the in-flight async save's commit AND its
                # telemetry event before the stream closes; multi-host
                # close is the disorderly path — the checkpointer
                # abandons the uncommitted save rather than risk a
                # collective against departed peers
                try:
                    self._poll_async_ckpt(block=True)
                except RuntimeError:
                    self.log.exception(
                        "in-flight async checkpoint failed during close"
                    )
            self.checkpointer.close()
        if self.writer is not None:
            self.writer.close()
        recorder = getattr(self, "_recorder", None)
        if recorder is not None:
            # a trigger at the very end of the run deferred its
            # postmortem record; land it before the stream closes
            recorder.flush_events()
        if self.telemetry is not None:
            self.telemetry.close()
        server = getattr(self, "_metrics_server", None)
        if server is not None:
            server.close()
            self._metrics_server = None

    def load_checkpoint(self, directory: str, epoch: Optional[int] = None):
        """Restore a snapshot from a checkpoint dir onto this trainer's mesh
        (orbax restores committed to one device; re-replicating over the mesh
        is the reference's post-load broadcast_parameters,
        dist_trainer.py:66, expressed as a sharding constraint). Returns the
        Snapshot; raises if none exists."""
        ckpt = Checkpointer(directory)
        try:
            snap = ckpt.restore(
                self._replicated_template_state(), epoch=epoch,
                carry_template=self._carry_template(),
            )
        finally:
            ckpt.close()
        if snap is None:
            raise FileNotFoundError(
                f"no checkpoint found under {directory!r}"
                + (f" at epoch {epoch}" if epoch is not None else "")
            )
        snap.state = self._replicate_onto_mesh(snap.state)
        return snap

    def _replicate_onto_mesh(self, tree):
        """Restored host/local-device leaves -> replicated arrays on the
        live mesh. Single-process this is the plain device_put; on a
        multi-host mesh device_put rejects non-addressable shardings, so
        each process contributes its (identical) local copy and jax
        assembles the global replicated array."""
        from jax.sharding import NamedSharding, PartitionSpec

        sharding = NamedSharding(self.mesh, PartitionSpec())
        if jax.process_count() == 1:
            return jax.device_put(tree, sharding)
        return jax.tree_util.tree_map(
            lambda a: jax.make_array_from_process_local_data(
                sharding, np.asarray(a)
            ),
            tree,
        )

    def _carry_template(self):
        """Restore template for a checkpointed BPTT carry (host form)."""
        if not self.meta.has_carry:
            return None
        return jax.tree_util.tree_map(
            np.asarray, self.model.initial_carry(self.process_batch)
        )

    def _apply_snapshot(
        self, snap: Snapshot, source: str, emit_resume: bool = True
    ) -> None:
        """Install a restored snapshot: state back onto the mesh (and
        re-scattered for the sharded-opt path), counters, and — for a
        mid-epoch snapshot — the exact data-iterator position so
        train_epoch skips the already-consumed batches (shared by resume
        and bad-step rollback; the latter passes emit_resume=False — it
        emits its own `rollback` record, and a `resume` row means "a
        restart picked up from a saved snapshot", which a rollback inside
        one uninterrupted process is not)."""
        if snap.native:
            # shard-native restore: the state is already in live form on
            # this mesh (sharded leaves as global arrays) — replicating or
            # re-scattering it would be wrong, not just wasteful
            self.state = snap.state
        else:
            self.state = self._from_interchange_state(
                self._replicate_onto_mesh(snap.state)
            )
        self.iteration = snap.iteration
        if snap.mid_epoch:
            self.start_epoch = snap.epoch
            # graft: group-uniform -- the restore step is group-agreed (broadcast / sibling-probe agreement)
            self._resume_epoch = snap.epoch
            # graft: group-uniform -- the restore step is group-agreed (broadcast / sibling-probe agreement)
            self._resume_skip_steps = snap.epoch_step
            self._resume_carry = snap.carry
        else:
            self.start_epoch = snap.epoch + 1
            self._resume_epoch = None
            self._resume_skip_steps = 0
            self._resume_carry = None
        if emit_resume:
            self._emit_event(
                "resume", epoch=int(snap.epoch),
                iteration=int(snap.iteration),
                mid_epoch=bool(snap.mid_epoch),
            )
        self.log.info(
            "%s from epoch %d (iter %d%s)", source, snap.epoch,
            snap.iteration,
            f", mid-epoch at step {snap.epoch_step}" if snap.mid_epoch
            else "",
        )

    def _restore_step(self, ckpt, step: Optional[int]):
        """Restore one step from `ckpt` by whatever path its format
        wants: shard-native entries restore NATIVELY (each process reads
        only its own/needed shard rows, re-sliced onto the live layout);
        orbax entries ride the legacy template path."""
        if step is None:
            step = ckpt.latest_step()
        if step is None:
            return None
        if ckpt.entry_format(step) == "sharded" and (
            self._sharded_opt or self._cross_step
        ):
            return self._restore_native(ckpt, int(step))
        # replicated target (or legacy payload): the template path's
        # reconstruction is the replicated view the target needs anyway
        snap = ckpt.restore(
            self._replicated_template_state(),
            step=int(step),
            carry_template=self._carry_template(),
        )
        return self._localize_restored_carry(snap)

    def _localize_restored_carry(self, snap):
        """The template restore path hands back the carry with GLOBAL
        batch rows; `train_epoch._globalize` expects THIS process's local
        block on a multi-host mesh (native restores already produce it).
        A row-count mismatch means the world changed — re-initialize the
        epoch's hidden state, exactly the native path's rule."""
        if (
            snap is None or snap.carry is None
            or jax.process_count() == 1 or not self.meta.has_carry
        ):
            return snap
        template = self._carry_template()
        local = int(jax.tree_util.tree_leaves(template)[0].shape[0])
        have = int(jax.tree_util.tree_leaves(snap.carry)[0].shape[0])
        if have != local * jax.process_count():
            self.log.warning(
                "carry in checkpoint covers %d global batch rows, this "
                "run wants %d: re-initializing the epoch's hidden state "
                "(params/opt state restore exactly)",
                have, local * jax.process_count(),
            )
            snap.carry = None
            return snap
        my_runs = self._carry_runs_by_process(have).get(
            jax.process_index(), []
        )
        if not my_runs:
            snap.carry = None
            return snap
        snap.carry = jax.tree_util.tree_map(
            lambda a: np.concatenate(
                [np.asarray(a)[s:e] for s, e in my_runs]
            )
            if len(my_runs) != 1
            else np.asarray(a)[my_runs[0][0] : my_runs[0][1]],
            snap.carry,
        )
        return snap

    def _restore_native(self, ckpt, step: int) -> Optional[Snapshot]:
        """Shard-native restore onto the live sharded layout: per-leaf
        re-slice from the manifest — works across world sizes, merge
        schedules, and comm_ops without materializing a world-sized
        buffer or a fully-replicated copy of any sharded leaf."""
        from mgwfbp_tpu.parallel.allreduce import (
            ShardedOptState,
            ShardedParams,
        )

        src = ckpt.open_sharded(step)
        mismatches = ckpt._diff_leaf_docs(
            src.leaves, self._params_template, "params"
        )
        if mismatches:
            from mgwfbp_tpu.checkpoint import CheckpointRestoreError

            raise CheckpointRestoreError(
                ckpt._drift_message(step, mismatches),
                mismatches=mismatches,
            )
        optim = self.reducer.optim
        dst = optim.manifest_layout()
        dst_dtypes = [
            np.dtype(jnp.dtype(d)) for d in dst["group_dtypes"]
        ]
        rows = self._local_needed_rows()
        meta = src.meta
        # optimizer slot-count drift fails HERE, named — not as a
        # misleading missing-file error (too many slots) or a silent
        # drop of saved state (too few)
        src_kind = src.section_kind("opt")
        if src_kind == "sharded":
            src_slots = src.opt_slots()
        else:
            src_slots = len(
                (src.manifest.get("opt") or {}).get("slot_leaf_index")
                or []
            )
        if src_slots != optim.num_slots:
            from mgwfbp_tpu.checkpoint import CheckpointRestoreError

            raise CheckpointRestoreError(
                f"cannot restore checkpoint step {step}: it carries "
                f"{src_slots} optimizer slot(s) but the current "
                f"optimizer uses {optim.num_slots} — optimizer config "
                "drift (momentum/adam changed between the saving and "
                "restoring run)"
            )
        # optimizer slots: re-sliced rows -> sharded global arrays
        slots = []
        for s in range(optim.num_slots):
            bufs = src.read_rows(
                "opt", s, dst["leaf_slots"], dst["shard_sizes"],
                dst_dtypes, rows,
            )
            slots.append(tuple(
                self._rows_to_global(
                    bufs[gi], rows, optim.world, dst["shard_sizes"][gi],
                )
                for gi in range(len(bufs))
            ))
        count = jnp.asarray(int(meta.get("opt_count", 0)), jnp.int32)
        opt_state = ShardedOptState(
            count=self._replicate_onto_mesh(count), slots=tuple(slots),
        )
        # params: the cross-step carry re-slices like a slot; in-step
        # lowerings keep the replicated tree
        if self._cross_step:
            bufs = src.read_rows(
                "params", None, dst["leaf_slots"], dst["shard_sizes"],
                dst_dtypes, rows,
            )
            params = ShardedParams(tuple(
                self._rows_to_global(
                    bufs[gi], rows, optim.world, dst["shard_sizes"][gi],
                )
                for gi in range(len(bufs))
            ))
        else:
            treedef = jax.tree_util.tree_structure(self._params_template)
            params = self._replicate_onto_mesh(
                jax.tree_util.tree_unflatten(
                    treedef,
                    [
                        src.read_leaf("params", j)
                        for j in range(len(src.leaves))
                    ],
                )
            )
        # batch stats / rng / step counter: replicated bookkeeping
        bs_docs = src.section_docs("batch_stats")
        bs_diff = ckpt._diff_leaf_docs(
            bs_docs, self.state.batch_stats, "batch_stats"
        )
        if bs_diff:
            from mgwfbp_tpu.checkpoint import CheckpointRestoreError

            raise CheckpointRestoreError(
                ckpt._drift_message(step, bs_diff), mismatches=bs_diff
            )
        batch_stats = self._replicate_onto_mesh(
            jax.tree_util.tree_unflatten(
                jax.tree_util.tree_structure(self.state.batch_stats),
                [
                    src.read_leaf("batch_stats", j)
                    for j in range(len(bs_docs))
                ],
            )
        )
        rng = self.state.rng
        if src.manifest.get("rng") is not None:
            rng = self._replicate_onto_mesh(jnp.asarray(
                np.asarray(src.manifest["rng"], np.uint32).reshape(
                    rng.shape
                ),
                rng.dtype,
            ))
        state = self.state.replace(
            step=self._replicate_onto_mesh(jnp.asarray(
                int(meta.get("train_step", meta.get("iteration", step))),
                self.state.step.dtype,
            )),
            params=params,
            batch_stats=batch_stats,
            opt_state=opt_state,
            rng=rng,
        )
        carry = self._native_carry(src)
        entry = ckpt._index.get(str(step)) or ckpt._heal_sharded_entry(
            step
        )
        return Snapshot(
            state=state,
            epoch=int(entry.get("epoch", meta.get("epoch", 0))),
            iteration=int(meta.get("iteration", step)),
            epoch_step=int(meta.get("epoch_step", 0)),
            mid_epoch=bool(entry.get(
                "mid_epoch", meta.get("mid_epoch", False)
            )),
            carry=carry,
            native=True,
            manifest_meta=meta,
        )

    def _rows_to_global(
        self, block: np.ndarray, rows: list[int], world: int, shard: int,
    ) -> jax.Array:
        """Local (len(rows), shard) rows -> the (world, shard) global
        array sharded P(axes) on the live mesh; each addressable device
        gets exactly its row."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        sharding = NamedSharding(self.mesh, P(self.reducer.optim.axes))
        row_pos = {r: i for i, r in enumerate(rows)}
        arrays = []
        for dev, idx in sharding.addressable_devices_indices_map(
            (world, shard)
        ).items():
            r = int(idx[0].start or 0)
            arrays.append(
                jax.device_put(block[row_pos[r]][None, :], dev)
            )
        return jax.make_array_from_single_device_arrays(
            (world, shard), sharding, arrays
        )

    def _native_carry(self, src):
        """This process's local carry block from a shard-native source,
        or None when the model is carry-free, the save had none, or the
        global batch changed (an elastic resize re-initializes the
        epoch's hidden state — batch semantics changed with the world)."""
        cdoc = src.carry_doc()
        if cdoc is None or not self.meta.has_carry:
            return None
        template = self._carry_template()
        t_leaves = jax.tree_util.tree_leaves(template)
        mult = jax.process_count()
        want_rows = int(t_leaves[0].shape[0]) * mult
        have_rows = int(cdoc["leaves"][0]["shape"][0])
        if want_rows != have_rows:
            self.log.warning(
                "carry in checkpoint covers %d global batch rows, the "
                "resized run wants %d: re-initializing the epoch's "
                "hidden state (params/opt state restore exactly)",
                have_rows, want_rows,
            )
            return None
        # this process's rows under the CURRENT sharding, in global
        # order — the exact runs `_globalize` will lay back out (they
        # interleave across processes on a multi-slice data sharding)
        my_runs = self._carry_runs_by_process(want_rows).get(
            jax.process_index(), []
        )
        if not my_runs:
            return None

        def read_leaf(li):
            pieces = [
                src.read_carry_range(li, a, b) for a, b in my_runs
            ]
            return (
                np.concatenate(pieces) if len(pieces) > 1 else pieces[0]
            )

        return jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(template),
            [read_leaf(li) for li in range(len(cdoc["leaves"]))],
        )

    def _maybe_resume(self) -> None:
        snap = None
        if self.checkpointer is not None:
            snap = self._restore_step(self.checkpointer, None)
        # graft: group-uniform -- checkpoint visibility is uniform on the shared checkpoint FS (the commit barrier publishes the sidecar before any process proceeds)
        if snap is None and self.checkpointer is not None and (
            _elastic_resume_enabled()
        ):
            # relaunched at a different world size under the supervisor's
            # resize policy: the checkpoint lives under the OLD world's
            # tag directory — find it and re-shard (ISSUE 13)
            if self._resume_cross_world():
                return
        if snap is not None:
            self._apply_snapshot(snap, "resumed")
            return
        if self._pretrain_init():
            return

    # -- supervisor-driven elastic resize (ISSUE 13) ---------------------
    def _sibling_resume_candidates(self) -> list[tuple[int, int, str]]:
        """(latest step, world, tag dir name) for every sibling tag under
        the checkpoint root that differs from this run's tag ONLY in its
        worker count and has committed snapshots — the candidates a
        resized relaunch may continue from."""
        from mgwfbp_tpu.checkpoint import peek_steps

        root = self.config.checkpoint_dir
        own = self.config.tag()
        parts = own.split("-")
        try:
            i = parts.index(f"n{self.data_size}")
        except ValueError:
            return []
        out = []
        try:
            names = os.listdir(root)
        except OSError:
            return []
        for name in names:
            q = name.split("-")
            if len(q) != len(parts) or q[:i] != parts[:i] \
                    or q[i + 1:] != parts[i + 1:]:
                continue
            if not (q[i].startswith("n") and q[i][1:].isdigit()):
                continue
            world = int(q[i][1:])
            if world == self.data_size:
                continue
            steps = peek_steps(os.path.join(root, name))
            if steps:
                out.append((steps[-1], world, name))
        return sorted(out)

    def _resume_cross_world(self) -> bool:
        """Resume from a sibling tag written at a DIFFERENT world size:
        re-shard the snapshot onto the live layout (shard-native
        manifests re-slice per leaf; legacy replicated payloads restore
        through the template path, which is world-independent by
        construction), continue the LR schedule from the manifest's
        anchor, and record the transition as a `resize` event. Returns
        True when a sibling snapshot was applied."""
        best = self._sibling_resume_candidates()
        step, old_world = (best[-1][0], best[-1][1]) if best else (-1, -1)
        if coord.process_count() > 1:
            # one agreed choice: the scan is filesystem state; process
            # 0's answer is the group's answer
            step = int(coord.broadcast_flag(float(step)))
            old_world = int(coord.broadcast_flag(float(old_world)))
        if step < 0 or old_world < 0:
            return False
        parts = self.config.tag().split("-")
        i = parts.index(f"n{self.data_size}")
        parts[i] = f"n{old_world}"
        sibling = os.path.join(self.config.checkpoint_dir, "-".join(parts))
        ckpt = Checkpointer(sibling)
        try:
            snap = self._restore_step(ckpt, step)
        finally:
            ckpt.close()
        if snap is None:
            return False
        # continue the LR schedule from the OLD run's anchor: the
        # step->epoch divisor may change with the world size, and the
        # schedule must continue smoothly (exactly update_nworker's
        # in-place arithmetic, reconstructed from the manifest)
        meta = snap.manifest_meta or {}
        old_nbpe = int(meta.get("steps_per_epoch", 0) or 0)
        if old_nbpe > 0:
            anchor_step = int(meta.get("sched_step_offset", 0))
            anchor_epoch = float(meta.get("sched_epoch_offset", 0.0))
            step_now = int(snap.iteration)
            new_epoch_off = anchor_epoch + (
                step_now - anchor_step
            ) / old_nbpe
            new_nbpe = max(self._steps_per_epoch(), 1)
            if (
                abs(new_epoch_off - step_now / new_nbpe) > 1e-12
                or old_nbpe != new_nbpe
            ):
                self._sched_epoch_offset = new_epoch_off
                self._sched_step_offset = step_now
                self._build_optimizer()
                # the sharded update interprets the OptimSpec baked into
                # the reducer; same solve inputs -> same layout, so the
                # restored shards stay valid under the rebuilt reducer
                self.reducer = self._build_reducer(
                    self._profile_backward_enabled
                )
                self._build_steps()
        self._apply_snapshot(
            snap, f"resumed after resize ({old_world} -> {self.data_size})"
        )
        self._emit_event(
            "resize",
            old_world=int(old_world),
            new_world=int(self.data_size),
            schedule_source="relaunch-reshard",
            num_groups=(
                self.reducer.layout.num_groups
                if self.reducer is not None else 0
            ),
        )
        self.log.warning(
            "elastic resize: resumed iteration %d from %s (world %d -> "
            "%d; state re-sharded onto the live layout)",
            snap.iteration, sibling, old_world, self.data_size,
        )
        return True

    def _pretrain_init(self) -> bool:
        if self.config.pretrain:
            # --pretrain initializes weights AND epoch/iter counters from
            # another run (reference dl_trainer.py:307-312 restores
            # {'state','epoch','iter'}; dist_trainer.py:36-39 broadcasts the
            # counters). Optimizer state starts fresh — the reference never
            # saves it.
            pre = self.load_checkpoint(self.config.pretrain)
            pre_params = pre.state.params
            if self._cross_step:
                # the live params are the sharded carry; re-scatter the
                # restored canonical tree onto it
                if jax.process_count() > 1:
                    pre_params = self.reducer.optim.scatter_params_onto(
                        pre_params, self.mesh
                    )
                else:
                    pre_params = self.reducer.optim.scatter_params(
                        pre_params
                    )
            self.state = self.state.replace(
                step=pre.state.step,
                params=pre_params,
                batch_stats=pre.state.batch_stats,
            )
            self.start_epoch = pre.epoch + 1
            self.iteration = pre.iteration
            self.log.info(
                "initialized from pretrain dir %s (epoch %d, iter %d)",
                self.config.pretrain, pre.epoch, pre.iteration,
            )
            return True
        return False

    def fit(self, num_epochs: Optional[int] = None) -> dict:
        """Run `num_epochs` epochs from wherever we are (resume-aware); with
        None, run through config.max_epochs (absolute, reference
        MAX_EPOCHS semantics)."""
        cfg = self.config
        end = (
            self.start_epoch + num_epochs
            if num_epochs is not None
            else cfg.max_epochs
        )
        metrics: dict = {}
        # progress watchdog (failure detection, utils/watchdog.py): armed
        # only when MGWFBP_WATCHDOG_S is set — a wedged device makes
        # runtime calls block silently forever; this logs (and optionally
        # aborts) instead
        from mgwfbp_tpu.utils.watchdog import ProgressWatchdog

        try:
            # stalls also land in the telemetry stream (structured
            # watchdog_stall events), greppable next to the step records
            with ProgressWatchdog(on_stall=self._on_watchdog_stall) as wd:
                self._watchdog = wd if wd.enabled else None
                # SIGTERM/SIGINT -> graceful drain for the whole fit
                self._arm_signals()
                if cfg.autotune and self.autotune_report is None:
                    # closed-loop tuning phase: the first few real steps
                    # race candidate schedules (cache hit skips the race)
                    self.autotune()
                if (
                    self.telemetry is not None
                    # single-process only: per-process traces diverge and
                    # the traced steps sync the device — on a group the
                    # overlap accounting stays on the cost model instead
                    and jax.process_count() == 1
                    and self._measured_group_times is None
                    and os.environ.get("MGWFBP_TELEMETRY_TRACE") == "1"
                ):
                    # opt-in: trace-attribute per-group comm from a couple
                    # of live steps BEFORE the epoch loop (this one syncs;
                    # the loop itself never does)
                    self._measure_group_times_live()
                metrics = self._fit_epochs(self.start_epoch, end, cfg)
        except coord.CoordinationTimeout as ct:
            # a peer is dead or wedged: every further collective —
            # including the checkpoint barrier — would hang, so record
            # the failure and exit DRAIN-LESS (train_cli maps this to
            # rc 75; the supervisor heals from the last committed step)
            self._emit_event(
                "failure", **{"class": "coordination"},
                target=f"p{jax.process_index()}",
                step=int(self.iteration), op=ct.op,
            )
            self.log.error(
                "coordination timeout in %r at step %d: %s",
                ct.op, self.iteration, ct,
            )
            raise
        finally:
            self._disarm_signals()
            self._watchdog = None
        if self.checkpointer is not None:
            self.checkpointer.wait()
        return metrics

    def _fit_epochs(self, start: int, end: int, cfg) -> dict:
        metrics: dict = {}
        epoch = start
        while epoch < end:
            try:
                train_metrics = self.train_epoch(epoch)
            except _RollbackRequested as rb:
                # K consecutive non-finite steps: restore the last
                # checkpoint and continue from its exact position
                # graft: group-uniform -- the rollback target is broadcast-agreed from p0
                epoch = self._rollback(rb)
                continue
            metrics = {"train": train_metrics}
            if self.writer is not None:
                self.writer.add_scalars("epoch", train_metrics, epoch)
                self.writer.add_scalar(
                    "epoch/lr",
                    float(self.epoch_schedule(jnp.asarray(float(epoch)))),
                    epoch,
                )
            if (epoch + 1) % cfg.eval_every_epochs == 0:
                eval_metrics = self.evaluate()
                metrics["eval"] = eval_metrics
                self.log.info(
                    "epoch %d eval: %s", epoch,
                    ", ".join(f"{k} {v:.4f}" for k, v in eval_metrics.items()),
                )
                if self.writer is not None:
                    self.writer.add_scalars("eval", eval_metrics, epoch)
            if (epoch + 1) % cfg.checkpoint_every_epochs == 0:
                wd = getattr(self, "_watchdog", None)
                if wd is not None:
                    from mgwfbp_tpu.utils.watchdog import CHECKPOINT_ALLOW_S

                    wd.beat(f"checkpoint epoch {epoch}",
                            allow_s=CHECKPOINT_ALLOW_S)
                self.save(epoch)
            if self._agreed_preempt(at_boundary=True):
                # the signal landed outside the step loop (eval or
                # checkpoint phase); drain at the epoch boundary
                self._graceful_drain_boundary(epoch)
            epoch += 1
        return metrics


# the `import` span of the process's set-up record ends with this module
phases.note_imported()
