"""Unit tests for the MG-WFBP merge solver against hand-computed cases and the
reference algorithm's documented semantics (reference
distributed_optimizer.py:140-261)."""

import functools

import numpy as np
import pytest

from mgwfbp_tpu.parallel.costmodel import AlphaBeta
from mgwfbp_tpu.parallel.solver import (
    LayerSpec,
    build_schedule,
    check_unique,
    mgwfbp_groups,
    single_group,
    threshold_groups,
)


def linear_cost(alpha, beta):
    return lambda nbytes: alpha + beta * nbytes


class TestThresholdPolicy:
    def test_zero_threshold_is_wfbp(self):
        # threshold=0 => one group per layer (reference: no merging).
        assert threshold_groups([10, 20, 30], 0) == [[0], [1], [2]]

    def test_packs_until_cumulative_reaches_threshold(self):
        # Group closes on the layer whose arrival reaches the threshold
        # (inclusive), matching reference :148-159.
        assert threshold_groups([5, 5, 5, 5], 10) == [[0, 1], [2, 3]]
        assert threshold_groups([5, 5, 5], 11) == [[0, 1, 2]]
        assert threshold_groups([20, 5, 5], 10) == [[0], [1, 2]]

    def test_trailing_partial_group(self):
        assert threshold_groups([8, 8, 8], 16) == [[0, 1], [2]]

    def test_single_group(self):
        assert single_group([1, 2, 3]) == [[0, 1, 2]]
        assert single_group([]) == []


class TestMgwfbpScan:
    def test_all_merge_when_comm_dominates(self):
        # Huge alpha: every wait is cheaper than a new startup -> one group.
        sizes = [100, 100, 100, 100]
        tb = [1e-3] * 4
        groups = mgwfbp_groups(sizes, tb, alpha=10.0, cost=linear_cost(10.0, 1e-9))
        assert groups == [[0, 1, 2, 3]]

    def test_no_merge_when_comm_is_free(self):
        # Zero-cost comm: each collective finishes before the next gradient
        # arrives -> pure WFBP.
        sizes = [100, 100, 100]
        tb = [1e-3] * 3
        groups = mgwfbp_groups(sizes, tb, alpha=0.0, cost=lambda b: 0.0)
        assert groups == [[0], [1], [2]]

    def test_rule_a_merges_backlogged_layer(self):
        # Comm of group 0 occupies the link past the next two arrivals; the
        # pending group cannot start before they arrive -> rule (a) merges.
        sizes = [1000, 10, 10]
        tb = [1.0, 0.001, 0.001]
        # cost: first group takes 5s, so arrivals at 1.001 and 1.002 happen
        # while the link is busy and their comm could not have started.
        def cost(nbytes):
            return 5.0 if nbytes >= 4000 else 0.5

        groups = mgwfbp_groups(sizes, tb, alpha=0.0, cost=cost)
        # layer 0 keeps the link until 6.0; layers 1,2 arrive at ~1.0 and
        # must queue; since start > ready(next), they merge together.
        assert groups[0] == [0]
        assert groups[1] == [1, 2]

    def test_rule_b_wait_cheaper_than_alpha(self):
        # Next gradient arrives just after comm could start; the wait
        # (r_next - start) is below alpha -> merge saves a startup.
        sizes = [100, 100]
        tb = [1.0, 0.01]
        alpha = 0.1  # wait of 0.01 < alpha 0.1
        groups = mgwfbp_groups(
            sizes, tb, alpha=alpha, cost=linear_cost(alpha, 1e-6)
        )
        assert groups == [[0, 1]]

    def test_rule_b_wait_more_expensive_than_alpha(self):
        sizes = [100, 100]
        tb = [1.0, 0.5]
        alpha = 0.1  # wait of 0.5 > alpha -> keep separate...
        # ...but only matters if comm is still in flight at arrival: make
        # comm long enough.
        groups = mgwfbp_groups(sizes, tb, alpha=alpha, cost=linear_cost(alpha, 1e-2))
        assert groups == [[0], [1]]

    def test_merge_cascade_with_repriced_mass(self):
        # After a merge the group's comm time is re-predicted from the
        # combined payload (reference __merge, :194-201). Hand-traced case:
        #   arrivals ready = [1.0, 1.05, 3.05, 3.06]
        #   i=0: wait 0.05 < alpha 0.06            -> merge {0,1}; repriced
        #        tc = 0.06 + 8000B*4e-4 = 3.26
        #   i=1: wait 2.0 > alpha                  -> close [0,1]
        #   i=2: merged comm holds the link until 4.31 > ready[3]
        #        -> rule (a) merge {2,3}
        sizes = [1000, 1000, 10, 10]
        tb = [1.0, 0.05, 2.0, 0.01]
        groups = mgwfbp_groups(
            sizes, tb, alpha=0.06, cost=linear_cost(0.06, 4e-4)
        )
        assert groups == [[0, 1], [2, 3]]

    def test_empty_and_mismatch(self):
        assert mgwfbp_groups([], [], alpha=0.0, cost=lambda b: 0.0) == []
        with pytest.raises(ValueError):
            mgwfbp_groups([1, 2], [0.1], alpha=0.0, cost=lambda b: 0.0)

    def test_groups_partition_all_layers(self):
        rng = np.random.RandomState(42)
        for _ in range(20):
            L = rng.randint(1, 60)
            sizes = rng.randint(1, 10_000_000, size=L).tolist()
            tb = np.abs(rng.normal(1e-3, 1e-3, size=L)).tolist()
            alpha = float(abs(rng.normal(1e-4, 1e-4)))
            beta = float(abs(rng.normal(1e-10, 1e-10)))
            groups = mgwfbp_groups(sizes, tb, alpha=alpha, cost=linear_cost(alpha, beta))
            flat = [i for g in groups for i in g]
            assert flat == list(range(L))  # contiguous, ordered, complete


class TestBuildSchedule:
    def _layers(self, sizes):
        return [LayerSpec(name=f"l{i}", size=s) for i, s in enumerate(sizes)]

    def test_mgwfbp_beats_or_matches_extremes(self):
        # The adaptive schedule's predicted total time must never lose to
        # both baselines it interpolates between (WFBP and single-group) —
        # the paper's core claim, evaluated on the reference's own cost
        # regime (56GbIB alpha-beta, resnet-like size distribution).
        rng = np.random.RandomState(7)
        ab = AlphaBeta(9.75367204301171e-05, 3.0568230536676206e-10)
        sizes = rng.choice(
            [1_000, 50_000, 200_000, 2_000_000, 500], size=50
        ).tolist()
        tb = np.abs(rng.normal(4e-4, 2e-4, size=50)).tolist()
        layers = self._layers(sizes)
        adaptive = build_schedule(layers, tb, policy="mgwfbp", cost_model=ab)
        wfbp = build_schedule(layers, tb, policy="wfbp", cost_model=ab)
        single = build_schedule(layers, tb, policy="single", cost_model=ab)
        best_baseline = min(wfbp.predicted_total_time, single.predicted_total_time)
        assert adaptive.predicted_total_time <= best_baseline * 1.0001

    def test_threshold_policy_via_build(self):
        layers = self._layers([5, 5, 5, 5])
        s = build_schedule(layers, None, policy="threshold", threshold=10)
        assert s.groups == ((0, 1), (2, 3))
        assert np.isnan(s.predicted_total_time)

    def test_named_groups(self):
        layers = self._layers([5, 5])
        s = build_schedule(layers, None, policy="single")
        assert s.named_groups() == [["l0", "l1"]]

    def test_mgwfbp_requires_inputs(self):
        with pytest.raises(ValueError):
            build_schedule(self._layers([5]), None, policy="mgwfbp")
        with pytest.raises(ValueError):
            build_schedule(self._layers([5]), [0.1], policy="nope")


def test_check_unique():
    check_unique(["a", "b"])
    with pytest.raises(ValueError):
        check_unique(["a", "a"])


class TestGammaAndAuto:
    """Per-collective fixed overhead (gamma) + the simulate-and-argmin
    'auto' policy (VERDICT r3 #1: the cost model must price what splitting
    actually costs, and the chosen schedule must beat every baseline it
    simulates)."""

    def _layers(self, sizes):
        return [LayerSpec(name=f"l{i}", size=s) for i, s in enumerate(sizes)]

    def test_simulate_groups_charges_gamma_per_group(self):
        from mgwfbp_tpu.parallel.solver import simulate_groups

        sizes_b = [100, 100, 100]
        tb = [1e-3, 1e-3, 1e-3]
        cost = linear_cost(0.0, 0.0)
        t1, n1, _ = simulate_groups([[0, 1, 2]], sizes_b, tb, cost, gamma=1e-3)
        t3, n3, _ = simulate_groups([[0], [1], [2]], sizes_b, tb, cost, gamma=1e-3)
        assert t3 - t1 == pytest.approx(2e-3)
        assert n3 - n1 == pytest.approx(2e-3)

    def test_gamma_widens_merge_rule(self):
        # Gaps of 2e-4 exceed alpha=1e-4 (no merge), but with gamma=5e-4 the
        # wait is cheaper than alpha+gamma, so everything merges.
        sizes = [10, 10, 10, 10]
        tb = [2e-4] * 4
        cost = linear_cost(1e-4, 0.0)
        split = mgwfbp_groups(sizes, tb, alpha=1e-4, cost=cost)
        merged = mgwfbp_groups(sizes, tb, alpha=1e-4, cost=cost, gamma=5e-4)
        assert len(merged) < len(split)
        assert merged == [[0, 1, 2, 3]]

    def test_auto_never_loses_to_any_candidate(self):
        from mgwfbp_tpu.parallel.solver import auto_groups, simulate_groups

        rng = np.random.RandomState(3)
        for gamma in (0.0, 2e-4, 1e-3):
            L = 40
            sizes = rng.choice([500, 50_000, 400_000, 2_000_000], size=L).tolist()
            tb = np.abs(rng.normal(4e-4, 2e-4, size=L)).tolist()
            ab = AlphaBeta(1e-4, 3e-10, gamma)
            groups, detail = auto_groups(
                sizes, tb, alpha=ab.alpha, cost=ab.predict, gamma=gamma
            )
            nbytes = [s * 4 for s in sizes]
            t_auto, _, _ = simulate_groups(groups, nbytes, tb, ab.predict, gamma)
            bases = [
                [[i] for i in range(L)],
                [list(range(L))],
                mgwfbp_groups(sizes, tb, alpha=ab.alpha, cost=ab.predict,
                              gamma=gamma),
            ]
            # every geometric threshold candidate, too: auto's argmin must be
            # <= each NAMED candidate (VERDICT r4 #2 regression pin)
            th = 1 << 14
            while th < sum(sizes):
                bases.append(threshold_groups(sizes, th))
                th <<= 1
            for base in bases:
                t_base, _, _ = simulate_groups(nbytes and base, nbytes, tb,
                                               ab.predict, gamma)
                assert t_auto <= t_base * 1.0001
            assert detail

    def test_auto_threshold_dedup_by_shape_not_count(self):
        # ADVICE r4 #1: sizes where th=65536 -> [[0],[1,2,3,4]] and
        # th=131072 -> [[0,1,2],[3,4]] have the SAME group count but
        # different boundaries; count-dedup dropped the latter, and under
        # this cost model the dropped shape is strictly optimal.
        from mgwfbp_tpu.parallel.solver import auto_groups, threshold_groups

        sizes = [100_000, 16_384, 16_384, 16_384, 16_384]
        tb = [1e-3, 1e-4, 1e-4, 1e-4, 1e-4]
        ab = AlphaBeta(1e-5, 1e-10, 0.0)
        assert threshold_groups(sizes, 65536) == [[0], [1, 2, 3, 4]]
        assert threshold_groups(sizes, 131072) == [[0, 1, 2], [3, 4]]
        groups, detail = auto_groups(
            sizes, tb, alpha=ab.alpha, cost=ab.predict, overlap=0.5
        )
        assert groups == [[0, 1, 2], [3, 4]]
        assert detail == "threshold:131072"

    def test_auto_picks_single_when_gamma_dominates(self):
        # Cheap comm + heavy per-group overhead: fusing everything wins even
        # though gradient gaps far exceed alpha (the greedy scan cannot get
        # there; the measured CPU-8 regime of VERDICT r3 Weak #1).
        from mgwfbp_tpu.parallel.solver import auto_groups

        sizes = [1000] * 30
        tb = [5e-3] * 30  # gaps >> alpha
        groups, detail = auto_groups(
            sizes, tb, alpha=1e-5, cost=linear_cost(1e-5, 1e-11), gamma=1e-3
        )
        assert groups == [list(range(30))]
        assert detail == "single"

    def test_auto_splits_when_overlap_wins(self):
        # Expensive comm, zero gamma: hiding comm behind backward requires
        # splitting, so auto must NOT pick single.
        from mgwfbp_tpu.parallel.solver import auto_groups

        sizes = [1_000_000] * 20
        tb = [2e-3] * 20
        groups, detail = auto_groups(
            sizes, tb, alpha=1e-5, cost=linear_cost(1e-5, 1e-9), gamma=0.0
        )
        assert len(groups) > 1

    def test_build_schedule_auto_sets_detail_and_requires_inputs(self):
        ab = AlphaBeta(1e-4, 3e-10, 1e-4)
        layers = self._layers([100, 100, 100])
        s = build_schedule(layers, [1e-3] * 3, policy="auto", cost_model=ab)
        assert s.policy_detail
        assert s.num_groups >= 1
        with pytest.raises(ValueError):
            build_schedule(layers, None, policy="auto")

    def test_gamma_profile_roundtrip(self, tmp_path):
        from mgwfbp_tpu.parallel.costmodel import (
            TwoLevelAlphaBeta, load_profile, save_profile,
        )

        p = str(tmp_path / "prof.json")
        save_profile(p, AlphaBeta(1e-4, 2e-10, 3e-4))
        m = load_profile(p)
        assert m.gamma == pytest.approx(3e-4)
        # pre-gamma profiles (no gamma key) load with gamma=0
        import json as _json

        d = _json.loads(open(p).read())
        del d["gamma"]
        open(p, "w").write(_json.dumps(d))
        assert load_profile(p).gamma == 0.0
        # two-level: one hier collective pays both levels' overhead once
        two = TwoLevelAlphaBeta(
            ici=AlphaBeta(1e-5, 1e-11, 2e-4),
            dcn=AlphaBeta(1e-4, 1e-10, 3e-4),
            ici_size=4, dcn_size=2,
        )
        assert two.gamma == pytest.approx(5e-4)
        save_profile(p, two)
        assert load_profile(p).gamma == pytest.approx(5e-4)

    def test_gamma_idle_rule_does_not_cascade_pipelined_groups(self):
        # Review finding (r4): large well-pipelined groups (comm ~ fits the
        # inter-arrival gap) must NOT collapse into a late mega-group just to
        # save slivers of gamma — the deferred transmit (tc - alpha) exceeds
        # gamma, so rule (c) must not fire.
        sizes = [2_500_000] * 10           # tc = alpha + 10 ms each
        tb = [10.3e-3] * 10                # arrivals just after comm drains
        cost = linear_cost(1e-4, 1e-9)
        groups = mgwfbp_groups(sizes, tb, alpha=1e-4, cost=cost, gamma=1e-3)
        assert len(groups) == 10
        # while SMALL deferred transmits (tc - alpha < gamma) still merge
        # across an idle gap
        small = [1000] * 10                # tc - alpha = 1 us << gamma
        groups = mgwfbp_groups(small, tb, alpha=1e-4, cost=cost, gamma=1e-3)
        assert len(groups) == 1

    def test_overlap_capability_blends_timelines(self):
        # overlap=1: reference async timeline; overlap=0: fully serialized
        # (bwd + all comm); the CPU-mesh regime where single-group wins.
        from mgwfbp_tpu.parallel.solver import auto_groups, simulate_groups

        sizes_b = [4000] * 10
        tb = [5e-3] * 10
        cost = linear_cost(0.0, 1e-7)  # 0.4 ms per small group, beta-only
        groups = [[i] for i in range(10)]
        t1, n1, c1 = simulate_groups(groups, sizes_b, tb, cost, overlap=1.0)
        t0, n0, c0 = simulate_groups(groups, sizes_b, tb, cost, overlap=0.0)
        assert c1 == pytest.approx(c0)
        # hidden: only the tail group's comm sticks out; serial: all of it
        assert t1 == pytest.approx(0.05 + 0.0004)
        assert t0 == pytest.approx(0.05 + 10 * 0.0004)
        th, _, _ = simulate_groups(groups, sizes_b, tb, cost, overlap=0.5)
        assert t1 < th < t0
        # with zero overlap and a gamma cost, auto must fuse to one group:
        # beta cost is grouping-invariant, so only gamma differentiates
        sizes = [1000] * 10
        g, detail = auto_groups(
            sizes, tb, alpha=0.0, cost=cost, gamma=3e-4, overlap=0.0
        )
        assert detail == "single"

    def test_pack_beta_charges_multi_member_groups_only(self):
        from mgwfbp_tpu.parallel.solver import simulate_groups

        sizes_b = [1000, 1000, 4000]
        tb = [1e-3] * 3
        cost = linear_cost(0.0, 0.0)
        # singleton groups: no pack cost at all
        t_singles, _, _ = simulate_groups(
            [[0], [1], [2]], sizes_b, tb, cost, pack_beta=1e-6
        )
        t_base, _, _ = simulate_groups([[0], [1], [2]], sizes_b, tb, cost)
        assert t_singles == pytest.approx(t_base)
        # fusing {0,1} pays pack_beta * 2000; fusing all pays * 6000
        t_pair, _, _ = simulate_groups(
            [[0, 1], [2]], sizes_b, tb, cost, pack_beta=1e-6
        )
        t_all, _, _ = simulate_groups(
            [[0, 1, 2]], sizes_b, tb, cost, pack_beta=1e-6
        )
        assert t_pair - t_base == pytest.approx(2000e-6)
        assert t_all - t_base == pytest.approx(6000e-6)

    def test_isolate_bigs_candidate_shape_and_auto_pick(self):
        from mgwfbp_tpu.parallel.solver import (
            auto_groups, isolate_bigs_groups,
        )

        nbytes = [100, 100, 10_000, 100, 100, 10_000, 100]
        assert isolate_bigs_groups(nbytes, 1000) == [
            [0, 1], [2], [3, 4], [5], [6],
        ]
        # regime where isolating bigs is optimal: zero-overlap link, cheap
        # wire, real gamma (fuse smalls) AND real pack cost (isolate bigs)
        sizes = [25, 25, 2500, 25, 25, 2500, 25]  # elems (x4 bytes)
        tb = [1e-3] * 7
        groups, detail = auto_groups(
            sizes, tb, alpha=0.0, cost=linear_cost(0.0, 1e-9),
            gamma=1e-3, overlap=0.0, pack_beta=1e-6,
        )
        assert detail.startswith("isolate-bigs")
        for g in groups:
            if any(sizes[i] > 250 for i in g):
                assert len(g) == 1  # bigs ride alone


# --------------------------------------------------------------------------
# The policies on the reference's own cluster tables, priced by the same
# simulate_groups the trainer's schedule choice runs on. Computed here from
# the package's constants (costmodel's 56GbIB / 10GbE tables at P=16, the
# reference's deployment scale) and the layer sizes of our own models.
# --------------------------------------------------------------------------

# total backward seconds per model in the reference's GPU-era regime
# (resnet50 ~0.13 s of a ~0.2 s iteration on P100s; the others by depth
# and parameter volume); each layer's share follows the volume prior
_TB_TOTAL_S = {
    "resnet20": 0.012, "resnet56": 0.036, "resnet50": 0.13, "vgg16": 0.3,
}
_REFERENCE_LINKS = ("56GbIB", "10GbE")


@functools.lru_cache(maxsize=None)
def _layer_table(model_name):
    """(element counts, item sizes, bytes, backward seconds) of a model's
    gradients in arrival order, from shapes alone (nothing is computed)."""
    import math

    import jax
    import jax.numpy as jnp

    from mgwfbp_tpu import models as zoo
    from mgwfbp_tpu.parallel.allreduce import arrival_order

    model, meta = zoo.create_model(model_name)
    x = jnp.zeros((1,) + tuple(meta.input_shape), meta.input_dtype)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), x, train=False)
    )
    paths = jax.tree_util.tree_flatten_with_path(shapes["params"])[0]
    perm = arrival_order(
        len(paths), names=[jax.tree_util.keystr(kp) for kp, _ in paths]
    )
    sizes = [int(math.prod(paths[i][1].shape)) for i in perm]
    items = [int(paths[i][1].dtype.itemsize) for i in perm]
    nbytes = [s * it for s, it in zip(sizes, items)]
    total = float(sum(sizes))
    tb = [_TB_TOTAL_S[model_name] * s / total for s in sizes]
    return sizes, items, nbytes, tb


@pytest.mark.parametrize("link", _REFERENCE_LINKS)
@pytest.mark.parametrize("model_name", ["resnet20", "resnet50", "vgg16"])
def test_auto_never_loses_on_the_reference_cluster_tables(model_name, link):
    """The paper's core claim on its own clusters: the argmin 'auto'
    schedule is no slower than the adaptive scan, WFBP or one fused
    all-reduce, and the scan itself is no slower than the two static
    baselines (gamma 0 and full overlap, the reference's assumptions, so
    only the merge rule is compared)."""
    from mgwfbp_tpu.parallel.costmodel import lookup_alpha_beta
    from mgwfbp_tpu.parallel.solver import auto_groups, simulate_groups

    sizes, items, nbytes, tb = _layer_table(model_name)
    ab = lookup_alpha_beta(link, 16)

    def total(groups):
        return simulate_groups(groups, nbytes, tb, ab.predict)[0]

    auto, _ = auto_groups(
        sizes, tb, alpha=ab.alpha, cost=ab.predict, itemsize=items
    )
    t = {
        "auto": total(auto),
        "mgwfbp": total(mgwfbp_groups(
            sizes, tb, alpha=ab.alpha, cost=ab.predict, itemsize=items
        )),
        "wfbp": total(threshold_groups(sizes, 0)),
        "single": total(single_group(sizes)),
    }
    for policy in ("mgwfbp", "wfbp", "single"):
        assert t["auto"] <= t[policy] * 1.0001, (policy, t)
    assert t["mgwfbp"] <= min(t["wfbp"], t["single"]) * 1.0001, t


@pytest.mark.parametrize("model_name", ["resnet20", "resnet56", "vgg16"])
def test_merge_decision_is_safe_under_gamma_error(model_name):
    """gamma (the per-collective overhead outside the link) is the term a
    calibration gets least right. Solve with gamma off by -30% and +30%,
    price what was chosen at the nominal gamma: the regret stays under 2%
    of the step, so a schedule that flips inside the band sits on a
    plateau of the argmin. The nominal gamma is the link's own alpha, the
    regime AlphaBeta.gamma's note names (fixed costs that rival alpha)."""
    from mgwfbp_tpu.parallel.costmodel import lookup_alpha_beta
    from mgwfbp_tpu.parallel.solver import auto_groups, simulate_groups

    sizes, items, nbytes, tb = _layer_table(model_name)
    for link in _REFERENCE_LINKS:
        ab = lookup_alpha_beta(link, 16)
        gamma = ab.alpha

        def priced_at_nominal(scale):
            groups, _ = auto_groups(
                sizes, tb, alpha=ab.alpha, cost=ab.predict,
                itemsize=items, gamma=gamma * scale,
            )
            return simulate_groups(groups, nbytes, tb, ab.predict, gamma)[0]

        nominal = priced_at_nominal(1.0)
        for scale in (0.7, 1.3):
            regret = priced_at_nominal(scale) - nominal
            assert 0.0 <= regret < 0.02 * nominal, (link, scale, regret)
