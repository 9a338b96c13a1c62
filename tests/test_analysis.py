"""Mutation tests for the static-analysis suite: every deliberately seeded
invariant violation must be caught with the RIGHT rule id, and the analyzer
must run clean on HEAD (the CI gate `tools/check.sh` depends on both)."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from mgwfbp_tpu.analysis import (
    collect_collectives,
    lint_source,
    trace_train_step,
    verify_jaxpr_against_reducer,
    verify_train_step,
)
from mgwfbp_tpu.analysis.rules import ERROR, RULES, has_errors
from mgwfbp_tpu.parallel.allreduce import make_merged_allreduce
from mgwfbp_tpu.parallel.mesh import DATA_AXIS, MeshSpec, make_mesh


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(MeshSpec(data=8, seq=1))


def _ids(findings):
    return {f.rule_id for f in findings}


# --------------------------------------------------------------------------
# AST lint: seeded tracing-unsafe patterns
# --------------------------------------------------------------------------

_TOY_MODULE = '''
import time
import numpy as np
import jax
import jax.numpy as jnp
from functools import partial

@partial(jax.jit, static_argnums=(1,))
def step(x, cfg, extras={}):
    t = time.time()
    noise = np.random.randn(4)
    if jnp.isnan(x).any():
        return x
    v = float(x)
    s = x.sum().item()
    return x + t + noise[0] + v + s

def helper(y):
    return time.time()  # NOT traced: must not fire

def scanned(carry, x):
    while jnp.abs(carry) > 1:
        carry = carry / 2
    return carry, x

out = jax.lax.scan(scanned, 0.0, None)
'''


def test_ast_lint_catches_each_seeded_violation():
    findings = lint_source(_TOY_MODULE, "toy.py")
    by_rule = {}
    for f in findings:
        by_rule.setdefault(f.rule_id, []).append(f)
    assert "JIT001" in by_rule  # time.time() in jitted step
    assert "JIT002" in by_rule  # np.random in jitted step
    assert "JIT003" in by_rule  # float()/.item() host round-trips
    assert len(by_rule["JIT003"]) == 2
    assert "JIT004" in by_rule  # if on jnp.isnan + while on jnp.abs
    assert len(by_rule["JIT004"]) == 2
    assert "JIT005" in by_rule  # mutable default on jitted fn
    # the untraced helper's time.time() must NOT be flagged
    assert all(f.line != _TOY_MODULE.splitlines().index(
        "    return time.time()  # NOT traced: must not fire") + 1
        for f in by_rule["JIT001"])


def test_ast_lint_noqa_suppression():
    src = (
        "import jax\nfrom functools import partial\n"
        "@partial(jax.jit)\n"
        "def f(x):\n"
        "    return float(x)  # graft: noqa[JIT003]\n"
    )
    assert lint_source(src, "t.py") == []
    # bare noqa suppresses everything; wrong id suppresses nothing
    src_wrong = src.replace("noqa[JIT003]", "noqa[JIT001]")
    assert _ids(lint_source(src_wrong, "t.py")) == {"JIT003"}
    src_bare = src.replace("noqa[JIT003]", "noqa")
    assert lint_source(src_bare, "t.py") == []


def test_ast_lint_clean_module_is_clean():
    src = (
        "import jax, jax.numpy as jnp\n"
        "from functools import partial\n"
        "@partial(jax.jit)\n"
        "def f(x, n=3):\n"
        "    y = jnp.where(x > 0, x, -x)\n"
        "    if n > 2:\n"  # static Python branch: legal
        "        y = y * 2\n"
        "    return y\n"
    )
    assert lint_source(src, "t.py") == []


def test_ast_lint_jit006_telemetry_in_traced_code():
    """JIT006: telemetry/logging emitters in a traced body run ONCE at
    trace time instead of per step — every flavour the project uses
    (print, logger methods, ScalarWriter, EventWriter.emit) must flag."""
    src = (
        "import jax\n"
        "def step(state, batch):\n"
        "    print('loss')\n"
        "    log.info('iter %d', 1)\n"
        "    self_writer = None\n"
        "    writer.add_scalar('train/loss', 1.0, 2)\n"
        "    telemetry.emit('step', step=1)\n"
        "    return state\n"
        "f = jax.jit(step)\n"
    )
    findings = lint_source(src, "t.py")
    assert _ids(findings) == {"JIT006"}
    assert len(findings) == 4


def test_ast_lint_jit006_spares_legit_calls():
    # jax.debug.print is a traced callback (legal, and separately policed
    # by the jaxpr pass SCH005 in the hot path); logging OUTSIDE traced
    # code is the normal idiom; a method named emit on a non-telemetry
    # receiver stays clean
    src = (
        "import jax\n"
        "def step(x):\n"
        "    jax.debug.print('x={}', x)\n"
        "    return x\n"
        "f = jax.jit(step)\n"
        "def untraced():\n"
        "    print('fine')\n"
        "    log.info('fine')\n"
        "def traced_other(x):\n"
        "    return sound.emit(x)\n"
        "g = jax.jit(traced_other)\n"
    )
    assert lint_source(src, "t.py") == []


def test_ast_lint_jit006_self_log_method():
    src = (
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    self.log.warning('hot path')\n"
        "    return x\n"
    )
    assert _ids(lint_source(src, "t.py")) == {"JIT006"}


# --------------------------------------------------------------------------
# jaxpr verifier: clean on HEAD across the policy surface
# --------------------------------------------------------------------------

@pytest.mark.parametrize("policy", ["wfbp", "single", "mgwfbp"])
def test_verifier_clean_on_head(policy):
    findings = verify_train_step("lenet", policy)
    assert findings == [], [f.format() for f in findings]


def test_verifier_clean_with_comm_dtype_wire_cast():
    findings = verify_train_step("lenet", "single", comm_dtype=jnp.bfloat16)
    assert findings == [], [f.format() for f in findings]


def test_verifier_sees_the_merge_groups():
    """Positive control: the pass must MATCH collectives, not trivially
    find nothing (a broken scope regex would 'pass' every check)."""
    closed, reducer, arr = trace_train_step("lenet", "wfbp")
    info = collect_collectives(closed)
    assert len(info["groups"]) == reducer.layout.num_groups > 1
    assert info["stray"] == []
    assert len(info["allowed"]) >= 1  # the metrics pmean


# --------------------------------------------------------------------------
# jaxpr verifier: seeded schedule violations
# --------------------------------------------------------------------------

def test_verifier_catches_dropped_leaf():
    closed, reducer, arr = trace_train_step("lenet", "mgwfbp")
    lay = reducer.layout
    groups = list(map(list, lay.groups))
    groups[-1].pop()  # the schedule "forgets" one gradient leaf
    doctored = dataclasses.replace(
        reducer,
        layout=dataclasses.replace(
            lay, groups=tuple(tuple(g) for g in groups)
        ),
    )
    findings = verify_jaxpr_against_reducer(closed, doctored, arr)
    assert "SCH003" in _ids(findings)
    assert has_errors(findings)


def test_verifier_catches_mixed_dtype_bucket():
    closed, reducer, arr = trace_train_step("lenet", "mgwfbp")
    lay = reducer.layout
    doctored = dataclasses.replace(
        reducer,
        layout=dataclasses.replace(
            lay, dtypes=(jnp.dtype(jnp.bfloat16),) + lay.dtypes[1:]
        ),
    )
    findings = verify_jaxpr_against_reducer(closed, doctored, arr)
    ids = _ids(findings)
    assert "SCH002" in ids  # collective dtype != claimed bucket dtype
    assert "SCH003" in ids  # bucket no longer homogeneous with its members


def test_verifier_catches_group_count_mismatch():
    # program traced with ONE fused group; expectation claims per-leaf groups
    closed, single_reducer, arr = trace_train_step("lenet", "single")
    wfbp_reducer = make_merged_allreduce(
        {"leaf%03d" % i: leaf for i, leaf in enumerate(arr)},
        axis_name=DATA_AXIS, policy="wfbp", perm=list(range(len(arr))),
    )
    findings = verify_jaxpr_against_reducer(closed, wfbp_reducer, arr)
    assert "SCH001" in _ids(findings)


def test_verifier_catches_stray_collective(mesh):
    tree = {"a": jnp.ones((8,), jnp.float32), "b": jnp.ones((4,), jnp.float32)}
    mar = make_merged_allreduce(tree, axis_name=DATA_AXIS, policy="single")

    def per_device(grads):
        grads = mar(grads)
        # the seeded violation: an undeclared all_gather in the hot path
        g = jax.lax.all_gather(grads["a"], DATA_AXIS)
        return {**grads, "a": g.mean(0)}

    fn = jax.jit(shard_map(
        per_device, mesh=mesh, in_specs=(P(),), out_specs=P(),
        check_vma=False,
    ))
    closed = jax.make_jaxpr(fn)(tree)
    arr = [jax.tree_util.tree_leaves(tree)[j] for j in mar.perm]
    findings = verify_jaxpr_against_reducer(
        closed, mar, arr, expect_donation=False
    )
    assert _ids(findings) == {"SCH004"}


def test_verifier_catches_host_callback(mesh):
    tree = {"a": jnp.ones((8,), jnp.float32)}
    mar = make_merged_allreduce(tree, axis_name=DATA_AXIS, policy="single")

    def per_device(grads):
        grads = mar(grads)
        jax.debug.print("grad[0] = {}", grads["a"][0])  # seeded violation
        return grads

    fn = jax.jit(shard_map(
        per_device, mesh=mesh, in_specs=(P(),), out_specs=P(),
        check_vma=False,
    ))
    closed = jax.make_jaxpr(fn)(tree)
    arr = [jax.tree_util.tree_leaves(tree)[j] for j in mar.perm]
    findings = verify_jaxpr_against_reducer(
        closed, mar, arr, expect_donation=False
    )
    assert _ids(findings) == {"SCH005"}


def test_verifier_catches_missing_donation():
    findings = verify_train_step(
        "lenet", "single", donate=False, expect_donation=True
    )
    assert _ids(findings) == {"SCH006"}


def test_verifier_catches_payload_size_mismatch():
    closed, reducer, arr = trace_train_step("lenet", "single")
    lay = reducer.layout
    doctored = dataclasses.replace(
        reducer,
        layout=dataclasses.replace(
            lay, group_sizes=(lay.group_sizes[0] + 128,)
            + lay.group_sizes[1:]
        ),
    )
    findings = verify_jaxpr_against_reducer(closed, doctored, arr)
    ids = _ids(findings)
    assert "SCH007" in ids


# --------------------------------------------------------------------------
# the CLI itself
# --------------------------------------------------------------------------

def test_cli_exits_zero_on_head(capsys):
    from mgwfbp_tpu.analysis.__main__ import main

    rc = main([])  # lint the package + verify wfbp/single/mgwfbp
    captured = capsys.readouterr()
    assert rc == 0, captured.out + captured.err
    assert "0 error(s)" in captured.err


def test_cli_nonzero_on_seeded_lint_error(tmp_path, capsys):
    bad = tmp_path / "bad.py"
    bad.write_text(
        "import time, jax\nfrom functools import partial\n"
        "@partial(jax.jit)\ndef f(x):\n    return x + time.time()\n"
    )
    from mgwfbp_tpu.analysis.__main__ import main

    rc = main(["--skip-jaxpr", str(bad)])
    captured = capsys.readouterr()
    assert rc == 1
    assert "JIT001" in captured.out


def test_ast_lint_static_argnums_params_are_not_traced():
    # int()/float() of a STATIC jit param is legal host code, not JIT003
    src = (
        "import jax\nfrom functools import partial\n"
        "@partial(jax.jit, static_argnums=(1,), static_argnames=('m',))\n"
        "def f(x, n, m=2):\n"
        "    return x * int(n) + float(m) + bool(x)\n"
    )
    findings = lint_source(src, "t.py")
    # only the bool(x) on the TRACED param remains
    assert [f.rule_id for f in findings] == ["JIT003"]
    assert "bool" in findings[0].message


def test_lint_paths_reports_missing_target(tmp_path):
    from mgwfbp_tpu.analysis.ast_lint import lint_paths

    findings = lint_paths([str(tmp_path / "no_such_dir_or_file")])
    assert _ids(findings) == {"JIT000"}
    # ... and so does the CLI (a typo'd path must not green the gate)
    from mgwfbp_tpu.analysis.__main__ import main

    assert main(["--skip-jaxpr", str(tmp_path / "nope")]) == 1


def test_cli_policies_whitespace_entries_ignored(capsys):
    from mgwfbp_tpu.analysis.__main__ import main

    rc = main(["--skip-lint", "--policies", "single, ,"])
    captured = capsys.readouterr()
    assert rc == 0, captured.out + captured.err


def test_verifier_skips_payload_size_for_compressor():
    from mgwfbp_tpu.parallel.compression import TopKCompressor

    tree = {"a": jnp.ones((64,), jnp.float32)}
    mar = make_merged_allreduce(
        tree, axis_name=DATA_AXIS, policy="single",
        compressor=TopKCompressor(density=0.25),
    )
    mesh = make_mesh(MeshSpec(data=8, seq=1))

    def per_device(grads):
        return mar(grads)

    fn = jax.jit(shard_map(
        per_device, mesh=mesh, in_specs=(P(),), out_specs=P(),
        check_vma=False,
    ))
    closed = jax.make_jaxpr(fn)(tree)
    arr = [jax.tree_util.tree_leaves(tree)[j] for j in mar.perm]
    findings = verify_jaxpr_against_reducer(
        closed, mar, arr, expect_donation=False
    )
    # top-k moves k < n elements; that must NOT read as SCH007
    assert "SCH007" not in _ids(findings), [f.format() for f in findings]


def test_verifier_allowed_scope_matching_is_segment_exact(mesh):
    # a scope merely CONTAINING an allowed token must not whitelist a
    # stray collective
    tree = {"a": jnp.ones((8,), jnp.float32)}
    mar = make_merged_allreduce(tree, axis_name=DATA_AXIS, policy="single")

    def per_device(grads):
        grads = mar(grads)
        with jax.named_scope("extra_metrics_reduce_v2"):
            g = jax.lax.all_gather(grads["a"], DATA_AXIS)
        return {**grads, "a": g.mean(0)}

    fn = jax.jit(shard_map(
        per_device, mesh=mesh, in_specs=(P(),), out_specs=P(),
        check_vma=False,
    ))
    closed = jax.make_jaxpr(fn)(tree)
    arr = [jax.tree_util.tree_leaves(tree)[j] for j in mar.perm]
    findings = verify_jaxpr_against_reducer(
        closed, mar, arr, expect_donation=False
    )
    assert "SCH004" in _ids(findings)


def test_layout_validate_reports_malformed_offsets():
    from mgwfbp_tpu.parallel.buckets import BucketLayout

    leaves = [jnp.ones((4,), jnp.float32), jnp.ones((2,), jnp.float32)]
    # offsets list shorter than the group: must report, not IndexError
    lay = BucketLayout(
        groups=((0, 1),), offsets=((0,),), group_sizes=(6,),
        dtypes=(jnp.dtype(jnp.float32),),
    )
    problems = lay.validate(leaves)
    assert any("offsets" in p for p in problems)


def test_rule_registry_consistent():
    for rid, rule in RULES.items():
        assert rule.id == rid
        assert rule.severity in (ERROR, "warning")
        assert rule.summary
