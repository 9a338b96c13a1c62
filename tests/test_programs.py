"""ops/programs.py: the one registry of what the entry points of `ops/` trace.
A call of each op notes its own way and kernel programs and nothing under
another op (the held experts' block, op `groups`, beside the two row
permutations it calls), traced for the CPU and traced as for a TPU; `counted`
notes for a cached trace whatever its first trace noted, for every op at
once, and a step built a second time in one process reads what the first
read; and the two rules of where shared code lives (no decoder imports
another's file, no op imports `ops/blockattn.py`) hold for every file, by its
imports."""

import ast
import pathlib

import jax
import jax.numpy as jnp
import pytest

import mgwfbp_tpu
from mgwfbp_tpu.models import lm_parts
from mgwfbp_tpu.ops import (
    blockattn,
    deltarule,
    groupmm,
    programs,
    rowperm,
    selscan,
    shortconv,
    ssd,
    streams,
)

PACKAGE = pathlib.Path(mgwfbp_tpu.__file__).parent
DECODERS = ("mellum", "granite", "laguna", "phi4flash", "qwen3next",
            "xing4", "nemotronh")
BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


def a(*shape, dtype=BF16):
    return jax.ShapeDtypeStruct(shape, dtype)


T = 2 * selscan._ROWS
# op -> (one call of its entry point, arguments at a shape its kernels take,
# what that call notes traced for the CPU, and traced as for a TPU: there
# with the kernel programs it and its transposes need; for an entry point
# that calls another op's, what those note the two ways)
CALLS = {
    "attention": (
        lambda q, kv: blockattn.blockwise_attention(q, kv, kv),
        (a(1, 256, 2, 64), a(1, 256, 1, 64)),
        {"kernel": 0, "blocks": 1}, {"kernel": 1, "blocks": 0}),
    "experts": (
        groupmm.grouped_product,
        (a(512, 256), a(2, 256, 128), a(2, dtype=I32)),
        {"kernel": 0, "ragged": 1, "programs": 0},
        {"kernel": 1, "ragged": 0, "programs": 3}),
    "rows": (
        rowperm.combine_rows,
        (a(2048, 256), a(2048, dtype=I32), a(2048, dtype=I32),
         a(256, 8, dtype=F32), a(4, dtype=I32)),
        {"rows_held": 0, "rows_all": 1, "rows_programs": 0},
        {"rows_held": 1, "rows_all": 0, "rows_programs": 1}),
    # k 8 over 4 held: the block is sized by its shapes alone, on any
    # platform; the experts here hand the rows back
    "groups": (
        lambda u, idx, weights: lm_parts._grouped_experts(
            u, idx, weights, 4, 0, lambda rows, sizes: (rows, None)),
        (a(256, 256), a(256, 8, dtype=I32), a(256, 8, dtype=F32)),
        {"bounded": 1, "whole": 0}, {"bounded": 1, "whole": 0},
        {"rows": ({"rows_held": 0, "rows_all": 2, "rows_programs": 0},
                  {"rows_held": 1, "rows_all": 1, "rows_programs": 1})}),
    "scan": (
        selscan.selective_scan,
        (a(1, T, 256, dtype=F32), a(1, T, 256, dtype=F32),
         a(256, 8, dtype=F32), a(1, T, 8, dtype=F32), a(1, T, 8, dtype=F32)),
        {"kernel": 0, "plain": 1, "programs": 0},
        {"kernel": 1, "plain": 0, "programs": 2}),
    "delta": (
        deltarule.gated_delta_rule,
        (a(1, 256, 8, 128), a(1, 256, 8, 128), a(1, 256, 8, 128),
         a(1, 256, 8, dtype=F32), a(1, 256, 8, dtype=F32)),
        {"kernel": 0, "plain": 1, "programs": 0},
        {"kernel": 1, "plain": 0, "programs": 3}),
    "conv": (
        shortconv.causal_conv_silu,
        (a(2, shortconv._ROWS, 128), a(4, 128, dtype=F32),
         a(128, dtype=F32)),
        {"kernel": 0, "plain": 1, "programs": 0},
        {"kernel": 1, "plain": 0, "programs": 2}),
    "streams": (
        streams.write_streams,
        (a(4, 1, 128, 128), a(4, 4, 1, 128, dtype=F32),
         a(4, 1, 128, dtype=F32), a(1, 128, 128)),
        {"kernel": 0, "plain": 1, "programs": 0},
        {"kernel": 1, "plain": 0, "programs": 2}),
    "ssd": (
        ssd.ssd_scan,
        (a(1, 256, 2, 64), a(1, 256, 2, dtype=F32), a(2, dtype=F32),
         a(1, 256, 128), a(1, 256, 128)),
        {"kernel": 0, "plain": 1, "programs": 0},
        {"kernel": 1, "plain": 0, "programs": 2}),
}
NOTHING = {
    op: dict.fromkeys((*ways, *([count] if count else ())), 0)
    for op, (ways, count) in programs.OPS.items()}


def test_the_table_of_calls_covers_every_op_and_every_record():
    assert set(CALLS) == set(programs.OPS)
    assert sorted(op for _, ops, _ in programs.RECORDS for op in ops) \
        == sorted(programs.OPS)


@pytest.mark.parametrize("tpu", [False, True], ids=["cpu", "traced-for-tpu"])
@pytest.mark.parametrize("op", list(CALLS))
def test_a_call_notes_its_own_way_and_nothing_under_another_op(
        monkeypatch, op, tpu):
    """Nothing runs: a kernel traced for a TPU cannot on the CPU. The one
    `traced_for_tpu` steers every op's entry point."""
    fn, args, plain, kernel, *calls = CALLS[op]
    if tpu:
        monkeypatch.setattr(programs, "traced_for_tpu", lambda: True)
    before = programs.LOWERED.copy()
    # a fresh function each time: a cached trace calls nothing, notes nothing
    jaxpr = jax.make_jaxpr(lambda *x: fn(*x))(*args)
    made = programs.lowered_since(before)
    beside = {
        of: ways[tpu] for of, ways in (calls[0] if calls else {}).items()}
    assert made == {**NOTHING, **beside, op: kernel if tpu else plain}
    assert ("pallas_call" in str(jaxpr)) == tpu


def two_layers():
    """A step of two EQUAL layers under `jax.checkpoint`, each an attention
    core, a short convolution, a selective scan and a chunked state-space
    scan: jax traces the first and
    finds the second in its cache of traces. A function of its own each
    call, so that no other test's trace is found there."""

    def layer(x, w, a_log):
        b, t, d = x.shape
        q = x.reshape(b, t, 2, d // 2)
        x = x + blockattn.blockwise_attention(q, q, q).reshape(b, t, d)
        x = shortconv.causal_conv_silu(x, w)
        y, _ = selscan.selective_scan(
            x, jax.nn.softplus(x), -jnp.exp(a_log), x[..., :4], x[..., 4:8])
        heads, _, _ = ssd.ssd_scan(
            q, jax.nn.softplus(x[..., :2]), -jnp.exp(a_log[:2, 0]),
            x[..., :4], x[..., 4:8], chunk=16)
        return y + heads.reshape(b, t, d)

    one = programs.counted(jax.checkpoint(layer))

    def step(x, ws, a_logs):
        for w, a_log in zip(ws, a_logs):
            x = one(x, w, a_log)
        return jnp.sum(x)

    args = (a(1, 32, 16, dtype=F32), [a(4, 16, dtype=F32)] * 2,
            [a(16, 4, dtype=F32)] * 2)
    return step, args


TWO_LAYERS = {
    **NOTHING, "attention": {"kernel": 0, "blocks": 2},
    "scan": {"kernel": 0, "plain": 2, "programs": 0},
    "conv": {"kernel": 0, "plain": 2, "programs": 0},
    "ssd": {"kernel": 0, "plain": 2, "programs": 0}}


def test_a_cached_trace_notes_every_op_its_first_trace_noted():
    """The second layer is never traced, and is noted as the first was:
    attention core, convolution and both scans alike, by ONE `counted`."""
    step, args = two_layers()
    calls = []
    real = blockattn._blocks

    def spied(*args, **kw):
        calls.append(1)
        return real(*args, **kw)

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(blockattn, "_blocks", spied)
        before = programs.LOWERED.copy()
        jax.make_jaxpr(jax.grad(step))(*args)
    assert len(calls) == 1  # the entry point's Python ran for one layer only
    assert programs.lowered_since(before) == TWO_LAYERS


def test_a_step_built_a_second_time_reads_what_the_first_read():
    """The same step built again in one process (a Trainer after
    `update_nworker`, a harness that checks a second seed) finds EVERY layer
    in jax's cache of traces and runs no entry point: each is noted as its
    first trace was, so the second build's `*_program` records are the
    first's and not 0 + 0 (PERF.md section 7)."""
    step, args = two_layers()
    built = []
    for _ in range(2):
        noted: dict = {}
        jax.jit(jax.grad(programs.traced_into(step, noted))).lower(*args)
        built.append(noted)
    assert built == [TWO_LAYERS, TWO_LAYERS]


def imports_of(path: pathlib.Path) -> set:
    """The modules a file imports, as dotted names: `from a import b` gives
    `a` and `a.b` (b may be a module)."""
    found = set()
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            found.update(alias.name for alias in node.names)
        elif isinstance(node, ast.ImportFrom) and node.module:
            found.add(node.module)
            found.update(f"{node.module}.{alias.name}" for alias in node.names)
    return found


MODEL_FILES = sorted(
    p.name for p in (PACKAGE / "models").glob("*.py") if p.name != "__init__.py")
OP_FILES = sorted(
    p.name for p in (PACKAGE / "ops").glob("*.py")
    if p.name not in ("__init__.py", "blockattn.py"))


@pytest.mark.parametrize("name", MODEL_FILES)
def test_no_model_file_imports_a_decoders_file(name):
    """What more than one decoder uses is in models/lm_parts.py: a decoder's
    file is imported by models/__init__.py alone."""
    theirs = {f"mgwfbp_tpu.models.{d}" for d in DECODERS}
    assert not imports_of(PACKAGE / "models" / name) & theirs


@pytest.mark.parametrize("name", OP_FILES)
def test_no_op_imports_the_attention_core(name):
    """What the ops share (`traced_for_tpu`, the registry) is in
    ops/programs.py, which imports none of them."""
    found = imports_of(PACKAGE / "ops" / name)
    assert "mgwfbp_tpu.ops.blockattn" not in found
    if name == "programs.py":
        assert not {m for m in found if m.startswith("mgwfbp_tpu")}
