"""The compiled step as one map (`profiling.hlo_instruction_map`,
`step_map`) and what is reduced by it: a kernel's custom call printed over
several lines, the TPU's asynchronous collective fusions with their merge
group, a fusion's body counted once; the scopes every model declares against
the `jax.named_scope`s its module enters; the process's record, built when
asked and replaced by a rebuilt step; the `optimizer` scope as metadata alone;
the `step_scopes` record through `events.py` and `tools/telemetry_report.py`.
Nothing here compares a measured time with a number."""

import importlib
import inspect
import logging
import os
import re

import jax
import jax.numpy as jnp
import pytest

from mgwfbp_tpu import profiling
from mgwfbp_tpu.config import make_config
from mgwfbp_tpu.models import lm_parts
from mgwfbp_tpu.parallel.mesh import MeshSpec, make_mesh
from mgwfbp_tpu.telemetry import events_of, phases, read_events
from mgwfbp_tpu.train import step as step_module

# as the TPU compiler prints a four-chip step (PR 29): the large buckets'
# all-reduces as async collective fusions, whose called computations hold the
# bare opcode and whose start carries no metadata of its own (asked of the
# compiler for a v5e here); a Pallas kernel over three lines; a loop round a
# fusion
TPU_TEXT = """\
HloModule jit_step, is_scheduled=true

%fused_computation.7 (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  ROOT %all-reduce.9 = f32[8]{0} all-reduce(f32[8]{0} %p0), channel_id=7, metadata={op_name="jit(step)/mgwfbp_group0001/psum"}
}

%fused_computation.9 (p1: (f32[8], f32[8], u32[])) -> f32[8] {
  %p1 = (f32[8]{0}, f32[8]{0}, u32[]) parameter(0)
  ROOT %gte.1 = f32[8]{0} get-tuple-element(%p1), index=1
}

%fused_computation.3 (p2: f32[8]) -> f32[8] {
  %p2 = f32[8]{0} parameter(0)
  ROOT %exp.1 = f32[8]{0} exponential(%p2), metadata={op_name="jit(step)/jvp(M)/attn_full/exp"}
}

%body (t: (f32[8])) -> (f32[8]) {
  %t = (f32[8]{0}) parameter(0)
  %fusion.4 = f32[8]{0} fusion(%t), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(step)/jvp(M)/attn_full/while/body/exp"}
  ROOT %tuple.1 = (f32[8]{0}) tuple(%fusion.4)
}

ENTRY %main (p: f32[8], q: f32[4]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %async-collective-start.1 = (f32[8]{0}, f32[8]{0}, u32[]) fusion(f32[8]{0} %p), kind=kCustom, calls=%fused_computation.7
  %while.2 = (f32[8]{0}) while(%p), condition=%cond, body=%body, metadata={op_name="jit(step)/jvp(M)/attn_full/while"}
  %splash_mha.7 = (f32[8]{0:T(8,128)(2,1)}, bf16[8]{0}) custom-call(%q, %k), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={
"xprof_metadata":"{\\"block_q\\": 1024}"
}}, metadata={op_name="jit(step)/transpose(jvp(M))/attn_full/pallas_call" stack_frame_id=20}, backend_config={"custom_call_config":{}}
  %bare.1 = f32[8]{0} copy(%x)
  %async-collective-done.1 = f32[8]{0} fusion((f32[8]{0}, f32[8]{0}, u32[]) %async-collective-start.1), kind=kCustom, calls=%fused_computation.9, metadata={op_name="jit(step)/mgwfbp_group0001/psum"}
  %fusion.5 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fc, metadata={op_name="jit(step)/mgwfbp_group0000/concatenate"}
  %all-reduce-start.1 = f32[4]{0} all-reduce-start(f32[4]{0} %fusion.5), channel_id=2, metadata={op_name="jit(step)/mgwfbp_group0000/psum"}
  %all-reduce-done.1 = f32[4]{0} all-reduce-done(f32[4]{0} %all-reduce-start.1), metadata={op_name="jit(step)/mgwfbp_group0000/psum"}
  %psum.4 = f32[4]{0} all-reduce(f32[4]{0} %m), channel_id=3, metadata={op_name="jit(step)/metrics_reduce/psum"}
  %fusion.6 = f32[8]{0} fusion(%u), kind=kLoop, calls=%fc, metadata={op_name="jit(step)/optimizer/mul"}
  ROOT %fusion.8 = f32[8]{0} fusion(%b), kind=kLoop, calls=%fc, metadata={op_name="jit(step)/jvp(M)/add"}
}
"""
SCOPES = {"attn_full": "attention", "optimizer": "update",
          "metrics_reduce": "update"}


@pytest.fixture(scope="module")
def instructions():
    return profiling.hlo_instruction_map(TPU_TEXT)


def test_a_kernel_over_several_lines_keeps_its_scope_in_the_one_parser(
        instructions):
    """The `kernel_metadata` JSON breaks the custom call over three lines and
    its op_name stands on the last; the instruction after it has none of its
    own and inherits none; a tuple's shape with a tiled layout in it does not
    hide the opcode."""
    kernel = instructions["splash_mha.7"]
    assert kernel.op_name.endswith("attn_full/pallas_call")
    assert kernel.kind == "compute"
    assert instructions["bare.1"] == profiling.Instruction(None, "compute")
    assert profiling.classify(kernel.op_name, list(SCOPES)) == (
        "attn_full", "backward")


def test_an_async_collective_fusion_pair_is_start_and_done_with_its_group(
        instructions):
    kinds = {name: i.kind for name, i in instructions.items()}
    assert kinds["async-collective-start.1"] == "collective_start"
    assert kinds["async-collective-done.1"] == "collective_done"
    assert kinds["all-reduce-start.1"] == "collective_start"
    assert kinds["all-reduce-done.1"] == "collective_done"
    assert kinds["psum.4"] == "collective"  # by its opcode, not its name
    assert kinds["while.2"] == "container"
    # the start takes the name stack of the `-done` of its number
    assert instructions["async-collective-start.1"].op_name \
        == instructions["async-collective-done.1"].op_name
    assert profiling.hlo_collective_scope_map(TPU_TEXT) == {
        "async-collective-start.1": "mgwfbp_group0001",
        "async-collective-done.1": "mgwfbp_group0001",
        "fusion.5": "mgwfbp_group0000",
        "all-reduce-start.1": "mgwfbp_group0000",
        "all-reduce-done.1": "mgwfbp_group0000",
    }
    assert profiling.collective_counts(instructions) == {
        "collectives": 3, "async_collectives": 2}
    assert profiling.hlo_collective_counts(TPU_TEXT) == {
        "collectives": 3, "async_collectives": 2}


def test_a_fusions_body_is_not_counted_twice(instructions):
    """The all-reduce inside the async fusion's called computation and the
    exponential inside the loop fusion's are their fusions' bodies; the loop's
    own body is kept (its events stand in the trace beside the loop's)."""
    assert not {"all-reduce.9", "gte.1", "exp.1", "p0"} & set(instructions)
    assert instructions["fusion.4"].op_name.endswith("while/body/exp")


def test_split_trace_on_the_hand_made_step(instructions):
    """Two steps of one chip: the loop is skipped for its body; the exchange
    holds what is under a group scope (the packing fusion too) or of
    collective kind (the metrics' all-reduce); `wait` the `-done` halves and
    the synchronous one; `calls` the three started a step."""
    step = [
        ("async-collective-start.1", 0, 10), ("while.2", 10, 70),
        ("fusion.4", 12, 30), ("fusion.4", 45, 30), ("splash_mha.7", 80, 50),
        ("bare.1", 130, 2), ("async-collective-done.1", 132, 40),
        ("%fusion.5 = f32[8]{0} fusion(%a)", 172, 6),
        ("all-reduce-start.1", 178, 4), ("all-reduce-done.1", 182, 20),
        ("psum.4", 202, 8), ("fusion.6", 210, 16), ("fusion.8", 226, 4),
        ("unknown.9", 230, 1),
    ]
    events = step + [(n, s + 1000, d) for n, s, d in step]
    events.append(("fusion.8", 5000, 999))  # outside the window
    out = profiling.split_trace(
        events, profiling.StepMap(instructions, SCOPES), (0, 2000), 2)
    ns = 1e-6
    assert out["events"] == 2 * 13
    assert out["scopes"] == {
        "attn_full": [pytest.approx(60 * ns), pytest.approx(50 * ns)],
        "(no metadata)": [pytest.approx(3 * ns), 0.0],
        "optimizer": [pytest.approx(16 * ns), 0.0],
        "(model, no scope)": [pytest.approx(4 * ns), 0.0],
    }
    assert out["groups"] == [pytest.approx(30 * ns), pytest.approx(50 * ns)]
    assert out["exchange"] == {
        "device_ms": pytest.approx(88 * ns), "wait_ms": pytest.approx(68 * ns),
        "calls": 3.0}
    assert out["total_ms"] == pytest.approx(221 * ns)
    assert out["top"][0] == [pytest.approx(60 * ns), "fusion.4", "attn_full"]
    sums = profiling.split_sums(out)
    assert sums["unscoped"] == pytest.approx(7 * ns)
    assert sums["update"] == pytest.approx(16 * ns)
    assert sums["forward"] == pytest.approx(64 * ns)
    assert sums["backward"] == pytest.approx(50 * ns)
    assert profiling.layer_ms(out, "attention") == pytest.approx(110 * ns)
    # the two ways the parts add up to the whole
    assert (profiling.layer_ms(out, "attention") + sums["unscoped"]
            + sums["update"] + out["exchange"]["device_ms"]
            ) == pytest.approx(out["total_ms"])
    assert (sums["forward"] + sums["backward"] + sums["update"]
            + out["exchange"]["device_ms"] + sums["no_metadata"]
            ) == pytest.approx(out["total_ms"])


# ---- the scopes are the models' to declare --------------------------------

MODELS = [
    ("mellum", "Mellum2LM"), ("granite", "Granite4HLM"),
    ("laguna", "LagunaLM"), ("phi4flash", "Phi4FlashLM"),
    ("qwen3next", "Qwen3NextLM"), ("xing4", "Xing4LM"),
    ("nemotronh", "NemotronHLM"),
]
LAYERS = {lm_parts.ATTENTION, lm_parts.EXPERTS, lm_parts.STATE_SPACE,
          lm_parts.LINEAR_ATTENTION, lm_parts.STREAMS, lm_parts.MLP,
          lm_parts.HEAD}


def entered(source: str) -> set:
    """The names of the `jax.named_scope`s a piece of source enters."""
    return {
        name for call in re.finditer(r"named_scope\((.*?)\):", source, re.S)
        for name in re.findall(r'"(\w+)"', call.group(1))}


@pytest.mark.parametrize("module,cls", MODELS)
def test_a_models_declared_scopes_are_the_scopes_its_module_enters(
        module, cls):
    """Exactly: what the module's own source enters and what the functions
    of `lm_parts` it calls enter; each with a layer of PERF.md's map."""
    mod = importlib.import_module(f"mgwfbp_tpu.models.{module}")
    want = entered(inspect.getsource(mod))
    for function, scopes in lm_parts.SCOPES.items():
        if hasattr(mod, function):
            want |= set(scopes)
    declared = getattr(mod, cls).scopes
    assert want and set(declared) == want
    assert set(declared.values()) <= LAYERS


def test_lm_parts_declares_what_its_functions_enter():
    for function, scopes in lm_parts.SCOPES.items():
        assert set(scopes) == entered(
            inspect.getsource(getattr(lm_parts, function))), function
    assert entered(inspect.getsource(lm_parts)) == {
        scope for scopes in lm_parts.SCOPES.values() for scope in scopes}


def test_the_step_declares_the_scopes_it_enters():
    assert set(step_module.STEP_SCOPES) == entered(
        inspect.getsource(step_module))
    for module, cls in MODELS:  # no name twice: a model's would shadow it
        scopes = getattr(importlib.import_module(
            f"mgwfbp_tpu.models.{module}"), cls).scopes
        assert not set(step_module.STEP_SCOPES) & set(scopes)


# ---- the process's record -------------------------------------------------


def _cfg(tmp_path, **kw):
    base = dict(
        lr=0.01, max_epochs=2, logdir=str(tmp_path), checkpoint_dir=None,
        seed=3, batch_size=8, num_batches_per_epoch=3, telemetry=True,
    )
    base.update(kw)
    return make_config("lenet", **base)


def test_the_map_is_not_built_until_asked_and_a_rebuilt_step_replaces_it(
        tmp_path, caplog, monkeypatch):
    """One device, as the ten one-chip cells: an untraced run's log and
    `setup` record hold no map-building span, the record pins no device
    buffer, the first request builds the map and the second finds it; a
    rebuilt step (`update_nworker`) drops it, and with gradient collectives
    to count the read that counts them fills it, under the `step_map`
    span."""
    from mgwfbp_tpu.train.trainer import Trainer

    monkeypatch.setattr(profiling, "_step", None)
    monkeypatch.setattr(profiling, "_step_map", None)
    built = []
    real = profiling.hlo_instruction_map
    monkeypatch.setattr(
        profiling, "hlo_instruction_map",
        lambda text: built.append(len(text)) or real(text))
    cfg = _cfg(tmp_path)
    mesh = make_mesh(MeshSpec(data=1), devices=jax.devices()[:1])
    # the Trainer's logger keeps its records to its own handlers
    monkeypatch.setattr(
        logging.getLogger("mgwfbp.trainer"), "handlers",
        [*logging.getLogger("mgwfbp.trainer").handlers, caplog.handler])
    with caplog.at_level(logging.INFO):
        t = Trainer(cfg, mesh=mesh, synthetic_data=True,
                    profile_backward=False)
        assert profiling.step_map() is None  # no step dispatched yet
        t.train_epoch(0)
        t.train_epoch(1)
    assert not built and "step map:" not in caplog.text
    noted = profiling._step
    assert noted["jitted"] is t.train_step
    assert noted["logdir"] == str(tmp_path)
    assert noted["scopes"] == dict.fromkeys(
        step_module.STEP_SCOPES, profiling.UPDATE_LAYER)
    assert not any(isinstance(leaf, jax.Array)
                   for leaf in jax.tree_util.tree_leaves(noted["args"]))
    recs = read_events(os.path.join(str(tmp_path), cfg.tag(),
                                    "telemetry.jsonl"))
    (setup,) = events_of(recs, "setup")
    assert "program_read" in setup["spans"]
    assert "step_map" not in setup["spans"]
    # the first request lowers nothing and compiles nothing: the noted
    # arguments are the dispatch's own (one device's batch is uncommitted
    # and described so), so jax hands back the executable it holds
    compiled = []

    def listen(name, secs, **kw):
        if name in (phases._LOWERING_EVENT, phases._COMPILE_EVENT):
            compiled.append(name)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        with caplog.at_level(logging.INFO):
            first = profiling.step_map()
    finally:
        from jax._src import monitoring

        monitoring.unregister_event_duration_listener(listen)
    assert not compiled
    assert len(built) == 1 and "step map:" in caplog.text
    assert first.hlo_bytes == built[0] and first.build_s > 0
    assert first.logdir == str(tmp_path)
    assert first.scopes == noted["scopes"]
    assert profiling.step_map() is first and len(built) == 1
    # a rebuilt step: eight devices, gradient collectives to count
    t.update_nworker(8)
    t.train_epoch(2)
    t.close()
    assert len(built) == 2
    # in the run's own log file (the resized run's: the tag names the world),
    # before the count it was read for
    with open(os.path.join(str(tmp_path), cfg.tag(), "train.log")) as f:
        log = f.read()
    assert log.count("step map: ") == 1
    assert log.index("step map: ") < log.index("merge schedule: the compiled")
    rebuilt = profiling.step_map()
    assert rebuilt is not first and len(built) == 2
    assert profiling.collective_counts(rebuilt.instructions) == {
        k: t._step_program[k] for k in ("collectives", "async_collectives")}
    assert t._step_program["collectives"] > 0
    recs = read_events(os.path.join(str(tmp_path), cfg.tag(),
                                    "telemetry.jsonl"))
    assert events_of(recs, "step_program")


def test_the_optimizer_scope_is_metadata_only(tmp_path, monkeypatch):
    """The lowered step with debug info stripped is the same text with and
    without the `optimizer` scope, that is, the parent's: jax's persistent
    compile cache keys on that (a warm cache may hand back an executable
    whose text lacks the scope, and `update_device_ms` reads the same either
    way)."""
    import contextlib

    from mgwfbp_tpu.train.trainer import Trainer

    def asm(lowered, debug_info: bool) -> str:
        return lowered.compiler_ir().operation.get_asm(
            enable_debug_info=debug_info)

    mesh = make_mesh(MeshSpec(data=1), devices=jax.devices()[:1])
    t = Trainer(_cfg(tmp_path, telemetry=False), mesh=mesh,
                synthetic_data=True, profile_backward=False)
    try:
        t.train_epoch(0)
        args = profiling._step["args"]
        scoped = t.train_step.lower(*args)
        real = jax.named_scope
        monkeypatch.setattr(
            jax, "named_scope", lambda name: contextlib.nullcontext()
            if name == "optimizer" else real(name))
        t._build_steps()
        bare = t.train_step.lower(*args)
    finally:
        monkeypatch.setattr(jax, "named_scope", real)
        t.close()
    # in a name stack (this test's own name is in the call stacks)
    assert "/optimizer/" in asm(scoped, True)
    assert "/optimizer/" not in asm(bare, True)
    assert "/finite_check/" in asm(bare, True)
    assert asm(scoped, False) == asm(bare, False)


# ---- the record through the stream and the report -------------------------


def test_a_step_scopes_record_round_trips_through_events_and_the_report(
        tmp_path, instructions):
    import telemetry_report
    from mgwfbp_tpu.telemetry.events import EVENT_TYPES, EventWriter

    assert EVENT_TYPES["step_scopes"] == (
        "step", "steps", "scopes", "groups", "exchange", "top")
    out = profiling.split_trace(
        [("fusion.4", 0, 3e6), ("splash_mha.7", 0, 5e6), ("psum.4", 0, 1e6),
         ("fusion.6", 0, 2e6), ("all-reduce-done.1", 0, 4e6)],
        profiling.StepMap(instructions, SCOPES))
    path = str(tmp_path / "telemetry.jsonl")
    w = EventWriter(path)
    fields = {k: out[k] for k in EVENT_TYPES["step_scopes"][2:]}
    w.emit("step_scopes", step=7, steps=1, layers=out["layers"], **fields)
    with pytest.raises(ValueError):
        w.emit("step_scopes", step=7, steps=1, scopes={})
    w.close()
    (rec,) = events_of(read_events(path), "step_scopes")
    assert {k: rec[k] for k in fields} == fields
    report = telemetry_report.format_report(read_events(path))
    assert "step by scope (profile window of 1 step(s) to step 7" in report
    assert ("15.000 ms of device ops a step in 0 events: attention 8.000, "
            "update 2.000, exchange 5.000; forward 3.000, backward 5.000, no "
            "metadata 0.000; the exchange waits 5.000 ms in 1 collective(s) "
            "a step; by group 4.000 0.000") in report
    assert re.search(r"attn_full +attention +3\.000 +5\.000 +8\.000", report)
    assert "5.000 splash_mha.7 [attn_full]" in report
