"""Ask the TPU's compiler, without a chip: whole step programs (ResNet-50 on
one chip, the merged exchange over four, the Qwen3-Next and Xing4.0 cells'
steps lowered, the Nemotron 3 Super cell's compiled).

libtpu is installed here and compiles for a chip that is described, not
attached (`topologies.get_topology_desc`, topology v5e:2x2), so what the
chip's compiler would refuse — a Pallas block the tiling rejects, a step
that does not fit 16 GB of HBM, a collective it cannot partition — fails
in tier-1 at no chip time. Nothing runs: a compile that passes says
nothing about results or speed, and is never reported as a chip run
(`chip_smoke.py` is the run).

Skipped where the topology cannot be described. The persistent compile
cache is off around these tests: an entry written for a described chip
cannot be read back without one, and the next compile would only warn.
The kernels are asked in files of their own, one a kind of program, so that
no one worker carries them all: tests/test_tpu_compile_attention.py,
_scans.py, _experts.py, _shortconv.py.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (
    Mesh,
    NamedSharding,
    PartitionSpec as P,
)

from mgwfbp_tpu import models as zoo
from mgwfbp_tpu.optim import make_optimizer
from mgwfbp_tpu.parallel.allreduce import make_merged_allreduce
from mgwfbp_tpu.parallel.costmodel import lookup_alpha_beta
from mgwfbp_tpu.parallel.mesh import DATA_AXIS
from mgwfbp_tpu.train import create_train_state, make_train_step

HBM_BYTES = 16 * 1024**3  # one v5e chip


# `topo` (the described v5e:2x2) and `_compile_cache_off` are tests/conftest.py's
pytestmark = pytest.mark.usefixtures("_compile_cache_off")


def _abstract_step_args(model, meta, tx, mesh, per_device_batch):
    """(state, batch) as ShapeDtypeStructs sharded the way the Trainer
    places them: state replicated, batch split over the data axis. A
    described device holds no array, so nothing is materialized."""
    example = jnp.zeros((1,) + tuple(meta.input_shape), meta.input_dtype)
    state = jax.eval_shape(
        lambda: create_train_state(jax.random.PRNGKey(0), model, example, tx)
    )
    rep = NamedSharding(mesh, P())
    state = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep), state
    )
    gb = per_device_batch * mesh.devices.size
    split = NamedSharding(mesh, P(None, DATA_AXIS))
    batch = {
        "x": jax.ShapeDtypeStruct(
            (1, gb) + tuple(meta.input_shape), meta.input_dtype,
            sharding=split,
        ),
        "y": jax.ShapeDtypeStruct((1, gb), jnp.int32, sharding=split),
    }
    return state, batch


def _imagenet_sgd():
    return make_optimizer(
        0.01, momentum=0.9, weight_decay=1e-4, lr_schedule="const",
        dataset="imagenet", num_batches_per_epoch=1,
    )[0]


def test_merged_allreduce_step_compiles_on_4_chip_mesh(topo):
    """The product's multi-chip program — jitted step + merged bucket
    collectives over a `data` mesh of four described chips — at CIFAR
    width so the compile stays in seconds."""
    mesh = Mesh(np.asarray(topo.devices).reshape(4), (DATA_AXIS,))
    model, meta = zoo.create_model("resnet20")
    tx = _imagenet_sgd()
    state, batch = _abstract_step_args(model, meta, tx, mesh, 32)
    n_leaves = len(jax.tree_util.tree_leaves(state.params))
    reducer = make_merged_allreduce(
        state.params, axis_name=DATA_AXIS, policy="mgwfbp",
        tb=[1.1e-5] * n_leaves, cost_model=lookup_alpha_beta("ici", 4),
    )
    step = make_train_step(
        model, meta, tx, mesh, reducer, compute_dtype=jnp.bfloat16,
        donate=True,
    )
    compiled = step.lower(state, batch).compile()
    assert "all-reduce" in compiled.as_text()
    assert reducer.schedule.num_groups >= 1


def test_resnet50_bf16_b128_step_fits_one_v5e_chip(topo):
    """chip_smoke's train phase, asked of the compiler: ResNet-50, bf16
    compute, per-chip batch 128, one chip, no reducer (the Trainer drops it
    at world size 1), donated state — compiles and fits 16 GB of HBM. The
    one long compile of this file (~40 s); tier-1 has the room for it."""
    mesh = Mesh(np.asarray(topo.devices[:1]), (DATA_AXIS,))
    model, meta = zoo.create_model("resnet50")
    tx = _imagenet_sgd()
    state, batch = _abstract_step_args(model, meta, tx, mesh, 128)
    step = make_train_step(
        model, meta, tx, mesh, None, compute_dtype=jnp.bfloat16, donate=True,
    )
    mem = step.lower(state, batch).compile().memory_analysis()
    need = mem.temp_size_in_bytes + mem.argument_size_in_bytes
    assert need < HBM_BYTES, f"{need / 1e9:.1f} GB does not fit one chip"


def _four_chip_mlp_step_text(topo):
    """Compiled text of a data-parallel step over four described chips:
    three dense layers wide enough that a gradient is worth hiding, `wfbp`
    (one all-reduce a leaf), bf16 compute. Compiles in seconds."""
    import flax.linen as nn

    from mgwfbp_tpu.models import ModelMeta

    width = 2048

    class MLP(nn.Module):
        @nn.compact
        def __call__(self, x, train=False):
            for _ in range(3):
                x = nn.relu(nn.Dense(width)(x))
            return nn.Dense(10)(x)

    mesh = Mesh(np.asarray(topo.devices).reshape(4), (DATA_AXIS,))
    model, meta = MLP(), ModelMeta("mlp", "synthetic", 10, (width,))
    tx = _imagenet_sgd()
    state, batch = _abstract_step_args(model, meta, tx, mesh, 64)
    n_leaves = len(jax.tree_util.tree_leaves(state.params))
    reducer = make_merged_allreduce(
        state.params, axis_name=DATA_AXIS, policy="wfbp",
        tb=[1e-3] * n_leaves, cost_model=lookup_alpha_beta("ici", 4),
    )
    step = make_train_step(
        model, meta, tx, mesh, reducer, compute_dtype=jnp.bfloat16,
        donate=True, health_stats=True,
    )
    assert reducer.schedule.num_groups == 8
    return step.lower(state, batch).compile().as_text()


@pytest.mark.parametrize("with_options", [True, False])
def test_four_chip_step_gets_asynchronous_allreduces(
    topo, monkeypatch, with_options
):
    """ISSUE 29, asked of the chip's compiler: a data-parallel step over
    four described chips is built with `ASYNC_COLLECTIVE_OPTIONS` (the
    mesh's reduction axis spans four TPU devices) and the compiled program
    then holds asynchronous all-reduces; built without them, as before
    PR 29, every all-reduce is synchronous. Either way there is one
    all-reduce per merge group and the metrics' own: the ordering token
    keeps the compiler's combiner off the buckets."""
    import mgwfbp_tpu.train.step as stepmod
    from mgwfbp_tpu.profiling import hlo_collective_counts

    mesh = Mesh(np.asarray(topo.devices).reshape(4), (DATA_AXIS,))
    assert stepmod.async_collective_options(mesh, (DATA_AXIS,)) == dict(
        stepmod.ASYNC_COLLECTIVE_OPTIONS
    )
    assert stepmod.async_collective_options(
        Mesh(np.asarray(topo.devices[:1]), (DATA_AXIS,)), (DATA_AXIS,)
    ) == {}
    if not with_options:
        monkeypatch.setattr(
            stepmod, "async_collective_options", lambda mesh, axes: {}
        )
    counts = hlo_collective_counts(_four_chip_mlp_step_text(topo))
    assert counts["collectives"] == 8 + 1
    if with_options:
        # the three 16 MB kernels' all-reduces at least; the biases' and the
        # metrics' scalar are too short to be worth a start/done pair
        assert counts["async_collectives"] >= 3
    else:
        assert counts["async_collectives"] == 0


def test_four_chip_steps_exchange_reads_the_same_from_text_and_trace(topo):
    """ISSUE 49, asked of the chip's compiler: the map of a four-chip step's
    compiled text knows its asynchronous all-reduces by kind (the start
    fusions carry no metadata of their own and take their `-done`'s merge
    group), and a trace with one event an instruction, reduced by that map,
    starts as many collectives a step as the text issues and puts every one
    of the eight merge groups' time under its group."""
    from mgwfbp_tpu import profiling
    from mgwfbp_tpu.train.step import STEP_SCOPES

    instructions = profiling.hlo_instruction_map(
        _four_chip_mlp_step_text(topo))
    counts = profiling.collective_counts(instructions)
    assert counts["collectives"] == 8 + 1 and counts["async_collectives"] >= 3
    starts = {name: i for name, i in instructions.items()
              if i.kind == "collective_start"}
    assert len(starts) == counts["async_collectives"]
    assert all("mgwfbp_group" in i.op_name for i in starts.values())
    step_map = profiling.StepMap(
        instructions, dict.fromkeys(STEP_SCOPES, profiling.UPDATE_LAYER))
    events = [(f"%{name} = f32[] op()", 10.0, 1000.0)
              for name in instructions] * 2
    out = profiling.split_trace(events, step_map, (0.0, 100.0), steps=2)
    assert out["exchange"]["calls"] == counts["collectives"]
    assert len(out["groups"]) == 8 and all(out["groups"])
    assert out["exchange"]["device_ms"] >= sum(out["groups"])
    assert "optimizer" in out["scopes"]


def _gradient_kernels_fed_by_a_reduced_bucket(text):
    """(n, fed): of the compiled entry computation's `n` kernels that
    compute a gradient (a fusion under `transpose(jvp(...))/.../dot_general`)
    the names of those with an operand that depends on a reduced bucket (a
    gradient all-reduce's result or an async collective's `-done`). The
    state an `async-collective-start` hands to the kernels that carry its
    steps is the collective running, not a reduced value, and is not
    followed."""
    import re

    lines = text.split("\n")
    at = next(i for i, line in enumerate(lines) if line.startswith("ENTRY"))
    fed: dict[str, bool] = {}
    kernels, hit = 0, []
    for line in lines[at + 1:]:
        if line.startswith("}"):
            break
        m = re.match(r"^\s*(?:ROOT )?%([\w.\-]+) = .*? ([\w\-]+)\((.*)$", line)
        if m is None:
            continue
        name, opcode, rest = m.groups()
        op_name = re.search(r'op_name="([^"]*)"', line)
        op_name = op_name.group(1) if op_name else ""
        if name.startswith("async-collective-done") or (
            opcode in ("all-reduce", "all-reduce-done")
            and "mgwfbp_group" in op_name
        ):
            fed[name] = True
            continue
        if name.startswith("async-collective-start"):
            fed[name] = False
            continue
        operands = re.findall(r"%([\w.\-]+)", rest.split("), ")[0])
        fed[name] = any(fed.get(o, False) for o in operands)
        if (opcode == "fusion" and "transpose(jvp" in op_name
                and "dot_general" in op_name):
            kernels += 1
            if fed[name]:
                hit.append(name)
    return kernels, hit


def test_no_gradient_kernel_waits_for_a_reduced_bucket(topo, monkeypatch):
    """What the one-element token is for, read off the chip compiler's
    program: no kernel of the backward pass has an operand that depends on
    a reduced bucket, so an asynchronous all-reduce holds up nothing but
    the next all-reduce. Under the whole-bucket token (the parent's) the
    compiler fuses the token's add into the kernels that compute the
    weight gradients and they do wait (four-chip VGG-16: 15 of 31 gradient
    kernels, PERF.md, PR 29)."""
    from mgwfbp_tpu.parallel import allreduce

    kernels, hit = _gradient_kernels_fed_by_a_reduced_bucket(
        _four_chip_mlp_step_text(topo))
    assert kernels >= 6 and hit == []
    monkeypatch.setattr(allreduce, "_order_after", allreduce._chain_token)
    kernels, hit = _gradient_kernels_fed_by_a_reduced_bucket(
        _four_chip_mlp_step_text(topo))
    assert kernels >= 6 and len(hit) >= 2


def test_qwen3next_step_counts_three_rules_through_three_programs(
        topo, monkeypatch):
    """The Qwen3-Next cell's step (four layers, 32 of 512 experts, 18,992
    ids, two sequences of 8,192) traced and lowered for the described chip
    (said so by the test: `traced_for_tpu` asks the default backend, which
    is the CPU here; nothing is compiled: the kernels are above, and the
    whole step takes the chip's compiler two minutes): `delta_program` reads
    3 + 0 and 3, three layers sharing the inverse's, the forward's and the
    backward's program."""
    from mgwfbp_tpu.ops import programs

    monkeypatch.setattr(programs, "traced_for_tpu", lambda: True)
    mesh = Mesh(np.asarray(topo.devices[:1]), (DATA_AXIS,))
    model, meta = zoo.create_model(
        "qwen3next", num_classes=18992, layers_held=4, experts_held=(0, 32))
    tx = _imagenet_sgd()
    state, batch = _abstract_step_args(model, meta, tx, mesh, 2)
    batch["y"] = jax.ShapeDtypeStruct(
        batch["x"].shape, jnp.int32, sharding=batch["x"].sharding)
    step = make_train_step(
        model, meta, tx, mesh, None, compute_dtype=jnp.bfloat16, donate=True)
    text = step.lower(state, batch).as_text()
    assert step.traced_programs["delta"] == {
        "kernel": 3, "plain": 0, "programs": 3}
    assert "gated_delta_rule_backward" in text


def test_xing4_step_counts_five_cores_through_the_kernel(topo, monkeypatch):
    """The Xing4.0 cell's step (layers 1 to 5, 8 of 64 experts, 16,384 ids,
    one sequence of 8,192) traced and lowered for the described chip (said so
    by the test: `traced_for_tpu` asks the default backend, which is the CPU
    here; nothing is compiled: the core at scores of 192 and values of 128 is
    asked in tests/test_tpu_compile_attention.py, and the whole step takes
    the chip's compiler a minute and a half): `attention_program` reads 5 +
    0, the four sparse layers' twelve grouped products go through the tiled
    kernel, the ten sub-layers' twenty passes over the streams through
    ops/streams.py's four kernel programs, and 759,346,446 parameters are
    held."""
    from mgwfbp_tpu.ops import programs

    monkeypatch.setattr(programs, "traced_for_tpu", lambda: True)
    mesh = Mesh(np.asarray(topo.devices[:1]), (DATA_AXIS,))
    model, meta = zoo.create_model(
        "xing4", num_classes=16384, layers_held="1:5", experts_held=(0, 8))
    tx = _imagenet_sgd()
    state, batch = _abstract_step_args(model, meta, tx, mesh, 1)
    assert sum(int(np.prod(leaf.shape)) for leaf in
               jax.tree_util.tree_leaves(state.params)) == 759346446
    batch["y"] = jax.ShapeDtypeStruct(
        batch["x"].shape, jnp.int32, sharding=batch["x"].sharding)
    step = make_train_step(
        model, meta, tx, mesh, None, compute_dtype=jnp.bfloat16, donate=True)
    text = step.lower(state, batch).as_text()
    assert step.traced_programs["attention"] == {"kernel": 5, "blocks": 0}
    assert step.traced_programs["experts"]["kernel"] == 12
    assert step.traced_programs["experts"]["ragged"] == 0
    assert step.traced_programs["streams"] == {
        "kernel": 20, "plain": 0, "programs": 4}
    # the streams between the sub-layers are bf16, four times the hidden size
    assert "4x1x8192x3584xbf16" in text
    assert "4x1x8192x3584xf32" not in text.split("func.func")[1][:2000]


def test_nemotron3s_step_compiles_and_fits_one_v5e_chip(topo, monkeypatch):
    """The Nemotron 3 Super cell's step (layers 26 to 36, 8 of 512 experts,
    member 0 of 8 chips' heads, 16,384 ids, one sequence of 8,192, AdamW as
    the cell trains) traced as for a TPU (said so by the test:
    `traced_for_tpu` asks the default backend, which is the CPU here) and
    COMPILED for the described chip, the second long compile of this file
    (two minutes): 508,189,680 parameters are held, every op goes down its
    kernels (the five scans at 16 heads over one group at the published chunk
    of 128, the five convolutions at 1,280 channels, the core at 4 query
    heads over 1, the ten grouped products at the 65,536 rows that can be in
    a group: 8,192 tokens x the 8 experts held of the 22 a token chooses),
    and the state (5.7 GiB: float32 weights and two moments) with the step's
    temporaries fits 16 GB with room; no array of the N x k = 180,224 rows
    is left in the step."""
    from mgwfbp_tpu.ops import programs

    monkeypatch.setattr(programs, "traced_for_tpu", lambda: True)
    mesh = Mesh(np.asarray(topo.devices[:1]), (DATA_AXIS,))
    model, meta = zoo.create_model(
        "nemotron3s", num_classes=16384, layers_held="26:11",
        experts_held=(0, 8), tensor_share="0:8")
    tx = make_optimizer(
        3e-4, momentum=0.9, weight_decay=0.1, lr_schedule="cosine",
        dataset="tokens", max_epochs=40, warmup_epochs=5,
        num_batches_per_epoch=32, norm_clip=1.0, optimizer="adamw", b2=0.95,
    )[0]
    state, batch = _abstract_step_args(model, meta, tx, mesh, 1)
    assert sum(int(np.prod(leaf.shape)) for leaf in
               jax.tree_util.tree_leaves(state.params)) == 508189680
    batch["y"] = jax.ShapeDtypeStruct(
        batch["x"].shape, jnp.int32, sharding=batch["x"].sharding)
    step = make_train_step(
        model, meta, tx, mesh, None, compute_dtype=jnp.bfloat16, donate=True)
    lowered = step.lower(state, batch)
    traced = step.traced_programs
    assert traced["attention"] == {"kernel": 1, "blocks": 0}
    assert traced["ssd"] == {"kernel": 5, "plain": 0, "programs": 2}
    assert traced["conv"] == {"kernel": 5, "plain": 0, "programs": 2}
    assert traced["experts"] == {"kernel": 10, "ragged": 0, "programs": 4}
    assert traced["rows"]["rows_programs"] == 1
    assert traced["groups"] == {"bounded": 5, "whole": 0}
    # the residual stream between the layers is bf16 at the full 4,096, the
    # latent the experts read is 1,024 wide, a row an assignment that can be
    # held
    text = lowered.as_text()
    assert "1x8192x4096xbf16" in text and "65536x1024xbf16" in text
    assert "180224x" not in text
    mem = lowered.compile().memory_analysis()
    need = mem.temp_size_in_bytes + mem.argument_size_in_bytes
    assert 5.6 * 2 ** 30 < mem.argument_size_in_bytes < 5.8 * 2 ** 30
    assert need < 12 * 2 ** 30, f"{need / 2 ** 30:.2f} GiB"
    assert need < HBM_BYTES


def test_granite_mixer_compiles_with_the_scan_as_two_kernel_programs(
        topo, monkeypatch):
    """models/granite.mamba_mixer at the Granite cell's shape (one sequence
    of 8,192, 64 heads of 64 over a state of 128, chunks of 256, bfloat16)
    under a `jax.checkpoint` as the layer has it, its value and its
    pull-back with the cotangent handed in, traced as for a TPU (said so by
    the test: this process's default backend is the CPU). It compiles for
    the chip with SIX Mosaic sites (the convolution's and the scan's forward
    kernels, both again under the checkpoint, and the two backward kernels)
    of FOUR distinct programs; x, B and C reach the scan's kernels as the
    convolution's one output, no (chunk, chunk) float32 matrix and no state
    a position exists in the text, the states the 32 chunks start from do
    (67 MB), and the mixer's scratch is 0.64 GiB where the parent's, asked
    the same way, is 0.78."""
    from jax.sharding import SingleDeviceSharding

    from mgwfbp_tpu.models import granite
    from mgwfbp_tpu.ops import programs

    monkeypatch.setattr(programs, "traced_for_tpu", lambda: True)
    one = SingleDeviceSharding(topo.devices[0])
    s = granite.GRANITE4H
    t, chunks = 8192, 8192 // s.mamba_chunk

    def arg(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one)

    p = {name: arg(*shape)
         for name, shape, _ in granite.layer_leaves(granite.MAMBA, s)}
    u = arg(1, t, s.hidden_size)

    def mixer(p, u, g):
        out, pull = jax.vjp(jax.checkpoint(
            lambda p, u: granite.mamba_mixer(p, u, s, 8)[0]), p, u)
        return out, pull(g)

    before = programs.LOWERED.copy()
    compiled = jax.jit(mixer).lower(p, u, u).compile()
    noted = programs.lowered_since(before)
    assert noted["ssd"] == {"kernel": 1, "plain": 0, "programs": 2}
    assert noted["conv"] == {"kernel": 1, "plain": 0, "programs": 2}
    text = compiled.as_text()
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    kernels = [re.search(r"(\w+)/pallas_call", line).group(1)
               for line in calls]
    assert sorted(kernels) == [
        "causal_conv_silu_backward", "causal_conv_silu_forward",
        "causal_conv_silu_forward", "ssd_scan_backward", "ssd_scan_forward",
        "ssd_scan_forward"]
    inner, n = s.mamba_inner, s.mamba_state
    for line, kernel in zip(calls, kernels):
        if kernel.startswith("ssd_scan"):  # xbc whole, where it lies
            assert f"bf16[1,{t},{s.conv_channels}]" in line
    assert f"f32[1,{chunks},{inner},{n}]" in text
    assert not re.search(rf"f32\[[\d,]*{s.mamba_chunk},{s.mamba_chunk}\]",
                         text)
    assert f"f32[1,{t},{inner},{n}]" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < 0.7 * 2 ** 30
