"""What the entry points of `ops/` trace, counted in one place.

Each entry point (`blockattn.blockwise_attention`, `groupmm.grouped_product`,
`rowperm`'s two permutations, `selscan.selective_scan`,
`deltarule.gated_delta_rule`, `shortconv.causal_conv_silu`, `streams`'
`map_streams` and `write_streams`, `ssd`'s two scans) chooses its way
down by platform (`traced_for_tpu`) and shape, and says which it took with
`note(op, way, programs)`: the call, and the keys of the kernel programs
that it and its transposes need, as jax tells programs apart (what makes two
programs distinct is the op's own knowledge: its `_programs`). One caller
of theirs notes its way here too: the held experts' block
(`models/lm_parts._grouped_experts`, op `groups`), which sizes the grouped
arrays it hands `groupmm` and `rowperm` by its shapes alone.

`make_train_step` reads the notes round the trace of its step (`traced_into`)
and the Trainer writes them as the records of `RECORDS`, one a step program
built. jax keeps one trace of a function under `jax.checkpoint` for equal
static arguments and argument shapes, so of four equal layers only the first
runs an entry point's Python: a model wraps such a layer in `counted`, which
notes for a call that traced nothing what the call of its arguments that did
trace noted, for every op at once.
"""

from __future__ import annotations

import collections

import jax

# op -> (the ways a call of it goes down, the field that holds the number of
# distinct kernel programs its calls need or None where it keeps none): the
# fields of its part of a record, in the record's order
OPS: dict[str, tuple[tuple[str, ...], str | None]] = {
    "attention": (("kernel", "blocks"), None),
    "experts": (("kernel", "ragged"), "programs"),
    "rows": (("rows_held", "rows_all"), "rows_programs"),
    "groups": (("bounded", "whole"), None),
    "scan": (("kernel", "plain"), "programs"),
    "delta": (("kernel", "plain"), "programs"),
    "conv": (("kernel", "plain"), "programs"),
    "streams": (("kernel", "plain"), "programs"),
    "ssd": (("kernel", "plain"), "programs"),
}
# (telemetry record, the ops whose fields it carries, the Trainer's log line
# over those fields)
RECORDS: tuple[tuple[str, tuple[str, ...], str], ...] = (
    ("attention_program", ("attention",),
     "attention: %(kernel)d core(s) of the step through the fused kernel, "
     "%(blocks)d through the plain blocks"),
    ("experts_program", ("experts", "rows", "groups"),
     "experts: %(kernel)d grouped product(s) of the step through the tiled "
     "kernel (%(programs)d distinct kernel program(s)), %(ragged)d through "
     "ragged_dot; %(rows_held)d row permutation(s) moving only the rows in a "
     "group (%(rows_programs)d distinct kernel program(s)), %(rows_all)d "
     "moving every assignment's row; %(bounded)d expert block(s) grouping a "
     "token's held choices alone (tokens x experts held rows), %(whole)d "
     "every choice (tokens x k rows)"),
    ("scan_program", ("scan",),
     "scan: %(kernel)d selective scan(s) of the step through the kernels "
     "with the state in VMEM (%(programs)d distinct kernel program(s)), "
     "%(plain)d through the chunked form"),
    ("delta_program", ("delta",),
     "delta rule: %(kernel)d gated delta rule(s) of the step through a "
     "kernel with the state in VMEM (%(programs)d distinct kernel "
     "program(s)), %(plain)d through the plain chunked form"),
    ("conv_program", ("conv",),
     "convolution: %(kernel)d short convolution(s) of the step through the "
     "kernels of one pass (%(programs)d distinct kernel program(s)), "
     "%(plain)d through the plain form"),
    ("streams_program", ("streams",),
     "streams: %(kernel)d pass(es) of the step through the kernels of one "
     "read (%(programs)d distinct kernel program(s)), %(plain)d through the "
     "plain form"),
    ("ssd_program", ("ssd",),
     "state-space scan: %(kernel)d chunked scan(s) of the step through the "
     "kernels with a chunk's matrices and the state in VMEM (%(programs)d "
     "distinct kernel program(s)), %(plain)d through the plain form"),
)

# calls traced so far under (op, way), and under (op, a kernel program's key)
# the calls traced so far that need that program
LOWERED: collections.Counter = collections.Counter()
# what a call through `counted` traced, by its arguments' shapes
_TRACED_BY: dict = {}


def traced_for_tpu() -> bool:
    """Whether what is traced now will be lowered for a TPU: this package
    builds its meshes from the default backend's devices."""
    return jax.default_backend() == "tpu"


def note(op: str, way: str, programs=()) -> None:
    """One call of `op` traced now went down `way` and needs the kernel
    programs of these keys (tuples; none where no kernel is called)."""
    LOWERED[op, way] += 1
    LOWERED.update((op, key) for key in programs)


def counted(fn):
    """`fn` with what EVERY call of it traces noted. Of equal layers under
    `jax.checkpoint` only the first runs the entry points' Python, and a step
    built a second time in one process runs none of it: a call that noted
    nothing is noted as the call of its arguments that did, whatever the ops
    it called."""

    def call(*args):
        before = LOWERED.copy()
        out = fn(*args)
        key = jax.tree.structure(args), tuple(
            (a.shape, a.dtype) if hasattr(a, "shape") else a
            for a in jax.tree.leaves(args))
        traced = LOWERED - before
        if traced:
            _TRACED_BY[key] = traced
        else:
            LOWERED.update(_TRACED_BY.get(key, ()))
        return out

    return call


def lowered_since(before: collections.Counter) -> dict[str, dict[str, int]]:
    """What was noted since `before` (a copy of `LOWERED`), by op: the calls
    that went each way and, where the op calls kernels, the distinct kernel
    programs those calls and their transposes need."""
    made = LOWERED - before
    fields = {}
    for op, (ways, programs) in OPS.items():
        fields[op] = {way: made[op, way] for way in ways}
        if programs is not None:
            fields[op][programs] = sum(
                1 for of, key in made if of == op and not isinstance(key, str))
    return fields


def traced_into(fn, into: dict):
    """`fn` that leaves in `into`, each time it is traced, what its trace
    noted (`lowered_since`)."""

    def traced(*args):
        before = LOWERED.copy()
        out = fn(*args)
        into.update(lowered_since(before))
        return out

    return traced
