"""What the files that train a preset under the Trainer with the telemetry on
(tests/test_<family>_trainer.py, tests/test_telemetry.py) share to hold the
`*_program` records of that ONE training: what the built step noted for an
op while it was traced (ops/programs.py), the record, the Trainer's log line
and the report's, each as it read before the ops shared a registry."""

import os

import telemetry_report

from mgwfbp_tpu.telemetry.events import events_of, read_events

OPS = ("attention", "experts", "rows", "groups", "scan", "delta", "conv",
       "streams", "ssd")
# op -> (its record, its part of the Trainer's log line, its part of the
# report's line)
SAID = {
    "attention": (
        "attention_program",
        "attention: {kernel} core(s) of the step through the fused kernel, "
        "{blocks} through the plain blocks",
        "{kernel} core(s) through the fused kernel, {blocks} through the "
        "plain blocks"),
    "experts": (
        "experts_program",
        "experts: {kernel} grouped product(s) of the step through the tiled "
        "kernel ({programs} distinct kernel program(s)), {ragged} through "
        "ragged_dot; ",
        "{kernel} grouped product(s) through the tiled kernel ({programs} "
        "distinct kernel program(s)), {ragged} through ragged_dot; "),
    "rows": (
        "experts_program",
        "; {rows_held} row permutation(s) moving only the rows in a group "
        "({rows_programs} distinct kernel program(s)), {rows_all} moving "
        "every assignment's row",
        "; {rows_held} row permutation(s) moving only the rows in a group "
        "({rows_programs} distinct kernel program(s)), {rows_all} moving "
        "every assignment's row"),
    "groups": (
        "experts_program",
        "; {bounded} expert block(s) grouping a token's held choices alone "
        "(tokens x experts held rows), {whole} every choice (tokens x k "
        "rows)",
        "; {bounded} expert block(s) grouping a token's held choices alone "
        "(tokens x experts held rows), {whole} every choice (tokens x k "
        "rows)"),
    "scan": (
        "scan_program",
        "scan: {kernel} selective scan(s) of the step through the kernels "
        "with the state in VMEM ({programs} distinct kernel program(s)), "
        "{plain} through the chunked form",
        "; {kernel} selective scan(s) through the kernels with the state in "
        "VMEM ({programs} distinct kernel program(s)), {plain} through the "
        "chunked form"),
    "delta": (
        "delta_program",
        "delta rule: {kernel} gated delta rule(s) of the step through a "
        "kernel with the state in VMEM ({programs} distinct kernel "
        "program(s)), {plain} through the plain chunked form",
        "; {kernel} gated delta rule(s) through a kernel with the state in "
        "VMEM ({programs} distinct kernel program(s)), {plain} through the "
        "plain chunked form"),
    "conv": (
        "conv_program",
        "convolution: {kernel} short convolution(s) of the step through the "
        "kernels of one pass ({programs} distinct kernel program(s)), "
        "{plain} through the plain form",
        "short convolution: {kernel} through the kernels of one pass "
        "({programs} distinct kernel program(s)), {plain} through the plain "
        "form"),
    "streams": (
        "streams_program",
        "streams: {kernel} pass(es) of the step through the kernels of one "
        "read ({programs} distinct kernel program(s)), {plain} through the "
        "plain form",
        "streams' passes: {kernel} through the kernels of one read "
        "({programs} distinct kernel program(s)), {plain} through the plain "
        "form"),
    "ssd": (
        "ssd_program",
        "state-space scan: {kernel} chunked scan(s) of the step through the "
        "kernels with a chunk's matrices and the state in VMEM ({programs} "
        "distinct kernel program(s)), {plain} through the plain form",
        "state-space scan: {kernel} through the kernels with the state in "
        "VMEM ({programs} distinct kernel program(s)), {plain} through the "
        "plain form"),
}


def read_run(logdir: str, cfg, trainer) -> tuple:
    """(what the closed trainer's step noted by op, the stream's records, the
    log's text) of a training under `logdir`."""
    where = os.path.join(logdir, cfg.tag())
    with open(os.path.join(where, "train.log")) as f:
        log = f.read()
    return (dict(trainer.train_step.traced_programs),
            read_events(os.path.join(where, "telemetry.jsonl")), log)


def holds(run: tuple, op: str, want: dict) -> None:
    """`run` (`read_run`) trained ONE built step program over its epochs: the
    step noted `want` for `op`, ONE record carries it from step 1 (a second
    epoch runs the same program and adds none), the log says it, and the
    report says it wherever it has a line for the op: always of the
    attention cores and the experts, of a scan or a delta rule on the line
    of the model's own counters, of the convolutions where there is one."""
    noted, records, log = run
    record, logged, reported = SAID[op]
    assert noted[op] == want
    (program,) = events_of(records, record)
    assert program["step"] == 1
    assert {name: program[name] for name in want} == want
    assert logged.format(**want) in log
    if op in ("attention", "experts", "rows", "groups") or any(want.values()):
        assert reported.format(**want) in telemetry_report.format_report(
            records)
