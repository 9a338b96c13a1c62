#!/usr/bin/env python3
"""Device time of a traced jitted step, split by `jax.named_scope`.

    python tools/trace_scopes.py --trace <dir holding *.xplane.pb> \
        --hlo <compiled HLO text of the step> --steps N \
        --scopes attn_window,attn_full,attn_proj,moe_route,moe_experts,lm_head,loss

(models/mellum.py's scopes; models/granite.py's are ssm_in_proj, ssm_conv,
ssm_scan, ssm_gate_norm, ssm_out_proj, mlp, attn_full, attn_proj, lm_head,
loss; models/laguna.py's are attn_proj, attn_gate, attn_window, attn_full,
mlp, moe_route, moe_shared, moe_experts, lm_head, loss; models/phi4flash.py's
are ssm_in_proj, ssm_conv, ssm_dt_proj, ssm_sel_scan, ssm_out_proj, gmu,
attn_proj, attn_window, attn_full, attn_cross, attn_diff, mlp, lm_head,
loss; models/qwen3next.py's are gdn_in_proj, gdn_conv, gdn_delta,
gdn_gate_norm, gdn_out_proj, attn_proj, attn_full, attn_gate, moe_route,
moe_shared, moe_experts, lm_head, loss; models/xing4.py's are mhc_map,
mhc_mix, mla_q_proj, mla_kv_proj, attn_full, mla_out_proj, mlp, moe_route,
moe_shared, moe_experts, lm_head, loss; models/nemotronh.py's are Granite's
ssm_* and attn_* and moe_route, moe_latent_down, moe_experts, moe_latent_up,
moe_shared, lm_head, loss.)

A TPU trace names each event of the `XLA Ops` line after the HLO instruction
it ran; the compiled module's per-instruction `op_name` metadata still carries
the name stack (`jit(step)/jvp(Model)/attn_window/...`, with `transpose(` on
the backward pass). This joins the two, as `Trainer._run_profile_window` does
for the merge groups, and sums the events' durations per scope: the first of
`--scopes` found in an instruction's op_name; else `(model, no scope)` where
the name stack passes through autodiff (`jvp(`: norms, residual adds, the
embedding), else `(outside the model)` (optimizer, guard, statistics). A
fusion is counted under the scope its own metadata names. The compiled HLO
text comes from `jitted.lower(...).compile().as_text()` on the machine with
the chip. Prints milliseconds per traced step, forward and backward apart.
"""

from __future__ import annotations

import argparse
import collections
import glob
import os
import re
import sys

_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def hlo_op_names(hlo_text: str) -> dict[str, str]:
    """{instruction name: its op_name metadata} over every computation. A
    Pallas kernel's custom call is printed over several lines (its
    `kernel_metadata` holds a JSON string with line breaks in it) and its
    `metadata={op_name=...}` stands on the last of them: lines that start no
    instruction belong to the one before."""
    out = {}
    waiting = None  # the instruction whose op_name has not been seen yet
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is not None:
            waiting = m.group(1)
        meta = _OP_NAME.search(line)
        if meta is not None and waiting is not None:
            out[waiting] = meta.group(1)
            waiting = None
    return out


# loops and calls span their bodies' events, which the line lists as well
_CONTAINERS = ("while", "conditional", "call")


def classify(op_name: str | None, scopes: list[str]) -> tuple[str, str]:
    """(scope, pass) of one instruction's op_name."""
    if op_name is None:
        return "(no metadata)", "-"
    parts = op_name.split("/")
    direction = "backward" if "transpose(" in op_name else "forward"
    for scope in scopes:
        if scope in parts:
            return scope, direction
    if "jvp(" in op_name:
        return "(model, no scope)", direction
    return "(outside the model)", "-"


def split(events, op_names: dict[str, str], scopes: list[str],
          prefixes: dict[str, str] | None = None):
    """events: (name, start_ns, duration_ns). {(scope, pass): ns}, and the
    longest instructions as (ns, instruction, scope)."""
    totals = collections.Counter()
    by_instruction = collections.Counter()
    for name, _start, dur in events:
        instruction = name.split(" = ", 1)[0].strip().lstrip("%")
        if instruction.split(".")[0] in _CONTAINERS:
            continue
        scope, direction = classify(op_names.get(instruction), scopes)
        for prefix, named in (prefixes or {}).items():
            # a custom call whose metadata lost the name stack
            if instruction.startswith(prefix) and scope.startswith("("):
                scope = named
        totals[(scope, direction)] += dur
        by_instruction[(instruction, scope)] += dur
    top = sorted(((ns, i, s) for (i, s), ns in by_instruction.items()),
                 reverse=True)
    return totals, top


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--trace", required=True)
    ap.add_argument("--hlo", required=True)
    ap.add_argument("--steps", type=int, default=1)
    ap.add_argument("--scopes", required=True)
    ap.add_argument("--top", type=int, default=25)
    ap.add_argument("--prefix", action="append", default=[],
                    metavar="INSTRUCTION_PREFIX=SCOPE",
                    help="count instructions so named, whose metadata names "
                         "no scope, under SCOPE (ragged-dot=moe_experts)")
    args = ap.parse_args(argv)
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(args.trace, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise SystemExit(f"no .xplane.pb under {args.trace}")
    with open(args.hlo) as f:
        op_names = hlo_op_names(f.read())
    events = []
    for plane in ProfileData.from_file(sorted(paths)[-1]).planes:
        if not re.match(r"^/device:(TPU|GPU):0$", plane.name):
            continue
        for line in plane.lines:
            if line.name == "XLA Ops":
                events = [(e.name, e.start_ns, e.duration_ns)
                          for e in line.events]
    scopes = args.scopes.split(",")
    totals, top = split(events, op_names, scopes,
                        dict(p.split("=", 1) for p in args.prefix))
    whole = sum(totals.values())
    print(f"{len(events)} events, {whole / 1e6 / args.steps:.2f} ms of "
          f"device ops a step over {args.steps} step(s)")
    for (scope, direction), ns in sorted(
            totals.items(), key=lambda kv: -kv[1]):
        print(f"  {scope:>22} {direction:>8} {ns / 1e6 / args.steps:10.2f} ms"
              f" {100.0 * ns / whole:6.1f}%")
    print("longest instructions (ms a step):")
    for ns, instruction, scope in top[: args.top]:
        print(f"  {ns / 1e6 / args.steps:10.2f} {instruction} [{scope}] "
              f"{op_names.get(instruction, '')[-90:]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
