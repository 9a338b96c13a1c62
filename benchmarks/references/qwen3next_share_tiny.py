"""The plain reference of `qwen3next_share.py` at the size the CPU tests hold:
hidden 32, Gated DeltaNet with 2 key heads and 4 value heads of 8, attention
with 4 query heads over 1 key head of 16 (rotary over the first 4), 16 routed
experts top 3 of width 16 and a gated shared one of width 16, 8 layers (two
periods: delta, delta, delta, full, twice). Not a cell's reference:
`configs/tiny-qwen3next-f32.json` and tests/benchmark name it.

It loads its own copy of the reference module and rebinds the copy's SHAPE
and SHARE, so the published sizes in `qwen3next_share.py` stay as they are
for whoever loads that file itself."""

from __future__ import annotations

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_qwen3next_share_at_tiny_size",
    os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "qwen3next_share.py"),
)
full = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(full)

full.SHAPE = {
    **full.SHAPE,
    "hidden_size": 32,
    "num_hidden_layers": 8,
    "num_attention_heads": 4,
    "num_key_value_heads": 1,
    "head_dim": 16,
    "linear_num_key_heads": 2,
    "linear_num_value_heads": 4,
    "linear_key_head_dim": 8,
    "linear_value_head_dim": 8,
    "num_experts": 16,
    "num_experts_per_tok": 3,
    "moe_intermediate_size": 16,
    "shared_expert_intermediate_size": 16,
}
# the tiny configuration holds the first period, routed experts 4 to 7 of 16
full.SHARE = {"layers": 4, "first_expert": 4, "experts": 4, "vocab": 256}
full.TIME_BLOCK = 24  # T 64 is no multiple of it: the short last block
full.QUERY_BLOCK = 24
full.LOSS_BLOCK = 48

first_step = full.first_step
forward_macs = full.forward_macs
