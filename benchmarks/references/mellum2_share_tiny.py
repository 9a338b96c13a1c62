"""The plain reference of `mellum2_share.py` at the size the CPU tests hold:
hidden 64, 4 query / 2 key-value heads of 16, 8 experts top 2 of width 32,
window 16, four layers (window, window, window, full). Not a cell's
reference: `configs/tiny-mellum2-f32.json` and tests/benchmark name it.

It loads its own copy of the reference module and rebinds the copy's SHAPE
and SHARE, so the published sizes in `mellum2_share.py` stay as they are for
whoever loads that file itself."""

from __future__ import annotations

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_mellum2_share_at_tiny_size",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "mellum2_share.py"),
)
full = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(full)

full.SHAPE = {
    **full.SHAPE,
    "hidden_size": 64,
    "num_attention_heads": 4,
    "num_key_value_heads": 2,
    "head_dim": 16,
    "num_experts": 8,
    "num_experts_per_tok": 2,
    "moe_intermediate_size": 32,
    "sliding_window": 16,
    "layer_types": [full.SLIDING, full.SLIDING, full.SLIDING, full.FULL],
}
# the tiny configuration holds experts 2 and 3 of 8, all four layers
full.SHARE = {"layers": 4, "first_expert": 2, "experts": 2}
full.QUERY_BLOCK = 24  # T 64 is no multiple of it: the short last block

first_step = full.first_step
forward_macs = full.forward_macs
