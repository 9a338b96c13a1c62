"""The plain reference of `granite4h_share.py` at the size the CPU tests hold:
hidden 32, MLP 48, 4 query / 2 key-value heads of 8, 4 Mamba heads of 16,
state 8, chunk 16, four layers (mamba, mamba, attention, mamba); the four
multipliers as published. Not a cell's reference:
`configs/tiny-granite4h-f32.json` and tests/benchmark name it.

It loads its own copy of the reference module and rebinds the copy's SHAPE,
SHARE and block sizes, so the published sizes in `granite4h_share.py` stay as
they are for whoever loads that file itself."""

from __future__ import annotations

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_granite4h_share_at_tiny_size",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "granite4h_share.py"),
)
full = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(full)

full.SHAPE = {
    **full.SHAPE,
    "hidden_size": 32,
    "shared_intermediate_size": 48,
    "num_attention_heads": 4,
    "num_key_value_heads": 2,
    "attention_head_dim": 8,
    "mamba_n_heads": 4,
    "mamba_d_head": 16,
    "mamba_d_state": 8,
    "mamba_chunk_size": 16,
    "layer_types": [full.MAMBA, full.MAMBA, full.ATTENTION, full.MAMBA],
}
full.SHARE = {"layers": 4}
# T 64 is a multiple of none of them: the short last block of each
full.TIME_BLOCK = 24
full.QUERY_BLOCK = 24
full.LOSS_BLOCK = 40

first_step = full.first_step
forward_macs = full.forward_macs
