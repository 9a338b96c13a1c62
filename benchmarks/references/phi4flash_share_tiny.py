"""The plain reference of `phi4flash_share.py` at the size the CPU tests hold:
hidden 32, MLP 48, 4 query / 2 key-value heads of 8 (one pair of key heads),
window 16, state 4, dt rank 2, and the 8 layers the publisher's rule gives
(mamba, window, mamba, window, mamba*, full*, gmu, cross). A test that wants
the 32-layer rule hands `shape={**SHAPE, "num_hidden_layers": 32}`. Not a
cell's reference: `configs/tiny-phi4flash-f32.json` and tests/benchmark name
it.

It loads its own copy of the reference module and rebinds the copy's SHAPE,
SHARE and block sizes, so the published sizes in `phi4flash_share.py` stay as
they are for whoever loads that file itself."""

from __future__ import annotations

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_phi4flash_share_at_tiny_size",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "phi4flash_share.py"),
)
full = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(full)

full.SHAPE = {
    **full.SHAPE,
    "hidden_size": 32,
    "intermediate_size": 48,
    "num_attention_heads": 4,
    "num_key_value_heads": 2,
    "head_dim": 8,
    "num_hidden_layers": 8,
    "sliding_window": 16,
    "mamba_d_state": 4,
    "mamba_dt_rank": 2,
}
full.SHARE = {"first_layer": 0, "layers": 8}
# T 64 is a multiple of none of them: the short last block of each
full.TIME_BLOCK = 24
full.QUERY_BLOCK = 24
full.LOSS_BLOCK = 40

first_step = full.first_step
forward_macs = full.forward_macs
