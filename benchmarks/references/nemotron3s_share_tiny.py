"""The plain reference of `nemotron3s_share.py` at the size the CPU tests
hold: hidden 32, seven layers of one mixer each (M E M * E M E), 8 Mamba
heads of 8 over 4 B/C groups of state 8 (2 heads a group, chunk 16), 8 query
heads over 2 key-value heads of 8, 8 routed relu^2 experts top 3 of width 24
in a latent of 16 and a shared expert of 48. Not a cell's reference:
`configs/tiny-nemotron3s-f32.json` and tests/benchmark name it.

It loads its own copy of the reference module and rebinds the copy's SHAPE,
SHARE and block sizes, so the published sizes in `nemotron3s_share.py` stay
as they are for whoever loads that file itself."""

from __future__ import annotations

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_nemotron3s_share_at_tiny_size",
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "nemotron3s_share.py"),
)
full = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(full)

full.SHAPE = {
    **full.SHAPE,
    "hidden_size": 32,
    "hybrid_override_pattern": "MEM*EME",
    "mamba_num_heads": 8,
    "mamba_head_dim": 8,
    "ssm_state_size": 8,
    "n_groups": 4,
    "chunk_size": 16,
    "num_attention_heads": 8,
    "num_key_value_heads": 2,
    "head_dim": 8,
    "n_routed_experts": 8,
    "num_experts_per_tok": 3,
    "moe_intermediate_size": 24,
    "moe_latent_size": 16,
    "moe_shared_expert_intermediate_size": 48,
}
# the tiny configuration holds layers 1 to 5 of 7 (E M * E M), routed
# experts 2 to 5 of 8, and member 1 of 2 chips' heads (2 B/C groups of 4, 4
# query heads over 1 key-value head, 24 shared columns)
full.SHARE = {"first_layer": 1, "layers": 5, "first_expert": 2, "experts": 4,
              "tensor": (1, 2)}
# T 64 is a multiple of none of them: the short last block of each
full.TIME_BLOCK = 24
full.QUERY_BLOCK = 24
full.LOSS_BLOCK = 40

first_step = full.first_step
forward_macs = full.forward_macs
