"""The plain reference of `xing4_share.py` at the size the CPU tests hold:
hidden 32, four residual streams, 4 latent-attention heads scoring over 16 +
8 rotary and summing values of 16 (latent ranks 24 and 16), dense width 48, 8
routed experts top 2 of width 16 and a shared one, four layers of which the
first two are dense. Not a cell's reference: `configs/tiny-xing4-f32.json`
and tests/benchmark name it.

It loads its own copy of the reference module and rebinds the copy's SHAPE
and SHARE, so the published sizes in `xing4_share.py` stay as they are for
whoever loads that file itself."""

from __future__ import annotations

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_xing4_share_at_tiny_size",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "xing4_share.py"),
)
full = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(full)

full.SHAPE = {
    **full.SHAPE,
    "hidden_size": 32,
    "intermediate_size": 48,
    "first_k_dense_replace": 2,
    "num_attention_heads": 4,
    "q_lora_rank": 24,
    "kv_lora_rank": 16,
    "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8,
    "v_head_dim": 16,
    "n_routed_experts": 8,
    "num_experts_per_tok": 2,
    "moe_intermediate_size": 16,
    # 8 dimensions rotate; the ramp over so few pairs needs a short original
    # length to start above pair 0's
    "rope_scaling": {
        **full.SHAPE["rope_scaling"], "beta_fast": 4.0,
        "original_max_position_embeddings": 32},
}
# the tiny configuration holds layers 1 to 3 of 4 (one dense, two sparse) and
# routed experts 2 to 5 of 8
full.SHARE = {"first_layer": 1, "layers": 3, "first_expert": 2, "experts": 4}
full.QUERY_BLOCK = 24  # T 64 is no multiple of it: the short last block

first_step = full.first_step
forward_macs = full.forward_macs
