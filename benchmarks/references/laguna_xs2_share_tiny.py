"""The plain reference of `laguna_xs2_share.py` at the size the CPU tests
hold: hidden 64, 2 key/value heads of 16 under 6 query heads on full layers
and 8 on window layers, 16 routed experts top 2 of width 32 and a shared one
of width 24, dense width 96, window 16, five layers (full + dense, window,
window, window, full). Not a cell's reference:
`configs/tiny-laguna-xs2-f32.json` and tests/benchmark name it.

It loads its own copy of the reference module and rebinds the copy's SHAPE
and SHARE, so the published sizes in `laguna_xs2_share.py` stay as they are
for whoever loads that file itself."""

from __future__ import annotations

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_laguna_xs2_share_at_tiny_size",
    os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "laguna_xs2_share.py"),
)
full = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(full)

_rope = full.SHAPE["rope_parameters"]
full.SHAPE = {
    **full.SHAPE,
    "hidden_size": 64,
    "intermediate_size": 96,
    "num_attention_heads_per_layer": [6, 8, 8, 8, 6],
    "num_key_value_heads": 2,
    "head_dim": 16,
    "num_experts": 16,
    "num_experts_per_tok": 2,
    "moe_intermediate_size": 32,
    "shared_expert_intermediate_size": 24,
    "sliding_window": 16,
    "layer_types": [full.FULL, full.SLIDING, full.SLIDING, full.SLIDING,
                    full.FULL],
    "mlp_layer_types": [full.DENSE] + [full.SPARSE] * 4,
    "rope_parameters": {
        **_rope,
        # 8 of 16 dimensions rotate; the ramp over so few pairs needs a
        # short original length to start above pair 0's
        full.FULL: {**_rope[full.FULL], "original_max_position_embeddings": 32,
                    "beta_fast": 4.0},
    },
}
# the tiny configuration holds routed experts 2 to 5 of 16, all five layers
full.SHARE = {"layers": 5, "first_expert": 2, "experts": 4}
full.QUERY_BLOCK = 24  # T 64 is no multiple of it: the short last block

first_step = full.first_step
forward_macs = full.forward_macs
