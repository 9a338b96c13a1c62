"""`profiling.hlo_instruction_map`, `classify` and `split_trace` on a hand-made
HLO text and event list (the cases of the by-hand tool whose library they
were until PR 49): the join of instruction names to `op_name` scopes, forward
against backward, loops not counted twice, a custom call without a name
stack outside the model."""

import pytest

from mgwfbp_tpu import profiling


def op_names(hlo: str) -> dict:
    """{instruction: its op_name} of those that have one."""
    return {name: i.op_name
            for name, i in profiling.hlo_instruction_map(hlo).items()
            if i.op_name is not None}


def split(events, hlo: str, scopes):
    """{(scope, pass): ns} and the longest instructions as (ns, instruction,
    scope), of `split_trace` over one step."""
    out = profiling.split_trace(events, profiling.StepMap(
        profiling.hlo_instruction_map(hlo), dict.fromkeys(scopes, "model")))
    no_pass = (profiling.NO_METADATA, profiling.OUTSIDE_MODEL)
    totals = {}
    for scope, (forward, backward) in out["scopes"].items():
        passes = ("-",) if scope in no_pass else ("forward", "backward")
        for direction, ms in zip(passes, (forward, backward)):
            if ms:
                totals[(scope, direction)] = round(ms * 1e6)
    top = [(round(ms * 1e6), name, scope) for ms, name, scope in out["top"]]
    return totals, top

HLO = """
HloModule jit_step
%fused_computation.1 (p: f32[8]) -> f32[8] {
  ROOT %exp.1 = f32[8]{0} exponential(%p), metadata={op_name="jit(step)/jvp(M)/attn_full/exp"}
}
ENTRY %main {
  %fusion.7 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/jvp(M)/attn_full/exp" stack_frame_id=2}
  %fusion.8 = f32[8]{0} fusion(%b), kind=kLoop, calls=%fc, metadata={op_name="jit(step)/transpose(jvp(M))/attn_full/mul"}
  %fusion.9 = f32[8]{0} fusion(%c), kind=kLoop, calls=%fc, metadata={op_name="jit(step)/jvp(M)/moe_experts/while/body/gather"}
  %while.3 = (f32[8]) while(%t), condition=%c, body=%b, metadata={op_name="jit(step)/jvp(M)/moe_experts/while"}
  %ragged-dot-none.4 = bf16[8,8] custom-call(%x), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %fusion.10 = f32[8]{0} fusion(%d), kind=kLoop, calls=%fc, metadata={op_name="jit(step)/jvp(M)/mul"}
  ROOT %fusion.11 = f32[8]{0} fusion(%e), kind=kLoop, calls=%fc, metadata={op_name="jit(step)/add"}
}
"""
EVENTS = [
    ("%fusion.7 = f32[8]{0} fusion(%a)", 0, 100), ("fusion.7", 500, 100),
    ("fusion.8", 100, 300), ("fusion.9", 1000, 40), ("while.3", 990, 70),
    ("ragged-dot-none.4", 1040, 20), ("fusion.10", 1100, 5),
    ("fusion.11", 1200, 7), ("copy.99", 1300, 1),
]
SCOPES = ["attn_window", "attn_full", "moe_experts"]


def test_join_and_split():
    names = op_names(HLO)
    assert names["fusion.7"].endswith("attn_full/exp")
    assert names["fusion.11"] == "jit(step)/add"
    # the fusion's body is its own: counted once, under the fusion
    assert "exp.1" not in names
    totals, top = split(EVENTS, HLO, SCOPES)
    assert totals[("attn_full", "forward")] == 200
    assert totals[("attn_full", "backward")] == 300
    # the loop spans its body's events: only the body's are counted
    assert totals[("moe_experts", "forward")] == 40
    assert totals[("(model, no scope)", "forward")] == 5
    # with the custom call whose metadata holds no name stack (the kernels
    # in use since PR 35 keep theirs: the tool's `--prefix` went with it)
    assert totals[("(outside the model)", "-")] == 7 + 20
    assert totals[("(no metadata)", "-")] == 1
    assert sum(totals.values()) == 573
    assert top[0] == (300, "fusion.8", "attn_full")


def test_a_kernels_custom_call_printed_over_several_lines_keeps_its_scope():
    """As the TPU compiler prints a Pallas kernel (ops/blockattn.py's): the
    `kernel_metadata` JSON breaks the instruction over three lines and the
    op_name stands on the last; the next instruction has none of its own and
    must not inherit it."""
    hlo = (
        '  %splash_mha_dkv_no_residuals.7 = (f32[8]{0}, bf16[8]{0}) '
        'custom-call(%q, %k), custom_call_target="tpu_custom_call", '
        'frontend_attributes={kernel_metadata={\n'
        '"xprof_metadata":"{\\"block_q_dkv\\": 1024}"\n'
        '}}, metadata={op_name="jit(step)/transpose(jvp(M))/attn_full/'
        'jit(_splash_attention)/splash_mha_dkv_no_residuals/pallas_call" '
        'stack_frame_id=20}, backend_config={"custom_call_config":{}}\n'
        '  %bare.1 = f32[8]{0} copy(%x)\n'
        '  %fusion.3 = f32[8]{0} fusion(%y), kind=kLoop, calls=%fc, '
        'metadata={op_name="jit(step)/jvp(M)/attn_window/mul"}\n')
    names = op_names(hlo)
    assert set(names) == {"splash_mha_dkv_no_residuals.7", "fusion.3"}
    totals, _ = split(
        [("splash_mha_dkv_no_residuals.7", 0, 17), ("bare.1", 20, 1),
         ("fusion.3", 30, 2)], hlo, SCOPES)
    assert totals == {("attn_full", "backward"): 17,
                      ("(no metadata)", "-"): 1,
                      ("attn_window", "forward"): 2}


def test_the_grouped_products_kernels_land_in_the_experts_scope():
    """As the TPU compiler prints ops/groupmm.py's kernels in a Mellum 2 step
    (compiled here for a v5e, PR 35): the library's jitted `gmm` / `tgmm`
    keep the model's name stack in front of their own, so the forward
    product, the recomputed one, d lhs and d rhs are all `moe_experts`, where
    `ragged-dot-none` carried no name stack and needed `--prefix`."""
    back = ("jit(step)/transpose(jvp(Mellum2LM))/moe_experts/jvp(Mellum2LM)/"
            "moe_experts/checkpoint/")
    hlo = "".join(
        f'  %{name} = bf16[131072,896]{{1,0:T(8,128)(2,1)}} custom-call(%a, '
        '%b), custom_call_target="tpu_custom_call", operand_layout_constraints'
        '={s32[], s32[17]{0}}, frontend_attributes={kernel_metadata={}}, '
        f'metadata={{op_name="{op_name}/pallas_call" stack_frame_id=116}}, '
        'backend_config={"custom_call_config":{"body":"TUzvUg"}}\n'
        for name, op_name in [
            ("gmm.1", "jit(step)/jvp(Mellum2LM)/moe_experts/jit(gmm)"),
            ("gmm.18", back + "rematted_computation/jit(gmm)"),
            ("gmm.14", back + "jit(gmm)"),
            ("tgmm.6", back + "jit(tgmm)")])
    names = op_names(hlo)
    assert set(names) == {"gmm.1", "gmm.18", "gmm.14", "tgmm.6"}
    totals, top = split(
        [("gmm.1", 0, 10), ("gmm.18", 20, 11), ("gmm.14", 40, 12),
         ("tgmm.6", 60, 13)], hlo, SCOPES)
    assert totals == {("moe_experts", "forward"): 10,
                      ("moe_experts", "backward"): 36}
    assert top[0] == (13, "tgmm.6", "moe_experts")


@pytest.mark.parametrize("op_name,want", [
    ("jit(step)/jvp(M)/attn_window/dot_general", ("attn_window", "forward")),
    ("jit(step)/transpose(jvp(M))/attn_window/dot_general",
     ("attn_window", "backward")),
    ("jit(step)/jvp(M)/checkpoint/moe_experts/x", ("moe_experts", "forward")),
    ("jit(step)/jvp(M)/not_attn_full_really/x", ("(model, no scope)", "forward")),
    (None, ("(no metadata)", "-")),
])
def test_classify(op_name, want):
    assert profiling.classify(op_name, SCOPES) == want


GRANITE_SCOPES = [
    "ssm_in_proj", "ssm_conv", "ssm_scan", "ssm_gate_norm", "ssm_out_proj",
    "mlp", "attn_full", "attn_proj", "lm_head", "loss",
]


@pytest.mark.parametrize("op_name,want", [
    # the hybrid state-space decoder's scopes (models/granite.py): each layer
    # is under jax.checkpoint, the scan's chunk blocks in a checkpointed loop
    ("jit(step)/jvp(Granite4HLM)/checkpoint/ssm_in_proj/dot_general",
     ("ssm_in_proj", "forward")),
    ("jit(step)/transpose(jvp(Granite4HLM))/checkpoint/rematted_computation/"
     "ssm_scan/while/body/checkpoint/rematted_computation/exp",
     ("ssm_scan", "backward")),
    ("jit(step)/jvp(Granite4HLM)/checkpoint/ssm_conv/mul", ("ssm_conv", "forward")),
    ("jit(step)/transpose(jvp(Granite4HLM))/checkpoint/ssm_gate_norm/rsqrt",
     ("ssm_gate_norm", "backward")),
    ("jit(step)/jvp(Granite4HLM)/checkpoint/ssm_out_proj/dot_general",
     ("ssm_out_proj", "forward")),
    ("jit(step)/transpose(jvp(Granite4HLM))/checkpoint/mlp/dot_general",
     ("mlp", "backward")),
    ("jit(step)/jvp(Granite4HLM)/checkpoint/attn_full/checkpoint/exp",
     ("attn_full", "forward")),
    ("jit(step)/jvp(Granite4HLM)/while/body/checkpoint/lm_head/dot_general",
     ("lm_head", "forward")),
    # the scan's name inside another word is no scope
    ("jit(step)/jvp(Granite4HLM)/not_ssm_scan_really/x",
     ("(model, no scope)", "forward")),
])
def test_classify_the_state_space_scopes(op_name, want):
    assert profiling.classify(op_name, GRANITE_SCOPES) == want


PHI4FLASH_SCOPES = [
    "ssm_in_proj", "ssm_conv", "ssm_dt_proj", "ssm_sel_scan", "ssm_out_proj",
    "gmu", "attn_proj", "attn_window", "attn_full", "attn_cross", "attn_diff",
    "mlp", "lm_head", "loss",
]


@pytest.mark.parametrize("op_name,want", [
    # the decoder-hybrid-decoder's scopes (models/phi4flash.py): each layer
    # is under jax.checkpoint, the selective scan's blocks in a checkpointed
    # loop whose body loops over a chunk's positions
    ("jit(step)/jvp(Phi4FlashLM)/checkpoint/ssm_sel_scan/while/body/"
     "checkpoint/while/body/exp", ("ssm_sel_scan", "forward")),
    ("jit(step)/transpose(jvp(Phi4FlashLM))/checkpoint/rematted_computation/"
     "ssm_sel_scan/while/body/checkpoint/rematted_computation/mul",
     ("ssm_sel_scan", "backward")),
    ("jit(step)/jvp(Phi4FlashLM)/checkpoint/ssm_dt_proj/softplus",
     ("ssm_dt_proj", "forward")),
    ("jit(step)/transpose(jvp(Phi4FlashLM))/checkpoint/gmu/dot_general",
     ("gmu", "backward")),
    ("jit(step)/jvp(Phi4FlashLM)/checkpoint/attn_cross/pallas_call",
     ("attn_cross", "forward")),
    ("jit(step)/transpose(jvp(Phi4FlashLM))/checkpoint/attn_diff/rsqrt",
     ("attn_diff", "backward")),
    # the older scan's name is no part of the selective scan's
    ("jit(step)/jvp(Phi4FlashLM)/checkpoint/ssm_scan_not/x",
     ("(model, no scope)", "forward")),
])
def test_classify_the_hybrid_decoders_scopes(op_name, want):
    assert profiling.classify(op_name, PHI4FLASH_SCOPES) == want



QWEN3NEXT_SCOPES = [
    "gdn_in_proj", "gdn_conv", "gdn_delta", "gdn_gate_norm", "gdn_out_proj",
    "attn_proj", "attn_full", "attn_gate", "moe_route", "moe_shared",
    "moe_experts", "lm_head", "loss",
]


@pytest.mark.parametrize("op_name,want", [
    # the hybrid linear-attention decoder's scopes (models/qwen3next.py):
    # each layer is under jax.checkpoint, the delta rule's blocks in a
    # checkpointed loop with a triangular solve inside
    ("jit(step)/jvp(Qwen3NextLM)/checkpoint/gdn_delta/while/body/checkpoint/"
     "triangular_solve", ("gdn_delta", "forward")),
    ("jit(step)/transpose(jvp(Qwen3NextLM))/checkpoint/rematted_computation/"
     "gdn_delta/while/body/checkpoint/rematted_computation/dot_general",
     ("gdn_delta", "backward")),
    ("jit(step)/jvp(Qwen3NextLM)/checkpoint/gdn_in_proj/dot_general",
     ("gdn_in_proj", "forward")),
    ("jit(step)/jvp(Qwen3NextLM)/checkpoint/gdn_conv/mul",
     ("gdn_conv", "forward")),
    ("jit(step)/transpose(jvp(Qwen3NextLM))/checkpoint/gdn_gate_norm/rsqrt",
     ("gdn_gate_norm", "backward")),
    ("jit(step)/transpose(jvp(Qwen3NextLM))/checkpoint/gdn_out_proj/"
     "dot_general", ("gdn_out_proj", "backward")),
    ("jit(step)/jvp(Qwen3NextLM)/checkpoint/attn_gate/logistic",
     ("attn_gate", "forward")),
    ("jit(step)/jvp(Qwen3NextLM)/checkpoint/moe_shared/dot_general",
     ("moe_shared", "forward")),
    # the gate-norm's name is no part of the attention gate's
    ("jit(step)/jvp(Qwen3NextLM)/checkpoint/gdn_delta_not/x",
     ("(model, no scope)", "forward")),
])
def test_classify_the_linear_attention_scopes(op_name, want):
    assert profiling.classify(op_name, QWEN3NEXT_SCOPES) == want


def test_the_selective_scans_kernels_land_in_its_scope():
    """As the TPU compiler prints ops/selscan.py's two kernels in a
    Phi-4-mini-flash step (compiled here for a v5e, PR 39): the jitted
    `_forward_kernel` / `_backward_kernel` keep the model's name stack in
    front of their own, so a layer's forward, its recomputation under the
    layer's `jax.checkpoint` and its backward are all `ssm_sel_scan`, the
    first forward and the other two backward."""
    back = ("jit(step)/transpose(jvp(Phi4FlashLM))/jvp(Phi4FlashLM)/"
            "checkpoint/")
    hlo = "".join(
        f'  %{name} = (f32[1,8192,5120]{{2,1,0:T(8,128)}}, '
        'f32[1,16,5120]{2,1,0:T(8,128)}) custom-call(%a, %b), '
        'custom_call_target="tpu_custom_call", frontend_attributes={'
        'kernel_metadata={}}, '
        f'metadata={{op_name="{op_name}/pallas_call" stack_frame_id=31}}, '
        'backend_config={"custom_call_config":{"body":"TUzvUg"}}\n'
        for name, op_name in [
            ("selective_scan_forward.4",
             "jit(step)/jvp(Phi4FlashLM)/ssm_sel_scan/jit(_forward_kernel)/"
             "selective_scan_forward"),
            ("selective_scan_forward.6",
             back + "rematted_computation/ssm_sel_scan/jit(_forward_kernel)/"
             "selective_scan_forward"),
            ("selective_scan_backward.4",
             back + "ssm_sel_scan/jit(_backward_kernel)/"
             "selective_scan_backward")])
    names = op_names(hlo)
    assert set(names) == {
        "selective_scan_forward.4", "selective_scan_forward.6",
        "selective_scan_backward.4"}
    totals, top = split(
        [("selective_scan_forward.4", 0, 17), ("selective_scan_forward.6", 20,
         18), ("selective_scan_backward.4", 40, 36)], hlo, PHI4FLASH_SCOPES)
    assert totals == {("ssm_sel_scan", "forward"): 17,
                      ("ssm_sel_scan", "backward"): 54}
    assert top[0] == (36, "selective_scan_backward.4", "ssm_sel_scan")


XING4_SCOPES = [
    "mhc_map", "mhc_mix", "mla_q_proj", "mla_kv_proj", "attn_full",
    "mla_out_proj", "mlp", "moe_route", "moe_shared", "moe_experts",
    "lm_head", "loss",
]


@pytest.mark.parametrize("op_name,want", [
    # the scopes of a decoder under several residual streams
    # (models/xing4.py): every sub-layer is under jax.checkpoint
    ("jit(step)/jvp(Xing4LM)/checkpoint/mhc_map/dot_general",
     ("mhc_map", "forward")),
    ("jit(step)/transpose(jvp(Xing4LM))/checkpoint/rematted_computation/"
     "mhc_map/div", ("mhc_map", "backward")),
    ("jit(step)/jvp(Xing4LM)/checkpoint/mhc_mix/reduce_sum",
     ("mhc_mix", "forward")),
    ("jit(step)/transpose(jvp(Xing4LM))/checkpoint/mhc_mix/mul",
     ("mhc_mix", "backward")),
    ("jit(step)/jvp(Xing4LM)/mhc_mix/reduce_sum", ("mhc_mix", "forward")),
    ("jit(step)/jvp(Xing4LM)/checkpoint/mla_q_proj/dot_general",
     ("mla_q_proj", "forward")),
    ("jit(step)/jvp(Xing4LM)/checkpoint/mla_kv_proj/concatenate",
     ("mla_kv_proj", "forward")),
    ("jit(step)/transpose(jvp(Xing4LM))/checkpoint/attn_full/pallas_call",
     ("attn_full", "backward")),
    ("jit(step)/transpose(jvp(Xing4LM))/checkpoint/mla_out_proj/dot_general",
     ("mla_out_proj", "backward")),
    ("jit(step)/jvp(Xing4LM)/checkpoint/moe_route/top_k",
     ("moe_route", "forward")),
    # a sub-layer's function name is no scope
    ("jit(step)/jvp(Xing4LM)/checkpoint/mlp_half/x",
     ("(model, no scope)", "forward")),
])
def test_xing4_scopes_classify(op_name, want):
    assert profiling.classify(op_name, XING4_SCOPES) == want


NEMOTRON3S_SCOPES = [
    "ssm_in_proj", "ssm_conv", "ssm_scan", "ssm_gate_norm", "ssm_out_proj",
    "attn_proj", "attn_full", "moe_route", "moe_latent_down", "moe_experts",
    "moe_latent_up", "moe_shared", "lm_head", "loss",
]


@pytest.mark.parametrize("op_name,want", [
    # the scopes of a decoder whose layer is one mixer (models/nemotronh.py):
    # every layer is under jax.checkpoint; `moe_latent_down` and
    # `moe_latent_up` are no prefixes of one another or of `moe_experts`
    ("jit(step)/jvp(NemotronHLM)/checkpoint/moe_latent_down/dot_general",
     ("moe_latent_down", "forward")),
    ("jit(step)/transpose(jvp(NemotronHLM))/checkpoint/rematted_computation/"
     "moe_latent_up/dot_general", ("moe_latent_up", "backward")),
    ("jit(step)/transpose(jvp(NemotronHLM))/checkpoint/moe_experts/"
     "pallas_call", ("moe_experts", "backward")),
    ("jit(step)/jvp(NemotronHLM)/checkpoint/moe_shared/integer_pow",
     ("moe_shared", "forward")),
    ("jit(step)/jvp(NemotronHLM)/checkpoint/ssm_scan/pallas_call",
     ("ssm_scan", "forward")),
    ("jit(step)/transpose(jvp(NemotronHLM))/checkpoint/ssm_gate_norm/mul",
     ("ssm_gate_norm", "backward")),
    ("jit(step)/jvp(NemotronHLM)/checkpoint/attn_full/pallas_call",
     ("attn_full", "forward")),
    # a layer's function name is no scope
    ("jit(step)/jvp(NemotronHLM)/checkpoint/layer/add",
     ("(model, no scope)", "forward")),
])
def test_nemotron3s_scopes_classify(op_name, want):
    assert profiling.classify(op_name, NEMOTRON3S_SCOPES) == want
