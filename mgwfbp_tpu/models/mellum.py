"""Mellum 2 (JetBrains Mellum2-12B-A2.5B): pre-norm decoder with grouped-query
attention, three sliding-window layers to one full layer, and a sparse block
of 64 SwiGLU experts (top 8, renormalized, no shared expert) in every layer.

Source: https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct
(config.json, `model_type` mellum). Per layer, with x the residual stream:

    h  = x + W_o Attn(rope(W_q u), rope(W_k u), W_v u),   u = RMSNorm(x)
    x' = h + sum_k w_k W_down,e_k (silu(W_gate,e_k v) * W_up,e_k v),
                                                          v = RMSNorm(h)

then a final RMSNorm and an untied head. No bias, no dropout. Rotary
embedding over the whole head dimension in the half-split layout: plain
(theta 500,000) on window layers, YaRN (factor 16 over 8,192 positions) on
full layers, as `transformers` computes it.

**A chip's share.** One chip cannot hold a whole layer's 64 experts with
their optimizer state (6.7 GB), so the module is told what it holds:
`layers_held` (the first n of `layer_types`), `experts_held = (first, count)`
and `vocab_size` (the rows of the embedding and the head that live here). The
router keeps its 64 outputs and its 8 experts a token; the weights are
renormalized over all 8 chosen; only the terms whose expert is held are
computed and added. A token none of whose experts live here gets zero from
the block. Nothing stands in for the absent chips or their exchange.

**Dropless.** No capacity: the step's (token, expert) assignments are sorted
by expert and go through ONE grouped product per weight
(`ops/groupmm.grouped_product`: a tiled kernel where the step is traced for a
TPU, `jax.lax.ragged_dot` elsewhere), sized for the worst case that every
assignment lands here (tokens x 8 rows, of which a quarter are expected to be
in a group; either grouped product skips the rest). Taking the tokens a chunk
at a time saved no memory and made the grouped products a half slower (224
against 150 ms a step, my chip runs, PR 26), so there is one group per expert
and step.

**Memory.** The attention core keeps no scores (ops/blockattn.py: the fused
kernel saves its output and a row log-sum-exp, the plain blocks recompute), the
experts recompute their sorted rows and products, and with `targets` the loss is taken over
`loss_block` tokens at a time, each block's logits recomputed in the backward
pass: no (tokens, vocabulary) array outlives a block. What is saved per layer
is the residual stream and the projections' inputs.

Assumed (the config names none of them): no query/key normalization, no
router bias, no load-balancing loss, no multi-token-prediction head; initial
weights normal(0, 0.02), norms at one.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
# `lax`: tests/benchmark/test_mellum2_reference.py poisons `mellum.lax.ragged_dot`
from jax import lax  # noqa: F401

from mgwfbp_tpu.models.lm_parts import (
    ATTENTION,
    EXPERTS,
    FULL,
    MOE_DROPPED_KEY,
    MOE_TOKENS_KEY,
    SCOPES,
    SLIDING,
    _Leaves,
    apply_rope,
    held_experts,
    plain_inv_freq,
    rms_norm,
    route,
    routing_counters,
    token_losses,
    yarn_inv_freq,
)
from mgwfbp_tpu.ops.blockattn import blockwise_attention
from mgwfbp_tpu.ops.programs import counted


@dataclasses.dataclass(frozen=True)
class MellumShape:
    """The published sizes (config.json); a test builds a smaller one."""

    vocab_size: int = 98304
    hidden_size: int = 2304
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    num_experts: int = 64
    experts_per_token: int = 8
    expert_width: int = 896
    sliding_window: int = 1024
    layer_types: tuple[str, ...] = (SLIDING, SLIDING, SLIDING, FULL) * 7
    rms_norm_eps: float = 1e-6
    rope_theta: float = 500000.0
    yarn_factor: float = 16.0
    yarn_original_len: int = 8192
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_attention_factor: float = 1.2772588722239782


MELLUM2 = MellumShape()
# the architecture at a size the CPU tests hold (benchmarks/references/
# mellum2_share_tiny.py states the same numbers independently)
MELLUM2_TINY = MellumShape(
    vocab_size=256, hidden_size=64, num_heads=4, num_kv_heads=2, head_dim=16,
    num_experts=8, experts_per_token=2, expert_width=32, sliding_window=16,
    layer_types=(SLIDING, SLIDING, SLIDING, FULL),
)


def rope_inv_freq(shape: MellumShape, kind: str) -> tuple[jax.Array, float]:
    """(inverse frequencies (head_dim / 2,), factor on cos and sin) of a layer
    of `kind`: plain on window layers, YaRN on full ones."""
    if kind == SLIDING:
        return plain_inv_freq(shape.head_dim, shape.rope_theta), 1.0
    return yarn_inv_freq(
        shape.head_dim, shape.rope_theta, shape.yarn_factor,
        shape.yarn_original_len, shape.yarn_beta_fast, shape.yarn_beta_slow,
    ), shape.yarn_attention_factor


def attention(p: dict, x: jax.Array, shape: MellumShape, kind: str,
              block: int) -> jax.Array:
    """The attention sublayer on the normed input x (B, T, hidden)."""
    b, t, _ = x.shape
    hd = shape.head_dim
    with jax.named_scope("attn_proj"):
        q = (x @ p["wq"]).reshape(b, t, shape.num_heads, hd)
        k = (x @ p["wk"]).reshape(b, t, shape.num_kv_heads, hd)
        v = (x @ p["wv"]).reshape(b, t, shape.num_kv_heads, hd)
        inv_freq, factor = rope_inv_freq(shape, kind)
        # the scores' 1 / sqrt(D) rides on q's rotation, which is float32
        # inside, so q is rounded to the compute dtype once: the fused
        # kernel takes its scale folded into q, and 2 ** -3.5 applied to a
        # rounded q rounds it again (first_loss_rel 2.1e-5 against 1.4e-5
        # on one seed, the plain blocks 1.5e-5; my chip runs, PR 31)
        q = apply_rope(q, inv_freq, factor * hd ** -0.5)
        k = apply_rope(k, inv_freq, factor)
    window = shape.sliding_window if kind == SLIDING else None
    if window is not None:
        # a block of the band computes block + window (+ alignment) keys
        # for `window` useful ones: a quarter of the window a block wastes
        # half as much as a half (16.4 against 33.2 ms a layer, forward and
        # backward, at 256 and 512 of 1,024; my chip run, PR 26)
        block = min(block, max(window // 4, 1))
    with jax.named_scope("attn_window" if kind == SLIDING else "attn_full"):
        a = blockwise_attention(
            q, k, v, window=window, block=block, scale=1.0)
    with jax.named_scope("attn_proj"):
        return a.reshape(b, t, shape.num_heads * hd) @ p["wo"]


def sparse_block(p: dict, x: jax.Array, shape: MellumShape, first: int):
    """The sparse block on the normed input x (B, T, hidden): (y, tokens per
    held expert (E,) float32, dropped float32). The experts' part keeps
    nothing but its inputs for the backward pass (`jax.checkpoint`)."""
    b, t, d = x.shape
    u = x.reshape(b * t, d)
    with jax.named_scope("moe_route"):
        idx, weights = route(u, p["router"], shape.experts_per_token)
    with jax.named_scope("moe_experts"):
        y, sizes, dropped = counted(
            jax.checkpoint(held_experts, static_argnums=6))(
                u, idx, weights, p["w_gate"], p["w_up"], p["w_down"], first)
    return (
        y.reshape(b, t, d), sizes.astype(jnp.float32),
        dropped.astype(jnp.float32),
    )


class Mellum2LM(nn.Module):
    """Causal LM over integer tokens, task `lm` without carry.

    `model(x)` returns logits (B, T, vocab_size). `model(x, targets=y)`
    returns (per-token loss (B, T) float32, routing counts) without ever
    holding the logits of more than `loss_block` tokens: the path the train
    and eval steps take (`ModelMeta.fused_loss`)."""

    vocab_size: int = MELLUM2.vocab_size
    shape: MellumShape = MELLUM2
    layers_held: Optional[int] = None  # the first n of shape.layer_types
    experts_held: tuple[int, int] = (0, MELLUM2.num_experts)  # (first, count)
    attn_block: int = 512  # queries a block; a window layer takes fewer
    loss_block: int = 2048

    # the scopes `__call__` enters, here and through lm_parts, each with its
    # layer of PERF.md's map (profiling.classify; Trainer._note_first_dispatch)
    scopes = {
        "attn_proj": ATTENTION, "attn_window": ATTENTION,
        "attn_full": ATTENTION, "moe_route": EXPERTS, "moe_experts": EXPERTS,
        **SCOPES["token_losses"],
    }
    # what `__call__` puts among the step's metrics, and `step_counters`
    # takes back on the host (Trainer._drain_health)
    health_keys = (MOE_TOKENS_KEY, MOE_DROPPED_KEY)

    def layer_kinds(self) -> tuple[str, ...]:
        kinds = self.shape.layer_types
        return kinds if self.layers_held is None else kinds[: self.layers_held]

    def step_counters(self, stats: dict, *, tokens: int) -> dict:
        """The `step` record's routing counters from one step's statistics
        as host arrays; `tokens` one device's tokens a (micro-)step, so
        tokens x experts a token are a layer's assignments."""
        return routing_counters(stats, tokens * self.shape.experts_per_token)

    @nn.compact
    def __call__(self, x: jax.Array, targets: Optional[jax.Array] = None,
                 train: bool = False):
        s = self.shape
        first, count = self.experts_held
        if not (0 <= first and count >= 1 and first + count <= s.num_experts):
            raise ValueError(
                f"experts held {first}:{count} are not among the model's "
                f"{s.num_experts}")
        d, f = s.hidden_size, s.expert_width
        dq, dkv = s.num_heads * s.head_dim, s.num_kv_heads * s.head_dim
        layer_shapes = (
            ("attn_norm", (d,), True), ("wq", (d, dq), False),
            ("wk", (d, dkv), False), ("wv", (d, dkv), False),
            ("wo", (dq, d), False), ("moe_norm", (d,), True),
            ("router", (d, s.num_experts), False),
            ("w_gate", (count, d, f), False), ("w_up", (count, d, f), False),
            ("w_down", (count, f, d), False),
        )
        embed = _Leaves(
            (("embedding", (self.vocab_size, d), False),), name="embed",
        )()["embedding"]
        kinds = self.layer_kinds()
        layers = [
            _Leaves(layer_shapes, name=f"layer_{i}")()
            for i in range(len(kinds))
        ]
        out = _Leaves(
            (("norm", (d,), True), ("head", (d, self.vocab_size), False)),
            name="out",
        )()
        if self.is_initializing():
            # init wants the declarations above and no forward pass (run
            # eagerly at the real size it would compile op by op)
            return jnp.zeros((*x.shape, self.vocab_size), embed.dtype)

        h = embed[x]
        tokens, dropped = [], []
        for p, kind in zip(layers, kinds):
            h = h + attention(
                p, rms_norm(h, p["attn_norm"], s.rms_norm_eps), s, kind,
                self.attn_block)
            y, layer_tokens, layer_dropped = sparse_block(
                p, rms_norm(h, p["moe_norm"], s.rms_norm_eps), s, first)
            h = h + y
            tokens.append(layer_tokens)
            dropped.append(layer_dropped)
        h = rms_norm(h, out["norm"], s.rms_norm_eps)
        if targets is None:
            with jax.named_scope("lm_head"):
                return jnp.dot(h, out["head"])
        b, t = x.shape
        losses = token_losses(
            h.reshape(b * t, d), out["head"], targets.reshape(b * t),
            self.loss_block)
        stats = {
            # (layers held, experts held): tokens each held expert took
            MOE_TOKENS_KEY: jnp.stack(tokens),
            MOE_DROPPED_KEY: jnp.sum(jnp.stack(dropped)),
        }
        return losses.reshape(b, t), stats
