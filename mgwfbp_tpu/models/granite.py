"""Granite 4.0-H (ibm-granite granite-4.0-h-micro, `model_type`
granitemoehybrid): a pre-norm decoder of Mamba-2 layers with one attention
layer in ten, a gated MLP in every layer, four muP-style multipliers and a
tied embedding.

Source: https://huggingface.co/ibm-granite/granite-4.0-h-micro (config.json),
the equations as `transformers`' `modeling_granitemoehybrid.py` computes
them. x is the residual stream, RMSNorm(u) = g u / sqrt(mean(u^2) + 1e-5),
no bias but the convolution's:

    x = 12 E[ids]
    Mamba layer:  u = RMSNorm(x);  [z | xBC | dt] = u W_in
                  xBC = silu(conv1d_causal_depthwise(xBC, 4) + b)
                  [xs | B | C] = xBC;  dt = softplus(dt + dt_bias)
                  per head: S_t = exp(dt_t A) S_{t-1} + dt_t xs_t B_t^T,
                            y_t = S_t C_t + D xs_t,      A = -exp(A_log)
                  x = x + 0.22 (RMSNorm(y silu(z)) W_out)
    attention layer (5, 15, 25, 35): GQA, no position term, softmax of
                  q.k / 64;  x = x + 0.22 (a W_o)
    every layer:  v = RMSNorm(x);  [p | q] = v W_1
                  x = x + 0.22 ((silu(p) q) W_2)
    out:          logits = (RMSNorm(x) E^T) / 8, the same E

B and C are shared by all heads (`mamba_n_groups` 1) and the gated norm runs
over all inner channels at once. The scan is `ops/ssd.py`'s chunked form
(chunk 256 as published), which reads x, B and C in place out of the
convolution's output (`ssd_scan_in_place`: kernels on the chip, the plain
form elsewhere).

**A chip's share.** `layers_held` (the first n of `layer_types`) and
`vocab_size` (the rows of the tied embedding that live here), as
models/mellum.py takes them. The model is dense: there is nothing else to
share out.

**Memory.** Every layer is under `jax.checkpoint`: what the forward pass
keeps per layer is the residual stream, and the backward pass recomputes a
layer before it differentiates it. Inside, the scan recomputes its blocks of
chunks (its kernels a chunk's matrices, in VMEM), the attention core keeps no scores (ops/blockattn.py), and the loss recomputes its token blocks
(`mellum.token_losses`), so neither a (chunk, chunk) decay matrix per head
and chunk, nor a (T, T) score matrix, nor (tokens, vocabulary) logits
outlive their block.

**Counters.** With `targets` the model returns, beside the per-token loss,
`health/ssm_state` (the root mean square of each Mamba layer's state after
the last position) and `health/ssm_log_decay_min` (per Mamba layer, the most
negative sum of log-decays over one chunk): arrays that leave the chip by
the health statistics' road; `step_counters` turns them into the `step`
record's `ssm_state_rms` and `ssm_log_decay_min`.

Assumed (config.json names none): initial weights normal(0, 0.02); the
convolution's weight and bias uniform in +-1 / sqrt(4), the depthwise
Conv1d's default that the Mamba-2 authors' code keeps (at normal(0, 0.02)
the scan's inputs are fifty times smaller than the `D` skip beside it, and a
wrong scan would move no number a seeded comparison reads); `A_log` the log
of a uniform draw from [1, 16]; `dt_bias` the inverse softplus of a
log-uniform step in [1e-3, 1e-1]; `D` and the norms at one; sequences of one
length, no document mask, the state never reset inside a sequence and zero
at its start.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from mgwfbp_tpu.models.lm_parts import (
    ATTENTION as ATTENTION_LAYER,
    SCOPES,
    _Leaves,
    gated_mlp,
    mamba2_leaves,
    mamba2_mixer,
    rms_norm,
    token_losses,
)
from mgwfbp_tpu.ops.blockattn import blockwise_attention
from mgwfbp_tpu.ops.programs import counted

MAMBA, ATTENTION = "mamba", "attention"
# the step's metrics carry these under HEALTH_PREFIX of train/step.py
SSM_STATE_KEY = "health/ssm_state"
SSM_LOG_DECAY_KEY = "health/ssm_log_decay_min"


@dataclasses.dataclass(frozen=True)
class GraniteShape:
    """The published sizes (config.json); a test builds a smaller one."""

    vocab_size: int = 100352
    hidden_size: int = 2048
    intermediate_size: int = 8192  # `shared_intermediate_size`
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 64
    mamba_heads: int = 64
    mamba_head_dim: int = 64
    mamba_state: int = 128
    mamba_conv: int = 4
    mamba_chunk: int = 256
    layer_types: tuple[str, ...] = (
        (MAMBA,) * 5 + (ATTENTION,) + (MAMBA,) * 4) * 4
    rms_norm_eps: float = 1e-5
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0

    @property
    def mamba_inner(self) -> int:  # hidden_size x `mamba_expand`
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_channels(self) -> int:  # xs, B and C
        return self.mamba_inner + 2 * self.mamba_state


GRANITE4H = GraniteShape()
# the architecture at a size the CPU tests hold (benchmarks/references/
# granite4h_share_tiny.py states the same numbers independently)
GRANITE4H_TINY = GraniteShape(
    vocab_size=256, hidden_size=32, intermediate_size=48, num_heads=4,
    num_kv_heads=2, head_dim=8, mamba_heads=4, mamba_head_dim=16,
    mamba_state=8, mamba_chunk=16,
    layer_types=(MAMBA, MAMBA, ATTENTION, MAMBA),
)


def mamba_mixer(p: dict, u: jax.Array, shape: GraniteShape, scan_block: int):
    """The Mamba-2 mixer on the normed input u (B, T, hidden): (y (B, T,
    hidden), root mean square of the final state, most negative chunk sum of
    log-decays). `lm_parts.mamba2_mixer` at this model's one group."""
    return mamba2_mixer(
        p, u, heads=shape.mamba_heads, head_dim=shape.mamba_head_dim,
        state=shape.mamba_state, groups=1, chunk=shape.mamba_chunk,
        eps=shape.rms_norm_eps, scan_block=scan_block)


def attention(p: dict, u: jax.Array, shape: GraniteShape, block: int):
    """Grouped-query attention without a position term on the normed input
    u (B, T, hidden), scores times the published multiplier."""
    b, t, _ = u.shape
    hd = shape.head_dim
    with jax.named_scope("attn_proj"):
        q = (u @ p["wq"]).reshape(b, t, shape.num_heads, hd)
        k = (u @ p["wk"]).reshape(b, t, shape.num_kv_heads, hd)
        v = (u @ p["wv"]).reshape(b, t, shape.num_kv_heads, hd)
    with jax.named_scope("attn_full"):
        a = blockwise_attention(
            q, k, v, block=block, scale=shape.attention_multiplier)
    with jax.named_scope("attn_proj"):
        return a.reshape(b, t, shape.num_heads * hd) @ p["wo"]


def layer(p: dict, x: jax.Array, kind: str, shape: GraniteShape,
          attn_block: int, scan_block: int):
    """One decoder layer on the residual stream: (x', state rms, most
    negative chunk log-decay); the last two zero on an attention layer."""
    u = rms_norm(x, p["norm"], shape.rms_norm_eps)
    if kind == MAMBA:
        y, state_rms, low = mamba_mixer(p, u, shape, scan_block)
    else:
        y = attention(p, u, shape, attn_block)
        state_rms = low = jnp.zeros((), jnp.float32)
    r = jnp.asarray(shape.residual_multiplier, x.dtype)
    x = x + r * y
    x = x + r * gated_mlp(p, rms_norm(x, p["mlp_norm"], shape.rms_norm_eps),
                          shape)
    return x, state_rms, low


def layer_leaves(kind: str, s: GraniteShape) -> tuple:
    d, f = s.hidden_size, s.intermediate_size
    mlp = (("mlp_norm", (d,), True), ("w1", (d, 2 * f), False),
           ("w2", (f, d), False))
    if kind == ATTENTION:
        dq, dkv = s.num_heads * s.head_dim, s.num_kv_heads * s.head_dim
        return (("norm", (d,), True), ("wq", (d, dq), False),
                ("wk", (d, dkv), False), ("wv", (d, dkv), False),
                ("wo", (dq, d), False), *mlp)
    return (
        ("norm", (d,), True),
        *mamba2_leaves(d, s.mamba_heads, s.mamba_head_dim, s.mamba_state, 1),
        *mlp)


class Granite4HLM(nn.Module):
    """Causal LM over integer tokens, task `lm` without carry.

    `model(x)` returns logits (B, T, vocab_size). `model(x, targets=y)`
    returns (per-token loss (B, T) float32, the scan's counters) without
    ever holding the logits of more than `loss_block` tokens: the path the
    train and eval steps take (`ModelMeta.fused_loss`)."""

    vocab_size: int = GRANITE4H.vocab_size
    shape: GraniteShape = GRANITE4H
    layers_held: Optional[int] = None  # the first n of shape.layer_types
    attn_block: int = 512
    loss_block: int = 2048
    scan_block: int = 8  # chunks of the scan recomputed together
    # the scopes `__call__` enters, here and through lm_parts, each with its
    # layer of PERF.md's map (profiling.classify; Trainer._note_first_dispatch)
    scopes = {
        **SCOPES["mamba2_mixer"], **SCOPES["gated_mlp"],
        "attn_proj": ATTENTION_LAYER, "attn_full": ATTENTION_LAYER,
        **SCOPES["token_losses"],
    }
    # what `__call__` puts among the step's metrics, and `step_counters`
    # takes back on the host (Trainer._drain_health)
    health_keys = (SSM_STATE_KEY, SSM_LOG_DECAY_KEY)

    def layer_kinds(self) -> tuple[str, ...]:
        kinds = self.shape.layer_types
        return kinds if self.layers_held is None else kinds[: self.layers_held]

    @nn.compact
    def __call__(self, x: jax.Array, targets: Optional[jax.Array] = None,
                 train: bool = False):
        s = self.shape
        d = s.hidden_size
        # ONE leaf, used by the lookup and by the head
        embed = _Leaves(
            (("embedding", (self.vocab_size, d), False),), name="embed",
        )()["embedding"]
        kinds = self.layer_kinds()
        layers = [
            _Leaves(layer_leaves(kind, s), name=f"layer_{i}")()
            for i, kind in enumerate(kinds)
        ]
        norm = _Leaves((("norm", (d,), True),), name="out")()["norm"]
        if self.is_initializing():
            # the declarations above and no forward pass (models/mellum.py)
            return jnp.zeros((*x.shape, self.vocab_size), embed.dtype)

        h = jnp.asarray(s.embedding_multiplier, embed.dtype) * embed[x]
        state_rms, low = [], []
        for p, kind in zip(layers, kinds):
            # what the layer traces is counted where its trace is a cached
            # one too
            h, layer_rms, layer_low = counted(jax.checkpoint(
                layer, static_argnums=(2, 3, 4, 5)))(
                    p, h, kind, s, self.attn_block, self.scan_block)
            if kind == MAMBA:
                state_rms.append(layer_rms)
                low.append(layer_low)
        h = rms_norm(h, norm, s.rms_norm_eps)
        h = h * jnp.asarray(1.0 / s.logits_scaling, h.dtype)
        if targets is None:
            with jax.named_scope("lm_head"):
                return jnp.dot(h, embed.T)
        b, t = x.shape
        losses = token_losses(
            h.reshape(b * t, d), embed.T, targets.reshape(b * t),
            self.loss_block)
        stats = {
            SSM_STATE_KEY: jnp.stack(state_rms),  # (Mamba layers held,)
            SSM_LOG_DECAY_KEY: jnp.stack(low),
        } if state_rms else {}
        return losses.reshape(b, t), stats

    def step_counters(self, stats: dict, *, tokens: int) -> dict:
        """The `step` record's counters from one step's statistics as host
        arrays: `ssm_state_rms` the mean over the Mamba layers held,
        `ssm_log_decay_min` the most negative over them."""
        del tokens
        return {
            "ssm_state_rms": float(np.mean(stats[SSM_STATE_KEY])),
            "ssm_log_decay_min": float(np.min(stats[SSM_LOG_DECAY_KEY])),
        }
