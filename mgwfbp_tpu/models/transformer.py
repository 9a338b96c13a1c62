"""Decoder-only transformer LM with optional ring-attention sequence
parallelism.

The reference has no transformer and no sequence parallelism (its only LM is
the PTB LSTM, SURVEY.md §2.7/§5 "Long-context") — this is the TPU-native
long-context extension the `seq` mesh axis exists for. With `seq_axis` set
the module must run inside shard_map with the time dimension of its input
sharded over that axis: attention runs as a ring (parallel.ringattn), all
other ops are token-local, and positions are derived from
`lax.axis_index(seq_axis)` so embeddings see GLOBAL positions.

Architecture: Pre-LN blocks (LN -> causal MHA -> residual, LN -> GELU MLP ->
residual), learned position embeddings, final LN + untied output head.
"""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from mgwfbp_tpu.parallel.ringattn import local_attention, ring_attention


class Block(nn.Module):
    d_model: int
    num_heads: int
    d_ff: int
    dropout: float
    seq_axis: Optional[str]

    @nn.compact
    def __call__(self, h: jax.Array, train: bool) -> jax.Array:
        b, t, d = h.shape
        dh = self.d_model // self.num_heads
        a_in = nn.LayerNorm(name="ln_attn")(h)
        qkv = nn.Dense(3 * self.d_model, name="qkv")(a_in)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(b, t, self.num_heads, dh)
        k = k.reshape(b, t, self.num_heads, dh)
        v = v.reshape(b, t, self.num_heads, dh)
        if self.seq_axis is not None:
            a = ring_attention(q, k, v, axis_name=self.seq_axis, causal=True)
        else:
            a = local_attention(q, k, v, causal=True)
        a = nn.Dense(self.d_model, name="proj")(a.reshape(b, t, d))
        a = nn.Dropout(self.dropout, deterministic=not train)(a)
        h = h + a
        m_in = nn.LayerNorm(name="ln_mlp")(h)
        m = nn.Dense(self.d_ff, name="up")(m_in)
        m = nn.gelu(m)
        m = nn.Dense(self.d_model, name="down")(m)
        m = nn.Dropout(self.dropout, deterministic=not train)(m)
        return h + m


class TransformerLM(nn.Module):
    """Causal LM over integer tokens. Input (B, T_local); returns logits
    (B, T_local, vocab). task='lm' WITHOUT carry (windowed, not BPTT)."""

    vocab_size: int
    d_model: int = 256
    num_heads: int = 4
    num_layers: int = 4
    d_ff: int = 1024
    max_len: int = 4096
    dropout: float = 0.1
    seq_axis: Optional[str] = None

    @nn.compact
    def __call__(self, x: jax.Array, train: bool = False) -> jax.Array:
        t_local = x.shape[1]
        # global positions: offset by this shard's place on the seq ring
        if self.seq_axis is not None:
            pos0 = lax.axis_index(self.seq_axis) * t_local
        else:
            pos0 = 0
        pos = pos0 + jnp.arange(t_local)
        h = nn.Embed(self.vocab_size, self.d_model, name="tok_embed")(x)
        h = h + nn.Embed(self.max_len, self.d_model, name="pos_embed")(pos)
        for i in range(self.num_layers):
            h = Block(
                self.d_model, self.num_heads, self.d_ff, self.dropout,
                self.seq_axis, name=f"Block_{i}",
            )(h, train)
        h = nn.LayerNorm(name="ln_out")(h)
        return nn.Dense(self.vocab_size, name="head")(h)
