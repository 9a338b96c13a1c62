"""Xing4.0-29B-A4B (XingChen-AGI, `model_type` xing4_0): a pre-norm decoder
whose residual path is FOUR streams under manifold-constrained
hyper-connections (arXiv:2512.24880), whose attention is multi-head latent
attention (scores over 128 + 64 rotary = 192, values over 128), and whose
feed-forward is dense (9,216) in layers 0 and 1 and, from layer 2 on, 64
sigmoid-routed SwiGLU experts of width 1,024 (4 a token, CHOSEN by score +
selection bias, WEIGHTED by score alone, normalised, times 2) beside one
shared expert.

Source: https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B/blob/main/config.json.
n = `hc_mult` = 4, C = 3,584, X the streams (n, B, T, C), no bias anywhere.

**Streams.** X_0[i] = Emb(t) for every i. A sub-layer F (the attention or the
feed-forward of a layer) has its own phi (nC x (n^2 + 2n)), b (n^2 + 2n) and
three scalars alpha_pre, alpha_post, alpha_res:

    x~ = vec(X) / sqrt(mean(vec(X)^2) + hc_eps)        over the nC, no scale
    [a_pre | a_post | a_res] = x~ phi                  float32
    H_pre  = sigmoid(alpha_pre a_pre + b_pre)          (n)
    H_post = 2 sigmoid(alpha_post a_post + b_post)     (n)
    H_res  = SK(clip(alpha_res mat(a_res) + b_res, -30, 30))      (n x n)
    SK(m): M = exp(m); 20 times: rows over (their sums + hc_eps), then
           columns over (their sums + hc_eps)
    u = sum_i H_pre[i] X[i];   y = F(RMSNorm_l(u))
    X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y

and after the last layer h = sum_i X[i], the final RMSNorm and an untied head.
The backward pass differentiates through the 20 iterations.

**Latent attention** (DeepSeek-V3's, whose key names the config carries), u
the normed read of the streams, 32 heads:

    c_q = RMSNorm(u W_dq) (768);   a head's [q_n 128 | q_r 64] = c_q W_uq
    [c_kv 512 | k_r 64] = u W_dkv; a head's [k_n 128 | v 128] = RMSNorm(c_kv) W_ukv
    q = [q_n | rope(q_r)],  k = [k_n | rope(k_r)]: ONE k_r for all 32 heads
    scores q . k x 192^-1/2 x m^2, m = 0.1 ln 64 + 1; causal; softmax float32
    out = concat_heads(P v) W_o                        (4,096 -> 3,584)

rope: YaRN over the 64 (theta 10,000, factor 64 over 4,096 positions,
beta_fast 32, beta_slow 1), half-split layout, cos and sin times 1 (`mscale`
equals `mscale_all_dim`). No cache and no absorbed form: this is training.

**Experts** (layers 2 on), u' the normed read: s = sigmoid(u' W_r) in float32
over all 64; the 4 largest of s + bias; weights s over the sum of the chosen
four, times 2; output = the held experts' part + Shared(u'), the shared expert
ungated. The bias is a leaf no gradient reaches (the indices carry none) and
the optimizer leaves where it is (its update from the load is the publisher's
recipe, in no key).

**A chip's share**, as models/laguna.py takes it: `layers_held = (first,
count)`, a pipeline stage under the published layer numbers (`layer_1` ..
`layer_5`), `experts_held = (first, count)` of every sparse layer and
`vocab_size`. The router keeps its 64 outputs, its 4 a token and its
normaliser over all 4 chosen; only the terms whose expert is held are added
(`lm_parts.held_experts`). Nothing stands in for the absent chips, and the
multi-token-prediction module (the model's last layer) is not built.

**Memory.** Every sub-layer (mapping, mixer or feed-forward, write-back) is
under one `jax.checkpoint`: what is saved is the four streams in the compute
dtype at each of the 2 x layers boundaries (T 8,192: 235 MB each) and nothing
of float32 as wide as the streams. The passes over the streams (the norm's
sum of squares, the products with phi's rows, the read, the write-back) are
`ops/streams.py`'s: on the chip four Pallas kernels of one read each, forward
and backward, elsewhere the plain `jax.numpy` form they were here (PR 44),
each pass under a `jax.checkpoint` of its own. What is left here is the
arithmetic on a token's 24 floats: the gates, the clamp, the Sinkhorn
iterations.

**Counters.** With `targets` the model returns Mellum 2's routing counts and,
per sub-layer, the largest |row or column sum of H_res - 1| and the mean mass
of H_res off its diagonal; per layer the rms of c_kv before its norm; per
sparse layer the share of the T x 4 choices that s + bias made and s alone
would not have. `step_counters` turns them into `mhc_res_gap`,
`mhc_res_offdiag`, `mla_kv_latent_rms`, `moe_bias_swap_share` beside the
routing counters.

Assumed, each in ONE place here (and one in the plain reference), because
config.json does not settle it:
  * n copies of the embedding at the input and the sum of the streams at the
    output (arXiv:2409.19606's): `Xing4LM.__call__`;
  * rows before columns in an iteration, `hc_eps` added to the sums, the clamp
    on the exponent, a norm without learned scale before phi: `stream_maps`;
  * the initial draws (`_phi_init`, `_b_init`, `_alpha_init`, `_bias_init`):
    phi normal(0, (nC)^-1/2) so that x~ phi is of order one at any size,
    alphas 0.5, b_pre = b_post = 0, b_res = 2 on the diagonal (H_res starts
    with about 0.3 of its mass off the diagonal, moved token by token by the
    dynamic part), the selection bias uniform +-`selection_bias_init`: the
    dynamic part, the mixing and the bias all move step 1's loss and gradient.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from mgwfbp_tpu.models.lm_parts import (
    ATTENTION,
    EXPERTS,
    MLP,
    MOE_DROPPED_KEY,
    MOE_TOKENS_KEY,
    SCOPES,
    STREAMS,
    _bias_init,
    _Leaves,
    apply_rope,
    held_experts,
    rms_norm,
    routing_counters,
    sigmoid_bias_route as route,
    swiglu,
    token_losses,
    yarn_inv_freq,
)
from mgwfbp_tpu.ops import streams
from mgwfbp_tpu.ops.blockattn import blockwise_attention
from mgwfbp_tpu.ops.programs import counted

DENSE, SPARSE = "dense", "sparse"
# the step's metrics carry these under HEALTH_PREFIX of train/step.py
MHC_GAP_KEY = "health/mhc_res_gap"
MHC_OFFDIAG_KEY = "health/mhc_res_offdiag"
MLA_LATENT_KEY = "health/mla_kv_latent_rms"
MOE_SWAP_KEY = "health/moe_bias_swap"


@dataclasses.dataclass(frozen=True)
class Xing4Shape:
    """The published sizes (config.json); a test builds a smaller one."""

    vocab_size: int = 131072
    hidden_size: int = 3584
    num_layers: int = 40
    first_k_dense: int = 2  # `first_k_dense_replace`: layers 0 and 1
    intermediate_size: int = 9216  # the dense layers' MLP
    num_heads: int = 32
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    num_experts: int = 64
    experts_per_token: int = 4
    expert_width: int = 1024
    shared_expert_width: int = 1024  # `n_shared_experts` 1 of the width
    routed_scaling_factor: float = 2.0
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    yarn_factor: float = 64.0
    yarn_original_len: int = 4096
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 1.0
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp: tuple[float, float] = (-30.0, 30.0)
    # half the width of the selection bias's seeded draw (assumed): against
    # scores whose fourth and fifth largest lie a few hundredths apart
    selection_bias_init: float = 0.1

    def kind(self, index: int) -> str:
        return DENSE if index < self.first_k_dense else SPARSE

    @property
    def score_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    def _yarn_mscale(self, scale: float) -> float:
        """`yarn_get_mscale(factor, scale)` of the published code."""
        return 0.1 * scale * math.log(self.yarn_factor) + 1.0

    @property
    def score_scale(self) -> float:
        """score_dim^-1/2 x m^2, m the mscale at `mscale_all_dim`."""
        return self.score_dim ** -0.5 * self._yarn_mscale(
            self.yarn_mscale_all_dim) ** 2

    @property
    def rope_factor(self) -> float:
        """What cos and sin carry: m(mscale) / m(mscale_all_dim)."""
        return self._yarn_mscale(self.yarn_mscale) / self._yarn_mscale(
            self.yarn_mscale_all_dim)

    @property
    def map_width(self) -> int:
        return self.hc_mult * self.hc_mult + 2 * self.hc_mult


XING4 = Xing4Shape()
# the architecture at a size the CPU tests hold (benchmarks/references/
# xing4_share_tiny.py states the same numbers independently): four streams,
# two dense layers then two sparse, a score width (16 + 8) one and a half
# times the values' (16) as published
XING4_TINY = Xing4Shape(
    vocab_size=256, hidden_size=32, num_layers=4, first_k_dense=2,
    intermediate_size=48, num_heads=4, q_lora_rank=24, kv_lora_rank=16,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, num_experts=8,
    experts_per_token=2, expert_width=16, shared_expert_width=16,
    yarn_original_len=32, yarn_beta_fast=4.0, selection_bias_init=0.02,
)


def _phi_init(key, shape, dtype=jnp.float32):
    return jax.random.normal(key, shape, dtype) * shape[0] ** -0.5


def _alpha_init(key, shape, dtype=jnp.float32):
    del key
    return jnp.full(shape, 0.5, dtype)


def _b_init(key, shape, dtype=jnp.float32):
    """[b_pre (n) | b_post (n) | b_res (n x n, rows first)]: zeros but for 2
    on b_res's diagonal."""
    del key
    n = int(round((1 + shape[0]) ** 0.5)) - 1  # n^2 + 2n = shape[0]
    return jnp.concatenate(
        [jnp.zeros((2 * n,), dtype), 2.0 * jnp.eye(n, dtype=dtype).reshape(-1)])


def rope_inv_freq(s: Xing4Shape) -> jax.Array:
    return yarn_inv_freq(
        s.qk_rope_head_dim, s.rope_theta, s.yarn_factor, s.yarn_original_len,
        s.yarn_beta_fast, s.yarn_beta_slow)


def sinkhorn(m: jax.Array, iters: int, eps: float) -> jax.Array:
    """m (n, n, ...) the exponents, rows first: exp, then `iters` times every
    row over (its sum + eps) and every column over (its sum + eps)."""
    h = jnp.exp(m)
    for _ in range(iters):
        h = h / (jnp.sum(h, axis=1, keepdims=True) + eps)
        h = h / (jnp.sum(h, axis=0, keepdims=True) + eps)
    return h


def stream_maps(phi, b, alpha, x: jax.Array, s: Xing4Shape):
    """The three mappings of one sub-layer from the streams x (n, B, T, C):
    (H_pre (n, B, T), H_post (n, B, T), H_res (n, n, B, T) as [to, from]),
    float32, the token's position last so that the 4 x 4 arithmetic runs over
    whole lanes; and, fourth, what `ops/streams.py`'s pass over the streams
    has read already (None, or u made from these alphas and the streams for
    the write-back: for `streams.read_streams`). The passes over the streams
    are `ops/streams.py`'s: x~ phi = (x phi) over the token's rms."""
    n = x.shape[0]
    a, read = streams.map_streams(phi, b, alpha, x, s.hc_eps)
    f32 = jnp.float32
    b = b.astype(f32)[:, None, None]
    alpha = alpha.astype(f32)
    pre = jax.nn.sigmoid(alpha[0] * a[:n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(alpha[1] * a[n:2 * n] + b[n:2 * n])
    lo, hi = s.hc_res_clamp
    res = sinkhorn(
        jnp.clip(alpha[2] * a[2 * n:] + b[2 * n:], lo, hi).reshape(
            n, n, *a.shape[1:]),
        s.hc_sinkhorn_iters, s.hc_eps)
    return pre, post, res, read


def res_counters(res: jax.Array):
    """(largest |row or column sum of H_res - 1| over the tokens, mean over
    the tokens and rows of a row's mass off the diagonal) of H_res (n, n, B,
    T)."""
    n = res.shape[0]
    gap = jnp.maximum(
        jnp.max(jnp.abs(jnp.sum(res, axis=1) - 1.0)),
        jnp.max(jnp.abs(jnp.sum(res, axis=0) - 1.0)))
    diagonal = sum(res[i, i] for i in range(n))
    offdiag = jnp.mean(jnp.sum(res, axis=(0, 1)) - diagonal) / n
    return lax.stop_gradient(gap), lax.stop_gradient(offdiag)


# x'[i] = sum_j H_res[i, j] x[j] + H_post[i] y: `write_streams(x, res, post,
# y)`, under the name the reference's tests call it by
write_streams = streams.write_streams


def latent_attention(p: dict, u: jax.Array, s: Xing4Shape, block: int):
    """The attention sub-layer on the normed read u (B, T, hidden): (W_o of
    the heads' outputs, rms of c_kv before its norm)."""
    b, t, _ = u.shape
    h, dn, dv = s.num_heads, s.qk_nope_head_dim, s.v_head_dim
    inv_freq = rope_inv_freq(s)
    with jax.named_scope("mla_q_proj"):
        # the scores' factor rides on c_q's norm, float32 inside, so q is
        # rounded to the compute dtype once (models/mellum.attention)
        c_q = rms_norm(
            u @ p["w_dq"],
            p["q_norm"].astype(jnp.float32) * s.score_scale, s.rms_norm_eps)
        q = (c_q @ p["w_uq"]).reshape(b, t, h, s.score_dim)
        q = jnp.concatenate(
            [q[..., :dn], apply_rope(q[..., dn:], inv_freq, s.rope_factor)],
            axis=-1)
    with jax.named_scope("mla_kv_proj"):
        latent = u @ p["w_dkv"]  # a token's [c_kv | k_r]
        c_kv = latent[..., :s.kv_lora_rank]
        latent_rms = lax.stop_gradient(jnp.sqrt(jnp.mean(
            jnp.square(c_kv.astype(jnp.float32)))))
        kv = (rms_norm(c_kv, p["kv_norm"], s.rms_norm_eps)
              @ p["w_ukv"]).reshape(b, t, h, dn + dv)
        # ONE rotated k_r a token, shared by every head
        k_r = apply_rope(
            latent[..., None, s.kv_lora_rank:], inv_freq, s.rope_factor)
        k = jnp.concatenate(
            [kv[..., :dn],
             jnp.broadcast_to(k_r, (b, t, h, s.qk_rope_head_dim))], axis=-1)
        v = kv[..., dn:]
    with jax.named_scope("attn_full"):
        a = blockwise_attention(q, k, v, block=block, scale=1.0)
    with jax.named_scope("mla_out_proj"):
        return a.reshape(b, t, h * dv) @ p["wo"], latent_rms


def sparse_block(p: dict, x: jax.Array, s: Xing4Shape, first: int):
    """The sparse block on the normed read x (B, T, hidden): the shared
    expert whole plus the held routed experts' part. (y, tokens per held
    expert (E,) float32, dropped float32, swapped share)."""
    b, t, d = x.shape
    u = x.reshape(b * t, d)
    with jax.named_scope("moe_route"):
        idx, weights, swapped = route(
            u, p["router"], p["router_bias"], s.experts_per_token,
            s.routed_scaling_factor)
    with jax.named_scope("moe_shared"):
        shared = swiglu(u, p["shared_gate"], p["shared_up"], p["shared_down"])
    with jax.named_scope("moe_experts"):
        y, sizes, dropped = held_experts(
            u, idx, weights, p["w_gate"], p["w_up"], p["w_down"], first)
    return ((shared + y).reshape(b, t, d), sizes.astype(jnp.float32),
            dropped.astype(jnp.float32), swapped)


def _sub_layer(p: dict, x: jax.Array, which: str, s: Xing4Shape, fn):
    """One sub-layer over the streams: the mappings, the read, `fn` of the
    normed read, the write-back. (x', H_res's (gap, off-diagonal mass), what
    `fn` returned beside its output)."""
    # the streams enter a sub-layer as they are stored: without the barrier
    # the chip's compiler fuses the float32 of THIS sub-layer's norm into the
    # write-back that produced the streams and stores both
    x = lax.optimization_barrier(x)
    with jax.named_scope("mhc_map"):
        pre, post, res, read = stream_maps(
            p[which + "_phi"], p[which + "_b"], p[which + "_alpha"], x, s)
        counters = res_counters(res)
    with jax.named_scope("mhc_mix"):
        u, x = streams.read_streams(x, pre, read)
    y, *rest = fn(rms_norm(u, p[which + "_norm"], s.rms_norm_eps))
    with jax.named_scope("mhc_mix"):
        return write_streams(x, res, post, y), counters, rest


def attention_half(p: dict, x: jax.Array, s: Xing4Shape, attn_block: int):
    """A layer's first sub-layer: (x', (gap, offdiag), rms of c_kv)."""
    x, counters, (latent_rms,) = _sub_layer(
        p, x, "attn", s, lambda u: latent_attention(p, u, s, attn_block))
    return x, counters, latent_rms


def mlp_half(p: dict, x: jax.Array, kind: str, s: Xing4Shape, first: int):
    """A layer's second sub-layer: (x', (gap, offdiag), the sparse block's
    (tokens per held expert, dropped, swapped share) or () on a dense
    layer)."""
    if kind == DENSE:
        def fn(u):
            with jax.named_scope("mlp"):
                return (swiglu(u, p["mlp_gate"], p["mlp_up"], p["mlp_down"]),)
    else:
        def fn(u):
            return sparse_block(p, u, s, first)
    x, counters, routing = _sub_layer(p, x, "mlp", s, fn)
    return x, counters, tuple(routing)


def layer_leaves(kind: str, count: int, s: Xing4Shape) -> tuple:
    d, h = s.hidden_size, s.num_heads
    maps = tuple(
        leaf for which in ("attn", "mlp") for leaf in (
            (which + "_phi", (s.hc_mult * d, s.map_width), _phi_init),
            (which + "_b", (s.map_width,), _b_init),
            (which + "_alpha", (3,), _alpha_init),
            (which + "_norm", (d,), True)))
    attn = (
        ("w_dq", (d, s.q_lora_rank), False), ("q_norm", (s.q_lora_rank,), True),
        ("w_uq", (s.q_lora_rank, h * s.score_dim), False),
        ("w_dkv", (d, s.kv_lora_rank + s.qk_rope_head_dim), False),
        ("kv_norm", (s.kv_lora_rank,), True),
        ("w_ukv", (s.kv_lora_rank, h * (s.qk_nope_head_dim + s.v_head_dim)),
         False),
        ("wo", (h * s.v_head_dim, d), False))
    if kind == DENSE:
        f = s.intermediate_size
        return (*maps, *attn, ("mlp_gate", (d, f), False),
                ("mlp_up", (d, f), False), ("mlp_down", (f, d), False))
    f, fs = s.expert_width, s.shared_expert_width
    return (
        *maps, *attn, ("router", (d, s.num_experts), False),
        ("router_bias", (s.num_experts,), _bias_init(s.selection_bias_init)),
        ("shared_gate", (d, fs), False), ("shared_up", (d, fs), False),
        ("shared_down", (fs, d), False),
        ("w_gate", (count, d, f), False), ("w_up", (count, d, f), False),
        ("w_down", (count, f, d), False))


class Xing4LM(nn.Module):
    """Causal LM over integer tokens, task `lm` without carry.

    `model(x)` returns logits (B, T, vocab_size). `model(x, targets=y)`
    returns (per-token loss (B, T) float32, the counters) without ever
    holding the logits of more than `loss_block` tokens: the path the train
    and eval steps take (`ModelMeta.fused_loss`)."""

    vocab_size: int = XING4.vocab_size
    shape: Xing4Shape = XING4
    layers_held: Optional[tuple[int, int]] = None  # (first, count)
    experts_held: tuple[int, int] = (0, XING4.num_experts)
    attn_block: int = 512  # queries a block of the plain blocks
    loss_block: int = 2048
    # the scopes `__call__` enters, here and through lm_parts, each with its
    # layer of PERF.md's map (profiling.classify; Trainer._note_first_dispatch)
    scopes = {
        "mhc_map": STREAMS, "mhc_mix": STREAMS, "mla_q_proj": ATTENTION,
        "mla_kv_proj": ATTENTION, "attn_full": ATTENTION,
        "mla_out_proj": ATTENTION, "mlp": MLP, "moe_route": EXPERTS,
        "moe_shared": EXPERTS, "moe_experts": EXPERTS,
        **SCOPES["token_losses"],
    }
    # what `__call__` puts among the step's metrics, and `step_counters`
    # takes back on the host (Trainer._drain_health)
    health_keys = (
        MOE_TOKENS_KEY, MOE_DROPPED_KEY, MHC_GAP_KEY, MHC_OFFDIAG_KEY,
        MLA_LATENT_KEY, MOE_SWAP_KEY)

    def layer_indices(self) -> tuple[int, ...]:
        """The published indices of the layers held, checked."""
        s = self.shape
        first, count = self.layers_held or (0, s.num_layers)
        if not (0 <= first and count >= 1 and first + count <= s.num_layers):
            raise ValueError(
                f"layers held {first}:{count} are not among the model's "
                f"{s.num_layers}")
        return tuple(range(first, first + count))

    def step_counters(self, stats: dict, *, tokens: int) -> dict:
        """The `step` record's counters from one step's statistics as host
        arrays; `tokens` one device's tokens a (micro-)step. Means over the
        held layers; the routing counts as `Mellum2LM.step_counters` gives
        them, over the SPARSE layers held (none: no such counter)."""
        out = {
            "mhc_res_gap": float(np.mean(stats[MHC_GAP_KEY])),
            "mhc_res_offdiag": float(np.mean(stats[MHC_OFFDIAG_KEY])),
            "mla_kv_latent_rms": float(np.mean(stats[MLA_LATENT_KEY])),
        }
        if MOE_TOKENS_KEY in stats:
            out.update(
                routing_counters(
                    stats, tokens * self.shape.experts_per_token),
                moe_bias_swap_share=float(np.mean(stats[MOE_SWAP_KEY])))
        return out

    @nn.compact
    def __call__(self, x: jax.Array, targets: Optional[jax.Array] = None,
                 train: bool = False):
        s = self.shape
        first, count = self.experts_held
        if not (0 <= first and count >= 1 and first + count <= s.num_experts):
            raise ValueError(
                f"experts held {first}:{count} are not among the model's "
                f"{s.num_experts}")
        d = s.hidden_size
        held = self.layer_indices()
        embed = _Leaves(
            (("embedding", (self.vocab_size, d), False),), name="embed",
        )()["embedding"]
        layers = [
            _Leaves(layer_leaves(s.kind(i), count, s), name=f"layer_{i}")()
            for i in held
        ]
        out = _Leaves(
            (("norm", (d,), True), ("head", (d, self.vocab_size), False)),
            name="out",
        )()
        if self.is_initializing():
            # the declarations above and no forward pass (models/mellum.py)
            return jnp.zeros((*x.shape, self.vocab_size), embed.dtype)

        # equal sub-layers share ONE cached trace under `jax.checkpoint`:
        # what a trace counted (attention cores, grouped products, row
        # permutations) is counted again where it is replayed
        attention = counted(
            jax.checkpoint(attention_half, static_argnums=(2, 3)))
        mlp = counted(jax.checkpoint(mlp_half, static_argnums=(2, 3, 4)))
        # n copies of the embedding (assumed): the streams (n, B, T, C)
        h = jnp.broadcast_to(embed[x], (s.hc_mult, *x.shape, d))
        maps, latents, routing = [], [], []
        for p, index in zip(layers, held):
            h, attn_map, latent_rms = attention(p, h, s, self.attn_block)
            h, mlp_map, layer_routing = mlp(p, h, s.kind(index), s, first)
            maps += [attn_map, mlp_map]
            latents.append(latent_rms)
            if layer_routing:
                routing.append(layer_routing)
        with jax.named_scope("mhc_mix"):  # the sum of the streams (assumed)
            h = jnp.sum(h.astype(jnp.float32), axis=0).astype(h.dtype)
        h = rms_norm(h, out["norm"], s.rms_norm_eps)
        if targets is None:
            with jax.named_scope("lm_head"):
                return jnp.dot(h, out["head"])
        b, t = x.shape
        losses = token_losses(
            h.reshape(b * t, d), out["head"], targets.reshape(b * t),
            self.loss_block)
        gaps, offdiags = zip(*maps)
        stats = {
            MHC_GAP_KEY: jnp.stack(gaps),  # (2 x layers held,)
            MHC_OFFDIAG_KEY: jnp.stack(offdiags),
            MLA_LATENT_KEY: jnp.stack(latents),  # (layers held,)
        }
        if routing:  # the sparse layers held
            tokens, dropped, swapped = zip(*routing)
            stats.update({
                # (sparse layers held, experts held): tokens each took
                MOE_TOKENS_KEY: jnp.stack(tokens),
                MOE_DROPPED_KEY: jnp.sum(jnp.stack(dropped)),
                MOE_SWAP_KEY: jnp.stack(swapped),
            })
        return losses.reshape(b, t), stats
