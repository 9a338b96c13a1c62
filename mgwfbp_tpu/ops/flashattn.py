"""Flash attention as a Pallas TPU kernel.

The transformer's attention (models/transformer.py) is the framework's one
O(T^2) hot op; XLA materializes the (T, T) score matrix in HBM, while this
kernel streams K/V blocks through VMEM with the standard online-softmax
recurrence — scores never leave on-chip memory, HBM traffic drops from
O(T^2) to O(T * D), and the MXU sees back-to-back (block_q x D) @
(D x block_k) matmuls.

Grid: one program per (batch*head, q-block); each program loops over K/V
blocks with running (m, l, acc) carried as values. Compute is float32
regardless of input dtype (bf16 inputs upcast per block — same policy as
parallel/ringattn.py). Causal masking is by global position, so for causal
attention blocks strictly above the diagonal are skipped entirely.

`flash_attention` is numerically equivalent to `ringattn.local_attention`
(same online-softmax math); tests pin them against each other. On CPU the
kernel runs in interpreter mode (slow but exact), so the suite exercises
the real kernel logic without a TPU. On a TPU it is always compiled; any
other platform is an error, never a quiet demotion to the interpreter.

FORWARD-ONLY on the chip: the kernel has no `custom_vjp`, and Pallas
cannot differentiate the compiled kernel (`jax.grad` through it fails with
an AssertionError in Pallas AD). Only interpret mode differentiates, so a
model built with it trains on the CPU and cannot train on a TPU yet.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def flash_supported(t: int, d: int, block_q: int = 128, block_k: int = 128) -> bool:
    """Shapes the kernel handles: sequence divisible into whole blocks and
    a head dim that fits a lane tile."""
    bq = min(block_q, t)
    bk = min(block_k, t)
    return t % bq == 0 and t % bk == 0 and d <= 256


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
    *, scale, causal, block_q, block_k,
):
    """One (bh, q-block, k-block) grid step. K is the INNERMOST grid dim so
    Pallas double-buffers the K/V block DMAs against compute; the running
    (acc, m, l) live in VMEM scratch across the k sweep of one q-block."""
    qi = pl.program_id(1)
    j = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # causal: a K block strictly above the diagonal contributes nothing
    live = (j * block_k <= qi * block_q + block_q - 1) if causal else True

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale  # (bq, d)
        k_blk = k_ref[0].astype(jnp.float32)  # (bk, d)
        v_blk = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (bq, bk)
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            k_pos = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            mask = k_pos <= q_pos
            s = jnp.where(mask, s, _NEG_INF)
        m_prev = m_ref[:, :1]  # (bq, 1)
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        if causal:
            p = jnp.where(mask, p, 0.0)
        a_old = jnp.exp(m_prev - m_new)  # (bq, 1)
        l_new = l_prev * a_old + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (bq, d)
        acc_ref[:] = acc_ref[:] * a_old + pv
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == nk - 1)
    def _finalize():
        out = acc_ref[:] / jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "scale", "block_q", "block_k",
                              "interpret")
)
def _flash_bhtd(q, k, v, causal, scale, block_q, block_k, interpret):
    """(BH, T, D) flash attention via pallas_call."""
    bh, t, d = q.shape
    bq = min(block_q, t)
    bk = min(block_k, t)
    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, block_q=bq, block_k=bk
    )
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((bh, t, d), q.dtype),
        grid=(bh, t // bq, t // bk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda i, qi, j: (i, qi, 0)),
            pl.BlockSpec((1, bk, d), lambda i, qi, j: (i, j, 0)),
            pl.BlockSpec((1, bk, d), lambda i, qi, j: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda i, qi, j: (i, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)


def _default_interpret() -> bool:
    """Compiled on a TPU, interpreted on the CPU, refused anywhere else: a
    platform-name test must never pick the interpreter on an accelerator."""
    platform = jax.devices()[0].platform
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(
        f"flash_attention: platform {platform!r} is neither 'tpu' (compiled "
        "kernel) nor 'cpu' (interpreter); pass interpret= explicitly"
    )


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Blockwise-softmax attention over (B, T, H, D) tensors.

    Drop-in equivalent of `ringattn.local_attention`; raises ValueError for
    unsupported shapes (callers guard with `flash_supported`). `interpret`
    left at None means compiled on a TPU and interpreted on the CPU
    (`_default_interpret`). Forward-only when compiled (module docstring).

    Sharding contract: operates on LOCAL (per-device) arrays. Inside the
    framework's train step this holds by construction (the whole model runs
    under shard_map, so the kernel sees each device's shard). Do NOT call
    it under a bare `jit` with GSPMD-sharded inputs — pallas_call carries
    no partitioning rule, so XLA would gather the global batch to every
    device and replicate the compute.
    """
    b, t, h, d = q.shape
    if not flash_supported(t, d, block_q, block_k):
        raise ValueError(
            f"flash_attention: unsupported shape T={t}, D={d} for blocks "
            f"({block_q}, {block_k})"
        )
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    if interpret is None:
        interpret = _default_interpret()

    def to_bhtd(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)

    out = _flash_bhtd(
        to_bhtd(q), to_bhtd(k), to_bhtd(v), causal, float(scale),
        int(block_q), int(block_k), bool(interpret),
    )
    return out.reshape(b, h, t, d).transpose(0, 2, 1, 3)
