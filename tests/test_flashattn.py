"""Pallas flash-attention kernel vs the dense jnp reference
(ringattn.local_attention). On CPU the kernel runs in interpreter mode, so
the real kernel logic (block loop, online softmax, causal block skipping)
is exercised without a TPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mgwfbp_tpu.ops import flash_attention, flash_supported
from mgwfbp_tpu.parallel.ringattn import local_attention


def _qkv(b=2, t=64, h=2, d=16, seed=0, dtype=jnp.float32):
    rs = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rs.randn(b, t, h, d), dtype)
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_dense(causal):
    q, k, v = _qkv()
    got = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
    want = local_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


def test_flash_multiblock_causal_skips_future():
    # T=64 with 16-blocks: 4 q-blocks x 4 k-blocks; causal skipping must
    # not change numerics vs the dense mask
    q, k, v = _qkv(b=1, t=64, h=1, d=8, seed=3)
    got = flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
    want = local_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5
    )


def test_flash_bf16_inputs():
    q, k, v = _qkv(dtype=jnp.bfloat16, seed=5)
    got = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    assert got.dtype == jnp.bfloat16
    want = local_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=2e-2, atol=2e-2,
    )


def test_flash_supported_guard():
    assert flash_supported(128, 64)
    assert not flash_supported(100, 64, 16, 16) or 100 % 16 == 0
    assert not flash_supported(64, 512)
    with pytest.raises(ValueError):
        q, k, v = _qkv(t=24, d=300)
        flash_attention(q, k, v)


def test_interpret_choice_is_explicit_per_platform(monkeypatch):
    """Compiled on `tpu`, interpreted on `cpu`, refused anywhere else: a
    renamed or unknown platform can never quietly demote the kernel to the
    interpreter again."""
    import types

    from mgwfbp_tpu.ops import flashattn

    def on(platform):
        monkeypatch.setattr(
            flashattn.jax, "devices",
            lambda: [types.SimpleNamespace(platform=platform)],
        )
        return flashattn._default_interpret()

    assert on("tpu") is False
    assert on("cpu") is True
    with pytest.raises(RuntimeError, match="neither 'tpu'"):
        on("some-plugin")
