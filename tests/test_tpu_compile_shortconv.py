"""Ask the TPU's compiler about ops/shortconv.py's kernels, without a chip.

As the other tests/test_tpu_compile*.py (`topo` and `_compile_cache_off` are
tests/conftest.py's): libtpu compiles for a described v5e, nothing runs, and
a compile that passes says nothing about results or speed. One file a kind of
program, so that under `--dist loadfile` no one worker carries them all.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from mgwfbp_tpu.ops import shortconv

pytestmark = pytest.mark.usefixtures("_compile_cache_off")

ROWS = shortconv._ROWS


@pytest.mark.parametrize("b,t,c,k,dtype,bias", [
    # three older cells: Qwen3-Next's q | k | v, Granite 4.0-H's xBC,
    # Phi-4-mini-flash's xs
    (2, 8192, 8192, 4, jnp.bfloat16, False),
    (1, 8192, 4352, 4, jnp.bfloat16, True),
    (1, 8192, 5120, 4, jnp.bfloat16, True),
    # Nemotron 3 Super's xBC at a tensor share: 16 heads of 64 and one group
    (1, 8192, 1280, 4, jnp.bfloat16, True),
    # the corners the rule admits: float32; one lane tile and one block of
    # positions; one tap and a sublane tile of taps; strips in uneven groups
    (2, 2 * ROWS, 2688, 4, jnp.float32, True),
    (1, ROWS, 128, 1, jnp.bfloat16, False),
    (2, ROWS, 128, 8, jnp.float32, False),
    (2, 2 * ROWS, 384, 2, jnp.bfloat16, True),
])
def test_the_convolutions_kernels_compile_for_a_v5e_and_keep_float32_off_hbm(
        topo, b, t, c, k, dtype, bias):
    """The convolution as one layer has it: under `jax.checkpoint`, its value
    and its pull-back, down the kernels outright (this process traces for
    the CPU). They compile for the chip as TWO kernel programs (the forward,
    its recomputation where the compiler keeps it, the backward), and no
    float32 array of x's size exists in the compiled text where x is
    bfloat16: the padded float32 copy and the saved pre-activation of the
    plain form are gone, and d w and d bias leave the kernel summed."""
    one = SingleDeviceSharding(topo.devices[0])

    def arg(shape, of):
        return jax.ShapeDtypeStruct(shape, of, sharding=one)

    tiles = shortconv._kernel_tiles(t, c, k, jnp.dtype(dtype))
    assert tiles is not None
    x, w = arg((b, t, c), dtype), arg((k, c), jnp.float32)
    bias = arg((c,), jnp.float32) if bias else None

    def layer(x, w, bias, dy):
        y, pull = jax.vjp(jax.checkpoint(
            lambda *v: shortconv._kernel_conv(*v, tiles, False)), x, w, bias)
        return y, pull(dy)

    compiled = jax.jit(layer).lower(x, w, bias, x).compile()
    text = compiled.as_text()
    kernels = set(re.findall(r"causal_conv_silu_(?:forward|backward)", text))
    assert kernels == {
        "causal_conv_silu_forward", "causal_conv_silu_backward"}
    assert 2 <= text.count("tpu_custom_call") <= 3
    if dtype == jnp.bfloat16:
        assert f"f32[{b},{t},{c}]" not in text
        assert f"f32[{b},{t + k - 1},{c}]" not in text
    # the saved pre-activation and the float32 copies gone: what is alive
    # beside the arguments and the results is under a tenth of one x
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 0.1 * b * t * c * jnp.dtype(dtype).itemsize + 2 ** 20
