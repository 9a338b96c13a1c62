"""Ask the TPU's compiler about ops/streams.py's kernels, without a chip.

As the other tests/test_tpu_compile*.py (`topo` and `_compile_cache_off` are
tests/conftest.py's): libtpu compiles for a described v5e, nothing runs, and
a compile that passes says nothing about results or speed. One file a kind of
program, so that under `--dist loadfile` no one worker carries them all.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from mgwfbp_tpu.models import xing4
from mgwfbp_tpu.ops import programs, streams

pytestmark = pytest.mark.usefixtures("_compile_cache_off")

KERNELS = {"streams_map_read", "streams_map_read_pull", "streams_write",
           "streams_write_pull"}


@pytest.mark.parametrize("n,b,t,c,dtype,rows", [
    # the cell: four streams of 3,584 over one sequence of 8,192
    (4, 1, 8192, 3584, jnp.bfloat16, 256),
    # the corners the rule admits: float32 (d a r whole, no second term);
    # two streams, one lane tile and the smallest block; eight streams (m 80:
    # the second bfloat16 term in a lane tile of its own); float32 at a block
    # of 128
    (4, 2, 512, 384, jnp.float32, 256),
    (2, 1, 128, 128, jnp.bfloat16, 128),
    (8, 1, 256, 128, jnp.bfloat16, 256),
    (2, 2, 384, 256, jnp.float32, 128),
])
def test_two_sub_layers_compile_for_a_v5e_as_four_programs(
        topo, monkeypatch, n, b, t, c, dtype, rows):
    """Two sub-layers in a row as models/xing4.py has them (`_sub_layer`
    under a `jax.checkpoint`, the mixer one product), value and pull-back,
    traced as for a TPU (said so by the test: this process's default backend
    is the CPU). They compile for the chip as FOUR kernel programs (the
    mapping with its read, its recomputation, the write-back and the two
    pull-backs of each sub-layer), and no float32 array of the streams' size
    exists in the compiled text: the copies the plain form's fusions store
    between them are gone."""
    monkeypatch.setattr(programs, "traced_for_tpu", lambda: True)
    one = SingleDeviceSharding(topo.devices[0])
    s = dataclasses.replace(xing4.XING4_TINY, hc_mult=n, hidden_size=c)
    dtype = jnp.dtype(dtype)

    def arg(*shape, of=dtype):
        return jax.ShapeDtypeStruct(shape, of, sharding=one)

    x = arg(n, b, t, c)
    assert streams._kernel_rows(x) == rows
    p = {"attn_phi": arg(n * c, s.map_width), "attn_b": arg(s.map_width),
         "attn_alpha": arg(3), "attn_norm": arg(c), "w": arg(c, c)}

    @jax.checkpoint
    def sub_layer(p, x):
        return xing4._sub_layer(p, x, "attn", s, lambda u: (u @ p["w"],))[0]

    def two(p, x, g):
        out, pull = jax.vjp(lambda p, x: sub_layer(p, sub_layer(p, x)), p, x)
        return out, pull(g)

    before = programs.LOWERED.copy()
    compiled = jax.jit(two).lower(p, x, x).compile()
    assert programs.lowered_since(before)["streams"] == {
        "kernel": 2, "plain": 0, "programs": 4}  # the second: a cached trace
    text = compiled.as_text()
    assert set(re.findall(r"streams_[a-z_]+", text)) == KERNELS
    # a sub-layer: both forward kernels, the mapping's again under the
    # checkpoint (the write-back's output is not needed there), two pulls
    assert text.count("tpu_custom_call") == 10
    if dtype == jnp.bfloat16:
        assert f"f32[{n},{b},{t},{c}]" not in text
