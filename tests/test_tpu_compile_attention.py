"""Ask the TPU's compiler, without a chip: the attention cores of the language cells.

libtpu is installed here and compiles for a chip that is described, not
attached (`topologies.get_topology_desc`, topology v5e:2x2), so what the
chip's compiler would refuse (a Pallas block the tiling rejects, a program
that does not fit 16 GB of HBM) fails in tier-1 at no chip time. Nothing
runs: a compile that passes says nothing about results or speed, and is never
reported as a chip run. `topo` (skipped where the topology cannot be
described) and `_compile_cache_off` are tests/conftest.py's; the other files
of this kind are tests/test_tpu_compile*.py, one a kind of program so that no
one worker carries them all.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

pytestmark = pytest.mark.usefixtures("_compile_cache_off")


@pytest.mark.parametrize("b,h,hkv,d,window,scale", [
    (1, 32, 32, (192, 128), None, 1.0),
    (2, 32, 4, 128, None, 1.0),
    (2, 32, 4, 128, 1024, 1.0),
    (1, 32, 8, 64, None, 1.0 / 64),
    (1, 64, 8, 128, 512, 1.0),
    (1, 48, 8, 128, None, 1.0),
    (1, 80, 40, 64, None, 0.125),
    (1, 80, 40, 64, 512, 0.125),
    (2, 16, 2, 256, None, 1.0),
    (1, 4, 1, 128, None, 128 ** -0.5),
], ids=["xing4-latent-scores-192-values-128",
        "mellum2-full", "mellum2-window-1024", "granite4h",
        "laguna-xs2-window-512-64-heads", "laguna-xs2-full-48-heads-groups-of-6",
        "phi4flash-full-80-stacked-heads", "phi4flash-window-512-80-stacked-heads",
        "qwen3next-full-head-256-groups-of-8",
        "nemotron3s-a-tensor-share-4-heads-over-1"])
def test_attention_core_compiles_for_v5e_at_the_cells_shapes(
        topo, b, h, hkv, d, window, scale):
    """ops/blockattn.py's fused kernel, forward and backward, at the ten
    call shapes of the language cells (T 8,192, bf16; the newest scores over
    192 and sums values of 128) and the tiles the shape test gives them: the
    tiles fit VMEM and the backward compiles. The kernel path is called
    outright: this process traces for the CPU."""
    from mgwfbp_tpu.ops import blockattn

    t = 8192
    d, dv = d if isinstance(d, tuple) else (d, d)
    one = SingleDeviceSharding(topo.devices[0])
    q = jax.ShapeDtypeStruct((b, t, h, d), jnp.bfloat16, sharding=one)
    kv = jax.ShapeDtypeStruct((b, t, hkv, d), jnp.bfloat16, sharding=one)
    v = jax.ShapeDtypeStruct((b, t, hkv, dv), jnp.bfloat16, sharding=one)
    tiles = blockattn._kernel_tiles(t, d, window, dv)
    assert tiles is not None

    def loss(q, k, v):
        out = blockattn._fused(q, k, v, window, scale, tiles)
        return jnp.sum(out.astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, v).compile().as_text()
    # forward, dk/dv and dq (or the two of a fused backward)
    assert text.count("tpu_custom_call") >= 2
    # no float32 array of a whole head's scores leaves the kernel
    assert f"f32[{t},{t}]" not in text and f",{t},{t}]" not in text
