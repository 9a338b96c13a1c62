"""The plain references: their FLOP functions against the published counts,
and each against the zoo model at a tiny size on the CPU in float32."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.traverse_util import flatten_dict

from bench_paths import load


def _flat(params):
    return {"/".join(k): np.asarray(v) for k, v in flatten_dict(params).items()}


@pytest.mark.parametrize("module, gmacs", [
    ("references/resnet50_v15.py", 4.09), ("references/vgg16_d.py", 15.47)])
def test_forward_macs_match_the_published_counts(module, gmacs):
    macs = load(module).forward_macs((224, 224), 1000)
    assert abs(macs / 1e9 - gmacs) < 0.005, macs


def test_resnet50_reference_matches_the_zoo_model():
    from mgwfbp_tpu.models.resnet_imagenet import imagenet_resnet
    from mgwfbp_tpu.train.step import cross_entropy

    ref = load("references/resnet50_v15.py")
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 64, 64, 3))
    y = jnp.arange(8, dtype=jnp.int32) % 10
    model = imagenet_resnet(50, 10)
    variables = model.init({"params": jax.random.PRNGKey(0)}, x, train=False)
    flat = _flat(variables["params"])
    out, _ = model.apply(variables, x, train=True, mutable=["batch_stats"])
    # float32 on the CPU: the two differ in summation order and in how the
    # variance is taken (E[x^2] - E[x]^2 against the two-pass form)
    np.testing.assert_allclose(ref.logits(flat, x), out, atol=5e-3)
    want = float(cross_entropy(out, y))
    step = ref.first_step(flat, x, y, seed=0, shards=1)
    assert abs(step["loss"] - want) < 1e-4 * want

    def zoo_loss(params):
        out, _ = model.apply({**variables, "params": params}, x, train=True,
                             mutable=["batch_stats"])
        return cross_entropy(out, y)

    grads = jax.grad(zoo_loss)(variables["params"])
    norm = float(np.sqrt(sum(np.sum(np.square(g)) for g in _flat(grads).values())))
    assert abs(step["grad_norm"] - norm) < 2e-3 * norm
    # two devices: each normalizes its own rows
    halves = [
        model.apply(variables, x[i:i + 4], train=True, mutable=["batch_stats"])[0]
        for i in (0, 4)]
    want2 = float(np.mean([
        cross_entropy(h, y[i:i + 4]) for h, i in zip(halves, (0, 4))]))
    assert abs(ref.first_step(flat, x, y, seed=0, shards=2)["loss"] - want2) < 1e-4 * want2
    assert abs(want2 - want) > 1e-3 * want  # the split is visible in the loss


@pytest.mark.parametrize("shards", [1, 2])
def test_vgg16_reference_matches_the_zoo_model_with_its_dropout(shards):
    """The zoo model under the keys `train/step.py` derives for each device:
    the reference re-derives them from the seed alone."""
    from mgwfbp_tpu.models.vgg import VGGImageNet
    from mgwfbp_tpu.train.step import cross_entropy

    ref = load("references/vgg16_d.py")
    seed = 5
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 32, 32, 3))
    y = jnp.arange(8, dtype=jnp.int32) % 10
    model = VGGImageNet("vgg16", 10)
    init_rng, state_rng = jax.random.split(jax.random.PRNGKey(seed))
    variables = model.init({"params": init_rng}, x, train=False)
    rows = 8 // shards
    def zoo_loss(params):
        losses = []
        for i in range(shards):
            rng = state_rng
            for fold in (0, i, 0):  # step, device, micro-step
                rng = jax.random.fold_in(rng, fold)
            out = model.apply({"params": params}, x[i * rows:(i + 1) * rows],
                              train=True, rngs={"dropout": rng})
            losses.append(cross_entropy(out, y[i * rows:(i + 1) * rows]))
        return jnp.mean(jnp.stack(losses))

    want, grads = jax.value_and_grad(zoo_loss)(variables["params"])
    want = float(want)
    norm = float(np.sqrt(sum(np.sum(np.square(g)) for g in _flat(grads).values())))
    step = ref.first_step(
        _flat(variables["params"]), x, y, seed=seed, shards=shards)
    got = step["loss"]
    assert abs(step["grad_norm"] - norm) < 1e-4 * norm
    fp8 = ref.first_step(_flat(variables["params"]), x, y, seed=seed,
                         shards=shards, dtype="float8_e4m3fn")
    assert abs(fp8["grad_norm"] - norm) > 1e-2 * norm  # the control shows
    assert abs(got - want) < 1e-5 * want
    plain = float(cross_entropy(model.apply(variables, x, train=False), y))
    assert abs(plain - want) > 1e-4 * want  # the masks are visible in the loss
