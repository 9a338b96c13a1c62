"""The `tokens` dataset (data/tokens.py): sequences of a stated length over a
stated vocabulary, from `<data_dir>/tokens/{train,valid}.npy` or a seeded
synthetic stream, served like images are (ShardedLoader, PrefetchLoader)."""

import numpy as np
import pytest

from mgwfbp_tpu.data import data_prepare
from mgwfbp_tpu.data.sharding import ShardInfo
from mgwfbp_tpu.data.tokens import sequence_dataset, synthetic_token_stream


def test_pieces_of_length_plus_one_x_and_y_shifted_by_one():
    stream = np.arange(100, dtype=np.int64) % 50
    ds = sequence_dataset(stream, 9, 50)
    assert ds.data.shape == ds.labels.shape == (10, 9)  # 100 // (9 + 1)
    assert ds.data.dtype == np.int32 and ds.num_classes == 50
    np.testing.assert_array_equal(ds.data[3], stream[30:39])
    np.testing.assert_array_equal(ds.labels[3], stream[31:40])
    with pytest.raises(ValueError, match="outside the vocabulary of 40"):
        sequence_dataset(stream, 9, 40)
    with pytest.raises(ValueError, match="holds no sequence"):
        sequence_dataset(stream[:5], 9, 50)


@pytest.mark.parametrize("seed", [0, 3000000001])
def test_synthetic_stream_is_seeded_and_within_the_vocabulary(seed):
    a = synthetic_token_stream(6, 64, 300, seed)
    b = synthetic_token_stream(6, 64, 300, seed)
    c = synthetic_token_stream(6, 64, 300, seed + 1)
    np.testing.assert_array_equal(a, b)
    assert (a != c).any() and a.shape == (6 * 65,) and a.dtype == np.int32
    assert a.min() >= 0 and a.max() < 300
    # local structure: most ids follow their predecessor by the fixed stride
    pieces = a.reshape(6, 65)
    follows = ((pieces[:, 1:] - pieces[:, :-1]) % 300 == 31).mean()
    assert 0.6 < follows < 0.85


def test_synthetic_bundle_counts_sequences_and_names_batches(monkeypatch):
    monkeypatch.setenv("MGWFBP_SYNTH_TRAIN_N", "12")
    monkeypatch.setenv("MGWFBP_SYNTH_VAL_N", "3")
    bundle = data_prepare(
        "tokens", batch_size=4, seed=7, synthetic=True, num_steps=32,
        vocab_size=500)
    assert bundle.synthetic and bundle.num_classes == 500
    assert len(bundle.train) == bundle.num_batches_per_epoch == 3
    assert len(bundle.val.dataset) == 3
    inner = bundle.train.inner  # the PrefetchLoader's ShardedLoader
    bundle.train.set_epoch(0)
    first = next(iter(bundle.train))
    named = inner.load_batch(0, 0)
    for got, want in zip(first, named):
        np.testing.assert_array_equal(got, want)
    x, y = named
    assert x.shape == y.shape == (4, 32) and x.dtype == np.int32
    np.testing.assert_array_equal(x[:, 1:], y[:, :-1])
    # two ranks see disjoint sequences of the same epoch
    halves = [
        data_prepare("tokens", batch_size=2, seed=7, synthetic=True,
                     num_steps=32, vocab_size=500,
                     shard=ShardInfo(rank, 2)).train.inner.load_batch(0, 0)[0]
        for rank in (0, 1)]
    assert not (halves[0][:, None] == halves[1][None]).all(axis=-1).any()


def test_real_files_are_read_and_checked(tmp_path):
    (tmp_path / "tokens").mkdir()
    rng = np.random.default_rng(0)
    np.save(tmp_path / "tokens" / "train.npy", rng.integers(0, 90, 1000))
    np.save(tmp_path / "tokens" / "valid.npy", rng.integers(0, 90, 200))
    bundle = data_prepare(
        "tokens", data_dir=str(tmp_path), batch_size=2, num_steps=16)
    assert not bundle.synthetic
    assert bundle.num_classes == 90  # the largest id plus one
    assert len(bundle.train.dataset) == 1000 // 17
    stated = data_prepare(
        "tokens", data_dir=str(tmp_path), batch_size=2, num_steps=16,
        vocab_size=128)
    assert stated.num_classes == 128
    with pytest.raises(ValueError, match="outside the vocabulary of 64"):
        data_prepare("tokens", data_dir=str(tmp_path), batch_size=2,
                     num_steps=16, vocab_size=64)
    np.save(tmp_path / "tokens" / "train.npy", np.zeros((10, 10), np.int32))
    with pytest.raises(ValueError, match="one 1-D integer array"):
        data_prepare("tokens", data_dir=str(tmp_path), batch_size=2,
                     num_steps=16)
    with pytest.raises(FileNotFoundError):
        data_prepare("tokens", data_dir=str(tmp_path / "none"), batch_size=2,
                     num_steps=16, synthetic=False)


def test_adamw_chain_and_its_spec_come_from_the_same_numbers():
    import jax
    import jax.numpy as jnp
    import optax

    from mgwfbp_tpu.optim import make_optimizer

    kw = dict(weight_decay=0.1, lr_schedule="const", num_batches_per_epoch=4,
              norm_clip=1.0, return_spec=True)
    tx, _, spec = make_optimizer(
        1e-2, optimizer="adamw", b1=0.9, b2=0.95, eps=1e-8, **kw)
    assert (spec.kind, spec.b2, spec.decoupled_wd, spec.weight_decay) == (
        "adam", 0.95, True, 0.1)
    params = {"w": jnp.ones((3, 3)), "g": jnp.ones((3,))}
    grads = {"w": jnp.full((3, 3), 0.5), "g": jnp.full((3,), 0.5)}
    twin = spec.make_tx()
    got, _ = tx.update(grads, tx.init(params), params)
    want, _ = twin.update(grads, twin.init(params), params)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # decoupled decay on the matrix, none on the vector
    plain = optax.chain(optax.clip_by_global_norm(1.0), optax.adam(
        1e-2, b1=0.9, b2=0.95, eps=1e-8))
    base, _ = plain.update(grads, plain.init(params), params)
    np.testing.assert_allclose(got["g"], base["g"], rtol=1e-6)
    np.testing.assert_allclose(got["w"], base["w"] - 1e-2 * 0.1, rtol=1e-5)
    _, _, sgd_spec = make_optimizer(1e-2, **kw)
    assert sgd_spec.kind == "sgd"
    with pytest.raises(ValueError, match="unknown optimizer"):
        make_optimizer(1e-2, optimizer="lamb")
