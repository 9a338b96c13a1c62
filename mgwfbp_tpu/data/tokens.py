"""Token sequences of a stated length over a stated vocabulary.

The `tokens` dataset of `data_prepare`: `<data_dir>/tokens/{train,valid}.npy`
each hold ONE 1-D integer array of token ids (a tokenized corpus, documents
concatenated); where they are absent a seeded synthetic stream over the
vocabulary is served. A stream is cut into non-overlapping pieces of
`seq_len + 1` ids; of a piece s the model reads `x = s[:-1]` and predicts
`y = s[1:]`. Every sequence has the full length: no padding, and no document
mask (attention runs across document boundaries, as in plain concatenated
pre-training).

The vocabulary is what the caller states (`--vocab-size`), else the real
stream's largest id plus one, else the model's published size. A chip that
holds a slice of the vocabulary trains on ids of that slice: a sliced
vocabulary is a smaller vocabulary.
"""

from __future__ import annotations

import os
from typing import Optional

import numpy as np

from mgwfbp_tpu.data.loader import ArrayDataset

SEQ_LEN = 8192
_STRIDE = 31  # the synthetic stream's next id is the last plus this ...
_NOISE = 0.15  # ... except where a uniform draw replaces it


def load_token_stream(data_dir: str, split: str) -> Optional[np.ndarray]:
    """The split's id stream, or None where its file is absent."""
    path = os.path.join(data_dir, "tokens", f"{split}.npy")
    if not os.path.exists(path):
        return None
    stream = np.load(path, mmap_mode="r")
    if stream.ndim != 1 or not np.issubdtype(stream.dtype, np.integer):
        raise ValueError(
            f"{path}: expected one 1-D integer array of token ids, found "
            f"shape {stream.shape} dtype {stream.dtype}")
    return stream


def synthetic_token_stream(
    n_sequences: int, seq_len: int, vocab_size: int, seed: int = 0,
) -> np.ndarray:
    """Deterministic pseudo-corpus with local structure (each id follows its
    predecessor by a fixed stride, 15% of the positions drawn uniformly), so
    the loss can fall during smoke runs. Built in bulk: no per-token loop."""
    rng = np.random.default_rng(seed)
    total = n_sequences * (seq_len + 1)
    pieces = np.arange(total, dtype=np.int64).reshape(n_sequences, seq_len + 1)
    starts = rng.integers(0, vocab_size, size=(n_sequences, 1))
    stream = (starts + _STRIDE * (pieces - pieces[:, :1])) % vocab_size
    noise = rng.integers(0, vocab_size, size=stream.shape)
    stream = np.where(rng.random(stream.shape) < _NOISE, noise, stream)
    return stream.reshape(total).astype(np.int32)


def sequence_dataset(
    stream: np.ndarray, seq_len: int, vocab_size: int,
) -> ArrayDataset:
    """Non-overlapping (x, y) = (s[:-1], s[1:]) pieces of seq_len + 1 ids."""
    n = len(stream) // (seq_len + 1)
    if n == 0:
        raise ValueError(
            f"stream of {len(stream)} ids holds no sequence of {seq_len} + 1")
    pieces = np.asarray(
        stream[: n * (seq_len + 1)], dtype=np.int32).reshape(n, seq_len + 1)
    if pieces.min() < 0 or pieces.max() >= vocab_size:
        raise ValueError(
            f"token ids span [{pieces.min()}, {pieces.max()}], outside the "
            f"vocabulary of {vocab_size}")
    return ArrayDataset(
        data=np.ascontiguousarray(pieces[:, :-1]),
        labels=np.ascontiguousarray(pieces[:, 1:]),
        num_classes=vocab_size,
    )
