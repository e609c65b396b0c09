"""Synthetic packed token sequences, resident on the device (the language
model's counterpart of data/synthetic.py + DeviceCifarLoader).

A corpus here is a stream of documents laid end to end and cut every
``seq_len`` tokens, with no padding: a sequence holds the tail of one
document, some whole ones and the head of the next, and a document cut by the
boundary goes on in the next sequence as a document of its own. A batch is

    tokens  [B, 2, T] int32   tokens[:, 0] the ids, tokens[:, 1] the document
                              (segment) id of each token, counted from 0 in
                              every sequence
    targets [B, T]    int32   the next id of the same document; the padding
                              label (data/padding.py) at a document's last
                              token, which has no next

**The layout is the dataset's, not the seed's.** Document lengths are
log-normal, clipped, drawn from ``layout_seed`` alone, so that every seed of
an experiment packs the same documents in the same order: the scan resets its
state at the same tokens, the attention kernel skips the same blocks, the
loss counts the same targets, and two runs that differ in their seed do the
same work. The seed draws the ids (and the weights).

Ids are log-uniform over the vocabulary (p(i) about 1 / i): a unigram skew a
model can learn, so a training loss falls from ln V. ``token_skew="uniform"``
draws every id alike instead: under the skew 7 % of a sequence is one token,
and a router that tells tokens apart by what they are sends all of it to the
same few experts, so that how much work a chip's experts get is the
seed's (models/nemotron_h.py's entry config asks for uniform ids).
"""

from __future__ import annotations

from typing import Iterator

import jax
import jax.numpy as jnp
import numpy as np

from .padding import PAD_LABEL

Batch = tuple[jax.Array, jax.Array]


def document_layout(
    num_sequences: int,
    seq_len: int,
    mu: float,
    sigma: float,
    min_len: int,
    max_len: int,
    layout_seed: int,
) -> np.ndarray:
    """Segment ids [num_sequences, seq_len] of a packed stream of documents
    whose lengths are ``exp(N(mu, sigma))`` clipped to [min_len, max_len]."""
    total = num_sequences * seq_len
    rng = np.random.default_rng(layout_seed)
    lengths: list[int] = []
    while sum(lengths) < total:
        draw = np.exp(rng.normal(mu, sigma, size=64))
        lengths.extend(int(x) for x in np.clip(np.rint(draw), min_len, max_len))
    document = np.repeat(np.arange(len(lengths)), lengths)[:total].reshape(num_sequences, seq_len)
    return (document - document[:, :1]).astype(np.int32)


def token_ids(shape: tuple, vocab_size: int, seed: int, skew: str = "log_uniform") -> np.ndarray:
    u = np.random.default_rng(seed).random(shape)
    if skew == "uniform":
        return np.minimum((u * vocab_size).astype(np.int32), vocab_size - 1)
    return np.minimum(np.exp(u * np.log(vocab_size)).astype(np.int32) - 1, vocab_size - 1)


def next_token_targets(ids: np.ndarray, segment_ids: np.ndarray) -> np.ndarray:
    targets = np.full(ids.shape, PAD_LABEL, np.int32)
    same = segment_ids[:, 1:] == segment_ids[:, :-1]
    targets[:, :-1] = np.where(same, ids[:, 1:], PAD_LABEL)
    return targets


class PackedTokenLoader:
    """Epoch iterator over device-resident packed sequences. Train: the
    sequences in an order drawn from (seed, epoch), whole batches only. Eval:
    in order, the last batch filled with sequences that have no target.
    The epoch counter is the loader's whole state, as DeviceCifarLoader's."""

    batch_scope = "global"

    def __init__(self, ids: np.ndarray, segment_ids: np.ndarray, batch_size: int, train: bool, seed: int = 0):
        self.batch_size, self.train = batch_size, train
        self.tokens = jax.device_put(jnp.asarray(np.stack([ids, segment_ids], axis=1), jnp.int32))
        self.targets = jax.device_put(jnp.asarray(next_token_targets(ids, segment_ids)))
        self.epoch = 0
        self._key = jax.random.PRNGKey(seed)

    def __len__(self) -> int:
        n = self.tokens.shape[0]
        return n // self.batch_size if self.train else -(-n // self.batch_size)

    @property
    def num_samples(self) -> int:
        return int(self.tokens.shape[0])

    def _stacked(self, tokens, targets) -> Batch:
        s, b = len(self), self.batch_size
        return (
            tokens[: s * b].reshape((s, b) + tokens.shape[1:]),
            targets[: s * b].reshape((s, b) + targets.shape[1:]),
        )

    def epoch_arrays(self) -> Batch:
        """One epoch stacked on a step axis: tokens [S, B, 2, T], targets
        [S, B, T] (train/steps.py make_scan_chunk). Advances the epoch."""
        if not self.train:
            raise ValueError("epoch_arrays is for the train loader")
        epoch, self.epoch = self.epoch, self.epoch + 1
        order = jax.random.permutation(jax.random.fold_in(self._key, epoch), self.num_samples)
        return self._stacked(jnp.take(self.tokens, order, axis=0), jnp.take(self.targets, order, axis=0))

    def eval_epoch_arrays(self) -> Batch:
        """The eval set stacked on a step axis (make_scan_eval); the harness
        keeps the one resident copy."""
        if self.train:
            raise ValueError("eval_epoch_arrays is for the eval loader")
        pad = len(self) * self.batch_size - self.num_samples
        tokens = jnp.pad(self.tokens, ((0, pad), (0, 0), (0, 0)))
        targets = jnp.pad(self.targets, ((0, pad), (0, 0)), constant_values=PAD_LABEL)
        return self._stacked(tokens, targets)

    def __iter__(self) -> Iterator[Batch]:
        tokens, targets = self.epoch_arrays() if self.train else self.eval_epoch_arrays()
        for step in range(len(self)):
            yield tokens[step], targets[step]


class SyntheticTokenLoaders:
    """Train / test pair over one layout (the test sequences follow the
    train sequences in the stream); ids from the seed."""

    def __init__(
        self,
        vocab_size: int,
        seq_len: int,
        batch_size: int,
        num_train: int,
        num_test: int,
        doc_len_mu: float,
        doc_len_sigma: float,
        doc_len_min: int,
        layout_seed: int,
        seed: int = 0,
        token_skew: str = "log_uniform",
    ):
        self.num_classes = vocab_size
        segment_ids = document_layout(
            num_train + num_test, seq_len, doc_len_mu, doc_len_sigma, doc_len_min, seq_len, layout_seed
        )
        ids = token_ids(segment_ids.shape, vocab_size, seed, token_skew)
        self.train_loader = PackedTokenLoader(
            ids[:num_train], segment_ids[:num_train], batch_size, train=True, seed=seed
        )
        self.test_loader = PackedTokenLoader(
            ids[num_train:], segment_ids[num_train:], batch_size, train=False, seed=seed + 1
        )
        # What a step holds, the same for every seed (mean over the steps of
        # an epoch; the sequences differ among themselves).
        steps = len(self.train_loader)
        used = segment_ids[: steps * batch_size]
        targets = next_token_targets(ids[: steps * batch_size], used)
        self.gauges = {
            "tokens_per_step": float(batch_size * seq_len),
            "target_tokens_per_step": float((targets != PAD_LABEL).sum()) / steps,
            "docs_per_sequence": float((used.max(axis=1) + 1).mean()),
        }
