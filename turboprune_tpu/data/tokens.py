"""Synthetic packed token sequences, resident on the device (the language
model's counterpart of data/synthetic.py + DeviceCifarLoader).

A corpus here is a stream of documents laid end to end and cut every
``seq_len`` tokens, with no padding: a sequence holds the tail of one
document, some whole ones and the head of the next, and a document cut by the
boundary goes on in the next sequence as a document of its own. There are
two kinds of batch. For a model trained on next tokens:

    tokens  [B, 2, T] int32   tokens[:, 0] the ids, tokens[:, 1] the document
                              (segment) id of each token, counted from 0 in
                              every sequence
    targets [B, T]    int32   the next id of the same document; the padding
                              label (data/padding.py) at a document's last
                              token, which has no next

For a model trained by diffusion over blocks (``block_length`` > 0; Arriola et
al., arXiv:2503.09573), the counterpart of data/augment.py's ``augment_epoch``:
each epoch every sequence is noised on the device (``noise_epoch``, one
compiled program an epoch, span ``epoch/noise``). A document is cut into
blocks of ``block_length`` tokens from its first (its last may be short), a
level ``t ~ U(T_MIN, 1)`` is drawn a block, and each token of the block
becomes the mask id with probability ``t``:

    tokens  [B, 5, T] int32   ``CLEAN`` the ids, ``DOC`` the document id,
                              ``BLK`` the block's ordinal inside the document,
                              ``POS`` the token's index inside the document,
                              ``NOISED`` the ids after noising
    targets [B, T]    int32   the clean id where the token was masked, the
                              padding label elsewhere
    weights [B, T]    float32 ``1 / t`` of the token's block where it was
                              masked, 0 where it was left, -1 where there is no
                              token at all (a sequence that fills the eval
                              set's last batch)

and the batch is ``(tokens, (targets, weights))`` (train/steps.py reads the
weighted loss off the pair). The mask id is the last id of the vocabulary
held and is never drawn as a token. The train loader noises from (seed,
epoch); the eval loader once, from ``layout_seed``, so that eval losses
compare across epochs and seeds' eval sets mask the same positions.

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

import functools
from typing import Iterator

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import tracing
from .padding import PAD_LABEL

Batch = tuple[jax.Array, jax.Array]
# Rows of a block-diffusion batch's ``tokens``; the first two are the
# next-token batch's.
CLEAN, DOC, BLK, POS, NOISED = range(5)
T_MIN = 1e-3  # the least noise level of a block


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


def blockdiff_kept_pairs(segment_ids: np.ndarray, block_length: int) -> float:
    """The (query, key) pairs the block-diffusion mask keeps over the clean
    and the noised copy of ``segment_ids`` [N, T], all sequences together. A
    clean query keeps the clean keys of its document up to its block's end, a
    noised one those before its block and its block's noised keys: as many."""
    total = 0
    for row in segment_ids:
        for length in np.bincount(row - row.min()):
            ends = np.minimum((np.arange(length) // block_length + 1) * block_length, length)
            total += 2 * int(ends.sum())
    return float(total)


def next_token_targets(ids: np.ndarray, segment_ids: np.ndarray) -> np.ndarray:
    targets = np.full(ids.shape, PAD_LABEL, np.int32)
    same = segment_ids[:, 1:] == segment_ids[:, :-1]
    targets[:, :-1] = np.where(same, ids[:, 1:], PAD_LABEL)
    return targets


def block_ordinals(segment_ids: np.ndarray, block_length: int) -> tuple[np.ndarray, np.ndarray]:
    """(blk, pos) [N, T]: each token's index inside its document (as the
    sequence holds it) and the ordinal of its block of ``block_length``."""
    at = np.arange(segment_ids.shape[1])
    starts = np.concatenate(
        [np.ones_like(segment_ids[:, :1], bool), segment_ids[:, 1:] != segment_ids[:, :-1]], axis=1
    )
    pos = (at - np.maximum.accumulate(np.where(starts, at, 0), axis=1)).astype(np.int32)
    return pos // block_length, pos


@functools.partial(jax.jit, static_argnames=("block_length", "mask_id"))
def noise_epoch(key, tokens, block_length: int, mask_id: int):
    """``tokens`` [N, 4, T] (``CLEAN``, ``DOC``, ``BLK``, ``POS``) noised:
    (tokens [N, 5, T], (targets [N, T], weights [N, T])). One level a block,
    one draw a token, all from ``key``."""
    level_key, mask_key = jax.random.split(key)
    ids, pos = tokens[:, CLEAN], tokens[:, POS]
    # A block's level is the draw at its first token.
    first = jnp.arange(ids.shape[1])[None] - pos % block_length
    u = jax.random.uniform(level_key, ids.shape, jnp.float32)
    t = T_MIN + (1.0 - T_MIN) * jnp.take_along_axis(u, first, axis=1)
    masked = jax.random.uniform(mask_key, ids.shape, jnp.float32) < t
    noised = jnp.where(masked, mask_id, ids)
    targets = jnp.where(masked, ids, PAD_LABEL)
    weights = jnp.where(masked, 1.0 / t, 0.0)
    return jnp.concatenate([tokens, noised[:, None]], axis=1), (targets, weights)


class PackedTokenLoader:
    """Epoch iterator over device-resident packed sequences. Train: the
    sequences in an order drawn from (seed, epoch), whole batches only. Eval:
    in order, the last batch filled with sequences that have no target.
    The epoch counter is the loader's whole state, as DeviceCifarLoader's.
    With ``block_length`` > 0 the batches are block-diffusion batches (top of
    the file): ``tokens`` holds the four rows the layout and the ids give,
    the train loader noises each epoch from (seed, epoch), the eval loader
    keeps the one noising ``noise_seed`` gives."""

    batch_scope = "global"

    def __init__(
        self, ids: np.ndarray, segment_ids: np.ndarray, batch_size: int, train: bool,
        seed: int = 0, block_length: int = 0, mask_id: int = 0, noise_seed: int = 0,
    ):  # fmt: skip
        self.batch_size, self.train = batch_size, train
        self.block_length, self.mask_id = block_length, mask_id
        self.epoch = 0
        self._key = jax.random.PRNGKey(seed)
        if not block_length:
            self.tokens = jax.device_put(jnp.asarray(np.stack([ids, segment_ids], axis=1), jnp.int32))
            self.targets = jax.device_put(jnp.asarray(next_token_targets(ids, segment_ids)))
            return
        rows = [ids, segment_ids, *block_ordinals(segment_ids, block_length)]
        self.tokens = jax.device_put(jnp.asarray(np.stack(rows, axis=1), jnp.int32))
        if not train:
            self.tokens, self.targets = self._noised(jax.random.PRNGKey(noise_seed), self.tokens)

    def _noised(self, key, tokens):
        with tracing.span("epoch/noise"):
            return noise_epoch(key, tokens, self.block_length, self.mask_id)

    def __len__(self) -> int:
        n = self.tokens.shape[0]
        return n // self.batch_size if self.train else -(-n // self.batch_size)

    @property
    def num_samples(self) -> int:
        return int(self.tokens.shape[0])

    def _stacked(self, tokens, targets) -> Batch:
        s, b = len(self), self.batch_size
        return jax.tree.map(lambda x: x[: s * b].reshape((s, b) + x.shape[1:]), (tokens, targets))

    def epoch_arrays(self) -> Batch:
        """One epoch stacked on a step axis: tokens [S, B, 2, T], targets
        [S, B, T] (train/steps.py make_scan_chunk), or the block-diffusion
        batch's tokens [S, B, 5, T] and (targets, weights). Advances the epoch."""
        if not self.train:
            raise ValueError("epoch_arrays is for the train loader")
        epoch, self.epoch = self.epoch, self.epoch + 1
        key = jax.random.fold_in(self._key, epoch)
        order = jax.random.permutation(key, self.num_samples)
        tokens = jnp.take(self.tokens, order, axis=0)
        if self.block_length:
            return self._stacked(*self._noised(jax.random.fold_in(key, 1), tokens))
        return self._stacked(tokens, jnp.take(self.targets, order, axis=0))

    def eval_epoch_arrays(self) -> Batch:
        """The eval set stacked on a step axis (make_scan_eval); the harness
        keeps the one resident copy."""
        if self.train:
            raise ValueError("eval_epoch_arrays is for the eval loader")
        pad = len(self) * self.batch_size - self.num_samples
        tokens = jnp.pad(self.tokens, ((0, pad), (0, 0), (0, 0)))
        fill = lambda x, value: jnp.pad(x, ((0, pad), (0, 0)), constant_values=value)
        if self.block_length:
            return self._stacked(tokens, (fill(self.targets[0], PAD_LABEL), fill(self.targets[1], -1.0)))
        return self._stacked(tokens, fill(self.targets, PAD_LABEL))

    def __iter__(self) -> Iterator[Batch]:
        batches = self.epoch_arrays() if self.train else self.eval_epoch_arrays()
        for step in range(len(self)):
            yield jax.tree.map(lambda x: x[step], batches)


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
        block_length: int = 0,
    ):
        self.num_classes = vocab_size
        segment_ids = document_layout(
            num_train + num_test, seq_len, doc_len_mu, doc_len_sigma, doc_len_min, seq_len, layout_seed
        )
        # Block-diffusion batches keep the vocabulary's last id for the mask.
        noising = dict(block_length=block_length, mask_id=vocab_size - 1, noise_seed=layout_seed)
        ids = token_ids(segment_ids.shape, vocab_size - bool(block_length), seed, token_skew)
        self.train_loader = PackedTokenLoader(
            ids[:num_train], segment_ids[:num_train], batch_size, train=True, seed=seed, **noising
        )
        self.test_loader = PackedTokenLoader(
            ids[num_train:], segment_ids[num_train:], batch_size, train=False, seed=seed + 1, **noising
        )
        # What a step holds, the same for every seed (mean over the steps of
        # an epoch; the sequences differ among themselves).
        steps = len(self.train_loader)
        used = segment_ids[: steps * batch_size]
        if block_length:  # every token can be a target, each with probability E[t]
            targets_per_step = float(batch_size * seq_len) * (1.0 + T_MIN) / 2.0
        else:
            targets = next_token_targets(ids[: steps * batch_size], used)
            targets_per_step = float((targets != PAD_LABEL).sum()) / steps
        self.gauges = {
            "tokens_per_step": float(batch_size * seq_len),
            "target_tokens_per_step": targets_per_step,
            "docs_per_sequence": float((used.max(axis=1) + 1).mean()),
        }
        if block_length:  # the model's layers see both copies (models/sdar.py)
            self.gauges.update(
                rows_per_step=float(2 * batch_size * seq_len),
                block_length=float(block_length),
                blockdiff_kept_pairs_per_step=blockdiff_kept_pairs(used, block_length) / steps,
            )
