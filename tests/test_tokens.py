"""The packed token loader (data/tokens.py): the layout is the dataset's and
the same for every seed, the seed draws the ids; documents are cut at the
sequence length with no padding; a document's last token has no target."""

import jax
import numpy as np
import pytest

from turboprune_tpu.data.padding import PAD_LABEL
from turboprune_tpu.data.tokens import SyntheticTokenLoaders, document_layout

KW = dict(vocab_size=97, seq_len=64, batch_size=2, num_train=6, num_test=3,
          doc_len_mu=2.5, doc_len_sigma=1.0, doc_len_min=2, layout_seed=0)  # fmt: skip


@pytest.fixture(scope="module")
def two_seeds():
    return SyntheticTokenLoaders(**KW, seed=1), SyntheticTokenLoaders(**KW, seed=2**31 + 5)


def _host(loader):
    return np.asarray(loader.tokens), np.asarray(loader.targets)


def test_two_seeds_pack_the_same_documents_and_draw_other_ids(two_seeds):
    a, b = (_host(l.train_loader) for l in two_seeds)
    np.testing.assert_array_equal(a[0][:, 1], b[0][:, 1])  # segment ids
    assert (a[0][:, 0] != b[0][:, 0]).mean() > 0.5  # ids
    np.testing.assert_array_equal(a[1] == PAD_LABEL, b[1] == PAD_LABEL)
    assert two_seeds[0].gauges == two_seeds[1].gauges
    assert two_seeds[0].gauges["tokens_per_step"] == 128
    assert 0 < two_seeds[0].gauges["target_tokens_per_step"] < 128


def test_another_layout_seed_packs_other_documents():
    other = SyntheticTokenLoaders(**{**KW, "layout_seed": 1}, seed=1)
    same = SyntheticTokenLoaders(**KW, seed=1)
    assert (_host(other.train_loader)[0][:, 1] != _host(same.train_loader)[0][:, 1]).any()


def test_a_documents_last_token_has_no_target_and_every_other_has_the_next(two_seeds):
    tokens, targets = _host(two_seeds[0].train_loader)
    ids, seg = tokens[:, 0], tokens[:, 1]
    last = np.ones_like(seg, bool)
    last[:, :-1] = seg[:, 1:] != seg[:, :-1]
    assert (targets[last] == PAD_LABEL).all()
    np.testing.assert_array_equal(targets[:, :-1][~last[:, :-1]], ids[:, 1:][~last[:, :-1]])
    assert ids.min() >= 0 and ids.max() < 97


def test_documents_are_cut_at_the_sequence_length_without_padding():
    seg = document_layout(4, 64, 3.0, 1.0, 2, 64, layout_seed=3)
    assert seg.shape == (4, 64) and (seg[:, 0] == 0).all()
    steps = np.diff(seg, axis=1)
    assert set(np.unique(steps)) <= {0, 1}  # contiguous, counted from 0, none skipped
    lengths = [np.bincount(row) for row in seg]
    assert all(l.min() >= 1 and l.max() <= 64 for l in lengths)
    # Whole documents keep the clip; only the pieces at a boundary may be shorter.
    assert all((l[1:-1] >= 2).all() for l in lengths)


def test_an_epoch_is_whole_batches_in_an_order_the_epoch_counter_decides(two_seeds):
    loader = two_seeds[0].train_loader
    loader.epoch = 0
    tokens, targets = loader.epoch_arrays()
    assert tokens.shape == (3, 2, 2, 64) and targets.shape == (3, 2, 64)
    again = loader.epoch_arrays()
    loader.epoch = 0
    first = loader.epoch_arrays()
    np.testing.assert_array_equal(np.asarray(first[0]), np.asarray(tokens))
    assert (np.asarray(again[0]) != np.asarray(tokens)).any()
    # The same sequences every epoch, whatever their order.
    key = lambda t: sorted(map(bytes, np.asarray(t).reshape(6, -1)))
    assert key(again[0]) == key(tokens)


def test_the_eval_set_is_in_order_and_its_last_batch_is_filled_with_no_targets(two_seeds):
    loader = two_seeds[0].test_loader
    tokens, targets = loader.eval_epoch_arrays()
    assert tokens.shape == (2, 2, 2, 64) and len(loader) == 2
    flat = np.asarray(targets).reshape(4, 64)
    np.testing.assert_array_equal(flat[:3], np.asarray(loader.targets))
    assert (flat[3] == PAD_LABEL).all()
    assert [jax.tree.map(np.shape, b) for b in loader] == [((2, 2, 64), (2, 64))] * 2
