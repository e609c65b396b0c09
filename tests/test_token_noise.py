"""The block-diffusion batches of data/tokens.py: masks only where drawn,
targets and weights only at masked rows, the mask's id never a token, the
same layout under two seeds, an eval set that is noised once, and the gauges
the layout gives."""

import jax
import numpy as np
import pytest

from turboprune_tpu.data import tokens as tk
from turboprune_tpu.data.padding import PAD_LABEL
from turboprune_tpu.utils import tracing

VOCAB, T, BLOCK = 96, 64, 4


def loaders(seed, **kw):
    args = dict(vocab_size=VOCAB, seq_len=T, batch_size=2, num_train=8, num_test=3, doc_len_mu=2.5,
                doc_len_sigma=1.2, doc_len_min=2, layout_seed=0, token_skew="uniform", block_length=BLOCK)  # fmt: skip
    return tk.SyntheticTokenLoaders(seed=seed, **{**args, **kw})


@pytest.fixture(scope="module")
def epoch():
    data = loaders(3)
    tokens, (targets, weights) = jax.device_get(data.train_loader.epoch_arrays())
    return data, tokens.reshape(-1, 5, T), targets.reshape(-1, T), weights.reshape(-1, T)


def test_a_token_is_the_mask_where_it_was_drawn_and_itself_elsewhere(epoch):
    data, tokens, targets, weights = epoch
    clean, noised = tokens[:, tk.CLEAN], tokens[:, tk.NOISED]
    masked = noised != clean
    assert clean.max() <= VOCAB - 2 and (noised[masked] == VOCAB - 1).all()  # the mask is never a token
    assert 0.3 < masked.mean() < 0.7  # t ~ U(0.001, 1): a half, about
    np.testing.assert_array_equal(targets, np.where(masked, clean, PAD_LABEL))
    assert (weights[~masked] == 0).all() and (weights[masked] >= 1.0).all()
    assert weights.max() <= 1.0 / tk.T_MIN


def test_a_block_has_one_level_and_blocks_count_from_the_documents_first_token(epoch):
    _, tokens, _, weights = epoch
    doc, blk, pos = tokens[:, tk.DOC], tokens[:, tk.BLK], tokens[:, tk.POS]
    starts = np.concatenate([np.ones_like(doc[:, :1], bool), doc[:, 1:] != doc[:, :-1]], axis=1)
    assert (pos[starts] == 0).all() and (np.diff(pos, axis=1)[~starts[:, 1:]] == 1).all()
    np.testing.assert_array_equal(blk, pos // BLOCK)
    for row in range(tokens.shape[0]):
        for d, b in {(d, b) for d, b in zip(doc[row], blk[row])}:
            levels = weights[row][(doc[row] == d) & (blk[row] == b) & (weights[row] > 0)]
            assert len(set(levels.tolist())) <= 1  # 1 / t of the block, wherever it masked
    short = [np.bincount(blk[0][doc[0] == d])[-1] for d in np.unique(doc[0])]
    assert min(short) < BLOCK  # some document's last block is short


def test_the_layout_is_the_datasets_and_the_noise_the_seeds():
    a, b = loaders(3), loaders(4)
    ta, tb = (jax.device_get(x.train_loader.tokens) for x in (a, b))
    for row in (tk.DOC, tk.BLK, tk.POS):
        np.testing.assert_array_equal(np.sort(ta[:, row], axis=0), np.sort(tb[:, row], axis=0))
    assert (ta[:, tk.CLEAN] != tb[:, tk.CLEAN]).mean() > 0.9
    assert a.gauges == b.gauges and a.gauges["rows_per_step"] == 2 * 2 * T
    assert a.gauges["block_length"] == BLOCK and a.gauges["target_tokens_per_step"] == pytest.approx(T, rel=0.01)
    # The pairs the mask keeps, from the layout alone, against the rule written out.
    seg = ta[:, tk.DOC]
    blk = ta[:, tk.BLK]
    same = seg[:, :, None] == seg[:, None, :]
    qb, kb = blk[:, :, None], blk[:, None, :]
    kept = (same & (kb <= qb)).sum() + (same & (kb < qb)).sum() + (same & (kb == qb)).sum()
    assert a.gauges["blockdiff_kept_pairs_per_step"] * len(a.train_loader) == kept
    # Two epochs of one seed are noised apart, one epoch of two loaders alike.
    first, second = a.train_loader.epoch_arrays(), a.train_loader.epoch_arrays()
    again = loaders(3).train_loader.epoch_arrays()
    assert not np.array_equal(first[1][0], second[1][0])
    np.testing.assert_array_equal(first[0], again[0])


def test_the_eval_set_is_noised_once_from_the_layouts_seed():
    a, b = loaders(3), loaders(4)
    (ta, (ga, wa)), (tb, (gb, wb)) = (jax.device_get(x.test_loader.eval_epoch_arrays()) for x in (a, b))
    assert ta.shape == (2, 2, 5, T) and ga.shape == wa.shape == (2, 2, T)
    np.testing.assert_array_equal(wa, wb)  # the same positions masked at the same levels
    np.testing.assert_array_equal(ga >= 0, gb >= 0)
    assert not np.array_equal(ga, gb)  # of other ids
    again = jax.device_get(a.test_loader.eval_epoch_arrays())
    np.testing.assert_array_equal(again[1][1], wa)
    # Three sequences in batches of two: the fourth place holds no token.
    assert (wa[1, 1] == -1).all() and (ga[1, 1] == PAD_LABEL).all() and (wa[:1] >= 0).all()
    with pytest.raises(ValueError, match="train loader"):
        a.test_loader.epoch_arrays()


def test_the_noising_is_one_program_under_its_span():
    data = loaders(5)
    with tracing.span("epoch/feed") as feed:
        data.train_loader.epoch_arrays()
    assert len(tracing.recorded("epoch/noise", feed.start, feed.end)) == 1
    batch = next(iter(data.train_loader))
    assert batch[0].shape == (2, 5, T) and batch[1][0].shape == batch[1][1].shape == (2, T)
    assert tk.noise_epoch.__wrapped__.__name__ == "noise_epoch"  # the module ``jit_noise_epoch``
