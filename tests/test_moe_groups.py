"""The grouped products stop at the last pair's tile (ops/moe.py, PR 42): a
round's group sizes are the rows its pairs fill, each expert's on whole
tiles, and the rows of the buffer past them are written by no product. Under
the CPU interpreter such a row IS NaN, so one reader that multiplies where it
should select poisons the sum: every case below asks for finite numbers
first, then for a plain loop over the experts held."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from turboprune_tpu.ops import moe

K, EXPERTS, HELD, OFFSET, WIDTH = 4, 16, 4, 4, 48
# (tokens, row width, tile, the capacity of a round that is half filled, a
# capacity that ``a_further_round``'s pairs outgrow once): XLA's rows, and rows
# of 1,024 float32 values in tiles of 128 tokens, which travel through the
# Pallas row kernel (interpreted here).
SHAPES = {"xla_rows": (64, 32, 8, 128, 128), "row_kernel": (128, 1024, 128, 1024, 256)}


def _routing(name, n):
    """[n, K] expert ids, each token's distinct."""
    t = np.arange(n)[:, None]
    if name == "half_the_buffer":  # one held expert a token, a quarter of the tokens each
        return (t + 4 * np.arange(K)) % EXPERTS
    if name == "empty_experts":  # the first and the last held expert get nothing
        return np.concatenate([np.where(t % 3 > 0, 5, 6), t * 0 + [8, 9, 10]], axis=1)
    if name == "a_further_round":  # two held experts get every token, a third half of them
        return np.concatenate([t * 0 + [4, 5], np.where(t % 2 > 0, 6, 8), t * 0 + 9], axis=1)
    if name == "no_held_expert":  # every pair is another chip's: groups that sum to 0, a grid of no tile
        return t * 0 + [0, 1, 8, 9]
    raise ValueError(name)


ROUTINGS = ("half_the_buffer", "empty_experts", "a_further_round", "no_held_expert")


def _case(routing, shape, kernels, seed=7):
    n, latent, tile, *capacities = SHAPES[shape]
    top, capacity = _routing(routing, n), capacities[routing == "a_further_round"]
    kz, kw, *of_kernels = jax.random.split(jax.random.PRNGKey(seed), 2 + kernels)
    z = jax.random.normal(kz, (n, latent))
    weights = jax.random.uniform(kw, (n, K), minval=0.1, maxval=1.0)
    *first, down = (0.2 * jax.random.normal(key, (HELD, latent, WIDTH)) for key in of_kernels)
    stacked = (*first, down.swapaxes(1, 2))
    load = np.bincount(top.ravel(), minlength=EXPERTS)[OFFSET : OFFSET + HELD]
    return jnp.asarray(top, jnp.int32), z, weights, stacked, capacity, tile, load


def _plain(z, top, weights, kernels):
    *first, down = kernels
    out = jnp.zeros(z.shape, jnp.float32)
    for e in range(HELD):
        w = jnp.sum(jnp.where(top == e + OFFSET, weights, 0), axis=-1)
        out += w[:, None] * (moe.BETWEEN[len(first)](*(z @ kernel[e] for kernel in first)) @ down[e])
    return out


def _close(got, want, tol):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert np.abs(got - want).max() <= tol * max(1.0, np.abs(want).max())


def _round_groups(load, tile, capacity):
    """What each round hands the products, by the plan's own arithmetic in numpy."""
    aligned = -(-load // tile) * tile
    ends = np.cumsum(aligned)
    starts = ends - aligned
    return [
        np.clip(np.minimum(ends, lo + capacity) - np.maximum(starts, lo), 0, None)
        for lo in range(0, max(int(ends[-1]), 1), capacity)
    ]


# The number of kernels changes what stands between the products and nothing of
# how rows travel, so the interpreted row kernel (5 s a case) runs under three alone.
@pytest.mark.parametrize(
    "shape, kernels",
    [("xla_rows", 2), ("xla_rows", 3), ("row_kernel", 3)],
    ids=["xla_rows-two_kernels", "xla_rows-three_kernels", "row_kernel-three_kernels"],
)
@pytest.mark.parametrize("routing", ROUTINGS)
def test_nothing_reads_a_row_no_product_wrote(routing, shape, kernels):
    """Output, counters and every gradient (z, the weights, each kernel) are
    finite and a plain loop's, whether the round leaves half the buffer to no
    group, experts empty, pairs for a further round, or no group a row (the
    products then run a grid of length 0 and write nothing at all)."""
    top, z, weights, stacked, capacity, tile, load = _case(routing, shape, kernels)
    assert moe.rows_move_in_tiles(jnp.float32, z.shape[1], capacity, z.shape[0], tile) == (shape == "row_kernel")

    def ours(z, weights, stacked):
        out, counted = moe.routed_experts(z, top, weights, stacked, OFFSET, capacity, tile)
        return jnp.sum(jnp.sin(out)), (out, counted)

    def theirs(z, weights, stacked):
        out = _plain(z, top, weights, stacked)
        return jnp.sum(jnp.sin(out)), out

    with jax.default_matmul_precision("highest"):
        (_, (out, counted)), grads = jax.jit(jax.value_and_grad(ours, (0, 1, 2), has_aux=True))(z, weights, stacked)
        (_, want), ref_grads = jax.jit(jax.value_and_grad(theirs, (0, 1, 2), has_aux=True))(z, weights, stacked)
    for got in (out, *jax.tree.leaves(grads)):
        assert bool(jnp.all(jnp.isfinite(got)))
    _close(out, want, 1e-5)
    for g, w in zip(jax.tree.leaves(grads), jax.tree.leaves(ref_grads)):
        _close(g, w, 1e-4)
    groups = _round_groups(load, tile, capacity)
    counted = {name: int(v) for name, v in counted.items()}
    assert counted == {
        "moe_pairs": load.sum(), "moe_dropped_pairs": 0, "moe_load_max": load.max(),
        "moe_rows_run": sum(g.sum() for g in groups),
    }  # fmt: skip
    assert counted["moe_pairs"] <= counted["moe_rows_run"] <= len(groups) * capacity
    assert len(groups) == (2 if routing == "a_further_round" else 1)
    assert counted["moe_rows_run"] < len(groups) * capacity  # slack that no product ran
    if routing == "no_held_expert":
        assert counted["moe_rows_run"] == 0 and not np.asarray(out).any()
        assert not any(np.asarray(g).any() for g in jax.tree.leaves(grads))


@pytest.mark.parametrize("kernels", [2, 3], ids=["two_kernels", "three_kernels"])
@pytest.mark.parametrize("routing", ROUTINGS)
def test_a_rounds_group_sizes_sum_to_its_aligned_rows(routing, kernels, monkeypatch):
    """What ``gmm`` is handed, call by call: the round's aligned rows by
    expert, in whole tiles, and not a row of the buffer's slack."""
    top, z, weights, stacked, capacity, tile, load = _case(routing, "xla_rows", kernels)
    handed = []

    def recorder(lhs, rhs, sizes, out_dtype, tiling, **_):
        handed.append(np.asarray(sizes))
        return jnp.zeros((lhs.shape[0], rhs.shape[2]), out_dtype)

    monkeypatch.setattr(moe, "gmm", recorder)
    with jax.disable_jit():
        moe.routed_experts(z, top, weights, stacked, OFFSET, capacity, tile)
    groups = _round_groups(load, tile, capacity)
    assert len(handed) == kernels * len(groups)
    for call, sizes in enumerate(handed):
        want = groups[call // kernels]
        np.testing.assert_array_equal(sizes, want)
        assert sizes.dtype == np.int32 and not (sizes % tile).any()
    assert handed[-1].sum() < capacity and sum(g.sum() for g in groups) == (-(-load // tile) * tile).sum()


def test_the_rows_past_the_last_group_are_written_by_no_product():
    """What gives the cases above their teeth: under the interpreter the rows
    past the last group come back NaN, the rows of the groups exactly what a
    call whose last group takes the slack returns, and ``tgmm`` reads no row
    past a group's end."""
    k0, k1 = jax.random.split(jax.random.PRNGKey(0))
    x, kernels = jax.random.normal(k0, (64, 32)), jax.random.normal(k1, (4, 32, 48))
    sizes, padded = jnp.asarray([8, 0, 16, 8], jnp.int32), jnp.asarray([8, 0, 16, 40], jnp.int32)
    got, whole = (moe.grouped_product(x, kernels, s, 8, jnp.float32) for s in (sizes, padded))
    np.testing.assert_array_equal(got[:32], whole[:32])
    assert bool(jnp.all(jnp.isnan(got[32:])))
    pull = lambda x, s: jax.vjp(lambda k: moe.grouped_product(x, k, s, 8, jnp.float32), kernels)[1]
    dy = jnp.ones((64, 48))
    (poisoned,), (clean,) = pull(x.at[32:].set(jnp.nan), sizes)(dy.at[32:].set(jnp.nan)), pull(x.at[32:].set(0), padded)(dy)
    np.testing.assert_array_equal(poisoned, clean)


@pytest.mark.parametrize(
    "obs, want",
    [
        ({}, None),  # a cell with no routed layer
        ({"moe": {"moe_pairs": 5632.0, "moe_load_max": 400.0}}, None),  # the parent's program: no such counter
        ({"lfm2_moe": {"moe_pairs": 0.0, "moe_rows_run": 0.0}}, None),
        ({"moe": {"moe_pairs": 5632.0, "moe_rows_run": 6656.0}}, 100 * 5632 / 6656),
        ({"moe_softmax": {"moe_pairs": 16384.0, "moe_rows_run": 17408.0}}, 100 * 16384 / 17408),
        ({"lfm2_moe": {"moe_pairs": 8192.0, "moe_rows_run": 13312.0}}, 100 * 8192 / 13312),
    ],
    ids=["no_routed_layer", "no_counter", "nothing_run", "sparse_expert", "blockdiff", "conv_hybrid"],
)
def test_the_share_of_the_rows_run_that_are_pairs(obs, want):
    """``benchmarks/metrics/moe_rows_pairs_pct.py``: the pairs over the rows
    the products ran, from whichever dictionary the cell's job fills; nothing
    where the program has no such counter."""
    from benchmarks import registry

    got = registry.load_metric("moe_rows_pairs_pct").read(obs)
    assert got is None if want is None else got == pytest.approx(want)
