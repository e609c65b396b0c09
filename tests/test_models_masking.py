import jax
import jax.numpy as jnp
import numpy as np
import pytest

from turboprune_tpu.models import create_model
from turboprune_tpu.ops import (
    apply_masks,
    global_threshold_mask,
    layerwise_sparsity,
    make_masks,
    mask_leaves,
    mask_where,
    masking,
    num_prunable,
    overall_density,
    overall_sparsity,
    reset_masks,
)
from turboprune_tpu.utils import tracing


@pytest.fixture(scope="module")
def tiny_resnet():
    model = create_model("resnet18", num_classes=10, dataset_name="CIFAR10")
    rng = jax.random.PRNGKey(0)
    variables = model.init(rng, jnp.zeros((1, 32, 32, 3)), train=False)
    return model, variables


def test_resnet18_shapes(tiny_resnet):
    model, variables = tiny_resnet
    x = jnp.zeros((2, 32, 32, 3))
    out = model.apply(variables, x, train=False)
    assert out.shape == (2, 10)


def test_resnet18_param_count(tiny_resnet):
    # torchvision CIFAR-surgered resnet18 ~11.17M params
    _, variables = tiny_resnet
    n = sum(x.size for x in jax.tree.leaves(variables["params"]))
    assert 11_000_000 < n < 11_300_000


def test_masks_cover_all_kernels(tiny_resnet):
    _, variables = tiny_resnet
    params = variables["params"]
    masks = make_masks(params)
    # every conv + dense kernel masked: resnet18 has 20 convs + 1 fc = 21
    assert len(mask_leaves(masks)) == 21
    assert overall_sparsity(masks) == 0.0
    # prunable count ≈ all non-BN params
    n_kernels = num_prunable(masks)
    assert 11_000_000 < n_kernels < 11_200_000


def test_apply_masks_zeroes_weights(tiny_resnet):
    _, variables = tiny_resnet
    params = variables["params"]
    masks = make_masks(params)
    masks = mask_where(masks, lambda m: jnp.zeros_like(m))
    masked = apply_masks(params, masks)
    for m, p in zip(
        mask_leaves(masks),
        [l for l in mask_leaves(make_masks(masked, lambda p: True))],
    ):
        pass  # structure check implicitly done by apply
    kernels = [
        leaf
        for path, leaf in jax.tree_util.tree_flatten_with_path(masked)[0]
        if str(getattr(path[-1], "key", "")) == "kernel"
    ]
    assert all(float(jnp.abs(k).sum()) == 0.0 for k in kernels)
    assert overall_sparsity(masks) == 100.0


def test_global_threshold_density(tiny_resnet):
    _, variables = tiny_resnet
    params = variables["params"]
    masks = make_masks(params)
    scores = mask_where(
        masks,
        lambda m, p: jnp.abs(p) * m.astype(p.dtype),
        params,
    )
    new_masks = global_threshold_mask(scores, masks, density=0.5)
    d = overall_density(new_masks)
    assert abs(d - 0.5) < 0.001


def test_mask_monotone_across_levels(tiny_resnet):
    # pruning twice can only remove weights, never resurrect (SURVEY §3.3)
    _, variables = tiny_resnet
    params = variables["params"]
    masks = make_masks(params)
    for density in (0.8, 0.64):
        scores = mask_where(
            masks, lambda m, p: jnp.abs(p) * m.astype(p.dtype), params
        )
        new_masks = global_threshold_mask(scores, masks, density=density)
        for old, new in zip(mask_leaves(masks), mask_leaves(new_masks)):
            resurrected = jnp.logical_and(new, jnp.logical_not(old))
            assert int(resurrected.sum()) == 0
        masks = new_masks
    assert abs(overall_density(masks) - 0.64) < 0.001


@pytest.fixture(scope="module")
def pruned_resnet_masks(tiny_resnet):
    """The tiny ResNet after a real magnitude prune to a tenth (the top_k the
    CPU makes quickest)."""
    _, variables = tiny_resnet
    params = variables["params"]
    masks = make_masks(params)
    scores = mask_where(masks, lambda m, p: jnp.abs(p) * m.astype(p.dtype), params)
    return global_threshold_mask(scores, masks, density=0.1)


def _drawn(shape, keep, seed):
    return np.random.default_rng(seed).random(shape) < keep


# Trees the one reduction (masking.kept_counts) has to count as numpy does.
MASK_TREES = {
    "none_leaves": lambda: {
        "conv": {"kernel": jnp.asarray(_drawn((3, 3, 4, 8), 0.7, 0)), "bias": None},
        "bn": {"scale": None, "bias": None},
        "fc": {"kernel": _drawn((8, 5), 0.2, 1)},  # a host array, as a restore leaves
    },
    "all_ones": lambda: {"a": {"kernel": jnp.ones((4, 6), bool)}, "b": {"kernel": jnp.ones((2, 2, 3, 3), bool)}},
    "all_zeros_leaf": lambda: {
        "a": {"kernel": jnp.zeros((5, 7), bool)},
        "b": {"kernel": jnp.asarray(_drawn((9, 2), 0.5, 2))},
    },
    "odd_sizes": lambda: {
        f"l{i}": {"kernel": jnp.asarray(_drawn(shape, 0.37, 3 + i))}
        for i, shape in enumerate([(1,), (7, 13), (3, 3, 5, 17), (1, 1, 1, 1), (257, 3), (1031,)])
    },
}


@pytest.fixture(params=[*MASK_TREES, "pruned_resnet"])
def mask_tree(request):
    if request.param == "pruned_resnet":
        return request.getfixturevalue("pruned_resnet_masks")
    return MASK_TREES[request.param]()


def _numpy_leaves(masks):
    return [np.asarray(m) for m in jax.tree.leaves(masks)]  # tree.leaves drops None


def test_the_reduction_counts_each_leaf_as_numpy_does(mask_tree):
    leaves = _numpy_leaves(mask_tree)
    kept = masking.kept_counts(mask_tree)
    assert kept == [int(np.count_nonzero(m)) for m in leaves]
    assert all(type(k) is int for k in kept)
    count = masking.count_masks(mask_tree)
    assert count == (sum(m.size - np.count_nonzero(m) for m in leaves), sum(m.size for m in leaves))
    assert count.total == num_prunable(mask_tree)


def test_the_three_readers_return_the_floats_of_the_formula_they_replace(mask_tree):
    """``zeros / total * 100.0`` on the same integers: equal with ``==``."""
    leaves = _numpy_leaves(mask_tree)
    total = zeros = 0
    for m in leaves:  # masking.py's loop as it stood, in numpy
        total += int(m.size)
        zeros += int(m.size - np.sum(m))
    sparsity = (zeros / total) * 100.0
    assert overall_sparsity(mask_tree) == sparsity
    assert overall_density(mask_tree) == 1.0 - sparsity / 100.0
    table = layerwise_sparsity(mask_tree)
    assert list(table.values()) == [(int(m.size - np.sum(m)) / m.size) * 100.0 for m in leaves]
    assert len(table) == len(leaves) and all("kernel" in k for k in table)


def test_the_pruned_resnet_is_what_the_fixture_says(pruned_resnet_masks):
    assert len(mask_leaves(pruned_resnet_masks)) == 21
    assert abs(overall_density(pruned_resnet_masks) - 0.1) < 0.001


def test_a_tree_with_nothing_prunable_reads_zero_and_dispatches_nothing():
    before = tracing.gauges().get("mask_reads", 0)
    assert overall_sparsity({"bn": {"scale": None}}) == 0.0
    assert layerwise_sparsity({"bn": {"scale": None}}) == {}
    assert tracing.gauges().get("mask_reads", 0) == before


@pytest.mark.parametrize("reader", [masking.kept_counts, overall_sparsity, overall_density, layerwise_sparsity])
def test_one_call_is_one_read_and_a_second_tree_of_the_shapes_compiles_nothing(reader):
    shapes = [(3, 3, 2, 5), (11, 4), (6,)]
    trees = [
        {f"l{i}": {"kernel": jnp.asarray(_drawn(s, keep, i)), "bias": None} for i, s in enumerate(shapes)}
        for keep in (0.9, 0.4)
    ]
    jax.block_until_ready(trees)
    reads = tracing.gauges().get("mask_reads", 0)
    with tracing.span("t/first"):
        first = reader(trees[0])
    assert tracing.gauges()["mask_reads"] == reads + 1
    with tracing.span("t/second") as second:
        again = reader(trees[1])
    assert tracing.gauges()["mask_reads"] == reads + 2
    assert second.compiles == 0  # jax.monitoring's compile events, by span
    assert first != again


def test_reset_masks(tiny_resnet):
    _, variables = tiny_resnet
    masks = make_masks(variables["params"])
    masks = mask_where(masks, lambda m: jnp.zeros_like(m))
    masks = reset_masks(masks)
    assert overall_sparsity(masks) == 0.0


def test_layerwise_sparsity_keys(tiny_resnet):
    _, variables = tiny_resnet
    masks = make_masks(variables["params"])
    table = layerwise_sparsity(masks)
    assert len(table) == 21
    assert all(v == 0.0 for v in table.values())


def test_masked_forward_gradient_semantics(tiny_resnet):
    """Gradient wrt raw params = mask * (grad wrt effective weight): pruned
    weights get zero grad through the forward (reference mask_layers.py:25)."""
    model, variables = tiny_resnet
    params = variables["params"]
    masks = make_masks(params)
    masks = mask_where(masks, lambda m: jnp.zeros_like(m))  # prune everything

    def loss_fn(p):
        out = model.apply(
            {"params": apply_masks(p, masks), "batch_stats": variables["batch_stats"]},
            jnp.ones((2, 32, 32, 3)),
            train=False,
        )
        return jnp.sum(out**2)

    grads = jax.grad(loss_fn)(params)
    kernel_grads = [
        leaf
        for path, leaf in jax.tree_util.tree_flatten_with_path(grads)[0]
        if str(getattr(path[-1], "key", "")) == "kernel"
    ]
    assert all(float(jnp.abs(g).sum()) == 0.0 for g in kernel_grads)


def test_vgg16_forward():
    model = create_model("vgg16_bn", num_classes=100, dataset_name="CIFAR100")
    variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3)))
    out = model.apply(variables, jnp.zeros((2, 32, 32, 3)), train=False)
    assert out.shape == (2, 100)


def test_deit_tiny_forward():
    model = create_model(
        "deit_tiny_patch16_224", num_classes=1000, dataset_name="ImageNet"
    )
    variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 224, 224, 3)))
    out = model.apply(variables, jnp.zeros((2, 224, 224, 3)), train=False)
    assert out.shape == (2, 1000)


def test_wide_resnet_widths_and_param_count():
    """wide_resnet50_2 doubles the bottleneck INNER convs only (torchvision
    width_per_group=128): block outputs keep 4x expansion, total params
    ~68.9M at 1000 classes."""
    model = create_model("wide_resnet50_2", num_classes=1000,
                         dataset_name="ImageNet")
    variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 64, 64, 3)))
    p = variables["params"]
    # layer1 block0: inner convs 128 wide, output 256 (torchvision shapes)
    assert p["layer1_0"]["Conv_0"]["kernel"].shape[-1] == 128
    assert p["layer1_0"]["Conv_2"]["kernel"].shape[-1] == 256
    n = sum(x.size for x in jax.tree.leaves(p))
    assert 68_000_000 < n < 69_500_000
    out = model.apply(variables, jnp.zeros((2, 64, 64, 3)), train=False)
    assert out.shape == (2, 1000)


def test_densenet121_forward_params_and_masks():
    """torchvision densenet121 ~7.98M params at 1000 classes; masks cover
    every conv + the classifier (name-based 'kernel' rule)."""
    model = create_model("densenet121", num_classes=1000,
                         dataset_name="ImageNet")
    variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 64, 64, 3)))
    p = variables["params"]
    n = sum(x.size for x in jax.tree.leaves(p))
    assert 7_800_000 < n < 8_200_000
    out = model.apply(variables, jnp.zeros((2, 64, 64, 3)), train=False)
    assert out.shape == (2, 1000)
    masks = make_masks(p)
    masked = sum(m.size for m in mask_leaves(masks))
    kernels = sum(
        x.size
        for path, x in jax.tree_util.tree_flatten_with_path(p)[0]
        if str(getattr(path[-1], "key", path[-1])) == "kernel"
    )
    assert masked == kernels > 7_700_000  # convs + classifier dominate


def test_densenet121_cifar_stem_prunes_end_to_end():
    model = create_model("densenet121", num_classes=10, dataset_name="CIFAR10")
    variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3)))
    p = variables["params"]
    assert p["conv0"]["kernel"].shape[:2] == (3, 3)  # CIFAR stem surgery
    masks = make_masks(p)
    masks2 = global_threshold_mask(p, masks, density=0.3)
    assert abs(overall_density(masks2) - 0.3) < 5e-3
    pruned = apply_masks(p, masks2)
    out = model.apply({**variables, "params": pruned},
                      jnp.zeros((2, 32, 32, 3)), train=False)
    assert out.shape == (2, 10)
