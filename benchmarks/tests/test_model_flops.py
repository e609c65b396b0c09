"""The model's operation count against the published figures."""

import jax
import jax.numpy as jnp
import pytest

from benchmarks import model_flops


def _shapes(name, dataset, classes, size):
    from turboprune_tpu.models import create_model

    model = create_model(name, num_classes=classes, dataset_name=dataset, compute_dtype=jnp.float32)
    return jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, size, size, 3)), train=False)
    )


@pytest.mark.parametrize(
    "name, dataset, classes, size, gmac",
    [
        # He et al. 2015 / torchvision: ResNet-50, 224x224, 4.09 G multiply-adds.
        ("resnet50", "ImageNet", 1000, 224, 4.09),
        # The CIFAR ResNet18 (3x3 stem, no max-pool) at 32x32: 0.56 G.
        ("resnet18", "CIFAR10", 10, 32, 0.556),
    ],
)
def test_forward_count_is_within_3_percent_of_the_published_figure(name, dataset, classes, size, gmac):
    v = _shapes(name, dataset, classes, size)
    flops = model_flops.forward_flops(v["params"], v["batch_stats"], size)
    assert flops / 2e9 == pytest.approx(gmac, rel=0.03)
    assert model_flops.train_step_flops(v["params"], v["batch_stats"], size, 256) == 3 * 256 * flops
