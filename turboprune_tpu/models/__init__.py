"""Model registry.

Replaces the reference's two-path factory (torchvision lookup with CIFAR
surgery + broken CustomModel globals() lookup,
/root/reference/utils/custom_models.py:169-245,
standard_pruning_harness.py:128-143) with a single explicit registry; CIFAR
stem surgery is a constructor argument instead of post-hoc module patching.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax.numpy as jnp

from . import brumby, densenet, granite, lfm2, nemotron_h, resnet, sdar, vgg, vit
from .densenet import DenseNet
from .resnet import ResNet, resnet18, resnet34, resnet50, resnet101, resnet152
from .vgg import VGG
from .vit import VisionTransformer

MODEL_REGISTRY: dict[str, Callable] = {
    "resnet18": resnet.resnet18,
    "resnet34": resnet.resnet34,
    "resnet50": resnet.resnet50,
    "resnet101": resnet.resnet101,
    "resnet152": resnet.resnet152,
    "wide_resnet50_2": resnet.wide_resnet50_2,
    "wide_resnet101_2": resnet.wide_resnet101_2,
    "densenet121": densenet.densenet121,
    "densenet169": densenet.densenet169,
    "vgg11": vgg.vgg11,
    "vgg11_bn": vgg.vgg11_bn,
    "vgg13": vgg.vgg13,
    "vgg13_bn": vgg.vgg13_bn,
    "vgg16": vgg.vgg16,
    "vgg16_bn": vgg.vgg16_bn,
    "vgg19": vgg.vgg19,
    "vgg19_bn": vgg.vgg19_bn,
    "deit_tiny_patch16_224": vit.deit_tiny_patch16_224,
    "deit_small_patch16_224": vit.deit_small_patch16_224,
    "deit_base_patch16_224": vit.deit_base_patch16_224,
    "deit_base_patch16_384": vit.deit_base_patch16_384,
    "deit_tiny_distilled_patch16_224": vit.deit_tiny_distilled_patch16_224,
    "deit_small_distilled_patch16_224": vit.deit_small_distilled_patch16_224,
    "deit_base_distilled_patch16_224": vit.deit_base_distilled_patch16_224,
    "deit_base_distilled_patch16_384": vit.deit_base_distilled_patch16_384,
}


class LanguageModel(NamedTuple):
    """A model that reads packed token batches (data/tokens.py) and returns
    logits over its vocabulary, ``num_classes``. Its factory takes
    ``(num_classes, *, num_layers, dtype, layer_pattern, share)`` and refuses
    what it has no use for."""

    factory: Callable
    shared: bool = False  # built as one chip's share of a deployment: takes ``share``
    block_diffusion: bool = False  # reads noised batches, ``dataset_params.block_length`` > 0

    @property
    def name(self) -> str:
        """What it is registered under: its factory's own name."""
        return self.factory.__name__


# The one place that says what kind a language model is. A new one is a file
# beside these (its blocks from models/blocks.py), an entry here, its conf/
# files and its tests.
LANGUAGE_TABLE = (
    LanguageModel(granite.granite_4_0_h_micro),
    LanguageModel(granite.hybrid_lm_tiny),
    LanguageModel(nemotron_h.nemotron_3_super_120b_a12b, shared=True),
    LanguageModel(nemotron_h.nemotron_h_tiny, shared=True),
    LanguageModel(sdar.sdar_30b_a3b, shared=True, block_diffusion=True),
    LanguageModel(sdar.sdar_moe_tiny, shared=True, block_diffusion=True),
    LanguageModel(lfm2.lfm2_8b_a1b, shared=True),
    LanguageModel(lfm2.lfm2_moe_tiny, shared=True),
    LanguageModel(brumby.brumby_14b_base, shared=True),
    LanguageModel(brumby.brumby_tiny, shared=True),
)
MODEL_REGISTRY.update({lm.name: lm.factory for lm in LANGUAGE_TABLE})
LANGUAGE_MODELS = tuple(lm.name for lm in LANGUAGE_TABLE)
SHARED_MODELS = tuple(lm.name for lm in LANGUAGE_TABLE if lm.shared)
BLOCK_DIFFUSION_MODELS = tuple(lm.name for lm in LANGUAGE_TABLE if lm.block_diffusion)


def is_language_model(model) -> bool:
    """Whether ``model`` is what a factory of ``LANGUAGE_TABLE`` builds: its
    class is defined in that factory's file."""
    return type(model).__module__ in {lm.factory.__module__ for lm in LANGUAGE_TABLE}


def create_model(
    model_name: str,
    num_classes: int,
    dataset_name: str = "CIFAR10",
    compute_dtype: Any = jnp.float32,
    attention_impl: str = "dense",
    mesh: Any = None,
    width_overrides: Any = None,
    nm_overrides: Any = None,
    num_layers: int = 0,
    layer_pattern: str = "",
    share: tuple = (),
):
    """Build a model module with dataset-appropriate stem.

    CIFAR datasets get the reference's stem surgery
    (custom_models.py:197-215) via ``cifar_stem=True``. ViT models accept
    ``attention_impl="ring"`` + a mesh for sequence-parallel attention
    (parallel/ring.py); CNNs reject it (no attention to shard).

    ``width_overrides`` (mapping of space name -> kept channels, from
    ``sparse.compact_params``) re-instantiates a dead-channel-compacted
    model; normalized to a sorted tuple so the module stays hashable.
    ``nm_overrides`` (hook key -> (kept_in, kept_out) index tuples, from
    ``sparse.nm_execute.build_nm_plan``) routes matmul-heavy layers through
    the gathered N:M path; same normalization, composes with
    ``width_overrides``. ``num_layers`` is a language model's depth (0 = as
    published); it always runs its causal flash kernel, whatever
    ``attention_impl`` says of the ViTs. ``layer_pattern`` and ``share`` are
    the ``SHARED_MODELS``': the stretch of the published pattern that is run,
    and (tensor_parallel, expert_parallel, expert_rank) of the deployment
    whose one chip this is; a model with no use for one refuses it."""
    if model_name not in MODEL_REGISTRY:
        raise ValueError(
            f"Model {model_name!r} not in registry: {sorted(MODEL_REGISTRY)}"
        )
    cifar_stem = dataset_name.lower() in ("cifar10", "cifar100")
    kwargs = {}
    if model_name in LANGUAGE_MODELS:
        if width_overrides or nm_overrides:
            raise ValueError(
                f"{model_name!r} has no compacted or gathered form "
                "(sparse/graph.py): it runs masked"
            )
        return MODEL_REGISTRY[model_name](
            num_classes, num_layers=num_layers, dtype=compute_dtype,
            layer_pattern=layer_pattern, share=tuple(share),
        )  # fmt: skip
    if model_name.startswith("deit"):
        kwargs = {"attention_impl": attention_impl, "mesh": mesh}
    elif attention_impl != "dense":
        raise ValueError(
            f"attention_impl={attention_impl!r} requires a ViT model "
            f"(got {model_name!r})"
        )
    if width_overrides:
        kwargs["width_overrides"] = tuple(sorted(dict(width_overrides).items()))
    if nm_overrides:
        kwargs["nm_overrides"] = tuple(sorted(dict(nm_overrides).items()))
    return MODEL_REGISTRY[model_name](
        num_classes, cifar_stem=cifar_stem, dtype=compute_dtype, **kwargs
    )


__all__ = [
    "MODEL_REGISTRY",
    "create_model",
    "DenseNet",
    "BLOCK_DIFFUSION_MODELS",
    "LANGUAGE_MODELS",
    "LANGUAGE_TABLE",
    "SHARED_MODELS",
    "is_language_model",
    "ResNet",
    "VGG",
    "VisionTransformer",
    "resnet18",
    "resnet34",
    "resnet50",
    "resnet101",
    "resnet152",
]
