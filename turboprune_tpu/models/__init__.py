"""Model registry.

Replaces the reference's two-path factory (torchvision lookup with CIFAR
surgery + broken CustomModel globals() lookup,
/root/reference/utils/custom_models.py:169-245,
standard_pruning_harness.py:128-143) with a single explicit registry; CIFAR
stem surgery is a constructor argument instead of post-hoc module patching.
"""

from __future__ import annotations

from typing import Any, Callable

import jax.numpy as jnp

from . import densenet, granite, lfm2, nemotron_h, resnet, sdar, vgg, vit
from .densenet import DenseNet
from .granite import HybridLM
from .nemotron_h import NemotronH
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
    "granite_4_0_h_micro": granite.granite_4_0_h_micro,
    "hybrid_lm_tiny": granite.hybrid_lm_tiny,
    "nemotron_3_super_120b_a12b": nemotron_h.nemotron_3_super_120b_a12b,
    "nemotron_h_tiny": nemotron_h.nemotron_h_tiny,
    "sdar_30b_a3b": sdar.sdar_30b_a3b,
    "sdar_moe_tiny": sdar.sdar_moe_tiny,
    "lfm2_8b_a1b": lfm2.lfm2_8b_a1b,
    "lfm2_moe_tiny": lfm2.lfm2_moe_tiny,
}
# Models that read packed token batches (data/tokens.py) and return logits
# over their vocabulary, ``num_classes``.
LANGUAGE_MODELS = (
    "granite_4_0_h_micro", "hybrid_lm_tiny", "nemotron_3_super_120b_a12b", "nemotron_h_tiny",
    "sdar_30b_a3b", "sdar_moe_tiny", "lfm2_8b_a1b", "lfm2_moe_tiny",
)  # fmt: skip
# Of those, the ones built as one chip's share of a deployment
# (models/nemotron_h.py, models/sdar.py, models/lfm2.py): they take
# ``layer_pattern`` and ``share``.
SHARED_MODELS = (
    "nemotron_3_super_120b_a12b", "nemotron_h_tiny", "sdar_30b_a3b", "sdar_moe_tiny",
    "lfm2_8b_a1b", "lfm2_moe_tiny",
)  # fmt: skip
# And the ones trained by diffusion over blocks (models/sdar.py): their batch
# is the noised one, ``dataset_params.block_length`` > 0.
BLOCK_DIFFUSION_MODELS = ("sdar_30b_a3b", "sdar_moe_tiny")


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
    models/nemotron_h.py's, models/sdar.py's and models/lfm2.py's: the stretch of the published
    pattern that is run, and (tensor_parallel, expert_parallel, expert_rank)
    of the deployment whose one chip this is."""
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
        if model_name in SHARED_MODELS:
            kwargs = {"layer_pattern": layer_pattern, "share": tuple(share)}
        elif layer_pattern or tuple(share):
            raise ValueError(f"{model_name!r} has no layer_pattern and no share")
        return MODEL_REGISTRY[model_name](
            num_classes, num_layers=num_layers, dtype=compute_dtype, **kwargs
        )
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
    "HybridLM",
    "BLOCK_DIFFUSION_MODELS",
    "LANGUAGE_MODELS",
    "NemotronH",
    "ResNet",
    "VGG",
    "VisionTransformer",
    "resnet18",
    "resnet34",
    "resnet50",
    "resnet101",
    "resnet152",
]
