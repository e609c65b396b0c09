"""Mask-structure analysis: channel spaces and per-architecture propagation.

Unstructured masks only pay off on TPU when they contain STRUCTURE the
compiler can exploit — XLA executes the full-size convolution regardless of
how many mask entries are zero ("Structured Model Pruning of Convolutional
Networks on TPUs", PAPERS.md). What high-sparsity lottery tickets do grow is
dead fan-out slices: entire output channels / neurons whose mask is all
zero. Those CAN be cashed in by physically shrinking tensors along channel
dims, but only if every tensor sharing the channel axis shrinks together —
the kernel's out-slice, its bias entry, the BN scale/bias/mean/var entries,
and the matching in-slice of every consumer kernel downstream.

This module builds that sharing structure as a *propagation graph*:

  Space     one compactable channel axis: the out-axis of exactly one
            producer kernel, plus the per-channel leaves riding on it
            (conv/dense bias, BN params+stats) and an optional ``post``
            op chain applied before the space's value reaches consumers
            (DenseNet's stem norm — see below).
  Consumer  a kernel whose in-axis is built from one or more spaces
            (concatenation order preserved), with the per-channel ``gate``
            op chain between the raw space value and the consumer's input
            (BN -> ReLU for CNNs, GELU for ViT MLPs), and a ``repeat``
            factor for flatten boundaries (VGG's 7x7xC -> fc0).

Spaces are only created where compaction is PROVABLY local:

  VGG        every conv out-space and both hidden fc layers (pure chain);
  ResNet     block-internal spaces only (BasicBlock's 3x3->3x3 middle,
             Bottleneck's two inner convs). The trunk — stem output, block
             outputs, downsample branches — is shared through residual
             adds by many producers at once, so propagation STOPS at
             residual joins and those axes are never compacted;
  DenseNet   concat-aware: every dense-layer bottleneck, every growth
             segment, the stem segment and each transition output. A
             growth segment is consumed (at its concat offset) by every
             later layer in the block, the transition, and possibly the
             final norm/classifier — each with its OWN BatchNorm, which is
             why gates live on consumers, not spaces;
  ViT        the MLP hidden axis of every encoder block (fc1 -> GELU ->
             fc2). Attention projections and the embed axis ride the
             residual stream and are left alone.

Whether a dead channel may actually be REMOVED is a numeric question on
top of this structure (a dead conv channel still emits relu(bn(0)), which
is only droppable when that residue is exactly zero) — that analysis lives
in compact.py; this module is shape/topology only.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Optional

PathT = tuple[str, ...]
# Per-channel op between a space's raw value and a consumer's input:
#   ("bn", module_path, eps)  BatchNorm with params[module]{scale,bias} and
#                             batch_stats[module]{mean,var}
#   ("relu",)                 max(x, 0)
#   ("gelu",)                 exact (erf) GELU
GateOp = tuple


class CompactionError(ValueError):
    """Raised when a model/mask pair cannot be compacted as requested."""


@dataclass(frozen=True)
class Producer:
    kernel: PathT                 # path of the kernel leaf in params
    bias: Optional[PathT] = None  # conv/dense bias leaf (None: no bias)


@dataclass(frozen=True)
class Consumer:
    kernel: PathT                 # kernel whose in-axis (-2) we slice
    segments: tuple[str, ...]     # space names composing the in-axis, in order
    gate: tuple[GateOp, ...] = ()
    # Flatten factor: the in-axis is ``repeat * sum(segment channels)`` laid
    # out channel-fastest (VGG's reshape of [7, 7, C] -> 49*C).
    repeat: int = 1
    # Per-channel leaves living on the CONSUMER side of the edge — a BN that
    # normalizes the (possibly concatenated) input before this kernel
    # (DenseNet's norm1 / transition norm / norm_final). Sliced by the
    # concatenated in-keep vector (pre-repeat).
    attached_params: tuple[PathT, ...] = ()
    attached_stats: tuple[PathT, ...] = ()


@dataclass
class Space:
    name: str
    channels: int
    producer: Producer
    # Per-channel leaves sliced together with the space.
    attached_params: list[PathT] = field(default_factory=list)
    attached_stats: list[PathT] = field(default_factory=list)
    # Op chain applied to the raw producer output before the value joins any
    # consumer's input (DenseNet stem: conv0 -> norm0 -> relu -> concat...).
    post: tuple[GateOp, ...] = ()
    # Key under which the compacted width is reported to the model ctor
    # (models' ``width_overrides``); convention: kernel path minus "kernel".
    override_key: str = ""


@dataclass
class PropagationGraph:
    arch: str
    spaces: dict[str, Space]
    consumers: list[Consumer]

    def kernel_out_space(self) -> dict[PathT, str]:
        return {sp.producer.kernel: name for name, sp in self.spaces.items()}


# --------------------------------------------------------------------- util
def _tree_get(tree: Any, path: PathT) -> Any:
    node = tree
    for key in path:
        try:
            node = node[key]
        except (KeyError, TypeError) as e:
            raise CompactionError(
                f"param path {'/'.join(path)} not found while building the "
                f"propagation graph — model/params mismatch? ({e!r})"
            ) from e
    return node


def _out_channels(params: Any, kernel: PathT) -> int:
    return int(_tree_get(params, kernel).shape[-1])


def _key_of(kernel: PathT) -> str:
    return "/".join(kernel[:-1])


# ------------------------------------------------------------ per-arch build
def _resnet_graph(model, params) -> PropagationGraph:
    from ..models.resnet import Bottleneck

    eps = float(model.bn_epsilon)
    inner = 2 if issubclass(model.block_cls, Bottleneck) else 1
    spaces: dict[str, Space] = {}
    consumers: list[Consumer] = []
    for i, count in enumerate(model.stage_sizes):
        for j in range(count):
            block = f"layer{i + 1}_{j}"
            for k in range(inner):
                conv, bn = f"Conv_{k}", f"BatchNorm_{k}"
                kernel = (block, conv, "kernel")
                name = _key_of(kernel)
                spaces[name] = Space(
                    name=name,
                    channels=_out_channels(params, kernel),
                    producer=Producer(kernel),
                    attached_params=[(block, bn, "scale"), (block, bn, "bias")],
                    attached_stats=[(block, bn, "mean"), (block, bn, "var")],
                    override_key=name,
                )
                consumers.append(
                    Consumer(
                        kernel=(block, f"Conv_{k + 1}", "kernel"),
                        segments=(name,),
                        gate=(("bn", (block, bn), eps), ("relu",)),
                    )
                )
    return PropagationGraph("resnet", spaces, consumers)


def _vgg_graph(model, params) -> PropagationGraph:
    eps = float(model.bn_epsilon)
    conv_names = [f"conv{k}" for k, v in enumerate(
        v for v in model.cfg if v != "M"
    )]
    spaces: dict[str, Space] = {}
    consumers: list[Consumer] = []

    def conv_space(k: int):
        conv = conv_names[k]
        attached_p: list[PathT] = []
        attached_s: list[PathT] = []
        gate: list[GateOp] = []
        if model.batch_norm:
            bn = f"bn{k}"
            attached_p += [(bn, "scale"), (bn, "bias")]
            attached_s += [(bn, "mean"), (bn, "var")]
            gate.append(("bn", (bn,), eps))
        gate.append(("relu",))
        sp = Space(
            name=conv,
            channels=_out_channels(params, (conv, "kernel")),
            producer=Producer((conv, "kernel"), bias=(conv, "bias")),
            attached_params=attached_p,
            attached_stats=attached_s,
            override_key=conv,
        )
        return sp, tuple(gate)

    for k in range(len(conv_names)):
        sp, gate = conv_space(k)
        spaces[sp.name] = sp
        if k + 1 < len(conv_names):
            consumers.append(
                Consumer(
                    kernel=(conv_names[k + 1], "kernel"),
                    segments=(sp.name,),
                    gate=gate,
                )
            )
        else:
            # features -> classifier: adaptive pool to 7x7 (channelwise),
            # then reshape [n, 7, 7, C] -> [n, 49*C], channel-fastest.
            consumers.append(
                Consumer(
                    kernel=("fc0", "kernel"),
                    segments=(sp.name,),
                    gate=gate,
                    repeat=49,
                )
            )
    for fc, nxt in (("fc0", "fc1"), ("fc1", "fc2")):
        spaces[fc] = Space(
            name=fc,
            channels=_out_channels(params, (fc, "kernel")),
            producer=Producer((fc, "kernel"), bias=(fc, "bias")),
            override_key=fc,
        )
        consumers.append(
            Consumer(kernel=(nxt, "kernel"), segments=(fc,), gate=(("relu",),))
        )
    return PropagationGraph("vgg", spaces, consumers)


def _densenet_graph(model, params) -> PropagationGraph:
    eps = float(model.bn_epsilon)
    spaces: dict[str, Space] = {}
    consumers: list[Consumer] = []
    # Stem segment: conv0 -> norm0 -> relu [-> maxpool] feeds the concat
    # stream already normalized, so its normalization is a space-level
    # ``post`` chain (every other segment is normalized per-consumer).
    spaces["conv0"] = Space(
        name="conv0",
        channels=_out_channels(params, ("conv0", "kernel")),
        producer=Producer(("conv0", "kernel")),
        attached_params=[("norm0", "scale"), ("norm0", "bias")],
        attached_stats=[("norm0", "mean"), ("norm0", "var")],
        post=(("bn", ("norm0",), eps), ("relu",)),
        override_key="conv0",
    )
    segs: list[str] = ["conv0"]
    for i, layers in enumerate(model.block_sizes):
        for j in range(layers):
            layer = f"denseblock{i + 1}_layer{j + 1}"
            # norm1(+relu) over the WHOLE running concat, then conv1 — the
            # norm's per-channel leaves span the concat and slice with it.
            consumers.append(
                Consumer(
                    kernel=(layer, "conv1", "kernel"),
                    segments=tuple(segs),
                    gate=(("bn", (layer, "norm1"), eps), ("relu",)),
                    attached_params=(
                        (layer, "norm1", "scale"), (layer, "norm1", "bias"),
                    ),
                    attached_stats=(
                        (layer, "norm1", "mean"), (layer, "norm1", "var"),
                    ),
                )
            )
            mid = f"{layer}/conv1"
            spaces[mid] = Space(
                name=mid,
                channels=_out_channels(params, (layer, "conv1", "kernel")),
                producer=Producer((layer, "conv1", "kernel")),
                attached_params=[(layer, "norm2", "scale"), (layer, "norm2", "bias")],
                attached_stats=[(layer, "norm2", "mean"), (layer, "norm2", "var")],
                override_key=mid,
            )
            consumers.append(
                Consumer(
                    kernel=(layer, "conv2", "kernel"),
                    segments=(mid,),
                    gate=(("bn", (layer, "norm2"), eps), ("relu",)),
                )
            )
            seg = f"{layer}/conv2"
            spaces[seg] = Space(
                name=seg,
                channels=_out_channels(params, (layer, "conv2", "kernel")),
                producer=Producer((layer, "conv2", "kernel")),
                override_key=seg,
            )
            segs.append(seg)
        if i + 1 < len(model.block_sizes):
            tr = f"transition{i + 1}"
            consumers.append(
                Consumer(
                    kernel=(tr, "conv", "kernel"),
                    segments=tuple(segs),
                    gate=(("bn", (tr, "norm"), eps), ("relu",)),
                    attached_params=((tr, "norm", "scale"), (tr, "norm", "bias")),
                    attached_stats=((tr, "norm", "mean"), (tr, "norm", "var")),
                )
            )
            name = f"{tr}/conv"
            spaces[name] = Space(
                name=name,
                channels=_out_channels(params, (tr, "conv", "kernel")),
                producer=Producer((tr, "conv", "kernel")),
                override_key=name,
            )
            segs = [name]
    consumers.append(
        Consumer(
            kernel=("classifier", "kernel"),
            segments=tuple(segs),
            gate=(("bn", ("norm_final",), eps), ("relu",)),
            attached_params=(("norm_final", "scale"), ("norm_final", "bias")),
            attached_stats=(("norm_final", "mean"), ("norm_final", "var")),
        )
    )
    return PropagationGraph("densenet", spaces, consumers)


def _vit_graph(model, params) -> PropagationGraph:
    spaces: dict[str, Space] = {}
    consumers: list[Consumer] = []
    for i in range(model.depth):
        kernel = (f"block{i}", "mlp", "fc1", "kernel")
        name = _key_of(kernel)
        spaces[name] = Space(
            name=name,
            channels=_out_channels(params, kernel),
            producer=Producer(kernel, bias=(f"block{i}", "mlp", "fc1", "bias")),
            override_key=name,
        )
        consumers.append(
            Consumer(
                kernel=(f"block{i}", "mlp", "fc2", "kernel"),
                segments=(name,),
                gate=(("gelu",),),
            )
        )
    return PropagationGraph("vit", spaces, consumers)


def build_graph(model, params: Any) -> PropagationGraph:
    """Propagation graph for a supported model, with channel counts read
    from the concrete ``params`` tree (so width-overridden models analyze
    correctly too). Raises CompactionError for unsupported architectures."""
    from ..models import is_language_model
    from ..models.densenet import DenseNet
    from ..models.resnet import ResNet
    from ..models.vgg import VGG
    from ..models.vit import VisionTransformer

    if isinstance(model, ResNet):
        return _resnet_graph(model, params)
    if isinstance(model, VGG):
        return _vgg_graph(model, params)
    if isinstance(model, DenseNet):
        return _densenet_graph(model, params)
    if isinstance(model, VisionTransformer):
        return _vit_graph(model, params)
    if is_language_model(model):
        # Their compactable axes are known (the SwiGLU hidden axis, a scan
        # head with its slices of in_proj, conv, gate norm and out_proj, a
        # key/value head with its group of query heads, a channel of the
        # short convolution; an expert's hidden axis, one Space for each
        # expert of a stacked kernel, and the latent axis its experts share)
        # and have no Space yet: the planner's answer for every model of the
        # registry's language table is ``masked``.
        raise CompactionError(
            "the hybrid language models have no propagation graph yet: the "
            "SwiGLU and expert hidden axes, scan heads and grouped attention "
            "heads need Spaces of their own; it runs masked"
        )
    raise CompactionError(
        f"no propagation graph for model type {type(model).__name__} — "
        "compaction supports ResNet, VGG, DenseNet and ViT (MLP blocks)"
    )
