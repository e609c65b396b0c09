"""Make sparsity pay: dead-channel compaction for train, eval and serving.

graph.py          mask-structure analysis — channel spaces with
                  per-architecture propagation (VGG chains, ResNet stops at
                  residual joins, DenseNet concat-aware offsets, ViT MLP
                  blocks)
compact.py        ``build_plan`` (keep vectors + shape report) and the
                  generic ``compact_tree``/``expand_tree`` slice/scatter
                  pair; ``compact_params`` — mask-folded smaller tensors +
                  ``width_overrides`` for eval/serving, with a
                  numeric-residue guard that keeps any dead channel whose
                  relu(bn(0)) constant is nonzero (exactness over size)
train_compact.py  ``compact_train_state``/``expand_train_state`` — the
                  WHOLE TrainState (raw params, masks, BN stats, optax
                  moments) sliced for compact-as-you-train and scattered
                  back to full coordinates for pruning/rewind/checkpoints

nm.py             N:M projection — snap unstructured masks to separable
                  (transposable) N:M block patterns, highest preserved
                  magnitude per M-block, vmap-batched solvers
nm_execute.py     gathered N:M execution — static int32 index maps +
                  custom-VJP reduced-width matmul, NM* drop-in modules and
                  ``build_nm_plan``; the second execution backend next to
                  compaction (composable: compact first, N:M the survivors)
plan.py           ``plan_execution`` — the ONE planner that turns live masks
                  into an ``ExecutionPlan`` (compact the dead channels, N:M
                  the scattered survivors, dense where neither pays, with an
                  optional cost-model/micro-bench autotune pass) consumed by
                  the harness and the serving engine alike

Consumed by serve/engine.py (planner-driven backend selection) and the
harness's compact eval and plan-execution paths. No benchmark cell runs a
sparse backend yet (ROADMAP A1).
"""

from .compact import (
    CompactionPlan,
    CompactionResult,
    analyze_masks,
    build_plan,
    compact_params,
    compact_stats,
    compact_tree,
    expand_stats,
    expand_tree,
)
from .graph import CompactionError, PropagationGraph, build_graph
from .nm import (
    NMError,
    check_divisibility,
    nm_pattern_inaxis,
    nm_pattern_transposable,
    project_masks,
)
from .nm_execute import NMExecPlan, build_nm_plan
from .plan import (
    AUTOTUNE_MODES,
    COMPACT_MODES,
    NM_MODES,
    ExecutionPlan,
    plan_execution,
)
from .train_compact import (
    compact_train_state,
    expand_opt_state,
    expand_train_state,
    slice_opt_state,
    width_signature,
)

__all__ = [
    "AUTOTUNE_MODES",
    "COMPACT_MODES",
    "CompactionError",
    "CompactionPlan",
    "CompactionResult",
    "ExecutionPlan",
    "NMError",
    "NMExecPlan",
    "NM_MODES",
    "PropagationGraph",
    "analyze_masks",
    "build_graph",
    "build_nm_plan",
    "build_plan",
    "check_divisibility",
    "compact_params",
    "compact_stats",
    "compact_tree",
    "compact_train_state",
    "expand_opt_state",
    "expand_stats",
    "expand_train_state",
    "expand_tree",
    "nm_pattern_inaxis",
    "nm_pattern_transposable",
    "plan_execution",
    "project_masks",
    "slice_opt_state",
    "width_signature",
]
