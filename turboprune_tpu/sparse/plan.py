"""One ExecutionPlan: the single producer of sparse-backend decisions.

Before this module the repo had three separately-wired execution paths —
masked-dense, channel compaction (compact.py/train_compact.py) and gathered
N:M (nm_execute.py) — each with its own enter/exit logic in the harness and
its own probe branch in serve/engine.py, each globally on or off per run.
Which backend applies depends on the masks (scattered zeros can only be
gathered, dead channels can be sliced out), so a single-backend run has
nothing to offer the layers that only the other backend fits. Whether
either beats masked-dense on the chip has no measurement yet (ROADMAP A1).

``plan_execution`` derives ONE ``ExecutionPlan`` from the live masks:

* channel compaction is attempted first (whole-model width slicing, gated
  on ``CompactionPlan.savings()`` clearing ``compact_min_savings``);
* N:M gathering is then planned over the SURVIVORS — the same
  compact-then-gather composition the harness used, but decided in one
  place — routing each hook-eligible layer whose live contraction rows
  clear ``nm_min_axis_savings``;
* everything else stays masked-dense.

The plan carries the model-ctor overrides (``width_overrides`` /
``nm_overrides``), hashable cache keys, and a stable ``plan_signature()``
whose leading element is the plan KIND ("masked" / "compact" / "nm" /
"mixed") — the vocabulary the exec-manifest enumerates and the AOT cache
keys on. Every per-layer decision (backend, reason, estimated or measured
gain) lands in ``plan.report["decisions"]`` so routing is auditable and a
silent fallback to dense is visible, never implicit.

Autotune (``autotune="cost"`` or ``"measure"``) re-checks each routed N:M
layer against the masked-dense floor — an analytic gather-overhead cost
model, or a per-layer jitted micro-benchmark on the host platform — and
demotes layers where gathering would not pay. Compaction is not per-layer
tunable (the slice geometry is a whole-model property), so autotune only
refines the N:M routing inside the committed widths.

Gradients remain exactly masked-dense through any mix: compaction slices
coordinates whose gradients are exactly zero under the mask (anchor
expansion restores them), and ``nm_matmul``'s custom VJP keeps dw a full
dense GEMM — composing the two changes which coordinates are *materialized*,
never the values the optimizer sees.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Optional

import numpy as np

from .compact import CompactionPlan, build_plan, compact_tree
from .graph import CompactionError, build_graph
from .nm import _matrix_view, eligible_layers
from .nm_execute import (
    MIN_AXIS_SAVINGS,
    NMExecPlan,
    _hook_key,
    build_nm_plan,
    nm_matmul,
)

# Executable-surface hook: the plan-signature kind for MIXED plans (both a
# compaction and an N:M component). analysis/exec_manifest.py enumerates
# every PLAN_SIGNATURE_KIND declaration in the package so the manifest and
# the AOT cache agree on the signature vocabulary; single-backend plans
# reuse the kinds declared by compact.py / nm_execute.py / serve/engine.py.
PLAN_SIGNATURE_KIND = "mixed"

# Planner enables. "force" commits compaction whenever the plan builds —
# even the identity slice — and lets CompactionError propagate: the
# explicit-backend serving contract ("compact means compact, and say so
# honestly in the report"). "auto" gates on the savings threshold and
# records failures as decisions instead of raising.
COMPACT_MODES = ("auto", "force", "off")
NM_MODES = ("auto", "off")
AUTOTUNE_MODES = ("off", "cost", "measure")

# Analytic gather overhead as a fraction of the dense layer cost: two
# static takes on the operands plus (transposable only) the output
# scatter. A constant set on a CPU, owed to the sparse-level cell (ROADMAP
# A1); autotune="measure" replaces it with a real timing.
_GATHER_OVERHEAD = 0.15


@dataclasses.dataclass
class ExecutionPlan:
    """The one decision object every execution surface consumes.

    ``compaction``/``nm`` hold only COMMITTED backend plans (None = that
    backend does not run). ``decisions`` is the machine-readable routing
    table; ``report`` is the full audit record including both sub-reports.
    """

    compaction: Optional[CompactionPlan]
    nm: Optional[NMExecPlan]
    decisions: dict
    report: dict

    @property
    def kind(self) -> str:
        """Plan-signature kind: which backend(s) actually run."""
        if self.compaction is not None and self.nm is not None:
            return "mixed"
        if self.compaction is not None:
            return "compact"
        if self.nm is not None:
            return "nm"
        return "masked"

    @property
    def width_overrides(self) -> Optional[dict]:
        """Model-ctor width overrides, None when compaction does not run."""
        return self.compaction.width_overrides if self.compaction else None

    @property
    def nm_overrides(self) -> Optional[dict]:
        """Model-ctor N:M hook overrides, None when gathering does not run."""
        return self.nm.overrides if self.nm else None

    def width_key(self) -> tuple:
        """Hashable compaction component of step/eval cache keys."""
        return self.compaction.as_override_tuple() if self.compaction else ()

    def nm_key(self) -> tuple:
        """Hashable N:M component of step cache keys."""
        return self.nm.as_override_tuple() if self.nm else ()

    def plan_signature(self) -> tuple:
        """(kind, ...) executable-cache signature — the plan component of
        AOT keys (serve/fleet/aot_cache.py make_key). Single-backend plans
        emit exactly the signatures their modules emitted before the
        planner existed, so warm AOT caches stay warm across the refactor."""
        kind = self.kind
        if kind == "compact":
            return ("compact", self.width_key())
        if kind == "nm":
            return ("nm", self.nm_key())
        if kind == "mixed":
            return (PLAN_SIGNATURE_KIND, self.width_key(), self.nm_key())
        return ("masked",)


def _default_factory(model) -> Callable[..., Any]:
    """clone()-based model factory for callers that don't pass one."""

    def factory(width_overrides=None, nm_overrides=None):
        kw = {}
        if width_overrides:
            kw["width_overrides"] = tuple(sorted(dict(width_overrides).items()))
        if nm_overrides:
            kw["nm_overrides"] = tuple(sorted(dict(nm_overrides).items()))
        return model.clone(**kw) if kw else model

    return factory


def _plan_compaction(
    model, params, masks, batch_stats, mode: str, min_savings: float
) -> tuple[Optional[CompactionPlan], dict]:
    """Compaction stage: build the slice plan and decide commit/decline."""
    if mode == "off":
        return None, {
            "backend": "dense",
            "committed": False,
            "reason": "compaction disabled",
        }
    try:
        graph = build_graph(model, params)
        candidate = build_plan(params, masks, graph, batch_stats)
    except CompactionError as e:
        if mode == "force":
            raise
        return None, {
            "backend": "dense",
            "committed": False,
            "reason": f"CompactionError: {e}",
        }
    savings = candidate.savings()
    if mode == "force":
        commit, reason = True, "backend forced compact"
    elif savings <= 0.0:
        commit, reason = False, "no dead channels to slice"
    elif savings < min_savings:
        commit, reason = (
            False,
            f"savings {savings:.4f} below threshold {min_savings}",
        )
    else:
        commit, reason = (
            True,
            f"savings {savings:.4f} clears threshold {min_savings}",
        )
    decision = {
        "backend": "compact" if commit else "dense",
        "committed": commit,
        "savings": round(float(savings), 6),
        "params_before": candidate.report["params_before"],
        "params_after": candidate.report["params_after"],
        "channels_before": candidate.report["channels_before"],
        "channels_after": candidate.report["channels_after"],
        "reason": reason,
    }
    return (candidate if commit else None), decision


def _time_call(fn, *args) -> float:
    """Best-of-3 wall ms for an already-warm jitted call."""
    import jax

    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, (time.perf_counter() - t0) * 1e3)
    return best


def _nm_layer_estimates(
    nplan: NMExecPlan, shapes: dict, mode: str
) -> dict[str, dict]:
    """Per routed hook-key: estimated (cost model) or measured (micro-bench)
    nm-vs-dense gain. Gain < 1.0 means gathering would LOSE to masked-dense
    for that layer and autotune demotes it."""
    import jax
    import jax.numpy as jnp

    out: dict[str, dict] = {}
    for key, (ki, ko) in nplan.overrides.items():
        i, o = shapes[key]
        if mode == "cost":
            kept_in = len(ki) / i
            kept_out = (len(ko) / o) if ko is not None else 1.0
            est_cost = kept_in * kept_out + _GATHER_OVERHEAD
            out[key] = {
                "mode": "cost",
                "est_gain": round(1.0 / est_cost, 4),
            }
            continue
        # measure: time the two executables on a synthetic batch. Runs on
        # whatever platform the caller is pinned to; index maps are
        # compile-time metadata.
        x = jnp.ones((32, i), jnp.float32)
        w = jnp.ones((i, o), jnp.float32)
        b = jnp.zeros((o,), jnp.float32)
        # graftlint: disable=retrace-hazard -- one jit per routed layer by design: each (ki, ko) index map is a distinct executable; both are timed once and discarded
        dense_fn = jax.jit(lambda x2, w2, b2: x2 @ w2 + b2)
        # graftlint: disable=retrace-hazard -- one jit per routed layer by design: nm_matmul's index tuples are static argnums, so each layer is necessarily its own program
        nm_fn = jax.jit(lambda x2, w2, b2: nm_matmul(ki, ko, x2, w2, b2))
        dense_ms = _time_call(dense_fn, x, w, b)
        nm_ms = _time_call(nm_fn, x, w, b)
        out[key] = {
            "mode": "measure",
            "dense_ms": round(dense_ms, 5),
            "nm_ms": round(nm_ms, 5),
            "est_gain": round(dense_ms / max(nm_ms, 1e-9), 4),
        }
    return out


def _demote(nplan: NMExecPlan, drop: set, key_by_name: dict) -> NMExecPlan:
    """Rebuild the N:M plan without the demoted hook keys, keeping the
    report's coverage accounting honest."""
    overrides = {k: v for k, v in nplan.overrides.items() if k not in drop}
    layers = {}
    routed_params = 0
    for name, info in nplan.report["layers"].items():
        info = dict(info)
        if info["routed"] and key_by_name.get(name) in drop:
            info["routed"] = False
        if info["routed"]:
            routed_params += info["numel"]
        layers[name] = info
    eligible = nplan.report["eligible_params"]
    report = {
        "eligible_params": eligible,
        "routed_params": routed_params,
        "coverage_frac": routed_params / eligible if eligible else 0.0,
        "layers": layers,
    }
    return NMExecPlan(overrides=overrides, report=report)


def plan_execution(
    model,
    params,
    masks,
    batch_stats=None,
    *,
    model_factory: Optional[Callable[..., Any]] = None,
    compact: str = "auto",
    nm: str = "auto",
    compact_min_savings: float = 0.0,
    nm_min_axis_savings: float = MIN_AXIS_SAVINGS,
    autotune: str = "off",
) -> ExecutionPlan:
    """Derive this level's ExecutionPlan from the live masks.

    Pure function of replicated inputs — every host derives the identical
    plan, so no collective is needed to agree on it (callers that gate
    jittable work on the outcome, like compact-as-you-train, still barrier
    on the derived signature; see the harness).

    ``compact``: "auto" (commit when ``savings()`` > 0 and clears
    ``compact_min_savings``), "force" (commit whenever the plan builds,
    CompactionError propagates — explicit-backend serving semantics), or
    "off". ``nm``: "auto" or "off". ``autotune`` refines the N:M routing
    against the masked-dense floor: "cost" (analytic) or "measure"
    (per-layer jitted micro-benchmark).
    """
    if compact not in COMPACT_MODES:
        raise ValueError(f"compact mode {compact!r} not in {COMPACT_MODES}")
    if nm not in NM_MODES:
        raise ValueError(f"nm mode {nm!r} not in {NM_MODES}")
    if autotune not in AUTOTUNE_MODES:
        raise ValueError(f"autotune {autotune!r} not in {AUTOTUNE_MODES}")
    batch_stats = batch_stats or {}
    factory = model_factory or _default_factory(model)

    cplan, comp_decision = _plan_compaction(
        model, params, masks, batch_stats, compact, compact_min_savings
    )

    nplan: Optional[NMExecPlan] = None
    nm_report: Optional[dict] = None
    layer_decisions: dict[str, dict] = {}
    if nm != "off":
        # Compose over the committed widths: gather the SURVIVORS. The
        # sliced masks stay exact because routing keys on live rows/cols,
        # not block alignment (see build_nm_plan).
        if cplan is not None and cplan.width_overrides:
            exec_model = factory(width_overrides=cplan.width_overrides)
            live_masks = compact_tree(masks, cplan)
        else:
            exec_model = model
            live_masks = masks
        candidate = build_nm_plan(
            exec_model, live_masks, min_axis_savings=nm_min_axis_savings
        )
        nm_report = candidate.report
        key_by_name = {}
        shapes = {}
        for name, shape, s in eligible_layers(live_masks):
            key = _hook_key(exec_model, name, shape)
            key_by_name[name] = key
            if key is not None:
                shapes[key] = _matrix_view(shape, s)
        estimates: dict[str, dict] = {}
        if candidate.overrides and autotune != "off":
            estimates = _nm_layer_estimates(candidate, shapes, autotune)
            drop = {k for k, e in estimates.items() if e["est_gain"] < 1.0}
            if drop:
                candidate = _demote(candidate, drop, key_by_name)
            nm_report = candidate.report
        if candidate.overrides:
            nplan = candidate
        for name, info in nm_report["layers"].items():
            key = key_by_name.get(name)
            if info["routed"]:
                decision = {
                    "backend": "nm",
                    "reason": (
                        f"live rows {info['kept_in_frac']:.3f} clear "
                        f"axis-savings threshold {nm_min_axis_savings}"
                    ),
                }
            elif not info["hookable"]:
                decision = {
                    "backend": "dense",
                    "reason": "no gathered-execution hook for this layer",
                }
            elif key in estimates and estimates[key]["est_gain"] < 1.0:
                decision = {
                    "backend": "dense",
                    "reason": "autotune: gather overhead beats the "
                    "reduced-GEMM win for this layer",
                }
            else:
                decision = {
                    "backend": "dense",
                    "reason": (
                        f"live rows {info['kept_in_frac']:.3f} above "
                        f"axis-savings threshold {nm_min_axis_savings}"
                    ),
                }
            if key in estimates:
                decision.update(estimates[key])
            layer_decisions[name] = decision

    decisions = {"compaction": comp_decision, "layers": layer_decisions}
    plan = ExecutionPlan(
        compaction=cplan, nm=nplan, decisions=decisions, report={}
    )
    routed = len(nplan.overrides) if nplan is not None else 0
    dense_layers = sum(
        1 for d in layer_decisions.values() if d["backend"] == "dense"
    )
    plan.report = {
        "kind": plan.kind,
        "autotune": autotune,
        "backend_counts": {
            "nm_layers": routed,
            "dense_layers": dense_layers,
            "compact_spaces": (
                cplan.report.get("compacted_spaces", 0) if cplan else 0
            ),
        },
        "coverage_frac": nm_report["coverage_frac"] if nm_report else 0.0,
        "compaction": comp_decision,
        "nm": nm_report,
        "decisions": decisions,
    }
    return plan


def report_gauges(report: dict) -> dict[str, float]:
    """An ExecutionPlan report as the ``plan_*`` gauge family: per-layer
    backend decision counts, N:M coverage, and — when compaction was
    planned — the dense vs compacted parameter/channel counts, so a scraper
    or a test reads the size and routing the process ACTUALLY
    compiled, not just the mask density. One mapping for the trainer's
    gauges (utils/tracing.py) and the server's (serve/metrics.py)."""
    counts = report.get("backend_counts", {})
    out = {
        "plan_layers_nm": counts.get("nm_layers", 0),
        "plan_layers_dense": counts.get("dense_layers", 0),
        "plan_spaces_compacted": counts.get("compact_spaces", 0),
        "plan_coverage_frac": report.get("coverage_frac", 0.0),
    }
    comp = report.get("compaction") or {}
    if "params_before" in comp:
        out.update(
            plan_params_dense=comp["params_before"],
            plan_params_compacted=comp["params_after"],
            plan_channels_dense=comp["channels_before"],
            plan_channels_compacted=comp["channels_after"],
        )
    return out
