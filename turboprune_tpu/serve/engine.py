"""InferenceEngine — pruned-checkpoint forward with a compiled-shape cache.

Loads any experiment-dir checkpoint (``model_level_{L}`` or a role like
``model_init``) next to the experiment's own ``expt_config.yaml`` snapshot,
so a served checkpoint can never be paired with the wrong architecture.
Masks are folded into the weights ONCE at load time (``w * m`` is exact in
fp32, so the folded forward is bit-identical to the training path's
apply-masks-inside-jit forward — asserted in tests/test_serve.py), and the
forward is AOT-compiled per padded batch-size bucket: a request for n rows
is padded up to the smallest bucket >= n (split at the largest bucket), so
at steady state no request ever triggers a fresh XLA trace. Compile-cache
hits/misses are reported through ServeMetrics.

Backend selection is delegated to the ONE planner (sparse/plan.py
``plan_execution``): ``backend="auto"``/``"mixed"`` let it compose —
channel-compact where dead channels actually shrink the checkpoint
(serving commits on ANY real shrinkage: no optimizer state to slice), N:M
gathering where the index plan routes a layer over the survivors, and
masked-dense where neither pays — while ``masked``/``compact``/``nm`` pin
a single backend (``compact`` raises loudly when the architecture has no
compaction graph; ``nm`` degrades honestly to masked when nothing routes).
Masks are folded before any slicing/gathering, so every backend reads
exact already-masked weights; ``engine.plan.report`` carries the per-layer
decision table. With an ``aot_cache`` (serve/fleet/aot_cache.py) each
bucket's compiled executable is looked up on disk before invoking XLA —
``xla_compiles_total`` counts only REAL compiles, so a warm cache provably
makes construction compile-free.

Serving is single-process/single-program by design — the training-side mesh
machinery (sharded steps, multihost barriers) is deliberately not involved;
model-parallel attention impls (ring) fall back to their dense equivalent,
which has an identical param tree (README "Long context / SP").
"""

from __future__ import annotations

import bisect
import threading
import time
from pathlib import Path
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import yaml

from ..config.schema import config_from_dict
from ..models import create_model
from ..ops import masking
from ..train.state import init_variables
from ..utils.checkpoint import ExperimentCheckpoints, restore_model_tree

DEFAULT_BUCKETS = (1, 8, 32, 128)

# Executable-surface hook: the plan-signature kind for the dense fallback
# (no sparse plan). The sparse kinds live next to their plan dataclasses
# (sparse/compact.py, sparse/nm_execute.py, sparse/plan.py for "mixed");
# analysis/exec_manifest.py enumerates every PLAN_SIGNATURE_KIND
# declaration to bound the set of plan formats an AOT cache key can carry.
PLAN_SIGNATURE_KIND = "masked"

# backend knob -> (compact mode, nm mode) handed to the planner. "mixed"
# is the explicit spelling of what "auto" already does — both backends
# offered, the planner composes whatever pays.
_BACKEND_MODES = {
    "masked": ("off", "off"),
    "compact": ("force", "off"),
    "nm": ("off", "auto"),
    "auto": ("auto", "auto"),
    "mixed": ("auto", "auto"),
}


def _clone_factory(model):
    """Default model re-instantiation for compact/nm backends: clone the
    module with normalized (hashable) override tuples."""

    def factory(width_overrides=None, nm_overrides=None):
        kw = {}
        if width_overrides:
            kw["width_overrides"] = tuple(
                sorted(dict(width_overrides).items())
            )
        if nm_overrides:
            kw["nm_overrides"] = tuple(sorted(dict(nm_overrides).items()))
        return model.clone(**kw)

    return factory


class NotServable(ValueError):
    """The experiment's model is of a kind the server has no path for."""


class InferenceEngine:
    """Bucketed, mask-folded forward over a loaded checkpoint.

    ``predict`` is thread-safe: compilation is serialized behind a lock and
    XLA executables are themselves safe to invoke concurrently."""

    def __init__(
        self,
        model,
        params,
        masks,
        batch_stats,
        *,
        input_shape: Sequence[int],
        buckets: Sequence[int] = DEFAULT_BUCKETS,
        metrics=None,
        level: Optional[int] = None,
        source: str = "",
        compact: bool = False,
        model_factory=None,
        backend: Optional[str] = None,
        aot_cache=None,
        nm_min_axis_savings: Optional[float] = None,
        autotune: str = "off",
    ):
        self.model = model
        self.buckets = tuple(sorted({int(b) for b in buckets}))
        if not self.buckets or self.buckets[0] < 1:
            raise ValueError(f"buckets must be positive ints, got {buckets!r}")
        self.input_shape = tuple(int(d) for d in input_shape)
        self.metrics = metrics
        self.aot_cache = aot_cache
        self.level = level
        self.source = source
        self.density = masking.overall_density(masks)
        self.compaction: Optional[dict] = None
        self.nm_plan_report: Optional[dict] = None
        if backend is None:
            backend = "compact" if compact else "masked"
        if backend not in _BACKEND_MODES:
            raise ValueError(f"unknown serving backend {backend!r}")
        factory = model_factory or _clone_factory(model)
        from ..sparse import compact_stats, compact_tree, plan_execution
        from ..sparse.nm_execute import MIN_AXIS_SAVINGS

        compact_mode, nm_mode = _BACKEND_MODES[backend]
        # The ONE planner (sparse/plan.py) produces the backend decision.
        # compact_min_savings=0 is serving's commit rule: any real shrinkage
        # pays at inference (no optimizer state to slice), which is exactly
        # the params_after < params_before probe this replaced. The real
        # batch_stats are handed to the planner — compaction slices attached
        # BN stats, so an empty tree would fail the probe for BN models.
        plan = plan_execution(
            model,
            params,
            masks,
            batch_stats or {},
            model_factory=factory,
            compact=compact_mode,
            nm=nm_mode,
            compact_min_savings=0.0,
            nm_min_axis_savings=(
                MIN_AXIS_SAVINGS
                if nm_min_axis_savings is None
                else nm_min_axis_savings
            ),
            autotune=autotune,
        )
        self.plan = plan
        self.backend = plan.kind
        self._plan_signature = plan.plan_signature()
        # Fold once: pruned weights become literal zeros in the served
        # params, so per-request forwards skip the mask multiply entirely —
        # and any N:M gathers read exact already-masked weights.
        folded = masking.apply_masks(params, masks)
        if plan.compaction is not None:
            # Slice the folded checkpoint to the committed widths and serve
            # the physically smaller model — the AOT lower below compiles
            # the smaller HLO. Numerically equivalent to the masked-dense
            # forward up to fp reassociation (tests/test_sparse.py pins the
            # tolerance).
            self._variables = {
                "params": compact_tree(folded, plan.compaction)
            }
            cstats = compact_stats(batch_stats or {}, plan.compaction)
            if cstats:
                self._variables["batch_stats"] = cstats
            self.compaction = plan.compaction.report
        else:
            self._variables = {"params": folded}
            if batch_stats:
                self._variables["batch_stats"] = batch_stats
        if plan.width_overrides or plan.nm_overrides:
            self.model = factory(
                width_overrides=plan.width_overrides,
                nm_overrides=plan.nm_overrides,
            )
        if plan.nm is not None:
            self.nm_plan_report = {
                "routed_layers": len(plan.nm.overrides),
                "coverage_frac": plan.nm.report["coverage_frac"],
                "eligible_params": plan.nm.report["eligible_params"],
                "routed_params": plan.nm.report["routed_params"],
            }
        if metrics:
            metrics.record_plan(plan.report)
        self.num_classes = None  # set by the first compile (output aval)
        self._compiled: dict[int, Any] = {}
        self._compile_lock = threading.Lock()

    # ----------------------------------------------------------- compiling
    def _apply(self, variables, images):
        return self.model.apply(variables, images, train=False)

    def _executable(self, bucket: int):
        """Compiled forward for one bucket shape; AOT via jit.lower so the
        trace happens exactly once per bucket per process."""
        fn = self._compiled.get(bucket)
        if fn is not None:
            if self.metrics:
                self.metrics.compile_hit()
            return fn
        with self._compile_lock:
            fn = self._compiled.get(bucket)
            if fn is not None:  # lost the race: someone compiled it already
                if self.metrics:
                    self.metrics.compile_hit()
                return fn
            if self.metrics:
                self.metrics.compile_miss()
            spec = jax.ShapeDtypeStruct(
                (bucket, *self.input_shape), jnp.float32
            )
            t0 = time.perf_counter()
            # graftlint: disable=retrace-hazard -- AOT by design: lower() runs once per bucket shape, guarded by the _compiled cache + _compile_lock double-check above
            # graftlint: disable=blocking-call-under-lock -- single-flight compile IS the point of _compile_lock: concurrent requests for the same cold bucket must wait for one trace, not each run their own; other buckets' hits stay lock-free via the fast path above
            lowered = jax.jit(self._apply).lower(self._variables, spec)
            fn = None
            key = None
            if self.aot_cache is not None:
                # Persistent layer: tracing (above) is cheap; the expensive
                # XLA compile is what the on-disk executable replaces.
                key = self.aot_cache.make_key(
                    hlo_fingerprint=self.aot_cache.fingerprint(lowered),
                    plan_signature=self._plan_signature,
                    bucket=bucket,
                )
                fn, status = self.aot_cache.load(key)
                if self.metrics:
                    self.metrics.inc(f"aot_cache_{status}_total")
            if fn is None:
                # graftlint: disable=blocking-call-under-lock -- single-flight XLA compile under _compile_lock, same contract as the lower() above; holding the lock for seconds on a cold bucket is the chosen trade
                fn = lowered.compile()
                if self.metrics:
                    self.metrics.inc("xla_compiles_total")
                if key is not None:
                    self.aot_cache.store(key, fn)
            if self.metrics:
                self.metrics.inc(
                    "compile_seconds_total", time.perf_counter() - t0
                )
            if self.num_classes is None:
                out = jax.tree.leaves(lowered.out_info)[0]
                self.num_classes = int(out.shape[-1])
            self._compiled[bucket] = fn
        return fn

    def warmup(self) -> None:
        """Compile every bucket up front (misses counted; later traffic is
        then all cache hits — the zero-steady-state-recompile property)."""
        for b in self.buckets:
            self._executable(b)

    @property
    def compiled_buckets(self) -> tuple[int, ...]:
        return tuple(sorted(self._compiled))

    # ----------------------------------------------------------- inference
    def predict(self, images: np.ndarray) -> np.ndarray:
        """Logits for a [n, H, W, C] float batch (or one [H, W, C] image),
        any n >= 1. Pads to the bucket internally; returns exactly n rows of
        float32 logits — padded rows never leak (rows are independent under
        eval-mode BatchNorm, asserted in tests)."""
        x = np.asarray(images, np.float32)
        if x.ndim == len(self.input_shape):
            x = x[None]
        if x.ndim != len(self.input_shape) + 1 or x.shape[1:] != self.input_shape:
            raise ValueError(
                f"expected images of shape [n, {', '.join(map(str, self.input_shape))}]"
                f" (or one unbatched image), got {x.shape}"
            )
        n = x.shape[0]
        if n == 0:
            raise ValueError("empty batch")
        max_b = self.buckets[-1]
        outs = [
            self._predict_chunk(x[off : off + max_b])
            for off in range(0, n, max_b)
        ]
        return outs[0] if len(outs) == 1 else np.concatenate(outs)

    def _predict_chunk(self, chunk: np.ndarray) -> np.ndarray:
        k = chunk.shape[0]
        bucket = self.buckets[bisect.bisect_left(self.buckets, k)]
        if bucket > k:
            pad = np.zeros((bucket - k, *self.input_shape), np.float32)
            chunk = np.concatenate([chunk, pad])
            if self.metrics:
                self.metrics.inc("padded_rows_total", bucket - k)
        logits = self._executable(bucket)(self._variables, chunk)
        return np.asarray(jax.device_get(logits), np.float32)[:k]

    def info(self) -> dict:
        out = {
            "level": self.level,
            "density": round(float(self.density), 6),
            "backend": self.backend,
            "buckets": list(self.buckets),
            "compiled_buckets": list(self.compiled_buckets),
            "input_shape": list(self.input_shape),
            "num_classes": self.num_classes,
            "source": self.source,
        }
        if self.nm_plan_report is not None:
            out["nm"] = dict(self.nm_plan_report)
        if self.compaction is not None:
            out["compaction"] = {
                "params_before": self.compaction["params_before"],
                "params_after": self.compaction["params_after"],
                "channels_before": self.compaction["channels_before"],
                "channels_after": self.compaction["channels_after"],
                "compacted_spaces": self.compaction["compacted_spaces"],
            }
        # The planner's machine-readable routing table: why each eligible
        # layer (and the compaction stage) landed on its backend. JSON-safe
        # scalars only, so /info can ship it verbatim.
        out["plan"] = {
            "kind": self.plan.kind,
            "autotune": self.plan.report["autotune"],
            "decisions": self.plan.decisions,
        }
        return out

    # -------------------------------------------------------- construction
    @classmethod
    def from_experiment(
        cls,
        expt_dir: str | Path,
        *,
        level: Optional[int] = None,
        role: str = "",
        buckets: Sequence[int] = DEFAULT_BUCKETS,
        metrics=None,
        precision: Optional[str] = None,
        compact: bool = False,
        backend: Optional[str] = None,
        aot_cache=None,
    ) -> "InferenceEngine":
        """Build from an experiment directory written by the driver.

        ``level=None`` / ``level=-1`` serves the highest saved
        ``model_level_{L}``; ``role`` (e.g. ``model_init``) overrides level.
        ``precision`` overrides the experiment's training_precision for the
        serving forward (default: serve with the training dtype, which keeps
        served logits bit-identical to the harness evaluate forward)."""
        from ..harness.pruning_harness import PRECISION_DTYPES

        expt_dir = Path(expt_dir)
        cfg_path = expt_dir / "expt_config.yaml"
        if not cfg_path.exists():
            raise FileNotFoundError(
                f"{cfg_path} not found — is {expt_dir} an experiment dir "
                "written by run_experiment.py?"
            )
        cfg = config_from_dict(yaml.safe_load(cfg_path.read_text()))
        dp = cfg.dataset_params
        if dp.is_tokens:
            raise NotServable(
                f"{expt_dir} holds a language model "
                f"({cfg.model_params.model_name} on {dp.dataset_name}): the "
                "server classifies images and has no generation path (no "
                "cache, no sampling), so it does not serve this checkpoint"
            )
        dtype = PRECISION_DTYPES[
            precision or cfg.experiment_params.training_precision
        ]
        # Serving is single-device: ring (sequence-parallel) falls back to
        # the param-identical dense attention path.
        attention_impl = cfg.model_params.attention_impl
        if attention_impl == "ring":
            attention_impl = "dense"
        model = create_model(
            cfg.model_params.model_name,
            num_classes=dp.num_classes,
            dataset_name=dp.dataset_name,
            compute_dtype=dtype,
            attention_impl=attention_impl,
        )
        input_shape = (dp.image_size, dp.image_size, 3)
        variables = init_variables(
            # graftlint: disable=rng-key-reuse -- shape-only init: every initialized weight is overwritten by restore_model_tree below; the key value can never reach served outputs
            model, jax.random.PRNGKey(0), (1, *input_shape)
        )
        like = {
            "params": variables["params"],
            "masks": masking.make_masks(variables["params"]),
            "batch_stats": variables.get("batch_stats", {}),
        }
        ckpts = ExperimentCheckpoints(expt_dir)
        if role:
            path = ckpts.model_path(role)
            level = None
        else:
            if level is None or level < 0:
                saved = ckpts.saved_levels()
                if not saved:
                    raise FileNotFoundError(
                        f"no model_level_* checkpoints under "
                        f"{ckpts.checkpoints_dir}"
                    )
                level = saved[-1]
            path = ckpts.level_path(level)
        if not path.exists():
            raise FileNotFoundError(f"checkpoint {path} does not exist")
        restored = restore_model_tree(path, like)
        return cls(
            model,
            restored["params"],
            restored["masks"],
            restored["batch_stats"],
            input_shape=input_shape,
            buckets=buckets,
            metrics=metrics,
            level=level,
            source=str(path),
            compact=compact,
            backend=backend,
            aot_cache=aot_cache,
            # The experiment's planner knobs travel to serving: one config
            # surface for the routing thresholds (the compact commit rule
            # stays serving's own threshold-0 "any shrinkage pays").
            nm_min_axis_savings=cfg.planner.nm_min_axis_savings,
            autotune=cfg.planner.autotune,
            # Re-instantiate through create_model so the compacted/gathered
            # model gets the exact same stem/dtype/attention wiring.
            model_factory=lambda width_overrides=None, nm_overrides=None: (
                create_model(
                    cfg.model_params.model_name,
                    num_classes=dp.num_classes,
                    dataset_name=dp.dataset_name,
                    compute_dtype=dtype,
                    attention_impl=attention_impl,
                    width_overrides=width_overrides,
                    nm_overrides=nm_overrides,
                )
            ),
        )
