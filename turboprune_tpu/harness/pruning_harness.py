"""PruningHarness — the training runtime.

Rebuilds the reference harness stack (BaseHarness + PruningHarness,
/root/reference/harness_definitions/base_harness.py:32-305,
standard_pruning_harness.py:25-275) as one class around a jitted SPMD step:

  - model / loaders / mesh built from config (reference _create_model /
    _setup_dataloaders, standard_pruning_harness.py:128-157)
  - ``train_one_level(epochs_per_level, level)`` owns the inner loop:
    per-level optimizer + schedule re-init, level-0 init/rewind artifact
    saves, per-epoch train + test, CSV/rich metric logging
    (standard_pruning_harness.py:159-269)
  - the hot loop is ONE compiled program per step: forward (masked weights),
    backward, psum over the data mesh axis, optimizer update — where the
    reference had DDP allreduce + autocast + host-side scheduler.step()
    (base_harness.py:115-134,178-188)

Metric sums stay on device during an epoch (loss*n / correct / n) and are
pulled once at epoch end — the reference pays a host sync every step for
wandb lr logging (base_harness.py:129-130); here async dispatch runs free.

No per-level recompiles: the step function is cached by (total_steps) —
same epoch budget every level means the level-1 compile is reused for all
subsequent levels (SURVEY.md §7 "Recompile hazards").
"""

from __future__ import annotations

import time
from contextlib import contextmanager
from pathlib import Path
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..config.schema import MainConfig
from ..data import create_loaders
from ..models import create_model
from ..ops import masking
from ..parallel import (
    assemble_batch,
    assemble_chunk,
    assert_width_agreement,
    create_mesh,
    is_primary,
    epoch_sharding,
    make_sharded_eval_step,
    make_sharded_scan_chunk,
    make_sharded_scan_eval,
    make_sharded_train_step,
    replicate,
)
from ..train import (
    TrainState,
    create_optimizer,
    create_schedule,
    create_train_state,
    eval_params,
    make_eval_step,
    make_scan_chunk,
    make_scan_eval,
    make_train_step,
)
from ..utils import (
    MID_LEVEL,
    MODEL_INIT,
    MODEL_REWIND,
    OPTIMIZER_INIT,
    OPTIMIZER_REWIND,
    ExperimentCheckpoints,
    MetricsLogger,
    config_fingerprint,
    display_training_info,
    rewind_roles,
    tracing,
)
from ..utils.wandb_logging import WandbRun

PRECISION_DTYPES = {
    "bfloat16": jnp.bfloat16,
    "float16": jnp.float16,
    "float32": jnp.float32,
}


class _LevelSteps(NamedTuple):
    """Everything a level runs for one (model, epoch budget) pair."""

    tx: Any
    schedule: Callable
    train_step: Callable  # one batch: streamed loaders, a chunk's tail
    scan_chunk: Callable  # K stacked batches; a resident loader's whole epoch
    eval_step: Callable
    scan_eval: Callable  # the whole stacked test set


class PruningHarness:
    """Concrete trainer for one experiment (reference PruningHarness,
    standard_pruning_harness.py:25)."""

    def __init__(
        self,
        cfg: MainConfig,
        expt_dir: tuple[str, str],
        loaders: Optional[Any] = None,
        state: Optional[TrainState] = None,
    ):
        with tracing.span("harness/init"):
            self._build(cfg, expt_dir, loaders, state)

    def _build(self, cfg, expt_dir, loaders, state) -> None:
        self.cfg = cfg
        self.prefix, self.expt_dir = expt_dir
        ep = cfg.experiment_params
        self.compute_dtype = PRECISION_DTYPES[ep.training_precision]

        with tracing.span("init/mesh_model"):
            self.mesh = create_mesh(
                num_devices=ep.num_devices, model_parallelism=ep.model_parallelism
            )
            self.model = create_model(
                cfg.model_params.model_name,
                num_classes=cfg.dataset_params.num_classes,
                dataset_name=cfg.dataset_params.dataset_name,
                compute_dtype=self.compute_dtype,
                attention_impl=cfg.model_params.attention_impl,
                mesh=self.mesh,
                num_layers=cfg.model_params.num_hidden_layers,
                layer_pattern=cfg.model_params.layer_pattern,
                share=cfg.model_params.share,
            )
        with tracing.span("init/loaders"):  # synthetic data is made here
            self.loaders = loaders if loaders is not None else create_loaders(cfg)
            # What a step holds, where the loader says (data/tokens.py):
            # gauges, the set-up line and level_timing.csv (driver.run).
            self.data_gauges: dict[str, float] = dict(getattr(self.loaders, "gauges", {}))
            for name, value in self.data_gauges.items():
                tracing.gauge(name, value)
        data_size = self.mesh.shape["data"]
        per_host_batch = cfg.dataset_params.total_batch_size // max(
            jax.process_count(), 1
        )
        if per_host_batch % (data_size // max(jax.process_count(), 1) or 1):
            raise ValueError(
                f"per-host batch {per_host_batch} not divisible by local "
                f"data-axis size — adjust total_batch_size or num_devices"
            )
        pp = cfg.pruning_params
        self.ckpts = ExperimentCheckpoints(
            self.expt_dir, keep=rewind_roles(pp.training_type, pp.rewind_optimizer)
        )
        # Identity stamps for the mid-level slot: a slot whose config hash
        # disagrees with the live config is never restored (it holds
        # mid-trajectory state trained under different knobs).
        self.config_hash = config_fingerprint(cfg)
        self.run_id = Path(self.expt_dir).name if self.expt_dir else ""
        self.metrics = MetricsLogger(self.expt_dir, self.prefix)
        self.wandb = WandbRun(cfg, self.prefix, self.expt_dir)

        self.steps_per_epoch = len(self.loaders.train_loader)
        if ep.max_steps_per_epoch:
            self.steps_per_epoch = min(self.steps_per_epoch, ep.max_steps_per_epoch)

        with tracing.span("init/state"):
            if state is None:
                state = self._fresh_state()
            self.state = replicate(state, self.mesh)
        # What ``mask_count`` carries between two writes of ``state.masks``.
        self._mask_count: Optional[masking.MaskCount] = None

        with tracing.span("init/steps"):
            # The dense model's records, cached by total_steps so identical
            # level budgets reuse one executable; _steps is the live one
            # (a plan's while a planned level runs).
            self._steps = self._build_steps(self.model, ep.epochs_per_level)
            self._step_cache: dict[int, _LevelSteps] = {
                ep.epochs_per_level * self.steps_per_epoch: self._steps
            }
        self._eval_batches = None  # device-cached stacked test set
        # Opt-in compacted eval (experiment_params.compact_eval): compiled
        # eval steps cached by the compacted width signature — widths only
        # change when the masks do (once per level), so per-epoch evals
        # reuse one executable.
        self._plan_eval_cache: dict[tuple, Any] = {}
        self.last_compaction_report: Optional[dict] = None
        # Sparse-backend execution (experiment_params.compact_train and/or
        # nm_sparsity): at each level boundary ONE planner
        # (sparse/plan.py plan_execution) derives an ExecutionPlan from the
        # live masks — slice the whole train state onto a physically smaller
        # model where dead channels clear the savings threshold, gather the
        # surviving N:M-patterned contractions, and stay masked-dense where
        # neither pays. The per-plan step record is cached by
        # (total_steps, width signature, nm signature); _plan_ctx holds the
        # plan, the dense record + the full-coordinate anchor (compaction
        # only) while the level runs on it (None <=> training masked-dense).
        # Cache sizes and the last plan report are exported as tracing
        # gauges so the tests can read the shape the level ACTUALLY compiled.
        self._plan_step_cache: dict[tuple, _LevelSteps] = {}
        self._plan_ctx: Optional[dict] = None
        self.last_plan_report: Optional[dict] = None
        self.last_nm_report: Optional[dict] = None
        if ep.nm_sparsity:
            # Fail fast at harness construction: a contraction width that
            # does not divide into M-blocks would otherwise only surface at
            # the first prune step, a full level of training later.
            from ..config.schema import parse_nm
            from ..sparse.nm import check_divisibility

            _, m_block = parse_nm(ep.nm_sparsity)
            check_divisibility(self.state.masks, m_block)

    def _fresh_state(self) -> TrainState:
        """The seeded initial state (pretrained weights laid over it where
        the config names a checkpoint)."""
        cfg, ep = self.cfg, self.cfg.experiment_params
        input_shape, input_dtype = cfg.dataset_params.input_spec()
        # tx is rebuilt per level; init with a placeholder SGD so the
        # opt_state pytree has the final structure.
        tx0, _ = self._build_tx(epochs=ep.epochs_per_level)
        state = create_train_state(
            self.model,
            tx0,
            jax.random.PRNGKey(ep.seed),
            input_shape,
            input_dtype=input_dtype,
            # The image models initialise op by op (PERF.md: ``init/state``);
            # making theirs one program moves their cells' set-up and is not
            # this model's change to make.
            init_as_one_program=cfg.dataset_params.is_tokens,
        )
        if cfg.model_params.pretrained_path:
            # Warm-start ViT weights from a local timm checkpoint
            # (reference deit.py:82-89; models/pretrained.py). Applied to
            # the fresh init only — resume/level restores keep their own
            # weights — and before the level-0 MODEL_INIT save, so the
            # imp rewind target carries the pretrained weights.
            from ..models.pretrained import load_pretrained

            state = state.replace(
                params=load_pretrained(
                    cfg.model_params.pretrained_path, self.model, state.params
                )
            )
        return state

    # ------------------------------------------------------------------ tx
    def _build_tx(self, epochs: int):
        op = self.cfg.optimizer_params
        schedule = create_schedule(
            op.scheduler_type,
            base_lr=op.lr,
            epochs=epochs,
            steps_per_epoch=self.steps_per_epoch,
            warmup_fraction=op.warmup_fraction,
        )
        tx = create_optimizer(
            op.optimizer_name,
            schedule,
            momentum=op.momentum,
            weight_decay=op.weight_decay,
        )
        return tx, schedule

    def _build_steps(self, model, epochs: int, evals=None) -> _LevelSteps:
        """The one place a level's executables are made (jitted, so compiled
        only when first called). ``evals`` hands on the eval pair of another
        record of the same model: eval does not depend on the budget."""
        tx, schedule = self._build_tx(epochs)
        raw_step = make_train_step(model, tx, schedule)
        if evals is None:
            raw_eval = make_eval_step(model)
            evals = (
                make_sharded_eval_step(raw_eval, self.mesh),
                make_sharded_scan_eval(make_scan_eval(raw_eval), self.mesh),
            )
        return _LevelSteps(
            tx,
            schedule,
            make_sharded_train_step(raw_step, self.mesh),
            make_sharded_scan_chunk(make_scan_chunk(raw_step), self.mesh),
            *evals,
        )

    @property
    def _scan_eval(self) -> Callable:
        """The live scanned eval, under the name the benchmark's job reads."""
        return self._steps.scan_eval

    def setup_level(self, epochs: int) -> None:
        """Fresh optimizer + schedule for a level/cycle (reference
        _setup_optimizer/_setup_scheduler per level,
        standard_pruning_harness.py:174-175). Reuses the compiled step when
        the epoch budget (=> schedule constants) is unchanged."""
        total_steps = epochs * self.steps_per_epoch
        self._current_epochs = epochs  # compact path rebuilds the same tx
        if total_steps not in self._step_cache:
            # No plan is live here (_exit_plan runs in the level's finally).
            self._step_cache[total_steps] = self._build_steps(
                self.model,
                epochs,
                evals=(self._steps.eval_step, self._steps.scan_eval),
            )
        self._steps = self._step_cache[total_steps]
        self.state = replicate(
            self.state.replace(
                step=jnp.zeros((), jnp.int32),
                opt_state=self._steps.tx.init(self.state.params),
            ),
            self.mesh,
        )

    def maybe_rewind_optimizer(self, level: int) -> None:
        """WR + ``rewind_optimizer``: restore the momentum buffers captured
        at rewind_epoch (the reference's unrealized intent — dead
        reset_optimizer, harness_utils.py:24-46). The schedule still restarts
        from step 0 (per-level fresh scheduler, like the reference): the
        restored ScaleByScheduleState (schedule position captured at
        rewind_epoch) is swapped for the fresh level-start one so the
        schedule is not fast-forwarded. ONLY the schedule state is reset —
        e.g. AdamW's ScaleByAdamState.count drives bias correction for the
        restored moments and must come back with them."""
        import optax

        pp = self.cfg.pruning_params
        if level > 0 and pp.training_type == "wr" and pp.rewind_optimizer:
            fresh = self.state.opt_state
            opt = self.ckpts.rewind_optimizer(fresh)  # the resident host tree
            is_sched = lambda x: isinstance(x, optax.ScaleByScheduleState)
            opt = jax.tree.map(
                lambda r, f: f if is_sched(r) else r, opt, fresh, is_leaf=is_sched
            )
            self.state = replicate(self.state.replace(opt_state=opt), self.mesh)

    # --------------------------------------------------------------- loops
    def train_epoch(self) -> dict:
        """One pass over the train loader (reference train_epoch,
        base_harness.py:151-202). Returns host-side epoch means.

        Fast path: device-resident loaders expose ``epoch_arrays`` and the
        whole epoch runs as ONE lax.scan program (make_scan_chunk over all
        of its steps) — no per-step host dispatch at all. Streaming loaders
        (grain/tpk) take the chunked-scan path when
        ``dataset_params.scan_chunk_steps > 1`` (K batches per compiled
        dispatch) and the per-batch path otherwise."""
        t0 = time.perf_counter()
        if (
            hasattr(self.loaders.train_loader, "epoch_arrays")
            and not self.cfg.experiment_params.max_steps_per_epoch
        ):
            with tracing.span("epoch/feed"):
                batches = jax.device_put(
                    self.loaders.train_loader.epoch_arrays(),
                    epoch_sharding(self.mesh),
                )
            with tracing.span("epoch/train"):
                self.state, sums = self._steps.scan_chunk(self.state, batches)
                # The epoch is dispatched and the host waits for it: room
                # for the write of the level before (utils/checkpoint.py).
                self.ckpts.start_write()
                sums = jax.device_get(sums)
        else:
            with tracing.span("epoch/train"):  # one span, none per batch
                sums = self._stream_epoch()
                self.ckpts.start_write()
                sums = jax.device_get(sums)
        wall = time.perf_counter() - t0
        n = float(sums["count"])
        return {
            "train_loss": float(sums["loss_sum"]) / n,
            "train_acc": 100.0 * float(sums["correct"]) / n,
            "epoch_seconds": wall,
            "samples_per_sec": n / wall,
            # What the model's layers counted over the epoch (train/steps.py),
            # come with the same fetch; a model without counters adds nothing.
            **{k: int(sums[k]) for k in getattr(self.model, "counters", ())},
        }

    def _stream_epoch(self):
        """The streamed epoch's dispatch loop; returns the device-side sums."""
        sums = None
        train_loader = self.loaders.train_loader
        train_scope = getattr(train_loader, "batch_scope", "global")
        chunk_steps = self.cfg.dataset_params.scan_chunk_steps
        if chunk_steps > 1 and hasattr(train_loader, "iter_chunks"):
            # Chunked-scan streamed path: the pipeline engine stacks K
            # prefetched batches ([K, B, ...]) and each full chunk runs as
            # ONE compiled lax.scan dispatch while the engine refills
            # behind it; a sub-K tail (epoch length % K) arrives as plain
            # per-step batches so only two executables ever compile.
            for batch in train_loader.iter_chunks(
                chunk_steps, max_batches=self.steps_per_epoch
            ):
                if batch[0].ndim == 5:
                    cb = assemble_chunk(batch, self.mesh, train_scope)
                    self.state, m = self._steps.scan_chunk(self.state, cb)
                else:
                    b = assemble_batch(batch, self.mesh, train_scope)
                    self.state, m = self._steps.train_step(self.state, b)
                    m = {k: v for k, v in m.items() if k != "lr"}
                sums = m if sums is None else jax.tree.map(jnp.add, sums, m)
        else:
            for i, batch in enumerate(train_loader):
                if i >= self.steps_per_epoch:
                    break
                batch = assemble_batch(batch, self.mesh, train_scope)
                self.state, m = self._steps.train_step(self.state, batch)
                m = {k: v for k, v in m.items() if k != "lr"}
                sums = m if sums is None else jax.tree.map(jnp.add, sums, m)
        if sums is None:
            raise RuntimeError(
                "train loader yielded no batches — dataset smaller than "
                "total_batch_size with drop_last?"
            )
        return sums

    def evaluate(self) -> dict:
        """Full test pass (reference test, base_harness.py:204-245). For
        schedule-free optimizers this evaluates the averaged weights.

        With ``experiment_params.compact_eval`` the pass runs on the
        dead-channel-COMPACTED model instead (sparse/) — numerically
        equivalent up to fp reassociation, and the per-level size report
        lands on ``last_compaction_report``."""
        ev_state = self.state
        if self.cfg.optimizer_params.optimizer_name == "ScheduleFreeSGD":
            ev_state = ev_state.replace(
                params=eval_params(ev_state.opt_state, ev_state.params)
            )
        if self.cfg.experiment_params.compact_eval and self._plan_ctx is None:
            # With an ExecutionPlan live the state/step functions already run
            # the planned shape — compact: the state is small and eval_step
            # is the small model's (re-compacting sliced params against the
            # full model's graph would be wrong); N:M: eval_step already
            # runs the gathered reduced-width path. Either way that IS the
            # level's compact eval.
            return self._evaluate_compacted(ev_state)
        test_loader = self.loaders.test_loader
        if hasattr(test_loader, "eval_epoch_arrays"):
            # Device-resident eval: the padded stacked test set is cached in
            # HBM once and the whole pass runs as ONE lax.scan program —
            # matching the train scan path's zero-dispatch hot loop.
            if self._eval_batches is None:
                self._eval_batches = jax.device_put(
                    test_loader.eval_epoch_arrays(), epoch_sharding(self.mesh)
                )
            sums = jax.device_get(self._steps.scan_eval(ev_state, self._eval_batches))
        else:
            sums = None
            test_scope = getattr(test_loader, "batch_scope", "global")
            for batch in test_loader:
                batch = assemble_batch(batch, self.mesh, test_scope)
                m = self._steps.eval_step(ev_state, batch)
                sums = m if sums is None else jax.tree.map(jnp.add, sums, m)
            if sums is None:
                raise RuntimeError("test loader yielded no batches")
            sums = jax.device_get(sums)
        n = float(sums["count"])
        return {
            "test_loss": float(sums["loss_sum"]) / n,
            "test_acc": 100.0 * float(sums["correct"]) / n,
        }

    def _evaluate_compacted(self, ev_state) -> dict:
        """Test pass on the physically compacted model (sparse/compact.py).

        The current state's masks are analyzed on the host, dead channels
        are sliced out, and the small model evaluates the same test set.
        Single-program (no mesh step): eval batches are replicated-small
        and the compacted executable is cached per width signature, so
        within a level every epoch reuses one compile. Ring attention falls
        back to its param-identical dense equivalent (as in serving)."""
        from ..sparse import build_graph, compact_params
        from ..train.state import TrainState

        graph = build_graph(self.model, ev_state.params)
        res = compact_params(
            ev_state.params, ev_state.masks, graph, ev_state.batch_stats
        )
        self.last_compaction_report = res.report
        key = res.as_override_tuple()
        if key not in self._plan_eval_cache:
            self._evict_stale_plan_caches(key)
            self._plan_eval_cache[key] = jax.jit(
                make_eval_step(self._small_model(res.width_overrides))
            )
            self._export_cache_gauges()
        step = self._plan_eval_cache[key]
        # make_eval_step multiplies masks into params; all-ones masks on
        # the compacted (already folded) params make that an exact no-op,
        # so the metric/padding semantics are shared with the dense path.
        small_state = TrainState(
            step=jnp.zeros((), jnp.int32),
            params=res.params,
            masks=masking.make_masks(res.params),
            batch_stats=res.batch_stats,
            opt_state=(),
            rng=jnp.zeros((), jnp.uint32),  # unused in eval
        )
        sums = None
        for batch in self.loaders.test_loader:
            m = step(small_state, batch)
            sums = m if sums is None else jax.tree.map(jnp.add, sums, m)
        if sums is None:
            raise RuntimeError("test loader yielded no batches")
        sums = jax.device_get(sums)
        n = float(sums["count"])
        return {
            "test_loss": float(sums["loss_sum"]) / n,
            "test_acc": 100.0 * float(sums["correct"]) / n,
        }

    # ----------------------------------------------------- plan execution
    def _small_model(self, width_overrides, nm_overrides=None):
        """Re-instantiate the architecture at compacted widths and/or with
        gathered N:M hooks. Ring attention falls back to its param-identical
        dense equivalent (as in serving): the small model is replicated, not
        sequence-sharded."""
        attention_impl = self.cfg.model_params.attention_impl
        if attention_impl == "ring":
            attention_impl = "dense"
        return create_model(
            self.cfg.model_params.model_name,
            num_classes=self.cfg.dataset_params.num_classes,
            dataset_name=self.cfg.dataset_params.dataset_name,
            compute_dtype=self.compute_dtype,
            attention_impl=attention_impl,
            mesh=self.mesh,
            width_overrides=width_overrides,
            nm_overrides=nm_overrides,
            num_layers=self.cfg.model_params.num_hidden_layers,
            layer_pattern=self.cfg.model_params.layer_pattern,
            share=self.cfg.model_params.share,
        )

    def _enter_plan(self) -> None:
        """Derive this level's ExecutionPlan from the live masks and swap
        the step record onto it (sparse/plan.py plan_execution — the ONE
        producer of backend decisions).

        The planner decides everything the old compact-then-nm enter pair
        decided, in one place: slice the whole train state onto a
        physically smaller model when dead-channel savings clear
        ``planner.compact_min_savings``, gather the surviving N:M-patterned
        contractions, stay masked-dense where neither pays. When compaction
        commits, the FULL state at entry is kept as the anchor: at exit
        (and for any checkpoint written mid-level) the trained small state
        is scattered back over it, so removed coordinates — including
        consumer in-rows of dead channels, whose real magnitudes the next
        level's GLOBAL threshold must still see — come back exactly as the
        dense run would have left them (exact for weight_decay=0 with the
        per-level fresh optimizer; a removed coordinate then sees zero
        gradient and zero momentum, i.e. it never moves). The N:M half is a
        function swap at the planned shapes — no state transformation.

        The plan is a pure function of the replicated masks + model family
        (mask agreement across hosts is asserted once per level by
        driver.prune_level's exact check_state_equality), so every process
        derives the identical plan without a collective; when compact_train
        is enabled the width signature is still barriered below because
        committing changes which jittable program runs.
        """
        ep = self.cfg.experiment_params
        if self._plan_ctx is not None:
            return
        compact_mode = "auto" if ep.compact_train else "off"
        nm_mode = "auto" if ep.nm_sparsity else "off"
        if compact_mode == "off" and nm_mode == "off":
            return
        from ..sparse import plan_execution, width_signature
        from ..sparse.plan import report_gauges

        pl = self.cfg.planner
        plan = plan_execution(
            self.model,
            self.state.params,
            self.state.masks,
            self.state.batch_stats,
            model_factory=self._small_model,
            compact=compact_mode,
            nm=nm_mode,
            compact_min_savings=pl.compact_min_savings,
            nm_min_axis_savings=pl.nm_min_axis_savings,
            autotune=pl.autotune,
        )
        if ep.compact_train:
            # Collective — every process must reach this call, with its
            # decision (including a planner decline or CompactionError)
            # encoded in the signature; skipping it on one host would
            # deadlock the others inside the allgather.
            if plan.compaction is not None:
                sig = {
                    "commit": True,
                    "widths": width_signature(plan.compaction),
                }
            else:
                sig = {
                    "commit": False,
                    "reason": plan.report["compaction"]["reason"],
                }
            assert_width_agreement(sig)
        self.last_plan_report = plan.report
        if plan.report["nm"] is not None:
            self.last_nm_report = plan.report["nm"]
        for name, value in report_gauges(plan.report).items():
            tracing.gauge(name, value)
        if plan.kind == "masked":
            # Neither backend pays at this level: keep the dense record.
            return
        if plan.compaction is not None:
            self.last_compaction_report = plan.compaction.report

        total_steps = self._current_epochs * self.steps_per_epoch
        width_key, nm_key = plan.width_key(), plan.nm_key()
        key = (total_steps, width_key, nm_key)
        self._evict_stale_plan_caches(width_key, nm_key)
        if key not in self._plan_step_cache:
            exec_model = self._small_model(
                plan.width_overrides, nm_overrides=plan.nm_overrides
            )
            self._plan_step_cache[key] = self._build_steps(
                exec_model, self._current_epochs
            )
        self._export_cache_gauges()
        self._plan_ctx = {
            "plan": plan,
            "anchor": self.state if plan.compaction is not None else None,
            "dense_steps": self._steps,
        }
        self._steps = self._plan_step_cache[key]
        if plan.compaction is not None:
            from ..sparse import compact_train_state

            self.state = replicate(
                compact_train_state(self.state, plan.compaction), self.mesh
            )
        if is_primary():
            r = plan.report
            parts = []
            comp = r["compaction"]
            if plan.compaction is not None:
                parts.append(
                    f"params {comp['params_before']:,} -> "
                    f"{comp['params_after']:,} "
                    f"({r['backend_counts']['compact_spaces']} spaces)"
                )
            if plan.nm is not None:
                parts.append(
                    f"{r['backend_counts']['nm_layers']} layers gathered "
                    f"(coverage {r['coverage_frac']:.2f})"
                )
            print(
                f"[plan] level runs {plan.kind}: " + ", ".join(parts),
                flush=True,
            )

    def _exit_plan(self) -> None:
        """Expand back to full coordinates (when the plan compacted) and
        restore the masked-dense step record. Idempotent; called in a
        finally so a raising epoch can't leave the harness stuck on a
        plan's shapes (the driver's save_level/prune always see full
        coordinates)."""
        if self._plan_ctx is None:
            return
        ctx = self._plan_ctx
        self._plan_ctx = None
        self._steps = ctx["dense_steps"]
        plan = ctx["plan"]
        if plan.compaction is not None:
            from ..sparse import expand_train_state

            self.state = replicate(
                expand_train_state(
                    self.state, plan.compaction, anchor=ctx["anchor"]
                ),
                self.mesh,
            )

    def _full_state(self) -> TrainState:
        """The live state in FULL coordinates — what every checkpoint
        (rewind artifacts, mid-level slots) must hold so restores never
        learn the level ran small."""
        ctx = self._plan_ctx
        if ctx is None or ctx["plan"].compaction is None:
            return self.state
        from ..sparse import expand_train_state

        return expand_train_state(
            self.state, ctx["plan"].compaction, anchor=ctx["anchor"]
        )

    def _full_masks(self):
        """Full-coordinate masks for metric rows. Masks never change inside
        a level, so while compacted the anchor's tree IS the current one."""
        ctx = self._plan_ctx
        if ctx is None or ctx["plan"].compaction is None:
            return self.state.masks
        return ctx["anchor"].masks

    def mask_count(self) -> masking.MaskCount:
        """The zeros and size of the current masks, full coordinates. The
        device is read once after a write of ``state.masks`` (the prune, a
        restore, construction) and the numbers are carried until the next:
        set-up, every epoch's row, the level's summary and the driver take
        them from here and reach no device. The state is donated to every
        epoch's program, so no array's identity could say the masks are the
        same ones: their writers say so, through ``masks_written``."""
        if self._mask_count is None:
            self._mask_count = masking.count_masks(self._full_masks())
        return self._mask_count

    def masks_written(self) -> None:
        """For whoever has just put other masks into ``state``."""
        self._mask_count = None

    def _evict_stale_plan_caches(
        self, width_key: tuple, nm_key: Optional[tuple] = None
    ) -> None:
        """The ladder only descends — executables compiled for an older
        (wider, or differently-indexed) plan signature can never be hit
        again and would pin dead HLO + donated buffers for the rest of the
        run. ``nm_key=None`` (the compact-eval path) evicts on widths
        only."""
        for k in [
            k
            for k in self._plan_step_cache
            if k[1] != width_key or (nm_key is not None and k[2] != nm_key)
        ]:
            del self._plan_step_cache[k]
        for k in [k for k in self._plan_eval_cache if k != width_key]:
            del self._plan_eval_cache[k]

    def _export_cache_gauges(self) -> None:
        tracing.gauge("plan_step_cache_size", len(self._plan_step_cache))
        tracing.gauge("plan_eval_cache_size", len(self._plan_eval_cache))

    # --------------------------------------------------------------- level
    def train_one_level(self, epochs_per_level: int, level: int) -> dict:
        """Train one sparsity level (reference train_one_level,
        standard_pruning_harness.py:159-269)."""
        ckpt_every = self.cfg.experiment_params.checkpoint_every_epochs
        max_test_acc = 0.0
        start_epoch = 0
        with tracing.span("level/setup"):
            self.setup_level(epochs_per_level)
            self.maybe_rewind_optimizer(level)
            density = self.mask_count().density
            display_training_info(self.cfg, level, density)

            if level == 0:
                # Level-0 artifacts: starting weights + optimizer (imp rewind
                # target; standard_pruning_harness.py:190-199).
                self.ckpts.save_model(MODEL_INIT, self.state)
                self.ckpts.save_optimizer(OPTIMIZER_INIT, self.state.opt_state)

            mid = self.ckpts.peek_mid_level() if ckpt_every else None
            if mid and mid.get("config_hash") != self.config_hash:
                # Identity mismatch (or a pre-stamp slot of unknown provenance):
                # the slot holds mid-trajectory state trained under a DIFFERENT
                # config (lr, epoch budget, loader type, ...) — restoring it
                # would silently continue the wrong trajectory. Refuse and
                # replay the level from its start.
                if is_primary():
                    print(
                        "[resume] REFUSING mid-level restore: slot config hash "
                        f"{mid.get('config_hash')!r} != current "
                        f"{self.config_hash!r} (run {mid.get('run_id')!r}) — "
                        "the config changed since the slot was written; "
                        "replaying the level from its start",
                        flush=True,
                    )
                self.ckpts.clear_mid_level()
            elif mid and mid["level"] != level:
                # Levels run in ascending order, so a slot for a different level
                # is always from an abandoned trajectory (e.g. resumed BELOW a
                # preempted level) — drop it before it can hijack a later
                # re-run of its level.
                self.ckpts.clear_mid_level()
            elif mid:
                # Epoch-granular re-entry (beyond-reference; checkpoint.py
                # MID_LEVEL): restore the FULL state — opt_state and step come
                # back mid-schedule — and fast-forward the train loader's epoch
                # counter so the per-epoch shuffle/augment PRNG stream continues
                # exactly where the interrupted run left it (bit-identical to an
                # uninterrupted run; asserted in tests/test_harness.py).
                restored = self.ckpts.load_mid_level(
                    self.state, expect_level=level, expect_epoch=mid["epoch"]
                )
                if restored is None:
                    # Torn save (header and state tree from different saves):
                    # replay the level from its start instead of mixing them.
                    if is_primary():
                        print(
                            "[resume] mid-level slot is torn (header/state "
                            "disagree) — replaying the level",
                            flush=True,
                        )
                    self.ckpts.clear_mid_level()
                else:
                    self.state = replicate(
                        self.state.replace(**restored), self.mesh
                    )
                    self.masks_written()
                    start_epoch = mid["epoch"] + 1
                    max_test_acc = mid.get("max_test_acc", 0.0)
                    # Pre-preemption epoch rows ride in the header so the level
                    # CSV and the summary's max_test_acc cover the WHOLE level,
                    # not just the post-resume epochs.
                    self.metrics.level_rows = [
                        dict(r) for r in mid.get("level_rows", [])
                    ]
                    self._restore_train_stream(mid, level)
                    if is_primary():
                        print(
                            f"[resume] mid-level checkpoint: re-entering level "
                            f"{level} at epoch {start_epoch}",
                            flush=True,
                        )
            # After any mid-level restore, so the anchor is the true
            # level-start full state (post-rewind, post-resume) and a resumed
            # level re-derives its ExecutionPlan from the restored full
            # coordinates. The epoch loop reads no masks: a slot's are counted
            # here, before a plan compacts them.
            self.mask_count()
            self._enter_plan()
        if level == 1:
            tracing.stop_profile()  # driver.run's session over the 0 -> 1 boundary
        try:
            for epoch in range(start_epoch, epochs_per_level):
                with self._epoch_scope(level, epoch):
                    max_test_acc = self._train_eval_log(
                        {"level": level, "epoch": epoch}, max_test_acc
                    )
                    if level == 0:
                        self._maybe_save_rewind_point(epoch)
                    if (
                        ckpt_every
                        and (epoch + 1) % ckpt_every == 0
                        and epoch + 1 < epochs_per_level  # last epoch -> level ckpt
                    ):
                        with tracing.span("epoch/ckpt"):
                            meta = {
                                "max_test_acc": max_test_acc,
                                # Slot identity (ADVICE r5): the restore path refuses
                                # a slot whose config hash disagrees with the live
                                # run.
                                "config_hash": self.config_hash,
                                "run_id": self.run_id,
                                "train_loader_epoch": getattr(
                                    self.loaders.train_loader, "epoch", 0
                                ),
                                # So the level CSV / summary survive the preemption
                                # (rows are plain float/int dicts — JSON-safe).
                                "level_rows": self.metrics.level_rows,
                            }
                            get_stream = getattr(
                                self.loaders.train_loader, "get_stream_state", None
                            )
                            if get_stream is not None:
                                stream = get_stream()
                                if stream is not None:
                                    # EVERY host writes its own blob (its own shard
                                    # position) — a shared primary-only header would
                                    # hand all hosts the primary's position.
                                    self.ckpts.save_mid_level_stream(
                                        level, epoch, stream, jax.process_index()
                                    )
                                    meta["train_loader_stream_hosts"] = (
                                        jax.process_count()
                                    )
                            self.ckpts.save_mid_level(
                                level, epoch, self._full_state(), meta=meta
                            )
        finally:
            self._exit_plan()

        with tracing.span("level/finish"):
            return self.metrics.finish_level(
                level,
                {
                    "density": density,
                    "final_sparsity": self.mask_count().sparsity,
                },
            )

    @contextmanager
    def _epoch_scope(self, level: int, epoch: int, **attrs):
        """One iteration of the epoch loop as the ``epoch`` span. With
        ``profile_dir`` set, the second epoch of level 0 (the first is
        compile-polluted) runs under a profiler session that covers the
        whole iteration: train, eval and logging."""
        profile_dir = self.cfg.experiment_params.profile_dir
        profiled = bool(profile_dir) and level == 0 and epoch == 1 and not attrs.get("cycle")
        if profiled:
            tracing.start_profile(Path(profile_dir) / "level0_epoch1")
        try:
            with tracing.span("epoch", epoch=epoch, **attrs):
                yield
            if not self._said_first_epoch:
                self._say_first_epoch()
        finally:
            if profiled:
                tracing.stop_profile()

    _said_first_epoch = False

    def _say_first_epoch(self) -> None:
        """Once a harness, where its first epoch has closed: the operator's
        line from process start to here. A run that dies in a long level 0
        has said where its set-up went, and so has one that never ends."""
        self._said_first_epoch = True
        roots = tracing.first_epoch_roots()
        if roots and is_primary():
            # The step program has been traced: what the code that built it
            # set while it was (what its backward pass keeps, ops/remat.py;
            # which form its kernels took; its loss's blocks, train/steps.py).
            built = tracing.trace_gauges()
            print(tracing.line("start to first epoch", tracing.breakdown(roots), built), flush=True)

    def _train_eval_log(self, row: dict, max_test_acc: float) -> float:
        """Train one epoch, evaluate, and log ``row`` (which already names
        the level, the epoch and, cyclic, the cycle); returns the level's
        best test accuracy so far."""
        row.update(self.train_epoch())
        with tracing.span("epoch/eval"):
            row.update(self.evaluate())
        with tracing.span("epoch/log"):
            max_test_acc = max(max_test_acc, row["test_acc"])
            row["max_test_acc"] = max_test_acc
            row["sparsity"] = self.mask_count().sparsity
            self.metrics.log_epoch(row)
            self.wandb.log(row)
            self._log_console(row)
        return max_test_acc

    def _maybe_save_rewind_point(self, epoch: int) -> None:
        """Weight-rewinding snapshot at ``rewind_epoch`` of level 0
        (standard_pruning_harness.py:212-223). Full coordinates — the rewind
        target must not depend on whether this level ran compacted."""
        rewind_epoch = self.cfg.pruning_params.rewind_epoch
        if rewind_epoch is not None and epoch == rewind_epoch:
            with tracing.span("epoch/ckpt"):
                full = self._full_state()
                self.ckpts.save_model(MODEL_REWIND, full)
                self.ckpts.save_optimizer(OPTIMIZER_REWIND, full.opt_state)

    def epochs_in_level(self) -> int:
        """The passes over the train loader that one level makes."""
        return self.cfg.experiment_params.epochs_per_level

    def resume_data_order(self, level: int) -> None:
        """Level-granular resume: the train loader goes on where the levels
        before ``level`` of a continuous run would have left it, so the
        resumed level sees that run's shuffle and augmentation (tier 2
        below; tier 3's warning for loaders that cannot)."""
        self._restore_train_stream(
            {"train_loader_epoch": level * self.epochs_in_level()}, level
        )

    def _restore_train_stream(self, mid: dict, level: int) -> None:
        """Restore the train loader's data-order state on mid-level resume.

        Three tiers, degrading gracefully (never crashing the resume):
        1. Stream-position loaders (grain): per-host tagged blob written by
           save_mid_level_stream — each host restores ITS OWN shard
           position. Missing/mistagged blob, changed host count, or a
           loader that rejects the state (e.g. num_workers changed) falls
           through to tier 3 with a warning.
        2. (seed, epoch)-stateless loaders (device/tpk/synthetic): the
           epoch counter IS the state; restoring it is bit-exact.
        3. Fallback: fresh shuffle pass — statistically equivalent, loudly
           not bit-identical."""
        train_loader = self.loaders.train_loader
        epoch = mid["train_loader_epoch"]
        if mid.get("train_loader_stream_hosts") and hasattr(
            train_loader, "set_stream_state"
        ):
            blob = None
            if mid["train_loader_stream_hosts"] == jax.process_count():
                blob = self.ckpts.load_mid_level_stream(
                    level, mid["epoch"], jax.process_index()
                )
            if blob is not None:
                try:
                    train_loader.set_stream_state(blob)
                    if hasattr(train_loader, "epoch"):
                        train_loader.epoch = epoch
                    return
                except Exception as e:  # incompatible state: degrade, don't die
                    if is_primary():
                        print(
                            f"[resume] stream state rejected ({e!r:.200}); "
                            "falling back to a fresh shuffle pass",
                            flush=True,
                        )
            elif is_primary():
                print(
                    "[resume] stream-state blob missing or from a different "
                    "save/host-count; falling back to a fresh shuffle pass",
                    flush=True,
                )
        elif getattr(train_loader, "resumable_epochs", True) and hasattr(
            train_loader, "epoch"
        ):
            train_loader.epoch = epoch
            return
        if is_primary():
            print(
                "[resume] WARNING: the resumed run sees a fresh shuffle "
                "pass — statistically equivalent, NOT bit-identical to an "
                "uninterrupted run",
                flush=True,
            )

    def _log_console(self, row: dict) -> None:
        # ``samples_per_sec`` counts what the step's ``count`` counts: images,
        # or a token dataset's target tokens.
        unit = "tok/s" if self.cfg.dataset_params.is_tokens else "img/s"
        print(
            f"[L{row['level']:>2} E{row['epoch']:>3}] "
            f"train {row['train_loss']:.4f}/{row['train_acc']:5.2f}% "
            f"test {row['test_loss']:.4f}/{row['test_acc']:5.2f}% "
            f"(best {row['max_test_acc']:5.2f}%) "
            f"sparsity {row['sparsity']:5.2f}% "
            f"{row['samples_per_sec']:,.0f} {unit}"
            + "".join(f" {k} {row[k]:,}" for k in getattr(self.model, "counters", ()) if k in row),
            flush=True,
        )
