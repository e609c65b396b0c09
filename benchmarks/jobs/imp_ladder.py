"""The job both training cells run: an IMP ladder through the program's own
entry point. A ladder whose window closes inside level 0 is a dense level.

``run_experiment.main`` is called with the configuration's overrides, so the
level loop (``driver.run``), the harness, the loader and the compile-cache
placement are the program's. The job patches in an observing subclass of
``PruningHarness`` and wrappers around ``driver.prune_level`` and the level
checkpoints; they stamp spans and change nothing the run computes.

The cell's file says in which unit the window is cut: ``"unit": "epoch"``
(boundaries are entries to ``train_epoch``) or ``"unit": "level"`` (boundaries
are returns of ``save_level``). The window opens once ``warmup`` units have
run and closes at the first boundary at or after ``--seconds``, where the job
leaves ``driver.run`` by raising ``WindowClosed``. ``correct`` is decided after
that, outside the window and outside ``setup_s``.

``correct`` follows the program's training, not only its forward pass: the
job keeps the state with which one epoch of set-up entered the program's
compiled epoch (``follow_epoch``, a call of ``train_epoch`` before the window
opens; the window drives that same compiled object) and the state that came
out. After the window, where the cell's limits name ``update_norm_gap``, the
plain float32 SGD of ``reference/sgd.py`` takes the same steps on the same
batches; where they name ``masked_update_gap``, the weights the masks hold at
zero are held to the optimizer's own arithmetic. PERF.md section 2 says what
each can and cannot catch.

Every level's prune is a program of its own: ``lax.top_k`` takes the number of
weights kept as a static size, and on a v5e each new size compiles for about
25 s. Nothing may compile inside the window, so in a cell that says
``"warm_prunes": "window"`` the job, as the last thing before the window
opens, runs the program's own ``prune_the_model`` for the levels the window
can reach and throws the masks away: the compilations are then in the process,
and in the persistent cache for the next run. How many levels that is it works
out from what it has just measured, the seconds of the last warm-up level
without its own prune: one and a half times as many levels as ``--seconds``
holds at that pace, so
there is no count for a faster program to outrun; a window that prunes a level
past the warmed ones ends the run with an error. The cost shows in ``setup_s``
and ``compile_s``.
"""

from __future__ import annotations

import math
import time
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import correct, model_flops
from benchmarks.observe import memory_stats
from benchmarks.reference import sgd
from benchmarks.trace_reduce import SPAN_PREFIX, TRACED_SPAN

# Images of the eval set that go through the program's eval program one by one.
SAMPLE_IMAGES = 64
# The eval loss gap is a share of the reference's loss where that is over
# this, and otherwise the plain difference: a ResNet18 that has fitted the
# synthetic classes has a loss of 1e-5, and a share of that swings with nothing.
LOSS_FLOOR = 1.0


class WindowClosed(Exception):
    """Raised at the boundary that ends the measured window."""


class Window:
    """Cuts the run at boundaries of the cell's unit."""

    def __init__(self, ctx, unit: str, open_at: int, trace_units: int):
        self.ctx, self.unit, self.open_at = ctx, unit, open_at
        self.trace_units = trace_units
        self.seen = 0
        self.last_unit_s = None  # seconds between the last two boundaries
        self._last_at = None
        self.opened_at = None
        self.closed_at = None
        self.boundaries: list[float] = []  # of the window, the opening one first
        self.on_open = lambda: None
        self._tracing = False
        self._traced = None

    def boundary(self, unit: str) -> None:
        if unit != self.unit:
            return
        n, self.seen = self.seen, self.seen + 1
        now = time.perf_counter()
        if self._last_at is not None:
            self.last_unit_s = now - self._last_at
        self._last_at = now
        if n < self.open_at:
            return
        if n == self.open_at:
            self.on_open()
            self.opened_at = time.perf_counter()
            self.boundaries.append(self.opened_at)
            return
        now = time.perf_counter()
        self.boundaries.append(now)
        done = len(self.boundaries) - 1
        if self.ctx.trace:
            # The profiler starts after the window's first unit and runs one
            # unit before the traced stretch opens: starting it is slow, part
            # of that lands in the unit that follows, and the stretch should
            # be steady state.
            if done == 1:
                self._start_trace()
            elif done == 2:
                self._traced = jax.profiler.TraceAnnotation(SPAN_PREFIX + TRACED_SPAN)
                self._traced.__enter__()
            elif done == 2 + self.trace_units:
                self.stop_trace()
        if now - self.opened_at >= self.ctx.seconds:
            self.closed_at = now
            raise WindowClosed()

    def _start_trace(self) -> None:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(self.ctx.trace_dir), profiler_options=options)
        self._tracing = True

    def stop_trace(self) -> None:
        if self._traced is not None:
            self._traced.__exit__(None, None, None)
            self._traced = None
        if self._tracing:
            self._tracing = False
            jax.profiler.stop_trace()


def _observed_harness(ctx, window: Window, rec: dict):
    from turboprune_tpu.harness import PruningHarness

    spans = ctx.spans

    class Observed(PruningHarness):
        def __init__(self, *args, **kwargs):
            with spans.span("harness_init"):
                super().__init__(*args, **kwargs)
            rec["harness"] = self
            self._epochs_begun = 0
            for name in ("save_level", "load_level"):
                setattr(self.ckpts, name, self._spanned(name, getattr(self.ckpts, name)))

        @staticmethod
        def _spanned(name, fn):
            def call(level, state):
                with spans.span(name, level=level):
                    out = fn(level, state)
                if name == "save_level":
                    window.boundary("level")
                return out

            return call

        def train_epoch(self):
            window.boundary("epoch")
            n, self._epochs_begun = self._epochs_begun, self._epochs_begun + 1
            if n == int(ctx.cell["params"]["follow_epoch"]):
                if window.opened_at is not None:
                    raise RuntimeError("follow_epoch lies inside the window")
                return self._followed_epoch()
            return self._train_epoch()

        def _followed_epoch(self):
            """The epoch the reference follows: what went into the program's
            compiled epoch and what came out, copied to the host."""
            state, loader = self.state, self.loaders.train_loader
            kept = {
                "params": jax.device_get(state.params),
                "buf": jax.device_get(_momentum(state.opt_state)),
                "masks": jax.device_get(state.masks),
                "batch_stats": jax.device_get(state.batch_stats),
                "first_step": int(state.step),
            }
            feed = loader.epoch_arrays

            def keeping():
                batches = feed()
                kept["images"], kept["labels"] = jax.device_get(batches)
                return batches

            if "update_norm_gap" in ctx.cell["limits"]:
                loader.epoch_arrays = keeping
            try:
                out = self._train_epoch()
            finally:
                loader.__dict__.pop("epoch_arrays", None)
            kept["steps"] = int(self.state.step) - kept["first_step"]
            kept["after"] = {
                "params": jax.device_get(self.state.params),
                "buf": jax.device_get(_momentum(self.state.opt_state)),
                "loss": out["train_loss"],
            }
            rec["followed"] = kept
            return out

        def _train_epoch(self):
            with spans.span("train_epoch") as s:
                out = super().train_epoch()
            s.meta.update(
                images=round(out["samples_per_sec"] * out["epoch_seconds"]),
                loss=out["train_loss"],
                program_img_per_s=out["samples_per_sec"],
            )
            return out

        def evaluate(self):
            with spans.span("evaluate") as s:
                out = super().evaluate()
            s.meta.update(loss=out["test_loss"])
            return out

        def train_one_level(self, epochs_per_level, level):
            with spans.span("train_one_level", level=level) as s:
                summary = super().train_one_level(epochs_per_level, level)
            s.meta.update(sparsity_pct=summary["final_sparsity"])
            return summary

    return Observed


def _momentum(opt_state):
    """The momentum buffers of the optimizer's state, a tree like the
    parameters."""
    found = [s.trace for s in opt_state if hasattr(s, "trace")]
    if len(found) != 1:
        raise ValueError(f"{len(found)} momentum traces in the optimizer's state")
    return found[0]


def _warm_prune_shapes(ctx, harness, window: Window) -> int:
    """Compiles the prunes of the levels the window can reach (see the top of
    the file); returns the last level warmed."""
    from turboprune_tpu.pruning import generate_densities, prune_the_model

    pp, state = harness.cfg.pruning_params, harness.state
    ladder = generate_densities(pp.prune_method, pp.target_sparsity, pp.prune_rate)
    first = int(ctx.cell["params"]["warmup"])
    # Without its own prune, which in a first run is mostly compilation.
    pace = window.last_unit_s - ctx.spans.named("prune_level")[-1].seconds
    count = math.ceil(1.5 * ctx.seconds / pace) + 1
    last = min(first + count, len(ladder)) - 1
    with ctx.spans.span("warm_prune_shapes", first=first, last=last) as s:
        for level in range(first, last + 1):
            masks = prune_the_model(
                pp.prune_method,
                harness.model,
                {"params": state.params},
                state.masks,
                ladder[level],
                jax.random.PRNGKey(0),
            )
            jax.block_until_ready(masks)
    ctx.say(
        f"[job] the last warm-up level took {pace:.3f} s without its prune: warmed "
        f"the prunes of levels {first} to {last} in {s.seconds:.1f} s"
    )
    return last


def _overrides(ctx) -> list[str]:
    return [
        *ctx.config["overrides"],
        *ctx.cell["params"].get("overrides", []),
        f"experiment_params.seed={ctx.seed}",
        f"experiment_params.base_dir={ctx.scratch / 'experiments'}",
    ]


def run(ctx):
    """Drive one cell; returns the observation the metrics read, the checks
    that decide ``correct``, and ``attempted`` / ``failed``."""
    import run_experiment
    from turboprune_tpu import driver

    params = ctx.cell["params"]
    # Epoch boundaries are starts, level boundaries are ends.
    open_at = int(params["warmup"]) - (1 if params["unit"] == "level" else 0)
    window = Window(ctx, params["unit"], open_at, int(params.get("trace_units", 1)))
    rec: dict = {}

    def on_open():
        if params.get("warm_prunes") == "window":
            rec["warmed_through"] = _warm_prune_shapes(ctx, rec["harness"], window)
        rec["params_at_open"] = jax.block_until_ready(
            jax.tree.map(jnp.copy, rec["harness"].state.params)
        )

    window.on_open = on_open

    real_prune = driver.prune_level

    def prune_level(harness, density, level):
        with ctx.spans.span("prune_level", level=level, density=density):
            return real_prune(harness, density, level)

    argv = [f"--config-name={ctx.config['entry_config']}", *_overrides(ctx)]
    ctx.say(f"[job] run_experiment.main({argv})")
    try:
        with mock.patch.object(
            driver, "PruningHarness", _observed_harness(ctx, window, rec)
        ), mock.patch.object(driver, "prune_level", prune_level):
            run_experiment.main(argv)
    except WindowClosed:
        pass
    finally:
        window.stop_trace()
    if window.closed_at is None:
        raise RuntimeError(
            "the program's run ended before the window closed: the cell's "
            "ladder is too short for --seconds"
        )
    pruned = [s.meta["level"] for s in ctx.spans.named("prune_level")]
    if pruned and max(pruned) > rec.get("warmed_through", max(pruned)):
        raise RuntimeError(
            f"the window pruned level {max(pruned)} and set-up had warmed the prunes "
            f"up to level {rec['warmed_through']} only: a prune compiled inside the window"
        )
    memory = memory_stats()  # the program's peak, before the reference runs

    harness = rec["harness"]
    t0, t1 = window.opened_at, window.closed_at
    epochs = ctx.spans.named("train_epoch", t0, t1)
    evals = ctx.spans.named("evaluate", t0, t1)
    levels = [s for s in ctx.spans.named("train_one_level") if "sparsity_pct" in s.meta]
    batch = harness.cfg.dataset_params.total_batch_size
    image_size = harness.cfg.dataset_params.image_size
    units = len(window.boundaries) - 1

    values, final = _compare(
        ctx, harness, rec["params_at_open"], rec["followed"], epochs, evals, levels, batch
    )
    checks = correct.judge(values, ctx.cell["limits"])

    obs = {
        "unit": window.unit,
        "window": (t0, t1),
        "boundaries": window.boundaries,
        "setup_s": t0 - ctx.t_start,
        "images": sum(s.meta["images"] for s in epochs),
        "batch": batch,
        "steps_per_epoch": harness.steps_per_epoch,
        "step_program": params["step_program"],
        **({"augment_program": params["augment_program"]} if "augment_program" in params else {}),
        "step_flops": model_flops.train_step_flops(
            harness.state.params, harness.state.batch_stats, image_size, batch
        ),
        "memory": memory,
    }
    _report(ctx, window, obs, epochs, levels)
    failed = int(values["nonfinite_losses"])
    return {
        "obs": obs,
        "checks": checks,
        "attempted": units,
        "failed": min(failed, units),
        "final": final,
    }


def _report(ctx, window: Window, obs: dict, epochs, levels) -> None:
    """Earlier lines of the run: the window, its slowest unit and what filled
    it, and a row per level."""
    t0, t1 = obs["window"]
    units = len(window.boundaries) - 1
    ctx.say(
        f"[job] window {t1 - t0:.3f} s, {units} {window.unit}s, "
        f"{obs['images']} train images; the program's own clock says "
        f"{np.median([s.meta['program_img_per_s'] for s in epochs]):.1f} img/s "
        f"inside train_epoch (median)"
    )
    b = window.boundaries
    slow = max(range(units), key=lambda i: b[i + 1] - b[i])
    inside = {
        name: sum(s.seconds for s in ctx.spans.named(name, b[slow], b[slow + 1] + 1e-3))
        for name in ("train_epoch", "evaluate", "prune_level", "load_level", "save_level")
    }
    ctx.say(
        f"[job] slowest {window.unit} of the window: number {slow}, "
        f"{b[slow + 1] - b[slow]:.3f} s (median {np.median(np.diff(b)):.3f} s), of it "
        + ", ".join(f"{k} {v:.3f} s" for k, v in inside.items() if v)
    )
    prunes = {s.meta["level"]: s.seconds for s in ctx.spans.named("prune_level")}
    for s in levels:
        ctx.say(
            f"[job] level {s.meta['level']}: {s.seconds:.3f} s in train_one_level, "
            f"{prunes.get(s.meta['level'], 0.0):.3f} s in prune_level, "
            f"sparsity {s.meta['sparsity_pct']:.6f} %"
            + (" (in the window)" if s.start >= t0 else "")
        )


def _compare(ctx, harness, params_at_open, followed, epochs, evals, levels, batch):
    """The numbers ``correct`` is decided on (PERF.md section 2), and what
    the control needs to put another forward pass in the program's place."""
    state = harness.state
    pp = harness.cfg.pruning_params
    values: dict[str, float] = {}

    losses = [s.meta["loss"] for s in epochs + evals]
    values["nonfinite_losses"] = sum(1 for x in losses if not math.isfinite(x))

    # A part of the batch left out shows in the program's own count.
    per_epoch = harness.steps_per_epoch * batch
    values["images_miscounted"] = sum(abs(s.meta["images"] - per_epoch) for s in epochs)

    # A step that returns its state unchanged moves nothing.
    values["param_change"] = correct.relative_change(state.params, params_at_open)

    # Every level's sparsity is the ladder's, up to weights tied with the
    # threshold.
    final_mask = correct.flat_masks(state.masks)
    n = final_mask.size
    level_now = ctx.spans.named("train_one_level")[-1].meta["level"]
    zeros = [(s.meta["level"], round(s.meta["sparsity_pct"] / 100.0 * n)) for s in levels]
    zeros.append((level_now, int(n - final_mask.sum())))
    values["ladder_excess_weights"] = max(
        abs(z - int((1.0 - correct.ladder_density(level, pp.prune_rate)) * n))
        for level, z in zeros
    )

    # The last mask made is a numpy global magnitude prune of the previous
    # level's weights, read back from the checkpoint the program wrote.
    if "mask_oracle_mismatch" in ctx.cell["limits"]:
        level = levels[-1].meta["level"]
        before = harness.ckpts.load_level(level - 1, state)
        want = correct.magnitude_oracle(
            before["params"], before["masks"],
            correct.ladder_density(level, pp.prune_rate),
        )
        values["mask_oracle_mismatch"] = int(np.sum(want != final_mask))
        del before

    # The program's eval arithmetic against the plain float32 forward: the
    # last evaluate() of the window over the whole eval set, and the same
    # compiled eval program on sampled images one by one.
    images, labels = harness.loaders.test_loader.eval_epoch_arrays()
    images = images.reshape((-1,) + images.shape[2:])
    labels = np.asarray(labels).reshape(-1)
    ref_all = correct.reference_logits(state.params, state.masks, state.batch_stats, images)
    ref_loss = correct.mean_loss(ref_all, labels)
    values["eval_loss_gap"] = abs(evals[-1].meta["loss"] - ref_loss) / max(ref_loss, LOSS_FLOOR)

    rng = np.random.default_rng(ctx.seed)
    valid = np.flatnonzero(labels >= 0)
    pick = np.sort(rng.choice(valid, min(SAMPLE_IMAGES, valid.size), replace=False))
    probe = rng.integers(0, ref_all.shape[1], pick.size)
    probed = _probe_losses(harness, pick, probe)
    ref_probed = correct.row_losses(ref_all[pick], probe)
    values["eval_probe_loss_gap"] = correct.probe_gap(probed, ref_probed, LOSS_FLOOR)
    ctx.say(
        f"[job] reference: eval loss {ref_loss:.6f} "
        f"(program {evals[-1].meta['loss']:.6f}) over {int((labels >= 0).sum())} "
        f"images; largest |logit| {np.max(np.abs(ref_all)):.4f}; probe losses "
        f"{ref_probed.min():.4f} to {ref_probed.max():.4f}, widest gap "
        f"{np.max(np.abs(probed - ref_probed) / np.maximum(ref_probed, LOSS_FLOOR)):.6f}"
    )

    # The program's training over the followed steps against plain float32
    # SGD: the whole steps where the cell says so, and the optimizer's own
    # path for the weights the masks hold at zero.
    recipe = _recipe(harness)
    ref_train = None
    if "update_norm_gap" in ctx.cell["limits"]:
        t = time.perf_counter()
        ref_train = sgd.follow(
            recipe, followed["params"], followed["buf"], followed["masks"],
            followed["batch_stats"], followed["images"], followed["labels"],
            followed["first_step"],
        )
        values.update(correct.training_gaps(followed, followed["after"], ref_train))
        ctx.say(
            f"[job] reference: followed {followed['steps']} steps of batch "
            f"{followed['images'].shape[1]} from step {followed['first_step']} in "
            f"{time.perf_counter() - t:.1f} s; loss {ref_train['loss']:.6f} "
            f"(program {followed['after']['loss']:.6f}); widest leaves, momentum: "
            + correct.worst_leaves(followed["after"]["buf"], ref_train["buf"])
            + "; update: "
            + correct.worst_leaves(
                correct.tree_change(followed["after"]["params"], followed["params"]),
                correct.tree_change(ref_train["params"], followed["params"]),
            )
        )
    if "masked_update_gap" in ctx.cell["limits"]:
        values["masked_update_gap"] = correct.masked_update_gap(
            followed,
            followed["after"],
            lambda w, buf: sgd.masked_path(
                recipe, w, buf, followed["steps"], followed["first_step"]
            ),
        )
    final = {
        "state": state, "images": images, "labels": labels, "pick": pick, "probe": probe,
        "ref": ref_all, "loss_floor": LOSS_FLOOR, "recipe": recipe, "followed": followed,
        "ref_train": ref_train,
    }
    return values, final


def _recipe(harness) -> sgd.Recipe:
    op = harness.cfg.optimizer_params
    if (op.optimizer_name, op.scheduler_type) != ("SGD", "TriangularSchedule"):
        raise ValueError(
            f"the reference follows SGD under the triangular schedule, not "
            f"{op.optimizer_name} under {op.scheduler_type}"
        )
    return sgd.Recipe(
        base_lr=op.lr,
        momentum=op.momentum,
        weight_decay=op.weight_decay,
        warmup_fraction=op.warmup_fraction,
        total_steps=harness.cfg.experiment_params.epochs_per_level * harness.steps_per_epoch,
    )


def _probe_losses(harness, pick, probe) -> np.ndarray:
    """The harness's compiled eval program, the one ``evaluate()`` runs in the
    window, at the eval set's own stacked shape, once for each sampled image:
    every row carries the padding label but that image's, which carries its
    probe class, so the loss sum that comes back is that image's loss against
    that class."""
    images, labels = harness._eval_batches
    out = []
    for row, label in zip(pick, probe):
        one = np.full(labels.size, -1, np.int32)
        one[row] = label
        one = jax.device_put(one.reshape(labels.shape), labels.sharding)
        out.append(harness._scan_eval(harness.state, (images, one))["loss_sum"])
    return np.asarray(jax.device_get(out), np.float64)
