"""The outer pruning-level loop (reference experiment drivers:
/root/reference/run_experiment.py:22-126,
run_cyclic_training_experiment.py:22-129).

Control relationship preserved from the reference (SURVEY.md §1): the driver
owns the LEVEL loop (density ladder, prune between levels, rewind, level
checkpoints); the harness owns the epoch loop. What changes on TPU: pruning
runs REPLICATED on every host from replicated state + a shared PRNG key —
deterministic by construction — instead of the reference's rank-0 prune +
DDP-construction broadcast (run_experiment.py:95-113); a post-prune
fingerprint check asserts cross-host agreement (the reference's dormant
check_model_equality, distributed_utils.py:31-60, made real).
"""

from __future__ import annotations

from pathlib import Path
from typing import Optional, Type

import jax
import numpy as np

from .config.schema import MainConfig
from .harness import CyclicPruningHarness, PruningHarness
from .parallel import broadcast_object, check_state_equality, is_primary
from .pruning import generate_densities, prune_the_model
from .utils import (
    gen_expt_dir,
    resume_experiment,
    reset_weights,
    save_config,
    set_seed,
    tracing,
)


def _first_train_batch(harness):
    """One GLOBALLY-IDENTICAL scoring batch for data-driven criteria.

    Host-scope loaders (grain/tpk) yield different rows on each process —
    scoring SNIP on those would diverge the masks across hosts and trip the
    post-prune fingerprint check. Allgather the per-host slices so every
    host scores on the same full global batch (the reference sidesteps this
    with rank-0 prune + DDP broadcast, run_experiment.py:95-113)."""
    loader = harness.loaders.train_loader
    for batch in loader:
        if (
            getattr(loader, "batch_scope", "global") == "host"
            and jax.process_count() > 1
        ):
            from jax.experimental import multihost_utils

            batch = jax.tree.map(
                lambda x: np.asarray(jax.device_get(x)), batch
            )
            batch = multihost_utils.process_allgather(batch, tiled=True)
        return batch
    raise RuntimeError("empty train loader")


def prune_level(harness, density: float, level: int) -> None:
    """Prune the harness state to ``density`` and apply rewind semantics
    (reference run_experiment.py:95-105 + reset_weights)."""
    cfg = harness.cfg
    method = cfg.pruning_params.prune_method
    # The span runs through the sparsity read that follows the prune, which
    # is where the host waits for the prune's device work.
    with tracing.span("level/prune"):
        # Same key on every host => identical Bernoulli/normal draws
        # (SURVEY.md §7 "Replicated pruning determinism").
        rng = jax.random.fold_in(
            jax.random.PRNGKey(cfg.experiment_params.seed), level
        )
        batch = None
        if method in ("snip", "synflow"):
            batch = _first_train_batch(harness)

        nm_spec = None
        if cfg.experiment_params.nm_sparsity:
            from .config.schema import parse_nm

            n, m = parse_nm(cfg.experiment_params.nm_sparsity)
            nm_spec = (n, m, cfg.experiment_params.nm_transposable)

        state = harness.state
        before = harness.mask_count().sparsity
        masks = prune_the_model(
            method,
            harness.model,
            {"params": state.params, "batch_stats": state.batch_stats}
            if state.batch_stats
            else {"params": state.params},
            state.masks,
            density,
            rng,
            batch=batch,
            nm=nm_spec if method == "nm" else None,
        )
        nm_note = ""
        if nm_spec is not None and method not in ("nm", "just dont"):
            # Projection post-pass on any other criterion: snap its mask to
            # the N:M pattern (monotone — the ladder's no-resurrection
            # invariant holds; the "nm" criterion projects inside
            # prune_the_model).
            from .sparse.nm import project_masks

            masks, nm_report = project_masks(
                state.params, masks, nm_spec[0], nm_spec[1], nm_spec[2]
            )
            nm_note = (
                f", {cfg.experiment_params.nm_sparsity} projection kept "
                f"{nm_report['preserved_magnitude_frac']:.3f} of magnitude"
            )
        harness.state = state.replace(masks=masks)
        harness.masks_written()
        after = harness.mask_count().sparsity
        if is_primary():
            print(
                f"[prune] level {level}: {method} to density {density:.4f} "
                f"(sparsity {before:.2f}% -> {after:.2f}%){nm_note}",
                flush=True,
            )
    # Rewind AFTER pruning: masks survive, weights roll back per
    # training_type (custom_models.py:112-146 semantics). The rewind notes
    # on this span the ``source`` of its target: ``resident`` in the process
    # that holds it, ``disk`` at a resumed process's first rewind.
    with tracing.span("level/rewind"):
        harness.state = reset_weights(
            cfg.pruning_params.training_type, harness.state, harness.ckpts
        )
    if jax.process_count() > 1:
        # Once per level, so the exact digest allgather (full device->host
        # transfer; catches element-permuting divergence the cheap moments
        # check cannot) stays off the per-step path.
        with tracing.span("level/agree"):
            check_state_equality(
                {"params": harness.state.params, "masks": harness.state.masks},
                exact=True,
            )


def _say_time(title: str, roots: list, gauges: Optional[dict] = None) -> dict:
    """The operator's ``[time]`` line for ``roots``, with ``gauges`` after it
    where there are any; returns the breakdown."""
    b = tracing.breakdown(roots)
    if is_primary():
        print(tracing.line(title, b, gauges), flush=True)
    return b


def run(cfg: MainConfig, harness_cls: Optional[Type[PruningHarness]] = None):
    """Run the full experiment; returns (expt_dir, per-level summaries)."""
    harness_cls = harness_cls or PruningHarness
    ep = cfg.experiment_params
    set_seed(ep.seed)

    # Experiment dir decided on the primary host, broadcast as strings
    # (reference broadcast_object of (prefix, expt_dir),
    # run_experiment.py:54-72).
    start_level = 0
    if ep.resume_experiment:
        prefix, expt_dir, start_level = resume_experiment(cfg)
    elif is_primary():
        prefix, expt_dir = gen_expt_dir(cfg)
    else:
        prefix, expt_dir = "", ""
    if jax.process_count() > 1:
        prefix, expt_dir, start_level = broadcast_object(
            (prefix, expt_dir, start_level)
        )
    if is_primary():
        save_config(expt_dir, cfg)

    harness = harness_cls(cfg, (prefix, expt_dir))
    _say_time("set-up", tracing.setup_roots(), harness.data_gauges)

    pp = cfg.pruning_params
    densities = generate_densities(
        pp.prune_method, pp.target_sparsity, pp.prune_rate
    )
    if start_level and not harness.ckpts.has_level(start_level - 1):
        raise FileNotFoundError(
            f"resume_level={start_level} needs checkpoint "
            f"model_level_{start_level - 1}"
        )

    summaries = []
    level_span = None
    try:
        for level in range(start_level, len(densities)):
            density = densities[level]
            with tracing.span("level", level=level, density=density) as level_span:
                if level == 0:
                    if pp.training_type == "at_init":
                        # PaI: prune the untrained network before any
                        # training (run_experiment.py:86-91). model_init is
                        # saved after, so it carries the pruned-at-init
                        # weights.
                        prune_level(harness, density, level)
                else:
                    # A level starts from the state the last one left in
                    # ``harness.state``: the very tree ``save_level`` fetched.
                    # Only the first level of a resumed process holds no such
                    # state and reads it back, and puts the train loader
                    # where the levels before would have left it.
                    if level == start_level:
                        with tracing.span("level/load"):
                            restored = harness.ckpts.load_level(level - 1, harness.state)
                            harness.state = harness.state.replace(**restored)
                            harness.masks_written()
                            harness.resume_data_order(level)
                    prune_level(harness, density, level)

                with tracing.span("level/train"):
                    summary = harness.train_one_level(ep.epochs_per_level, level)
                if ep.profile_dir and level == 0 and len(densities) > 1:
                    # The second session of profile_dir: the 0 -> 1 level
                    # boundary, stopped by the harness where level 1's
                    # set-up ends.
                    tracing.start_profile(Path(ep.profile_dir) / "level0_to_1")
                # Saves are primary-only — state is replicated, so host 0
                # holds everything — and a level's is written behind the next
                # level: this returns once the tree is on the host, and the
                # directory and its cross-host barrier are the next wait()'s
                # (utils/checkpoint.py).
                with tracing.span("level/save"):
                    harness.ckpts.save_level(level, harness.state)
                summary["achieved_density"] = harness.mask_count().density
                summaries.append(summary)
            timing = _say_time(f"level {level}", [level_span])
            harness.metrics.log_level_timing(
                {**tracing.timing_row(level_span, timing), **harness.data_gauges}
            )
    finally:
        tracing.stop_profile()  # a level that raised must not leave one running
        if level_span is not None and "error" in level_span.attrs:
            # What ended it closed its spans on the way out: the level says
            # where it had got to, and writes no row.
            _say_time(f"level {level_span.attrs['level']} (unfinished)", [level_span])
        # Returning or raising, every level this run reported is on disk
        # before anyone is told the run is over.
        harness.ckpts.wait()
    if ep.checkpoint_every_epochs:
        # Run complete: the final level's mid-level slot is stale — left
        # behind it would hijack a later resume of this dir after a config
        # change (its embedded config hash defends too; this removes the
        # hazard outright).
        harness.ckpts.clear_mid_level()
    harness.wandb.finish()
    return expt_dir, summaries


def run_cyclic(cfg: MainConfig):
    return run(cfg, harness_cls=CyclicPruningHarness)
