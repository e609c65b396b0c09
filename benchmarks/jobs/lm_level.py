"""The job of the language-model training cell: level 0 of an IMP ladder on
packed token sequences, through the program's own entry point, the window cut
in whole epochs.

What it shares with ``imp_ladder.py`` it imports from there: the window, the
observing subclass of ``PruningHarness`` (spans around ``train_epoch`` and
``evaluate``, the followed epoch kept on the host), the overrides, the recipe.
What differs is what a sample is and what ``correct`` compares against:

- one "image" of ``train_img_per_s`` is one packed sequence; the program's own
  count (``count`` of its step) is of target tokens, and ``images_miscounted``
  holds that count to the layout's;
- the reference is ``reference/granite.py`` (float32, the recurrence a token
  at a time, a plain masked softmax) under the published keys of the
  configuration's file, and ``reference/sgd_granite.py`` for the followed
  epoch. At the cell's size the program's state and the reference's do not
  fit the chip together, so the job asks the program what it needs of it
  (the eval probes), fetches the state, frees the device and only then runs
  the reference;
- a traced run also splits the step's device time by the model's named scopes
  (``scope_times.py``), from which ``ssd_ms`` and the two roofline shares read.

``correct`` (PERF.md section 2), all after the window:

``eval_loss_gap``        the window's last ``evaluate()`` against the
                         reference's mean loss over the eval set's valid
                         targets, as a share of max(reference, 1).
``eval_probe_loss_gap``  the program's compiled eval (``_scan_eval``, at the
                         eval set's stacked shape) run once a probe: every
                         target the padding label but ``probe_positions``
                         positions a sequence, each with a token id drawn
                         from the seed; the loss sum that comes back against
                         the reference's for the same positions and ids, as a
                         share of it; the median over ``probes`` probes.
``train_loss_gap``, ``momentum_norm_gap``, ``update_norm_gap``
                         the followed epoch (the first, from the seeded
                         weights) against plain float32 SGD on the same
                         batches: mean loss, the whole tree's momentum norm
                         and the norm of the parameters' change.
``param_change``, ``images_miscounted``, ``nonfinite_losses``,
``ladder_excess_weights``  as in ``imp_ladder.py``.
"""

from __future__ import annotations

import gc
import math
import time
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import correct, granite_flops, scope_times, trace_reduce
from benchmarks.jobs.imp_ladder import (
    Window,
    WindowClosed,
    _observed_harness,
    _overrides,
    _recipe,
)
from benchmarks.observe import memory_stats
from benchmarks.reference import granite as reference
from benchmarks.reference import sgd_granite

LOSS_FLOOR = 1.0
# The published keys the reference reads, from the configuration's file.
SPEC_KEYS = (
    "embedding_multiplier", "residual_multiplier", "attention_multiplier", "logits_scaling",
    "rms_norm_eps", "num_attention_heads", "num_key_value_heads",
)  # fmt: skip
# The model's named scopes (models/granite.py) and the step's, as paths.
SCOPES = (
    "ssd", "attn/flash", "mamba/in_proj", "mamba/conv", "mamba/gate_norm", "mamba/out_proj",
    "attn/qkv", "attn/out_proj", "mlp", "lm_head", "mask_apply", "loss", "optimizer",
)  # fmt: skip


def run(ctx):
    import run_experiment
    from turboprune_tpu import driver

    params = ctx.cell["params"]
    if params["unit"] != "epoch":
        raise ValueError("this job cuts its window in epochs")
    window = Window(ctx, "epoch", int(params["warmup"]), int(params.get("trace_units", 1)))
    rec: dict = {}
    # On the host: a second copy of the weights does not fit the chip.
    window.on_open = lambda: rec.update(
        params_at_open=jax.device_get(rec["harness"].state.params)
    )

    argv = [f"--config-name={ctx.config['entry_config']}", *_overrides(ctx)]
    ctx.say(f"[job] run_experiment.main({argv})")
    try:
        with mock.patch.object(driver, "PruningHarness", _observed_harness(ctx, window, rec)):
            run_experiment.main(argv)
    except WindowClosed:
        pass
    finally:
        window.stop_trace()
    if window.closed_at is None:
        raise RuntimeError(
            "the program's run ended before the window closed: the cell's "
            "level is too short for --seconds"
        )
    memory = memory_stats()  # the program's peak, before the reference runs

    harness = rec["harness"]
    t0, t1 = window.opened_at, window.closed_at
    epochs = ctx.spans.named("train_epoch", t0, t1)
    evals = ctx.spans.named("evaluate", t0, t1)
    batch, steps = harness.cfg.dataset_params.total_batch_size, harness.steps_per_epoch
    spec = {k: ctx.config[k] for k in SPEC_KEYS}
    train_tokens = np.asarray(harness.loaders.train_loader.tokens)
    layout = train_tokens[: steps * batch, 1].reshape(steps, batch, -1)
    counts = granite_flops.step_counts(
        harness.state.params, spec, layout, int(ctx.config["mamba_chunk_size"])
    )

    obs = {
        "unit": window.unit,
        "window": (t0, t1),
        "boundaries": window.boundaries,
        "setup_s": t0 - ctx.t_start,
        "images": len(epochs) * steps * batch,  # packed sequences
        "batch": batch,
        "steps_per_epoch": steps,
        "step_program": params["step_program"],
        "step_flops": counts["step_flops"],
        "kernel_counts": counts,
        "memory": memory,
    }
    if ctx.trace:
        obs["scope_ms"] = _scope_split(ctx, harness, rec["followed"], obs)

    tokens_s = np.median([s.meta["program_img_per_s"] for s in epochs])
    ctx.say(
        f"[job] window {t1 - t0:.3f} s, {len(window.boundaries) - 1} epochs of {steps} steps, "
        f"{obs['images']} sequences of {layout.shape[-1]} tokens; the program's own clock says "
        f"{tokens_s:.0f} target tokens/s inside train_epoch (median); a step holds "
        f"{counts['tokens_per_step']:.0f} tokens, {harness.data_gauges['target_tokens_per_step']:.1f} "
        f"targets, {counts['causal_pairs_per_step']:.0f} causal pairs, "
        f"{counts['step_flops'] / 1e12:.3f} TFLOP (scan {counts['ssd_flops'] / 1e12:.3f}, "
        f"attention {counts['flash_causal_flops'] / 1e12:.4f})"
    )
    b = window.boundaries
    ctx.say(
        "[job] epochs of the window, seconds: "
        + " ".join(f"{hi - lo:.3f}" for lo, hi in zip(b, b[1:]))
    )

    del harness  # _compare lets the program's state go once it has asked it all it needs
    values, final = _compare(ctx, rec, epochs, evals, spec)
    checks = correct.judge(values, ctx.cell["limits"])
    units = len(window.boundaries) - 1
    return {
        "obs": obs,
        "checks": checks,
        "attempted": units,
        "failed": min(int(values["nonfinite_losses"]), units),
        "final": final,
    }


def _scope_split(ctx, harness, followed, obs) -> dict:
    """Milliseconds a step by named scope, from this run's trace and the step
    program's compiled text; an earlier line says all of it."""
    shapes = tuple(
        jax.ShapeDtypeStruct(followed[k].shape, followed[k].dtype) for k in ("images", "labels")
    )
    hlo = harness._steps.scan_chunk.lower(harness.state, shapes).compile().as_text()
    split = scope_times.program_seconds(
        trace_reduce.find_xplane(ctx.trace_dir),
        obs["step_program"],
        scope_times.instruction_scopes(hlo),
        SCOPES,
    )
    if split is None:
        return {}
    per_step = {k: 1e3 * v / obs["steps_per_epoch"] for k, v in split["seconds"].items()}
    total = sum(per_step.values())
    ctx.say(
        f"[job] the step's device time by scope, ms a step over {split['runs']} traced runs "
        f"(sum {total:.2f}): "
        + ", ".join(f"{k} {v:.2f}" for k, v in sorted(per_step.items(), key=lambda kv: -kv[1]))
    )
    return per_step


def _tree_norm(tree, other=None) -> float:
    """Norm of ``tree`` (less ``other``) over all leaves, a leaf at a time in
    float64: the whole trees are gigabytes."""
    total = 0.0
    others = jax.tree.leaves(other) if other is not None else None
    for i, x in enumerate(jax.tree.leaves(tree)):
        x = np.asarray(x, np.float64)
        if others is not None:
            x = x - np.asarray(others[i], np.float64)
        total += float(np.sum(np.square(x)))
    return math.sqrt(total)


def _followed_norms(before: dict, after: dict, labels: np.ndarray) -> dict:
    """What the comparison keeps of a followed epoch's end (the program's or
    a reference's), so that the trees can go: the whole tree's momentum norm,
    the norm of the parameters' change, and the epoch's loss as the program
    reports it (over the epoch's valid targets: each step's mean weighs as
    many targets as it has)."""
    loss = after["loss"]
    if "losses" in after:
        counts = (labels >= 0).reshape(labels.shape[0], -1).sum(axis=1)
        loss = float(np.average(after["losses"], weights=counts))
    return {
        "loss": loss,
        "losses": after.get("losses"),
        "momentum_norm": _tree_norm(after["buf"]),
        "update_norm": _tree_norm(after["params"], before["params"]),
    }


def _training_gaps(program: dict, ref: dict) -> dict:
    """``correct.training_gaps(whole=True)`` on ``_followed_norms``."""
    rel = lambda a, b: abs(a - b) / b
    return {
        "train_loss_gap": abs(program["loss"] - ref["loss"]) / abs(ref["loss"]),
        "momentum_norm_gap": rel(program["momentum_norm"], ref["momentum_norm"]),
        "update_norm_gap": rel(program["update_norm"], ref["update_norm"]),
    }


def _probes(seed: int, cell: dict, targets: np.ndarray, vocab: int):
    """(positions, ids) [K, N, P]: for each probe and eval sequence, P
    positions that have a target, and a token id for each."""
    rng = np.random.default_rng(seed)
    k, p = int(cell["params"]["probes"]), int(cell["params"]["probe_positions"])
    positions = np.stack(
        [
            [np.sort(rng.choice(np.flatnonzero(row >= 0), p, replace=False)) for row in targets]
            for _ in range(k)
        ]
    )
    return positions, rng.integers(0, vocab, positions.shape)


def _program_probe_losses(harness, positions, ids) -> np.ndarray:
    """The harness's compiled eval program, the one ``evaluate()`` runs in
    the window, once a probe: the loss sum over that probe's positions."""
    tokens, labels = harness._eval_batches
    bsz = labels.shape[1]
    out = []
    for pos, tok in zip(positions, ids):
        one = np.full(labels.shape, -1, np.int32)
        for n in range(pos.shape[0]):
            one[n // bsz, n % bsz, pos[n]] = tok[n]
        one = jax.device_put(one, labels.sharding)
        out.append(harness._scan_eval(harness.state, (tokens, one))["loss_sum"])
    return np.asarray(jax.device_get(out), np.float64)


def _reference_eval(spec, state, tokens, targets, positions, ids, quantize=None):
    """(mean loss over the valid targets, probe loss sums [K]) of the plain
    forward of ``params * masks``, a sequence at a time."""
    weights = jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), state["params"])
    masks = jax.tree.map(jnp.asarray, state["masks"])
    logp_of = jax.jit(
        lambda w, m, tok: jax.nn.log_softmax(
            reference.forward(w, spec, tok[None, 0], tok[None, 1], quantize, masks=m)[0]
        )
    )
    loss_sum, probes = 0.0, np.zeros(positions.shape[0], np.float64)
    with jax.default_matmul_precision("highest"):
        for n in range(tokens.shape[0]):
            logp = logp_of(weights, masks, jnp.asarray(tokens[n]))
            valid = targets[n] >= 0
            rows = logp[jnp.arange(logp.shape[0]), jnp.asarray(np.maximum(targets[n], 0))]
            loss_sum -= float(jnp.sum(jnp.where(jnp.asarray(valid), rows, 0.0)))
            picked = logp[jnp.asarray(positions[:, n]), jnp.asarray(ids[:, n])]  # [K, P]
            probes -= np.asarray(jnp.sum(picked, axis=1), np.float64)
    return loss_sum / float((targets >= 0).sum()), probes


def _probe_gap(losses: np.ndarray, ref: np.ndarray) -> float:
    return float(np.median(np.abs(losses - ref) / np.maximum(ref, LOSS_FLOOR)))


def _compare(ctx, rec, epochs, evals, spec):
    """The numbers ``correct`` is decided on, and what the control needs to
    put another forward pass in the program's place. The program's state, the
    reference's and the host copies of both are gigabytes each: each goes as
    soon as the comparison has what it needs of it (a one-chip machine has
    40 GiB of host memory, and a run that kept them all met it)."""
    harness, followed = rec.pop("harness"), rec.pop("followed")
    cfg = harness.cfg
    values: dict[str, float] = {}

    losses = [s.meta["loss"] for s in epochs + evals]
    values["nonfinite_losses"] = sum(1 for x in losses if not math.isfinite(x))

    # The program counts target tokens; the layout says how many an epoch has.
    per_epoch = round(harness.data_gauges["target_tokens_per_step"] * harness.steps_per_epoch)
    values["images_miscounted"] = sum(abs(s.meta["images"] - per_epoch) for s in epochs)

    # What only the program can say, while its state is on the device.
    test = harness.loaders.test_loader
    tokens, targets = np.asarray(test.tokens), np.asarray(test.targets)
    positions, ids = _probes(ctx.seed, ctx.cell, targets, cfg.dataset_params.num_classes)
    probed = _program_probe_losses(harness, positions, ids)
    recipe = _recipe(harness)
    level_now = ctx.spans.named("train_one_level")[-1].meta["level"]
    prune_rate = cfg.pruning_params.prune_rate

    # Fetch and free: the reference needs the chip's memory, and the host's.
    state = {"params": jax.device_get(harness.state.params), "masks": jax.device_get(harness.state.masks)}
    harness.state = harness._eval_batches = None
    del harness, test  # with it the program's resident rewind target
    gc.collect()

    at_open = rec.pop("params_at_open")
    values["param_change"] = _tree_norm(state["params"], at_open) / _tree_norm(at_open)
    del at_open
    program_train = _followed_norms(followed, followed.pop("after"), followed["labels"])
    final_mask = correct.flat_masks(state["masks"])
    want_zeros = int((1.0 - correct.ladder_density(level_now, prune_rate)) * final_mask.size)
    values["ladder_excess_weights"] = abs(int(final_mask.size - final_mask.sum()) - want_zeros)
    del final_mask

    t = time.perf_counter()
    ref_loss, ref_probed = _reference_eval(spec, state, tokens, targets, positions, ids)
    values["eval_loss_gap"] = abs(evals[-1].meta["loss"] - ref_loss) / max(ref_loss, LOSS_FLOOR)
    values["eval_probe_loss_gap"] = _probe_gap(probed, ref_probed)
    ctx.say(
        f"[job] reference: eval loss {ref_loss:.6f} (program {evals[-1].meta['loss']:.6f}) over "
        f"{int((targets >= 0).sum())} targets of {tokens.shape[0]} sequences in "
        f"{time.perf_counter() - t:.1f} s; probe losses {ref_probed.min():.3f} to "
        f"{ref_probed.max():.3f}, widest gap "
        f"{np.max(np.abs(probed - ref_probed) / np.maximum(ref_probed, LOSS_FLOOR)):.6f}"
    )

    t = time.perf_counter()
    ref_train = _followed_norms(
        followed,
        sgd_granite.follow(
            recipe, spec, followed["params"], followed["buf"], followed["masks"],
            followed["images"], followed["labels"], followed["first_step"],
        ),
        followed["labels"],
    )  # fmt: skip
    values.update(_training_gaps(program_train, ref_train))
    ctx.say(
        f"[job] reference: followed {followed['steps']} steps of {followed['images'].shape[1]} "
        f"sequence(s) from step {followed['first_step']} in {time.perf_counter() - t:.1f} s; "
        f"loss {ref_train['loss']:.6f} (program {program_train['loss']:.6f}), step by step "
        + " ".join(f"{x:.4f}" for x in ref_train["losses"])
        + f"; momentum norm {ref_train['momentum_norm']:.6g} (program "
        f"{program_train['momentum_norm']:.6g}), update norm {ref_train['update_norm']:.6g} "
        f"(program {program_train['update_norm']:.6g})"
    )
    final = {
        "spec": spec, "state": state, "tokens": tokens, "targets": targets,
        "positions": positions, "ids": ids, "ref_loss": ref_loss, "ref_probed": ref_probed,
        "recipe": recipe, "followed": followed, "ref_train": ref_train,
        "control_numbers": control_numbers,
    }  # fmt: skip
    return values, final


def control_numbers(final: dict) -> dict:
    """The numbers of ``correct`` that a precision moves, with the float8
    reference where the program was (``control_tokens.py``): its forward
    over the eval set, and its SGD over the followed steps."""
    low = reference.fp8_operand
    state, f = final["state"], final["followed"]
    low_loss, low_probed = _reference_eval(
        final["spec"], state, final["tokens"], final["targets"],
        final["positions"], final["ids"], quantize=low,
    )  # fmt: skip
    low_train = _followed_norms(
        f,
        sgd_granite.follow(
            final["recipe"], final["spec"], f["params"], f["buf"], f["masks"],
            f["images"], f["labels"], f["first_step"], quantize=low,
        ),
        f["labels"],
    )  # fmt: skip
    return {
        "eval_loss_gap": abs(low_loss - final["ref_loss"]) / max(final["ref_loss"], LOSS_FLOOR),
        "eval_probe_loss_gap": _probe_gap(low_probed, final["ref_probed"]),
        **_training_gaps(low_train, final["ref_train"]),
    }
