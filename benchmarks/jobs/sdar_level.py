"""The job of the block-diffusion language-model cell: level 0 of an IMP
ladder on packed token sequences, each noised into a clean and a noised copy,
through the program's own entry point, the window cut in whole epochs. As
``nemotron_level.py`` it works on a copy of ``lm_level.py``'s module of its
own (``registry.load_job``) and takes from there what does not depend on what
a batch is: the trees' norms and the gaps.
The module ``benchmarks.jobs.lm_level`` that others import is left as it was.

What is this job's own, because a batch here is ``(tokens [B, 5, T],
(targets, weights))`` and the loss a weighted sum over the masked targets
divided by the tokens (data/tokens.py, train/steps.py):

- the reference is ``reference/sdar_moe.py`` given the same share of the
  deployment as the program, and ``reference/sgd_sdar_moe.py`` for the
  followed epoch. Both are given the noised arrays the program was fed (the
  followed epoch's, kept as ``imp_ladder._followed_epoch`` keeps them; the eval
  set's, which the loader noised once): no random stream is drawn again;
- the observing harness keeps each epoch's step counters (ops/moe.py's
  ``COUNTERS``, and the model's ``moe_rounds`` and ``masked_targets``) and a
  handle on the targets each epoch was fed;
- ``images_miscounted``: the program's ``count`` is of tokens, a constant of
  the layout; what varies with the noise is how many of them are targets. So
  each epoch's ``masked_targets`` (the model's own count, rows whose noised id
  is not the clean one) is held to the targets of the arrays that epoch was
  fed that are not the padding label, and the tokens to the layout's;
- the eval probes put a target of weight 1 at ``probe_positions`` positions a
  sequence and nothing elsewhere, through the program's compiled eval;
- ``moe_dropped_pairs`` and ``routing_mismatch`` as in ``nemotron_level.py``,
  the latter on every layer's MoE input over both copies of the eval set.
"""

from __future__ import annotations

import gc
import math
import time
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import correct, registry, scope_times, sdar_flops, trace_reduce
from benchmarks.jobs.imp_ladder import (
    Window,
    WindowClosed,
    _observed_harness,
    _overrides,
    _recipe,
)
from benchmarks.observe import memory_stats
from benchmarks.reference import sdar_moe as reference
from benchmarks.reference import sgd_sdar_moe

# The published keys the reference reads, from the configuration's file (the
# counts of heads as held here), and the share's first expert.
SPEC_KEYS = (
    "rms_norm_eps", "num_attention_heads", "num_key_value_heads", "head_dim", "rope_theta",
    "num_experts_per_tok", "expert_offset", "mask_row_scale",
)  # fmt: skip
# The model's named scopes (models/sdar.py) and the step's, as paths. The
# Pallas kernels are traced under ``attn/flash`` and ``moe/experts`` and carry
# those scopes.
SCOPES = (
    "moe/router", "moe/dispatch", "moe/experts", "moe/combine", "attn/flash", "attn/qkv",
    "attn/qk_norm", "attn/rope", "attn/out_proj", "lm_head", "mask_apply", "loss", "optimizer",
)  # fmt: skip
NOISE_PROGRAM = "jit_noise_epoch"

base = registry.load_job("lm_level")  # this job's own copy
LOSS_FLOOR = base.LOSS_FLOOR


def _counting_harness(ctx, window, rec):
    """``imp_ladder``'s observing harness, keeping every epoch's counters and
    the targets it was fed (device arrays: nothing is computed or fetched
    inside the window)."""

    class Counting(_observed_harness(ctx, window, rec)):
        def _train_epoch(self):
            loader = self.loaders.train_loader
            before = loader.__dict__.get("epoch_arrays")  # the followed epoch's, or none
            feed = loader.epoch_arrays

            def noting():
                batches = feed()
                rec.setdefault("fed_targets", []).append(batches[1][0])
                return batches

            loader.epoch_arrays = noting
            try:
                out = super()._train_epoch()
            finally:
                if before is None:
                    loader.__dict__.pop("epoch_arrays", None)
                else:
                    loader.epoch_arrays = before
            rec.setdefault("counted", []).append(
                (time.perf_counter(), {k: out[k] for k in self.model.counters})
            )
            return out

    return Counting


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
        with mock.patch.object(driver, "PruningHarness", _counting_harness(ctx, window, rec)):
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
    dp = harness.cfg.dataset_params
    batch, steps = dp.total_batch_size, harness.steps_per_epoch
    spec = {k: ctx.config[k] for k in SPEC_KEYS}
    train_tokens = np.asarray(harness.loaders.train_loader.tokens)
    layout = train_tokens[: steps * batch, reference.DOC].reshape(steps, batch, -1)
    counted = [c for at, c in rec["counted"] if t0 <= at <= t1]
    names = counted[0].keys()
    moe = {k: sum(c[k] for c in counted) / (len(counted) * steps) for k in names}
    counts = sdar_flops.step_counts(
        harness.state.params, spec, layout, dp.block_length, moe["moe_pairs"]
    )
    layers = len(sdar_flops.layers(harness.state.params))
    held = int(ctx.config["num_experts"])

    obs = {
        "unit": window.unit,
        "window": (t0, t1),
        "boundaries": window.boundaries,
        "setup_s": t0 - ctx.t_start,
        "images": len(epochs) * steps * batch,  # packed sequences
        "batch": batch,
        "steps_per_epoch": steps,
        "step_program": params["step_program"],
        "noise_program": NOISE_PROGRAM,
        "step_flops": counts["step_flops"],
        "kernel_counts": counts,
        "moe_softmax": {**moe, "layers": layers, "experts_here": held},
        "memory": memory,
    }
    if ctx.trace:
        obs["scope_ms"] = _scope_split(ctx, harness, rec["followed"], obs)

    tokens_s = np.median([s.meta["program_img_per_s"] for s in epochs])
    gauges = harness.data_gauges
    ctx.say(
        f"[job] window {t1 - t0:.3f} s, {len(window.boundaries) - 1} epochs of {steps} steps, "
        f"{obs['images']} sequences of {layout.shape[-1]} tokens; the program's own clock says "
        f"{tokens_s:.0f} tokens/s inside train_epoch (median); a step holds "
        f"{counts['rows_per_step']:.0f} rows (gauge {gauges['rows_per_step']:.0f}) in blocks of "
        f"{dp.block_length}, {moe['masked_targets']:.1f} masked targets, "
        f"{counts['kept_pairs_per_step']:.0f} kept (query, key) pairs (gauge "
        f"{gauges['blockdiff_kept_pairs_per_step']:.0f}), {moe['moe_pairs']:.1f} (row, expert) "
        f"pairs in {layers} layers of {held} experts held (a layer: fullest expert "
        f"{moe['moe_load_max'] / layers:.1f}, mean {moe['moe_pairs'] / layers / held:.1f}, "
        f"moe_load_max {moe['moe_load_max']:.1f}, rounds {moe['moe_rounds'] / layers:.3f}), "
        f"moe_dropped_pairs {moe['moe_dropped_pairs']:.0f}, "
        f"{counts['step_flops'] / 1e12:.3f} TFLOP (attention "
        f"{counts['flash_blockdiff_flops'] / 1e12:.4f}, expert products "
        f"{counts['swiglu_experts_flops'] / 1e12:.3f} with the rebuilt forward)"
    )
    b = window.boundaries
    ctx.say(
        "[job] epochs of the window, seconds: "
        + " ".join(f"{hi - lo:.3f}" for lo, hi in zip(b, b[1:]))
    )
    ctx.say(
        "[job] every epoch's (moe_pairs, moe_load_max, moe_rounds, masked_targets): "
        + " ".join(
            f"({c['moe_pairs']}, {c['moe_load_max']}, {c['moe_rounds']}, {c['masked_targets']})"
            for _, c in rec["counted"]
        )
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
    """``lm_level._scope_split`` for a batch whose labels are a pair:
    milliseconds a step by named scope, from this run's trace and the step
    program's compiled text; an earlier line says all of it."""
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), (followed["images"], followed["labels"])
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
    ctx.say(
        f"[job] the step's device time by scope, ms a step over {split['runs']} traced runs "
        f"(sum {sum(per_step.values()):.2f}): "
        + ", ".join(f"{k} {v:.2f}" for k, v in sorted(per_step.items(), key=lambda kv: -kv[1]))
    )
    return per_step


def _routing_mismatch(ctx, harness, spec) -> float:
    """The share of (row, layer) of the eval set, both copies, whose chosen
    experts, as the program chose them on the chip in its compute dtype, are
    not the set the reference's float32 router chooses for the same MoE input
    (the residual stream as the program has it: ``intermediates`` is made
    mutable in one extra forward pass a sequence after the window)."""
    from turboprune_tpu.ops.masking import apply_masks

    model = harness.model

    def differing(params, masks, tokens):
        _, sown = model.apply(
            {"params": apply_masks(params, masks)}, tokens, mutable=["intermediates"]
        )
        wrong, total = jnp.zeros((), jnp.int32), 0
        for name, layer in sown["intermediates"].items():
            ours = jnp.sort(layer["mlp"]["top"][0], axis=-1)
            theirs = reference.routing(layer["moe_in"][0], params[name], spec)
            wrong += jnp.sum(jnp.any(ours != theirs, axis=-1))
            total += ours.shape[0]
        return wrong, total

    differing = jax.jit(differing)
    t = time.perf_counter()
    wrong = total = 0
    for tokens in np.asarray(harness.loaders.test_loader.tokens):
        w, n = differing(harness.state.params, harness.state.masks, jnp.asarray(tokens[None]))
        wrong, total = wrong + int(w), total + int(n)
    ctx.say(
        f"[job] routing: {wrong} of {total} (row, layer) chose another set than the float32 "
        f"reference does on the same layer input, in {time.perf_counter() - t:.1f} s"
    )
    return wrong / total


def _probes(seed: int, cell: dict, targets: np.ndarray, vocab: int):
    """(positions, ids) [K, N, P]: for each probe and eval sequence, P
    positions of the sequence and a token id (not the mask's) for each."""
    rng = np.random.default_rng(seed)
    k, p = int(cell["params"]["probes"]), int(cell["params"]["probe_positions"])
    positions = np.stack(
        [[np.sort(rng.choice(targets.shape[1], p, replace=False)) for _ in targets] for _ in range(k)]
    )
    return positions, rng.integers(0, vocab - 1, positions.shape)


def _program_probe_losses(harness, positions, ids) -> np.ndarray:
    """The harness's compiled eval program, the one ``evaluate()`` runs in
    the window, once a probe: the loss sum over that probe's positions, each
    a target of weight 1."""
    tokens, (targets, weights) = harness._eval_batches
    bsz = targets.shape[1]
    out = []
    for pos, tok in zip(positions, ids):
        one, w = np.full(targets.shape, -1, np.int32), np.zeros(weights.shape, np.float32)
        for n in range(pos.shape[0]):
            one[n // bsz, n % bsz, pos[n]] = tok[n]
            w[n // bsz, n % bsz, pos[n]] = 1.0
        labels = (jax.device_put(one, targets.sharding), jax.device_put(w, weights.sharding))
        out.append(harness._scan_eval(harness.state, (tokens, labels))["loss_sum"])
    return np.asarray(jax.device_get(out), np.float64)


def _reference_eval(spec, state, tokens, labels, positions, ids, quantize=None):
    """(the loss over the eval set: the weighted sum over its masked targets
    over its tokens; probe loss sums [K]) of the plain forward of ``params *
    masks``, a sequence at a time."""
    targets, sample_weights = labels
    weights = jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), state["params"])
    masks = jax.tree.map(jnp.asarray, state["masks"])
    logp_of = jax.jit(
        lambda w, m, tok: jax.nn.log_softmax(
            reference.forward(w, spec, tok[None], quantize, masks=m)[0]
        )
    )
    loss_sum, probes = 0.0, np.zeros(positions.shape[0], np.float64)
    with jax.default_matmul_precision("highest"):
        for n in range(tokens.shape[0]):
            logp = logp_of(weights, masks, jnp.asarray(tokens[n]))
            rows = logp[jnp.arange(logp.shape[0]), jnp.asarray(np.maximum(targets[n], 0))]
            kept = jnp.where(jnp.asarray(targets[n] >= 0), jnp.asarray(sample_weights[n]) * rows, 0.0)
            loss_sum -= float(jnp.sum(kept))
            picked = logp[jnp.asarray(positions[:, n]), jnp.asarray(ids[:, n])]  # [K, P]
            probes -= np.asarray(jnp.sum(picked, axis=1), np.float64)
    return loss_sum / float((sample_weights >= 0).sum()), probes


def _followed_norms(before: dict, after: dict) -> dict:
    """``lm_level._followed_norms``; every step holds as many tokens, so the
    epoch's loss is the plain mean of the steps'."""
    return {
        "loss": after["loss"],
        "losses": after.get("losses"),
        "momentum_norm": base._tree_norm(after["buf"]),
        "update_norm": base._tree_norm(after["params"], before["params"]),
    }


def _compare(ctx, rec, epochs, evals, spec):
    """The numbers ``correct`` is decided on, and what the control needs to
    put another forward pass in the program's place; ``lm_level._compare``'s
    order: ask the program, fetch and free, then the reference."""
    harness, followed = rec.pop("harness"), rec.pop("followed")
    cfg = harness.cfg
    values: dict[str, float] = {}

    losses = [s.meta["loss"] for s in epochs + evals]
    values["nonfinite_losses"] = sum(1 for x in losses if not math.isfinite(x))

    # The program counts tokens, the model its masked rows; the layout says
    # how many tokens an epoch has, the arrays it was fed how many targets.
    per_epoch = round(harness.data_gauges["tokens_per_step"] * harness.steps_per_epoch)
    fed = [int((np.asarray(t) >= 0).sum()) for t in rec.pop("fed_targets")]
    said = [c["masked_targets"] for _, c in rec["counted"]]
    if len(fed) != len(said):
        raise RuntimeError(f"{len(fed)} epochs fed, {len(said)} counted")
    values["images_miscounted"] = sum(abs(s.meta["images"] - per_epoch) for s in epochs) + sum(
        abs(a - b) for a, b in zip(said, fed)
    )
    values["moe_dropped_pairs"] = float(sum(c["moe_dropped_pairs"] for _, c in rec["counted"]))
    values["routing_mismatch"] = _routing_mismatch(ctx, harness, spec)

    # What only the program can say, while its state is on the device.
    test = harness.loaders.test_loader
    tokens, labels = np.asarray(test.tokens), tuple(np.asarray(x) for x in test.targets)
    positions, ids = _probes(ctx.seed, ctx.cell, labels[0], cfg.dataset_params.num_classes)
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
    values["param_change"] = base._tree_norm(state["params"], at_open) / base._tree_norm(at_open)
    del at_open
    program_train = _followed_norms(followed, followed.pop("after"))
    final_mask = correct.flat_masks(state["masks"])
    want_zeros = int((1.0 - correct.ladder_density(level_now, prune_rate)) * final_mask.size)
    values["ladder_excess_weights"] = abs(int(final_mask.size - final_mask.sum()) - want_zeros)
    del final_mask

    t = time.perf_counter()
    ref_loss, ref_probed = _reference_eval(spec, state, tokens, labels, positions, ids)
    values["eval_loss_gap"] = abs(evals[-1].meta["loss"] - ref_loss) / max(ref_loss, LOSS_FLOOR)
    values["eval_probe_loss_gap"] = base._probe_gap(probed, ref_probed)
    ctx.say(
        f"[job] reference: eval loss {ref_loss:.6f} (program {evals[-1].meta['loss']:.6f}) over "
        f"{int((labels[0] >= 0).sum())} masked targets of {tokens.shape[0]} sequences in "
        f"{time.perf_counter() - t:.1f} s; probe losses {ref_probed.min():.3f} to "
        f"{ref_probed.max():.3f}, widest gap "
        f"{np.max(np.abs(probed - ref_probed) / np.maximum(ref_probed, LOSS_FLOOR)):.6f}"
    )

    t = time.perf_counter()
    ref_train = _followed_norms(
        followed,
        sgd_sdar_moe.follow(
            recipe, spec, followed["params"], followed["buf"], followed["masks"],
            followed["images"], followed["labels"], followed["first_step"],
        ),
    )  # fmt: skip
    values.update(base._training_gaps(program_train, ref_train))
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
        "spec": spec, "state": state, "tokens": tokens, "labels": labels,
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
        final["spec"], state, final["tokens"], final["labels"],
        final["positions"], final["ids"], quantize=low,
    )  # fmt: skip
    low_train = _followed_norms(
        f,
        sgd_sdar_moe.follow(
            final["recipe"], final["spec"], f["params"], f["buf"], f["masks"],
            f["images"], f["labels"], f["first_step"], quantize=low,
        ),
    )  # fmt: skip
    return {
        "eval_loss_gap": abs(low_loss - final["ref_loss"]) / max(final["ref_loss"], LOSS_FLOOR),
        "eval_probe_loss_gap": base._probe_gap(low_probed, final["ref_probed"]),
        **base._training_gaps(low_train, final["ref_train"]),
    }
