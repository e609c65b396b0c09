"""The job of the sparse-expert language-model cell: level 0 of an IMP ladder
on packed token sequences through the program's own entry point, the window
cut in whole epochs. It is ``lm_level.py``'s job with another reference and
three more things to say, and it takes from there all that does not name
granite: a copy of that module of its own (``registry.load_job``) whose
``reference``, ``sgd_granite`` and ``SCOPES`` it rebinds to this model's, so
that ``_compare`` (the eval probes, the fetch-and-free, the followed epoch
against plain float32 SGD), ``_scope_split`` and the float8 control
(``control_tokens.py``) run as they are. The module ``benchmarks.jobs.lm_level``
that others import is left as it was.

What is this job's own:

- the reference is ``reference/nemotron_h.py`` given the same share of the
  deployment as the program (the configuration's file says which), and
  ``reference/sgd_nemotron_h.py`` for the followed epoch;
- the observing harness also keeps each epoch's step counters
  (ops/moe.py's ``COUNTERS``, which the program fetches with the epoch's
  sums): the operations of the expert products are counted from
  ``moe_pairs`` (``nemotron_flops.py``), and two of ``correct``'s numbers
  read them;
- ``moe_dropped_pairs``: the sum of that counter over every epoch the run
  made, the window's among them. A pair no product computed is a wrong
  answer however small its weight;
- ``routing_mismatch``: the share of (token, ``E`` layer) of the eval set
  whose chosen experts, as the program chose them on the chip in its compute
  dtype, are not the set that ``reference.routing`` chooses in float32 for the
  same layer input (the residual stream as the program has it: it is made
  mutable as ``intermediates`` in one extra forward pass after the window).
  Float32 against float32 on one input differs by the order of the sums
  alone; a router or a norm before it in bfloat16 flips the 22nd place of a
  large share of tokens.
"""

from __future__ import annotations

import time
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import correct, nemotron_flops, registry
from benchmarks.jobs.imp_ladder import Window, WindowClosed, _observed_harness, _overrides
from benchmarks.observe import memory_stats
from benchmarks.reference import nemotron_h as reference
from benchmarks.reference import sgd_nemotron_h
from turboprune_tpu.ops.moe import COUNTERS

# The published keys the reference reads, from the configuration's file (the
# counts of heads and groups as held here), and the share's first expert.
SPEC_KEYS = (
    "layer_norm_epsilon", "num_attention_heads", "num_key_value_heads", "head_dim", "n_groups",
    "num_experts_per_tok", "routed_scaling_factor", "expert_offset", "chunk_size",
)  # fmt: skip
# The model's named scopes (models/nemotron_h.py, models/granite.py) and the
# step's, as paths. The grouped products are Pallas kernels traced under
# ``moe/experts`` and carry that scope.
SCOPES = (
    "moe/router", "moe/latent_down", "moe/dispatch", "moe/experts", "moe/combine",
    "moe/latent_up", "moe/shared", "ssd", "attn/flash", "mamba/in_proj", "mamba/conv",
    "mamba/gate_norm", "mamba/out_proj", "attn/qkv", "attn/out_proj", "lm_head", "mask_apply",
    "loss", "optimizer",
)  # fmt: skip

base = registry.load_job("lm_level")  # this job's own copy
base.reference, base.sgd_granite, base.SCOPES = reference, sgd_nemotron_h, SCOPES


def _counting_harness(ctx, window, rec):
    """``imp_ladder``'s observing harness, keeping every epoch's counters."""

    class Counting(_observed_harness(ctx, window, rec)):
        def _train_epoch(self):
            out = super()._train_epoch()
            rec.setdefault("counted", []).append(
                (time.perf_counter(), {k: out[k] for k in COUNTERS})
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
    batch, steps = harness.cfg.dataset_params.total_batch_size, harness.steps_per_epoch
    spec = {k: ctx.config[k] for k in SPEC_KEYS}
    train_tokens = np.asarray(harness.loaders.train_loader.tokens)
    layout = train_tokens[: steps * batch, 1].reshape(steps, batch, -1)
    counted = [c for at, c in rec["counted"] if t0 <= at <= t1]
    moe = {k: sum(c[k] for c in counted) / (len(counted) * steps) for k in COUNTERS}
    counts = nemotron_flops.step_counts(harness.state.params, spec, layout, moe["moe_pairs"])
    layers = len(nemotron_flops.mixers(harness.state.params, "router"))

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
        "moe": {**moe, "layers": layers, "experts_here": int(ctx.config["n_routed_experts"])},
        "memory": memory,
    }
    if ctx.trace:
        obs["scope_ms"] = base._scope_split(ctx, harness, rec["followed"], obs)

    tokens_s = np.median([s.meta["program_img_per_s"] for s in epochs])
    ctx.say(
        f"[job] window {t1 - t0:.3f} s, {len(window.boundaries) - 1} epochs of {steps} steps, "
        f"{obs['images']} sequences of {layout.shape[-1]} tokens; the program's own clock says "
        f"{tokens_s:.0f} target tokens/s inside train_epoch (median); a step holds "
        f"{counts['tokens_per_step']:.0f} tokens, {harness.data_gauges['target_tokens_per_step']:.1f} "
        f"targets, {counts['causal_pairs_per_step']:.0f} causal pairs, {moe['moe_pairs']:.1f} "
        f"(token, expert) pairs in {layers} layers of {obs['moe']['experts_here']} experts held "
        f"(fullest expert {moe['moe_load_max'] / layers:.1f}, mean "
        f"{moe['moe_pairs'] / layers / obs['moe']['experts_here']:.1f}), "
        f"{counts['step_flops'] / 1e12:.3f} TFLOP (scan {counts['ssd_flops'] / 1e12:.3f}, "
        f"attention {counts['flash_causal_flops'] / 1e12:.4f}, expert products "
        f"{counts['experts_flops'] / 1e12:.3f} with the rebuilt forward)"
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


def _routing_mismatch(ctx, harness, spec) -> float:
    """See the top of the file. One forward pass a sequence of the eval set,
    the program's model in its compute dtype with ``intermediates`` mutable,
    the reference's router on each ``E`` layer's input."""
    from turboprune_tpu.ops.masking import apply_masks

    model = harness.model

    def differing(params, masks, tokens):
        _, sown = model.apply(
            {"params": apply_masks(params, masks)}, tokens, mutable=["intermediates"]
        )
        wrong, total = jnp.zeros((), jnp.int32), 0
        for name, layer in sown["intermediates"].items():
            if "layer_in" not in layer:
                continue
            ours = jnp.sort(layer["mixer"]["top"][0], axis=-1)
            theirs = reference.routing(layer["layer_in"][0], params[name], spec)
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
        f"[job] routing: {wrong} of {total} (token, layer) chose another set than the float32 "
        f"reference does on the same layer input, in {time.perf_counter() - t:.1f} s"
    )
    return wrong / total


def _compare(ctx, rec, epochs, evals, spec):
    """``lm_level._compare``'s numbers and this job's two; the routing is
    asked while the program's state is on the device."""
    extra = {
        "moe_dropped_pairs": float(sum(c["moe_dropped_pairs"] for _, c in rec["counted"])),
        "routing_mismatch": _routing_mismatch(ctx, rec["harness"], spec),
    }
    values, final = base._compare(ctx, rec, epochs, evals, spec)
    values.update(extra)
    return values, final
