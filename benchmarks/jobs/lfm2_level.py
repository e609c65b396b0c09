"""The job of the convolution-hybrid sparse-expert language-model cell: level
0 of an IMP ladder on packed token sequences through the program's own entry
point, the window cut in whole epochs. As ``nemotron_level.py`` it is
``lm_level.py``'s job with another reference and more to say, and it takes
from there all that does not name granite: a copy of that module of its own
(``registry.load_job``) whose ``reference``, ``sgd_granite`` and ``SCOPES`` it
rebinds to this model's, so that ``_compare`` (the eval probes, the
fetch-and-free, the followed epoch against plain float32 SGD),
``_scope_split`` and the float8 control (``control_tokens.py``) run as they
are. The module ``benchmarks.jobs.lm_level`` that others import is left as it
was.

What is this job's own:

- the reference is ``reference/lfm2_moe.py`` given the same share of the
  deployment as the program (the configuration's file says which), and
  ``reference/sgd_lfm2_moe.py`` for the followed epoch;
- the observing harness also keeps each epoch's step counters (the model's
  ``counters``: ops/moe.py's ``COUNTERS`` and ``moe_rounds``, which the
  program fetches with the epoch's sums): the operations of the expert
  products are counted from ``moe_pairs`` (``lfm2_flops.py``), and every
  epoch's counters are printed, the warm-up's among them;
- ``moe_dropped_pairs``: the sum of that counter over every epoch the run
  made. A pair no product computed is a wrong answer however small its weight;
- ``routing_mismatch``: the share of (token, routed layer) of the eval set
  whose chosen experts, as the program chose them on the chip in its compute
  dtype, are not the set that ``reference.routing`` chooses in float32 for the
  same MoE input (the residual stream as the program has it: it is made
  mutable as ``intermediates`` in one extra forward pass after the window).
"""

from __future__ import annotations

import time
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import correct, lfm2_flops, registry
from benchmarks.jobs.imp_ladder import Window, WindowClosed, _observed_harness, _overrides
from benchmarks.observe import memory_stats
from benchmarks.reference import lfm2_moe as reference
from benchmarks.reference import sgd_lfm2_moe

# The published keys the reference reads, from the configuration's file (the
# counts of heads as held here), and the share's first expert.
SPEC_KEYS = (
    "norm_eps", "num_attention_heads", "num_key_value_heads", "head_dim", "rope_theta",
    "num_experts_per_tok", "routed_scaling_factor", "expert_offset",
)  # fmt: skip
# The model's named scopes (models/lfm2.py) and the step's, as paths. The
# Pallas kernels are traced under ``attn/flash`` and ``moe/experts`` and carry
# those scopes.
SCOPES = (
    "moe/router", "moe/dispatch", "moe/experts", "moe/combine", "conv/in_proj", "conv/gate_conv",
    "conv/out_proj", "attn/flash", "attn/qkv", "attn/qk_norm", "attn/rope", "attn/out_proj",
    "mlp", "lm_head", "mask_apply", "loss", "optimizer",
)  # fmt: skip

base = registry.load_job("lm_level")  # this job's own copy
base.reference, base.sgd_granite, base.SCOPES = reference, sgd_lfm2_moe, SCOPES


def _counting_harness(ctx, window, rec):
    """``imp_ladder``'s observing harness, keeping every epoch's counters."""

    class Counting(_observed_harness(ctx, window, rec)):
        def _train_epoch(self):
            out = super()._train_epoch()
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
    batch, steps = harness.cfg.dataset_params.total_batch_size, harness.steps_per_epoch
    spec = {k: ctx.config[k] for k in SPEC_KEYS}
    train_tokens = np.asarray(harness.loaders.train_loader.tokens)
    layout = train_tokens[: steps * batch, 1].reshape(steps, batch, -1)
    counted = [c for at, c in rec["counted"] if t0 <= at <= t1]
    moe = {k: sum(c[k] for c in counted) / (len(counted) * steps) for k in counted[0]}
    counts = lfm2_flops.step_counts(harness.state.params, spec, layout, moe["moe_pairs"])
    layers = len(lfm2_flops.layers(harness.state.params, "router", "mlp"))
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
        "step_flops": counts["step_flops"],
        "kernel_counts": counts,
        "lfm2_moe": {**moe, "layers": layers, "experts_here": held},
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
        f"(token, expert) pairs in {layers} routed layers of {held} experts held (a layer: fullest "
        f"expert {moe['moe_load_max'] / layers:.1f}, mean {moe['moe_pairs'] / layers / held:.1f}, "
        f"rounds {moe['moe_rounds'] / layers:.3f}), moe_dropped_pairs {moe['moe_dropped_pairs']:.0f}, "
        f"{counts['step_flops'] / 1e12:.3f} TFLOP (expert products "
        f"{counts['lfm2_experts_flops'] / 1e12:.3f} with the rebuilt forward, attention "
        f"{counts['flash_causal_flops'] / 1e12:.4f}, the short-convolution mixers "
        f"{counts['shortconv_flops'] / 1e12:.3f} over {counts['shortconv_bytes'] / 1e9:.3f} GB)"
    )
    b = window.boundaries
    ctx.say(
        "[job] epochs of the window, seconds: "
        + " ".join(f"{hi - lo:.3f}" for lo, hi in zip(b, b[1:]))
    )
    ctx.say(
        "[job] every epoch's (moe_pairs, moe_load_max, moe_rounds): "
        + " ".join(
            f"({c['moe_pairs']}, {c['moe_load_max']}, {c['moe_rounds']})" for _, c in rec["counted"]
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


def _routing_mismatch(ctx, harness, spec) -> float:
    """See the top of the file. One forward pass a sequence of the eval set,
    the program's model in its compute dtype with ``intermediates`` mutable,
    the reference's router on each routed layer's MoE input."""
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
