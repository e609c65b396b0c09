"""The job of the power-retention language-model cell: level 0 of an IMP
ladder on packed 32,768-token sequences through the program's own entry
point, the window cut in whole epochs. As ``lfm2_level.py`` it is
``lm_level.py``'s job with another reference and more to say, and it takes
from there all that does not name granite: a copy of that module of its own
(``registry.load_job``) whose ``reference``, ``sgd_granite`` and ``SCOPES`` it
rebinds to this model's, so that ``_compare`` (the eval probes, the
fetch-and-free, the followed epoch against plain float32 SGD),
``_scope_split`` and the float8 control (``control_tokens.py``) run as they
are. The module ``benchmarks.jobs.lm_level`` that others import is left as it
was.

What is this job's own:

- the reference is ``reference/brumby.py`` given the same share of the
  deployment as the program (the configuration's file says which), retention
  in its quadratic form, and ``reference/sgd_brumby.py`` for the followed
  epoch, head and loss a block of rows at a time there too;
- the operations are ``brumby_flops.py``'s: the projections and the head, and
  retention as each document's cheaper form;
- what the program's traces said of themselves (utils/tracing.py's gauges):
  ``retention_kernel_calls`` / ``retention_xla_calls``, ``loss_blocks_per_step``
  and ``carried_chunks_per_step``; ``loss_blocks`` goes into what the metrics
  read, and ``kernels_bypassed`` into ``correct``: the traced retention calls
  that took XLA's form, and one more if the loss ran whole. A run that timed
  another path than the cell's is no run of the cell;
- under the control (``control_tokens.py``) it also reads the float32
  reference with its pairs cut at the program's chunk length (``carry_cut``):
  what a program whose carried state is lost would compute. Both controls
  are then judged as a run is, ``correct.Check`` against the cell's own
  limits for the numbers they have: each is said a line a number and as one
  verdict (``[control] carry_cut: correct=False, fails ...``) and kept in
  ``final["controls"]``; what ``control_tokens.py`` gets back is the float8
  numbers, as from the other jobs.
"""

from __future__ import annotations

from unittest import mock

import jax
import numpy as np

from benchmarks import brumby_flops, correct, registry
from benchmarks.jobs.imp_ladder import Window, WindowClosed, _observed_harness, _overrides
from benchmarks.observe import memory_stats
from benchmarks.reference import brumby as reference
from benchmarks.reference import sgd_brumby

# The published keys the reference reads, from the configuration's file (the
# counts of heads as held here).
SPEC_KEYS = (
    "rms_norm_eps", "num_attention_heads", "num_key_value_heads", "head_dim", "rope_theta",
    "retention_eps",
)  # fmt: skip
# The model's named scopes (models/brumby.py) and the step's, as paths. The
# Pallas kernels are traced under ``retention/scan`` and carry that scope; the
# blocks of the loss lie under ``lm_head``.
SCOPES = (
    "retention/scan", "retention/qkv", "retention/qk_norm", "retention/rope", "retention/gate",
    "retention/out_proj", "mlp", "lm_head", "mask_apply", "loss", "optimizer",
)  # fmt: skip
GAUGES = (
    "retention_kernel_calls", "retention_xla_calls", "loss_blocks_per_step", "carried_chunks_per_step",
)  # fmt: skip

base = registry.load_job("lm_level")  # this job's own copy
base.reference, base.sgd_granite, base.SCOPES = reference, sgd_brumby, SCOPES


def run(ctx):
    import run_experiment
    from turboprune_tpu import driver
    from turboprune_tpu.utils import tracing

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
    gauges = {k: tracing.gauges().get(k, 0) for k in GAUGES}

    harness = rec["harness"]
    t0, t1 = window.opened_at, window.closed_at
    epochs = ctx.spans.named("train_epoch", t0, t1)
    evals = ctx.spans.named("evaluate", t0, t1)
    batch, steps = harness.cfg.dataset_params.total_batch_size, harness.steps_per_epoch
    spec = {k: ctx.config[k] for k in SPEC_KEYS}
    train_tokens = np.asarray(harness.loaders.train_loader.tokens)
    layout = train_tokens[: steps * batch, 1].reshape(steps, batch, -1)
    counts = brumby_flops.step_counts(harness.state.params, spec, layout)

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
        "loss_blocks": gauges["loss_blocks_per_step"],
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
        f"targets, {counts['causal_pairs_per_step']:.0f} causal pairs, "
        f"{counts['carried_tokens_per_step']:.0f} tokens in documents cheaper carried than squared, "
        f"{counts['step_flops'] / 1e12:.3f} TFLOP (retention {counts['retention_flops'] / 1e12:.3f} "
        f"with the rebuilt forward, over {counts['retention_bytes'] / 1e9:.3f} GB); "
        + ", ".join(f"{k} {v:g}" for k, v in gauges.items())
    )
    b = window.boundaries
    ctx.say(
        "[job] epochs of the window, seconds: "
        + " ".join(f"{hi - lo:.3f}" for lo, hi in zip(b, b[1:]))
    )

    del harness  # _compare lets the program's state go once it has asked it all it needs
    values, final = base._compare(ctx, rec, epochs, evals, spec)
    values["kernels_bypassed"] = gauges["retention_xla_calls"] + (not gauges["loss_blocks_per_step"])
    final["control_numbers"] = _control_numbers(ctx, int(ctx.config["retention_chunk"]), ctx.cell["limits"])
    checks = correct.judge(values, ctx.cell["limits"])
    units = len(window.boundaries) - 1
    return {
        "obs": obs,
        "checks": checks,
        "attempted": units,
        "failed": min(int(values["nonfinite_losses"]), units),
        "final": final,
    }


def _control_numbers(ctx, chunk: int, limits: dict):
    """``lm_level.control_numbers`` (the float8 reference in the program's
    place) and the same numbers of the float32 reference with its carried
    state lost every ``chunk`` tokens, both judged against ``limits``."""

    def judged(which: str, numbers: dict) -> dict:
        checks = [correct.Check(name, float(numbers[name]), *limits[name]) for name in sorted(numbers)]
        for check in checks:
            ctx.say(f"[control] {which}: " + check.line().removeprefix("[correct] "))
        failed = [check.name for check in checks if not check.ok]
        ctx.say(f"[control] {which}: correct={not failed}, fails {', '.join(failed) or 'no limit'}")
        return {"numbers": numbers, "failed": failed, "correct": not failed}

    def numbers(final: dict) -> dict:
        state, f, spec = final["state"], final["followed"], dict(final["spec"], carry_cut=chunk)
        cut_loss, cut_probed = base._reference_eval(
            spec, state, final["tokens"], final["targets"], final["positions"], final["ids"]
        )
        cut_train = base._followed_norms(
            f,
            sgd_brumby.follow(
                final["recipe"], spec, f["params"], f["buf"], f["masks"],
                f["images"], f["labels"], f["first_step"],
            ),
            f["labels"],
        )  # fmt: skip
        cut = {
            "eval_loss_gap": abs(cut_loss - final["ref_loss"]) / max(final["ref_loss"], base.LOSS_FLOOR),
            "eval_probe_loss_gap": base._probe_gap(cut_probed, final["ref_probed"]),
            **base._training_gaps(cut_train, final["ref_train"]),
        }
        float8 = base.control_numbers(final)
        final["controls"] = {"carry_cut": judged("carry_cut", cut), "float8": judged("float8", float8)}
        return float8

    return numbers
