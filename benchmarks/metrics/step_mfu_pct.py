"""Model operations of a training step (model_flops.py: three forward passes
over the batch, counted from shapes) over the step's device time and the
chip's bf16 peak (peaks.json)."""

from benchmarks.metrics import step_ms


def read(obs):
    ms = step_ms.read(obs)
    if ms is None or obs["peaks"] is None or "step_flops" not in obs:
        return None
    return 100.0 * obs["step_flops"] / (ms * 1e-3) / obs["peaks"]["bf16_flops_per_s"]
