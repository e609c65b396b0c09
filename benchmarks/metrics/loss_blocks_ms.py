"""Device time of the head and the loss in one training step where they run a
block of tokens at a time (train/steps.py): the trace's operations inside
``step_program`` runs whose compiled ``op_name`` lies under the ``lm_head``
scope, in which the blocks' products, their softmax (``loss`` inside it) and
the rebuilt logits of the backward pass all lie, or under the step's own
``loss`` scope (``benchmarks/scope_times.py``), a step. Nothing to read where
the job took no such split or the step has its logits whole (no
``loss_blocks`` in what the job observed)."""


def read(obs):
    split = obs.get("scope_ms") or {}
    if not obs.get("loss_blocks") or "lm_head" not in split:
        return None
    return split["lm_head"] + split.get("loss", 0.0)
