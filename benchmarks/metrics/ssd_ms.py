"""Device time of the state-space scan in one training step, all Mamba
layers together, forward, the recomputed forward and backward: the trace's
operations inside ``step_program`` runs whose compiled ``op_name`` lies under
the model's ``ssd`` scope (``benchmarks/scope_times.py``), a step. Nothing to
read where the job took no such split or the program has no such scope."""


def read(obs):
    ms = (obs.get("scope_ms") or {}).get("ssd")
    return ms if ms else None
