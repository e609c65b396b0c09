"""Device time of power retention in one training step, all layers together,
forward, the rebuilt forward and backward: the trace's operations inside
``step_program`` runs whose compiled ``op_name`` lies under the model's
``retention/scan`` scope (``benchmarks/scope_times.py``), a step: the two
Pallas kernels (``retention_fwd``, ``retention_bwd``) and XLA's work on what
every token is scaled by. The projections, the head norms, the rotation and
the gate around it are other scopes. Nothing to read where the job took no
such split or the program has no such scope."""


def read(obs):
    return (obs.get("scope_ms") or {}).get("retention/scan")
