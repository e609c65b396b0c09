"""Device time of the sigmoid-routed SwiGLU expert layers in one training
step, all of them together, forward, the recomputed forward and backward: the
trace's operations inside ``step_program`` runs whose compiled ``op_name``
lies under one of the model's ``moe/*`` scopes (router, dispatch, experts,
combine; ``benchmarks/scope_times.py``). The sort, the gather and the scatter
of the dispatch are in it. Nothing to read where the job took no such split,
or took it of another model's program."""


def read(obs):
    if "lfm2_experts_flops" not in (obs.get("kernel_counts") or {}):
        return None
    ms = sum(v for k, v in (obs.get("scope_ms") or {}).items() if k.startswith("moe/"))
    return ms if ms else None
