"""Device time of the LatentMoE layers in one training step, all of them
together, forward, the recomputed forward and backward: the trace's
operations inside ``step_program`` runs whose compiled ``op_name`` lies under
one of the model's ``moe/*`` scopes (router, latent projections, dispatch,
experts, combine, shared expert; ``benchmarks/scope_times.py``). The sort,
the gather and the scatter of the dispatch are in it. Nothing to
read where the job took no such split or the program has no such scope."""


def read(obs):
    split = obs.get("scope_ms") or {}
    ms = sum(v for k, v in split.items() if k.startswith("moe/"))
    return ms if ms else None
