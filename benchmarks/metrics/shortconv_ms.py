"""Device time of the doubly gated short-convolution mixers in one training
step, all of them together, forward, the recomputed forward and backward: the
trace's operations inside ``step_program`` runs whose compiled ``op_name``
lies under one of the model's ``conv/*`` scopes (``conv/in_proj``,
``conv/gate_conv``, ``conv/out_proj``; ``benchmarks/scope_times.py``), a
step. The whole mixer and not ``conv/gate_conv`` alone: XLA computes the taps
and the second gate inside ``out_proj``'s fusion and their gradients inside
``in_proj``'s, a fusion is charged to its root's scope, and what is left
under ``conv/gate_conv`` is the first gate's product alone (0.47 ms a step
where the three streams' traffic alone would take 0.98; PERF.md section 6,
PR 40). Nothing to read where the job took no such split or the program has
no such scope."""


def read(obs):
    ms = sum(v for k, v in (obs.get("scope_ms") or {}).items() if k.startswith("conv/"))
    return ms if ms else None
