"""Device time of block-diffusion attention in one training step, all layers
together, forward, the recomputed forward and backward: the trace's
operations inside ``step_program`` runs whose compiled ``op_name`` lies under
the model's ``attn/flash`` scope (``benchmarks/scope_times.py``): the three
``flash_blockdiff_*`` Pallas kernels over the clean and the noised copy, the
rows' bounds and the row sums between them. Nothing to read where the job
took no such split, or took it of a program that has no such kernels."""


def read(obs):
    counts = obs.get("kernel_counts") or {}
    ms = (obs.get("scope_ms") or {}).get("attn/flash")
    return ms if ms and "flash_blockdiff_flops" in counts else None
