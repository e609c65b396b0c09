"""The least time the chip could take for causal attention inside the packed
documents in a training step (``granite_flops.py``: each document's own
triangle of query-key pairs, one read of q, k, v and one write of the output
in bf16, three passes; the larger of operations over the bf16 peak and bytes
over HBM bandwidth) over the measured device time of what runs under the
model's ``attn/flash`` scope: the three Pallas kernels and the row sums
between them."""

from benchmarks import granite_flops


def read(obs):
    ms, counts = (obs.get("scope_ms") or {}).get("attn/flash"), obs.get("kernel_counts")
    if not ms or counts is None or obs["peaks"] is None:
        return None
    least = granite_flops.roofline_seconds(
        counts["flash_causal_flops"], counts["flash_causal_bytes"], obs["peaks"]
    )
    return 100.0 * least / (ms * 1e-3)
