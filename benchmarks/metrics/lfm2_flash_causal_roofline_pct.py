"""The least time the chip could take for causal attention inside the packed
documents in a training step at this model's 8 query heads on 2 key/value
heads of 64 (``lfm2_flops.py`` by ``granite_flops.py``'s counts: each
document's own triangle of query-key pairs, one read of q, k, v and one write
of the output in bf16, three passes; the larger of operations over the bf16
peak and bytes over HBM bandwidth) over the measured device time of what runs
under the model's ``attn/flash`` scope: the three packed causal Pallas kernels
and the row sums between them. The norm and the rotation of the queries and
keys run before the kernels, under scopes of their own, and are not in it."""

from benchmarks import lfm2_flops


def read(obs):
    ms, counts = (obs.get("scope_ms") or {}).get("attn/flash"), obs.get("kernel_counts") or {}
    if not ms or "lfm2_experts_flops" not in counts or obs["peaks"] is None:
        return None
    least = lfm2_flops.roofline_seconds(
        counts["flash_causal_flops"], counts["flash_causal_bytes"], obs["peaks"]
    )
    return 100.0 * least / (ms * 1e-3)
