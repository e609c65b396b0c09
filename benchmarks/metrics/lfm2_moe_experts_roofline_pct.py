"""The least time the chip could take for the gated experts' three grouped
products in a training step (``lfm2_flops.py``: ``6 D F`` operations a (token,
expert) pair held here, the pairs counted by the step's own counter; one read
of the held experts' bf16 kernels a pass and each pair's rows in and out; four
passes, the layer's rebuilt forward among them; the larger of operations over
the bf16 peak and bytes over HBM bandwidth) over the measured device time of
the products: what runs under the model's ``moe/experts`` scope (the grouped
Pallas kernels and the activation between them). The kernels run over the
whole pair buffer, the rows no pair fills among them: the operations counted
are the pairs', so the share says what the padding costs too."""

from benchmarks import lfm2_flops


def read(obs):
    split, counts = obs.get("scope_ms") or {}, obs.get("kernel_counts") or {}
    ms = split.get("moe/experts", 0.0)
    if not ms or "lfm2_experts_flops" not in counts or obs["peaks"] is None:
        return None
    least = lfm2_flops.roofline_seconds(
        counts["lfm2_experts_flops"], counts["lfm2_experts_bytes"], obs["peaks"]
    )
    return 100.0 * least / (ms * 1e-3)
