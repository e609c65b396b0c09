"""The least time the chip could take for attention under the block-diffusion
mask in a training step (``sdar_flops.py``: ``4 d`` a kept (query, key) pair
and query head, the pairs counted from the layout by the rule; one read of q,
k, v and one write of the output in bf16 over the 2 T rows; three passes; the
larger of operations over the bf16 peak and bytes over HBM bandwidth) over
the measured device time of what runs under the model's ``attn/flash`` scope:
the three ``flash_blockdiff_*`` kernels, the rebuilt forward among their runs,
and the row sums between them. The kernels' grids hold every block of the
2 T x 2 T scores, the skipped ones among them: the share says what those
cost too."""

from benchmarks import sdar_flops


def read(obs):
    ms, counts = (obs.get("scope_ms") or {}).get("attn/flash"), obs.get("kernel_counts") or {}
    if not ms or "flash_blockdiff_flops" not in counts or obs["peaks"] is None:
        return None
    least = sdar_flops.roofline_seconds(
        counts["flash_blockdiff_flops"], counts["flash_blockdiff_bytes"], obs["peaks"]
    )
    return 100.0 * least / (ms * 1e-3)
