"""The least time the chip could take for power retention's mathematics in a
training step (``brumby_flops.py``: each document as the cheaper of its
quadratic and its recurrent form, whatever implements it; one read of q, k,
v and the gate and one write of the output in bf16; four passes, the forward
twice because the layer is rebuilt; the larger of operations over the bf16
peak and bytes over HBM bandwidth, which is the operations) over retention's
measured device time (``retention_ms``): what the chunked walk's within-chunk
squares, its 8,320 features for 8,256, the rotations and the kept states cost
beside the mathematics."""

from benchmarks import brumby_flops
from benchmarks.metrics import retention_ms


def read(obs):
    ms, counts = retention_ms.read(obs), obs.get("kernel_counts") or {}
    if ms is None or "retention_bytes" not in counts or obs["peaks"] is None:
        return None
    least = brumby_flops.roofline_seconds(
        counts["retention_flops"], counts["retention_bytes"], obs["peaks"]
    )
    return 100.0 * least / (ms * 1e-3)
