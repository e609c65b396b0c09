"""The least time the chip could take for the scan's mathematics in a
training step (``granite_flops.py``: the four block products at the chunk
length, one read of x, B, C, dt and one write of y in bf16, three passes; the
larger of operations over the bf16 peak and bytes over HBM bandwidth) over
the scan's measured device time (``ssd_ms``)."""

from benchmarks import granite_flops
from benchmarks.metrics import ssd_ms


def read(obs):
    ms, counts = ssd_ms.read(obs), obs.get("kernel_counts")
    if ms is None or counts is None or obs["peaks"] is None:
        return None
    least = granite_flops.roofline_seconds(counts["ssd_flops"], counts["ssd_bytes"], obs["peaks"])
    return 100.0 * least / (ms * 1e-3)
