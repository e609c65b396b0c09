"""The least time the chip could take for the gated short-convolution mixers'
mathematics in a training step (``lfm2_flops.py``: the two projections, 2 x
their parameters a token, and ``2 K + 2`` operations a token and channel for
both gates and the taps; one read of the mixer's input and kernels and one
write of its output in bf16, nothing of B, C and u; three passes; the larger
of operations over the bf16 peak and bytes over HBM bandwidth, which is the
operations) over the mixers' measured device time (``shortconv_ms``, every
``conv/*`` scope): what the gates, the taps, the splits and the rebuilt
forward cost beside the two products, and the number a kernel written for the
mixer would have to beat."""

from benchmarks import lfm2_flops
from benchmarks.metrics import shortconv_ms


def read(obs):
    ms, counts = shortconv_ms.read(obs), obs.get("kernel_counts") or {}
    if ms is None or "shortconv_bytes" not in counts or obs["peaks"] is None:
        return None
    least = lfm2_flops.roofline_seconds(
        counts["shortconv_flops"], counts["shortconv_bytes"], obs["peaks"]
    )
    return 100.0 * least / (ms * 1e-3)
