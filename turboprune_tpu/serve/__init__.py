"""Inference serving for pruned checkpoints (beyond-reference subsystem).

engine.py   InferenceEngine — checkpoint loading, mask folding / channel
            compaction / N:M gathering backends, AOT compiled-shape cache
            over padded batch-size buckets
batcher.py  DynamicBatcher — deadline/size micro-batching with bounded-queue
            backpressure, replica round-robin, graceful drain
metrics.py  ServeMetrics + MetricsHub — per-model labelled latency
            histograms, counters, gauges, Prometheus text exposition
server.py   InferenceServer — stdlib HTTP /predict /healthz /metrics with
            fleet routing on the request's "model" field
fleet/      ModelRegistry + FleetEngine + AOTExecutableCache — every level
            of an experiment family from one process, weight paging, and
            load-not-compile cold starts
loadgen.py  Open-loop Poisson load generator — p50/p99/p99.9 vs offered
            load and the saturation knee

Entry point: run_server.py at the repo root, configured by the conf/serve/
group composed through config/compose.py.
"""

from .batcher import DynamicBatcher, QueueFullError
from .engine import DEFAULT_BUCKETS, InferenceEngine, NotServable
from .fleet import (
    AOTExecutableCache,
    FleetEngine,
    ModelRegistry,
    UnknownModelError,
    open_cache,
)
from .loadgen import detect_knee, run_open_loop, sweep_offered_load
from .metrics import (
    LATENCY_BUCKETS_MS,
    MetricsHub,
    ServeMetrics,
    render_prometheus_all,
)
from .server import InferenceServer, build_server

__all__ = [
    "AOTExecutableCache",
    "DEFAULT_BUCKETS",
    "DynamicBatcher",
    "FleetEngine",
    "InferenceEngine",
    "NotServable",
    "InferenceServer",
    "LATENCY_BUCKETS_MS",
    "MetricsHub",
    "ModelRegistry",
    "QueueFullError",
    "ServeMetrics",
    "UnknownModelError",
    "build_server",
    "detect_knee",
    "open_cache",
    "render_prometheus_all",
    "run_open_loop",
    "sweep_offered_load",
]
