#!/usr/bin/env python
"""Headline bench + north-star workload numbers.

Prints ONE JSON line {"metric", "value", "unit", "vs_baseline", "extra"}.
The headline metric stays ResNet18 ImageNet-shape training throughput on
one chip (round-to-round continuity); ``extra`` carries the north-star
numbers:

  resnet50_img_per_sec     ResNet50/224 bs512 train throughput, one chip
                           (the reference's actual recipe batch,
                           conf/dataset_params/dp_imagenet_ffcv.yaml:3)
  resnet50_tflops_per_sec  achieved model TFLOP/s (XLA cost analysis)
  resnet50_mfu             achieved / peak for the detected chip kind
  tpk_decode_img_per_sec   native .tpk JPEG decode HOST throughput
  grain_decode_img_per_sec grain pipeline decode HOST throughput
                           (decode -> host uint8 batch; device transfer
                           excluded — see _steady_epochs for why)
  resnet50_fed_img_per_sec ResNet50 step throughput with the tpk pipeline
                           actually feeding (decode + transfer + train),
                           at the recipe batch 512; ``fed_pipeline``
                           carries the engine's per-stage wall-time
                           breakdown (decode-wait / transfer / consumer-
                           wait, data/pipeline.py)
  scan_chunk_k{K}_*        chunk-size sweep: resnet18 on the streamed tpk
                           path with K prefetched batches fused into ONE
                           compiled lax.scan dispatch — img/s and host
                           dispatches per epoch per K, plus the pipeline
                           stage breakdown at the largest K
  flash_fwdbwd_ms /        Pallas flash attention fwd+bwd wall time and
  flash_vs_dense_speedup   speedup vs dense-softmax attention, REAL chip
                           (proves Mosaic lowering outside interpret mode)
  serving_img_per_sec /    serve/ subsystem end-to-end: a density-0.5
  serving_p50_ms /         pruned resnet18 behind the dynamic batcher under
  serving_p99_ms           concurrent mixed-size clients — sustained img/s,
                           caller-observed latency quantiles, and the
                           compile-cache accounting proving zero
                           steady-state recompiles
  nm_frontier_*            N:M gathered execution frontier (sparse/nm.py):
                           masked-dense vs gathered 2:4 vs 4:8 vs channel-
                           compacted train-step ms on deit_tiny + the
                           resnet18 fc head, CPU-pinned subprocess; per
                           pattern: kept-|w| accuracy proxy, routing
                           coverage (unrouted eligible layers listed),
                           forward parity max-abs-diff, and the zero
                           steady-state-recompile count
  mixed_plan_*             one-planner backend mix (sparse/plan.py): a
                           heterogeneous-mask VGG (dead conv channels +
                           scattered in-axis 2:4 fc stack) timed as a
                           train step under masked-dense / compact-only /
                           nm-only / MIXED — every variant produced by
                           plan_execution with forced modes; carries the
                           per-layer decision table (backend + reason +
                           cost-model est_gain), forward/grad parity vs
                           masked-dense, per-variant steady-state
                           recompiles, and mixed-vs-best-single-backend;
                           CPU-pinned subprocess
  serving_load_*           fleet serving under OPEN-LOOP Poisson load
                           (serve/fleet/ + serve/loadgen.py): closed-loop
                           capacity, p50/p99/p99.9 + goodput + sheds per
                           offered load (0.3x/0.7x/1.5x capacity), the
                           DETECTED saturation knee (null when the sweep
                           stayed healthy — never a fake number), and the
                           per-model execution backends proving
                           multi-tenant routing; CPU-pinned subprocess
  compaction_s{S}_*        dead-channel compaction sweep (sparse/):
                           vgg16_bn with channel-structured masks at
                           sparsity S% — masked-dense vs compacted eval
                           img/s, speedup, compacted param/channel counts,
                           and the parity max-abs-diff between the two
                           forwards

Stage persistence: each stage's fields are written to
``$BENCH_DATA_DIR/stages.json`` the moment they are measured; a rerun skips
stages already captured (set BENCH_FORCE=1 to re-measure), and the watchdog
reports everything accumulated so far. A run that stalls therefore still
converges to a complete BENCH record across attempts, and the final print
labels which fields came from the cache (``cached_stages`` + per-stage
timestamps) so the artifact stays honest about when each number was taken.

Baseline: the reference's only published number — ResNet18/ImageNet at
1:09 min/epoch on 4x A100 with FFCV (/root/reference/README.md:8) =
1,281,167 images / 69 s ≈ 18,567 img/s over 4 GPUs ≈ 4,642 img/s per GPU.
``vs_baseline`` is OUR one-chip throughput / that per-GPU number.

The input-pipeline numbers measure the host CPU of the machine the bench
runs on and scale with its cores (tpk decode threads and grain workers are
per-core parallel); ``pipeline_host_cpu_cores`` records how many it had.

Measurement: rounds of K donated steps chained through the state pytree,
synced by fetching the last step's loss VALUE: the donation chain makes
that fetch wait on every step in the round.

Every device stage needs a TPU: the bench refuses to start on any other
backend, a stage that raises makes the run exit non-zero, and a device kind
missing from PEAK_TFLOPS is an error, not a run without MFU.
"""

from __future__ import annotations

import json
import os
import sys
import time
from datetime import datetime, timezone
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

BATCH_R18 = 1024
BATCH_R50 = 512
BATCH_FED = 512  # recipe batch (BASELINE.md) — was 256 pre-r5
WARMUP_STEPS = 3
STEPS_PER_ROUND = 10
ROUNDS = 3
# README.md:8 — 1.28M ImageNet train images / 69 s on 4x A100, per-GPU share.
BASELINE_IMG_PER_SEC_PER_CHIP = 1_281_167 / 69.0 / 4.0

# Peak bf16 TFLOP/s per chip by device_kind substring (public spec sheets).
PEAK_TFLOPS = {
    "v6e": 918.0,
    "v6": 918.0,
    "v5p": 459.0,
    "v5e": 197.0,
    "v5": 197.0,
    "v4": 275.0,
    "v3": 123.0,
    "v2": 45.0,
}


def _detect_peak_tflops() -> float:
    kind = jax.devices()[0].device_kind.lower()
    for key, peak in PEAK_TFLOPS.items():
        if key in kind:
            return peak
    raise RuntimeError(
        f"device kind {kind!r} is not in PEAK_TFLOPS — add its published "
        "peak there; MFU against a guessed peak is not a measurement"
    )


def _make_step(model_name: str, batch_size: int):
    from turboprune_tpu.models import create_model
    from turboprune_tpu.train import (
        create_optimizer,
        create_schedule,
        create_train_state,
        make_train_step,
    )

    model = create_model(
        model_name, num_classes=1000, dataset_name="ImageNet",
        compute_dtype=jnp.bfloat16,
    )
    schedule = create_schedule(
        "TriangularSchedule", base_lr=0.2, epochs=90, steps_per_epoch=1251
    )
    tx = create_optimizer("SGD", schedule, momentum=0.9, weight_decay=1e-4)
    # graftlint: disable=rng-key-reuse -- fixed seed on purpose: bench inputs must be identical across rounds for round-to-round comparability
    state = create_train_state(model, tx, jax.random.PRNGKey(0), (1, 224, 224, 3))
    # AOT-compile once and bench the compiled executable directly — the same
    # artifact serves cost_analysis, so the step is not XLA-compiled twice.
    jitted = jax.jit(make_train_step(model, tx, schedule), donate_argnums=0)

    # graftlint: disable=rng-key-reuse -- fixed seed on purpose: identical bench batch every round
    rng = jax.random.PRNGKey(1)
    images = jax.random.normal(rng, (batch_size, 224, 224, 3), jnp.float32)
    # graftlint: disable=rng-key-reuse -- deliberate same-key draw: synthetic bench labels need no independence from the images
    labels = jax.random.randint(rng, (batch_size,), 0, 1000)
    batch = (images, labels)
    step = jitted.lower(state, batch).compile()
    return step, state, batch


def bench_train(model_name: str, batch_size: int) -> tuple[float, float]:
    """(img/s, flops_per_step) for synthetic device-resident batches."""
    step, state, batch = _make_step(model_name, batch_size)
    flops = float(step.cost_analysis()["flops"])
    for _ in range(WARMUP_STEPS):
        state, metrics = step(state, batch)
    float(metrics["loss_sum"])  # real sync (see module docstring)

    best = float("inf")
    for _ in range(ROUNDS):
        t0 = time.perf_counter()
        for _ in range(STEPS_PER_ROUND):
            state, metrics = step(state, batch)
        float(metrics["loss_sum"])
        best = min(best, (time.perf_counter() - t0) / STEPS_PER_ROUND)
    return batch_size / best, flops


# ----------------------------------------------------------- input pipeline
def _ensure_jpeg_dataset(root: Path, n: int = 2048, size: int = 256) -> Path:
    """Synthetic-JPEG ImageFolder (2 classes) for pipeline benches; cached."""
    split = root / "train"
    marker = root / f".done_{n}_{size}"
    if marker.exists():
        return split
    # Regenerating the JPEGs (size knobs changed) invalidates any .tpk
    # packed from the previous set — remove it so the tpk bench repacks.
    (root / "train.tpk").unlink(missing_ok=True)
    from PIL import Image

    rng = np.random.default_rng(0)
    means = rng.uniform(40, 215, size=(2, 1, 1, 3))
    per = n // 2
    for c, cls in enumerate(("class_a", "class_b")):
        d = split / cls
        d.mkdir(parents=True, exist_ok=True)
        for i in range(per):
            arr = np.clip(
                means[c] + rng.normal(0, 25, size=(size, size, 3)), 0, 255
            ).astype(np.uint8)
            Image.fromarray(arr).save(d / f"{i}.jpeg", quality=90)
    marker.touch()
    return split


def _steady_epochs(epoch_fn, epochs: int = 3) -> float:
    """img/s over epochs 2..N — epoch 1 is discarded as warmup. Measuring a
    single short epoch flatters prefetching loaders (workers decode the
    whole tail during the first batch's latency), so the rate must be taken
    at steady state. ``epoch_fn(e)`` receives the epoch index so loaders can
    derive fresh per-epoch augmentation seeds.

    Both decode benches measure the HOST pipeline (decode -> host uint8
    batch) with the device transfer excluded; the fed-resnet50 number
    below keeps the full decode+transfer+train path."""
    n, t = 0, 0.0
    for e in range(epochs):
        t0 = time.perf_counter()
        count = epoch_fn(e)
        dt = time.perf_counter() - t0
        if e > 0:
            n += count
            t += dt
    return n / t


def bench_tpk_decode(split: Path, root: Path, batch: int = 256) -> float:
    from turboprune_tpu.data.native import TpkFile, pack_imagefolder

    tpk = root / "train.tpk"
    if not tpk.exists():
        pack_imagefolder(split, tpk)
    f = TpkFile(tpk)
    rng = np.random.default_rng(0)
    nthreads = min(16, os.cpu_count() or 1)
    steps = f.num_samples // batch

    def one_epoch(e: int) -> int:
        order = rng.permutation(f.num_samples).astype(np.int64)
        count = 0
        for b in range(steps):
            idx = order[b * batch : (b + 1) * batch]
            # Seed from (epoch, batch) so steady-state epochs decode FRESH
            # random crops, like real training, instead of replaying epoch 1.
            images, _ = f.decode(
                idx, 224, train=True, seed=e * steps + b, nthreads=nthreads
            )
            count += images.shape[0]
        return count

    rate = _steady_epochs(one_epoch)
    f.close()
    return rate


def bench_grain_decode(split: Path, batch: int = 256, workers: int = 2) -> float:
    """Measured in a CPU-pinned SUBPROCESS: grain's ShardByJaxProcess
    queries the JAX backend, and the parent holds the chip — a child that
    reached for it too would fail or hang. The quantity measured here is
    pure host decode throughput, so the child is pinned to the CPU
    platform."""
    import subprocess

    code = f"""
import time
import jax
jax.config.update("jax_platforms", "cpu")
from turboprune_tpu.data.imagenet import GrainImageLoader

loader = GrainImageLoader(
    {str(split)!r}, total_batch_size={batch}, train=True, num_workers={workers}
)
n, t = 0, 0.0
for e in range(3):
    t0 = time.perf_counter()
    count = sum(images.shape[0] for images, _ in loader._raw_batches())
    dt = time.perf_counter() - t0
    if e > 0:
        n += count
        t += dt
print("RATE", n / t)
"""
    out = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        cwd=str(Path(__file__).resolve().parent),
        # Must sit UNDER the 480s stage watchdog: TimeoutExpired kills the
        # child cleanly, whereas the watchdog's os._exit would orphan the
        # decoder (and its grain workers) onto the next retry's CPU.
        timeout=420,
    )
    for line in out.stdout.splitlines():
        if line.startswith("RATE "):
            return float(line.split()[1])
    raise RuntimeError(
        f"grain decode subprocess failed: {out.stderr[-400:]}"
    )


def bench_fed_resnet50(
    split: Path, root: Path, batch: int = BATCH_FED
) -> tuple[float, dict | None]:
    """ResNet50 steps with the tpk pipeline actually feeding — the honest
    epoch-wall-clock shape (BASELINE.md's 69 s/epoch includes FFCV decode),
    at the recipe batch (512, dp_imagenet_ffcv.yaml). Also returns the
    prefetch engine's per-stage wall-time breakdown for the LAST timed
    epoch (decode-wait / transfer / consumer-wait), so the BENCH record
    says where the remaining fed-path time goes."""
    from turboprune_tpu.data.native import TpkImageLoader

    step, state, warm_batch = _make_step("resnet50", batch)
    state, metrics = step(state, warm_batch)  # compile outside timing
    float(metrics["loss_sum"])

    loader = TpkImageLoader(
        root / "train.tpk", total_batch_size=batch, train=True, image_size=224
    )
    n, t = 0, 0.0
    for epoch in range(3):  # epoch 0 discarded (buffer warmup)
        t0 = time.perf_counter()
        count = 0
        for images, labels in loader:
            state, metrics = step(state, (images, labels))
            count += images.shape[0]
        float(metrics["loss_sum"])  # sync before closing the epoch timer
        dt = time.perf_counter() - t0
        if epoch > 0:
            n += count
            t += dt
    return n / t, loader.last_pipeline_stats


def bench_scan_chunk_sweep(
    root: Path, batch: int = 256, ks: tuple = (1, 4, 8)
) -> dict:
    """Chunk-size sweep on the streamed train path: resnet18 fed by the tpk
    pipeline, with K prefetched batches fused into one compiled ``lax.scan``
    dispatch (train/steps.py make_scan_chunk). Reports img/s and the host
    dispatch count per epoch for each K — the dispatch count drops by K×
    while the pipeline refills behind the running scan — plus the engine's
    stage-time breakdown at the largest K."""
    from turboprune_tpu.data.native import TpkImageLoader
    from turboprune_tpu.models import create_model
    from turboprune_tpu.train import (
        create_optimizer,
        create_schedule,
        create_train_state,
        make_scan_chunk,
        make_train_step,
    )

    model = create_model(
        "resnet18", num_classes=1000, dataset_name="ImageNet",
        compute_dtype=jnp.bfloat16,
    )
    schedule = create_schedule(
        "TriangularSchedule", base_lr=0.2, epochs=90, steps_per_epoch=1251
    )
    tx = create_optimizer("SGD", schedule, momentum=0.9, weight_decay=1e-4)
    raw = make_train_step(model, tx, schedule)
    step = jax.jit(raw, donate_argnums=0)
    scan = jax.jit(make_scan_chunk(raw), donate_argnums=0)

    loader = TpkImageLoader(
        root / "train.tpk", total_batch_size=batch, train=True, image_size=224
    )
    fields: dict = {}
    for k in ks:
        # Fresh state per K: donation consumed the previous one's buffers.
        state = create_train_state(
            model, tx, jax.random.PRNGKey(k), (1, 224, 224, 3)
        )
        n, t = 0, 0.0
        for epoch in range(2):  # epoch 0 discarded (compile + warmup)
            dispatches = 0
            count = 0
            t0 = time.perf_counter()
            it = iter(loader) if k == 1 else loader.iter_chunks(k)
            for images, labels in it:
                if images.ndim == 5:
                    state, metrics = scan(state, (images, labels))
                    count += images.shape[0] * images.shape[1]
                else:
                    state, metrics = step(state, (images, labels))
                    count += images.shape[0]
                dispatches += 1
            float(metrics["loss_sum"])  # value-fetch sync (module docstring)
            dt = time.perf_counter() - t0
            if epoch > 0:
                n += count
                t += dt
        fields[f"scan_chunk_k{k}_img_per_sec"] = round(n / t, 1)
        fields[f"scan_chunk_k{k}_dispatches_per_epoch"] = dispatches
    fields["scan_chunk_batch"] = batch
    stats = loader.last_pipeline_stats
    if stats:
        fields["scan_chunk_pipeline"] = {
            key: (round(v, 4) if isinstance(v, float) else v)
            for key, v in stats.items()
        }
    return fields


# ------------------------------------------------------------- serving
def bench_serving() -> dict:
    """The serve/ subsystem end-to-end on the chip: a pruned resnet18
    (ImageNet shape, density 0.5) behind the dynamic batcher, hammered by
    concurrent single/multi-row clients. Reports sustained img/s and the
    caller-observed p50/p99 latency, plus the compile-cache accounting that
    proves ZERO steady-state recompiles (all traffic lands on the buckets
    compiled during warmup)."""
    import threading

    from turboprune_tpu.models import create_model
    from turboprune_tpu.ops import masking
    from turboprune_tpu.serve import DynamicBatcher, InferenceEngine, ServeMetrics
    from turboprune_tpu.train.state import init_variables

    buckets = (1, 8, 32, 128)
    model = create_model(
        "resnet18", num_classes=1000, dataset_name="ImageNet",
        compute_dtype=jnp.bfloat16,
    )
    # graftlint: disable=rng-key-reuse -- fixed seed on purpose: serve the same pruned weights every bench round
    variables = init_variables(model, jax.random.PRNGKey(0), (1, 224, 224, 3))
    params = variables["params"]
    masks = masking.make_masks(params)
    # Magnitude-prune to density 0.5: serve what the repo trains — a pruned
    # checkpoint, not a dense one.
    scores = masking.mask_where(
        masks, lambda m, p: jnp.abs(p) * m.astype(p.dtype), params
    )
    masks = masking.global_threshold_mask(scores, masks, density=0.5)

    metrics = ServeMetrics()
    engine = InferenceEngine(
        model, params, masks, variables.get("batch_stats", {}),
        input_shape=(224, 224, 3), buckets=buckets, metrics=metrics,
    )
    engine.warmup()
    warm_misses = int(metrics.counter("compile_cache_misses_total"))
    batcher = DynamicBatcher(
        engine, max_batch=128, max_wait_ms=2.0, queue_depth=2048,
        metrics=metrics,
    ).start()

    rng = np.random.default_rng(0)
    sizes = [1, 2, 4, 8]  # mixed request sizes, like real traffic
    reqs_per_client, n_clients = 24, 12
    images = {
        s: rng.standard_normal((s, 224, 224, 3), dtype=np.float32)
        for s in sizes
    }
    # Prime the batcher path once so the timed window is steady-state.
    batcher.predict(images[1], timeout=120)

    def client(cid: int):
        for i in range(reqs_per_client):
            batcher.predict(images[sizes[(cid + i) % len(sizes)]], timeout=120)

    threads = [
        threading.Thread(target=client, args=(c,)) for c in range(n_clients)
    ]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    batcher.close()

    total_images = sum(
        images[sizes[(c + i) % len(sizes)]].shape[0]
        for c in range(n_clients)
        for i in range(reqs_per_client)
    )
    misses = int(metrics.counter("compile_cache_misses_total"))
    return {
        "serving_img_per_sec": round(total_images / wall, 1),
        "serving_p50_ms": round(metrics.latency_quantile_ms(0.5), 3),
        "serving_p99_ms": round(metrics.latency_quantile_ms(0.99), 3),
        "serving_compile_cache_hits": int(
            metrics.counter("compile_cache_hits_total")
        ),
        "serving_steady_state_recompiles": misses - warm_misses,
        "serving_buckets": list(buckets),
        "serving_density": round(float(engine.density), 3),
    }


# ------------------------------------------------------------ compaction
def _tree_leaf(tree, path):
    for k in path:
        tree = tree[k]
    return tree


def _channel_structured_masks(params, graph, kill_frac: float, spaces=None):
    """Kill the kill_frac smallest-L2 fan-out slices of every compactable
    space; everything else stays dense. The channel structure compaction
    needs — scattered unstructured zeros would compact to nothing.
    ``spaces``: optional name predicate restricting which spaces are killed
    (the mixed_plan stage kills only conv spaces, leaving the fc stack to
    the gathered path)."""
    from turboprune_tpu.ops import masking

    masks = jax.tree.map(
        lambda m: None if m is None else np.array(m),
        masking.make_masks(params),
        is_leaf=lambda v: v is None,
    )
    for name, sp in graph.spaces.items():
        if spaces is not None and not spaces(name):
            continue
        node = masks
        for k in sp.producer.kernel[:-1]:
            node = node[k]
        kernel = np.asarray(
            jax.device_get(_tree_leaf(params, sp.producer.kernel)),
            np.float32,
        )
        norms = np.sqrt(
            (kernel.reshape(-1, kernel.shape[-1]) ** 2).sum(axis=0)
        )
        order = np.argsort(norms)
        m = node[sp.producer.kernel[-1]]
        m[..., order[: int(len(order) * kill_frac)]] = False
    return jax.tree.map(
        lambda m: None if m is None else jnp.asarray(m), masks,
        is_leaf=lambda v: v is None,
    )


def bench_compaction() -> dict:
    """Dead-channel compaction payoff (sparse/): masked-dense vs compacted
    eval throughput across sparsity levels, plus the parity max-abs-diff.

    vgg16_bn at ImageNet shape because EVERY conv/fc hidden axis is
    compactable there (no residual joins); masks are channel-structured
    magnitude (whole fan-out slices of smallest L2 killed per space) — the
    structure compaction needs; scattered unstructured zeros would compact
    to nothing, which is exactly the point the README section documents."""
    from turboprune_tpu.models import create_model
    from turboprune_tpu.ops import masking
    from turboprune_tpu.sparse import build_graph, compact_params
    from turboprune_tpu.train.state import init_variables

    batch = 64
    model = create_model(
        "vgg16_bn", num_classes=1000, dataset_name="ImageNet",
        compute_dtype=jnp.bfloat16,
    )
    # graftlint: disable=rng-key-reuse -- fixed seed on purpose: identical weights/masks every bench round
    variables = init_variables(model, jax.random.PRNGKey(0), (1, 224, 224, 3))
    params, stats = variables["params"], variables["batch_stats"]
    graph = build_graph(model, params)
    rng = np.random.default_rng(0)
    x = jnp.asarray(
        rng.standard_normal((batch, 224, 224, 3)).astype(np.float32)
    )

    def timed(fn, *args) -> float:
        logits = fn(*args)
        float(jnp.sum(logits.astype(jnp.float32)))  # compile + value sync
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(5):
                logits = fn(*args)
            float(jnp.sum(logits.astype(jnp.float32)))
            best = min(best, (time.perf_counter() - t0) / 5)
        return best

    fields: dict = {"compaction_model": "vgg16_bn", "compaction_batch": batch}
    for frac in (0.5, 0.75, 0.9):
        masks = _channel_structured_masks(params, graph, frac)
        sparsity = masking.overall_sparsity(masks)

        def dense_fwd(p, xx, masks=masks):
            var = {
                "params": masking.apply_masks(p, masks),
                "batch_stats": stats,
            }
            return model.apply(var, xx, train=False)

        # Each sparsity level IS a new program (masks close over the jit, the
        # compacted model has different shapes) — one compile per level is
        # the thing being measured, not a retrace bug; both executables are
        # reused for the timing loops and the parity diff below.
        # graftlint: disable=retrace-hazard -- one jit per sparsity level by design: masks/widths differ per iteration, executable reused for timing + parity
        dense_jit = jax.jit(dense_fwd)
        dense_t = timed(dense_jit, params, x)

        res = compact_params(params, masks, graph, stats)
        small = create_model(
            "vgg16_bn", num_classes=1000, dataset_name="ImageNet",
            compute_dtype=jnp.bfloat16, width_overrides=res.width_overrides,
        )
        small_vars = {"params": res.params, "batch_stats": res.batch_stats}

        def small_fwd(var, xx, small=small):
            return small.apply(var, xx, train=False)

        # graftlint: disable=retrace-hazard -- one jit per sparsity level by design: the compacted model changes shape per iteration
        small_jit = jax.jit(small_fwd)
        small_t = timed(small_jit, small_vars, x)
        diff = float(
            jnp.max(
                jnp.abs(
                    dense_jit(params, x).astype(jnp.float32)
                    - small_jit(small_vars, x).astype(jnp.float32)
                )
            )
        )
        tag = f"compaction_s{int(round(sparsity))}"
        fields[f"{tag}_sparsity_pct"] = round(sparsity, 2)
        fields[f"{tag}_dense_img_per_sec"] = round(batch / dense_t, 1)
        fields[f"{tag}_compacted_img_per_sec"] = round(batch / small_t, 1)
        fields[f"{tag}_speedup"] = round(dense_t / small_t, 3)
        fields[f"{tag}_parity_max_abs_diff"] = diff
        fields[f"{tag}_params_after"] = res.report["params_after"]
        fields[f"{tag}_channels_after"] = res.report["channels_after"]
    fields["compaction_params_dense"] = res.report["params_before"]
    fields["compaction_channels_dense"] = res.report["channels_before"]
    return fields


# -------------------------------------------------------- compact train
def bench_compact_train() -> dict:
    """Compact-as-you-train payoff (sparse/train_compact.py + the harness's
    compact_train path): per-step TRAIN time — fwd+bwd+update — of the
    masked-dense model vs the physically re-instantiated small one at
    90/95% channel-structured sparsity, plus the full-coordinate round-trip
    parity of one train step (compact -> step -> expand vs the dense step
    from the identical start state).

    SGD+momentum with weight_decay=0 — the regime where the round trip is
    exact: a fully-masked coordinate sees zero data-gradient and fresh zero
    momentum, so the dense run never moves it and the anchor-restored value
    matches (README "Sparsity execution"). Kept-coordinate diffs are pure
    XLA reassociation noise, reported honestly as the measured max.
    Dropout is DISABLED for the parity leg: per-unit dropout draws cannot
    align across differently-shaped hidden axes, so with it on the diff
    measures dropout sampling, not the round trip (the same caveat the
    README documents for compact training of dropout models)."""
    from turboprune_tpu.models.vgg import VGG, VGG_CFGS
    from turboprune_tpu.ops import masking
    from turboprune_tpu.sparse import (
        build_graph,
        build_plan,
        compact_train_state,
        expand_train_state,
    )
    from turboprune_tpu.train import (
        create_optimizer,
        create_train_state,
        make_train_step,
    )

    batch = 32
    model = VGG(
        VGG_CFGS["vgg16"], 1000, batch_norm=True, dtype=jnp.bfloat16,
        dropout_rate=0.0,
    )
    tx = create_optimizer("SGD", 0.05, momentum=0.9, weight_decay=0.0)
    # graftlint: disable=rng-key-reuse -- fixed seed on purpose: identical weights every bench round
    init_key = jax.random.PRNGKey(0)
    state0 = create_train_state(model, tx, init_key, (1, 224, 224, 3))
    graph = build_graph(model, state0.params)
    rng = np.random.default_rng(0)
    batch_data = (
        jnp.asarray(rng.standard_normal((batch, 224, 224, 3)).astype(np.float32)),
        jnp.asarray(rng.integers(0, 1000, size=(batch,)).astype(np.int32)),
    )

    def timed_step(step, st) -> float:
        out, _ = step(st, batch_data)
        jax.block_until_ready(out.params)  # compile + sync
        best = float("inf")
        for _ in range(3):
            cur = st
            t0 = time.perf_counter()
            for _ in range(5):
                cur, _ = step(cur, batch_data)
            jax.block_until_ready(cur.params)
            best = min(best, (time.perf_counter() - t0) / 5)
        return best

    fields: dict = {
        "compact_train_model": "vgg16_bn",
        "compact_train_batch": batch,
    }
    plan = None
    for frac in (0.9, 0.95):
        masks = _channel_structured_masks(state0.params, graph, frac)
        st = state0.replace(masks=masks, opt_state=tx.init(state0.params))
        sparsity = masking.overall_sparsity(masks)

        # Each sparsity level IS a new program (masks close over the dense
        # step via the state, the compacted model has different shapes) —
        # one compile per level is the thing being measured; both
        # executables are reused for the timing loops and the parity diff.
        # graftlint: disable=retrace-hazard -- one jit per sparsity level by design: widths differ per iteration, executable reused for timing + parity
        dense_step = jax.jit(make_train_step(model, tx))
        dense_t = timed_step(dense_step, st)

        plan = build_plan(st.params, st.masks, graph, st.batch_stats)
        small_model = VGG(
            VGG_CFGS["vgg16"], 1000, batch_norm=True, dtype=jnp.bfloat16,
            dropout_rate=0.0,
            width_overrides=tuple(sorted(plan.width_overrides.items())),
        )
        # graftlint: disable=retrace-hazard -- one jit per sparsity level by design: the compacted model changes shape per iteration
        small_step = jax.jit(make_train_step(small_model, tx))
        small_st = compact_train_state(st, plan)
        small_t = timed_step(small_step, small_st)

        # One-step round trip, compared in FULL coordinates.
        dense_after, _ = dense_step(st, batch_data)
        small_after, _ = small_step(small_st, batch_data)
        restored = expand_train_state(small_after, plan, anchor=st)
        diff = max(
            jax.tree.leaves(
                jax.tree.map(
                    lambda a, b: float(
                        jnp.max(
                            jnp.abs(
                                jnp.asarray(a, jnp.float32)
                                - jnp.asarray(b, jnp.float32)
                            )
                        )
                    ),
                    dense_after.params,
                    restored.params,
                )
            )
        )
        tag = f"compact_train_s{int(round(sparsity))}"
        fields[f"{tag}_sparsity_pct"] = round(sparsity, 2)
        fields[f"{tag}_dense_step_ms"] = round(dense_t * 1e3, 2)
        fields[f"{tag}_compacted_step_ms"] = round(small_t * 1e3, 2)
        fields[f"{tag}_speedup"] = round(dense_t / small_t, 3)
        fields[f"{tag}_roundtrip_parity_max_abs_diff"] = diff
        fields[f"{tag}_params_after"] = plan.report["params_after"]
    fields["compact_train_params_dense"] = plan.report["params_before"]
    return fields


# ----------------------------------------------------------- n:m frontier
def bench_nm_frontier() -> dict:
    """N:M gathered execution vs channel compaction (sparse/nm.py +
    sparse/nm_execute.py): the accuracy-proxy-vs-throughput frontier of
    masked-dense / gathered 2:4 / gathered 4:8 / channel-compacted on
    deit_tiny (full train step: fwd+bwd+update) plus the resnet18 fc head
    (1000-class layer, fwd+bwd) — per-step CPU milliseconds.

    Runs CPU-pinned (see the stage wrapper): these are CPU milliseconds,
    not a speed of this system on the chip. The accuracy
    axis is the kept-|w| fraction of each technique's final mask over the
    dense weights — an honesty note, not trained accuracy: projection cost
    in real accuracy terms needs the harness's full IMP budget.

    Per ISSUE-10 satellite 6 the record carries per-layer routing coverage
    (routed vs unrouted-eligible layer names) so a silent masked-dense
    fallback is visible in the artifact, and the executable cache size
    after the timing loop, proving zero steady-state recompiles within a
    level."""
    from turboprune_tpu.models import create_model
    from turboprune_tpu.ops import masking
    from turboprune_tpu.pruning.criteria import prune_mag
    from turboprune_tpu.sparse import (
        build_graph,
        build_nm_plan,
        build_plan,
        compact_train_state,
        project_masks,
    )
    from turboprune_tpu.train import (
        create_optimizer,
        create_train_state,
        make_train_step,
    )

    batch, image = 16, 64
    model_name = "deit_tiny_patch16_224"
    model = create_model(
        model_name, num_classes=1000, dataset_name="ImageNet",
        compute_dtype=jnp.float32,
    )
    tx = create_optimizer("SGD", 0.05, momentum=0.9, weight_decay=0.0)
    # graftlint: disable=rng-key-reuse -- fixed seed on purpose: identical weights/masks every bench round
    state0 = create_train_state(model, tx, jax.random.PRNGKey(0), (1, image, image, 3))
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((batch, image, image, 3)).astype(np.float32))
    batch_data = (
        x, jnp.asarray(rng.integers(0, 1000, size=(batch,)).astype(np.int32))
    )

    def flat(tree):
        return jax.tree_util.tree_flatten_with_path(
            tree, is_leaf=lambda v: v is None
        )[0]

    def kept_mag_frac(masks) -> float:
        """sum |w| surviving the mask / sum |w|, over maskable leaves — the
        frontier's accuracy proxy, one yardstick for every technique."""
        num = den = 0.0
        for (_, m), (_, p) in zip(flat(masks), flat(state0.params)):
            if m is None:
                continue
            a = jnp.abs(p.astype(jnp.float32))
            num += float(jnp.sum(a * m.astype(jnp.float32)))
            den += float(jnp.sum(a))
        return num / den

    def timed_step(step, st) -> float:
        out, _ = step(st, batch_data)
        jax.block_until_ready(out.params)  # compile + sync
        best = float("inf")
        for _ in range(2):
            cur = st
            t0 = time.perf_counter()
            for _ in range(4):
                cur, _ = step(cur, batch_data)
            jax.block_until_ready(cur.params)
            best = min(best, (time.perf_counter() - t0) / 4)
        return best

    mag_masks = prune_mag(
        state0.params, masking.make_masks(state0.params), 0.25
    )
    fields: dict = {
        "nm_frontier_model": model_name,
        "nm_frontier_batch": batch,
        "nm_frontier_image": image,
    }
    st = state0.replace(masks=mag_masks, opt_state=tx.init(state0.params))
    dense_step = jax.jit(make_train_step(model, tx))
    dense_t = timed_step(dense_step, st)
    fields["nm_frontier_dense_step_ms"] = round(dense_t * 1e3, 2)
    fields["nm_frontier_dense_sparsity_pct"] = round(
        masking.overall_sparsity(mag_masks), 2
    )
    fields["nm_frontier_dense_magnitude_frac"] = round(
        kept_mag_frac(mag_masks), 4
    )

    for pat in ("2:4", "4:8"):
        n, m = (int(v) for v in pat.split(":"))
        pmasks, _ = project_masks(state0.params, mag_masks, n, m)
        plan = build_nm_plan(model, pmasks)
        nm_model = create_model(
            model_name, num_classes=1000, dataset_name="ImageNet",
            compute_dtype=jnp.float32, nm_overrides=plan.overrides,
        )
        # One jit per pattern by design: the index maps are module metadata,
        # so each pattern IS a different program; the executable is reused
        # for the timing loop and the cache-size check below.
        # graftlint: disable=retrace-hazard -- one jit per N:M pattern by design: index maps are compile-time metadata, executable reused across the timing loop
        nm_step = jax.jit(make_train_step(nm_model, tx))
        stp = state0.replace(masks=pmasks, opt_state=tx.init(state0.params))
        nm_t = timed_step(nm_step, stp)
        masked = masking.apply_masks(state0.params, pmasks)
        parity = float(
            jnp.max(
                jnp.abs(
                    model.apply({"params": masked}, x, train=False)
                    - nm_model.apply({"params": masked}, x, train=False)
                )
            )
        )
        rep = plan.report
        routed = sorted(
            name for name, r in rep["layers"].items() if r["routed"]
        )
        unrouted = sorted(
            name for name, r in rep["layers"].items() if not r["routed"]
        )
        tag = f"nm_frontier_{pat.replace(':', '_')}"
        fields[f"{tag}_step_ms"] = round(nm_t * 1e3, 2)
        fields[f"{tag}_speedup_vs_masked_dense"] = round(dense_t / nm_t, 3)
        fields[f"{tag}_sparsity_pct"] = round(
            masking.overall_sparsity(pmasks), 2
        )
        fields[f"{tag}_magnitude_frac"] = round(kept_mag_frac(pmasks), 4)
        fields[f"{tag}_coverage_frac"] = round(rep["coverage_frac"], 4)
        fields[f"{tag}_routed_layers"] = len(routed)
        fields[f"{tag}_unrouted_eligible"] = unrouted
        fields[f"{tag}_fwd_parity_max_abs_diff"] = parity
        fields[f"{tag}_steady_state_recompiles"] = nm_step._cache_size() - 1

    # Channel-compaction comparator: the OTHER execution backend, at the
    # structured masks it needs (whole mlp-hidden/embed slices dead).
    graph = build_graph(model, state0.params)
    cmasks = _channel_structured_masks(state0.params, graph, 0.5)
    cplan = build_plan(state0.params, cmasks, graph, state0.batch_stats)
    small_model = create_model(
        model_name, num_classes=1000, dataset_name="ImageNet",
        compute_dtype=jnp.float32, width_overrides=cplan.width_overrides,
    )
    small_step = jax.jit(make_train_step(small_model, tx))
    st_c = state0.replace(masks=cmasks, opt_state=tx.init(state0.params))
    small_t = timed_step(small_step, compact_train_state(st_c, cplan))
    fields["nm_frontier_compact_step_ms"] = round(small_t * 1e3, 2)
    fields["nm_frontier_compact_speedup_vs_masked_dense"] = round(
        dense_t / small_t, 3
    )
    fields["nm_frontier_compact_sparsity_pct"] = round(
        masking.overall_sparsity(cmasks), 2
    )
    fields["nm_frontier_compact_magnitude_frac"] = round(
        kept_mag_frac(cmasks), 4
    )

    # resnet18 head: the 512 -> 1000 fc at ImageNet classes, fwd+bwd — the
    # CNN-head case where the gathered path applies (conv trunk dominates a
    # full resnet step on CPU, so the head is measured in isolation).
    import flax.linen as nn

    from turboprune_tpu.sparse.nm_execute import NMDense

    hb, hi, ho = 256, 512, 1000
    xh = jnp.asarray(rng.standard_normal((hb, hi)).astype(np.float32))
    # graftlint: disable=rng-key-reuse -- fixed seed on purpose: identical head weights every round
    wk = jax.random.normal(jax.random.PRNGKey(1), (hi, ho), jnp.float32) * 0.05
    head_tree = {"fc": {"kernel": wk, "bias": jnp.zeros((ho,))}}
    hmask = prune_mag(head_tree, masking.make_masks(head_tree), 0.25)

    def timed_grad(loss) -> float:
        g = jax.jit(jax.value_and_grad(loss))
        v, _ = g(head_tree)
        float(v)
        best = float("inf")
        for _ in range(2):
            t0 = time.perf_counter()
            for _ in range(4):
                v, _ = g(head_tree)
            float(v)
            best = min(best, (time.perf_counter() - t0) / 4)
        return best

    def dense_loss(p):
        masked = masking.apply_masks(p, hmask)
        y = nn.Dense(ho).apply(
            {"params": masked["fc"]}, xh
        )
        return (y**2).sum()

    hd_t = timed_grad(dense_loss)
    fields["nm_frontier_r18head_dense_ms"] = round(hd_t * 1e3, 3)
    for pat in ("2:4", "4:8"):
        n, m = (int(v) for v in pat.split(":"))
        pm, _ = project_masks(head_tree, hmask, n, m)
        m2 = np.asarray(jax.device_get(pm["fc"]["kernel"]))
        ki = tuple(int(v) for v in np.nonzero(m2.any(axis=1))[0])
        lo = np.nonzero(m2.any(axis=0))[0]
        ko = tuple(int(v) for v in lo) if len(lo) < ho else None
        nmd = NMDense(features=ho, kept_in=ki, kept_out=ko)

        def nm_loss(p, pm=pm, nmd=nmd):
            masked = masking.apply_masks(p, pm)
            return (nmd.apply({"params": masked["fc"]}, xh) ** 2).sum()

        hn_t = timed_grad(nm_loss)
        tag = f"nm_frontier_r18head_{pat.replace(':', '_')}"
        fields[f"{tag}_ms"] = round(hn_t * 1e3, 3)
        fields[f"{tag}_speedup_vs_masked_dense"] = round(hd_t / hn_t, 3)
    return fields


# ------------------------------------------------------------- mixed plan
def bench_mixed_plan() -> dict:
    """One planner, four backends (sparse/plan.py): a HETEROGENEOUS-mask
    model — dead conv channels (compaction's structure) plus a scattered
    in-axis 2:4 pattern on the fc stack (gathering's structure) — timed as
    a full train step under every backend the planner can emit:
    masked-dense, compact-only, nm-only, and the MIXED plan that routes
    each layer to whichever backend its own mask population pays for.

    Every variant is produced by plan_execution with per-variant forced
    modes — the planner is the only code deciding widths/index maps, so
    the bench exercises the exact decision path the harness and the
    serving engine run. The mixed record carries the machine-readable
    per-layer decision table (backend + reason + cost-model est_gain),
    the compaction commit decision, the unrouted-eligible layer names,
    forward/grad parity vs masked-dense, and the per-variant steady-state
    recompile count (jit cache size - 1 after the timing loop).

    CPU-pinned subprocess (see the stage wrapper): the win being measured
    is reduced GEMM width + sliced conv channels, which is chip-agnostic;
    the fc stack is deliberately wide (3136 -> 512 -> 512) so the gathered
    path's contribution is visible next to the conv slicing."""
    from turboprune_tpu.models.vgg import VGG
    from turboprune_tpu.ops import masking
    from turboprune_tpu.sparse import (
        build_graph,
        compact_train_state,
        plan_execution,
        project_masks,
    )
    from turboprune_tpu.sparse.compact import (
        compact_stats,
        compact_tree,
        expand_tree,
    )
    from turboprune_tpu.train import (
        create_optimizer,
        create_train_state,
        make_train_step,
    )

    batch, image = 16, 32
    cfg = [16, "M", 32, "M", 32, 32, "M", 64, 64, "M", 64, 64, "M"]

    def make_model(width_overrides=None, nm_overrides=None):
        return VGG(
            cfg, 100, batch_norm=True, fc_features=(512, 512),
            dropout_rate=0.0,
            width_overrides=(
                tuple(sorted(dict(width_overrides).items()))
                if width_overrides else None
            ),
            nm_overrides=nm_overrides,
        )

    model = make_model()
    tx = create_optimizer("SGD", 0.05, momentum=0.9, weight_decay=0.0)
    state0 = create_train_state(
        # graftlint: disable=rng-key-reuse -- fixed seed on purpose: identical weights/masks every bench round
        model, tx, jax.random.PRNGKey(0), (1, image, image, 3)
    )
    graph = build_graph(model, state0.params)
    rng = np.random.default_rng(0)
    x = jnp.asarray(
        rng.standard_normal((batch, image, image, 3)).astype(np.float32)
    )
    batch_data = (
        x, jnp.asarray(rng.integers(0, 100, size=(batch,)).astype(np.int32))
    )

    # Heterogeneous masks: kill half of every CONV channel space (smallest
    # fan-out L2), then project the fc stack in-axis 2:4 — in-axis only,
    # so the fc widths stay live and the fc population is purely the
    # gathered path's structure, not compaction's.
    masks = _channel_structured_masks(
        state0.params, graph, 0.5, spaces=lambda name: name.startswith("conv")
    )
    masks, _ = project_masks(state0.params, masks, 2, 4, transposable=False)
    st = state0.replace(masks=masks, opt_state=tx.init(state0.params))
    folded = masking.apply_masks(state0.params, masks)

    def timed_step(step, stv) -> float:
        out, _ = step(stv, batch_data)
        jax.block_until_ready(out.params)  # compile + sync
        best = float("inf")
        for _ in range(2):
            cur = stv
            t0 = time.perf_counter()
            for _ in range(4):
                cur, _ = step(cur, batch_data)
            jax.block_until_ready(cur.params)
            best = min(best, (time.perf_counter() - t0) / 4)
        return best

    fields: dict = {
        "mixed_plan_model": "vgg_small_fc512",
        "mixed_plan_batch": batch,
        "mixed_plan_image": image,
        "mixed_plan_sparsity_pct": round(masking.overall_sparsity(masks), 2),
    }

    # (variant, compact mode, nm mode, autotune) — every backend decision
    # below comes out of the one planner, never hand-assembled.
    variants = (
        ("masked", "off", "off", "off"),
        ("compact", "force", "off", "off"),
        ("nm", "off", "auto", "off"),
        ("mixed", "auto", "auto", "cost"),
    )
    step_ms: dict[str, float] = {}
    mixed_plan = None
    for name, cmode, nmode, tune in variants:
        plan = plan_execution(
            model, st.params, st.masks, st.batch_stats,
            model_factory=make_model, compact=cmode, nm=nmode,
            compact_min_savings=0.0, autotune=tune,
        )
        exec_model = (
            make_model(
                plan.width_overrides,
                plan.nm.as_override_tuple() if plan.nm else None,
            )
            if (plan.width_overrides or plan.nm_overrides) else model
        )
        # device_put: compact_train_state returns numpy (uncommitted)
        # leaves, and the jit cache keys on committed-ness — without it the
        # first chained step counts as a spurious "recompile".
        stv = (
            jax.device_put(compact_train_state(st, plan.compaction))
            if plan.compaction else st
        )
        # Each variant IS a different program (widths/index maps are module
        # metadata) — one compile per variant is the thing being measured.
        # graftlint: disable=retrace-hazard -- one jit per planner variant by design: widths/index maps differ per variant, executable reused across the timing loop
        step = jax.jit(make_train_step(exec_model, tx))
        t = timed_step(step, stv)
        step_ms[name] = t
        fields[f"mixed_plan_{name}_step_ms"] = round(t * 1e3, 2)
        fields[f"mixed_plan_{name}_steady_state_recompiles"] = (
            step._cache_size() - 1
        )
        if name != "masked":
            fields[f"mixed_plan_{name}_speedup_vs_masked"] = round(
                step_ms["masked"] / t, 3
            )
        if name == "mixed":
            mixed_plan = plan

            # Forward parity vs masked-dense on the SAME folded weights.
            p_small = compact_tree(folded, plan.compaction)
            s_small = compact_stats(st.batch_stats, plan.compaction)
            y_dense = model.apply(
                {"params": folded, "batch_stats": st.batch_stats},
                x, train=False,
            )
            y_mixed = exec_model.apply(
                {"params": p_small, "batch_stats": s_small}, x, train=False
            )
            fields["mixed_plan_fwd_parity_max_abs_diff"] = float(
                jnp.max(jnp.abs(y_dense - y_mixed))
            )

            # Grad parity over MATERIALIZED coordinates (removed coords
            # are frozen by design; the harness's anchor expansion carries
            # them — see tests/test_plan.py).
            m_small = compact_tree(masks, plan.compaction)

            def dense_loss(p):
                var = {
                    "params": masking.apply_masks(p, masks),
                    "batch_stats": st.batch_stats,
                }
                return (model.apply(var, x, train=False) ** 2).sum()

            def mixed_loss(p):
                var = {
                    "params": masking.apply_masks(p, m_small),
                    "batch_stats": s_small,
                }
                return (exec_model.apply(var, x, train=False) ** 2).sum()

            g_d = jax.grad(dense_loss)(state0.params)
            g_m = jax.grad(mixed_loss)(compact_tree(state0.params, plan.compaction))
            ind = expand_tree(
                jax.tree.map(np.ones_like, g_m), plan.compaction
            )
            g_m_full = expand_tree(g_m, plan.compaction)
            fields["mixed_plan_grad_parity_max_abs_diff"] = max(
                jax.tree.leaves(
                    jax.tree.map(
                        lambda a, b, i: float(
                            np.max(np.abs(np.asarray(a) * i - np.asarray(b)))
                        ),
                        g_d, g_m_full, ind,
                    )
                )
            )

    # The headline claim: the planner's mix is at least as fast as the
    # best single backend it could have chosen.
    best_single = min(step_ms["masked"], step_ms["compact"], step_ms["nm"])
    fields["mixed_plan_best_single_ms"] = round(best_single * 1e3, 2)
    fields["mixed_plan_mixed_vs_best_single"] = round(
        best_single / step_ms["mixed"], 3
    )

    # Machine-readable decision table for the mixed plan: every per-layer
    # call (backend + reason + cost-model gain) and the compaction commit.
    rep = mixed_plan.report
    fields["mixed_plan_kind"] = rep["kind"]
    fields["mixed_plan_compaction_decision"] = mixed_plan.decisions[
        "compaction"
    ]
    fields["mixed_plan_decision_table"] = mixed_plan.decisions["layers"]
    fields["mixed_plan_backend_counts"] = rep["backend_counts"]
    fields["mixed_plan_coverage_frac"] = round(rep["coverage_frac"], 4)
    fields["mixed_plan_unrouted_eligible"] = sorted(
        name
        for name, r in (rep["nm"] or {"layers": {}})["layers"].items()
        if not r["routed"]
    )
    return fields


# ----------------------------------------------------------- serving load
def bench_serving_load() -> dict:
    """Open-loop load sweep against the FLEET engine (serve/fleet/ +
    serve/loadgen.py), CPU-pinned subprocess like nm_frontier.

    Builds a 3-level synthetic fleet (dense / channel-structured /
    2:4-projected — the engines can't tell these apart from trained
    checkpoints), measures closed-loop capacity, then offers Poisson
    traffic at 0.3x / 0.7x / 1.5x capacity and reports p50/p99/p99.9,
    goodput, sheds, and the detected saturation knee. Honesty convention:
    ``serving_load_knee_rps`` is null when no point saturated — a knee is
    a DETECTED number, never a default."""
    import shutil
    import tempfile

    from turboprune_tpu.config.compose import compose
    from turboprune_tpu.models import create_model
    from turboprune_tpu.ops import masking
    from turboprune_tpu.serve import (
        AOTExecutableCache,
        FleetEngine,
        ModelRegistry,
        sweep_offered_load,
    )
    from turboprune_tpu.sparse import build_graph
    from turboprune_tpu.sparse.nm import project_masks
    from turboprune_tpu.train.state import init_variables
    from turboprune_tpu.utils.checkpoint import (
        ExperimentCheckpoints,
        save_model_tree,
    )
    from turboprune_tpu.utils.experiment import save_config

    base = Path(tempfile.mkdtemp(prefix="turboprune_fleet_bench_"))
    fleet = None
    try:
        expt_dir = base / "fleet_expt"
        expt_dir.mkdir()
        cfg = compose(
            "cifar10_imp",
            overrides=[
                f"experiment_params.base_dir={base}",
                "experiment_params.training_precision=float32",
                "dataset_params.dataloader_type=synthetic",
                "dataset_params.total_batch_size=16",
                "model_params.model_name=resnet18",
            ],
        )
        save_config(str(expt_dir), cfg)
        model = create_model("resnet18", 10, "CIFAR10", jnp.float32)
        variables = init_variables(
            # graftlint: disable=rng-key-reuse -- synthetic fixture weights; never trained, never compared across seeds
            model, jax.random.PRNGKey(0), (1, 32, 32, 3)
        )
        params = variables["params"]
        batch_stats = variables.get("batch_stats", {})
        dense = masking.make_masks(params)
        graph = build_graph(model, params)
        channel = _channel_structured_masks(params, graph, 0.5)
        nm_masks, _ = project_masks(params, dense, 2, 4, transposable=True)
        ckpts = ExperimentCheckpoints(expt_dir)
        ckpts.checkpoints_dir.mkdir(parents=True, exist_ok=True)
        for lvl, masks in enumerate((dense, channel, nm_masks)):
            save_model_tree(
                ckpts.level_path(lvl),
                {
                    "params": params,
                    "masks": masks,
                    "batch_stats": batch_stats,
                },
            )
        fleet = FleetEngine(
            ModelRegistry([expt_dir]),
            buckets=(1, 8),
            max_batch=8,
            max_wait_ms=2.0,
            queue_depth=64,
            aot_cache=AOTExecutableCache(base / "aot"),
        )
        rng = np.random.default_rng(0)

        def img(n):
            return rng.standard_normal((n, 32, 32, 3)).astype(np.float32)

        # Page in + compile every model once: the sweep measures steady
        # state, and the per-model backends prove real multi-tenancy.
        backends = {}
        for model_id in fleet.registry.ids():
            fleet.predict(img(1), model=model_id, timeout=600)
        for model_id, row in fleet.info()["models"].items():
            backends[model_id] = row["backend"]

        # Closed-loop capacity of the default route (rows/s through the
        # batcher) calibrates the offered-load points.
        t0 = time.perf_counter()
        rows = 0
        while time.perf_counter() - t0 < 2.0:
            fleet.predict(img(8), timeout=600)
            rows += 8
        capacity = rows / (time.perf_counter() - t0)

        probe_future, resident = fleet.submit(img(1))
        probe_future.result(timeout=600)
        result = sweep_offered_load(
            lambda: (lambda: fleet.submit(img(1))[0]),
            rps_list=[
                max(1.0, round(capacity * f, 1)) for f in (0.3, 0.7, 1.5)
            ],
            duration_s=2.0,
            seed=0,
            settle_s=0.5,
            drain_timeout_s=20.0,
            depth_probe=lambda: resident.batcher.queue_depth,
        )
        points = [
            {
                k: (round(v, 2) if isinstance(v, float) else v)
                for k, v in p.items()
            }
            for p in result["points"]
        ]
        return {
            "serving_load_capacity_rps": round(capacity, 1),
            "serving_load_models": backends,
            "serving_load_points": points,
            # null (never 0.0) when the sweep stayed healthy end-to-end
            "serving_load_knee_rps": result["knee_rps"],
            "serving_load_saturated": result["saturated"],
        }
    finally:
        if fleet is not None:
            fleet.close()
        shutil.rmtree(base, ignore_errors=True)


# ------------------------------------------------------- flash attention
def bench_flash_attention() -> dict:
    """Pallas flash vs dense attention, fwd+bwd, on the REAL chip — the
    committed proof that Mosaic lowering works outside interpret mode
    (compiled, never interpreted). deit_small-shaped heads (6 x 64) at
    S=1024, batch 8 -> [48, 1024, 64]."""
    from turboprune_tpu.ops.flash import flash_attention

    bh, s_len, d = 48, 1024, 64
    scale = d**-0.5
    # graftlint: disable=rng-key-reuse -- fixed seed on purpose: identical attention inputs every round
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (
        jax.random.normal(key, (bh, s_len, d), jnp.bfloat16) for key in ks
    )
    valid = jnp.ones((1, s_len), jnp.float32)

    def flash_loss(q, k, v):
        o = flash_attention(q, k, v, valid, scale, interpret=False)
        return o.astype(jnp.float32).sum()

    def dense_loss(q, k, v):
        # bf16 operands + fp32 accumulation — the SAME numeric contract as
        # the model's dense attention path and the flash kernel, so the
        # speedup is measured against the program flash actually replaces
        # (an fp32-upcast baseline would run off the bf16 MXU path and
        # flatter the kernel).
        s = jnp.einsum(
            "bqd,bkd->bqk", q * scale, k,
            preferred_element_type=jnp.float32,
        )
        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        out = jnp.einsum(
            "bqk,bkd->bqd", p, v, preferred_element_type=jnp.float32
        )
        return out.sum()

    def timed(loss_fn) -> float:
        g = jax.jit(jax.grad(loss_fn, argnums=(0, 1, 2)))
        dq, _, _ = g(q, k, v)
        float(dq[0, 0, 0])  # compile + real sync (value fetch)
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(10):
                dq, dk, dv = g(q, k, v)
            float(dq[0, 0, 0])
            best = min(best, (time.perf_counter() - t0) / 10)
        return best

    t_flash = timed(flash_loss)
    t_dense = timed(dense_loss)
    return {
        "flash_fwdbwd_ms": round(t_flash * 1e3, 3),
        "dense_fwdbwd_ms": round(t_dense * 1e3, 3),
        "flash_vs_dense_speedup": round(t_dense / t_flash, 3),
        "flash_shape": f"bh{bh}xS{s_len}xD{d}",
    }


def _log(msg: str) -> None:
    print(f"[bench +{time.monotonic() - _T0:7.1f}s] {msg}", file=sys.stderr, flush=True)


_T0 = time.monotonic()


_watchdog = None
_partial: dict = {}  # stage results gathered so far, reported if we stall


def _arm_watchdog(seconds: int = 480) -> None:
    """A device op can block forever; run_stage catches exceptions, not
    hangs, so without this the bench would hang and the round would record
    NO result at all. Re-armed after every stage: if the
    CURRENT stage hasn't finished within ``seconds``, emit whatever was
    already measured (including stage-cache contents) as the result line
    (with an error marker) and exit."""
    import threading

    global _watchdog
    if _watchdog is not None:
        _watchdog.cancel()

    def fire():
        if _partial.get("done"):
            return  # lost the race with the final print — not a stall
        extra = dict(_partial.get("extra", {}))
        error = (
            f"watchdog: stage exceeded {seconds}s; "
            "reporting partial results"
        )
        extra["error"] = error
        print(
            json.dumps(
                _headline_record(_partial.get("img_r18"), extra, error=error)
            ),
            flush=True,
        )
        os._exit(3)

    t = threading.Timer(seconds, fire)
    t.daemon = True
    t.start()
    _watchdog = t


def _headline_record(
    img_r18, extra: dict, error: str | None = None
) -> dict:
    """The single printed JSON record. When the headline stage never ran
    (it raised and nothing was cached) value/vs_baseline are null with a
    TOP-LEVEL marker — never a fake measured-looking 0.0: a reader of the
    record must not mistake a failed stage for a measured zero
    throughput."""
    record = {
        "metric": "resnet18_imagenet224_train_throughput_1chip",
        "value": None,
        "unit": "img/s",
        "vs_baseline": None,
        "extra": extra,
    }
    # Falsy check on purpose: zero throughput is not a measurable outcome,
    # so a 0.0 here is always an artifact of a stage cache that persisted
    # one.
    if img_r18:
        record["value"] = round(img_r18, 1)
        record["vs_baseline"] = round(
            img_r18 / BASELINE_IMG_PER_SEC_PER_CHIP, 3
        )
    else:
        record["skipped"] = (
            "resnet18 headline stage not measured this run "
            "(stage error) and no cached value"
        )
    if error:
        record["error"] = error
    return record


# ------------------------------------------------------- stage persistence
def _load_stage_cache(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    # graftlint: disable=broad-except -- a missing/corrupt stage cache means a cold start by design; every stage then re-measures
    except Exception:
        return {}


def _save_stage(path: Path, cache: dict, name: str, fields: dict) -> None:
    cache[name] = {
        "fields": fields,
        "ts": datetime.now(timezone.utc).isoformat(timespec="seconds"),
    }
    tmp = path.with_suffix(".tmp")
    tmp.write_text(json.dumps(cache, indent=1))
    tmp.replace(path)


def main() -> None:
    from turboprune_tpu.utils.compile_cache import place_compile_cache

    place_compile_cache()
    platform = jax.devices()[0].platform
    if platform != "tpu":
        raise SystemExit(
            f"bench.py measures the chip and found platform {platform!r}: "
            "refusing to run (a CPU number is not a speed of this system)"
        )
    root = Path(os.environ.get("BENCH_DATA_DIR", "/tmp/turboprune_bench"))
    root.mkdir(parents=True, exist_ok=True)
    cache_path = root / "stages.json"
    force = bool(os.environ.get("BENCH_FORCE"))
    # `cache` is what gets persisted: ALWAYS seeded from disk, so a forced
    # rerun that stalls mid-run cannot clobber stages it never re-reached.
    # BENCH_FORCE only stops run_stage from REUSING the old values.
    cache = _load_stage_cache(cache_path)
    hits = {} if force else cache

    extra: dict = {}
    cached_stages: dict = {}  # name -> capture timestamp
    failed_stages: list[str] = []
    _partial["extra"] = extra

    def run_stage(name: str, fn) -> dict | None:
        """fn() -> dict of extra fields. Cached stages are reused (with
        their original timestamp surfaced); fresh results are persisted the
        moment they land so a later stall can't lose them."""
        hit = hits.get(name)
        if hit:
            extra.update(hit["fields"])
            cached_stages[name] = hit["ts"]
            extra["cached_stages"] = cached_stages
            _log(f"{name}: cached from {hit['ts']}")
            return hit["fields"]
        _arm_watchdog()
        _log(f"{name}...")
        try:
            fields = fn()
        # graftlint: disable=broad-except -- stage isolation: one failed stage must not stop the later ones from being measured; the error is recorded in extra, logged, and makes the run exit non-zero
        except Exception as e:
            extra[f"{name}_error"] = repr(e)[:200]
            failed_stages.append(name)
            _log(f"{name} error: {e!r}")
            return None
        _save_stage(cache_path, cache, name, fields)
        extra.update(fields)
        _log(f"{name} done: {fields}")
        return fields

    _arm_watchdog()
    def stage_r18() -> dict:
        img, _ = bench_train("resnet18", BATCH_R18)
        return {"resnet18_img_per_sec": round(img, 1)}

    r18 = run_stage("resnet18", stage_r18)
    # None (not 0.0) when the stage failed: the final record must show
    # null + a skipped marker, never a fake measured zero. A cached 0.0 is
    # scrubbed to None for the same reason.
    img_r18 = (r18 or {}).get("resnet18_img_per_sec") or None
    _partial["img_r18"] = img_r18

    def stage_r50() -> dict:
        img, flops = bench_train("resnet50", BATCH_R50)
        fields = {
            "resnet50_img_per_sec": round(img, 1),
            "resnet50_vs_baseline_per_chip": round(
                img / BASELINE_IMG_PER_SEC_PER_CHIP, 3
            ),
        }
        achieved = img / BATCH_R50 * flops / 1e12
        peak = _detect_peak_tflops()
        fields["resnet50_tflops_per_sec"] = round(achieved, 1)
        fields["resnet50_mfu"] = round(achieved / peak, 3)
        fields["chip_peak_tflops"] = peak
        return fields

    run_stage("resnet50", stage_r50)
    run_stage("flash_attention", bench_flash_attention)

    # Host-pipeline stages share the JPEG dataset; build it lazily only if
    # at least one of them is not already cached.
    _split: list[Path] = []

    def split_dir() -> Path:
        if not _split:
            _arm_watchdog()
            _log("jpeg dataset...")
            _split.append(_ensure_jpeg_dataset(root))
        return _split[0]

    def stage_tpk() -> dict:
        return {"tpk_decode_img_per_sec": round(bench_tpk_decode(split_dir(), root), 1)}

    def stage_grain() -> dict:
        return {"grain_decode_img_per_sec": round(bench_grain_decode(split_dir()), 1)}

    def stage_fed() -> dict:
        rate, pstats = bench_fed_resnet50(split_dir(), root)
        fields = {
            "resnet50_fed_img_per_sec": round(rate, 1),
            "fed_batch": BATCH_FED,
        }
        if pstats:
            # Per-stage pipeline wall-time breakdown (data/pipeline.py
            # stats): says whether the fed path is decode-, transfer- or
            # compute-bound on this host.
            fields["fed_pipeline"] = {
                k: (round(v, 4) if isinstance(v, float) else v)
                for k, v in pstats.items()
            }
        return fields

    def stage_scan_chunk() -> dict:
        split = split_dir()
        if not (root / "train.tpk").exists():  # tpk stage may be cached
            from turboprune_tpu.data.native import pack_imagefolder

            pack_imagefolder(split, root / "train.tpk")
        return bench_scan_chunk_sweep(root)

    run_stage("tpk_decode", stage_tpk)
    run_stage("grain_decode", stage_grain)
    run_stage("fed_resnet50", stage_fed)
    run_stage("scan_chunk_sweep", stage_scan_chunk)
    run_stage("serving", bench_serving)
    run_stage("compaction", bench_compaction)
    run_stage("compact_train", bench_compact_train)

    def stage_nm_frontier() -> dict:
        """CPU-pinned SUBPROCESS, like the grain stage: the quantity is
        per-step CPU milliseconds by definition (bench.py --nm-frontier
        runs bench_nm_frontier there), and the parent holds the chip."""
        import subprocess

        out = subprocess.run(
            [sys.executable, str(Path(__file__).resolve()), "--nm-frontier"],
            capture_output=True,
            text=True,
            cwd=str(Path(__file__).resolve().parent),
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
            timeout=420,
        )
        for line in out.stdout.splitlines():
            if line.startswith("NM_FRONTIER "):
                return json.loads(line[len("NM_FRONTIER "):])
        raise RuntimeError(
            f"nm_frontier subprocess failed: {out.stderr[-400:]}"
        )

    run_stage("nm_frontier", stage_nm_frontier)

    def stage_mixed_plan() -> dict:
        """CPU-pinned SUBPROCESS like nm_frontier: the planner's backend
        mix is compared in per-step CPU milliseconds by definition."""
        import subprocess

        out = subprocess.run(
            [sys.executable, str(Path(__file__).resolve()), "--mixed-plan"],
            capture_output=True,
            text=True,
            cwd=str(Path(__file__).resolve().parent),
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
            timeout=600,
        )
        for line in out.stdout.splitlines():
            if line.startswith("MIXED_PLAN "):
                return json.loads(line[len("MIXED_PLAN "):])
        raise RuntimeError(
            f"mixed_plan subprocess failed: {out.stderr[-400:]}"
        )

    run_stage("mixed_plan", stage_mixed_plan)

    def stage_serving_load() -> dict:
        """CPU-pinned SUBPROCESS like nm_frontier: the open-loop sweep
        measures the serving stack on host CPU by definition."""
        import subprocess

        out = subprocess.run(
            [sys.executable, str(Path(__file__).resolve()), "--serving-load"],
            capture_output=True,
            text=True,
            cwd=str(Path(__file__).resolve().parent),
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
            timeout=600,
        )
        for line in out.stdout.splitlines():
            if line.startswith("SERVING_LOAD "):
                return json.loads(line[len("SERVING_LOAD "):])
        raise RuntimeError(
            f"serving_load subprocess failed: {out.stderr[-400:]}"
        )

    run_stage("serving_load", stage_serving_load)
    extra["pipeline_host_cpu_cores"] = os.cpu_count()

    _partial["done"] = True  # fire() checks this — cancel can lose the race
    _watchdog.cancel()
    print(json.dumps(_headline_record(img_r18, extra)))
    if failed_stages:
        sys.exit(f"bench: stage(s) failed: {', '.join(failed_stages)}")


if __name__ == "__main__":
    if "--nm-frontier" in sys.argv:
        # Child mode for the nm_frontier stage (CPU-pinned by the parent).
        print("NM_FRONTIER " + json.dumps(bench_nm_frontier()), flush=True)
    elif "--mixed-plan" in sys.argv:
        # Child mode for the mixed_plan stage (CPU-pinned by the parent).
        print("MIXED_PLAN " + json.dumps(bench_mixed_plan()), flush=True)
    elif "--serving-load" in sys.argv:
        # Child mode for the serving_load stage (CPU-pinned by the parent).
        print("SERVING_LOAD " + json.dumps(bench_serving_load()), flush=True)
    else:
        main()
