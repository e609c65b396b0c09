"""Device mesh + SPMD step wiring.

The reference distributes with DDP over NCCL: one replica per GPU, bucketed
gradient allreduce inside ``loss.backward()`` (base_harness.py:81,127). The
TPU-native design is SPMD under one jit: a ``Mesh`` over all devices with a
``data`` axis (and a ``model`` axis left open for tensor/sequence sharding),
the batch sharded on ``data``, state replicated, and the gradient psum
inserted by XLA's partitioner — collectives ride ICI, no NCCL-style
process-group code at all (SURVEY.md §5 "Distributed communication
backend").
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

PyTree = Any

DATA_AXIS = "data"
MODEL_AXIS = "model"


def create_mesh(
    num_devices: int = 0,
    model_parallelism: int = 1,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Mesh of shape (data, model). ``num_devices=0`` = all visible devices;
    model axis defaults to 1 (pure DP — the reference's only strategy,
    SURVEY.md §2.3) but is first-class so tensor/sequence sharding can use
    the same mesh."""
    devs = list(devices) if devices is not None else jax.devices()
    if num_devices:
        if num_devices < 0 or len(devs) < num_devices:
            raise ValueError(
                f"create_mesh(num_devices={num_devices}): only {len(devs)} "
                f"device(s) visible on backend {jax.default_backend()!r} — "
                "refusing to silently under-provision the mesh"
            )
        devs = devs[:num_devices]
    n = len(devs)
    if n % model_parallelism:
        raise ValueError(
            f"{n} devices not divisible by model_parallelism={model_parallelism}"
        )
    grid = np.array(devs).reshape(n // model_parallelism, model_parallelism)
    return Mesh(grid, (DATA_AXIS, MODEL_AXIS))


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Batch dim sharded over data axis; replicated over model."""
    return NamedSharding(mesh, P(DATA_AXIS))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def shard_batch(batch: PyTree, mesh: Mesh) -> PyTree:
    """Place a host-global batch sharded on the data axis."""
    return jax.device_put(batch, batch_sharding(mesh))


def assemble_batch(batch: PyTree, mesh: Mesh, scope: str = "global") -> PyTree:
    """Turn a loader batch into a GLOBAL data-sharded array.

    The loader contract (data/__init__.py): loaders declare
    ``batch_scope`` — "global" (every host holds the full batch: device
    CIFAR, synthetic) or "host" (each host holds total/process_count rows:
    grain/tpk ImageNet, FFCV's ``distributed=True`` equivalent,
    /root/reference/utils/dataset.py:411).

    Host-local batches are assembled with
    ``jax.make_array_from_process_local_data`` — handing a host-local array
    straight to a global sharding would scatter the wrong rows (or die on
    divisibility) on >1 process.
    """
    sharding = batch_sharding(mesh)
    if scope == "global" or jax.process_count() == 1:
        return jax.device_put(batch, sharding)
    if scope != "host":
        raise ValueError(f"unknown batch scope {scope!r}")
    return jax.tree.map(
        lambda x: jax.make_array_from_process_local_data(sharding, x), batch
    )


def replicate(tree: PyTree, mesh: Mesh) -> PyTree:
    return jax.device_put(tree, replicated(mesh))


def make_sharded_train_step(
    train_step: Callable, mesh: Mesh, donate_state: bool = True
) -> Callable:
    """jit the pure step with state replicated and batch data-sharded.

    XLA partitions the fwd/bwd over the batch and inserts the gradient
    all-reduce — the TPU equivalent of DDP's bucketed NCCL allreduce, but
    fused into the same program as the optimizer update."""
    return jax.jit(
        train_step,
        in_shardings=(replicated(mesh), batch_sharding(mesh)),
        out_shardings=(replicated(mesh), replicated(mesh)),
        donate_argnums=(0,) if donate_state else (),
    )


def make_sharded_eval_step(eval_step: Callable, mesh: Mesh) -> Callable:
    return jax.jit(
        eval_step,
        in_shardings=(replicated(mesh), batch_sharding(mesh)),
        out_shardings=replicated(mesh),
    )


def epoch_sharding(mesh: Mesh) -> NamedSharding:
    """Stacked-epoch tensors [steps, batch, ...]: batch axis (dim 1) sharded
    over data, step axis replicated."""
    return NamedSharding(mesh, P(None, DATA_AXIS))


def make_sharded_scan_eval(scan_eval: Callable, mesh: Mesh) -> Callable:
    """jit the lax.scan eval runner (train/steps.py make_scan_eval): state
    replicated (NOT donated — it is reused for training), stacked batches
    sharded on the batch axis."""
    return jax.jit(
        scan_eval,
        in_shardings=(replicated(mesh), epoch_sharding(mesh)),
        out_shardings=replicated(mesh),
    )


def make_sharded_scan_chunk(
    scan_chunk: Callable, mesh: Mesh, donate_state: bool = True
) -> Callable:
    """jit the lax.scan runner (train/steps.py make_scan_chunk): K stacked
    batches [K, B, ...] execute as ONE XLA program (state replicated +
    donated, batch axis sharded on ``data``) with the per-step psum still
    inserted by the partitioner. K is a whole epoch for a device-resident
    loader (zero host dispatches in the hot loop) and
    ``scan_chunk_steps`` prefetched batches on the STREAMED train path, so
    data that doesn't fit in HBM still amortizes dispatch."""
    return jax.jit(
        scan_chunk,
        in_shardings=(replicated(mesh), epoch_sharding(mesh)),
        out_shardings=(replicated(mesh), replicated(mesh)),
        donate_argnums=(0,) if donate_state else (),
    )


def assemble_chunk(batch: PyTree, mesh: Mesh, scope: str = "global") -> PyTree:
    """``assemble_batch`` for a STACKED chunk [K, B, ...]: place with the
    step axis replicated and the batch axis (dim 1) sharded on ``data``
    (epoch_sharding). Host-scope chunks ([K, local_B, ...] per host) are
    assembled with ``jax.make_array_from_process_local_data`` like their
    per-batch counterpart."""
    sharding = epoch_sharding(mesh)
    if scope == "global" or jax.process_count() == 1:
        return jax.device_put(batch, sharding)
    if scope != "host":
        raise ValueError(f"unknown batch scope {scope!r}")
    return jax.tree.map(
        lambda x: jax.make_array_from_process_local_data(sharding, x), batch
    )
