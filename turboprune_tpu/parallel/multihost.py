"""Multi-host coordination.

Replaces the reference's NCCL process-group utilities
(/root/reference/utils/distributed_utils.py): ``setup_distributed`` becomes
``jax.distributed.initialize``; ``broadcast_object`` (rank-0 strings like the
run id and experiment dir, run_experiment.py:70-72) becomes a
``broadcast_one_to_all`` over encoded bytes; and the reference's dormant
``check_model_equality`` (distributed_utils.py:31-60 — written but never
called) is revived as a real post-prune assertion, because the TPU design
computes masks replicated on every host and key-discipline bugs would
otherwise diverge silently (SURVEY.md §5 race-detection note).
"""

from __future__ import annotations

import hashlib
import json
import os
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any


def _cluster_hinted() -> bool:
    """True only when env vars show a MULTI-worker launch whose topology
    jax.distributed.initialize() can auto-detect (SLURM, OpenMPI, multi-host
    TPU pod). Presence alone is not enough: a single-host TPU VM also sets
    these (its runtime exports TPU_WORKER_HOSTNAMES=localhost), and
    initializing a 1-process distributed service there is pure downside."""
    try:
        if int(os.environ.get("OMPI_COMM_WORLD_SIZE") or 1) > 1:
            return True
        if int(os.environ.get("SLURM_NTASKS") or 1) > 1:
            return True
    except ValueError:
        pass
    # Cloud TPU pods: comma-separated list of all worker hostnames.
    return "," in os.environ.get("TPU_WORKER_HOSTNAMES", "")


def initialize_distributed() -> None:
    """Join the multi-host world when launched under a JAX cluster
    (coordinator env vars / TPU metadata present); no-op single-host.
    The TPU analog of dist.init_process_group("nccl")
    (distributed_utils.py:63-66) — after this, collectives ride ICI/DCN.

    MUST be the first JAX touch in the process: ``jax.process_count()`` /
    ``jax.devices()`` initialize the backend, after which distributed init
    is rejected and every host silently comes up as its own single-process
    world (all-primary — each host writes its own expt dir and
    ``broadcast_object`` no-ops). So this inspects ONLY env vars before
    deciding, and calls ``jax.distributed.initialize`` before anything else
    queries the runtime. Regression-tested via tests/mp_worker.py, which
    joins its 2-process world through this exact entry path."""
    if jax.distributed.is_initialized():
        return  # already joined (e.g. a direct jax.distributed.initialize)
    coord = os.environ.get("JAX_COORDINATOR_ADDRESS") or os.environ.get(
        "COORDINATOR_ADDRESS"
    )
    if coord:
        nproc = os.environ.get("JAX_NUM_PROCESSES")
        pid = os.environ.get("JAX_PROCESS_ID")
        jax.distributed.initialize(
            coordinator_address=coord,
            num_processes=int(nproc) if nproc else None,
            process_id=int(pid) if pid else None,
        )
    elif _cluster_hinted():
        jax.distributed.initialize()  # cluster auto-detect (SLURM/MPI/pod)


def process_index() -> int:
    return jax.process_index()


def is_primary() -> bool:
    """Host 0 — the reference's rank-0 role (logging, expt dir, checkpoints)."""
    return jax.process_index() == 0


def broadcast_object(obj: Any) -> Any:
    """Host-0's JSON-serializable object to all hosts
    (reference broadcast_object, distributed_utils.py:7-11)."""
    if jax.process_count() == 1:
        return obj
    from jax.experimental import multihost_utils

    payload = np.frombuffer(
        json.dumps(obj if is_primary() else None).encode(), dtype=np.uint8
    )
    # Fixed-size buffer: length first, then padded payload.
    length = multihost_utils.broadcast_one_to_all(
        np.array([payload.size], np.int32)
    )[0]
    buf = np.zeros(int(length), np.uint8)
    if is_primary():
        buf[: payload.size] = payload
    out = multihost_utils.broadcast_one_to_all(buf)
    return json.loads(out.tobytes().decode())


def tree_fingerprint(tree: PyTree) -> str:
    """Deterministic content hash of every array leaf (order-stable)."""
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: x is None
    )[0]:
        if leaf is None:
            continue
        h.update(str(path).encode())
        h.update(np.asarray(jax.device_get(leaf)).tobytes())
    return h.hexdigest()


@jax.jit
def _leaf_moments(leaves):
    # Module-level jit: caches per leaves-structure, so the per-prune
    # equality check compiles once per state signature, not per call.
    out = []
    for x in leaves:
        xf = jnp.asarray(x).astype(jnp.float32)
        out.append(jnp.stack([xf.sum(), (xf * xf).sum()]))
    return jnp.stack(out)


def tree_moments(tree: PyTree) -> np.ndarray:
    """Per-leaf [sum, sum-of-squares] computed ON DEVICE — a [L, 2] array is
    all that crosses to the host (the old path pulled every leaf for
    hashing: a full params+masks device->host transfer per prune, r4 weak
    #8). Determinism makes this an equality check, not just a sketch: hosts
    hold bit-identical replicated arrays and run the same compiled
    reduction, so equal state implies exactly equal moments; divergence
    escapes detection only if it cancels both moments of every leaf."""
    leaves = [
        leaf
        for _, leaf in jax.tree_util.tree_flatten_with_path(
            tree, is_leaf=lambda x: x is None
        )[0]
        if leaf is not None
    ]
    return np.asarray(jax.device_get(_leaf_moments(leaves)))


def check_state_equality(
    tree: PyTree, what: str = "state", exact: bool = False
) -> None:
    """Assert all hosts hold identical replicated state; raises on divergence.

    Upgrade of the reference's never-called check_model_equality
    (distributed_utils.py:31-60): per-leaf device-side moments, allgathered
    and compared bit-exactly (see tree_moments for why equality of moments
    is the right check here). Moments are permutation-invariant, though — a
    divergence that permutes elements within a leaf (or cancels both
    moments) slips past them — so ``exact=True`` ADDITIONALLY allgathers
    the full ``tree_fingerprint`` digest (a complete device->host transfer;
    the driver pays it once per level, not per step). The cheap moments
    check still runs first: when it fires it names the first differing
    leaf, which the opaque digest cannot."""
    if jax.process_count() == 1:
        return
    from jax.experimental import multihost_utils

    m = tree_moments(tree)
    all_m = np.asarray(multihost_utils.process_allgather(m, tiled=False))
    ref = all_m[0]
    for i, other in enumerate(all_m):
        # equal_nan: hosts that ALL went NaN identically (diverged loss)
        # have not diverged from each other — don't misreport a PRNG bug.
        if not np.array_equal(ref, other, equal_nan=True):
            bad = int(np.argwhere((ref != other).any(axis=-1))[0][0])
            raise RuntimeError(
                f"{what} diverged across hosts: host 0 != host {i} "
                f"(first differing leaf index {bad}). Replicated pruning "
                "requires identical PRNG keys on every host."
            )
    if exact:
        digest = np.frombuffer(
            bytes.fromhex(tree_fingerprint(tree)), dtype=np.uint8
        )
        all_d = np.asarray(
            multihost_utils.process_allgather(digest, tiled=False)
        )
        for i, other in enumerate(all_d):
            if not np.array_equal(all_d[0], other):
                raise RuntimeError(
                    f"{what} diverged across hosts: host 0 != host {i} "
                    "(exact content-hash mismatch despite equal per-leaf "
                    "moments — an element-permuting divergence)."
                )


def assert_width_agreement(signature: Any, what: str = "compact-train") -> None:
    """Assert every process derived the SAME compaction decision before any
    re-instantiation happens; raises on divergence.

    ``signature`` is any JSON-serializable encoding of the decision — the
    harness passes ``{"commit": bool, "widths": [[space, kept], ...]}``.
    Masks are replicated, so agreement is guaranteed by construction; this
    assertion exists because the failure mode it guards — replicas
    compiling DIFFERENT small-model shapes and then deadlocking inside a
    collective with mismatched buffer sizes — is near-undebuggable when it
    happens, while a digest allgather per level is free. Every process must
    call this (it is itself a collective); encode skip decisions in the
    signature rather than skipping the call."""
    if jax.process_count() == 1:
        return
    from jax.experimental import multihost_utils

    payload = json.dumps(signature, sort_keys=True).encode()
    digest = np.frombuffer(hashlib.sha256(payload).digest(), dtype=np.uint8)
    all_d = np.asarray(multihost_utils.process_allgather(digest, tiled=False))
    for i, other in enumerate(all_d):
        if not np.array_equal(all_d[0], other):
            raise RuntimeError(
                f"{what} width signature diverged across hosts: host 0 != "
                f"host {i} (this host's signature: {signature!r}). "
                "Re-instantiating would compile divergent shapes; replicated "
                "pruning requires identical masks on every host."
            )


def sync_hosts(name: str = "barrier") -> None:
    """Cross-host barrier (reference dist.barrier, distributed_utils.py:27)."""
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices(name)
