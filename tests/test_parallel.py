"""SPMD layer on the virtual 8-device CPU mesh (SURVEY.md §4 strategy)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from turboprune_tpu.models import create_model
from turboprune_tpu.parallel import (
    batch_sharding,
    check_state_equality,
    create_mesh,
    make_sharded_eval_step,
    make_sharded_train_step,
    replicate,
    shard_batch,
    tree_fingerprint,
)
from turboprune_tpu.train import create_train_state, make_eval_step, make_train_step, sgd


@pytest.fixture(scope="module")
def setup():
    model = create_model("resnet18", num_classes=10, dataset_name="CIFAR10")
    tx = sgd(0.1, momentum=0.9, weight_decay=5e-4)
    state = create_train_state(
        model, tx, jax.random.key(0), input_shape=(2, 16, 16, 3)
    )
    images = jax.random.normal(jax.random.key(1), (16, 16, 16, 3))
    labels = jnp.arange(16) % 10
    return model, tx, state, (images, labels)


def test_mesh_shape(devices):
    mesh = create_mesh()
    assert mesh.devices.size == len(devices)
    assert mesh.axis_names == ("data", "model")
    mesh2 = create_mesh(model_parallelism=2)
    assert mesh2.shape["model"] == 2
    assert mesh2.shape["data"] == len(devices) // 2


def test_create_mesh_raises_on_insufficient_devices(devices):
    """Requesting more devices than exist must fail loudly, not silently
    truncate."""
    with pytest.raises(ValueError, match="refusing"):
        create_mesh(num_devices=len(devices) + 1)


def test_batch_is_sharded_over_data_axis(setup):
    _, _, _, batch = setup
    mesh = create_mesh()
    sharded = shard_batch(batch, mesh)
    assert sharded[0].sharding == batch_sharding(mesh)
    # each device holds batch/8 rows
    shard_shapes = {s.data.shape for s in sharded[0].addressable_shards}
    assert shard_shapes == {(2, 16, 16, 3)}


def test_sharded_train_matches_single_device(setup):
    """DP over 8 devices must be numerically the plain single-device step —
    the partitioner's psum replaces DDP allreduce with no semantic drift."""
    model, tx, state, batch = setup
    step = make_train_step(model, tx)

    ref_state, ref_metrics = jax.jit(step)(state, batch)

    mesh = create_mesh()
    sharded_step = make_sharded_train_step(step, mesh, donate_state=False)
    dstate = replicate(state, mesh)
    dbatch = shard_batch(batch, mesh)
    new_state, metrics = sharded_step(dstate, dbatch)

    np.testing.assert_allclose(
        float(metrics["loss_sum"]), float(ref_metrics["loss_sum"]), rtol=1e-4
    )
    np.testing.assert_allclose(
        np.asarray(new_state.params["fc"]["kernel"]),
        np.asarray(ref_state.params["fc"]["kernel"]),
        rtol=1e-4,
        atol=1e-6,
    )
    # BN batch stats also match: under one jit the batch statistics are
    # computed over the GLOBAL batch (unlike DDP's per-replica BN).
    np.testing.assert_allclose(
        np.asarray(new_state.batch_stats["bn1"]["mean"]),
        np.asarray(ref_state.batch_stats["bn1"]["mean"]),
        rtol=1e-4,
        atol=1e-6,
    )


def test_sharded_eval(setup):
    model, tx, state, batch = setup
    mesh = create_mesh()
    eval_sharded = make_sharded_eval_step(make_eval_step(model), mesh)
    out = eval_sharded(replicate(state, mesh), shard_batch(batch, mesh))
    assert float(out["count"]) == 16.0


def test_cluster_hint_requires_multi_worker_evidence(monkeypatch):
    """initialize_distributed must NOT start a distributed service on a
    single host: a single-host TPU VM's runtime exports
    TPU_WORKER_HOSTNAMES=localhost, which used to trip the hint check and
    crash/hang every entry-script run."""
    from turboprune_tpu.parallel.multihost import _cluster_hinted

    for k in ("OMPI_COMM_WORLD_SIZE", "SLURM_NTASKS", "TPU_WORKER_HOSTNAMES"):
        monkeypatch.delenv(k, raising=False)
    assert not _cluster_hinted()
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "localhost")
    assert not _cluster_hinted()  # single worker — a one-host TPU VM
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "host-0,host-1")
    assert _cluster_hinted()  # real pod
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "localhost")
    monkeypatch.setenv("OMPI_COMM_WORLD_SIZE", "4")
    assert _cluster_hinted()
    monkeypatch.setenv("OMPI_COMM_WORLD_SIZE", "1")
    assert not _cluster_hinted()
    monkeypatch.setenv("SLURM_NTASKS", "8")
    assert _cluster_hinted()


def test_fingerprint_and_equality(setup):
    _, _, state, _ = setup
    fp1 = tree_fingerprint(state.params)
    fp2 = tree_fingerprint(jax.tree.map(lambda x: x + 0, state.params))
    assert fp1 == fp2
    perturbed = jax.tree.map(lambda x: x + 1e-3, state.params)
    assert tree_fingerprint(perturbed) != fp1
    check_state_equality(state.params)  # single-host: must not raise
