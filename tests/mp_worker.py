"""Worker process for the real 2-process distributed tests.

Launched by tests/test_multiprocess.py as
``python tests/mp_worker.py <pid> <nproc> <port> <outdir>``. Each worker
joins a jax.distributed world over localhost (CPU backend, 4 virtual
devices per process = 8-device global mesh) and exercises the
``process_count() > 1`` branches no single-process test can reach:
broadcast_object, assemble_batch's host-scope path, primary-only Orbax
save + all-host restore, grain's ShardByJaxProcess disjointness, the full
driver level loop (scan path), and SNIP scoring on a host-scope loader.

Results land in ``<outdir>/result_<pid>.json``; cross-host agreement is
asserted both in-worker (check_state_equality) and by the parent test
(fingerprint comparison across the two result files).
"""

import json
import os
import sys
import traceback
from pathlib import Path

pid, nproc, port, outdir = (
    int(sys.argv[1]),
    int(sys.argv[2]),
    sys.argv[3],
    Path(sys.argv[4]),
)

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

# Join through the PRODUCTION entry path (env-var style), not a direct
# jax.distributed.initialize — regression for r4 weak #1, where
# initialize_distributed touched the backend before distributed init and
# every host came up as its own single-process world.
os.environ["JAX_COORDINATOR_ADDRESS"] = f"localhost:{port}"
os.environ["JAX_NUM_PROCESSES"] = str(nproc)
os.environ["JAX_PROCESS_ID"] = str(pid)

import jax  # noqa: E402

jax.config.update("jax_enable_compilation_cache", False)  # as conftest.py

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from turboprune_tpu.parallel import initialize_distributed  # noqa: E402

initialize_distributed()
assert jax.process_count() == nproc, "initialize_distributed failed to join"

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from turboprune_tpu.config.compose import compose  # noqa: E402
from turboprune_tpu.driver import _first_train_batch, run  # noqa: E402
from turboprune_tpu.harness import PruningHarness  # noqa: E402
from turboprune_tpu.parallel import (  # noqa: E402
    assemble_batch,
    broadcast_object,
    create_mesh,
    replicated,
)
from turboprune_tpu.parallel.multihost import tree_fingerprint  # noqa: E402
from turboprune_tpu.utils.checkpoint import (  # noqa: E402
    restore_pytree,
    save_pytree,
)

result: dict = {"pid": pid}


def check_world():
    assert jax.process_count() == nproc, jax.process_count()
    assert jax.device_count() == 4 * nproc
    assert jax.local_device_count() == 4
    result["world"] = [jax.process_count(), jax.device_count()]


def check_broadcast_object():
    obj = {"run": "abc123", "lvl": 7} if pid == 0 else None
    out = broadcast_object(obj)
    assert out == {"run": "abc123", "lvl": 7}, out
    result["broadcast"] = out


def check_assemble_batch(mesh):
    # Host p holds rows p*8 .. p*8+7 of a known global batch of 16 — after
    # assembly, EVERY host must see the full batch in global row order.
    rows = 8
    local_x = (np.arange(rows * 4, dtype=np.float32) + pid * rows * 4).reshape(
        rows, 4
    )
    local_y = np.arange(rows, dtype=np.int32) + pid * rows
    gx, gy = assemble_batch((local_x, local_y), mesh, "host")
    assert gx.shape == (rows * nproc, 4), gx.shape
    pull = jax.jit(lambda a: a, out_shardings=replicated(mesh))
    got_x = np.asarray(jax.device_get(pull(gx)))
    got_y = np.asarray(jax.device_get(pull(gy)))
    want_x = np.arange(rows * 4 * nproc, dtype=np.float32).reshape(rows * nproc, 4)
    want_y = np.arange(rows * nproc, dtype=np.int32)
    np.testing.assert_array_equal(got_x, want_x)
    np.testing.assert_array_equal(got_y, want_y)

    # Global scope: every host already holds the full batch; content must
    # survive placement unchanged.
    gx2 = assemble_batch(want_x, mesh, "global")
    np.testing.assert_array_equal(np.asarray(jax.device_get(pull(gx2))), want_x)
    result["assemble_batch"] = "ok"


def check_primary_only_checkpoint():
    # Would DEADLOCK before the MultiprocessingOptions(active_processes={0})
    # fix: host 0 stuck in Orbax's global barrier, host 1 at sync_hosts.
    tree = {
        "w": np.arange(12, dtype=np.float32).reshape(3, 4),
        "nested": {"b": np.full(5, 3.5, np.float32), "n": 7},
    }
    path = outdir / "ckpt_roundtrip"
    save_pytree(path, tree)
    got = restore_pytree(path, tree)
    np.testing.assert_array_equal(got["w"], tree["w"])
    np.testing.assert_array_equal(got["nested"]["b"], tree["nested"]["b"])
    assert got["nested"]["n"] == 7
    result["checkpoint"] = "ok"


def check_grain_shard_disjoint():
    import grain.python as grain
    from jax.experimental import multihost_utils

    shard = grain.ShardByJaxProcess(drop_remainder=False)
    assert (shard.shard_index, shard.shard_count) == (pid, nproc)
    sampler = grain.IndexSampler(
        num_records=11,
        shard_options=shard,
        shuffle=False,
        num_epochs=1,
        seed=0,
    )
    # grain's DataLoader consumes the sampler strided by shard:
    # islice(sampler, shard_index, None, shard_count) — the record_keys that
    # stride yields are this process's actual sample set.
    from itertools import islice

    keys = sorted(
        md.record_key
        for md in islice(iter(sampler), shard.shard_index, None, shard.shard_count)
    )
    # Pad to a fixed length for allgather (11 doesn't split evenly).
    padded = np.full(11, -1, np.int64)
    padded[: len(keys)] = keys
    gathered = multihost_utils.process_allgather(padded, tiled=False)
    all_keys = [int(k) for row in np.asarray(gathered) for k in row if k >= 0]
    assert sorted(all_keys) == list(range(11)), sorted(all_keys)
    assert len(set(all_keys)) == len(all_keys)  # disjoint
    result["grain_shard"] = "ok"


def _base_overrides(base_dir):
    return [
        f"experiment_params.base_dir={base_dir}",
        "dataset_params.dataloader_type=synthetic",
        "dataset_params.total_batch_size=16",
        "dataset_params.synthetic_num_train=64",
        "dataset_params.synthetic_num_test=32",
        "experiment_params.epochs_per_level=1",
        "pruning_params.target_sparsity=0.2",
        "model_params.model_name=resnet18",
    ]


def check_driver_imp():
    """Full IMP loop (2 levels) on the scan path; broadcast_object picks the
    expt dir, prune runs replicated, check_state_equality asserts in-run."""
    captured = {}

    class CapturingHarness(PruningHarness):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            captured["h"] = self

    cfg = compose("cifar10_imp", overrides=_base_overrides(outdir / "imp"))
    expt_dir, summaries = run(cfg, harness_cls=CapturingHarness)
    assert len(summaries) == 2
    np.testing.assert_allclose(
        [s["density"] for s in summaries], [1.0, 0.8], atol=1e-6
    )
    state = captured["h"].state
    result["imp_expt_dir"] = str(expt_dir)  # must MATCH across hosts
    result["imp_fingerprint"] = tree_fingerprint(
        {"params": state.params, "masks": state.masks}
    )
    result["imp_sparsity"] = summaries[-1]["achieved_density"]
    # Level saves write behind, on the primary's writer alone; run() waited
    # (the write, then the cross-host barrier) before it returned, so both
    # hosts list both levels.
    ckpts = captured["h"].ckpts
    result["imp_has_writer"] = ckpts._behind._pool is not None
    result["imp_unsettled_after_run"] = ckpts._unsettled
    result["imp_saved_levels"] = ckpts.saved_levels()


class _HostScopeLoader:
    """Wrap a global-scope device loader into a host-scope one: each host
    yields only its process's slice of every batch (the shape grain/tpk
    loaders produce on >1 process)."""

    batch_scope = "host"

    def __init__(self, inner):
        self.inner = inner

    def __len__(self):
        return len(self.inner)

    def __iter__(self):
        n_local = None
        for images, labels in self.inner:
            if n_local is None:
                n_local = images.shape[0] // jax.process_count()
            lo = pid * n_local
            yield images[lo : lo + n_local], labels[lo : lo + n_local]


def check_driver_snip_host_scope():
    """SNIP at_init through the driver with HOST-SCOPE loaders: the scoring
    batch must be allgathered to global consistency (driver._first_train_batch)
    and every train/eval batch must go through assemble_batch's host path.
    check_state_equality inside prune_level raises if masks diverge."""
    captured = {}

    class HostScopeHarness(PruningHarness):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            captured["h"] = self
            self.loaders.train_loader = _HostScopeLoader(self.loaders.train_loader)
            self.loaders.test_loader = _HostScopeLoader(self.loaders.test_loader)

    cfg = compose(
        "cifar10_imp",
        overrides=_base_overrides(outdir / "snip")
        + [
            "pruning_params.prune_method=snip",
            "pruning_params.training_type=at_init",
            "pruning_params.target_sparsity=0.5",
        ],
    )
    expt_dir, summaries = run(cfg, harness_cls=HostScopeHarness)
    assert len(summaries) == 1
    assert abs(summaries[0]["achieved_density"] - 0.5) < 5e-3
    state = captured["h"].state
    result["snip_fingerprint"] = tree_fingerprint(
        {"params": state.params, "masks": state.masks}
    )

    # The SNIP scoring batch itself must be identical across hosts.
    batch = _first_train_batch(captured["h"])
    result["snip_batch_fingerprint"] = tree_fingerprint(
        {"x": jnp.asarray(batch[0]), "y": jnp.asarray(batch[1])}
    )


def check_ring_attention_cross_host():
    """Ring attention on a (data=4, model=2) mesh laid over the TWO-process
    world: shard_map + ppermute K/V rotation run under jax.distributed, and
    the replicated output must be bit-identical across hosts."""
    from turboprune_tpu.models.vit import VisionTransformer
    from turboprune_tpu.parallel import replicate
    from turboprune_tpu.parallel.mesh import batch_sharding

    mesh_sp = create_mesh(model_parallelism=2)
    vit = VisionTransformer(
        num_classes=4, patch_size=4, embed_dim=16, depth=1, num_heads=2,
        attention_impl="ring", mesh=mesh_sp,
    )
    # Same seeds on every host => identical params and batch.
    x = np.random.default_rng(0).normal(size=(16, 8, 8, 3)).astype(np.float32)
    params = vit.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, 8, 3)))["params"]
    params = replicate(params, mesh_sp)
    batch = assemble_batch(jnp.asarray(x), mesh_sp, "global")
    fn = jax.jit(
        lambda p, xs: vit.apply({"params": p}, xs, train=False),
        in_shardings=(replicated(mesh_sp), batch_sharding(mesh_sp)),
        out_shardings=replicated(mesh_sp),
    )
    out = fn(params, batch)
    assert np.isfinite(np.asarray(jax.device_get(out))).all()
    result["ring_mp_fingerprint"] = tree_fingerprint({"o": out})


def main():
    mesh = create_mesh()
    check_world()
    check_broadcast_object()
    check_assemble_batch(mesh)
    check_primary_only_checkpoint()
    check_grain_shard_disjoint()
    check_driver_imp()
    check_driver_snip_host_scope()
    check_ring_attention_cross_host()
    result["ok"] = True


try:
    main()
except Exception:
    result["ok"] = False
    result["error"] = traceback.format_exc()

with open(outdir / f"result_{pid}.json", "w") as f:
    json.dump(result, f, default=str)

sys.exit(0 if result.get("ok") else 1)
