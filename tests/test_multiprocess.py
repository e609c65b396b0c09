"""REAL 2-process distributed tests (VERDICT r3 item 1/6).

Everything else in the suite runs on a 1-process virtual mesh, which can
never enter the ``jax.process_count() > 1`` branches: broadcast_object's
allgather, assemble_batch's make_array_from_process_local_data path,
primary-only Orbax saves (which DEADLOCK if Orbax's internal barriers span
the world), grain's ShardByJaxProcess, and the driver's cross-host
fingerprint check. Here we launch two actual processes that join a
jax.distributed world over localhost (CPU backend, Gloo collectives,
4 virtual devices each) and run those exact seams — see tests/mp_worker.py
for the per-worker checks.
"""

import json
import os
import socket
import subprocess
import sys
from pathlib import Path

import pytest

NPROC = 2
WORKER = Path(__file__).parent / "mp_worker.py"


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


@pytest.fixture(scope="module")
def mp_results(tmp_path_factory):
    outdir = tmp_path_factory.mktemp("mp")
    port = _free_port()
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    procs = [
        subprocess.Popen(
            [sys.executable, str(WORKER), str(i), str(NPROC), str(port), str(outdir)],
            env=env,
            cwd=str(WORKER.parents[1]),
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
        )
        for i in range(NPROC)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=900)
            outs.append(out.decode(errors="replace"))
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        pytest.fail(
            "2-process workers timed out (deadlock?) — this is the failure "
            "mode of primary-only saves with world-spanning Orbax barriers"
        )
    results = []
    for i in range(NPROC):
        path = outdir / f"result_{i}.json"
        assert path.exists(), (
            f"worker {i} wrote no result (rc={procs[i].returncode})\n{outs[i][-4000:]}"
        )
        with open(path) as f:
            results.append(json.load(f))
    for i, r in enumerate(results):
        assert r.get("ok"), f"worker {i} failed:\n{r.get('error')}\n{outs[i][-4000:]}"
    return results


class TestTwoProcessWorld:
    def test_world_shape(self, mp_results):
        for r in mp_results:
            assert r["world"] == [2, 8]

    def test_broadcast_object_host0_wins(self, mp_results):
        for r in mp_results:
            assert r["broadcast"] == {"run": "abc123", "lvl": 7}

    def test_assemble_batch_host_scope_content(self, mp_results):
        for r in mp_results:
            assert r["assemble_batch"] == "ok"

    def test_primary_only_checkpoint_roundtrip(self, mp_results):
        for r in mp_results:
            assert r["checkpoint"] == "ok"

    def test_grain_shards_disjoint(self, mp_results):
        for r in mp_results:
            assert r["grain_shard"] == "ok"

    def test_imp_expt_dir_broadcast(self, mp_results):
        # gen_expt_dir has a uuid+timestamp — hosts only agree because the
        # driver broadcasts host 0's choice.
        assert mp_results[0]["imp_expt_dir"] == mp_results[1]["imp_expt_dir"]

    def test_imp_final_state_identical(self, mp_results):
        assert (
            mp_results[0]["imp_fingerprint"] == mp_results[1]["imp_fingerprint"]
        )

    def test_only_the_primary_has_a_writer_and_every_host_sees_every_level(self, mp_results):
        # save_level writes behind on process 0's one writer thread; the
        # other process fetches nothing, has no writer, and meets the primary
        # at wait()'s barrier, after which it may read what was written.
        assert [r["imp_has_writer"] for r in mp_results] == [True, False]
        for r in mp_results:
            assert r["imp_unsettled_after_run"] is None
            assert r["imp_saved_levels"] == [0, 1]

    def test_ring_attention_cross_host_identical(self, mp_results):
        # shard_map ring attention over a mesh spanning both processes:
        # the ppermute ring crosses the process boundary and the replicated
        # output must agree bit-for-bit.
        assert (
            mp_results[0]["ring_mp_fingerprint"]
            == mp_results[1]["ring_mp_fingerprint"]
        )

    def test_snip_host_scope_consistent(self, mp_results):
        # SNIP scored on a host-scope loader: masks and the scoring batch
        # itself must be identical across hosts (the r3 divergence defect).
        assert (
            mp_results[0]["snip_fingerprint"] == mp_results[1]["snip_fingerprint"]
        )
        assert (
            mp_results[0]["snip_batch_fingerprint"]
            == mp_results[1]["snip_batch_fingerprint"]
        )
