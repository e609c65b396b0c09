#!/usr/bin/env python
"""Does the system still start on the chip?

    python chip_smoke.py             # one TPU chip: train, serve, flash
    python chip_smoke.py --chips 4   # one four-chip host: the mesh paths only

One process, no network, no dataset on disk, weights and data made from the
config's seed. It drives the program through the entry points a user calls
and checks what comes out by the repo's own means:

  train  run_experiment.main — ResNet50 at published widths, 224x224, 1000
         classes, bf16, synthetic ImageNet-shaped data, three IMP levels
         (dense, then prune + rewind twice). Losses finite, sparsity exactly
         the ladder, state on the chip, the train step compiled once.
  serve  build_server on the directory the train phase wrote, as
         run_server.py does; /predict over HTTP against a direct apply of
         the restored, mask-folded checkpoint; /healthz, /metrics; drain.
  flash  the Pallas kernel compiled by Mosaic (interpret=False), forward and
         jax.grad, against a dense jnp oracle.

  --chips 4 runs none of the above. It runs what exists only across chips:
  the first two IMP levels data-parallel on four devices against the same
  global batch and seed on one, and ring attention on a (data=2, model=2)
  mesh against dense attention.

The first failed check raises and the process exits non-zero: no phase is
wrapped in try/except. The last line of stdout is one JSON object naming
the device as JAX reports it. Without a TPU the script refuses to start.

Each phase is a plain function of its sizes, so tests/test_chip_smoke.py
calls the train and four-device phases at tiny size on the CPU mesh.
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
import threading
import time
import urllib.request
from pathlib import Path
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np

CHECKOUT = Path(__file__).resolve().parent
# The chip tool brings this directory back (at most 64 MiB of it).
OUT_DIR = CHECKOUT / "chiprun_out" / "chip_smoke"

# BASELINE.md's batch is 512, and it does not fit one 16 GB chip beside what
# the harness keeps there: the step's temporaries are 14.97 GB by the
# compiler's count (tests/test_tpu_compile.py), and on the chip loading it
# failed with "Attempting to reserve 13.89G at the bottom of memory ... There
# are 12.60G free": the synthetic epoch, its shuffled copy, the eval set and
# the state held the other 3.15 GiB of 15.75 (chip run, PR 21). 256 is the
# largest power of two that fits.
TRAIN_BATCH = 256

# bf16 keeps 8 bits of mantissa. Two programs that differ only in the order
# of their reductions agree to a few of its ulps per op.
BF16_TOL = 3e-2

# Two runs of the same recipe on different meshes. The smoke's four steps
# ramp the learning rate to its peak and the loss climbs, so rounding
# differences grow from step to step: on the chip the epoch means of four
# devices and of one differed by 2.6e-2 at level 0 (PR 21). This bound says
# "the same run", not "the same rounding".
SAME_RUN_TOL = 0.1


def say(msg: str) -> None:
    print(msg, flush=True)


def compiled_since(t0: float) -> tuple[float, list, int, int]:
    """What the program's recorder (utils/tracing.py::modules) holds of the
    modules that left the backend since ``t0``, on any thread (the serve
    phase compiles off this one): seconds lowering and compiling (or reading
    the persistent cache), their names, cache hits and misses."""
    from turboprune_tpu.utils import tracing

    modules = tracing.modules(t0)
    return (
        sum(m.lower_s + m.compile_s for m in modules),
        [m.name for m in modules],
        sum(m.cache == "hit" for m in modules),
        sum(m.cache == "miss" for m in modules),
    )


def timed(name: str, phase, **sizes):
    """Run one phase and print its wall time split into compile and run."""
    say(f"[{name}] start {sizes}")
    t0 = time.perf_counter()
    out = phase(**sizes)
    wall = time.perf_counter() - t0
    compile_s, modules, hits, misses = compiled_since(t0)
    say(
        f"[{name}] ok: wall {wall:.1f}s = compile {compile_s:.1f}s + trace "
        f"and run {wall - compile_s:.1f}s; {len(modules)} modules reached "
        f"XLA, persistent cache {hits} hits / {misses} misses"
    )
    return out


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(f"chip_smoke check failed: {what}")
    say(f"  ok  {what}")


def cache_entries(cache_dir: str) -> int:
    p = Path(cache_dir)
    return sum(1 for f in p.iterdir() if f.is_file()) if p.is_dir() else 0


def hbm() -> str:
    """Peak device memory so far. A TPU program's temporaries are reserved
    apart from the buffers in use, and both come out of ``bytes_limit``."""
    stats = jax.local_devices()[0].memory_stats()
    if not stats:
        return "no memory statistics on this backend"
    gib = lambda key: f"{stats.get(key, 0) / 2**30:.2f} GiB"  # noqa: E731
    return (
        f"peak in use {gib('peak_bytes_in_use')}, peak reserved for programs "
        f"{gib('peak_bytes_reserved')}, of {gib('bytes_limit')}"
    )


# ------------------------------------------------------------------- train
def _flat(tree) -> np.ndarray:
    """Every array leaf of a tree (None leaves of a mask tree skipped) as
    one host vector."""
    from turboprune_tpu.ops.masking import mask_leaves

    return np.concatenate([np.asarray(x).reshape(-1) for x in mask_leaves(tree)])


def _magnitude_oracle(params, masks, density: float) -> np.ndarray:
    """Global magnitude pruning in numpy, from the reference's definition
    (pruning_utils.py:61-89): the k-th smallest |w * m| over all prunable
    weights, k = (1 - density) * N, and ``mask = score > threshold`` — so
    weights tied with the threshold go too."""
    from turboprune_tpu.ops.masking import mask_where

    scores = _flat(
        mask_where(
            masks,
            lambda m, p: np.abs(np.asarray(p) * np.asarray(m, np.float32)),
            params,
        )
    )
    k = int((1.0 - density) * scores.size)
    return scores > np.partition(scores, k - 1)[k - 1]


def _observed_harness(levels: list, densities: list):
    """PruningHarness that appends one record per trained level. It changes
    nothing the run computes: it reads the state after the level ends."""
    from turboprune_tpu.harness import PruningHarness

    class Observed(PruningHarness):
        def train_one_level(self, epochs_per_level, level):
            summary = super().train_one_level(epochs_per_level, level)
            state = self.state
            leaf = jax.tree.leaves(state.params)[0]
            levels.append(
                {
                    "level": level,
                    "train_loss": float(summary["train_loss"]),
                    "test_loss": float(summary["test_loss"]),
                    "mask": _flat(state.masks),
                    # What the next level's prune must make of this state.
                    "next_mask": _magnitude_oracle(
                        state.params, state.masks, densities[level + 1]
                    )
                    if level + 1 < len(densities)
                    else None,
                    "param_devices": sorted(
                        (d.platform, d.id) for d in leaf.devices()
                    ),
                    "step_cache_entries": len(self._step_cache),
                    "step_executables": self._steps.train_step._cache_size(),
                    "expt_dir": self.expt_dir,
                }
            )
            return summary

    return Observed


def _recording_assemble(real, layout: list):
    """assemble_batch that also notes, for the first batch of the run, which
    device got which rows and whether they are the rows it should hold."""

    def assemble_batch(batch, mesh, scope="global"):
        placed = real(batch, mesh, scope)
        if not layout:
            host = np.asarray(batch[0])
            for s in placed[0].addressable_shards:
                rows = s.index[0]
                layout.append(
                    {
                        "device": s.device.id,
                        "rows": (rows.start or 0, rows.stop or host.shape[0]),
                        "intact": bool(
                            np.array_equal(np.asarray(s.data), host[s.index])
                        ),
                    }
                )
        return placed

    return assemble_batch


def phase_train(
    *,
    base_dir: Path,
    platform: str,
    config_name: str,
    batch: int,
    num_train: int,
    num_test: int,
    steps: int,
    target_sparsity: float,
    num_devices: int = 0,
    overrides: tuple = (),
) -> dict:
    """Iterative magnitude pruning through run_experiment.main, on
    synthetic data shaped like the config's dataset. ``num_devices=0`` is
    the program's default: every visible device."""
    import run_experiment
    from turboprune_tpu import driver
    from turboprune_tpu.config.compose import compose
    from turboprune_tpu.harness import pruning_harness
    from turboprune_tpu.pruning import generate_densities

    settings = [
        "dataset_params.dataloader_type=synthetic",
        f"dataset_params.total_batch_size={batch}",
        f"dataset_params.synthetic_num_train={num_train}",
        f"dataset_params.synthetic_num_test={num_test}",
        "experiment_params.epochs_per_level=1",
        f"experiment_params.max_steps_per_epoch={steps}",
        f"experiment_params.num_devices={num_devices}",
        f"experiment_params.base_dir={base_dir}",
        f"pruning_params.target_sparsity={target_sparsity}",
        *overrides,
    ]
    pp = compose(config_name, settings).pruning_params
    densities = generate_densities(
        pp.prune_method, pp.target_sparsity, pp.prune_rate
    )
    levels: list = []
    layout: list = []
    began = time.perf_counter()
    with mock.patch.object(
        driver, "PruningHarness", _observed_harness(levels, densities)
    ), mock.patch.object(
        pruning_harness,
        "assemble_batch",
        _recording_assemble(pruning_harness.assemble_batch, layout),
    ):
        rc = run_experiment.main([f"--config-name={config_name}", *settings])
    check(rc == 0, f"run_experiment.main returned {rc}")
    check(
        [r["level"] for r in levels] == list(range(len(densities))),
        f"trained levels {[r['level'] for r in levels]} of a ladder of "
        f"{len(densities)}",
    )
    for r, density in zip(levels, densities):
        n = r["mask"].size
        zeros, k = int(n - r["mask"].sum()), int((1.0 - density) * n)
        say(
            f"  level {r['level']}: train loss {r['train_loss']:.4f}, test "
            f"loss {r['test_loss']:.4f}, pruned {zeros}/{n} = "
            f"{100.0 * zeros / n:.5f}% (the ladder's {k} + {zeros - k} tied "
            f"with the threshold)"
        )
        check(
            np.isfinite(r["train_loss"]) and np.isfinite(r["test_loss"]),
            f"level {r['level']} losses finite",
        )
        if r["level"] == 0:
            check(zeros == 0, "level 0 is dense")
        else:
            check(
                np.array_equal(r["mask"], levels[r["level"] - 1]["next_mask"]),
                f"level {r['level']} mask is, bit for bit, a numpy global "
                f"magnitude prune of level {r['level'] - 1}'s weights to the "
                f"ladder's {100.0 * (1.0 - density):.0f}%",
            )
        check(
            all(p == platform for p, _ in r["param_devices"]),
            f"level {r['level']} params live on {r['param_devices']}",
        )
        check(
            r["step_cache_entries"] == 1 and r["step_executables"] == 1,
            f"level {r['level']}: one train step, compiled for one signature",
        )
    check(
        compiled_since(began)[1].count("jit(train_step)") == 1,
        "jit(train_step) reached XLA once in the whole run (not again at "
        "levels 1 and 2)",
    )
    return {"expt_dir": levels[-1]["expt_dir"], "levels": levels, "layout": layout}


# ------------------------------------------------------------------- serve
def _reference_logits(expt_dir: str, images: np.ndarray) -> np.ndarray:
    """A direct model.apply on the highest level's checkpoint, restored here
    independently of the engine and mask-folded as the eval step folds it
    (train/steps.py make_eval_step)."""
    import yaml

    from turboprune_tpu.config.schema import config_from_dict
    from turboprune_tpu.harness.pruning_harness import PRECISION_DTYPES
    from turboprune_tpu.models import create_model
    from turboprune_tpu.ops.masking import apply_masks, make_masks
    from turboprune_tpu.train.state import init_variables
    from turboprune_tpu.utils.checkpoint import (
        ExperimentCheckpoints,
        restore_model_tree,
    )

    cfg = config_from_dict(
        yaml.safe_load((Path(expt_dir) / "expt_config.yaml").read_text())
    )
    dp = cfg.dataset_params
    model = create_model(
        cfg.model_params.model_name,
        num_classes=dp.num_classes,
        dataset_name=dp.dataset_name,
        compute_dtype=PRECISION_DTYPES[cfg.experiment_params.training_precision],
    )
    like = init_variables(
        model, jax.random.PRNGKey(0), (1, dp.image_size, dp.image_size, 3)
    )
    ckpts = ExperimentCheckpoints(expt_dir)
    restored = restore_model_tree(
        ckpts.level_path(ckpts.saved_levels()[-1]),
        {
            "params": like["params"],
            "masks": make_masks(like["params"]),
            "batch_stats": like.get("batch_stats", {}),
        },
    )

    def forward(v, x):
        variables = {"params": apply_masks(v["params"], v["masks"])}
        if v["batch_stats"]:
            variables["batch_stats"] = v["batch_stats"]
        return model.apply(variables, x, train=False)

    return np.asarray(jax.jit(forward)(restored, images), np.float32)


def _http(url: str, body: dict | None = None) -> tuple[int, bytes]:
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"}
    )
    with urllib.request.urlopen(req, timeout=120) as r:
        return r.status, r.read()


def phase_serve(
    *,
    expt_dir: str,
    platform: str,
    request_sizes: tuple,
    final_level: int,
) -> None:
    """The server of run_server.py on the train phase's directory."""
    from turboprune_tpu.config.compose import compose
    from turboprune_tpu.serve import build_server

    cfg = compose("serve", ["serve.port=0"])  # 0: an ephemeral port
    server = build_server(cfg, expt_dir=expt_dir)
    thread = threading.Thread(target=server.serve_forever, name="smoke-http")
    thread.start()
    try:
        engine = server.engine
        leaves = jax.tree.leaves(engine._variables)
        check(
            all(
                isinstance(x, jax.Array)
                and {d.platform for d in x.devices()} == {platform}
                for x in leaves
            ),
            f"all {len(leaves)} engine variables are device arrays on "
            f"{platform}",
        )
        url = f"http://127.0.0.1:{server.port}"
        rng = np.random.default_rng(0)
        images = rng.standard_normal(
            (sum(request_sizes), *engine.input_shape)
        ).astype(np.float32)
        served = []
        offset = 0
        for n in request_sizes:
            t0 = time.perf_counter()
            status, raw = _http(
                f"{url}/predict",
                {"instances": images[offset : offset + n].tolist()},
            )
            resp = json.loads(raw)
            logits = np.asarray(resp["logits"], np.float32)
            check(
                status == 200
                and logits.shape == (n, engine.num_classes)
                and resp["model_level"] == final_level,
                f"/predict of {n} image(s): HTTP {status}, logits "
                f"{logits.shape}, level {resp['model_level']}, "
                f"{time.perf_counter() - t0:.2f}s",
            )
            served.append(logits)
            offset += n
        served = np.concatenate(served)
        reference = _reference_logits(expt_dir, images)
        scale = float(np.abs(reference).max())
        worst = float(np.abs(served - reference).max())
        check(
            np.isfinite(served).all() and worst <= BF16_TOL * scale,
            f"served logits match a direct apply: max |diff| {worst:.3e} "
            f"against max |logit| {scale:.3e}",
        )

        status, raw = _http(f"{url}/healthz")
        health = json.loads(raw)
        check(
            status == 200
            and health["status"] == "ok"
            and health["level"] == final_level
            and health["compiled_buckets"] == health["buckets"],
            f"/healthz: level {health['level']}, density "
            f"{health['density']}, buckets {health['compiled_buckets']}",
        )
        status, raw = _http(f"{url}/metrics")
        lines = raw.decode().splitlines()
        misses = f"turboprune_serve_compile_cache_misses_total {len(engine.buckets)}"
        requests = f"turboprune_serve_requests_total {len(request_sizes)}"
        check(
            status == 200 and misses in lines and requests in lines,
            f"/metrics: {requests!r}, and no compile under traffic "
            f"({misses!r})",
        )
    finally:
        report = server.graceful_shutdown()
        thread.join(30)
    check(not thread.is_alive(), f"server drained and stopped: {report}")


# ------------------------------------------------------------------- flash
def _dense_attention(q, k, v, valid, scale):
    """The jnp oracle of tests/test_flash.py: fp32, scores materialized."""
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32), k.astype(jnp.float32))
    s = jnp.where(valid[:, None, :] > 0, s * scale, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32))


def _close(a, b, what: str) -> None:
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    scale, worst = float(np.abs(b).max()), float(np.abs(a - b).max())
    check(
        np.isfinite(a).all() and worst <= BF16_TOL * scale,
        f"{what}: max |diff| {worst:.3e} against max |ref| {scale:.3e}",
    )


def phase_flash(*, bh: int, seq: int, valid_len: int, d: int):
    """ops/flash.py compiled by Mosaic — interpret=False is passed, so this
    phase cannot run interpreted — forward and jax.grad, bf16."""
    from turboprune_tpu.ops.flash import flash_attention

    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v, tgt = (
        jax.random.normal(key, (bh, seq, d), jnp.bfloat16) for key in keys
    )
    valid = (jnp.arange(seq) < valid_len).astype(jnp.float32)[None, :]
    scale = d**-0.5

    def flash(q, k, v):
        return flash_attention(q, k, v, valid, scale, interpret=False)

    def dense(q, k, v):
        return _dense_attention(q, k, v, valid, scale)

    def loss(attn):
        return lambda q, k, v: jnp.sum(
            attn(q, k, v).astype(jnp.float32) * tgt.astype(jnp.float32)
        )

    compiled = jax.jit(flash).lower(q, k, v).compile()
    check(
        "tpu_custom_call" in compiled.as_text(),
        f"forward at [{bh}, {seq}, {d}] bf16 is a Mosaic kernel",
    )
    _close(compiled(q, k, v), jax.jit(dense)(q, k, v), "flash forward vs dense")
    grads = jax.jit(jax.grad(loss(flash), argnums=(0, 1, 2)))(q, k, v)
    want = jax.jit(jax.grad(loss(dense), argnums=(0, 1, 2)))(q, k, v)
    for name, got, ref in zip("qkv", grads, want):
        _close(got, ref, f"flash d{name} vs dense")


# ------------------------------------------------------------- four chips
def phase_data_parallel(
    *, devices: int, mask_tol: float, **train_sizes
) -> None:
    """The IMP run sharded over ``devices`` against the same global batch
    and seed on one device."""
    many = phase_train(num_devices=devices, **train_sizes)
    one = phase_train(num_devices=1, **train_sizes)

    layout = sorted(many["layout"], key=lambda s: s["rows"])
    say(f"  first train batch: {layout}")
    batch = train_sizes["batch"]
    per = batch // devices
    check(
        len({s["device"] for s in layout}) == devices
        and [s["rows"] for s in layout]
        == [(i * per, (i + 1) * per) for i in range(devices)]
        and all(s["intact"] for s in layout),
        f"each of {devices} devices holds its own {per} rows of the batch "
        f"of {batch}",
    )
    check(
        len(one["layout"]) == 1 and one["layout"][0]["rows"] == (0, batch),
        "the comparison run held the whole batch on one device",
    )
    check(
        len(many["levels"][-1]["param_devices"]) == devices
        and len(one["levels"][-1]["param_devices"]) == 1,
        f"state replicated over {devices} devices against 1",
    )
    for a, b in zip(many["levels"], one["levels"]):
        rel = abs(a["train_loss"] - b["train_loss"]) / abs(b["train_loss"])
        check(
            rel <= SAME_RUN_TOL,
            f"level {a['level']} train loss: {a['train_loss']:.5f} on "
            f"{devices} against {b['train_loss']:.5f} on 1 (rel {rel:.2e}, "
            f"bound {SAME_RUN_TOL:.0e})",
        )
        # Not compared: eval-mode loss after a handful of steps rides on
        # BatchNorm running statistics that have barely moved from their
        # initial values, and amplifies last-bit differences many times over.
        say(
            f"  level {a['level']} test loss: {a['test_loss']:.5f} on "
            f"{devices}, {b['test_loss']:.5f} on 1"
        )
    a, b = many["levels"][1]["mask"], one["levels"][1]["mask"]
    differ = int(np.count_nonzero(a != b))
    # Both runs cut the same count (checked against the ladder above), but
    # not bit-identical sets: the trained weights differ in their last bits
    # by reduction order, and the weights nearest zero — the ones the
    # threshold decides between — are the ones a bf16 gradient moves most
    # in proportion.
    check(
        differ <= mask_tol * a.size,
        f"masks after the first prune: {differ} of {a.size} positions "
        f"differ ({differ / a.size:.2e}, bound {mask_tol:.0e})",
    )


def phase_ring(
    *,
    data: int,
    model: int,
    batch: int,
    seq: int,
    dim: int,
    heads: int,
) -> None:
    """Sequence-parallel attention (parallel/ring.py, as models/vit.py wires
    it) on a (data, model) mesh against flax's dense attention with the same
    parameters, forward and grad, bf16."""
    import flax.linen as nn
    from jax.sharding import NamedSharding, PartitionSpec as P

    from turboprune_tpu.models.vit import RingSelfAttention
    from turboprune_tpu.parallel import create_mesh

    mesh = create_mesh(num_devices=data * model, model_parallelism=model)
    check(
        dict(mesh.shape) == {"data": data, "model": model},
        f"mesh {dict(mesh.shape)} over devices "
        f"{[d.id for d in mesh.devices.flat]}",
    )
    dense = nn.MultiHeadDotProductAttention(num_heads=heads, dtype=jnp.bfloat16)
    ring = RingSelfAttention(num_heads=heads, mesh=mesh, dtype=jnp.bfloat16)
    kx, kt, kp = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(kx, (batch, seq, dim), jnp.float32)
    tgt = jax.random.normal(kt, (batch, seq, dim), jnp.float32)
    params = dense.init(kp, x, x)
    x, tgt = jax.device_put((x, tgt), NamedSharding(mesh, P("data")))
    params = jax.device_put(params, NamedSharding(mesh, P()))

    def loss(apply):
        def f(params, x):
            out = apply(params, x).astype(jnp.float32)
            return jnp.sum(out * tgt), out

        return jax.jit(jax.value_and_grad(f, argnums=(0, 1), has_aux=True))

    ring_fn = loss(lambda p, x: ring.apply(p, x))
    dense_fn = loss(lambda p, x: dense.apply(p, x, x))
    hlo = ring_fn.lower(params, x).compile().as_text()
    check(
        "collective-permute" in hlo,
        "the ring program rotates K/V between devices (collective-permute)",
    )
    (_, out_r), (gp_r, gx_r) = ring_fn(params, x)
    (_, out_d), (gp_d, gx_d) = dense_fn(params, x)
    check(
        len(out_r.sharding.device_set) == data * model,
        f"ring output spans {len(out_r.sharding.device_set)} devices",
    )
    _close(out_r, out_d, f"ring forward vs dense at [{batch}, {seq}, {dim}]")
    _close(gx_r, gx_d, "ring d(input) vs dense")
    # One scale for the whole tree: the key bias's gradient is zero in exact
    # arithmetic (softmax ignores a shift of every key), so alone it is
    # rounding noise against rounding noise.
    _close(_flat(gp_r), _flat(gp_d), "ring d(params) vs dense")


# -------------------------------------------------------------------- main
# ResNet50 / ImageNet-224 at the published widths (conf/imagenet_imp.yaml);
# the ladder 1.0, 0.8, 0.64 is dense, then prune + rewind twice.
_RESNET50 = dict(
    config_name="imagenet_imp",
    batch=TRAIN_BATCH,
    num_train=2048,
    num_test=512,
    steps=4,
)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--chips",
        type=int,
        choices=(1, 4),
        default=1,
        help="4: run only the paths that exist across four chips",
    )
    args = parser.parse_args(argv)

    from turboprune_tpu.utils.compile_cache import place_compile_cache

    cache_dir = place_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke needs a TPU and JAX found platform {dev.platform!r}"
        )
    if len(jax.devices()) < args.chips:
        raise SystemExit(
            f"--chips {args.chips} on a host with {len(jax.devices())} device(s)"
        )
    from turboprune_tpu.parallel.multihost import _cluster_hinted

    entries = cache_entries(cache_dir)
    say(
        f"device: {dev.device_kind} x{len(jax.devices())}, jax "
        f"{jax.__version__}; compile cache {cache_dir}: {entries} entries; "
        f"cluster hinted: {_cluster_hinted()}"
    )
    check(
        not _cluster_hinted() and jax.process_count() == 1,
        "a single host is not taken for a cluster",
    )

    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            timed(
                "data-parallel",
                phase_data_parallel,
                devices=4,
                mask_tol=5e-2,
                base_dir=OUT_DIR,
                platform="tpu",
                target_sparsity=0.2,
                **_RESNET50,
            )
            # DeiT-small attention: 6 heads of 64, 196 patches + cls.
            timed(
                "ring", phase_ring,
                data=2, model=2, batch=64, seq=197, dim=384, heads=6,
            )
        else:
            run = timed(
                "train",
                phase_train,
                base_dir=OUT_DIR,
                platform="tpu",
                target_sparsity=0.3,
                **_RESNET50,
            )
            say(
                f"  batch {TRAIN_BATCH} (BASELINE.md asks 512: see "
                f"TRAIN_BATCH); HBM after the train phase: {hbm()}"
            )
            timed(
                "serve",
                phase_serve,
                expt_dir=run["expt_dir"],
                platform="tpu",
                request_sizes=(1, 3, 8),
                final_level=2,
            )
            # DeiT-small on one chip: batch 64 x 6 heads, 197 -> 256.
            timed(
                "flash", phase_flash,
                bh=384, seq=256, valid_len=197, d=64,
            )
        compile_s, _, hits, misses = compiled_since(t0)
        say(
            f"total {time.perf_counter() - t0:.1f}s, of it compile "
            f"{compile_s:.1f}s; compile cache {entries} -> "
            f"{cache_entries(cache_dir)} entries, {hits} hits / "
            f"{misses} misses; HBM: {hbm()}"
        )
    finally:
        # Checkpoints are ~100 MB each; the tool brings back 64 MiB. Metrics
        # CSVs and the config snapshot stay for a post-mortem.
        for heavy in (*OUT_DIR.glob("*/checkpoints"), *OUT_DIR.glob("*/artifacts")):
            shutil.rmtree(heavy)

    print(
        json.dumps(
            {
                "ok": True,
                "device": {
                    "platform": dev.platform,
                    "kind": dev.device_kind,
                    "count": len(jax.devices()),
                },
            }
        ),
        flush=True,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
