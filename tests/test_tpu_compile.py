"""What can be shown about the chip without one.

Compiles, kept in ONE file because only one process may hold the TPU
compiler's library and pytest-xdist hands a file to one worker:

* For a DESCRIBED TPU v5e (``jax.experimental.topologies``; the
  compiler is installed here, no chip is attached): the Pallas flash kernel
  forward and backward at the shapes the main path uses, ring attention on
  a 2x2 mesh, and (slow) the BASELINE ResNet50 batch-512 train step with its
  memory count. A compile that passes is not a run; it is what interpret
  mode cannot show (tiling, VMEM, whether the program fits).
* Where the compile cache is placed (utils/compile_cache.py).

chip_smoke.py's phases at tiny size run in tests/test_chip_smoke.py.

The topology is described inside a fixture, never at import, in a skipif or
in a parametrize argument: every xdist worker imports this file, and only
the one that runs it may load the library.
"""

import os
import re

from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

import chip_smoke
from turboprune_tpu.ops.flash import (
    flash_attention,
    flash_attention_blockdiff,
    flash_attention_causal,
)
from turboprune_tpu.ops import retention, ssd
from turboprune_tpu.ops.ssd import ssd_chunked

V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs to /tmp
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    # graftlint: disable=broad-except -- whatever keeps the TPU compiler from describing a topology here (no libtpu, lock held by another process) is a reason to skip, not to fail
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _placed(tree, sharding):
    """The tree's shapes, each said to live under ``sharding`` — there is no
    device to put an array on, so compiles take shapes."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree,
    )


@pytest.fixture()
def no_persistent_cache():
    """A compile for a described device can be written to the persistent
    cache but not read back without a chip (the next one warns and compiles
    again), so the cache is off around these compiles whatever the session
    has set."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


# DeiT-small on one chip (batch 64 x 6 heads of 64, 197 tokens padded to
# 256) and a long-sequence shape.
@pytest.mark.parametrize("backward", [False, True], ids=["forward", "grad"])
@pytest.mark.parametrize("shape", [(384, 256, 64), (48, 1024, 64)])
def test_flash_kernel_compiles_for_v5e(
    one_chip, no_persistent_cache, shape, backward
):
    qkv, valid = _placed(
        (
            jax.ShapeDtypeStruct(shape, jnp.bfloat16),
            jax.ShapeDtypeStruct((1, shape[1]), jnp.float32),
        ),
        one_chip,
    )

    def forward(q, k, v, valid):
        return flash_attention(
            q, k, v, valid, shape[2] ** -0.5, interpret=False
        )

    def loss(q, k, v, valid):
        return forward(q, k, v, valid).astype(jnp.float32).sum()

    fn = jax.grad(loss, argnums=(0, 1, 2)) if backward else forward
    compiled = jax.jit(fn).lower(qkv, qkv, qkv, valid).compile()
    assert "tpu_custom_call" in compiled.as_text()


# The language-model cell's attention layer: 32 query heads over 8 key/value
# heads of 64, one packed sequence of 8,192 tokens, blocks of 512; and the
# convolution-hybrid cell's, a tensor-parallel quarter of the same: 8 over 2.
@pytest.mark.parametrize("backward", [False, True], ids=["forward", "grad"])
@pytest.mark.parametrize("heads, kv_heads", [(32, 8), (8, 2)])
def test_causal_flash_kernel_compiles_for_v5e(one_chip, no_persistent_cache, heads, kv_heads, backward):
    q, kv, seg = _placed(
        (
            jax.ShapeDtypeStruct((heads, 8192, 64), jnp.bfloat16),
            jax.ShapeDtypeStruct((kv_heads, 8192, 64), jnp.bfloat16),
            jax.ShapeDtypeStruct((1, 8192), jnp.int32),
        ),
        one_chip,
    )

    def forward(q, k, v, seg):
        return flash_attention_causal(q, k, v, seg, 0.015625, 512, 512, interpret=False)

    def loss(q, k, v, seg):
        return forward(q, k, v, seg).astype(jnp.float32).sum()

    fn = jax.grad(loss, argnums=(0, 1, 2)) if backward else forward
    text = jax.jit(fn).lower(q, kv, kv, seg).compile().as_text()
    names = ("flash_causal_fwd", "flash_causal_dq", "flash_causal_dkv") if backward else ("flash_causal_fwd",)
    assert "tpu_custom_call" in text and all(name in text for name in names)


@pytest.mark.parametrize("backward", [False, True], ids=["forward", "grad"])
def test_blockdiff_flash_kernel_compiles_for_v5e(one_chip, no_persistent_cache, backward):
    """The block-diffusion cell's shapes: 4 query heads on one key/value head,
    the clean and the noised copy of 8,192 tokens, blocks of 512."""
    q, kv, rows = _placed(
        (
            jax.ShapeDtypeStruct((4, 16384, 128), jnp.bfloat16),
            jax.ShapeDtypeStruct((1, 16384, 128), jnp.bfloat16),
            jax.ShapeDtypeStruct((1, 8192), jnp.int32),
        ),
        one_chip,
    )

    def forward(q, k, v, doc, blk):
        return flash_attention_blockdiff(q, k, v, doc, blk, 128**-0.5, 512, 512, interpret=False)

    def loss(q, k, v, doc, blk):
        return forward(q, k, v, doc, blk).astype(jnp.float32).sum()

    fn = jax.grad(loss, argnums=(0, 1, 2)) if backward else forward
    text = jax.jit(fn).lower(q, kv, kv, rows, rows).compile().as_text()
    names = ("flash_blockdiff_fwd", "flash_blockdiff_dq", "flash_blockdiff_dkv") if backward else ("flash_blockdiff_fwd",)
    assert "tpu_custom_call" in text and all(name in text for name in names)


# One Mamba-2 layer's scan at published widths over 8,192 tokens: granite's
# 64 heads at chunks of 256, and one chip's group of Nemotron-3-Super, 16
# heads at chunks of 128.
@pytest.mark.parametrize("backward", [False, True], ids=["forward", "grad"])
@pytest.mark.parametrize("heads, chunk, temp_mib", [(64, 256, 256), (16, 128, 48)])
def test_chunked_scan_compiles_for_v5e_and_fits(one_chip, no_persistent_cache, heads, chunk, temp_mib, backward):
    """The scan must come out as its Pallas kernels, whose blocks must fit the
    chip's fast memory, and no [Q, Q] tensor may be among the program's
    temporaries: the float32 states entering the chunks (64 MiB at 64 heads),
    the gradients and the per-head factors are all it holds (192.4 MiB as
    compiled for the gradient at 64 heads, 32.2 for the forward at 16; XLA's
    form held 2 GiB of decays and scores under a limit of 4)."""
    x, dt, a, bc, seg = _placed(
        (
            jax.ShapeDtypeStruct((1, 8192, heads, 64), jnp.bfloat16),
            jax.ShapeDtypeStruct((1, 8192, heads), jnp.float32),
            jax.ShapeDtypeStruct((heads,), jnp.float32),
            jax.ShapeDtypeStruct((1, 8192, 128), jnp.bfloat16),
            jax.ShapeDtypeStruct((1, 8192), jnp.int32),
        ),
        one_chip,
    )

    def forward(x, dt, a, b, c, seg):
        return ssd_chunked(x, dt, a, b, c, seg, chunk)

    def loss(x, dt, a, b, c, seg):
        return jnp.square(forward(x, dt, a, b, c, seg).astype(jnp.float32)).sum()

    fn = jax.grad(loss, argnums=(0, 1, 2, 3, 4)) if backward else forward
    with mock.patch.object(ssd, "_use_interpret", return_value=False):  # the CPU backend would interpret
        compiled = jax.jit(fn).lower(x, dt, a, bc, bc, seg).compile()
    text = compiled.as_text()
    names = ("ssd_scan_fwd", "ssd_scan_bwd") if backward else ("ssd_scan_fwd",)
    assert "tpu_custom_call" in text and all(name in text for name in names)
    assert compiled.memory_analysis().temp_size_in_bytes < temp_mib * 2**20


def test_grouped_scan_compiles_for_v5e(one_chip, no_persistent_cache):
    """Two groups of 16 heads: the kernels under ``jax.vmap`` must still be
    handed blocks whose last two axes are tokens and lanes (with the group as
    the third axis of ``B`` and ``C`` the chip's compiler refuses them; the
    interpreter does not)."""
    x, dt, a, bc, seg = _placed(
        (
            jax.ShapeDtypeStruct((1, 2048, 32, 64), jnp.bfloat16),
            jax.ShapeDtypeStruct((1, 2048, 32), jnp.float32),
            jax.ShapeDtypeStruct((32,), jnp.float32),
            jax.ShapeDtypeStruct((1, 2048, 2, 128), jnp.bfloat16),
            jax.ShapeDtypeStruct((1, 2048), jnp.int32),
        ),
        one_chip,
    )

    def loss(x, dt, a, b, c, seg):
        return jnp.square(ssd_chunked(x, dt, a, b, c, seg, 128).astype(jnp.float32)).sum()

    with mock.patch.object(ssd, "_use_interpret", return_value=False):
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(x, dt, a, bc, bc, seg).compile().as_text()
    assert "ssd_scan_fwd" in text and "ssd_scan_bwd" in text


# One layer's power retention as one chip of eight holds Brumby-14B-Base: 5
# query heads on 1 key/value head of 128, 32,768 tokens in chunks of 512.
@pytest.mark.parametrize("backward", [False, True], ids=["forward", "grad"])
def test_power_retention_compiles_for_v5e_and_fits(one_chip, no_persistent_cache, backward):
    """Retention must come out as its Pallas kernels, whose state (4.06 MiB
    of features by values, float32) and blocks must fit the chip's fast
    memory, and nothing as wide as the features may be among the program's
    temporaries: the gradient holds the states entering the 64 chunks (260
    MiB), the float32 gradients and what every token is scaled by (389 MiB as
    compiled), the plain forward 16 MiB."""
    q, kv, lam, seg = _placed(
        (
            jax.ShapeDtypeStruct((1, 1, 5, 32768, 128), jnp.bfloat16),
            jax.ShapeDtypeStruct((1, 1, 32768, 128), jnp.bfloat16),
            jax.ShapeDtypeStruct((1, 32768, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, 32768), jnp.int32),
        ),
        one_chip,
    )

    def forward(q, k, v, lam, seg):
        return retention.power_retention(q, k, v, lam, seg, chunk=512)

    def loss(q, k, v, lam, seg):
        return jnp.square(forward(q, k, v, lam, seg).astype(jnp.float32)).sum()

    fn = jax.grad(loss, argnums=(0, 1, 2, 3)) if backward else forward
    with mock.patch.object(retention, "_use_interpret", return_value=False):  # the CPU backend would interpret
        compiled = jax.jit(fn).lower(q, kv, kv, lam, seg).compile()
    text = compiled.as_text()
    names = ("retention_fwd", "retention_bwd") if backward else ("retention_fwd",)
    assert "tpu_custom_call" in text and all(name in text for name in names)
    assert compiled.memory_analysis().temp_size_in_bytes < (448 if backward else 32) * 2**20


def test_routed_experts_compile_for_v5e_as_grouped_kernels(one_chip, no_persistent_cache):
    """One LatentMoE layer's routed part at published widths over 8,192
    tokens, forward and backward: top-22 of 512, the 16 experts held, the
    10,496-row pair buffer in tiles of 128. The two products and their
    transposes must come out as the grouped Pallas kernels (a tile of rows
    against its expert's kernel), not as a dense product over every expert,
    their blocks must fit the chip's fast memory, and the further rounds (a
    loop as long as the routing makes it) must not cost the chip gigabytes
    they never use."""
    from turboprune_tpu.ops import moe

    tokens, k, experts, held, latent, width = 8192, 22, 512, 16, 1024, 2688
    capacity, tile = moe.pair_capacity(tokens, k, experts, held), moe.pair_tile(tokens, k, experts)
    z, logits, up, down = _placed(
        (
            jax.ShapeDtypeStruct((tokens, latent), jnp.bfloat16),
            jax.ShapeDtypeStruct((tokens, experts), jnp.float32),
            jax.ShapeDtypeStruct((held, latent, width), jnp.bfloat16),
            jax.ShapeDtypeStruct((held, width, latent), jnp.bfloat16),
        ),
        one_chip,
    )

    def loss(z, logits, up, down):
        top, weights = moe.route(logits, jnp.zeros(experts), k, 5.0)
        out, counters = moe.routed_experts(z, top, weights, (up, down), 0, capacity, tile)
        return jnp.sin(out).sum(), counters  # a backward pass that needs the result, as a next layer's does

    # On the CPU backend the kernels would be interpreted: compile the chip's.
    with mock.patch.object(moe, "_use_interpret", lambda: False):
        lowered = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3), has_aux=True)).lower(z, logits, up, down)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert (capacity, tile) == (10496, 128)
    assert text.count("tpu_custom_call") >= 6  # two forward, four backward, and the rounds' own
    # The combine's float32 rows of 1,024 are added by the row kernel; the
    # dispatch's bfloat16 rows of 1,024 are half a tile of words, so their
    # gradients are still added by XLA's scatter.
    assert "moe_add_rows" in text
    assert re.search(rf"bf16\[{tokens},{latent}\]\S* scatter\(", text)
    assert not re.search(rf"f32\[{tokens},{latent}\]\S* scatter\(", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 2**30


@pytest.mark.parametrize(
    "rows, k, experts, held, width, buffer",
    [(16384, 8, 128, 16, 768, 26624), (8192, 4, 32, 8, 1792, 13312)],
    ids=["blockdiff", "conv_hybrid"],
)
def test_gated_routed_experts_compile_for_v5e_as_grouped_kernels(
    one_chip, no_persistent_cache, rows, k, experts, held, width, buffer
):
    """One routed layer's experts at published widths, forward and backward,
    three kernels an expert. The block-diffusion cell's: 16,384 rows, top-8
    of 128, 16 experts of 768 held, a 26,624-row pair buffer. The
    convolution-hybrid cell's: 8,192 rows, top-4 of 32, 8 experts of 1,792
    held, 13,312 rows (its sigmoid router's choice is a ``top_k`` like the
    softmax router's here)."""
    from turboprune_tpu.ops import moe

    hidden = 2048
    capacity, tile = moe.pair_capacity(rows, k, experts, held), moe.pair_tile(rows, k, experts)
    z, logits, up, down = _placed(
        (
            jax.ShapeDtypeStruct((rows, hidden), jnp.bfloat16),
            jax.ShapeDtypeStruct((rows, experts), jnp.float32),
            jax.ShapeDtypeStruct((held, hidden, width), jnp.bfloat16),
            jax.ShapeDtypeStruct((held, width, hidden), jnp.bfloat16),
        ),
        one_chip,
    )

    def loss(z, logits, gate, up, down):
        top, weights = moe.route_softmax(logits, k)
        out, counters = moe.routed_experts(z, top, weights, (gate, up, down), 0, capacity, tile)
        return jnp.sin(out).sum(), counters  # a backward pass that needs the result, as a next layer's does

    with mock.patch.object(moe, "_use_interpret", lambda: False):
        lowered = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True)).lower(z, logits, up, up, down)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert (capacity, tile) == (buffer, 128)
    assert text.count("tpu_custom_call") >= 9  # three forward, six backward, and the rounds' own
    # Rows of 2,048 in both dtypes: the combine's scatter-add and the
    # gather's transpose are the row kernel, in the first round and in the
    # loop of further ones, and no scatter of XLA's over the tokens is left.
    assert text.count("moe_add_rows") >= 4
    assert not re.search(rf"(?:bf16|f32)\[{rows},{hidden}\]\S* scatter\(", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 2**30


def test_ring_attention_compiles_for_a_2x2_mesh(topo, no_persistent_cache):
    """The sequence-parallel path exists only across chips and the driver
    runs one: this keeps it compiling for the four-chip host."""
    import flax.linen as nn

    from turboprune_tpu.models.vit import RingSelfAttention

    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))
    ring = RingSelfAttention(num_heads=6, mesh=mesh, dtype=jnp.bfloat16)
    dense = nn.MultiHeadDotProductAttention(num_heads=6, dtype=jnp.bfloat16)
    x = jax.ShapeDtypeStruct((64, 197, 384), jnp.float32)
    params = jax.eval_shape(
        lambda: dense.init(jax.random.key(0), jnp.zeros(x.shape), jnp.zeros(x.shape))
    )
    def loss(params, x):
        return ring.apply(params, x).astype(jnp.float32).sum()

    compiled = (
        jax.jit(jax.grad(loss, argnums=(0, 1)))
        .lower(
            _placed(params, NamedSharding(mesh, P())),
            _placed(x, NamedSharding(mesh, P("data"))),
        )
        .compile()
    )
    assert "collective-permute" in compiled.as_text()


@pytest.mark.slow
def test_resnet50_batch512_train_step_fits_one_v5e(one_chip, no_persistent_cache):
    """BASELINE.md's step: ResNet50, ImageNet-224, batch 512, bf16, donated
    state — the program conf/imagenet_imp.yaml asks the harness for. The
    compiler counts this one program, not what else the process keeps on
    the device (chip_smoke.TRAIN_BATCH says what that came to)."""
    from turboprune_tpu.models import create_model
    from turboprune_tpu.train import (
        create_optimizer,
        create_schedule,
        create_train_state,
        make_train_step,
    )

    model = create_model(
        "resnet50", num_classes=1000, dataset_name="ImageNet",
        compute_dtype=jnp.bfloat16,
    )
    schedule = create_schedule(
        "TriangularSchedule", base_lr=0.2, epochs=90, steps_per_epoch=1251
    )
    tx = create_optimizer("SGD", schedule, momentum=0.9, weight_decay=1e-4)
    state = _placed(
        jax.eval_shape(
            lambda: create_train_state(
                model, tx, jax.random.key(0), (1, 224, 224, 3)
            )
        ),
        one_chip,
    )
    batch = _placed(
        (
            jax.ShapeDtypeStruct((512, 224, 224, 3), jnp.float32),
            jax.ShapeDtypeStruct((512,), jnp.int32),
        ),
        one_chip,
    )
    step = jax.jit(make_train_step(model, tx, schedule), donate_argnums=0)
    memory = step.lower(state, batch).compile().memory_analysis()
    assert (
        memory.temp_size_in_bytes + memory.argument_size_in_bytes
        < V5E_HBM_BYTES
    )


class TestCompileCachePlacement:
    def test_environment_wins_and_nothing_is_set_in_code(
        self, tmp_path, monkeypatch
    ):
        from turboprune_tpu.utils.compile_cache import place_compile_cache

        before = jax.config.jax_compilation_cache_dir
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert place_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before

    def test_default_is_a_fixed_path_in_the_checkout(self, monkeypatch):
        from pathlib import Path

        from turboprune_tpu.utils.compile_cache import place_compile_cache

        before = jax.config.jax_compilation_cache_dir
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        try:
            placed = place_compile_cache()
            assert placed == str(Path(chip_smoke.__file__).parent / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == placed
        finally:
            jax.config.update("jax_compilation_cache_dir", before)
