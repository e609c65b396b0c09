"""Jitted train / eval steps.

The reference's hot loop is ``train_step``: forward under bf16 autocast,
CE loss, backward (DDP allreduce fires inside), optimizer step
(base_harness.py:115-134). Here the whole thing is ONE pure function
``(state, batch) -> (state, metrics)`` that jit compiles to a single fused
XLA program: the mask multiply folds into each conv's operand, the psum over
the data axis is inserted by the partitioner, and donation makes the update
in-place in HBM. No autocast machinery — the model's compute dtype is bf16
by construction and params/optimizer stay fp32 (the reference's AMP policy,
base_harness.py:92-101, without the amp plumbing).

Metrics come back as global SUMS (loss*n, correct, n) so the host can
accumulate exact epoch averages without per-step device syncs — replacing
torchmetrics' dist_sync_on_step + loss all_reduce AVG
(base_harness.py:54-60,192-200) with arithmetic that is already correct
under the jit partitioner.

Three kinds of batch go through the same step (``Batch`` below): images with
integer labels (CE summed over the batch); packed token sequences with
next-token targets (CE over the targets that are not the padding label, ``n``
their count); and packed sequences noised for block-diffusion training, whose
labels are a pair (targets, weights): the loss is the weighted sum over the
masked targets divided by the tokens, ``n`` the tokens (data/tokens.py). Which
one a step has it reads off the labels, never off a model's name.

**The loss in blocks.** A token batch whose logits would take more than
``LOGITS_BYTES`` (tokens x vocabulary x 4, read off the shapes as the step
is traced) never has them whole: the model is handed ``sums_in_blocks`` as
its head's ``reduce`` (models/blocks.py's ``head_output``) and returns the
three sums, head and loss run ``LOSS_BLOCK`` tokens at a time, in the train
step, in the eval step and so in ``evaluate()`` and every probe. A block's
logits are formed once: where the step is differentiated the block that has
them makes its gradient too (``sums_in_blocks``' own derivative rule), and
what is kept from the forward pass to the backward pass is the gradient of
the head's input and of its kernel, which the backward pass would build first
anyway. Smaller batches run as they always did, program for program
(tests/test_loss_blocks.py). Gauges ``loss_blocks_per_step``, the blocks of
the newest such trace, and ``loss_grad_blocks_per_step``, those of them that
made their gradient with their loss (a train step's; an eval's sets only the
first); a process whose steps all have their logits whole sets neither.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import optax

from ..ops.masking import PyTree, apply_masks
from ..utils import tracing
from .state import TrainState

# Float32 logits of a token batch beyond which head and loss run in blocks: 0.75 GiB. The four
# 8,192-token entries read 0.38 to 0.58 GiB and keep their programs; two such sequences a step
# (1.0 GiB) and one of 32,768 tokens (2.3 GiB) run in blocks.
LOGITS_BYTES = 3 * 2**28
LOSS_BLOCK = 2048  # tokens a block, at most

# Three kinds of batch, and which one a step has it reads off the labels:
# (images NHWC, integer labels [B]); for a language model trained on next
# tokens, (tokens [B, 2, T], targets [B, T] with the padding label where a
# token has none), told by the labels' rank; for one trained by diffusion
# over blocks, (tokens [B, 5, T], (targets [B, T], weights [B, T])), a batch
# with weights, told by the pair (data/tokens.py makes both token batches).
Batch = tuple[jax.Array, jax.Array]


def cross_entropy_sum(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Summed CE in fp32 (mean is taken on the host over exact counts)."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    return -jnp.take_along_axis(logp, labels[:, None], axis=1).sum()


def masked_cross_entropy(logits: jax.Array, labels: jax.Array):
    """(summed CE, hits, count) over the labels that are not the padding
    label (< 0), in fp32; any rank, the classes last."""
    valid = labels >= 0
    safe = jnp.maximum(labels, 0)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    per_row = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
    hit = jnp.argmax(logits, axis=-1) == safe
    return (
        jnp.sum(jnp.where(valid, per_row, 0.0)),
        jnp.sum(valid & hit).astype(jnp.float32),
        jnp.sum(valid).astype(jnp.float32),
    )


def weighted_cross_entropy(logits: jax.Array, targets: jax.Array, weights: jax.Array):
    """(weighted CE summed, weighted hits, tokens) of a batch with weights, in
    fp32: the sum over the targets that are not the padding label of ``weight
    * CE``, and how many tokens the batch holds (``weights >= 0``: a target's
    weight is positive, a token without a target has 0, a place without a
    token -1). The sum over the tokens is the loss's divisor: with ``1 / t``
    a masked target its quotient is the block-diffusion bound a token."""
    safe = jnp.maximum(targets, 0)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    per_row = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
    w = jnp.where(targets >= 0, weights.astype(jnp.float32), 0.0)
    hit = jnp.argmax(logits, axis=-1) == safe
    return (
        jnp.sum(w * per_row),
        jnp.sum(jnp.where(hit, w, 0.0)),
        jnp.sum(weights >= 0).astype(jnp.float32),
    )


def token_loss_sums(logits: jax.Array, labels):
    """(loss sum, hits, count) of a token batch, weighted or not."""
    if isinstance(labels, tuple):
        return weighted_cross_entropy(logits, *labels)
    return masked_cross_entropy(logits, labels)


def token_count(labels) -> jax.Array:
    """``token_loss_sums``' count, which is of the labels alone."""
    return jnp.sum((labels[1] if isinstance(labels, tuple) else labels) >= 0).astype(jnp.float32)


def loss_blocks(model, labels) -> int:
    """How many blocks of tokens a step's head and loss run in: 0 where the
    logits are formed whole (an image batch; a token batch whose float32
    logits fit ``LOGITS_BYTES``), else the fewest equal blocks of at most
    ``LOSS_BLOCK`` tokens a sequence divides into."""
    targets = labels[0] if isinstance(labels, tuple) else labels
    if targets.ndim < 2:
        return 0
    if 4 * targets.size * getattr(model, "vocab_size", 0) <= LOGITS_BYTES:
        return 0
    t = targets.shape[-1]
    blocks = next(n for n in range(-(-t // LOSS_BLOCK), t + 1) if t % n == 0)
    tracing.gauge("loss_blocks_per_step", blocks)
    return blocks


def sums_in_blocks(labels, blocks: int) -> Callable:
    """A head's ``reduce`` (models/blocks.py): ``token_loss_sums`` of the
    logits of ``x`` [B, T, D] against ``labels``, ``T / blocks`` tokens of
    every sequence at a time, as a function with a derivative rule of its own.

    Not differentiated (the eval step), a block makes its logits and its three
    sums. Differentiated (the train step), a block also makes, of the logits
    it has in hand, the gradient of its loss sum with respect to its ``x`` and
    to ``operands`` (the head's kernel or the tied table): the one scan keeps
    ``d x`` [B, T, D] in ``x``'s dtype and one float32 accumulator an operand.
    Those are the arrays a backward scan over the blocks would build; the loss
    is the last of the forward pass and the first of the backward pass, so
    they are alive no longer for being made early, and no block's logits are
    formed twice. A block makes its gradient at the cotangent of the mean
    loss, ``1 / token_count(labels)``, so that what it rounds is what a
    backward pass would have rounded; the backward pass multiplies by the
    cotangent it is given over that one, which in the train step is 1.
    Gauge ``loss_grad_blocks_per_step``: the blocks whose gradient the newest
    such trace made with their loss."""
    split = lambda a: jnp.moveaxis(a.reshape(a.shape[0], blocks, -1, *a.shape[2:]), 1, 0)
    zero = (jnp.zeros((), jnp.float32),) * 3
    add = lambda sums, new: tuple(a + b for a, b in zip(sums, new))

    def reduce(logits_of, x, *operands):
        def sums_of(x_block, labels_block, *operands):
            with jax.named_scope("loss"):
                return token_loss_sums(logits_of(x_block, *operands), labels_block)

        @jax.custom_vjp
        def total(x, labels, *operands):
            block = lambda sums, inp: (add(sums, sums_of(*inp, *operands)), None)
            return jax.lax.scan(block, zero, jax.tree.map(split, (x, labels)))[0]

        def total_fwd(x, labels, *operands):
            tracing.gauge("loss_grad_blocks_per_step", blocks)
            at = 1.0 / token_count(labels)

            def block(carry, inp):
                sums, grads = carry
                x_block, labels_block = inp
                # The block as an array of its own, not a slice fused into each product that reads
                # it: XLA then holds it in fast memory for the product with the logits' gradient,
                # as it did in the backward scan this rule replaced (PERF.md section 6, PR 45).
                x_block = jax.lax.optimization_barrier(x_block)
                new, pull = jax.vjp(
                    lambda x_block, *operands: sums_of(x_block, labels_block, *operands), x_block, *operands
                )
                dx, *d_operands = pull((at, *zero[1:]))  # hits and count carry no gradient
                grads = tuple(g + d.astype(jnp.float32) for g, d in zip(grads, d_operands))
                return (add(sums, new), grads), dx

            grads = tuple(jnp.zeros(o.shape, jnp.float32) for o in operands)
            (sums, grads), dx = jax.lax.scan(block, (zero, grads), jax.tree.map(split, (x, labels)))
            return sums, (jnp.moveaxis(dx, 0, 1).reshape(x.shape), grads, at)

        def total_bwd(kept, cotangents):
            dx, grads, at = kept
            g = cotangents[0] / at
            return (
                (g * dx).astype(dx.dtype),
                None,
                *((g * a).astype(o.dtype) for a, o in zip(grads, operands)),
            )

        total.defvjp(total_fwd, total_bwd)
        return total(x, labels, *operands)

    return reduce


def _forward_train(model, params, masks, batch_stats, images, rng, **head):
    """(logits, new batch statistics, the layers' counters); with ``reduce``
    in ``head``, what the model's head makes of the logits in their place. A model that
    names ``counters`` (models/nemotron_h.py) sows int32 scalars under those
    names into the ``counters`` collection, a layer at a time; they come back
    summed over the layers. Every other model returns {} and runs as before."""
    variables = {"params": apply_masks(params, masks)}
    if batch_stats:
        variables["batch_stats"] = batch_stats
        logits, new_model_state = model.apply(
            variables,
            images,
            train=True,
            mutable=["batch_stats"],
            rngs={"dropout": rng},
        )
        return logits, new_model_state.get("batch_stats", {}), {}
    names = getattr(model, "counters", ())
    if names:
        logits, sown = model.apply(
            variables, images, train=True, mutable=["counters"], rngs={"dropout": rng}, **head
        )
        leaves = jax.tree_util.tree_leaves_with_path(sown["counters"])
        return logits, batch_stats, {
            name: sum(v for path, v in leaves if any(getattr(p, "key", None) == name for p in path))
            for name in names
        }
    # No mutable collections (plain VGG, ViT): mutable=[] would make flax
    # return a (logits, state) tuple — don't pass it at all.
    logits = model.apply(variables, images, train=True, rngs={"dropout": rng}, **head)
    return logits, batch_stats, {}


def make_train_step(
    model,
    tx: optax.GradientTransformation,
    schedule: Optional[Callable] = None,
) -> Callable[[TrainState, Batch], tuple[TrainState, dict]]:
    """Build the pure train step. Loss gradient is taken wrt the RAW params —
    the mask multiply inside the forward means masked weights get zero
    data-gradient but still receive weight-decay/momentum updates, exactly
    the reference's semantics (SURVEY.md §3.3)."""

    def train_step(state: TrainState, batch: Batch) -> tuple[TrainState, dict]:
        images, labels = batch
        step_rng = jax.random.fold_in(state.rng, state.step)

        blocks = loss_blocks(model, labels)
        head = {"reduce": sums_in_blocks(labels, blocks)} if blocks else {}

        def loss_fn(params):
            # Named scopes label the device trace's operations by layer; the
            # backward pass comes out as transpose(jvp(forward)).
            with jax.named_scope("forward"):
                logits, new_batch_stats, counters = _forward_train(
                    model, params, state.masks, state.batch_stats, images, step_rng, **head
                )
            if blocks:  # the head has made the loss's sums of the logits, a block at a time
                loss_sum, correct, n = logits
                return loss_sum / n, (None, new_batch_stats, loss_sum, n, correct, counters)
            with jax.named_scope("loss"):
                if isinstance(labels, tuple) or labels.ndim > 1:
                    # Token targets: the mean is over the valid ones (over the
                    # tokens, for a batch with weights), and the logits
                    # [B, T, V] stay inside the gradient's scope. The image
                    # path below keeps its arithmetic, and with it the
                    # compiled program its cells have cached.
                    loss_sum, correct, n = token_loss_sums(logits, labels)
                    return loss_sum / n, (None, new_batch_stats, loss_sum, n, correct, counters)
                n = jnp.asarray(labels.shape[0], jnp.float32)
                loss_sum = cross_entropy_sum(logits, labels)
            return loss_sum / n, (logits, new_batch_stats, loss_sum, n, None, counters)

        grads, (logits, new_batch_stats, loss_sum, n, correct, counters) = jax.grad(
            loss_fn, has_aux=True
        )(state.params)
        with jax.named_scope("optimizer"):
            updates, new_opt_state = tx.update(grads, state.opt_state, state.params)
            new_params = optax.apply_updates(state.params, updates)

        if correct is None:
            correct = jnp.sum(jnp.argmax(logits, axis=-1) == labels).astype(jnp.float32)
        metrics = {"loss_sum": loss_sum, "correct": correct, "count": n, **counters}
        if schedule is not None:
            metrics["lr"] = jnp.asarray(schedule(state.step), jnp.float32)

        new_state = state.replace(
            step=state.step + 1,
            params=new_params,
            batch_stats=new_batch_stats,
            opt_state=new_opt_state,
        )
        return new_state, metrics

    return train_step


def make_scan_chunk(
    train_step: Callable[[TrainState, Batch], tuple[TrainState, dict]],
) -> Callable[[TrainState, Batch], tuple[TrainState, dict]]:
    """Fold a stacked sequence of K train steps into ONE compiled program.

    ``batches`` is K steps stacked on a leading axis: (images
    [K, B, H, W, C], labels [K, B]). ``lax.scan`` runs the step K times
    inside a single XLA executable, collapsing K host dispatches (each
    paying fixed launch latency) into one. Returned metrics are summed over
    the K steps (``lr`` dropped — it is per-step, not summable).

    This is the CIFAR zero-dispatch trick generalized to data that does NOT
    fit in HBM: the streamed harness path stacks K prefetched batches from
    the pipeline engine (data/pipeline.py) and scans them while the engine
    refills behind the running program. K is
    ``dataset_params.scan_chunk_steps``. A device-resident loader's whole
    epoch, already stacked in HBM (data/cifar.py ``epoch_arrays``), is the
    K = steps-per-epoch case of the same program: zero per-step host
    dispatch (the reference pays Python-loop + DDP launch overhead per step
    instead, base_harness.py:174).

    The inner function keeps the name ``scan_chunk``: the traced module
    ``jit_scan_chunk`` is what the benchmark reads as ``step_program``."""

    def scan_chunk(state: TrainState, batches: Batch) -> tuple[TrainState, dict]:
        def body(s, batch):
            s, m = train_step(s, batch)
            return s, m

        state, ms = jax.lax.scan(body, state, batches)
        sums = {
            k: jnp.sum(v) for k, v in ms.items() if k != "lr"
        }
        return state, sums

    return scan_chunk


# benchmarks/tests/test_reference.py imports this name and a PR outside the
# benchmark may not edit that file (ROADMAP D14): it goes when that import does.
make_scan_epoch = make_scan_chunk


def make_scan_eval(
    eval_step: Callable[[TrainState, Batch], dict],
) -> Callable[[TrainState, Batch], dict]:
    """Whole-test-set eval as ONE compiled program (the eval analog of a
    whole-epoch make_scan_chunk): batches stacked [S, B, ...] with padded rows
    carrying label -1, scanned with the state as a constant carry. On 150-epoch CIFAR
    levels eval runs every epoch — per-batch dispatch was the one remaining
    host-loop in the level (VERDICT r3 weak #7)."""

    def scan_eval(state: TrainState, batches: Batch) -> dict:
        def body(s, batch):
            return s, eval_step(s, batch)

        _, ms = jax.lax.scan(body, state, batches)
        return {k: jnp.sum(v) for k, v in ms.items()}

    return scan_eval


def make_eval_step(model) -> Callable[[TrainState, Batch], dict]:
    """Pure eval step (reference test_step, base_harness.py:136-149).

    Rows with label < 0 are PADDING and excluded from every metric: eval
    loaders pad their final batch to the full batch size with label -1 so
    all eval batches share one shape (single compiled executable, and every
    host issues the same number of lockstep collective steps in multi-host
    SPMD — a partial last batch would otherwise deadlock or recompile).

    For schedule-free optimizers evaluate with the averaged weights by
    passing ``state.replace(params=optim.eval_params(opt_state, params))``."""

    def eval_step(state: TrainState, batch: Batch) -> dict:
        images, labels = batch
        variables = {"params": apply_masks(state.params, state.masks)}
        if state.batch_stats:
            variables["batch_stats"] = state.batch_stats
        blocks = loss_blocks(model, labels)
        head = {"reduce": sums_in_blocks(labels, blocks)} if blocks else {}
        with jax.named_scope("eval_forward"):
            logits = model.apply(variables, images, train=False, **head)
        # In blocks, the head has made the sums of the logits already.
        loss_sum, correct, count = logits if blocks else token_loss_sums(logits, labels)
        return {"loss_sum": loss_sum, "correct": correct, "count": count}

    return eval_step
