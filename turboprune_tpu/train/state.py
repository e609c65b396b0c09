"""Training state pytree.

The reference's trainer state is scattered across mutable objects — a DDP
module with mask buffers, a torch optimizer, a scheduler with its own step
counter (base_harness.py:42-113). Here it is one immutable pytree: the unit
that a jitted step consumes and returns (donated, so XLA updates in place),
that Orbax checkpoints, and that ``jax.device_put`` replicates across the
mesh. Masks live beside params — not inside layers — so pruning is plain
pytree math between levels.
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
import optax
from flax import struct

from ..ops.masking import PyTree, make_masks


@struct.dataclass
class TrainState:
    step: jax.Array                      # global optimizer step count
    params: PyTree                       # raw (unmasked) fp32 params
    masks: PyTree                        # bool mask tree (None at non-prunable)
    batch_stats: PyTree                  # BatchNorm running stats ({} for ViT)
    opt_state: optax.OptState
    rng: jax.Array                       # base key; folded with step per-step

    @property
    def variables(self) -> dict:
        out = {"params": self.params}
        if self.batch_stats:
            out["batch_stats"] = self.batch_stats
        return out


def init_variables(
    model,
    rng: jax.Array,
    input_shape: tuple,
    input_dtype: str = "float32",
    as_one_program: bool = False,
) -> dict:
    """Initialize model variables with a dummy batch (shape-only trace).
    ``as_one_program`` jits the init: op by op, the init pass of a model of
    some hundred operations is some hundred compilations."""
    p_rng, d_rng = jax.random.split(rng)
    dummy = jnp.zeros(input_shape, input_dtype)
    init = functools.partial(model.init, train=False)
    if as_one_program:
        init = jax.jit(init)
    return init({"params": p_rng, "dropout": d_rng}, dummy)


def create_train_state(
    model,
    tx: optax.GradientTransformation,
    rng: jax.Array,
    input_shape: tuple,
    variables: Optional[dict] = None,
    masks: Optional[PyTree] = None,
    input_dtype: str = "float32",
    init_as_one_program: bool = False,
) -> TrainState:
    """Fresh state: init variables (unless given), all-ones masks (unless
    given), fresh optimizer state — the reference's per-level optimizer
    re-init is `create_train_state(..., variables=prev, masks=pruned)`
    (standard_pruning_harness.py:174 semantics without object rebuild)."""
    init_rng, state_rng = jax.random.split(rng)
    if variables is None:
        variables = init_variables(
            model, init_rng, input_shape, input_dtype, init_as_one_program
        )
    params = variables["params"]
    if masks is None:
        masks = make_masks(params)
    return TrainState(
        step=jnp.zeros((), jnp.int32),
        params=params,
        masks=masks,
        batch_stats=variables.get("batch_stats", {}),
        opt_state=tx.init(params),
        rng=state_rng,
    )


def reset_optimizer(state: TrainState, tx: optax.GradientTransformation) -> TrainState:
    """Fresh opt_state + step counter for a new level/cycle, keeping
    params/masks/batch_stats (reference rebuilds the optimizer each level,
    standard_pruning_harness.py:174; each cycle, cyclic_harness.py:193)."""
    return state.replace(
        step=jnp.zeros((), jnp.int32), opt_state=tx.init(state.params)
    )
