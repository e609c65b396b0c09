"""What the backward pass of a checkpointed layer keeps.

Both language models wrap every layer in a ``jax.checkpoint``: a backward pass
holds the layer's input and runs the layer's forward again. A few values are
narrow and dear to rebuild (a float32 product, a ``top_k``, a sort, a
projection into fewer columns), so the code that makes one tags it
(``jax.ad_checkpoint.checkpoint_name``) and a model lists, at its own
``nn.remat`` line, the tags its layers keep (``keeping``). A tag decides
nothing: under a bare ``jax.checkpoint``, or a policy that does not name it,
the value is rebuilt as it always was.

Gauges (utils/tracing.py), set as JAX asks the policy, which it does once a
tagged value where a program that differentiates the layers is traced: a run
of the compiled program sets nothing, and neither does the trace of a program
that does not differentiate (the init, the eval). ``remat_saved_values``: the
values the newest such trace of a model keeps; ``remat_saved_mib``: their
bytes, from the traced shapes.
"""

from __future__ import annotations

import math

import jax

from ..utils import tracing


def keeping(names: tuple):
    """The ``jax.checkpoint`` policy of one trace of a model's layers: keep
    the values tagged with one of ``names``, rebuild the rest."""
    named = jax.checkpoint_policies.save_only_these_names(*names)
    kept = {"values": 0, "bytes": 0}

    def policy(prim, *avals, **params):
        saved = named(prim, *avals, **params)
        if saved:  # a ``name`` equation: its one operand is the value kept
            kept["values"] += 1
            kept["bytes"] += math.prod(avals[0].shape) * avals[0].dtype.itemsize
            tracing.gauge("remat_saved_values", kept["values"])
            tracing.gauge("remat_saved_mib", kept["bytes"] / 2**20)
        return saved

    return policy
