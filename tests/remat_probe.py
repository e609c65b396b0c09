"""What tests/test_granite.py and tests/test_nemotron_h.py ask of a
checkpointed layer's backward pass (ops/remat.py): the same model under a
bare ``nn.remat``, the two gauges, the primitives of a gradient's jaxpr, and
the values a backward pass was handed because a tag named them."""

import collections

import jax
from jax._src.ad_checkpoint import saved_residuals

from turboprune_tpu.ops import remat
from turboprune_tpu.utils import tracing


def bare(monkeypatch):
    """From here on the models are handed no policy: every layer is the bare
    ``jax.checkpoint`` it was before any value was tagged."""
    monkeypatch.setattr(remat, "keeping", lambda names: None)


def gauges() -> list:
    """[``remat_saved_values``, ``remat_saved_mib``] as they stand."""
    return [tracing.gauges().get(name) for name in ("remat_saved_values", "remat_saved_mib")]


def primitives(fn, *args) -> collections.Counter:
    """How often each primitive stands in ``fn``'s jaxpr, inner jaxprs included."""
    counts: collections.Counter = collections.Counter()

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            if name == "dot_general" and "HIGHEST" in str(eqn.params["precision"]):
                counts["dot_general_highest"] += 1
            counts[name] += 1
            for inner in jax.core.jaxprs_in_params(eqn.params):
                walk(inner)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return counts


def residuals(fn, *args) -> list:
    """(shape, where from) of every value ``fn``'s backward pass is handed."""
    return [(aval.shape, why) for aval, why in saved_residuals(fn, *args)]


def kept_shapes(fn, *args) -> list:
    """The sorted shapes of the residuals that a tag handed over: an integer
    one is listed as ``named '<tag>'``, a float one as the output of the
    ``reduce_precision`` that ``jax.checkpoint`` puts on a value it saves (a
    layer's input is listed under the equation that made it)."""
    return sorted(s for s, why in residuals(fn, *args) if "named '" in why or "reduce_precision" in why)
