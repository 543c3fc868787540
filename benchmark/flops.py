"""Required FLOPs of a forward pass, from its shapes.

The forward is traced with ``jax.make_jaxpr`` on abstract inputs and every
``dot_general`` and ``conv_general_dilated`` equation is counted at
2 x multiply-accumulates; nothing else is counted (normalisation, softmax
and element-wise work are a rounding error beside the contractions, and the
published counts leave them out too). This is what the algorithm requires:
recomputation added by ``jax.checkpoint`` or by the compiler is never in
it, unlike XLA's ``cost_analysis``. There is no table of models, so a new
configuration needs no edit here.
"""

from __future__ import annotations

import math
from typing import Callable


def _eqn_macs(eqn) -> int:
    name = eqn.primitive.name
    if name == "dot_general":
        (lhs_contract, _), _ = eqn.params["dimension_numbers"]
        lhs = eqn.invars[0].aval.shape
        out = eqn.outvars[0].aval.shape
        return math.prod(out) * math.prod(lhs[i] for i in lhs_contract)
    if name == "conv_general_dilated":
        rhs = eqn.invars[1].aval.shape
        out = eqn.outvars[0].aval.shape
        spec = eqn.params["dimension_numbers"].rhs_spec
        # rhs_spec = (out-feature dim, in-feature dim, spatial dims...); the
        # in-feature extent is already per feature group.
        taps = math.prod(rhs[d] for d in spec[2:]) * rhs[spec[1]]
        return math.prod(out) * taps
    return 0


def _sub_jaxprs(eqn):
    """(jaxpr, repeat) for every jaxpr nested in ``eqn``'s parameters."""
    repeat = int(eqn.params.get("length", 1)) if \
        eqn.primitive.name == "scan" else 1
    for v in eqn.params.values():
        for item in (v if isinstance(v, (tuple, list)) else (v,)):
            inner = getattr(item, "jaxpr", item)
            if hasattr(inner, "eqns"):
                yield inner, repeat


def jaxpr_macs(jaxpr) -> int:
    """Multiply-accumulates of every contraction in ``jaxpr``, nested calls
    included; a ``scan`` body counts once per iteration. ``cond`` counts
    every branch and ``while`` one trip: neither occurs in a forward pass
    of the models here, and an over- or under-count there would show in the
    pinned published counts."""
    total = 0
    for eqn in jaxpr.eqns:
        total += _eqn_macs(eqn)
        for inner, repeat in _sub_jaxprs(eqn):
            total += repeat * jaxpr_macs(inner)
    return total


def forward_macs(forward: Callable, *abstract_args) -> int:
    """MACs of ``forward(*abstract_args)`` (``jax.ShapeDtypeStruct`` trees)."""
    import jax
    return jaxpr_macs(jax.make_jaxpr(forward)(*abstract_args).jaxpr)


def model_forward_macs_per_image(model, image_size: int,
                                 channels: int = 3) -> float:
    """MACs per image of ``model.apply(variables, x, train=False)`` for a
    flax module as the program builds it, at batch 1 (the count is linear
    in the batch)."""
    import jax
    import jax.numpy as jnp
    x = jax.ShapeDtypeStruct((1, image_size, image_size, channels),
                             jnp.float32)
    variables = jax.eval_shape(
        lambda: model.init(jax.random.key(0),
                           jnp.zeros(x.shape, x.dtype), train=False))
    return float(forward_macs(
        lambda v, im: model.apply(v, im, train=False), variables, x))


def train_step_flops(macs_per_image: float, global_batch: int) -> float:
    """FLOPs one optimizer step requires: forward 2 x MACs, backward twice
    the forward (gradients with respect to activations and to weights), so
    3 x forward; recomputation is not required work and is not counted."""
    return 3.0 * 2.0 * macs_per_image * global_batch
