"""Loss functions.

Reproduces torch ``nn.CrossEntropyLoss(weight=...)`` semantics exactly, since
the reference's loss is a weighted CE with a hard-coded 7-class imbalance
vector [3,3,10,1,4,4,5] (train.py:157-158): per-sample NLL scaled by the label
class weight, normalized by the *sum of the applied weights* (not the sample
count). The inception path adds ``loss1 + 0.4 * loss2`` over main and aux
logits (train.py:48-52).

A validity mask supports SPMD's static shapes: padded samples contribute zero
weight, so global loss over a padded final batch is exact.

A looped model (models/ouro.py) trains on ``exit_expected_loss``: the
expectation of the per-pass cross-entropies under the exit distribution its
gates give, less an entropy term (Ouro, arXiv:2510.25741).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def _row_nll_and_weights(logits, labels, class_weights, mask,
                         label_smoothing):
    """Per-row NLL [..., B] of logits [..., B, C] and the row weights [B]
    (label class weight x validity mask) the mean is taken under."""
    logits = logits.astype(jnp.float32)
    num_classes = logits.shape[-1]
    logp = jax.nn.log_softmax(logits, axis=-1)
    # one_hot (iota comparison) rather than eye()[labels]: a gather indexed by
    # the batch-sharded label array would force sharding-unfriendly lowering;
    # the comparison form stays elementwise and fuses.
    onehot = jax.nn.one_hot(labels, num_classes, dtype=jnp.float32)
    if label_smoothing > 0.0:
        onehot = onehot * (1.0 - label_smoothing) + label_smoothing / num_classes
    nll = -jnp.sum(onehot * logp, axis=-1)  # [B]
    if class_weights is not None:
        cw = jnp.asarray(class_weights, jnp.float32)
        w = jnp.sum(jax.nn.one_hot(labels, num_classes, dtype=jnp.float32)
                    * cw[None, :], axis=-1)
    else:
        w = jnp.ones(labels.shape, jnp.float32)
    if mask is not None:
        w = w * mask.astype(jnp.float32)
    return nll, w


def weighted_cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray,
                           class_weights: Optional[jnp.ndarray] = None,
                           mask: Optional[jnp.ndarray] = None,
                           label_smoothing: float = 0.0) -> jnp.ndarray:
    """Mean weighted CE over valid samples; torch-compatible normalization.

    logits [B, C] (any float dtype; upcast to f32), labels [B] int,
    class_weights [C] or None, mask [B] (1=valid) or None.
    """
    nll, w = _row_nll_and_weights(logits, labels, class_weights, mask,
                                  label_smoothing)
    # torch weighted-CE normalizer: sum of applied weights.
    return jnp.sum(w * nll) / jnp.maximum(jnp.sum(w), 1e-12)


def exit_distribution(gate_logits: jnp.ndarray) -> jnp.ndarray:
    """``log p`` [T, B] of the exit distribution the gates [T, B] give:
    ``p_1 = l_1``, ``p_t = l_t prod_{j<t} (1 - l_j)``, and the last pass
    takes what is left, ``p_T = prod_{j<T} (1 - l_j)`` (its own gate plays
    no part); ``l = sigmoid(gate)``. In logs throughout: a saturated gate
    gives a large finite log and ``p log p`` an exact 0, never a nan."""
    g = gate_logits.astype(jnp.float32)
    if g.shape[0] == 1:
        return jnp.zeros_like(g)
    # log prod_{j<=t} (1 - l_j) for t = 1..T-1, and the same for j < t
    stay = jnp.cumsum(jax.nn.log_sigmoid(-g[:-1]), axis=0)
    before = jnp.concatenate([jnp.zeros_like(g[:1]), stay[:-1]], axis=0)
    return jnp.concatenate([before + jax.nn.log_sigmoid(g[:-1]), stay[-1:]],
                           axis=0)


def exit_expected_loss(outputs, labels, *, class_weights=None, mask=None,
                       label_smoothing: float = 0.0,
                       entropy_weight: float = 0.05):
    """``(loss, stats)`` of a looped model's train-mode ``ExitOutputs``:
    the weighted row mean of ``sum_t p_t CE(z_t, y) - beta H(p)`` with
    ``H(p) = -sum_t p_t log p_t``. Class weights, label smoothing and the
    row mask act on every pass's CE as ``weighted_cross_entropy`` applies
    them. ``stats`` are the step's counters: each pass's CE, the batch mean
    of every ``p_t``, of the expected exit pass and of the entropy."""
    nll, w = _row_nll_and_weights(outputs.logits, labels, class_weights,
                                  mask, label_smoothing)        # [T, B], [B]
    log_p = exit_distribution(outputs.gate_logits)
    p = jnp.exp(log_p)
    entropy = -jnp.sum(p * log_p, axis=0)                       # [B]
    den = jnp.maximum(jnp.sum(w), 1e-12)

    def mean(rows):
        return jnp.sum(w * rows, axis=-1) / den
    loss = mean(jnp.sum(p * nll, axis=0) - entropy_weight * entropy)
    passes = jnp.arange(1, p.shape[0] + 1, dtype=jnp.float32)
    stats = {"exit_expected_pass": mean(jnp.sum(p * passes[:, None], axis=0)),
             "exit_entropy": mean(entropy)}
    pass_loss, pass_p = mean(nll), mean(p)
    for t in range(p.shape[0]):
        stats[f"loss_pass{t + 1}"] = pass_loss[t]
        stats[f"exit_p{t + 1}"] = pass_p[t]
    return loss, stats


LOSS_IMPLS = ("reference", "fused")


def classification_loss(outputs, labels, *, class_weights=None, mask=None,
                        aux_weight: float = 0.4,
                        label_smoothing: float = 0.0,
                        impl: str = "reference", mesh=None) -> jnp.ndarray:
    """Main loss, plus the inception aux term when outputs is a tuple.

    Reference train.py:48-56: ``loss = loss_fn(out1,l) + 0.4*loss_fn(out2,l)``
    in train mode, plain CE otherwise. ``impl='fused'`` routes through the
    Pallas kernel (tpuic/kernels/cross_entropy.py), same numerics; pass
    ``mesh`` so the kernel stays batch-parallel under a sharded jit.
    """
    if impl not in LOSS_IMPLS:
        raise ValueError(f"unknown loss impl '{impl}'; available: {LOSS_IMPLS}")
    if hasattr(outputs, "gate_logits"):
        raise TypeError("a looped model's ExitOutputs go through "
                        "exit_expected_loss, which also returns the step's "
                        "exit counters")
    if impl == "fused":
        from tpuic.kernels import fused_weighted_cross_entropy

        def ce(logits):
            return fused_weighted_cross_entropy(logits, labels, class_weights,
                                                mask, label_smoothing, 128,
                                                None, mesh)
    else:
        def ce(logits):
            return weighted_cross_entropy(logits, labels, class_weights, mask,
                                          label_smoothing)

    if isinstance(outputs, tuple):
        logits, aux_logits = outputs
        return ce(logits) + aux_weight * ce(aux_logits)
    return ce(outputs)
