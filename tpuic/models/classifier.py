"""Classifier = backbone + MLP head.

Re-design of reference nn/classifier.py:7-37: ``Classifier(name, num_classes)``
selects a backbone by string and replaces its final FC with the
in->128->64->32->n MLP head (nn/classifier.py:26-34). Differences by design:

- The reference mutates ``encoder.fc`` in place; here backbone and head are
  separate submodules (``backbone``, ``head``) — the converter maps torch's
  ``encoder.fc.*`` onto ``head`` when importing checkpoints.
- The reference's efficientnet branch is broken (sets ``fc`` on a model whose
  attr is ``_fc``, nn/classifier.py:17-18+27 — AttributeError); here the
  intended behavior is implemented.
- Inception-v3's aux head (nn/classifier.py:22-23) surfaces as a second logits
  output in train mode, consumed by the 0.4-weighted aux loss (train.py:48-52).
- A looped backbone (models/ouro.py) hands over a read-out and an exit-gate
  logit per pass: the ONE head is applied to each, and train mode returns
  :class:`ExitOutputs` for ``train/loss.py::exit_expected_loss``. Eval,
  predict and serve take the last pass's logits (no early exit).
"""

from __future__ import annotations

from typing import Any, Sequence

import jax.numpy as jnp
from flax import linen as nn
from flax import struct

from tpuic.models.layers import MLPHead
from tpuic.models.ouro import LoopedFeatures


@struct.dataclass
class ExitOutputs:
    """Train-mode output of a looped model: every pass's logits and exit
    gate. Not a tuple, so the aux-head path never mistakes it for one."""

    logits: jnp.ndarray         # [passes, B, classes]
    gate_logits: jnp.ndarray    # [passes, B]


class Classifier(nn.Module):
    backbone: nn.Module
    num_classes: int
    head_widths: Sequence[int] = (128, 64, 32)
    has_aux: bool = False
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, images: jnp.ndarray, train: bool = False):
        """images: [B, H, W, 3] float32 (normalized). Returns logits [B, C];
        inception in train mode returns (logits, aux_logits), a looped
        model ExitOutputs."""
        out = self.backbone(images, train=train)
        head = MLPHead(self.num_classes, self.head_widths, dtype=self.dtype,
                       param_dtype=self.param_dtype, name="head")
        if isinstance(out, LoopedFeatures):
            if not train:
                return head(out.features[-1])
            return ExitOutputs(logits=head(out.features),
                               gate_logits=out.gate_logits)
        aux = None
        if isinstance(out, tuple):
            out, aux = out
        logits = head(out)
        if self.has_aux and train:
            return logits, aux
        return logits
