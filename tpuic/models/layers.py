"""Shared model building blocks.

The decoder stacks that serve as backbones (models/ouro.py,
models/kanana.py, models/mellum.py) share the bias-free projection,
``RMSNorm``, the gated SiLU MLP and the patch tokeniser below; the looped
and the banded stacks share the one-pass rotary, ``rotate``.

The MLP classifier head reproduces the reference's
``in_features -> 128 -> ReLU -> 64 -> ReLU -> 32 -> ReLU -> num_classes`` head
(nn/classifier.py:26-34). BatchNorm notes:

- The reference converts every BN layer to SyncBatchNorm over the world group
  (train.py:124), so training statistics are global-batch statistics. In this
  framework the train step is jitted over a mesh with the batch sharded on the
  ``data`` axis, so a plain ``nn.BatchNorm`` reduction over the batch dim *is*
  a global-batch reduction — GSPMD inserts the cross-replica all-reduce.
  SyncBN is the default semantics here, not an opt-in wrapper.
- Momentum/eps defaults follow torch BN (momentum 0.1 torch-style == 0.9 flax
  EMA style; eps 1e-5), which the reference inherits untouched.
"""

from __future__ import annotations

from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn


class MLPHead(nn.Module):
    """Reference nn/classifier.py:26-34 head: widths (128, 64, 32) + ReLU."""

    num_classes: int
    widths: Sequence[int] = (128, 64, 32)
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        for i, w in enumerate(self.widths):
            x = nn.Dense(w, dtype=self.dtype, param_dtype=self.param_dtype,
                         name=f"fc{i}")(x)
            x = nn.relu(x)
        x = nn.Dense(self.num_classes, dtype=jnp.float32,
                     param_dtype=self.param_dtype, name="out")(x)
        return x.astype(jnp.float32)


def batch_norm(train: bool, *, momentum: float = 0.9, eps: float = 1e-5,
               dtype: Any = jnp.float32, param_dtype: Any = jnp.float32,
               f32_stats: bool = True,
               name: str | None = None) -> nn.BatchNorm:
    """BatchNorm with torch-default hyperparameters (see module docstring).

    Under the sharded-jit train step this computes *global* batch statistics —
    the reference's SyncBatchNorm (train.py:124) semantics.

    ``f32_stats=False`` accumulates batch mean/var in the compute dtype
    (bf16) instead of float32 — a bandwidth experiment: the BN stat
    fusions are the top HBM readers in the ResNet-50 step profile
    (ModelConfig.bn_f32_stats).
    """
    return nn.BatchNorm(use_running_average=not train, momentum=momentum,
                        epsilon=eps, dtype=dtype, param_dtype=param_dtype,
                        force_float32_reductions=f32_stats,
                        name=name)


Conv = nn.Conv


def conv3x3(features: int, strides: int = 1, *, dtype=jnp.float32,
            param_dtype=jnp.float32, name: str | None = None) -> nn.Conv:
    return nn.Conv(features, (3, 3), strides=(strides, strides), padding=1,
                   use_bias=False, dtype=dtype, param_dtype=param_dtype,
                   name=name)


def conv1x1(features: int, strides: int = 1, *, dtype=jnp.float32,
            param_dtype=jnp.float32, name: str | None = None) -> nn.Conv:
    return nn.Conv(features, (1, 1), strides=(strides, strides),
                   use_bias=False, dtype=dtype, param_dtype=param_dtype,
                   name=name)


def proj(features: int, name: str, dtype, param_dtype, logical):
    """A projection without bias, as every one in a decoder block."""
    return nn.Dense(
        features, use_bias=False, dtype=dtype, param_dtype=param_dtype,
        name=name, kernel_init=nn.with_logical_partitioning(
            nn.initializers.xavier_uniform(), logical))


class RMSNorm(nn.Module):
    """``x / sqrt(mean(x^2) + eps) * g``, statistics in float32."""

    eps: float = 1e-6
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           self.param_dtype)
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(
            jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + self.eps)
        return (y * scale.astype(jnp.float32)).astype(self.dtype)


class GatedMlp(nn.Module):
    """``W_down(silu(x W_gate) * x W_up)``, no bias."""

    width: int
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        d = x.shape[-1]
        gate = proj(self.width, "gate", self.dtype, self.param_dtype,
                    ("embed", "model"))(x)
        up = proj(self.width, "up", self.dtype, self.param_dtype,
                  ("embed", "model"))(x)
        return proj(d, "down", self.dtype, self.param_dtype,
                    ("model", "embed"))(nn.silu(gate) * up)


def rotate(x: jnp.ndarray, cos, sin) -> jnp.ndarray:
    """``x`` [B, N, H*D], as a projection writes it, turned by position:
    ``x cos + R(x) sin`` with ``R(x) = [-x2, x1]`` within each head (the
    rotate-half layout), from the tables ``cos``, ``sin`` [N, D] (numpy
    float32, one pair for every head). float32 arithmetic, ``x``'s dtype
    in and out.

    One pass each way under a hand-written VJP. With ``S = sin`` signed
    ``[-1, +1]`` by half, ``R(x) sin = swap(x) S`` where ``swap`` trades
    the halves; and since ``R^T = -R``, the gradient ``g cos + R^T(g sin)``
    is ``g cos + swap(g) swap(S)``: the same pass over ``g`` with another
    table, so autodiff builds no slices, pads or concatenates. The swap
    acts on ``x``'s own dtype (a permutation: exact), and ``x`` is read as
    ``[B*N/8, H, 8, D]``, the bytes of ``[B, N, H*D]`` under the chip's
    (8, 128) tiles, so the pass needs no relayout and the [N, D] tables
    broadcast over heads without being tiled. Jitted, so that an eager
    ``model.init`` dispatches one program a shape."""
    d = cos.shape[-1]
    signed = sin * np.where(np.arange(d) < d // 2, -1, 1).astype(np.float32)
    with jax.named_scope("rotary"):
        return _rotate(x, cos, signed, np.roll(signed, d // 2, axis=-1))


def _turn(x, cos, signed):
    """``x cos + swap(x) signed`` per head, ``x`` [B, N, H*D]."""
    b, n, width = x.shape
    d = cos.shape[-1]
    rows = b * n
    group = 8 if rows % 8 == 0 else 1      # the rows of a tile
    v = x.reshape(rows // group, group, width // d, d).transpose(0, 2, 1, 3)
    swapped = jnp.roll(v, d // 2, axis=-1)

    def table(t):
        return jnp.broadcast_to(t, (b, n, d)).reshape(
            rows // group, 1, group, d)
    y = (v.astype(jnp.float32) * table(cos) +
         swapped.astype(jnp.float32) * table(signed))
    return y.astype(x.dtype).transpose(0, 2, 1, 3).reshape(b, n, width)


@jax.custom_vjp
def _rotate_once(x, cos, signed, swapped_signed):
    return _turn(x, cos, signed)


def _rotate_fwd(x, cos, signed, swapped_signed):
    return _turn(x, cos, signed), (cos, swapped_signed)


def _rotate_bwd(tables, g):
    cos, swapped_signed = tables
    return _turn(g, cos, swapped_signed), None, None, None


_rotate_once.defvjp(_rotate_fwd, _rotate_bwd)
_rotate = jax.jit(_rotate_once)


def patch_tokens(images: jnp.ndarray, hidden: int, patch: int, dtype,
                 param_dtype) -> jnp.ndarray:
    """Tokens [B, (size / patch)**2, hidden] in raster order from a
    ``patch`` x ``patch`` / ``patch`` convolution with bias, ``patch_embed``
    of the module whose ``__call__`` this is called from: what stands
    where a decoder's token table stood."""
    with jax.named_scope("tokenize"):
        x = nn.Conv(hidden, (patch, patch), strides=(patch, patch),
                    dtype=dtype, param_dtype=param_dtype,
                    name="patch_embed")(images.astype(dtype))
        return x.reshape(x.shape[0], -1, hidden)
