"""Mellum2-12B-A2.5B's decoder stack (JetBrains, ``config.json`` of
``Mellum2-12B-A2.5B-Instruct``, ``model_type`` ``mellum``) as a classifier
backbone: sliding-window layers among full ones, a few key-value heads
under many query heads, a rotary table per kind of layer, and a dropless
top-8 softmax router over the experts this chip holds.

As with the other decoder stacks (models/ouro.py, models/kanana.py) the
language model's *stack* is the backbone: the patch embedding of the
image, standardised channel by channel over its own pixels
(``kanana.standardized``, and why), stands where the token table stood, a
row of the batch is an image of ``(size / patch)**2`` tokens in raster
order, the read-out is the mean over positions of the closing norm's
output, and the ``Classifier`` head stands where the LM head stood. Every
width and the layer's equations are the published ones; no bias in any
projection, no norm on queries or keys:

- block ``l``: ``h += Attn_l(N(h))``, ``h += MoE(N(h))``, RMSNorm; one
  closing RMSNorm after the last layer;
- ``Attn_l`` (:class:`GroupedBandAttention`): ``num_heads`` query heads
  over ``kv_heads`` key-value heads, query head ``j`` reading key-value
  head ``j // (num_heads / kv_heads)``; rotate-half rotary on queries and
  keys from the table of the layer's kind, position = raster index; causal
  softmax over ``q k^T / sqrt(head_dim)``, and in a *sliding* layer only
  over the ``window`` keys up to the query itself. ``layer_types[l]`` says
  which kind layer ``l`` is: ``sliding_attention`` has the window and the
  plain table (``theta^(-2i/d)``), ``full_attention`` has no window and
  the YaRN table (:func:`yarn_inv_freq`: the slow frequencies divided by
  ``factor``, a linear ramp between; cosines and sines times
  ``attention_factor``, which scales every score by its square). Both
  kinds go through the one kernel call (kernels/flash_attention.py,
  ``causal=True``, ``window``): key blocks above the diagonal or beyond
  the window are not visited, and ``[H, N, N]`` is never built;
- ``MoE`` (:class:`SoftmaxExpertLayer`): ``p = softmax(x W_r)`` in float32
  over all ``num_experts``; the ``top_k`` largest are chosen; weights ``p_i
  / sum over the chosen of p`` (``norm_topk``); ``y = sum over the chosen
  experts held here of w_i E_i(x)``, each expert a gated SiLU MLP. No
  selection bias, no shared expert, no scaling factor. The sum is
  ``kanana.routed_sum`` (grouped products over the pairs on held experts,
  nothing dropped), and the layer sows the counters every routed layer
  sows.

A chip may hold a share of the model: ``depth`` of the published layers (a
pipeline stage's; whole periods of ``layer_types``) and ``held`` of every
layer's experts (an expert-parallel rank's); the router, the normaliser
and the choice stay over all experts. On one chip the layer runs without
its exchange: what the absent experts would add is left out.

The stack sows, once a step: ``attention_key_blocks_visited`` and
``attention_key_blocks_square`` (the forward grids' tiles of a head of one
image, summed over the layers: ``flash_attention.blocks_visited``), and
``attention_window_layers`` / ``attention_full_layers``.

Activations are what grows with the batch: the memory mode is per-block
rematerialisation (``ModelConfig.remat_policy='blocks'``).
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from tpuic.models.kanana import (HIGHEST, held_experts, routed_sum,
                                 sow_routing_counters, standardized)
from tpuic.models.layers import RMSNorm, patch_tokens, proj, rotate
from tpuic.models.ouro import rotary_tables

SLIDING, FULL = "sliding_attention", "full_attention"
# (factor, original_max_position_embeddings, beta_fast, beta_slow,
# attention_factor) of the published ``rope_parameters.full_attention``
YARN = (16.0, 8192, 32.0, 1.0, 1.2772588722239782)


def yarn_inv_freq(head_dim: int, theta: float, factor: float,
                  original_max: int, beta_fast: float,
                  beta_slow: float) -> np.ndarray:
    """YaRN's frequencies [head_dim / 2], float32: ``theta^(-2i/d)`` where
    a dimension turns more than ``beta_fast`` times over the original
    context, that over ``factor`` where it turns less than ``beta_slow``
    times, and a linear ramp over the dimensions between."""
    def dimension_of(turns):
        return (head_dim * np.log(original_max / (turns * 2 * np.pi))
                / (2 * np.log(theta)))
    low = max(np.floor(dimension_of(beta_fast)), 0)
    high = min(np.ceil(dimension_of(beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    plain = 1.0 / theta ** (np.arange(0, head_dim, 2, dtype=np.float64)
                            / head_dim)
    ramp = np.clip((np.arange(head_dim // 2) - low) / (high - low), 0, 1)
    return (plain * (1 - ramp) + plain / factor * ramp).astype(np.float32)


def layer_rotary_tables(positions: int, head_dim: int, theta: float,
                        yarn: Optional[Tuple]):
    """``(cos, sin)`` [positions, head_dim] float32 in the rotate-half
    layout: the plain table (the looped stack's), or (``yarn``:
    :data:`YARN`'s fields) YaRN's with both times its
    ``attention_factor``."""
    if yarn is None:
        return rotary_tables(positions, head_dim, theta)
    inv_freq, times = yarn_inv_freq(head_dim, theta, *yarn[:4]), yarn[4]
    angles = np.arange(positions, dtype=np.float32)[:, None] * inv_freq[None]
    angles = np.concatenate([angles, angles], axis=-1)
    return (np.float32(times) * np.cos(angles),
            np.float32(times) * np.sin(angles))


class GroupedBandAttention(nn.Module):
    num_heads: int
    kv_heads: int
    head_dim: int
    window: Optional[int]       # None: a full layer
    rope_theta: float
    yarn: Optional[Tuple]       # None: the plain table
    blocks: Optional[Tuple[int, int]] = None    # the kernel's, else by length
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    mesh: Any = None

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        b, n, d = x.shape

        def heads(name, count):
            return proj(count * self.head_dim, name, self.dtype,
                        self.param_dtype, ("embed", "model"))(x)
        q, k, v = (heads("q", self.num_heads), heads("k", self.kv_heads),
                   heads("v", self.kv_heads))
        cos, sin = layer_rotary_tables(n, self.head_dim, self.rope_theta,
                                       self.yarn)
        q, k = rotate(q, cos, sin), rotate(k, cos, sin)
        q, k, v = (t.reshape(b, n, -1, self.head_dim) for t in (q, k, v))
        # Pallas is imported when a model is traced, not with the registry
        from tpuic.kernels.flash_attention import flash_attention
        block_q, block_k = self.blocks or (None, None)
        with jax.named_scope("attention_core"):
            out = flash_attention(q, k, v, block_q, block_k, None, self.mesh,
                                  None, True, self.window)
        return proj(d, "o", self.dtype, self.param_dtype,
                    ("model", "embed"))(out.reshape(b, n, -1))


class SoftmaxExpertLayer(nn.Module):
    """The routed experts held here under the softmax router over all of
    them."""

    num_experts: int
    held: Tuple[int, int]       # (first, how many) of num_experts
    width: int
    top_k: int
    norm_topk: bool = True
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        b, n, d = x.shape
        first, held = self.held
        if not (0 <= first and 0 < held and first + held <= self.num_experts):
            raise ValueError(f"held experts {self.held} of "
                             f"{self.num_experts}")
        xf = x.reshape(b * n, d)
        with jax.named_scope("router"):
            router = self.param(
                "router", nn.with_logical_partitioning(
                    nn.initializers.xavier_uniform(), ("embed", "unsharded")),
                (d, self.num_experts), self.param_dtype)
            probs = jax.nn.softmax(jnp.dot(
                xf.astype(jnp.float32), router.astype(jnp.float32),
                precision=HIGHEST), axis=-1)
            weights, chosen = jax.lax.top_k(probs, self.top_k)
            if self.norm_topk:
                weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        gate_up, down = held_experts(self, held, d, self.width)
        with jax.named_scope("routed_experts"):
            y, sizes, computed, over = routed_sum(
                xf.astype(self.dtype), chosen, weights, gate_up, down, first,
                self.num_experts)
        sow_routing_counters(self, b * n * self.top_k, sizes, computed, over,
                             probs)
        return y.astype(self.dtype).reshape(b, n, d)


class BandMoeBlock(nn.Module):
    """One published layer; ``window`` None and ``yarn`` set make it a
    full layer."""

    num_heads: int
    kv_heads: int
    head_dim: int
    window: Optional[int]
    rope_theta: float
    yarn: Optional[Tuple]
    num_experts: int
    held: Tuple[int, int]
    expert_width: int
    top_k: int
    norm_topk: bool = True
    blocks: Optional[Tuple[int, int]] = None
    eps: float = 1e-6
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    mesh: Any = None

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        def norm(name):
            return RMSNorm(self.eps, self.dtype, self.param_dtype, name=name)
        with jax.named_scope("band_attention"):
            x = x + GroupedBandAttention(
                self.num_heads, self.kv_heads, self.head_dim, self.window,
                self.rope_theta, self.yarn, self.blocks, self.dtype,
                self.param_dtype, self.mesh, name="attn")(
                    norm("attn_norm")(x))
        return x + SoftmaxExpertLayer(
            self.num_experts, tuple(self.held), self.expert_width, self.top_k,
            self.norm_topk, self.dtype, self.param_dtype, name="moe")(
                norm("mlp_norm")(x))


class BandMoeStack(nn.Module):
    """Returns the read-out [B, hidden] float32: the mean over positions
    of the closing norm's output."""

    patch: int = 16
    hidden: int = 2304
    layer_types: Tuple[str, ...] = (SLIDING, SLIDING, SLIDING, FULL) * 7
    num_heads: int = 32
    kv_heads: int = 4
    head_dim: int = 128
    window: int = 1024
    rope_theta: float = 500000.0
    yarn: Tuple = YARN
    num_experts: int = 64
    held: Tuple[int, int] = (0, 64)
    expert_width: int = 896
    top_k: int = 8
    norm_topk: bool = True
    blocks: Optional[Tuple[int, int]] = None
    eps: float = 1e-6
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    mesh: Any = None
    # Per-block remat (ModelConfig.remat_policy='blocks'): the residuals of
    # the backward pass are the block inputs; one block is recomputed at a
    # time, its attention kernel, its routing and its grouped products
    # included.
    remat_blocks: bool = False

    @nn.compact
    def __call__(self, x: jnp.ndarray, train: bool = False) -> jnp.ndarray:
        del train       # no dropout, no statistics: one forward for both
        with jax.named_scope("standardize"):
            x = standardized(x)
        h = patch_tokens(x, self.hidden, self.patch, self.dtype,
                         self.param_dtype)
        block_cls = nn.remat(BandMoeBlock) if self.remat_blocks \
            else BandMoeBlock
        for i, kind in enumerate(self.layer_types):
            if kind not in (SLIDING, FULL):
                raise ValueError(f"layer {i} is of the unknown kind {kind!r}")
            sliding = kind == SLIDING
            h = block_cls(
                self.num_heads, self.kv_heads, self.head_dim,
                self.window if sliding else None, self.rope_theta,
                None if sliding else tuple(self.yarn), self.num_experts,
                tuple(self.held), self.expert_width, self.top_k,
                self.norm_topk, self.blocks, self.eps, self.dtype,
                self.param_dtype, self.mesh, name=f"layer{i}")(h)
        if not self.is_initializing():
            self._sow_attention_counters(h.shape[1])
        h = RMSNorm(self.eps, self.dtype, self.param_dtype,
                    name="norm_final")(h)
        return jnp.mean(h.astype(jnp.float32), axis=1)

    def _sow_attention_counters(self, tokens: int) -> None:
        from tpuic.kernels.flash_attention import blocks_visited
        block_q, block_k = self.blocks or (None, None)
        visited = square = 0
        for kind in self.layer_types:
            v, s = blocks_visited(tokens, block_q, block_k, True,
                                  self.window if kind == SLIDING else None)
            visited, square = visited + v, square + s
        sliding = sum(kind == SLIDING for kind in self.layer_types)
        for name, value in (
                ("attention_key_blocks_visited", visited),
                ("attention_key_blocks_square", square),
                ("attention_window_layers", sliding),
                ("attention_full_layers", len(self.layer_types) - sliding)):
            self.sow("counters", name, jnp.float32(value))


def mellum2_12b_a2_5b(depth: int = 28, held: Tuple[int, int] = (0, 64),
                      **kw) -> BandMoeStack:
    """Mellum2-12B-A2.5B's published widths, window, tables, router and
    counts; ``depth`` is how many of its 28 layers are held (a pipeline
    stage's share when cut: the first ``depth`` of ``layer_types``) and
    ``held`` this chip's experts of every layer."""
    return BandMoeStack(layer_types=BandMoeStack.layer_types[:depth],
                        held=tuple(held), **kw)


def mellum_tiny(depth: int = 4, held: Tuple[int, int] = (0, 8),
                **kw) -> BandMoeStack:
    """Test-scale stack (fast CI): 256 tokens at 128 px, a window of 64
    over kernel blocks of 64 (so that a band has tiles it skips), two
    key-value heads under four, top-3 of 16 experts with 8 held; a rotary
    base and a YaRN context small enough that 256 positions turn."""
    return BandMoeStack(
        patch=8, hidden=64, layer_types=BandMoeStack.layer_types[:depth],
        num_heads=4, kv_heads=2, head_dim=16, window=64, rope_theta=10000.0,
        yarn=(16.0, 64, 32.0, 1.0, YARN[4]), num_experts=16,
        held=tuple(held), expert_width=24, top_k=3, blocks=(64, 64), **kw)
