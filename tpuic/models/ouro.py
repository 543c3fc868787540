"""Looped decoder stack (Ouro, ByteDance: "Scaling Latent Reasoning via
Looped Language Models", arXiv:2510.25741) as a classifier backbone.

The language model's *stack* is the backbone: a patch embedding stands
where the token table stood and the ``Classifier`` head where the LM head
stood. A row of the batch is an image of ``(size / patch)**2`` tokens in
raster order; every width, the block's equations and the loop are the
published ones:

- RMSNorm everywhere, no bias anywhere in a block; sandwich norms, four a
  block: ``a = x + N2(Attn(N1(x)))``, ``x' = a + N4(MLP(N3(a)))``.
- Multi-head attention with rotary positions (rotate-half, position = raster
  index, the same in every pass) under a causal mask; float32 softmax.
- Gated SiLU MLP: ``W_down(silu(x W_gate) * x W_up)``.
- The loop: ``h(t) = N_f(Block_L ... Block_1(h(t-1)))`` for ``t = 1..passes``
  with ONE set of weights, the final norm closing every pass; its output is
  that pass's read-out and the next pass's input. One ``scan`` over the
  passes (``nn.scan`` with the parameters broadcast), so the loop adds no
  weights and a FLOP count from the jaxpr multiplies the body by its length.
- Per pass, the read-out is the last position (the only one that has seen
  every token under the causal mask) and an exit gate gives one logit from
  it; ``train/loss.py::exit_expected_loss`` turns the gates into the exit
  distribution and the objective.

Activations grow with layers x passes while weights do not, so the memory
mode is per-block rematerialisation (``ModelConfig.remat_policy='blocks'``):
each block under ``nn.remat``, the saved residuals being the block inputs
of every pass.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn
from flax import struct

from tpuic.models.layers import GatedMlp, RMSNorm, patch_tokens, rotate
from tpuic.models.layers import proj as _proj


@struct.dataclass
class LoopedFeatures:
    """What a looped backbone hands the ``Classifier``: the read-out of
    every pass and the exit gate's logit for it."""

    features: jnp.ndarray       # [passes, B, hidden] float32
    gate_logits: jnp.ndarray    # [passes, B] float32


def rotary_tables(positions: int, head_dim: int, theta: float):
    """``(cos, sin)`` [positions, head_dim] in float32, each half of the
    head given the same angles (the rotate-half layout)."""
    inv_freq = (1.0 / theta ** (np.arange(0, head_dim, 2, dtype=np.float32)
                                / np.float32(head_dim))).astype(np.float32)
    angles = np.arange(positions, dtype=np.float32)[:, None] * inv_freq[None]
    angles = np.concatenate([angles, angles], axis=-1)
    return np.cos(angles), np.sin(angles)


class CausalRotaryAttention(nn.Module):
    num_heads: int
    head_dim: int
    rope_theta: float = 1e6
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        b, n, d = x.shape
        width = self.num_heads * self.head_dim
        q, k, v = (_proj(width, name, self.dtype, self.param_dtype,
                         ("embed", "model"))(x) for name in ("q", "k", "v"))
        cos, sin = rotary_tables(n, self.head_dim, self.rope_theta)
        q, k = rotate(q, cos, sin), rotate(k, cos, sin)
        q, k, v = (t.reshape(b, n, self.num_heads, self.head_dim)
                   for t in (q, k, v))
        scale = 1.0 / np.sqrt(self.head_dim)

        @jax.named_scope("attention_core")
        def core(q, k, v):
            logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(
                jnp.float32) * scale
            causal = np.tril(np.ones((n, n), bool))
            logits = jnp.where(causal[None, None], logits,
                               jnp.finfo(jnp.float32).min)
            probs = nn.softmax(logits, axis=-1).astype(self.dtype)
            return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

        out = core(q, k, v).reshape(b, n, width)
        return _proj(d, "o", self.dtype, self.param_dtype,
                     ("model", "embed"))(out)


class LoopedBlock(nn.Module):
    """One published layer: sandwich norms around attention and the MLP."""

    num_heads: int
    head_dim: int
    mlp_width: int
    rope_theta: float = 1e6
    eps: float = 1e-6
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        def norm(name):
            return RMSNorm(self.eps, self.dtype, self.param_dtype, name=name)
        y = CausalRotaryAttention(self.num_heads, self.head_dim,
                                  self.rope_theta, self.dtype,
                                  self.param_dtype, name="attn")(norm("norm1")(x))
        x = x + norm("norm2")(y)
        y = GatedMlp(self.mlp_width, self.dtype, self.param_dtype,
                     name="mlp")(norm("norm3")(x))
        return x + norm("norm4")(y)


class LoopPass(nn.Module):
    """One pass of the loop: every block, then the final norm. The body of
    the scan: ``carry`` in, ``(carry, read-out)`` out."""

    depth: int
    num_heads: int
    head_dim: int
    mlp_width: int
    rope_theta: float = 1e6
    eps: float = 1e-6
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    remat_blocks: bool = False

    @nn.compact
    def __call__(self, h: jnp.ndarray):
        block_cls = nn.remat(LoopedBlock) if self.remat_blocks else LoopedBlock
        for i in range(self.depth):
            h = block_cls(self.num_heads, self.head_dim, self.mlp_width,
                          self.rope_theta, self.eps, self.dtype,
                          self.param_dtype, name=f"block{i}")(h)
        h = RMSNorm(self.eps, self.dtype, self.param_dtype,
                    name="norm_final")(h)
        return h, h[:, -1].astype(jnp.float32)


class LoopedStack(nn.Module):
    """Returns :class:`LoopedFeatures`: the read-out and the exit gate's
    logit of each of ``passes`` passes through the same ``depth`` layers."""

    patch: int = 16
    hidden: int = 2048
    depth: int = 48
    num_heads: int = 16
    head_dim: int = 128
    mlp_width: int = 5632
    passes: int = 4
    rope_theta: float = 1e6
    eps: float = 1e-6
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    # Per-block remat (ModelConfig.remat_policy='blocks'): the residuals of
    # the backward pass are the block inputs of every pass
    # (depth x passes x [B, N, hidden]) and one block is recomputed at a
    # time. Without it every block application keeps its q/k/v, the
    # [B, H, N, N] probabilities and three mlp_width-wide tensors.
    remat_blocks: bool = False

    @nn.compact
    def __call__(self, x: jnp.ndarray, train: bool = False) -> LoopedFeatures:
        del train       # no dropout, no statistics: one forward for both
        h = patch_tokens(x, self.hidden, self.patch, self.dtype,
                         self.param_dtype)
        loop = nn.scan(LoopPass, variable_broadcast="params",
                       split_rngs={"params": False}, length=self.passes)
        _, features = loop(self.depth, self.num_heads, self.head_dim,
                           self.mlp_width, self.rope_theta, self.eps,
                           self.dtype, self.param_dtype, self.remat_blocks,
                           name="loop_pass")(h)
        with jax.named_scope("exit_gate"):
            gate = nn.Dense(1, dtype=jnp.float32,
                            param_dtype=self.param_dtype,
                            name="exit_gate")(features)[..., 0]
        return LoopedFeatures(features=features, gate_logits=gate)


def ouro_2_6b(depth: int = 48, **kw) -> LoopedStack:
    """Ouro-2.6B's published widths and passes; ``depth`` is how many of
    its 48 layers are held (a pipeline stage's share when cut)."""
    return LoopedStack(patch=16, hidden=2048, depth=depth, num_heads=16,
                       head_dim=128, mlp_width=5632, passes=4,
                       rope_theta=1e6, eps=1e-6, **kw)


def ouro_tiny(passes: int = 4, **kw) -> LoopedStack:
    """Test-scale looped stack (fast CI)."""
    return LoopedStack(patch=4, hidden=64, depth=2, num_heads=4, head_dim=16,
                       mlp_width=176, passes=passes, rope_theta=1e6,
                       eps=1e-6, **kw)
