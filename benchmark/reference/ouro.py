"""Looped decoder stack (Ouro: "Scaling Latent Reasoning via Looped Language
Models", ByteDance, arXiv:2510.25741) as an image classifier's backbone, in
plain float32 ``jax.numpy``.

Reads the parameter tree of the program's flax model: ``backbone.
{patch_embed, loop_pass.{block{i}.{norm1, attn.{q, k, v, o}, norm2, norm3,
mlp.{gate, up, down}, norm4}, norm_final}, exit_gate}`` and ``head``. Depth
and patch size are read off the tree; what the tree cannot say is the
configuration's: ``num_attention_heads``, ``head_dim``, ``rope_theta``,
``rms_norm_eps``, ``total_ut_steps`` (the passes of the loop) and
``exit_entropy_weight``.

The mathematics, for tokens ``x0 = Conv(image) + b`` in raster order:

- ``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g``;
- block: ``a = x + N2(Attn(N1(x)))``, ``x' = a + N4(MLP(N3(a)))``;
- ``Attn``: ``q, k, v = x W_q, x W_k, x W_v`` split into heads; rotary
  embedding on ``q`` and ``k`` (rotate-half, position = raster index);
  ``softmax(q k^T / sqrt(head_dim) + causal mask) v``; ``W_o``. No bias;
- ``MLP(x) = W_down(silu(x W_gate) * x W_up)``, no bias;
- loop: ``h(0) = x0``, ``h(t) = N_f(Block_L(... Block_1(h(t-1))))`` for
  ``t = 1..T`` with one set of weights, the final norm closing each pass;
- per pass, at the last position ``r(t) = h(t)[:, -1]``: logits ``z(t) =
  Head(r(t))`` and gate ``l_t = sigmoid(w_g . r(t) + b_g)``;
- exit distribution: ``p_1 = l_1``, ``p_t = l_t prod_{j<t} (1 - l_j)``,
  ``p_T = prod_{j<T} (1 - l_j)``;
- train loss: mean over the rows of ``sum_t p_t CE(z(t), y) - beta H(p)``,
  ``H(p) = -sum_t p_t log p_t``;
- eval: all ``T`` passes (the published early-exit threshold of 1 exits
  nowhere early), logits ``z(T)``.

Departures from the published model, all the program's own: the patch
embedding where the token table stood, the reference system's MLP head
(``resnet.mlp_head``) where the LM head stood, one gate value per image
(the paper's is per token) read at the last position, which under the
causal mask is the only one that has seen every token.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.resnet import EVAL, Mode, mlp_head

HIGHEST = jax.lax.Precision.HIGHEST


def _matmul(x, p, mode):
    return jnp.dot(mode.rounded(x), mode.rounded(p["kernel"].astype(
        jnp.float32)), precision=HIGHEST)


def _rms_norm(x, p, eps: float):
    return (x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                              + eps) * p["scale"].astype(jnp.float32))


def _rotary(x, theta: float):
    """``x`` [B, N, H, Dh]: pairs ``(x[i], x[i + Dh/2])`` turned by the
    angle ``position * theta^(-2i/Dh)``."""
    n, dh = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / theta ** (np.arange(0, dh, 2, dtype=np.float32)
                               / np.float32(dh))
    angles = (np.arange(n, dtype=np.float32)[:, None]
              * inv_freq[None].astype(np.float32))          # [N, Dh/2]
    cos = np.cos(angles)[None, :, None, :]
    sin = np.sin(angles)[None, :, None, :]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def _attention(x, p, heads: int, head_dim: int, theta: float, mode):
    b, n, _ = x.shape
    q, k, v = (_matmul(x, p[name], mode).reshape(b, n, heads, head_dim)
               for name in ("q", "k", "v"))
    q, k = _rotary(q, theta), _rotary(k, theta)
    logits = jnp.einsum("bqhd,bkhd->bhqk", mode.rounded(q), mode.rounded(k),
                        precision=HIGHEST) / np.sqrt(head_dim)
    future = np.triu(np.ones((n, n), bool), k=1)
    logits = jnp.where(future[None, None], -jnp.inf, logits)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", mode.rounded(probs), mode.rounded(v),
                     precision=HIGHEST)
    return _matmul(out.reshape(b, n, heads * head_dim), p["o"], mode)


def _mlp(x, p, mode):
    return _matmul(jax.nn.silu(_matmul(x, p["gate"], mode))
                   * _matmul(x, p["up"], mode), p["down"], mode)


def _block(x, blk, heads: int, head_dim: int, theta: float, eps: float, mode):
    y = _attention(_rms_norm(x, blk["norm1"], eps), blk["attn"], heads,
                   head_dim, theta, mode)
    x = x + _rms_norm(y, blk["norm2"], eps)
    y = _mlp(_rms_norm(x, blk["norm3"], eps), blk["mlp"], mode)
    return x + _rms_norm(y, blk["norm4"], eps)


def _passes(variables, images, config, mode):
    """``(logits [T, B, classes], gate logits [T, B])``, pass by pass."""
    p = variables["params"]["backbone"]
    heads, head_dim = (int(config["num_attention_heads"]),
                       int(config["head_dim"]))
    theta, eps = float(config["rope_theta"]), float(config["rms_norm_eps"])
    kernel = p["patch_embed"]["kernel"].astype(jnp.float32)
    patch = kernel.shape[0]
    x = jax.lax.conv_general_dilated(
        mode.rounded(jnp.asarray(images, jnp.float32)), mode.rounded(kernel),
        (patch, patch), "VALID", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=HIGHEST) + p["patch_embed"]["bias"].astype(jnp.float32)
    h = x.reshape(x.shape[0], -1, kernel.shape[-1])
    block = jax.checkpoint(_block, static_argnums=(2, 3, 4, 5, 6)) \
        if mode.remat else _block
    stack = p["loop_pass"]
    depth = sum(1 for name in stack if name.startswith("block"))
    gate = p["exit_gate"]
    logits, gates = [], []
    for _ in range(int(config["total_ut_steps"])):
        for i in range(depth):
            h = block(h, stack[f"block{i}"], heads, head_dim, theta, eps,
                      mode)
        h = _rms_norm(h, stack["norm_final"], eps)
        r = h[:, -1]
        logits.append(mlp_head(r, variables["params"]["head"], mode))
        gates.append((_matmul(r, gate, mode)
                      + gate["bias"].astype(jnp.float32))[:, 0])
    return jnp.stack(logits), jnp.stack(gates)


def exit_probabilities(gates):
    """``p`` [T, B] from the gates' logits [T, B]."""
    lam = jax.nn.sigmoid(gates)
    ps, left = [], jnp.ones_like(lam[0])
    for t in range(lam.shape[0] - 1):
        ps.append(lam[t] * left)
        left = left * (1.0 - lam[t])
    return jnp.stack(ps + [left])


def forward(variables, images, config, mode=None):
    """Logits [B, classes] of the last pass for normalised float32 images
    [B, H, W, 3]; ``config`` is the configuration file's content; ``mode``
    (eval where absent) lets the control round its inputs."""
    return _passes(variables, images, config, mode or EVAL)[0][-1]


def train_loss(variables, images, labels, config, mode=None):
    """The loss a training step reports for this batch with these
    (pre-step) variables: the expectation of the per-pass cross-entropies
    under the exit distribution, less ``beta`` times its entropy; float32,
    each block recomputed in the backward pass so that a full-size batch
    fits."""
    mode = mode or Mode(train=True, remat=True)
    logits, gates = _passes(variables, images, config, mode)
    p = exit_probabilities(gates)                               # [T, B]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(
        logp, jnp.broadcast_to(labels.astype(jnp.int32)[None, :, None],
                               logp.shape[:2] + (1,)), axis=-1)[..., 0]
    entropy = -jnp.sum(p * jnp.log(jnp.maximum(p, 1e-30)), axis=0)
    return jnp.mean(jnp.sum(p * nll, axis=0)
                    - float(config["exit_entropy_weight"]) * entropy)
