"""Kanana-2-30B-A3B's decoder stack (kakaocorp, ``config.json`` of
``kanana-2-30b-a3b-instruct-2601``, ``model_type`` ``deepseek_v3``) as an
image classifier's backbone, in plain float32 ``jax.numpy``.

Reads the parameter tree of the program's flax model: ``backbone.
{patch_embed, layer{i}.{attn_norm, attn.{q, kv_a, kv_norm, kv_b, o},
mlp_norm, mlp.{gate, up, down} | moe.{router, selection_bias,
experts_gate_up, experts_down, shared.{gate, up, down}}}, norm_final}`` and
``head``. Depth, patch size and how many experts are held are read off the
tree; every other size is the configuration's: ``num_attention_heads``,
``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``,
``kv_lora_rank``, ``rope_theta``, ``rms_norm_eps``, ``moe_intermediate_size``,
``num_experts_per_tok``, ``norm_topk_prob``, ``routed_scaling_factor``,
``n_shared_experts``, ``topk_method`` (``noaux_tc``: the selection bias is
added before the choice), ``experts_held_first`` (the first expert of this
chip's share; their number is the tree's) and ``rotary_on`` (``rope``).

The mathematics, for tokens ``h = Conv(S(image)) + b`` in raster order,
``T`` of them an image (all projections without bias):

- ``S(image) = (image - m) / sqrt(v + 1e-6)``, ``m`` and ``v`` the mean and
  the variance of each channel over the image's own pixels;
- ``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g``;
- block: ``h += Attn(N(h))``, ``h += F(N(h))``; ``F`` is the dense MLP in
  the layers whose tree holds ``mlp`` and the expert layer in those that
  hold ``moe``; a closing norm after the last block;
- ``Attn(x)``: ``q = x W_q`` as heads of ``nope + rope``, split ``q_nope |
  q_rope``; ``x W_kva`` split ``c [kv_lora_rank] | k_rope [rope]``; ``c =
  RMSNorm(c)``; ``c W_kvb`` as heads of ``nope + v``, split ``k_nope | v``;
  rotary on ``q_rope`` and on the one ``k_rope`` (interleaved pairs ``(2i,
  2i+1)``, angle ``position * theta^(-2i/rope)``), ``k_rope`` then the
  same for every head; ``softmax([q_nope | q_rope] [k_nope | k_rope]^T /
  sqrt(nope + rope) + causal mask) v``; ``W_o``;
- ``MLP(x) = W_down(silu(x W_gate) * x W_up)``: the dense layer's, every
  expert's, and the shared experts' as one of their summed width;
- expert layer: ``s = sigmoid(x W_r)``, one score for each of the
  ``n_routed_experts`` as published (the router's width); the choice is
  the ``num_experts_per_tok`` largest of ``s + b`` (``b`` the selection
  bias: it selects and does not weigh); ``w_i = routed_scaling_factor *
  s_i / (sum over the choice of s + 1e-20)``, zero for an expert not
  chosen; ``y = sum over the experts held here of w_i E_i(x) +
  Shared(x)``: a ``for`` over the held experts, each applied to all
  tokens. What the absent experts would add is left out;
- read-out: the mean over positions of the closing norm's output; logits
  from the reference system's MLP head; loss: the plain cross-entropy.

Departures from the published model, all the program's own: the
standardised image's patch embedding where the token table stood, the MLP head where the LM head
stood, the mean read-out, and this chip's share of the layers and of each
layer's experts.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.resnet import EVAL, Mode, cross_entropy, mlp_head

HIGHEST = jax.lax.Precision.HIGHEST


def _dot(x, w, mode):
    return jnp.dot(mode.rounded(x), mode.rounded(w.astype(jnp.float32)),
                   precision=HIGHEST)


def _rms_norm(x, p, eps: float):
    return (x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                              + eps) * p["scale"].astype(jnp.float32))


def _rotary(x, theta: float):
    """``x`` [B, N, H, D]: pairs ``(x[2i], x[2i+1])`` turned by the angle
    ``position * theta^(-2i/D)``."""
    n, d = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float32)
                               / np.float32(d))
    angles = (np.arange(n, dtype=np.float32)[:, None]
              * inv_freq[None].astype(np.float32))          # [N, D/2]
    cos = np.cos(angles)[None, :, None, :]
    sin = np.sin(angles)[None, :, None, :]
    pairs = x.reshape(x.shape[:-1] + (d // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def _attention(x, p, config, mode):
    b, n, _ = x.shape
    heads = int(config["num_attention_heads"])
    nope, rope = (int(config["qk_nope_head_dim"]),
                  int(config["qk_rope_head_dim"]))
    v_dim, rank = int(config["v_head_dim"]), int(config["kv_lora_rank"])
    theta, eps = float(config["rope_theta"]), float(config["rms_norm_eps"])
    q = _dot(x, p["q"]["kernel"], mode).reshape(b, n, heads, nope + rope)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    kv = _dot(x, p["kv_a"]["kernel"], mode)
    latent, k_rope = kv[..., :rank], kv[..., rank:]
    kv_up = _dot(_rms_norm(latent, p["kv_norm"], eps), p["kv_b"]["kernel"],
                 mode).reshape(b, n, heads, nope + v_dim)
    k_nope, v = kv_up[..., :nope], kv_up[..., nope:]
    if config.get("rotary_on", "rope") == "rope":
        q_rope = _rotary(q_rope, theta)
    else:       # a fault to be read: the rotation on the wrong part of q
        q_nope = _rotary(q_nope, theta)
    k_rope = jnp.broadcast_to(_rotary(k_rope[:, :, None], theta),
                              (b, n, heads, rope))
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, k_rope], axis=-1)
    logits = jnp.einsum("bqhd,bkhd->bhqk", mode.rounded(q), mode.rounded(k),
                        precision=HIGHEST) / np.sqrt(nope + rope)
    future = np.triu(np.ones((n, n), bool), k=1)
    logits = jnp.where(future[None, None], -jnp.inf, logits)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", mode.rounded(probs), mode.rounded(v),
                     precision=HIGHEST)
    return _dot(out.reshape(b, n, heads * v_dim), p["o"]["kernel"], mode)


def _mlp(x, gate, up, down, mode):
    return _dot(jax.nn.silu(_dot(x, gate, mode)) * _dot(x, up, mode), down,
                mode)


def routing_weights(x, p, config, mode=EVAL):
    """``w`` [..., n_routed_experts]: each expert's weight for each token,
    zero where the expert is not among the token's choice."""
    scores = jax.nn.sigmoid(_dot(x, p["router"], mode))
    select = scores
    if config["topk_method"] == "noaux_tc":
        select = scores + p["selection_bias"].astype(jnp.float32)
    _, choice = jax.lax.top_k(select, int(config["num_experts_per_tok"]))
    weights = scores * jnp.sum(jax.nn.one_hot(choice, scores.shape[-1]),
                               axis=-2)
    if config["norm_topk_prob"]:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    return weights * float(config["routed_scaling_factor"])


def expert_layer(x, p, config, mode=EVAL):
    """``sum over the experts held here of w_i E_i(x) + Shared(x)``."""
    weights = routing_weights(x, p, config, mode)
    first = int(config.get("experts_held_first", 0))
    width = int(config["moe_intermediate_size"])
    y = jnp.zeros_like(x)
    for e in range(p["experts_down"].shape[0]):
        gate_up = p["experts_gate_up"][e]
        y = y + weights[..., first + e, None] * _mlp(
            x, gate_up[:, :width], gate_up[:, width:], p["experts_down"][e],
            mode)
    if int(config["n_shared_experts"]):
        shared = p["shared"]
        y = y + _mlp(x, shared["gate"]["kernel"], shared["up"]["kernel"],
                     shared["down"]["kernel"], mode)
    return y


def _block(x, blk, config, mode):
    eps = float(config["rms_norm_eps"])
    x = x + _attention(_rms_norm(x, blk["attn_norm"], eps), blk["attn"],
                       config, mode)
    y = _rms_norm(x, blk["mlp_norm"], eps)
    if "mlp" in blk:
        mlp = blk["mlp"]
        return x + _mlp(y, mlp["gate"]["kernel"], mlp["up"]["kernel"],
                        mlp["down"]["kernel"], mode)
    return x + expert_layer(y, blk["moe"], config, mode)


def _logits(variables, images, config, mode):
    p = variables["params"]["backbone"]
    kernel = p["patch_embed"]["kernel"].astype(jnp.float32)
    patch = kernel.shape[0]
    images = jnp.asarray(images, jnp.float32)
    centred = images - jnp.mean(images, axis=(1, 2), keepdims=True)
    images = centred / jnp.sqrt(
        jnp.mean(centred ** 2, axis=(1, 2), keepdims=True) + 1e-6)
    x = jax.lax.conv_general_dilated(
        mode.rounded(images), mode.rounded(kernel),
        (patch, patch), "VALID", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=HIGHEST) + p["patch_embed"]["bias"].astype(jnp.float32)
    h = x.reshape(x.shape[0], -1, kernel.shape[-1])
    frozen = _Frozen(config)
    block = jax.checkpoint(_block, static_argnums=(2, 3)) if mode.remat \
        else _block
    depth = sum(1 for name in p if name.startswith("layer"))
    for i in range(depth):
        h = block(h, p[f"layer{i}"], frozen, mode)
    h = _rms_norm(h, p["norm_final"], float(config["rms_norm_eps"]))
    return mlp_head(jnp.mean(h, axis=1), variables["params"]["head"], mode)


class _Frozen(dict):
    """The configuration as a static argument of ``jax.checkpoint``:
    hashable by identity, read like the dict it is."""

    __hash__ = object.__hash__
    __eq__ = object.__eq__


def forward(variables, images, config, mode=None):
    """Logits [B, classes] for normalised float32 images [B, H, W, 3];
    ``config`` is the configuration file's content; ``mode`` (eval where
    absent) lets the control round its inputs."""
    return _logits(variables, images, config, mode or EVAL)


def train_loss(variables, images, labels, config, mode=None):
    """The loss a training step reports for this batch with these
    (pre-step) variables: the plain cross-entropy of the logits; float32,
    each block recomputed in the backward pass so that a full-size batch
    fits."""
    mode = mode or Mode(train=True, remat=True)
    return cross_entropy(_logits(variables, images, config, mode), labels)
