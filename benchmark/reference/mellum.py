"""Mellum2-12B-A2.5B's decoder stack (JetBrains, ``config.json`` of
``Mellum2-12B-A2.5B-Instruct``, ``model_type`` ``mellum``) as an image
classifier's backbone, in plain float32 ``jax.numpy``.

Reads the parameter tree of the program's flax model: ``backbone.
{patch_embed, layer{i}.{attn_norm, attn.{q, k, v, o}, mlp_norm,
moe.{router, experts_gate_up, experts_down}}, norm_final}`` and ``head``.
Depth, patch size and how many experts are held are read off the tree;
every other size is the configuration's: ``num_attention_heads``,
``num_key_value_heads``, ``head_dim``, ``layer_types``, ``sliding_window``,
``rope_parameters`` (one section a kind of layer), ``rms_norm_eps``,
``moe_intermediate_size``, ``num_experts_per_tok``, ``norm_topk_prob``,
``experts_held_first`` (the first expert of this chip's share; their
number is the tree's), and the two keys that exist so that a fault can be
planted: ``kv_head_of`` (``group``) and ``full_attention_rope``
(``yarn``).

The mathematics, for tokens ``h = Conv(S(image)) + b`` in raster order,
``T`` of them an image, position = raster index (all projections without
bias):

- ``S(image) = (image - m) / sqrt(v + 1e-6)``, ``m`` and ``v`` the mean and
  the variance of each channel over the image's own pixels;
- ``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * g``;
- block ``l``: ``h += Attn_l(N(h))``, ``h += MoE(N(h))``; a closing norm
  after the last block;
- ``Attn_l(x)``: ``q = x W_q`` as ``H`` heads, ``k = x W_k`` and ``v = x
  W_v`` as ``Hkv``; ``q`` and ``k`` rotated (pairs ``(i, i + d/2)``, angle
  ``position * f_i``) and both times ``a``; query head ``j`` reads
  key-value head ``j // (H / Hkv)``; ``softmax(q k^T / sqrt(d) + mask) v``
  with ``mask[i, t] = 0`` where ``t <= i`` and ``t > i - W_l``, ``-inf``
  elsewhere; ``W_o``. ``layer_types[l]`` picks ``W_l`` and the table: a
  ``sliding_attention`` layer has ``W_l = sliding_window`` and its
  section's ``rope_type`` ``default``: ``f_i = theta^(-2i/d)``, ``a = 1``;
  a ``full_attention`` layer has no window and ``yarn``: with ``dim(n) = d
  ln(original_max / (2 pi n)) / (2 ln theta)``, ``low = floor(dim(
  beta_fast))`` and ``high = ceil(dim(beta_slow))`` clipped to ``[0, d -
  1]``, ``ramp_i = clip((i - low) / (high - low), 0, 1)``, ``f_i = (1 -
  ramp_i) theta^(-2i/d) + ramp_i theta^(-2i/d) / factor``, ``a =
  attention_factor``;
- ``MLP_e(x) = W_down(silu(x W_gate) * x W_up)``, every expert's;
- ``MoE(x)``: ``p = softmax(x W_r)`` over the ``num_experts`` as published
  (the router's width); the choice is the ``num_experts_per_tok`` largest;
  ``w_e = p_e / sum over the choice of p`` (``norm_topk_prob``; else
  ``p_e``), zero for an expert not chosen; ``y = sum over the experts held
  here of w_e MLP_e(x)``: a ``for`` over the held experts, each applied to
  all tokens. What the absent experts would add is left out;
- read-out: the mean over positions of the closing norm's output; logits
  from the reference system's MLP head; loss: the plain cross-entropy.

Computed in blocks so that it fits the chip at the cell's size (4,096
tokens a row): attention runs image by image and, within an image, eight
query heads at a time (``lax.map``), each slice recomputed in the backward
pass when ``mode.remat``, so that no more than one ``[8, T, T]`` float32
score block is alive.

Departures from the published model, all the program's own: the
standardised image's patch embedding where the token table stood, the MLP
head where the LM head stood, the mean read-out, this chip's share of the
layers and of each layer's experts, and no multi-token-prediction head
(the ``config`` has no key for one).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.kanana import (HIGHEST, _dot, _Frozen, _mlp,
                                        _rms_norm)
from benchmark.reference.resnet import EVAL, Mode, cross_entropy, mlp_head

HEADS_AT_ONCE = 8


def rotary_table(rope: dict, positions: int, d: int):
    """``(cos, sin)`` [positions, d / 2] of one section of
    ``rope_parameters``, the attention factor in both."""
    theta = float(rope["rope_theta"])
    freq = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    times = 1.0
    if rope["rope_type"] == "yarn":
        def dim(turns):
            return (d * np.log(rope["original_max_position_embeddings"]
                               / (turns * 2 * np.pi)) / (2 * np.log(theta)))
        low = max(np.floor(dim(rope["beta_fast"])), 0)
        high = min(np.ceil(dim(rope["beta_slow"])), d - 1)
        ramp = np.clip((np.arange(d // 2) - low)
                       / (high - low if high > low else 0.001), 0, 1)
        freq = (1 - ramp) * freq + ramp * freq / rope["factor"]
        times = float(rope["attention_factor"])
    elif rope["rope_type"] != "default":
        raise ValueError(f"no rotary table of type {rope['rope_type']!r}")
    angles = (np.arange(positions, dtype=np.float32)[:, None]
              * freq.astype(np.float32)[None])
    return ((times * np.cos(angles)).astype(np.float32),
            (times * np.sin(angles)).astype(np.float32))


def _rotated(x, cos, sin):
    """``x`` [B, N, H, d]: pairs ``(x[i], x[i + d/2])`` turned."""
    a, b = jnp.split(x, 2, axis=-1)
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(x, p, kind: str, config, mode):
    b, n, _ = x.shape
    heads, kv_heads = (int(config["num_attention_heads"]),
                       int(config["num_key_value_heads"]))
    d = int(config["head_dim"])
    q = _dot(x, p["q"]["kernel"], mode).reshape(b, n, heads, d)
    k = _dot(x, p["k"]["kernel"], mode).reshape(b, n, kv_heads, d)
    v = _dot(x, p["v"]["kernel"], mode).reshape(b, n, kv_heads, d)
    full = kind == "full_attention"
    rope = config["rope_parameters"][kind]
    if full and config.get("full_attention_rope", "yarn") != "yarn":
        # a fault to be read: the full layers on the sliding layers' table
        rope = config["rope_parameters"]["sliding_attention"]
    cos, sin = rotary_table(rope, n, d)
    q, k = _rotated(q, cos, sin), _rotated(k, cos, sin)
    if config.get("kv_head_of", "group") == "group":
        kv_of = np.arange(heads) // (heads // kv_heads)
    else:       # a fault to be read: the key-value heads dealt out in turn
        kv_of = np.arange(heads) % kv_heads
    at_once = min(HEADS_AT_ONCE, heads)
    i, t = np.arange(n)[:, None], np.arange(n)[None]
    seen = t <= i
    if not full:
        seen &= t > i - int(config["sliding_window"])

    def of_heads(qkv):
        q, k, v = qkv       # [at_once, N, d] each
        logits = jnp.einsum("hqd,hkd->hqk", mode.rounded(q), mode.rounded(k),
                            precision=HIGHEST) / np.sqrt(d)
        probs = jax.nn.softmax(jnp.where(seen[None], logits, -jnp.inf),
                               axis=-1)
        return jnp.einsum("hqk,hkd->hqd", mode.rounded(probs),
                          mode.rounded(v), precision=HIGHEST)
    if mode.remat:
        of_heads = jax.checkpoint(of_heads)

    def slices(t):      # [B, N, H, d] -> [B * H / at_once, at_once, N, d]
        return jnp.transpose(t, (0, 2, 1, 3)).reshape(-1, at_once, n, d)
    out = jax.lax.map(of_heads, (slices(q), slices(k[:, :, kv_of]),
                                 slices(v[:, :, kv_of])))
    out = jnp.transpose(out.reshape(b, heads, n, d), (0, 2, 1, 3))
    return _dot(out.reshape(b, n, heads * d), p["o"]["kernel"], mode)


def routing_weights(x, p, config, mode=EVAL):
    """``w`` [..., num_experts as published]: each expert's weight for each
    token, zero where the expert is not among the token's choice."""
    probs = jax.nn.softmax(_dot(x, p["router"], mode), axis=-1)
    _, choice = jax.lax.top_k(probs, int(config["num_experts_per_tok"]))
    weights = probs * jnp.sum(jax.nn.one_hot(choice, probs.shape[-1]),
                              axis=-2)
    if config["norm_topk_prob"]:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights


def expert_layer(x, p, config, mode=EVAL):
    """``sum over the experts held here of w_e MLP_e(x)``."""
    weights = routing_weights(x, p, config, mode)
    first = int(config.get("experts_held_first", 0))
    width = int(config["moe_intermediate_size"])
    y = jnp.zeros_like(x)
    for e in range(p["experts_down"].shape[0]):
        gate_up = p["experts_gate_up"][e]
        y = y + weights[..., first + e, None] * _mlp(
            x, gate_up[:, :width], gate_up[:, width:], p["experts_down"][e],
            mode)
    return y


def _block(x, blk, kind, config, mode):
    eps = float(config["rms_norm_eps"])
    x = x + _attention(_rms_norm(x, blk["attn_norm"], eps), blk["attn"],
                       kind, config, mode)
    return x + expert_layer(_rms_norm(x, blk["mlp_norm"], eps), blk["moe"],
                            config, mode)


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
    block = jax.checkpoint(_block, static_argnums=(2, 3, 4)) if mode.remat \
        else _block
    depth = sum(1 for name in p if name.startswith("layer"))
    for i in range(depth):
        h = block(h, p[f"layer{i}"], config["layer_types"][i], frozen, mode)
    h = _rms_norm(h, p["norm_final"], float(config["rms_norm_eps"]))
    return mlp_head(jnp.mean(h, axis=1), variables["params"]["head"], mode)


def forward(variables, images, config, mode=None):
    """Logits [B, classes] for normalised float32 images [B, H, W, 3];
    ``config`` is the configuration file's content; ``mode`` (eval where
    absent) lets the control round its inputs."""
    return _logits(variables, images, config, mode or EVAL)


def train_loss(variables, images, labels, config, mode=None):
    """The loss a training step reports for this batch with these
    (pre-step) variables: the plain cross-entropy of the logits; float32,
    each block and each attention slice recomputed in the backward pass so
    that a full-size batch fits."""
    mode = mode or Mode(train=True, remat=True)
    return cross_entropy(_logits(variables, images, config, mode), labels)
