"""Vision Transformer (Dosovitskiy et al., arXiv:2010.11929) in plain
float32 ``jax.numpy``.

Reads the parameter tree of the program's flax model: ``backbone.
{patch_embed, cls, pos_embed, block{i}.{ln1, attn.{qkv, out}, ln2, mlp_up,
mlp_down}, ln_final}`` and ``head``. Depth is read off the tree and the
patch size off the embedding kernel; the number of heads is not in the
tree, so it is the configuration's ``num_heads``, or ``hidden / 64`` (every
published ViT has 64-wide heads) where none is given.

Departures from the published description, all the program's own:
- the head is the reference system's MLP (see ``resnet.mlp_head``) in place
  of one linear layer;
- LayerNorm eps 1e-6 and exact (erf) GELU, as timm; the paper names
  neither;
- no dropout (the program's default rate is 0), eval mode.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.resnet import _dense, mlp_head

LN_EPS = 1e-6
HIGHEST = jax.lax.Precision.HIGHEST


def _layer_norm(x, p):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return ((x - mean) * jax.lax.rsqrt(var + LN_EPS)
            * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32))


def _attention(x, p, heads: int):
    b, n, d = x.shape
    q, k, v = jnp.split(_dense(x, p["qkv"]), 3, axis=-1)
    q, k, v = (t.reshape(b, n, heads, d // heads) for t in (q, k, v))
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        precision=HIGHEST) / jnp.sqrt(d // heads)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision=HIGHEST)
    return _dense(out.reshape(b, n, d), p["out"])


def forward(variables, images, config=None):
    """Logits [B, classes] for normalised float32 images [B, H, W, 3];
    ``config`` is the configuration file's content (for ``num_heads``)."""
    p = variables["params"]["backbone"]
    x = jnp.asarray(images, jnp.float32)
    kernel = p["patch_embed"]["kernel"].astype(jnp.float32)
    patch, hidden = kernel.shape[0], kernel.shape[-1]
    heads = int((config or {}).get("num_heads") or max(1, hidden // 64))
    x = jax.lax.conv_general_dilated(
        x, kernel, (patch, patch), "VALID",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)
    x = x + p["patch_embed"]["bias"].astype(jnp.float32)
    b = x.shape[0]
    x = x.reshape(b, -1, hidden)
    cls = jnp.broadcast_to(p["cls"].astype(jnp.float32), (b, 1, hidden))
    x = jnp.concatenate([cls, x], axis=1) + p["pos_embed"].astype(jnp.float32)
    i = 0
    while f"block{i}" in p:
        blk = p[f"block{i}"]
        x = x + _attention(_layer_norm(x, blk["ln1"]), blk["attn"], heads)
        y = jax.nn.gelu(_dense(_layer_norm(x, blk["ln2"]), blk["mlp_up"]),
                        approximate=False)
        x = x + _dense(y, blk["mlp_down"])
        i += 1
    x = _layer_norm(x, p["ln_final"])[:, 0]
    return mlp_head(x, variables["params"]["head"])
