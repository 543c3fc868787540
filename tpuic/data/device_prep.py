"""Device-side augmentation + normalization (TPU does the per-epoch math).

With the packed uint8 cache (tpuic/data/pack.py) the host's per-epoch work
is reduced to batch assembly; the whole per-sample transform chain of the
reference — rot90^k / vflip / hflip (dp/loader.py:63-71), the if/elif color
jitter (dp/loader.py:74-81), and /255 + ImageNet standardization
(dp/loader.py:86-91) — runs on the TPU as one jitted elementwise program
over the batch. This also cuts H2D traffic 4x (uint8 ships instead of
float32).

Augmentation *decisions* are still drawn on the host from the
(seed, epoch, index) RNG stream (transforms.draw_augment — the single
source of truth shared with the NumPy and native paths), so a sample's
augmentation is identical no matter which path executed it. This module
only *applies* pre-drawn decisions, vectorized per sample:

- geometry: the four rot90 variants are computed batch-wise (transpose +
  reverse are free layout ops for XLA) and selected per sample, then
  conditional v/h flips — a permutation, bitwise-equal to the NumPy path.
- color: same f32 arithmetic as transforms.adjust_* (clip to [0,255]);
  reduction order in the contrast mean may differ from NumPy's pairwise
  sums at the last-ulp level (tests/test_pack.py::
  test_device_prep_matches_numpy_all_paths pins the tolerance).
- normalize: x/255 (true division), then (x-mean)/std, f32.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from tpuic.data.transforms import IMAGENET_MEAN, IMAGENET_STD, _LUMA


def apply_batch_augment(images_u8: jnp.ndarray, params: Dict[str, jnp.ndarray],
                        mean=None, std=None,
                        out_dtype=jnp.float32) -> jnp.ndarray:
    """[B,S,S,3] uint8 + per-sample params -> [B,S,S,3] normalized float.

    params: {'rot': [B] i32 (k in 0..3), 'vflip': [B] i32, 'hflip': [B] i32,
    'color': [B] i32 (0 none / 1 sat / 2 bright / 3 contrast),
    'factor': [B] f32}. Traced; call under jit (make_device_prep)."""
    x = images_u8.astype(jnp.float32)
    rot = params["rot"].astype(jnp.int32)[:, None, None, None]
    # np.rot90(m, k, axes=(0,1)) parity: out_k[i,j] selected per sample.
    xt = jnp.swapaxes(x, 1, 2)
    r1 = jnp.flip(xt, axis=1)                 # out[i,j] = m[j, S-1-i]
    r2 = jnp.flip(jnp.flip(x, axis=1), axis=2)
    r3 = jnp.flip(xt, axis=2)                 # out[i,j] = m[S-1-j, i]
    g = jnp.where(rot == 1, r1, jnp.where(rot == 2, r2,
                                          jnp.where(rot == 3, r3, x)))
    vf = params["vflip"].astype(bool)[:, None, None, None]
    hf = params["hflip"].astype(bool)[:, None, None, None]
    g = jnp.where(vf, jnp.flip(g, axis=1), g)
    g = jnp.where(hf, jnp.flip(g, axis=2), g)

    color = params["color"].astype(jnp.int32)[:, None, None, None]
    factor = params["factor"].astype(jnp.float32)[:, None, None, None]
    luma = jnp.asarray(_LUMA, jnp.float32)
    gray = jnp.sum(g * luma, axis=-1, keepdims=True)
    sat = jnp.clip(gray + (g - gray) * factor, 0.0, 255.0)
    bright = jnp.clip(g * factor, 0.0, 255.0)
    gmean = jnp.mean(g, axis=(1, 2, 3), keepdims=True)
    contrast = jnp.clip(gmean + (g - gmean) * factor, 0.0, 255.0)
    y = jnp.where(color == 1, sat, jnp.where(color == 2, bright,
                                             jnp.where(color == 3, contrast,
                                                       g)))
    mean = jnp.asarray(IMAGENET_MEAN if mean is None else mean, jnp.float32)
    std = jnp.asarray(IMAGENET_STD if std is None else std, jnp.float32)
    y = (y / 255.0 - mean) / std
    return y.astype(out_dtype)


def identity_params(batch: int) -> Dict[str, np.ndarray]:
    """No-op augmentation (val / non-train folds): normalize only."""
    return {
        "rot": np.zeros((batch,), np.int32),
        "vflip": np.zeros((batch,), np.int32),
        "hflip": np.zeros((batch,), np.int32),
        "color": np.zeros((batch,), np.int32),
        "factor": np.ones((batch,), np.float32),
    }


PARAM_KEYS = ("rot", "vflip", "hflip", "color", "factor")


def pack_params(params: Dict[str, np.ndarray]) -> np.ndarray:
    """[B,5] f32 row per sample — ONE host->device transfer instead of five
    (each transfer has a fixed dispatch cost)."""
    return np.stack([np.asarray(params[k], np.float32)
                     for k in PARAM_KEYS], axis=1)


def _unpack_params(packed: jnp.ndarray) -> Dict[str, jnp.ndarray]:
    cols = {k: packed[:, i] for i, k in enumerate(PARAM_KEYS)}
    return {k: (cols[k].astype(jnp.int32) if k != "factor" else cols[k])
            for k in PARAM_KEYS}


def make_device_prep(mean=None, std=None, out_dtype=jnp.float32,
                     sharding: Optional[jax.sharding.NamedSharding] = None):
    """Jitted (images_u8, packed_params [B,5] f32) -> normalized batch.

    ``sharding``: the batch's data-axis NamedSharding under a mesh — the
    prep is elementwise per sample, so it runs shard-local with no
    collectives."""
    fn = lambda imgs, packed: apply_batch_augment(
        imgs, _unpack_params(packed), mean=mean, std=std,
        out_dtype=out_dtype)
    if sharding is None:
        return jax.jit(fn)
    return jax.jit(fn, in_shardings=(sharding, sharding),
                   out_shardings=sharding, donate_argnums=(0,))


def make_resident_prep(mean=None, std=None, out_dtype=jnp.float32,
                       sharding: Optional[jax.sharding.NamedSharding] = None,
                       replicated=None):
    """Jitted (dataset_u8 [N,S,S,3], indices [B] i32, packed_params) ->
    normalized batch, for the DEVICE-RESIDENT dataset cache.

    The whole packed uint8 dataset lives in HBM (uploaded once, replicated
    under a mesh); a batch costs one [B]-row gather + augment + normalize
    ON DEVICE. Per-step host->device traffic is the index/param vectors —
    a few KB — instead of the image bytes. This is what makes the training
    loop immune to host-link bandwidth."""
    def fn(data, idx, packed):
        imgs = jnp.take(data, idx, axis=0)
        return apply_batch_augment(imgs, _unpack_params(packed), mean=mean,
                                   std=std, out_dtype=out_dtype)
    if sharding is None:
        return jax.jit(fn)
    return jax.jit(fn, in_shardings=(replicated, sharding, sharding),
                   out_shardings=sharding)
