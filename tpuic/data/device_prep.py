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

- geometry: rot90^k, vflip and hflip compose to one of the eight
  symmetries of the square, so each sample takes at most one transpose,
  one reversal of the rows and one of the columns, the three conditions
  computed from the decisions by integer logic, all on the uint8 batch
  before the conversion to float32 — a permutation, bitwise-equal to the
  NumPy path. On the TPU a transpose or a reversal is no free layout op:
  each is a pass over the batch, paid by the byte (as four rot90 variants
  in float32 the program took 2.7 ms of a 49 ms ResNet-50 step, composed
  on uint8 0.7; PERF.md PR 30).
- color: same f32 arithmetic as transforms.adjust_* (clip to [0,255]);
  reduction order in the contrast mean is the compiler's: it may differ
  from NumPy's pairwise sums, and from one compiled shape to another, at
  the last-ulp level (tests/test_pack.py::
  test_device_prep_matches_numpy_all_paths pins the tolerance).
- normalize: x/255 (true division), then (x-mean)/std, f32.
"""

from __future__ import annotations

import math
import re
from typing import Dict, Optional, Tuple

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
    # The eight outcomes of (rot, vflip, hflip) are the symmetries of the
    # square: each is "transpose or not, flip rows or not, flip columns or
    # not". np.rot90(m, k, axes=(0,1)) is k=1: flip(m.T, 0); k=2:
    # flip(flip(m, 0), 1); k=3: flip(m.T, 1); flips of different axes
    # commute and each undoes itself, so the reference's vflip and hflip
    # fold into the same two reversals. All of it on the uint8 batch: a
    # permutation commutes with the exact conversion to float32, and the
    # chip pays a reversal by the byte.
    rot = params["rot"].astype(jnp.int32)[:, None, None, None]
    vf = params["vflip"].astype(bool)[:, None, None, None]
    hf = params["hflip"].astype(bool)[:, None, None, None]
    g = images_u8
    g = jnp.where(rot % 2 == 1, jnp.swapaxes(g, 1, 2), g)
    g = jnp.where(((rot == 1) | (rot == 2)) ^ vf, jnp.flip(g, axis=1), g)
    g = jnp.where(((rot == 2) | (rot == 3)) ^ hf, jnp.flip(g, axis=2), g)
    g = g.astype(jnp.float32)

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
    # named: the function's name is the program's in a device trace
    def device_prep(imgs, packed):
        return apply_batch_augment(imgs, _unpack_params(packed), mean=mean,
                                   std=std, out_dtype=out_dtype)
    if sharding is None:
        return jax.jit(device_prep)
    return jax.jit(device_prep, in_shardings=(sharding, sharding),
                   out_shardings=sharding, donate_argnums=(0,))


# The device-resident corpus is held as [N, R, 128] uint8: each image one
# dense run of R*128 bytes, the image axis the only one the device does not
# tile. The TPU tiles a uint8 array's two minor dimensions (8, 128), so a
# row is whole tiles of 1 KiB, and its compiler gathers a slice in place
# only up to 256 KiB (past that it slices the whole operand first), so a
# longer row is gathered as equal pieces of at most that. Rows are padded
# up to fit both; at 32 px (3 tiles), 224 px (147) and 299 px (2 x 131)
# there is nothing to pad, at 768 px 1 KiB a row.
_LANES = 128
_TILE_BYTES = 8 * _LANES
_MAX_GATHER_TILES = 256


def _resident_geometry(size: int) -> Tuple[int, int]:
    """(tiles a gathered piece, pieces a row) of a ``size``-px image."""
    tiles = -(-size * size * 3 // _TILE_BYTES)
    pieces = -(-tiles // _MAX_GATHER_TILES)
    return -(-tiles // pieces), pieces


def resident_row_bytes(size: int) -> int:
    """Bytes one ``size``-px image takes in the held form (padding counted)."""
    tiles, pieces = _resident_geometry(size)
    return tiles * pieces * _TILE_BYTES


def resident_shape(n: int, size: int) -> Tuple[int, int, int]:
    """Shape of the held form of ``n`` images of ``size`` px."""
    return (n, resident_row_bytes(size) // _LANES, _LANES)


def resident_rows(images: np.ndarray) -> np.ndarray:
    """Host [n,S,S,3] uint8 -> the held form [n,R,128].

    A view of a contiguous array (the packed memmap, a slice of it) when a
    row needs no padding; a zero-padded copy otherwise."""
    n, size = images.shape[:2]
    flat = images.reshape(n, -1)
    pad = resident_row_bytes(size) - flat.shape[1]
    if pad:
        flat = np.pad(flat, ((0, 0), (0, pad)))
    return flat.reshape(n, -1, _LANES)


def make_resident_prep(size: int, mean=None, std=None,
                       out_dtype=jnp.float32,
                       sharding: Optional[jax.sharding.NamedSharding] = None,
                       replicated=None):
    """Jitted (corpus_u8 [N,R,128], indices [B] i32, packed_params) ->
    normalized [B,size,size,3] batch, for the DEVICE-RESIDENT dataset cache.

    The whole packed uint8 dataset lives in HBM (uploaded once, replicated
    under a mesh) in the row-contiguous form of ``resident_rows``; a batch
    is one gather of B rows read in place, reshaped to [B,S,S,3] only then,
    + augment + normalize ON DEVICE. No op of the program has an operand
    or a result that grows with N, so a step costs the device B rows of
    traffic and the host the index/param vectors (a few KB).

    The form is the point. Held as [N,S,S,3] the corpus arrives in the
    TPU's default layout for that shape (N minor-most) and the gather wants
    N major-most, so the compiler put a copy of all N rows in front of
    every batch: 3.68 ms a step at 0.77 GB, 14.7 ms at 3.1 GB (ledger, PR
    25). A flat [N,S*S*3] does no better: N is then tiled, rows interleave
    inside tiles, and the compiler slices the whole corpus (PERF.md PR 26).
    check_resident_prep is the guard."""
    row = size * size * 3
    tiles, pieces = _resident_geometry(size)

    # named: the function's name is the program's in a device trace
    def resident_prep(data, idx, packed):
        batch = idx.shape[0]
        # [N,R,128] -> [N*pieces, 8*tiles, 128] splits whole tiles off the
        # untiled axis: no bytes move. One piece a row at 224 px.
        held = data.reshape(-1, 8 * tiles, _LANES)
        at = (idx[:, None] * pieces
              + jnp.arange(pieces, dtype=idx.dtype)).reshape(-1)
        rows = jnp.take(held, at, axis=0).reshape(batch, -1)
        imgs = rows[:, :row].reshape(batch, size, size, 3)
        return apply_batch_augment(imgs, _unpack_params(packed), mean=mean,
                                   std=std, out_dtype=out_dtype)
    if sharding is None:
        return jax.jit(resident_prep)
    return jax.jit(resident_prep,
                   in_shardings=(replicated, sharding, sharding),
                   out_shardings=sharding)


# A float32 reversal in a compiled program's text, and its dimensions.
_F32_REVERSE = re.compile(r"= f32\[([\d,]+)\]\S* reverse\(")


def check_resident_prep(size: int, rows: int = 4096, batch: int = 8,
                        mesh: Optional[jax.sharding.Mesh] = None,
                        device=None) -> Dict:
    """Compile the resident prep for a corpus >> batch and assert it holds
    no corpus-sized temporary and reverses no float32 copy of the batch.

    Only shapes are handed to the compiler; nothing is allocated. On the
    TPU (chip_smoke.py, and a described one in tests/test_chip_compile.py)
    this is the check that would have caught the per-step copy of the whole
    corpus, and the augmentation's geometry done after the conversion to
    float32 (four times the bytes, in every step); on a CPU it catches a
    gather that converts the corpus first. ``bytes_accessed`` is the
    compiler's own count of the program's traffic, for the caller to hold
    against a record. Compiles for the default backend's first device, for
    ``device``, or, with ``batch`` the global batch, for ``mesh``."""
    from jax.sharding import (NamedSharding, PartitionSpec as P,
                              SingleDeviceSharding)

    if mesh is not None:
        repl, shard = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
        prep = make_resident_prep(size, sharding=shard, replicated=repl)
    else:
        repl = shard = (None if device is None
                        else SingleDeviceSharding(device))
        prep = make_resident_prep(size)
    shape = resident_shape(rows, size)
    compiled = prep.lower(
        jax.ShapeDtypeStruct(shape, jnp.uint8, sharding=repl),
        jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=shard),
        jax.ShapeDtypeStruct((batch, len(PARAM_KEYS)), jnp.float32,
                             sharding=shard)).compile()
    one_chip = batch // (1 if mesh is None else mesh.shape["data"])
    facts = {"corpus_bytes": int(np.prod(shape)),
             "temp_bytes": int(compiled.memory_analysis().temp_size_in_bytes),
             "bytes_accessed": float(
                 compiled.cost_analysis()["bytes accessed"]),
             "f32_reversals": sorted(
                 f"f32[{m.group(1)}]"
                 for m in _F32_REVERSE.finditer(compiled.as_text())
                 if math.prod(map(int, m.group(1).split(",")))
                 >= one_chip * size * size * 3)}
    if 4 * facts["temp_bytes"] >= facts["corpus_bytes"]:
        raise AssertionError(
            f"the resident prep holds a corpus-sized temporary: {facts}")
    if facts["f32_reversals"]:
        raise AssertionError(
            f"the resident prep reverses the batch in float32: {facts}")
    return facts
