"""Causal attention over a whole short sequence as one Pallas TPU kernel,
scored from the pieces a latent-attention layer's projections leave.

``flash_attention.py`` tiles the keys and carries an online softmax, which
is what a long sequence needs. At a few hundred tokens a head's whole score
matrix fits VMEM (196 x 196 float32 is 154 KB), and what the dense XLA path
then pays for is HBM traffic: a ``[B, H, N, N]`` tensor written and read a
dozen times forward and backward, head-split transposes around it, and, for
latent attention (models/kanana.py), ONE rotary key broadcast to every head
and concatenated to each head's own key. This kernel computes

    s[b,h,i,j] = (q_nope[b,i,h] . k_nope[b,j,h]
                  + q_rope[b,i,h] . k_rope[b,j]) * scale     (float32)
    p = softmax over j <= i of s                              (float32)
    o[b,i,h]   = sum_j cast(p[b,h,i,j]) v[b,j,h]

from the four pieces where they lie: no concatenated query or key, the
shared rotary key read once a batch row, and no score in HBM. The rotary
pieces are optional (``q_rope=None, k_rope=None``): without them this is
plain causal attention over ``nope``-wide heads.

One grid cell holds one batch row and a group of heads. Operands keep the
``[B, N, H * D]`` layout the projections write and the output projection
reads (a reshape of ``[B, N, H, D]``, no transpose): a head is a lane
slice at a multiple of 128, the 64-wide rotary pieces lie two heads to a
lane tile. Forward writes the output and the rows' log-sum-exp; ONE
backward kernel rebuilds ``s`` and ``p`` from them and produces every
gradient; the shared key's, the sum over heads of ``ds^T q_rope``, is
accumulated in float32 across a batch row's head groups (the grid's second
axis is sequential for it).

The sequence is NOT padded. A block's rows are the array's own, which
Mosaic takes at any length and tiles itself (196 rows are 12 bfloat16 tiles
and a quarter; it masks the rest). Padding to whole tiles in the wrapper
was measured and lost: every operand and result took one more pass over HBM
(a pad or a slice the compiler did not fuse into its neighbours), and the
kernel is bound by HBM already: at the 30B widths, batch 32 and N = 196 ->
208, latent attention forward + backward 11.12 ms a layer against 10.35
unpadded, the same numbers to the last digit (chip, PR 35).

:func:`supports` is the dispatch rule, by shape alone; :func:`reference`
is the same formulation in plain ``jnp`` (the tests' oracle).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
# Rows a block takes in VMEM: bfloat16 tiles are 16 rows (float32 8).
_TILE_ROWS = 16
# What a grid cell may take of VMEM, and what the kernels are compiled
# with as their limit: a quarter of a v5e core's 128 MiB (the default
# scoped limit, 16 MiB, would refuse the backward cell of 8 heads).
VMEM_LIMIT_BYTES = 32 * 2 ** 20
# [N, N] float32 temporaries alive at once in the backward cell: s, p, dp,
# ds, the mask, and bfloat16 copies of p and ds (two halves).
_SCORE_TEMPORARIES = 6


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def cell_bytes(n: int, group: int, nope: int, rope: int, v_dim: int,
               itemsize: int) -> int:
    """VMEM the backward kernel's grid cell takes (the forward's is
    smaller) at ``group`` heads a cell: every operand and result block
    twice (the pipeline's double buffering) and the score-sized float32
    temporaries of the one head in flight, rows to whole tiles and the
    scores' lanes to 128."""
    rows = _round_up(n, _TILE_ROWS)
    # q_nope, k_nope, v, o, do and dq_nope, dk_nope, dv; q_rope, dq_rope
    per_head = rows * itemsize * (2 * 2 * nope + 4 * v_dim + 2 * rope)
    # k_rope in, dk_rope (float32) out, the log-sum-exp (a lane tile)
    shared = rows * (rope * (itemsize + 4) + 128 * 4)
    scores = _SCORE_TEMPORARIES * rows * _round_up(n, 128) * 4
    return 2 * (group * per_head + shared) + scores


def head_group(n: int, heads: int, nope: int, rope: int, v_dim: int,
               itemsize: int) -> int:
    """Heads a grid cell holds: the largest of 8, 4, 2, 1 (or all of
    fewer than 8) that divides ``heads``, whose 64-wide rotary pieces fill
    lane tiles (an even group, or every head) and whose cell fits
    ``VMEM_LIMIT_BYTES``; 0 if none does. Fewer cells amortise a cell's
    fixed cost (~0.35 us) and the shared key's reload."""
    for g in sorted({g for g in (8, 4, 2, 1, min(heads, 8))
                     if heads % g == 0}, reverse=True):
        if (g == heads or g * rope % 128 == 0) and cell_bytes(
                n, g, nope, rope, v_dim, itemsize) <= VMEM_LIMIT_BYTES:
            return g
    return 0


def supports(n: int, heads: int, nope: int, rope: int, v_dim: int,
             itemsize: int = 2) -> bool:
    """Whether the kernel takes a core of these shapes: ``nope`` and
    ``v_dim`` multiples of 128 (a head is whole lane tiles), ``rope`` a
    multiple of 64 or 0, and a cell that fits VMEM (:func:`cell_bytes`:
    heads of 128 + 64 / 128 in bfloat16 fit 8 a cell up to N ~ 600, 4 to
    ~ 800, 2 to ~ 900; past N ~ 950 nothing fits and the keys want
    tiling, which is flash_attention.py's)."""
    return (nope > 0 and nope % 128 == 0 and v_dim > 0 and v_dim % 128 == 0
            and rope % 64 == 0
            and head_group(n, heads, nope, rope, v_dim, itemsize) > 0)


def reference(q_nope, k_nope, v, q_rope=None, k_rope=None, *,
              scale: Optional[float] = None):
    """The formulation in plain ``jnp``: two contractions accumulated in
    float32, no concatenate, no broadcast. ``q_nope``, ``k_nope`` [B, N,
    H, nope], ``v`` [B, N, H, v_dim], ``q_rope`` [B, N, H, rope],
    ``k_rope`` [B, N, rope] -> [B, N, H, v_dim]."""
    n = q_nope.shape[1]
    rope = 0 if q_rope is None else q_rope.shape[-1]
    if scale is None:
        scale = 1.0 / np.sqrt(q_nope.shape[-1] + rope)
    s = jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope,
                   preferred_element_type=jnp.float32)
    if q_rope is not None:
        s = s + jnp.einsum("bqhd,bkd->bhqk", q_rope, k_rope,
                           preferred_element_type=jnp.float32)
    causal = np.tril(np.ones((n, n), bool))
    s = jnp.where(causal[None, None], s * scale, jnp.finfo(jnp.float32).min)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _precision(dtype):
    # float32 operands: the default is ONE bfloat16 pass (flash_attention.py)
    return (jax.lax.Precision.HIGHEST if dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)


def _dot(a, b, contract, prec):
    """``a`` and ``b`` contracted over ``contract = (dim of a, dim of b)``,
    accumulated in float32."""
    return jax.lax.dot_general(a, b, (((contract[0],), (contract[1],)),
                                      ((), ())),
                               preferred_element_type=jnp.float32,
                               precision=prec)


def _causal(rows: int):
    return (jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 1)
            <= jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 0))


def _head(h: int, width: int, offset: int = 0, stride: int = 0):
    """The index of head ``h``'s ``width`` lanes in a ``[1, N, group *
    stride]`` block, ``offset`` lanes into the head (``stride`` defaults
    to ``width``)."""
    start = h * (stride or width) + offset
    return (0, slice(None), slice(start, start + width))


def _split(refs, rope: int, packed: bool):
    """The kernels' leading operands by name: ``(q_nope, keys, values,
    q_rope, k_rope, rest)``. Packed, a head's key lies beside its value in
    ONE operand, which is then both ``keys`` and ``values``."""
    qn, refs = refs[0], refs[1:]
    keys, values, refs = ((refs[0], refs[0], refs[1:]) if packed
                          else (refs[0], refs[1], refs[2:]))
    qr, kr, refs = (refs[0], refs[1], refs[2:]) if rope else (None, None, refs)
    return qn, keys, values, qr, kr, refs


def _scores(q_nope, k_nope, q_rope, k_rope, scale, causal, prec):
    """One head's masked scores [N, N] float32."""
    s = _dot(q_nope, k_nope, (1, 1), prec)
    if q_rope is not None:
        s = s + _dot(q_rope, k_rope, (1, 1), prec)
    return jnp.where(causal, s * scale, _NEG_INF)


def _fwd_kernel(*refs, group, nope, rope, v_dim, packed, scale):
    qn_ref, k_ref, v_ref, qr_ref, kr_ref, (o_ref, lse_ref) = _split(
        refs, rope, packed)
    stride = (nope + v_dim) if packed else 0
    dt = v_ref.dtype
    prec = _precision(dt)
    causal = _causal(qn_ref.shape[1])
    kr = kr_ref[0] if rope else None
    for h in range(group):
        s = _scores(qn_ref[_head(h, nope)], k_ref[_head(h, nope, 0, stride)],
                    qr_ref[_head(h, rope)] if rope else None, kr, scale,
                    causal, prec)
        m = jnp.max(s, axis=-1, keepdims=True)
        e = jnp.exp(s - m)
        l = jnp.sum(e, axis=-1, keepdims=True)
        p = (e * (1.0 / l)).astype(dt)
        v = v_ref[_head(h, v_dim, nope if packed else 0, stride)]
        o_ref[_head(h, v_dim)] = _dot(p, v, (1, 0), prec).astype(o_ref.dtype)
        lse_ref[0, 0, :, h:h + 1] = m + jnp.log(l)


def _bwd_kernel(*refs, group, nope, rope, v_dim, packed, scale):
    qn_ref, k_ref, v_ref, qr_ref, kr_ref, refs = _split(refs, rope, packed)
    o_ref, lse_ref, do_ref = refs[:3]
    dqn_ref, dk_ref, dv_ref, dqr_ref, dkr_ref, _ = _split(
        refs[3:], rope, packed)
    stride = (nope + v_dim) if packed else 0
    dt = v_ref.dtype
    prec = _precision(dt)
    causal = _causal(qn_ref.shape[1])
    if rope:
        kr = kr_ref[0]
        dkr = jnp.zeros(kr.shape, jnp.float32)
    for h in range(group):
        key, value = (_head(h, nope, 0, stride),
                      _head(h, v_dim, nope if packed else 0, stride))
        q_nope, k_nope = qn_ref[_head(h, nope)], k_ref[key]
        q_rope = qr_ref[_head(h, rope)] if rope else None
        s = _scores(q_nope, k_nope, q_rope, kr if rope else None, scale,
                    causal, prec)
        p = jnp.exp(s - lse_ref[0, 0, :, h:h + 1])
        do = do_ref[_head(h, v_dim)]
        dv_ref[value] = _dot(p.astype(dt), do, (0, 0),
                             prec).astype(dv_ref.dtype)
        dp = _dot(do, v_ref[value], (1, 1), prec)
        delta = jnp.sum(do.astype(jnp.float32)
                        * o_ref[_head(h, v_dim)].astype(jnp.float32),
                        axis=-1, keepdims=True)
        ds = (p * (dp - delta) * scale).astype(dt)
        dqn_ref[_head(h, nope)] = _dot(ds, k_nope, (1, 0),
                                       prec).astype(dqn_ref.dtype)
        dk_ref[key] = _dot(ds, q_nope, (0, 0), prec).astype(dk_ref.dtype)
        if rope:
            dqr_ref[_head(h, rope)] = _dot(ds, kr, (1, 0),
                                           prec).astype(dqr_ref.dtype)
            dkr = dkr + _dot(ds, q_rope, (0, 0), prec)
    if rope:
        @pl.when(pl.program_id(1) == 0)
        def _first_group():
            dkr_ref[0] = dkr

        @pl.when(pl.program_id(1) > 0)
        def _add():
            dkr_ref[0] = dkr_ref[0] + dkr


def _call(kernel, name, semantics, operands, results, *, heads, group,
          interpret):
    """``kernel`` over the grid (batch row, head group). ``operands`` and
    ``results`` are ``(array or ShapeDtypeStruct, kind)`` pairs: kind
    ``"heads"`` is ``[B, N, H * width]`` (a cell takes its group's lanes),
    ``"shared"`` ``[B, N, width]`` (every group of a row takes all of it),
    ``"lse"`` ``[B, H / group, N, group]``. A block's rows are the whole
    sequence, whatever its length (see the module docstring)."""
    b, rows = operands[0][0].shape[:2]

    def spec(x, kind):
        if kind == "lse":
            # a head's log-sum-exp is a column, as the row reductions leave
            # it; the block's last two dims are the array's own
            block, index = (1, 1, rows, group), lambda i, g: (i, g, 0, 0)
        elif kind == "shared":
            block, index = (1, rows, x.shape[-1]), lambda i, g: (i, 0, 0)
        else:
            block, index = ((1, rows, x.shape[-1] // heads * group),
                            lambda i, g: (i, 0, g))
        return pl.BlockSpec(block, index, memory_space=pltpu.VMEM)
    return pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)
                   for x, _ in results],
        grid=(b, heads // group),
        in_specs=[spec(*o) for o in operands],
        out_specs=[spec(*r) for r in results],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics,
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name=name,
    )(*[x for x, _ in operands])


def _pieces(q_nope, keys, q_rope, k_rope):
    """The kernels' leading operands, as ``_split`` reads them."""
    rotary = [] if q_rope is None else [(q_rope, "heads"),
                                        (k_rope, "shared")]
    return [(q_nope, "heads")] + [(k, "heads") for k in keys] + rotary


def _flat(t):
    """[B, N, H, D] -> [B, N, H * D]: heads side by side on the lanes, as
    a projection writes them."""
    return t.reshape(t.shape[0], t.shape[1], -1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _attention(q_nope, keys, q_rope, k_rope, scale, interpret):
    return _attention_fwd(q_nope, keys, q_rope, k_rope, scale, interpret)[0]


def _static(heads, q_nope, keys, q_rope):
    """The kernels' static widths from the operands, [B, N, H, D] or flat
    [B, N, H * D]: ``keys`` is ``(k_nope, v)`` or the packed ``(kv,)``."""
    nope = math.prod(q_nope.shape[2:]) // heads
    packed = len(keys) == 1
    return dict(nope=nope, packed=packed,
                rope=0 if q_rope is None
                else math.prod(q_rope.shape[2:]) // heads,
                v_dim=math.prod(keys[-1].shape[2:]) // heads
                - (nope if packed else 0))


def _attention_fwd(q_nope, keys, q_rope, k_rope, scale, interpret):
    b, n, heads, _ = q_nope.shape
    static = _static(heads, q_nope, keys, q_rope)
    group = head_group(n, heads, static["nope"], static["rope"],
                       static["v_dim"], q_nope.dtype.itemsize)
    flat = jax.tree_util.tree_map(_flat, (q_nope, keys, q_rope, k_rope))
    o, lse = _call(
        functools.partial(_fwd_kernel, group=group, scale=scale, **static),
        "causal_attention_fwd", ("parallel", "parallel"), _pieces(*flat),
        [(jax.ShapeDtypeStruct((b, n, heads * static["v_dim"]),
                               q_nope.dtype), "heads"),
         (jax.ShapeDtypeStruct((b, heads // group, n, group),
                               jnp.float32), "lse")],
        heads=heads, group=group, interpret=interpret)
    return o.reshape(b, n, heads, -1), (flat, o, lse)


def _attention_bwd(scale, interpret, residuals, do):
    flat, o, lse = residuals
    b, n, heads, _ = do.shape
    group = lse.shape[-1]
    pieces = _pieces(*flat)
    # every gradient in its operand's shape and dtype, but the shared
    # key's: float32, summed over a row's head groups (a sequential axis)
    results = [(jax.ShapeDtypeStruct(x.shape, jnp.float32
                                     if kind == "shared" else x.dtype), kind)
               for x, kind in pieces]
    grads = _call(
        functools.partial(_bwd_kernel, group=group, scale=scale,
                          **_static(heads, *flat[:3])),
        "causal_attention_bwd", ("parallel", "arbitrary"),
        pieces + [(o, "heads"), (lse, "lse"), (_flat(do), "heads")],
        results, heads=heads, group=group, interpret=interpret)
    grads = [g.astype(x.dtype).reshape(
        (b, n, heads, -1) if kind == "heads" else (b, n, -1))
        for g, (x, kind) in zip(grads, pieces)]
    keys = len(flat[1])
    return (grads[0], tuple(grads[1:1 + keys]),
            *(grads[1 + keys:] or (None, None)))


_attention.defvjp(_attention_fwd, _attention_bwd)


def causal_attention(q_nope, k_nope=None, v=None, q_rope=None, k_rope=None,
                     *, kv=None, scale: Optional[float] = None,
                     interpret: Optional[bool] = None):
    """Causal attention of ``q_nope``, ``k_nope`` [B, N, H, nope] and ``v``
    [B, N, H, v_dim], with the optional rotary pieces ``q_rope`` [B, N, H,
    rope] and the ONE shared ``k_rope`` [B, N, rope] (both or neither) ->
    [B, N, H, v_dim]. In place of ``k_nope`` and ``v``, ``kv`` [B, N, H,
    nope + v_dim] is a head's key beside its value, as latent attention's
    up-projection writes them: read in place (no copies to split it), and
    its gradient comes back as one array. ``scale`` defaults to ``1 /
    sqrt(nope + rope)``. Differentiable in every operand. The shapes have
    to be ones :func:`supports` takes."""
    if (q_rope is None) != (k_rope is None):
        raise ValueError("the rotary pieces come together: q_rope and k_rope")
    if (kv is None) == (k_nope is None) or (kv is None) != (v is not None):
        raise ValueError("keys and values: k_nope and v, or the packed kv")
    b, n, heads, nope = q_nope.shape
    keys = (k_nope, v) if kv is None else (kv,)
    static = _static(heads, q_nope, keys, q_rope)
    if not supports(n, heads, nope, static["rope"], static["v_dim"],
                    q_nope.dtype.itemsize):
        raise ValueError(
            f"causal_attention takes heads of whole lane tiles whose cell "
            f"fits VMEM; got N={n}, H={heads}, nope={nope}, "
            f"rope={static['rope']}, v_dim={static['v_dim']} (see "
            f"supports())")
    if scale is None:
        scale = 1.0 / np.sqrt(nope + static["rope"])
    if interpret is None:
        from tpuic.kernels import default_interpret
        interpret = default_interpret()
    return _attention(q_nope, keys, q_rope, k_rope, float(scale),
                      bool(interpret))
