"""Flash attention (blockwise online-softmax) as Pallas TPU kernels.

The reference has no attention op at all (SURVEY.md §2c: vision CNNs only);
attention enters this framework through the ViT backbone (BASELINE.md config
4), the sequence-parallel path (tpuic/parallel/ring_attention.py) and the
decoder stacks that serve as backbones (models/mellum.py). The kernels are
the per-device block primitive: the forward never materializes the [N, N]
probability matrix in HBM — only [block_q, block_k] tiles in VMEM — and
contractions are MXU-shaped with a float32 online softmax carried across
key blocks.

Backward is blockwise Pallas too (jax.custom_vjp): the forward saves only
(q, k, v, o, logsumexp) — no probability matrix — and two backward kernels
rebuild [block_q, block_k] probability tiles in VMEM from the saved
logsumexp: a dq kernel gridded over query blocks and a dk/dv kernel gridded
over key blocks, both using the standard FlashAttention identity
ds = p * (dp - rowsum(do·o)). Peak HBM stays O(N·D) end to end.

``flash_attention(q, k, v, ..., causal=False, window=None)`` is the one
entry; three variants stand behind it, chosen by what the call asks for:

- **folded** ([B, N, H, D] -> [B*H, N, D]) and **lane-packed** (two 64-wide
  heads a 128-lane block of the natural [B, N, H*D] layout): bidirectional
  attention, every head its own key and value, the whole square of tiles
  visited and only the keys past the sequence masked. A ViT's, and the
  ring / ulysses compositions' per-step calls. The defaults take this
  path, as before the mask existed, to the byte of the lowered program.
- **banded**: a causal mask (``causal``), a sliding window (``window``:
  query i sees keys i - window < t <= i; implies ``causal``) and grouped
  key-value heads (``k``, ``v`` of Hkv heads, H % Hkv == 0, query head h
  reading h // (H // Hkv)). Its grids hold only the (query block, key
  block) tiles with an unmasked pair: the list of tiles is built on the
  host and rides in as scalar-prefetch operands that the block index maps
  read, so a tile above the diagonal or beyond the window costs neither a
  grid step nor a DMA (``blocks_visited``: a causal square visits 36 of 64
  tiles of 512 at N = 4096, a window of 1,024 visits 21); the diagonal and
  the window's far edge are masked by position inside the tile, interior
  tiles skip the mask. The dk/dv kernel sums over the group's query heads
  in its scratch. I/O stays in the model's [B, N, H*D] layout, a head one
  D-wide column block: on the chip D is a multiple of 128. The three calls
  are named ``banded_attention_fwd``, ``banded_attention_dq`` and
  ``banded_attention_dkv`` in a device trace.

Sharding: a Pallas call is an opaque custom call — GSPMD/Shardy cannot
partition it and would all-gather batch-sharded operands onto every device.
Pass ``mesh`` (with a ``data`` axis) and the wrapper runs the kernel inside
``jax.shard_map`` over the batch axis, keeping the computation fully
batch-parallel; attention itself is per-sample so no collectives are needed.

Layout: [B, N, H, D] ("bqhd", matching models/vit.py einsums). N is padded to
the block size (padded keys masked, or after every real query under a causal
mask), so callers can pass any length (ViT's 197 tokens included).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, PartitionSpec as P

_NEG_INF = -1e30


# Lane width of the (block_q, LANES) f32 scratch that carries the online
# softmax m/l rows across key-block grid steps (TPU vregs are 128 lanes; a
# [bq, 1] scratch would not tile).
_LANES = 128

# Grid semantics for every kernel here: (batch*head, outer-block) are
# embarrassingly parallel; the innermost axis is the sequential reduction
# that the VMEM scratch accumulates across.
_DIM_SEMANTICS = ("parallel", "parallel", "arbitrary")


def _compiler_params(semantics=_DIM_SEMANTICS):
    return pltpu.CompilerParams(dimension_semantics=semantics)


# Packed grids are (batch, head-pair, own-block, reduction).
def _compiler_params_4d():
    return _compiler_params(("parallel", "parallel", "parallel",
                             "arbitrary"))


def _dot_precision(dtype) -> jax.lax.Precision:
    """MXU precision for kernel contractions, by operand dtype.

    bf16 operands are a native single MXU pass — leave the default. f32
    operands MUST be HIGHEST: the default lowers f32 matmuls to ONE lossy
    bf16 pass (measured 5e-3 max error on chip, round-3 smoke), which
    would silently degrade f32 attention."""
    return (jax.lax.Precision.HIGHEST if dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)


def _f32_for(ref_dtype, x):
    """Softmax-side f32 view of a probability tile, cast back to the
    operand dtype only when the MXU pass is narrow anyway."""
    return x.astype(ref_dtype) if ref_dtype != jnp.float32 else x


def _masked(s, kpos, vl, keep):
    """The score tile with what may not be seen at ``_NEG_INF``: the keys
    at or past ``vl`` (the bidirectional kernels' only mask), or whatever
    ``keep`` [bq, bk] leaves out (the banded kernels': a causal diagonal, a
    window's far edge); ``kpos`` None and no ``keep`` is a tile that lies
    whole inside its band."""
    if keep is not None:
        return jnp.where(keep, s, _NEG_INF)
    return s if kpos is None else jnp.where(kpos < vl, s, _NEG_INF)


def _fwd_tile(q_t, k_t, v_t, kpos, vl, m, l, acc, *, scale, prec, dt,
              keep=None):
    """One (q-tile, k-tile) online-softmax update — the single copy of the
    forward tile math shared by the folded, lane-packed and banded kernels.
    Returns (m_new, l_new, acc_new)."""
    s = jax.lax.dot_general(q_t, k_t, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32,
                            precision=prec) * scale          # [bq, bk]
    s = _masked(s, kpos, vl, keep)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m - m_new)
    l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc * alpha + jnp.dot(_f32_for(dt, p), v_t,
                                    preferred_element_type=jnp.float32,
                                    precision=prec)
    return m_new, l_new, acc_new


def _finish_tile(m, l, acc, masked_sentinel):
    """(o_tile_f32, lse_row) from the final online-softmax state; fully
    masked rows get ``masked_sentinel`` (see _fwd_kernel docstring)."""
    o = acc / jnp.maximum(l, 1e-30)
    lse = jnp.where(m[:, 0] > _NEG_INF / 2,
                    m[:, 0] + jnp.log(jnp.maximum(l[:, 0], 1e-30)),
                    masked_sentinel)
    return o, lse


def _bwd_dq_tile(q_t, k_t, v_t, do_t, lse, delta, kpos, vl, *, scale, prec,
                 dt, keep=None):
    """dq increment for one (q-tile, k-tile): ds @ k (the caller applies
    the final ``scale``). Shared by the folded, packed and banded dq
    kernels."""
    s = scale * jax.lax.dot_general(q_t, k_t, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32,
                                    precision=prec)
    s = _masked(s, kpos, vl, keep)
    p = jnp.exp(s - lse)
    dp = jax.lax.dot_general(do_t, v_t, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32,
                             precision=prec)
    ds = p * (dp - delta)
    return jnp.dot(_f32_for(dt, ds), k_t, preferred_element_type=jnp.float32,
                   precision=prec)


def _bwd_dkv_tile(q_t, k_t, v_t, do_t, lse, delta, kpos, vl, *, scale, prec,
                  dt, keep=None):
    """(dk_increment_unscaled, dv_increment) for one (k-tile, q-tile) —
    the caller applies ``scale`` to dk. Shared by the folded, packed and
    banded dk/dv kernels."""
    s = scale * jax.lax.dot_general(q_t, k_t, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32,
                                    precision=prec)
    s = _masked(s, kpos, vl, keep)
    p = jnp.exp(s - lse)
    dv_inc = jax.lax.dot_general(_f32_for(dt, p), do_t,
                                 (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32,
                                 precision=prec)
    dp = jax.lax.dot_general(do_t, v_t, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32,
                             precision=prec)
    ds = p * (dp - delta)
    dk_inc = jax.lax.dot_general(_f32_for(dt, ds), q_t,
                                 (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32,
                                 precision=prec)
    return dk_inc, dv_inc


def _fwd_kernel(q_ref, k_ref, v_ref, valid_ref, o_ref, lse_ref, m_s, l_s,
                acc_s, *, block_k: int, scale: float, valid_len: int,
                n_k_blocks: int, masked_sentinel: float):
    """One (batch*head, q-block, k-block) program.

    The grid's innermost axis walks key blocks sequentially; (m, l, acc)
    live in VMEM scratch across those steps, so per-program VMEM is
    O(block_q·D + block_k·D) no matter how long the sequence is.

    ``valid_ref`` (SMEM scalar, optional) overrides the static
    ``valid_len`` — the ring-attention composition rotates key blocks, so
    the number of real keys in THIS call is only known at trace time.
    ``masked_sentinel`` is the lse written for fully-masked query rows:
    0.0 for the single-call path (padded q rows; keeps the backward's
    exp(s - lse) finite under zero cotangents) and -1e30 for the ring
    path, where a fully-padded key block's lse must weigh ZERO in the
    cross-block logsumexp combination.
    """
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, _NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    dt = q_ref.dtype
    prec = _dot_precision(dt)
    bq = q_ref.shape[1]
    kpos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (bq, block_k), 1)
    vl = valid_len if valid_ref is None else valid_ref[0]
    m_new, l_new, acc_new = _fwd_tile(
        q_ref[0], k_ref[0], v_ref[0], kpos, vl,
        m_s[:, :1], l_s[:, :1], acc_s[...], scale=scale, prec=prec, dt=dt)
    acc_s[...] = acc_new
    m_s[...] = jnp.broadcast_to(m_new, m_s.shape)
    l_s[...] = jnp.broadcast_to(l_new, l_s.shape)

    @pl.when(ki == n_k_blocks - 1)
    def _finish():
        o, lse = _finish_tile(m_s[:, :1], l_s[:, :1], acc_s[...],
                              masked_sentinel)
        o_ref[0] = o.astype(o_ref.dtype)
        if lse_ref is not None:
            # logsumexp per query row, the only softmax residual the backward
            # needs. lse blocks are [1, 1, block_q]: row vectors must
            # keep a unit second-minor dim — Mosaic requires the last two
            # block dims to be (mult of 8, mult of 128) OR equal to the
            # array dims, which a [1, block_q] block of a 2D array violates
            # (surfaced on real TPU, round-3 smoke; interpret mode did
            # not enforce it).
            lse_ref[0, 0] = lse


def _pad_seq(t: jnp.ndarray, to: int) -> jnp.ndarray:
    pad = to - t.shape[1]
    if pad == 0:
        return t
    return jnp.pad(t, ((0, 0), (0, pad), (0, 0)))


def _fold(t, b, h, n, d, n_padded):  # [B,N,H,D] -> [B*H, N_padded, D]
    t = jnp.transpose(t, (0, 2, 1, 3)).reshape(b * h, n, d)
    return _pad_seq(t, n_padded)


def _unfold(t, b, h, n, d):  # [B*H, N_padded, D] -> [B,N,H,D]
    t = t[:, :n].reshape(b, h, n, d)
    return jnp.transpose(t, (0, 2, 1, 3))


def _padded_len(n: int, block_q: int, block_k: int) -> int:
    return max(-(-n // block_q) * block_q, -(-n // block_k) * block_k)


def _resolve_blocks(n: int, block_q, block_k):
    """Fill ``None`` block sizes from the sequence length.

    Heuristic: among square block sizes {128, 256, 512}, take the
    LARGEST whose padded length stays within 10% of the best achievable —
    padding is pure waste (masked FLOPs + HBM on every padded key), but
    per-program grid overhead is why the old fixed 128x128 default was
    ~2x slower than dense at N=2048 (16x16 inner programs per batch*head;
    builder, <= 2026-08-01, other code) — so small padding buys big blocks,
    large padding never does. Examples: 197 -> 256 (one k pass), 577 -> 128
    (padded 640; larger blocks pad >= 768), 1025 -> 128 (1152),
    2048 -> 512, 2305 -> 512 (2560, 5% over the 128-block 2432 but 16x
    fewer programs). VMEM at 512x512 blocks: ~1 MB f32 score tile, 128 KB
    per f32 operand tile (512x64), two (512,128) f32 m/l scratches at
    256 KB each — comfortably inside v5e VMEM.

    Powers of two ONLY: 384 was in the palette until the one chip hang
    ever observed hit exactly the one config that auto-picked 384x384
    blocks (N=1025; builder, <= 2026-08-01, other code — 128/256/512
    configs all ran, the 384 child hung 900s).
    Non-power-of-two Mosaic tilings are the suspect; the palette sticks
    to {128, 256, 512} — worst case vs 384 is bounded by the same 10%
    padding rule.
    """
    if block_q is None or block_k is None:
        sizes = (128, 256, 512)
        best = min(-(-n // b) * b for b in sizes)
        auto = max(b for b in sizes if -(-n // b) * b <= 1.1 * best)
        block_q = auto if block_q is None else block_q
        block_k = auto if block_k is None else block_k
    return block_q, block_k


@functools.partial(jax.jit, static_argnames=("block_q", "block_k",
                                             "interpret", "with_lse",
                                             "masked_sentinel",
                                             "static_valid"))
def _flash_fwd(q, k, v, block_q: int, block_k: int, interpret: bool,
               with_lse: bool = False, valid=None,
               masked_sentinel: float = 0.0, static_valid=None):
    """q,k,v: [B, N, H, D] -> out [B, N, H, D] (and logsumexp [B*H, N_padded]
    when with_lse — the backward residual). Single-device (or per-shard).

    ``valid``: optional [1] int32 device scalar overriding the static key
    validity count (the ring composition's rotating block ownership).
    ``static_valid``: compile-time override for callers whose inputs carry
    MORE padding than the block rounding (ulysses pads tokens to the seq
    axis before the kernel sees them)."""
    b, n, h, d = q.shape
    valid_len = n if static_valid is None else static_valid
    scale = 1.0 / (d ** 0.5)
    n_padded = _padded_len(n, block_q, block_k)

    qf = _fold(q, b, h, n, d, n_padded)
    kf = _fold(k, b, h, n, d, n_padded)
    vf = _fold(v, b, h, n, d, n_padded)
    n_k_blocks = n_padded // block_k
    grid = (b * h, n_padded // block_q, n_k_blocks)
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda i, j, ki: (i, j, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_k, d), lambda i, j, ki: (i, ki, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_k, d), lambda i, j, ki: (i, ki, 0),
                     memory_space=pltpu.VMEM),
    ]
    operands = [qf, kf, vf]
    if valid is not None:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        operands.append(valid.astype(jnp.int32))
    out_shape = [jax.ShapeDtypeStruct((b * h, n_padded, d), q.dtype)]
    # The o/lse blocks revisit the same tile across the (sequential)
    # innermost k axis; writes land on the final k step.
    out_specs = [pl.BlockSpec((1, block_q, d), lambda i, j, ki: (i, j, 0),
                              memory_space=pltpu.VMEM)]
    if with_lse:
        # [B*H, 1, N_padded]: the unit middle dim makes the block's last two
        # dims (1, block_q) = (full array dim, lane multiple) — TPU-legal.
        out_shape.append(jax.ShapeDtypeStruct((b * h, 1, n_padded),
                                              jnp.float32))
        out_specs.append(pl.BlockSpec((1, 1, block_q),
                                      lambda i, j, ki: (i, 0, j),
                                      memory_space=pltpu.VMEM))

    def kernel(q_ref, k_ref, v_ref, *rest):
        valid_ref, rest = ((rest[0], rest[1:]) if valid is not None
                           else (None, rest))
        o_ref = rest[0]
        lse_ref = rest[1] if with_lse else None
        scratch = rest[2:] if with_lse else rest[1:]
        _fwd_kernel(q_ref, k_ref, v_ref, valid_ref, o_ref, lse_ref, *scratch,
                    block_k=block_k, scale=scale, valid_len=valid_len,
                    n_k_blocks=n_k_blocks, masked_sentinel=masked_sentinel)

    res = pl.pallas_call(
        kernel,
        out_shape=out_shape,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((block_q, _LANES), jnp.float32),
                        pltpu.VMEM((block_q, _LANES), jnp.float32),
                        pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=4 * b * h * n_padded * n_padded * d,
            bytes_accessed=3 * b * h * n_padded * d * q.dtype.itemsize,
            transcendentals=b * h * n_padded * n_padded),
    )(*operands)
    out = _unfold(res[0], b, h, n, d)
    if with_lse:
        return out, res[1]
    return out


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, valid_ref,
                   dq_ref, acc_s, *, block_k: int, scale: float,
                   valid_len: int, n_k_blocks: int):
    """One (bh, q-block, k-block) program: dq = scale * Σ_j ds_j @ k_j,
    accumulated in VMEM scratch across the sequential k axis."""
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_s[...] = jnp.zeros_like(acc_s)

    dt = q_ref.dtype
    prec = _dot_precision(dt)
    bq = q_ref.shape[1]
    kpos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (bq, block_k), 1)
    vl = valid_len if valid_ref is None else valid_ref[0]
    acc_s[...] += _bwd_dq_tile(
        q_ref[0], k_ref[0], v_ref[0], do_ref[0],
        lse_ref[0, 0][:, None], delta_ref[0, 0][:, None], kpos, vl,
        scale=scale, prec=prec, dt=dt)

    @pl.when(ki == n_k_blocks - 1)
    def _finish():
        dq_ref[0] = (scale * acc_s[...]).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    valid_ref, dk_ref, dv_ref, dk_s, dv_s, *, block_q: int,
                    scale: float, valid_len: int, n_q_blocks: int):
    """One (bh, k-block, q-block) program: dk/dv accumulated in VMEM scratch
    across the sequential q axis."""
    qi_idx = pl.program_id(2)

    @pl.when(qi_idx == 0)
    def _init():
        dk_s[...] = jnp.zeros_like(dk_s)
        dv_s[...] = jnp.zeros_like(dv_s)

    dt = q_ref.dtype
    prec = _dot_precision(dt)
    bk = k_ref.shape[1]
    j = pl.program_id(1)
    kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)  # [1, bk]
    vl = valid_len if valid_ref is None else valid_ref[0]
    dk_inc, dv_inc = _bwd_dkv_tile(
        q_ref[0], k_ref[0], v_ref[0], do_ref[0],
        lse_ref[0, 0][:, None], delta_ref[0, 0][:, None], kpos, vl,
        scale=scale, prec=prec, dt=dt)
    dv_s[...] += dv_inc
    dk_s[...] += scale * dk_inc

    @pl.when(qi_idx == n_q_blocks - 1)
    def _finish():
        dk_ref[0] = dk_s[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_s[...].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_q", "block_k",
                                             "interpret", "static_valid"))
def _flash_bwd(q, k, v, o, lse, do, block_q: int, block_k: int,
               interpret: bool, valid=None, static_valid=None):
    """Blockwise backward: (dq, dk, dv), each [B, N, H, D]. lse is the folded
    [B*H, 1, N_padded] logsumexp saved by the forward. ``valid`` /
    ``static_valid`` as in :func:`_flash_fwd`."""
    b, n, h, d = q.shape
    valid_len = n if static_valid is None else static_valid
    scale = 1.0 / (d ** 0.5)
    n_padded = _padded_len(n, block_q, block_k)

    qf, kf, vf, of, dof = (_fold(t, b, h, n, d, n_padded)
                           for t in (q, k, v, o, do))
    # delta_i = rowsum(do_i * o_i): the softmax-jacobian correction term.
    # Kept [B*H, 1, N_padded] like lse (see the TPU block-shape note in
    # _fwd_kernel).
    delta = jnp.sum(dof.astype(jnp.float32) * of.astype(jnp.float32),
                    axis=-1)[:, None, :]
    n_q_blocks = n_padded // block_q
    n_k_blocks = n_padded // block_k

    # Index maps: axis 1 is the block this program OWNS (q-block for dq,
    # k-block for dk/dv); axis 2 is the sequential reduction axis.
    own = lambda bsz: pl.BlockSpec((1, bsz, d), lambda i, j, r: (i, j, 0),
                                   memory_space=pltpu.VMEM)
    red = lambda bsz: pl.BlockSpec((1, bsz, d), lambda i, j, r: (i, r, 0),
                                   memory_space=pltpu.VMEM)
    row_own = lambda bsz: pl.BlockSpec((1, 1, bsz),
                                       lambda i, j, r: (i, 0, j),
                                       memory_space=pltpu.VMEM)
    row_red = lambda bsz: pl.BlockSpec((1, 1, bsz),
                                       lambda i, j, r: (i, 0, r),
                                       memory_space=pltpu.VMEM)
    operands = [qf, kf, vf, dof, lse, delta]
    extra_specs = []
    if valid is not None:
        operands.append(valid.astype(jnp.int32))
        extra_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))

    def _dq_kernel(*refs):
        if valid is not None:
            *ins, valid_ref, dq_ref, acc_s = refs
        else:
            *ins, dq_ref, acc_s = refs
            valid_ref = None
        _bwd_dq_kernel(*ins, valid_ref, dq_ref, acc_s, block_k=block_k,
                       scale=scale, valid_len=valid_len,
                       n_k_blocks=n_k_blocks)

    dq = pl.pallas_call(
        _dq_kernel,
        out_shape=jax.ShapeDtypeStruct((b * h, n_padded, d), q.dtype),
        grid=(b * h, n_q_blocks, n_k_blocks),
        in_specs=[own(block_q), red(block_k), red(block_k), own(block_q),
                  row_own(block_q), row_own(block_q)] + extra_specs,
        out_specs=own(block_q),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=5 * b * h * n_padded * n_padded * d,
            bytes_accessed=4 * b * h * n_padded * d * q.dtype.itemsize,
            transcendentals=b * h * n_padded * n_padded),
    )(*operands)

    def _dkv_kernel(*refs):
        if valid is not None:
            *ins, valid_ref, dk_ref, dv_ref, dk_s, dv_s = refs
        else:
            *ins, dk_ref, dv_ref, dk_s, dv_s = refs
            valid_ref = None
        _bwd_dkv_kernel(*ins, valid_ref, dk_ref, dv_ref, dk_s, dv_s,
                        block_q=block_q, scale=scale, valid_len=valid_len,
                        n_q_blocks=n_q_blocks)

    dk, dv = pl.pallas_call(
        _dkv_kernel,
        out_shape=[jax.ShapeDtypeStruct((b * h, n_padded, d), k.dtype),
                   jax.ShapeDtypeStruct((b * h, n_padded, d), v.dtype)],
        grid=(b * h, n_k_blocks, n_q_blocks),
        in_specs=[red(block_q), own(block_k), own(block_k), red(block_q),
                  row_red(block_q), row_red(block_q)] + extra_specs,
        out_specs=[own(block_k), own(block_k)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=5 * b * h * n_padded * n_padded * d,
            bytes_accessed=4 * b * h * n_padded * d * q.dtype.itemsize,
            transcendentals=b * h * n_padded * n_padded),
    )(*operands)

    return (_unfold(dq, b, h, n, d), _unfold(dk, b, h, n, d),
            _unfold(dv, b, h, n, d))


# -- lane-packed variant ----------------------------------------------------
#
# The folded layout above reshapes [B, N, H, 64] to [B*H, N, 64]: a minor
# dim of 64 under the TPU's (8, 128) tiled layout pads every lane row to
# 128, so each q/k/v/o HBM array allocates 2x its bytes (seen directly in
# the N=4097 OOM dump, PERF_ANALYSIS.md §10f), and the fold itself is a
# transpose copy. The packed variant keeps kernel I/O in the model's
# NATURAL [B, N, H*64] layout — [B, N, H, D] -> [B, N, H*D] is a free
# contiguous reshape, the minor dim is 128-aligned (no tiling waste, no
# transpose), and the grid gains a head-pair axis: each program loads one
# 128-lane block holding TWO heads and runs both 64-wide online softmaxes.
# lse/delta keep the legacy [B*H, 1, N_padded] layout via leading-dim-2
# blocks (the (8,128) rule constrains only the last two block dims), so
# residual formats are identical across variants. Dispatched automatically
# for head_dim 64 + even head count (the whole ViT zoo except vit-tiny)
# from BOTH the public flash_attention custom-vjp and the ring
# composition's per-step calls (tpuic/parallel/ring_attention.py — the
# identical lse format is what makes its cross-block combination
# layout-agnostic); TPUIC_FLASH_PACKED=0 disables everywhere.


def _use_packed(h: int, d: int) -> bool:
    import os
    if os.environ.get("TPUIC_FLASH_PACKED", "1") == "0":
        return False
    return d == 64 and h % 2 == 0


def _select_kernels(h: int, d: int):
    """(fwd, bwd) implementation pair for these head dims — the ONE place
    the packed-vs-folded choice is made (public custom-vjp fwd/bwd and
    both ring_attention impls all call this; fwd and bwd must never come
    from different variants: their lse padding/layout contract is shared
    but their dispatch predicate must match)."""
    if _use_packed(h, d):
        return _flash_fwd_packed, _flash_bwd_packed
    return _flash_fwd, _flash_bwd


def _fwd_kernel_packed(q_ref, k_ref, v_ref, valid_ref, o_ref, lse_ref,
                       m0_s, l0_s, m1_s, l1_s, acc0_s, acc1_s, *,
                       block_k: int, d: int, scale: float, valid_len: int,
                       n_k_blocks: int, masked_sentinel: float):
    """One (batch, head-pair, q-block, k-block) program: two 64-wide heads
    share the 128-lane operand block; each keeps its own online-softmax
    state. Math per head is identical to :func:`_fwd_kernel`."""
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        for m_s, l_s, acc_s in ((m0_s, l0_s, acc0_s), (m1_s, l1_s, acc1_s)):
            m_s[...] = jnp.full_like(m_s, _NEG_INF)
            l_s[...] = jnp.zeros_like(l_s)
            acc_s[...] = jnp.zeros_like(acc_s)

    dt = q_ref.dtype
    prec = _dot_precision(dt)
    q2, k2, v2 = q_ref[0], k_ref[0], v_ref[0]     # [bq|bk, 2d]
    bq = q2.shape[0]
    kpos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (bq, block_k), 1)
    vl = valid_len if valid_ref is None else valid_ref[0]

    for h_i, (m_s, l_s, acc_s) in enumerate(((m0_s, l0_s, acc0_s),
                                             (m1_s, l1_s, acc1_s))):
        lo = h_i * d
        m_new, l_new, acc_new = _fwd_tile(
            q2[:, lo:lo + d], k2[:, lo:lo + d], v2[:, lo:lo + d], kpos, vl,
            m_s[:, :1], l_s[:, :1], acc_s[...], scale=scale, prec=prec,
            dt=dt)
        acc_s[...] = acc_new
        m_s[...] = jnp.broadcast_to(m_new, m_s.shape)
        l_s[...] = jnp.broadcast_to(l_new, l_s.shape)

    @pl.when(ki == n_k_blocks - 1)
    def _finish():
        halves = []
        for h_i, (m_s, l_s, acc_s) in enumerate(((m0_s, l0_s, acc0_s),
                                                 (m1_s, l1_s, acc1_s))):
            o, lse = _finish_tile(m_s[:, :1], l_s[:, :1], acc_s[...],
                                  masked_sentinel)
            halves.append(o)
            if lse_ref is not None:
                lse_ref[h_i, 0] = lse
        o_ref[0] = jnp.concatenate(halves, axis=-1).astype(o_ref.dtype)


def _pack(t, b, n, h, d, n_padded):  # [B,N,H,D] -> [B, N_padded, H*D]
    return _pad_seq(t.reshape(b, n, h * d), n_padded)


def _unpack(t, b, h, n, d):  # [B, N_padded, H*D] -> [B,N,H,D]
    return t[:, :n].reshape(b, n, h, d)


@functools.partial(jax.jit, static_argnames=("block_q", "block_k",
                                             "interpret", "with_lse",
                                             "masked_sentinel",
                                             "static_valid"))
def _flash_fwd_packed(q, k, v, block_q: int, block_k: int, interpret: bool,
                      with_lse: bool = False, valid=None,
                      masked_sentinel: float = 0.0, static_valid=None):
    """Packed-layout forward: same contract as :func:`_flash_fwd` (lse, when
    requested, in the identical [B*H, 1, N_padded] layout)."""
    b, n, h, d = q.shape
    hp = h // 2
    valid_len = n if static_valid is None else static_valid
    scale = 1.0 / (d ** 0.5)
    n_padded = _padded_len(n, block_q, block_k)

    qp = _pack(q, b, n, h, d, n_padded)
    kp = _pack(k, b, n, h, d, n_padded)
    vp = _pack(v, b, n, h, d, n_padded)
    n_k_blocks = n_padded // block_k
    grid = (b, hp, n_padded // block_q, n_k_blocks)
    pair = lambda bsz, row: pl.BlockSpec(
        (1, bsz, 2 * d), lambda bi, hi, j, ki, _r=row: (bi, (j, ki)[_r], hi),
        memory_space=pltpu.VMEM)
    in_specs = [pair(block_q, 0), pair(block_k, 1), pair(block_k, 1)]
    operands = [qp, kp, vp]
    if valid is not None:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        operands.append(valid.astype(jnp.int32))
    out_shape = [jax.ShapeDtypeStruct((b, n_padded, h * d), q.dtype)]
    out_specs = [pair(block_q, 0)]
    if with_lse:
        # Legacy lse layout; this program owns rows (b*h + 2*hi, +1) of
        # dim 0 — a leading block dim of 2, index b*hp + hi in block
        # units. Last two block dims stay (1, block_q): TPU-legal.
        out_shape.append(jax.ShapeDtypeStruct((b * h, 1, n_padded),
                                              jnp.float32))
        out_specs.append(pl.BlockSpec((2, 1, block_q),
                                      lambda bi, hi, j, ki: (bi * hp + hi,
                                                             0, j),
                                      memory_space=pltpu.VMEM))

    def kernel(q_ref, k_ref, v_ref, *rest):
        valid_ref, rest = ((rest[0], rest[1:]) if valid is not None
                           else (None, rest))
        o_ref = rest[0]
        lse_ref = rest[1] if with_lse else None
        scratch = rest[2:] if with_lse else rest[1:]
        _fwd_kernel_packed(q_ref, k_ref, v_ref, valid_ref, o_ref, lse_ref,
                           *scratch, block_k=block_k, d=d, scale=scale,
                           valid_len=valid_len, n_k_blocks=n_k_blocks,
                           masked_sentinel=masked_sentinel)

    res = pl.pallas_call(
        kernel,
        out_shape=out_shape,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((block_q, _LANES), jnp.float32),
                        pltpu.VMEM((block_q, _LANES), jnp.float32),
                        pltpu.VMEM((block_q, _LANES), jnp.float32),
                        pltpu.VMEM((block_q, _LANES), jnp.float32),
                        pltpu.VMEM((block_q, d), jnp.float32),
                        pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=_compiler_params_4d(),
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=4 * b * h * n_padded * n_padded * d,
            bytes_accessed=3 * b * h * n_padded * d * q.dtype.itemsize,
            transcendentals=b * h * n_padded * n_padded),
    )(*operands)
    out = _unpack(res[0], b, h, n, d)
    if with_lse:
        return out, res[1]
    return out


def _bwd_dq_kernel_packed(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          valid_ref, dq_ref, acc0_s, acc1_s, *, block_k: int,
                          d: int, scale: float, valid_len: int,
                          n_k_blocks: int):
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc0_s[...] = jnp.zeros_like(acc0_s)
        acc1_s[...] = jnp.zeros_like(acc1_s)

    dt = q_ref.dtype
    prec = _dot_precision(dt)
    q2, k2, v2, do2 = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
    bq = q2.shape[0]
    kpos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (bq, block_k), 1)
    vl = valid_len if valid_ref is None else valid_ref[0]

    for h_i, acc_s in enumerate((acc0_s, acc1_s)):
        lo = h_i * d
        acc_s[...] += _bwd_dq_tile(
            q2[:, lo:lo + d], k2[:, lo:lo + d], v2[:, lo:lo + d],
            do2[:, lo:lo + d], lse_ref[h_i, 0][:, None],
            delta_ref[h_i, 0][:, None], kpos, vl, scale=scale, prec=prec,
            dt=dt)

    @pl.when(ki == n_k_blocks - 1)
    def _finish():
        dq_ref[0] = jnp.concatenate(
            [scale * acc0_s[...], scale * acc1_s[...]],
            axis=-1).astype(dq_ref.dtype)


def _bwd_dkv_kernel_packed(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                           valid_ref, dkv_ref, dk0_s, dv0_s, dk1_s, dv1_s,
                           *, block_q: int, d: int, scale: float,
                           valid_len: int, n_q_blocks: int):
    qi_idx = pl.program_id(3)

    @pl.when(qi_idx == 0)
    def _init():
        for s_ in (dk0_s, dv0_s, dk1_s, dv1_s):
            s_[...] = jnp.zeros_like(s_)

    dt = q_ref.dtype
    prec = _dot_precision(dt)
    q2, k2, v2, do2 = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
    bk = k2.shape[0]
    j = pl.program_id(2)
    kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
    vl = valid_len if valid_ref is None else valid_ref[0]

    for h_i, (dk_s, dv_s) in enumerate(((dk0_s, dv0_s), (dk1_s, dv1_s))):
        lo = h_i * d
        dk_inc, dv_inc = _bwd_dkv_tile(
            q2[:, lo:lo + d], k2[:, lo:lo + d], v2[:, lo:lo + d],
            do2[:, lo:lo + d], lse_ref[h_i, 0][:, None],
            delta_ref[h_i, 0][:, None], kpos, vl, scale=scale, prec=prec,
            dt=dt)
        dv_s[...] += dv_inc
        dk_s[...] += scale * dk_inc

    @pl.when(qi_idx == n_q_blocks - 1)
    def _finish():
        # dk and dv ride ONE [., bk, 4d] output (dk pair | dv pair):
        # separate outputs would be fine too, this just keeps the store
        # count down.
        dkv_ref[0] = jnp.concatenate(
            [dk0_s[...], dk1_s[...], dv0_s[...], dv1_s[...]],
            axis=-1).astype(dkv_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_q", "block_k",
                                             "interpret", "static_valid"))
def _flash_bwd_packed(q, k, v, o, lse, do, block_q: int, block_k: int,
                      interpret: bool, valid=None, static_valid=None):
    """Packed-layout backward: same contract as :func:`_flash_bwd`."""
    b, n, h, d = q.shape
    hp = h // 2
    valid_len = n if static_valid is None else static_valid
    scale = 1.0 / (d ** 0.5)
    n_padded = _padded_len(n, block_q, block_k)

    qp, kp, vp, dop = (_pack(t, b, n, h, d, n_padded)
                       for t in (q, k, v, do))
    # delta in the legacy [B*H, 1, N_padded] layout, computed from the
    # unfolded tensors (no folded copies).
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    delta = _pad_seq(jnp.transpose(delta, (0, 2, 1)).reshape(b * h, n, 1),
                     n_padded)[..., 0][:, None, :]
    n_q_blocks = n_padded // block_q
    n_k_blocks = n_padded // block_k

    pair = lambda bsz, row: pl.BlockSpec(
        (1, bsz, 2 * d), lambda bi, hi, j, r, _r=row: (bi, (j, r)[_r], hi),
        memory_space=pltpu.VMEM)
    lse_own = pl.BlockSpec((2, 1, block_q),
                           lambda bi, hi, j, r: (bi * hp + hi, 0, j),
                           memory_space=pltpu.VMEM)
    lse_red = pl.BlockSpec((2, 1, block_q),
                           lambda bi, hi, j, r: (bi * hp + hi, 0, r),
                           memory_space=pltpu.VMEM)
    operands = [qp, kp, vp, dop, lse, delta]
    extra_specs = []
    if valid is not None:
        operands.append(valid.astype(jnp.int32))
        extra_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))

    def _dq_kernel(*refs):
        if valid is not None:
            *ins, valid_ref, dq_ref, acc0, acc1 = refs
        else:
            *ins, dq_ref, acc0, acc1 = refs
            valid_ref = None
        _bwd_dq_kernel_packed(*ins, valid_ref, dq_ref, acc0, acc1,
                              block_k=block_k, d=d, scale=scale,
                              valid_len=valid_len, n_k_blocks=n_k_blocks)

    dq = pl.pallas_call(
        _dq_kernel,
        out_shape=jax.ShapeDtypeStruct((b, n_padded, h * d), q.dtype),
        grid=(b, hp, n_q_blocks, n_k_blocks),
        in_specs=[pair(block_q, 0), pair(block_k, 1), pair(block_k, 1),
                  pair(block_q, 0), lse_own, lse_own] + extra_specs,
        out_specs=pair(block_q, 0),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32),
                        pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=_compiler_params_4d(),
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=5 * b * h * n_padded * n_padded * d,
            bytes_accessed=4 * b * h * n_padded * d * q.dtype.itemsize,
            transcendentals=b * h * n_padded * n_padded),
    )(*operands)

    def _dkv_kernel(*refs):
        if valid is not None:
            *ins, valid_ref, dkv_ref, dk0, dv0, dk1, dv1 = refs
        else:
            *ins, dkv_ref, dk0, dv0, dk1, dv1 = refs
            valid_ref = None
        _bwd_dkv_kernel_packed(*ins, valid_ref, dkv_ref, dk0, dv0, dk1, dv1,
                               block_q=block_q, d=d, scale=scale,
                               valid_len=valid_len, n_q_blocks=n_q_blocks)

    dkv_spec = pl.BlockSpec((1, block_k, 4 * d),
                            lambda bi, hi, j, r: (bi, j, hi),
                            memory_space=pltpu.VMEM)
    # The single dkv output must not quantize EITHER gradient: use the
    # widest of the two operand dtypes and cast the halves back after the
    # unscramble (mixed dtypes are rare; same-dtype calls pay nothing).
    dkv_dtype = jnp.result_type(k.dtype, v.dtype)
    dkv = pl.pallas_call(
        _dkv_kernel,
        out_shape=jax.ShapeDtypeStruct((b, n_padded, 2 * h * d), dkv_dtype),
        grid=(b, hp, n_k_blocks, n_q_blocks),
        in_specs=[pair(block_q, 1), pair(block_k, 0), pair(block_k, 0),
                  pair(block_q, 1), lse_red, lse_red] + extra_specs,
        out_specs=dkv_spec,
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        compiler_params=_compiler_params_4d(),
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=5 * b * h * n_padded * n_padded * d,
            bytes_accessed=4 * b * h * n_padded * d * q.dtype.itemsize,
            transcendentals=b * h * n_padded * n_padded),
    )(*operands)
    # dkv: [B, N_padded, 2*H*D] laid out as per-pair [dk0|dk1|dv0|dv1].
    # Halves come back in their own operand dtypes (custom_vjp requires
    # cotangent dtype == primal dtype); dkv_dtype above guarantees the
    # cast never LOSES precision relative to the folded variant's
    # separate out_shapes.
    dkv = dkv[:, :n].reshape(b, n, hp, 4, d)
    dk = dkv[:, :, :, :2].reshape(b, n, h, d).astype(k.dtype)
    dv = dkv[:, :, :, 2:].reshape(b, n, h, d).astype(v.dtype)
    return _unpack(dq, b, h, n, d), dk, dv


# -- banded variant: causal mask, sliding window, grouped key-value heads ----
#
# A decoder stack's attention (models/mellum.py): query ``i`` sees key ``t``
# iff ``t <= i`` and, under a window ``W``, ``t > i - W``; ``Hkv`` key-value
# heads serve ``H`` query heads, head ``h`` reading ``h // (H // Hkv)``. Of
# the square of (query block, key block) tiles only those that hold an
# unmasked pair exist: :func:`_band` lists them on the host, the list rides
# into the kernel as scalar-prefetch operands, and the grid's reduction
# axis walks the LIST, not the square, so a tile above the diagonal or
# beyond the window costs neither a grid step nor a DMA. The block index
# maps read the list (``q_of[s]``, ``k_of[s]``); ``edge[s]`` says whether
# step ``s`` is the first or the last of its own block (initialise, write
# back) and whether the tile is cut by the mask or lies whole inside the
# band (the diagonal and the window's far edge are masked by position in
# the tile; an interior tile skips the mask's compares and selects).
#
# Kernel I/O stays in the model's layout, ``[B, N, H*D]`` (a free reshape):
# a head is one ``D``-wide column block, so on the chip ``D`` has to be a
# multiple of the 128 lanes (interpret mode takes any). The key-value
# block index map divides the head by the group; the dk/dv kernel's grid
# gains an innermost axis over the group's query heads and sums them in
# its scratch. lse/delta keep the ``[B*H, 1, N_padded]`` layout of the
# other variants.

_FIRST, _LAST, _CUT = 1, 2, 4


def _band(n_padded: int, block_q: int, block_k: int, causal: bool,
          window: Optional[int], valid_len: int, by_key: bool = False):
    """``(q_of, k_of, edge)`` int32 arrays, one entry a tile that holds an
    unmasked (query, key) pair: ordered by query block then key block, or
    (``by_key``) by key block then query block; ``edge`` flags the first
    and last tile of each own block and the tiles the mask cuts."""
    import numpy as np
    tiles = []
    for j in range(n_padded // block_q):
        lo_row, hi_row = j * block_q, (j + 1) * block_q - 1
        for ki in range(n_padded // block_k):
            lo_key = ki * block_k
            hi_key = min(lo_key + block_k, valid_len) - 1
            if hi_key < lo_key or (causal and lo_key > hi_row) or (
                    window is not None and hi_key <= lo_row - window):
                continue
            cut = (hi_key < lo_key + block_k - 1
                   or (causal and hi_key > lo_row)
                   or (window is not None and lo_key <= hi_row - window))
            tiles.append((j, ki, _CUT * cut))
    own = 1 if by_key else 0
    tiles.sort(key=lambda t: (t[own], t[1 - own]))
    edge = [cut | (_FIRST * (i == 0 or tiles[i - 1][own] != t[own]))
            | (_LAST * (i == len(tiles) - 1 or tiles[i + 1][own] != t[own]))
            for i, (*t, cut) in enumerate(tiles)]
    q_of, k_of = ([t[i] for t in tiles] for i in (0, 1))
    return tuple(np.asarray(a, np.int32) for a in (q_of, k_of, edge))


def blocks_visited(n: int, block_q: Optional[int] = None,
                   block_k: Optional[int] = None, causal: bool = False,
                   window: Optional[int] = None):
    """``(visited, square)``: the (query block, key block) tiles the banded
    forward grid is launched with for a sequence of ``n`` (a head of one
    batch row), and all the tiles of its square."""
    block_q, block_k = _resolve_blocks(n, block_q, block_k)
    n_padded = _padded_len(n, block_q, block_k)
    visited = len(_band(n_padded, block_q, block_k, _causal(causal, window),
                        window, n)[0])
    return visited, (n_padded // block_q) * (n_padded // block_k)


def _keep(j, ki, block_q: int, block_k: int, causal: bool,
          window: Optional[int], valid_len: int):
    """The pairs of tile (``j``, ``ki``) that may be seen, [bq, bk] bool,
    by position (under a causal mask a padded key lies after every real
    query and needs no compare of its own)."""
    qpos = j * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    kpos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    keep = kpos <= qpos if causal else kpos < valid_len
    if window is not None:
        keep = keep & (qpos - kpos < window)
    return keep


def _either(cut, body):
    """``body(masked)`` under the branch the tile takes."""
    pl.when(cut)(lambda: body(True))
    pl.when(jnp.logical_not(cut))(lambda: body(False))


def _banded_fwd_kernel(q_of, k_of, edge, q_ref, k_ref, v_ref, o_ref, lse_ref,
                       m_s, l_s, acc_s, *, scale: float, keep):
    s = pl.program_id(2)
    e = edge[s]

    @pl.when(e & _FIRST != 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, _NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    dt = q_ref.dtype

    def update(masked):
        m_new, l_new, acc_new = _fwd_tile(
            q_ref[0], k_ref[0], v_ref[0], None, None, m_s[:, :1], l_s[:, :1],
            acc_s[...], scale=scale, prec=_dot_precision(dt), dt=dt,
            keep=keep(q_of[s], k_of[s]) if masked else None)
        acc_s[...] = acc_new
        m_s[...] = jnp.broadcast_to(m_new, m_s.shape)
        l_s[...] = jnp.broadcast_to(l_new, l_s.shape)
    _either(e & _CUT != 0, update)

    @pl.when(e & _LAST != 0)
    def _finish():
        o, lse = _finish_tile(m_s[:, :1], l_s[:, :1], acc_s[...], 0.0)
        o_ref[0] = o.astype(o_ref.dtype)
        lse_ref[0, 0] = lse


def _banded_dq_kernel(q_of, k_of, edge, q_ref, k_ref, v_ref, do_ref, lse_ref,
                      delta_ref, dq_ref, acc_s, *, scale: float, keep):
    s = pl.program_id(2)
    e = edge[s]

    @pl.when(e & _FIRST != 0)
    def _init():
        acc_s[...] = jnp.zeros_like(acc_s)

    dt = q_ref.dtype

    def update(masked):
        acc_s[...] += _bwd_dq_tile(
            q_ref[0], k_ref[0], v_ref[0], do_ref[0], lse_ref[0, 0][:, None],
            delta_ref[0, 0][:, None], None, None, scale=scale,
            prec=_dot_precision(dt), dt=dt,
            keep=keep(q_of[s], k_of[s]) if masked else None)
    _either(e & _CUT != 0, update)

    @pl.when(e & _LAST != 0)
    def _finish():
        dq_ref[0] = (scale * acc_s[...]).astype(dq_ref.dtype)


def _banded_dkv_kernel(q_of, k_of, edge, q_ref, k_ref, v_ref, do_ref,
                       lse_ref, delta_ref, dk_ref, dv_ref, dk_s, dv_s, *,
                       scale: float, group: int, keep):
    """One (batch, key-value head, tile of a key block's list, query head
    of the group) program: dk and dv of the key block summed in scratch
    over its query blocks and over the group's query heads."""
    s, g = pl.program_id(2), pl.program_id(3)
    e = edge[s]

    @pl.when((e & _FIRST != 0) & (g == 0))
    def _init():
        dk_s[...] = jnp.zeros_like(dk_s)
        dv_s[...] = jnp.zeros_like(dv_s)

    dt = q_ref.dtype

    def update(masked):
        dk_inc, dv_inc = _bwd_dkv_tile(
            q_ref[0], k_ref[0], v_ref[0], do_ref[0], lse_ref[0, 0][:, None],
            delta_ref[0, 0][:, None], None, None, scale=scale,
            prec=_dot_precision(dt), dt=dt,
            keep=keep(q_of[s], k_of[s]) if masked else None)
        dv_s[...] += dv_inc
        dk_s[...] += scale * dk_inc
    _either(e & _CUT != 0, update)

    @pl.when((e & _LAST != 0) & (g == group - 1))
    def _finish():
        dk_ref[0] = dk_s[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_s[...].astype(dv_ref.dtype)


def _banded_setup(q, k, block_q, block_k, interpret, causal, window,
                  static_valid):
    b, n, h, d = q.shape
    hkv = k.shape[2]
    if h % hkv:
        raise ValueError(f"{h} query heads over {hkv} key-value heads: "
                         "each key-value head serves a whole group")
    if not interpret and d % _LANES:
        raise ValueError(
            f"the banded kernels read a head as a column block of the "
            f"[B, N, H*D] operand: on the chip D = {d} has to be a "
            f"multiple of {_LANES}")
    n_padded = _padded_len(n, block_q, block_k)
    valid_len = n if static_valid is None else static_valid
    keep = functools.partial(
        _keep, block_q=block_q, block_k=block_k, causal=causal,
        window=window, valid_len=valid_len)
    tiles = functools.partial(_band, n_padded, block_q, block_k, causal,
                              window, valid_len)
    return b, n, h, hkv, d, n_padded, keep, tiles


def _band_spec(rows, d, index):
    return pl.BlockSpec((1, rows, d), index, memory_space=pltpu.VMEM)


def _row_spec(rows, index):
    return pl.BlockSpec((1, 1, rows), index, memory_space=pltpu.VMEM)


@functools.partial(jax.jit, static_argnames=(
    "block_q", "block_k", "interpret", "causal", "window", "static_valid"))
def _banded_fwd(q, k, v, block_q: int, block_k: int, interpret: bool,
                causal: bool, window: Optional[int], static_valid=None):
    """q [B, N, H, D], k, v [B, N, Hkv, D] -> (out [B, N, H, D], logsumexp
    [B*H, 1, N_padded])."""
    b, n, h, hkv, d, n_padded, keep, tiles = _banded_setup(
        q, k, block_q, block_k, interpret, causal, window, static_valid)
    group = h // hkv
    q_of, k_of, edge = tiles()
    steps = len(edge)
    own = lambda bi, hi, s, q_of, k_of, edge: (bi, q_of[s], hi)
    red = lambda bi, hi, s, q_of, k_of, edge: (bi, k_of[s], hi // group)
    out, lse = pl.pallas_call(
        functools.partial(_banded_fwd_kernel, scale=1.0 / (d ** 0.5),
                          keep=keep),
        out_shape=[jax.ShapeDtypeStruct((b, n_padded, h * d), q.dtype),
                   jax.ShapeDtypeStruct((b * h, 1, n_padded), jnp.float32)],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(b, h, steps),
            in_specs=[_band_spec(block_q, d, own),
                      _band_spec(block_k, d, red),
                      _band_spec(block_k, d, red)],
            out_specs=[_band_spec(block_q, d, own),
                       _row_spec(block_q,
                                 lambda bi, hi, s, q_of, k_of, edge:
                                 (bi * h + hi, 0, q_of[s]))],
            scratch_shapes=[pltpu.VMEM((block_q, _LANES), jnp.float32),
                            pltpu.VMEM((block_q, _LANES), jnp.float32),
                            pltpu.VMEM((block_q, d), jnp.float32)]),
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="banded_attention_fwd",
        cost_estimate=pl.CostEstimate(
            flops=4 * b * h * steps * block_q * block_k * d,
            bytes_accessed=(2 * h + 2 * hkv) * b * n_padded * d
            * q.dtype.itemsize,
            transcendentals=b * h * steps * block_q * block_k),
    )(q_of, k_of, edge, _pack(q, b, n, h, d, n_padded),
      _pack(k, b, n, hkv, d, n_padded), _pack(v, b, n, hkv, d, n_padded))
    return _unpack(out, b, h, n, d), lse


@functools.partial(jax.jit, static_argnames=(
    "block_q", "block_k", "interpret", "causal", "window", "static_valid"))
def _banded_bwd(q, k, v, o, lse, do, block_q: int, block_k: int,
                interpret: bool, causal: bool, window: Optional[int],
                static_valid=None):
    """(dq [B, N, H, D], dk, dv [B, N, Hkv, D]) over the same band."""
    b, n, h, hkv, d, n_padded, keep, tiles = _banded_setup(
        q, k, block_q, block_k, interpret, causal, window, static_valid)
    group = h // hkv
    scale = 1.0 / (d ** 0.5)
    qp, dop = (_pack(t, b, n, h, d, n_padded) for t in (q, do))
    kp, vp = (_pack(t, b, n, hkv, d, n_padded) for t in (k, v))
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    delta = _pad_seq(jnp.transpose(delta, (0, 2, 1)).reshape(b * h, n, 1),
                     n_padded)[..., 0][:, None, :]
    flops = lambda steps: 5 * b * h * steps * block_q * block_k * d
    bytes_accessed = (3 * h + 3 * hkv) * b * n_padded * d * q.dtype.itemsize

    q_of, k_of, edge = tiles()
    own = lambda bi, hi, s, q_of, k_of, edge: (bi, q_of[s], hi)
    red = lambda bi, hi, s, q_of, k_of, edge: (bi, k_of[s], hi // group)
    row = lambda bi, hi, s, q_of, k_of, edge: (bi * h + hi, 0, q_of[s])
    dq = pl.pallas_call(
        functools.partial(_banded_dq_kernel, scale=scale, keep=keep),
        out_shape=jax.ShapeDtypeStruct((b, n_padded, h * d), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(b, h, len(edge)),
            in_specs=[_band_spec(block_q, d, own),
                      _band_spec(block_k, d, red),
                      _band_spec(block_k, d, red),
                      _band_spec(block_q, d, own),
                      _row_spec(block_q, row), _row_spec(block_q, row)],
            out_specs=_band_spec(block_q, d, own),
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)]),
        compiler_params=_compiler_params(),
        interpret=interpret,
        name="banded_attention_dq",
        cost_estimate=pl.CostEstimate(
            flops=flops(len(edge)), bytes_accessed=bytes_accessed,
            transcendentals=b * h * len(edge) * block_q * block_k),
    )(q_of, k_of, edge, qp, kp, vp, dop, lse, delta)

    # the same tiles by key block; the innermost axis walks the group's
    # query heads (the key and value blocks stay where they are meanwhile)
    q_of, k_of, edge = tiles(by_key=True)
    head = lambda bi, hi, s, g, q_of, k_of, edge: (
        bi, q_of[s], hi * group + g)
    kv = lambda bi, hi, s, g, q_of, k_of, edge: (bi, k_of[s], hi)
    row = lambda bi, hi, s, g, q_of, k_of, edge: (
        bi * h + hi * group + g, 0, q_of[s])
    dk, dv = pl.pallas_call(
        functools.partial(_banded_dkv_kernel, scale=scale, group=group,
                          keep=keep),
        out_shape=[jax.ShapeDtypeStruct((b, n_padded, hkv * d), k.dtype),
                   jax.ShapeDtypeStruct((b, n_padded, hkv * d), v.dtype)],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(b, hkv, len(edge), group),
            in_specs=[_band_spec(block_q, d, head),
                      _band_spec(block_k, d, kv), _band_spec(block_k, d, kv),
                      _band_spec(block_q, d, head),
                      _row_spec(block_q, row), _row_spec(block_q, row)],
            out_specs=[_band_spec(block_k, d, kv),
                       _band_spec(block_k, d, kv)],
            scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                            pltpu.VMEM((block_k, d), jnp.float32)]),
        compiler_params=_compiler_params(
            ("parallel", "parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="banded_attention_dkv",
        cost_estimate=pl.CostEstimate(
            flops=flops(len(edge)), bytes_accessed=bytes_accessed,
            transcendentals=b * h * len(edge) * block_q * block_k),
    )(q_of, k_of, edge, qp, kp, vp, dop, lse, delta)
    return (_unpack(dq, b, h, n, d), _unpack(dk, b, hkv, n, d),
            _unpack(dv, b, hkv, n, d))


def _causal(causal: bool, window) -> bool:
    """A window looks back: it implies the causal mask."""
    return bool(causal) or window is not None


def _is_banded(q, k, causal: bool, window) -> bool:
    """Whether a call leaves the bidirectional kernels' ground."""
    return _causal(causal, window) or k.shape[2] != q.shape[2]


def _shard_batch(mesh: Optional[Mesh], b: int) -> bool:
    """True when the kernel should run under shard_map over the data axis."""
    if mesh is None or "data" not in mesh.axis_names:
        return False
    n_data = mesh.shape["data"]
    return n_data > 1 and b % n_data == 0


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def flash_attention(q, k, v, block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    mesh: Optional[Mesh] = None,
                    valid_len: Optional[int] = None,
                    causal: bool = False, window: Optional[int] = None):
    """Softmax attention, ``q`` [B, N, H, D] in and out, ``k`` and ``v``
    [B, N, Hkv, D] with ``H % Hkv == 0`` (query head ``h`` reads key-value
    head ``h // (H // Hkv)``). Bidirectional by default (a ViT's);
    ``causal`` lets query ``i`` see the keys ``t <= i`` and ``window``
    (which implies ``causal``: a window looks back) only those with ``t >
    i - window``. A call with a mask or with fewer key-value heads than
    heads takes the banded kernels, whose grids hold only the tiles with
    an unmasked pair (:func:`blocks_visited`).

    ``block_q``/``block_k`` default to a length-adaptive size
    (``_resolve_blocks``); ``interpret=None`` auto-selects interpret mode
    off-TPU; ``mesh`` keeps the kernel batch-parallel under a sharded jit
    (see module docstring); ``valid_len`` masks keys beyond a static count
    when the inputs carry caller-side padding (ulysses)."""
    block_q, block_k = _resolve_blocks(q.shape[1], block_q, block_k)
    if _is_banded(q, k, causal, window):
        with jax.named_scope("flash_attention"):
            return _batch_parallel(
                lambda interp, *ops: _banded_fwd(
                    *ops, block_q, block_k, interp, _causal(causal, window),
                    window, static_valid=valid_len)[0],
                mesh, interpret, 1, q, k, v)
    fwd, _ = _select_kernels(q.shape[2], q.shape[3])
    # Scope tag for the device-time waterfall (telemetry/profile.py):
    # the Pallas custom-call rolls up under 'flash_attention' instead of
    # an anonymous custom-call.
    with jax.named_scope("flash_attention"):
        return _batch_parallel(
            lambda interp, *ops: fwd(*ops, block_q, block_k, interp,
                                     static_valid=valid_len),
            mesh, interpret, 1, q, k, v)


def _batch_parallel(fn, mesh, interpret, n_out, *operands):
    """Run ``fn(interpret, *operands)`` per batch shard under shard_map when
    the mesh shards the batch, else directly. Pallas calls are opaque to
    GSPMD, so without this a sharded jit would all-gather the operands onto
    every device. check_vma=False: pallas out_shapes carry no vma
    annotations. All operands/outputs are batch-major."""
    if interpret is None:
        from tpuic.kernels import default_interpret
        interpret = default_interpret()
    if not _shard_batch(mesh, operands[0].shape[0]):
        return fn(interpret, *operands)
    spec = P("data")
    return jax.shard_map(
        lambda *ops: fn(interpret, *ops),
        mesh=mesh, in_specs=(spec,) * len(operands),
        out_specs=spec if n_out == 1 else (spec,) * n_out,
        check_vma=False,
    )(*operands)


def _vjp_fwd(q, k, v, block_q, block_k, interpret, mesh, valid_len=None,
             causal=False, window=None):
    block_q, block_k = _resolve_blocks(q.shape[1], block_q, block_k)
    if _is_banded(q, k, causal, window):
        with jax.named_scope("flash_attention"):
            out, lse = _batch_parallel(
                lambda interp, *ops: _banded_fwd(
                    *ops, block_q, block_k, interp, _causal(causal, window),
                    window, static_valid=valid_len),
                mesh, interpret, 2, q, k, v)
        return out, (q, k, v, out, lse)
    fwd, _ = _select_kernels(q.shape[2], q.shape[3])
    with jax.named_scope("flash_attention"):
        out, lse = _batch_parallel(
            lambda interp, *ops: fwd(*ops, block_q, block_k, interp,
                                     with_lse=True,
                                     static_valid=valid_len),
            mesh, interpret, 2, q, k, v)
    return out, (q, k, v, out, lse)


def _vjp_bwd(block_q, block_k, interpret, mesh, valid_len, causal, window,
             res, g):
    q, k, v, out, lse = res
    # Same resolution as the forward: lse was padded with these blocks.
    block_q, block_k = _resolve_blocks(q.shape[1], block_q, block_k)
    if _is_banded(q, k, causal, window):
        with jax.named_scope("flash_attention_bwd"):
            return _batch_parallel(
                lambda interp, *ops: _banded_bwd(
                    *ops, block_q, block_k, interp, _causal(causal, window),
                    window, static_valid=valid_len),
                mesh, interpret, 3, q, k, v, out, lse, g)
    _, bwd = _select_kernels(q.shape[2], q.shape[3])
    with jax.named_scope("flash_attention_bwd"):
        return _batch_parallel(
            lambda interp, *ops: bwd(*ops, block_q, block_k, interp,
                                     static_valid=valid_len),
            mesh, interpret, 3, q, k, v, out, lse, g)


flash_attention.defvjp(_vjp_fwd, _vjp_bwd)
