"""The ``k``-th largest score of each row, and the key at which ``lax.top_k``
would cut a run of equal scores there, as one Pallas TPU kernel: what
models/keye.py's selection needs of ``top_k`` (its last value and key),
found by counting instead of sorting.

``kth_largest(scores, k, first)`` over ``scores`` [B, C, N] float32 (rows
of queries ``first .. first + C - 1``, ``-inf`` past each query, ``-0.0``
already read as ``0.0``) returns ``(bound, last, tie)``, each [B, C, 1]:

- **bound** (float32), the ``k``-th largest score of the row, exactly: the
  scores are read as int32 keys in the float order (``bits ^ ((bits >> 31)
  & 0x7fffffff)``, no unsigned arithmetic), and 32 bisection steps from the
  sign bit down set a bit of the candidate where at least ``k`` keys of the
  row are ``>=`` it. A row with fewer than ``k`` finite scores ends at
  ``-inf``, as ``top_k``'s last value does there.
- **last** (int32), where the cut falls inside a run of scores equal to
  ``bound``: ``need = k - #(score > bound)`` of the equal ones are taken,
  lowest key first (``top_k``'s rule), and ``last`` is the key of the
  ``need``-th, found by bisection over the key index. A row whose equal
  scores are all taken reads ``N - 1``: the same set.
- **tie** (int32), 1 where that second bisection decided: ``bound`` is
  finite and more than ``k`` keys are ``>= bound``.

So ``scores > bound | (scores == bound & s <= last)`` (with ``scores >
-inf``) is ``top_k``'s set. The count of each candidate is carried
through the first bisection (the count of the last candidate taken is
``#(>= bound)``, of the last one refused ``#(> bound)``), so the tie step
costs no pass of its own where no row of a block needs it.

The grid is (batch, blocks of ``ROWS`` query rows). A block's ``[ROWS, N]``
strip stays in VMEM, turned into keys once, for every pass; each pass is
one compare and one add an element on the vector unit, over the keys up to
the block's last query only (``first`` is a scalar-prefetch operand): past
it every score is ``-inf``, which no count that decides can see.
``interpret=None`` picks interpret mode off the TPU.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["kth_largest"]

ROWS = 32           # query rows a block: an int8 and a float32 tile deep
_LANES = 128
_WIDTH = 512        # key columns a counting loop's iteration reads
_INT_MIN = np.iinfo(np.int32).min


def _keys(x):
    """float32 -> int32 that sorts as signed int32 in the float order."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    return bits ^ ((bits >> 31) & 0x7FFFFFFF)


def _floats(key):
    """The inverse of :func:`_keys` (the same map)."""
    return jax.lax.bitcast_convert_type(key ^ ((key >> 31) & 0x7FFFFFFF),
                                        jnp.float32)


_NEG_INF_KEY = int(np.array(-np.inf, np.float32).view(np.int32)) ^ 0x7FFFFFFF


def _kernel(first_ref, s_ref, bound_ref, last_ref, tie_ref, keys_s, *,
            k: int, rows: int, width: int, n: int, index_bits: int):
    reach = jnp.minimum(first_ref[0] + (pl.program_id(1) + 1) * rows, n)
    chunks = (reach + width - 1) // width

    def to_keys(c, carry):
        off = pl.multiple_of(c * width, width)
        keys_s[:, pl.ds(off, width)] = _keys(s_ref[0, :, pl.ds(off, width)])
        return carry
    jax.lax.fori_loop(0, chunks, to_keys, 0)

    def count(hit):
        """Per row, over the reach: how many keys ``hit(keys, column)``
        holds for; float32 [rows, LANES], the count in every lane."""
        def body(c, acc):
            for j in range(width // _LANES):
                off = pl.multiple_of(c * width + j * _LANES, _LANES)
                acc = acc + jnp.where(hit(keys_s[:, pl.ds(off, _LANES)], off),
                                      1.0, 0.0)
            return acc
        acc = jax.lax.fori_loop(0, chunks, body,
                                jnp.zeros((rows, _LANES), jnp.float32))
        return jnp.broadcast_to(jnp.sum(acc, axis=1, keepdims=True),
                                (rows, _LANES))

    def value_step(i, carry):
        key, ge, gt = carry
        cand = key ^ jnp.left_shift(jnp.int32(1), 31 - i)
        got = count(lambda keys, off: keys >= cand)
        take = got >= k
        return (jnp.where(take, cand, key), jnp.where(take, got, ge),
                jnp.where(take, gt, got))
    zero = jnp.zeros((rows, _LANES), jnp.float32)
    key, ge, gt = jax.lax.fori_loop(
        0, 32, value_step,
        (jnp.full((rows, _LANES), _INT_MIN, jnp.int32), zero, zero))
    # keys past the reach are -inf: a row that finds fewer than k over the
    # reach has its k-th there
    bound = jnp.maximum(key, _NEG_INF_KEY)
    tie = (bound > _NEG_INF_KEY) & (ge > k)
    bound_ref[0] = bound
    tie_ref[0] = tie.astype(jnp.int32)
    last_ref[0] = jnp.full((rows, _LANES), n - 1, jnp.int32)

    @pl.when(jnp.max(jnp.where(tie, 1.0, 0.0)) > 0)
    def _ties():
        need = k - gt
        lane = jax.lax.broadcasted_iota(jnp.int32, (rows, _LANES), 1)

        def index_step(i, at):
            """``at``: the largest index with fewer than ``need`` equal
            keys before it, built bit by bit."""
            cand = at | jnp.left_shift(jnp.int32(1), index_bits - 1 - i)
            got = count(lambda keys, off: (keys == bound)
                        & (lane + off < cand))
            return jnp.where(got < need, cand, at)
        at = jax.lax.fori_loop(0, index_bits, index_step,
                               jnp.zeros((rows, _LANES), jnp.int32))
        last_ref[0] = jnp.where(tie, at, n - 1)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def _kth_largest(scores, first, k: int, interpret: bool):
    b, c, n = scores.shape
    rows = ROWS if c % ROWS == 0 else c
    pad = -n % _LANES
    if pad:
        scores = jnp.pad(scores, ((0, 0), (0, 0), (0, pad)),
                         constant_values=-jnp.inf)
    wide = n + pad
    width = next(w for w in (_WIDTH, 256, _LANES) if wide % w == 0)
    row = pl.BlockSpec((1, rows, _LANES), lambda bi, ri, first: (bi, ri, 0),
                       memory_space=pltpu.VMEM)
    out = jax.ShapeDtypeStruct((b, c, _LANES), jnp.int32)
    bound, last, tie = pl.pallas_call(
        functools.partial(_kernel, k=k, rows=rows, width=width, n=n,
                          index_bits=max(1, (n - 1).bit_length())),
        out_shape=[out, out, out],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, c // rows),
            in_specs=[pl.BlockSpec((1, rows, wide),
                                   lambda bi, ri, first: (bi, ri, 0),
                                   memory_space=pltpu.VMEM)],
            out_specs=[row, row, row],
            scratch_shapes=[pltpu.VMEM((rows, wide), jnp.int32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="kth_largest",
        cost_estimate=pl.CostEstimate(flops=0, transcendentals=0,
                                      bytes_accessed=4 * b * c * wide),
    )(jnp.reshape(first, (1,)).astype(jnp.int32), scores)
    return _floats(bound[..., :1]), last[..., :1], tie[..., :1]


def kth_largest(scores, k: int, first=0, interpret: Optional[bool] = None):
    """``(bound float32, last int32, tie int32)``, each [B, C, 1], of
    ``scores`` [B, C, N] float32 whose row ``i`` is query ``first + i``
    (``-inf`` past it): the ``k``-th largest score, the key up to which
    scores equal to it are taken, and 1 where that key decided (module
    docstring). ``1 <= k <= N``."""
    if not 1 <= k <= scores.shape[-1]:
        raise ValueError(f"k = {k} of {scores.shape[-1]} scores a row")
    if interpret is None:
        from tpuic.kernels import default_interpret
        interpret = default_interpret()
    return _kth_largest(scores, jnp.asarray(first), k, interpret)
