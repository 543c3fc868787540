"""Kanana-2-30B-A3B's decoder stack (kakaocorp, ``config.json`` of
``kanana-2-30b-a3b-instruct-2601``, ``model_type`` ``deepseek_v3``) as a
classifier backbone: latent attention with a decoupled rotary key, and a
dropless top-6 sigmoid router with a selection bias beside two shared
experts.

As with the looped stack (models/ouro.py) the language model's *stack* is
the backbone: a patch embedding stands where the token table stood, a row
of the batch is an image of ``(size / patch)**2`` tokens in raster order,
and the ``Classifier`` head stands where the LM head stood. Each image is
standardised first, channel by channel over its own pixels
(:func:`standardized`): a token table's rows point apart, and patches that
share their image's brightness embed to one token ``(size / patch)**2``
times over, which a router sends to the same experts. The read-out is the
mean over positions of the closing norm's output. Every width and the
layer's equations are the published ones; no bias in any projection:

- block: ``h += Attn(N(h))``, ``h += F(N(h))``, RMSNorm; ``F`` is a gated
  SiLU MLP in the first ``dense_layers`` layers and the expert layer in the
  others; one closing RMSNorm after the last layer;
- ``Attn`` (latent attention, :class:`LatentAttention`): queries of
  ``nope + rope`` dimensions a head straight from ``x`` (no query latent);
  keys and values out of a ``kv_rank``-wide normed latent, ``nope``
  un-rotated key dimensions and ``v_dim`` value dimensions a head, beside
  ONE ``rope``-wide rotary key shared by every head; rotary on interleaved
  pairs ``(2i, 2i+1)``, position = raster index; causal softmax in float32
  over ``(q_nope k_nope^T + q_rope k_rope^T) / sqrt(nope + rope)``. The
  core runs in one fused whole-sequence kernel (kernels/
  causal_attention.py: the four pieces scored where they lie, the shared
  key never broadcast, scores accumulated in float32 and kept in VMEM)
  when the heads are whole lane tiles and a cell fits VMEM, which the
  published widths are and do; otherwise (``kanana_tiny``) in the dense
  path, which concatenates the pieces and rounds the scores to the compute
  dtype. ``attention_core_fused`` in the ``counters`` says which ran;
- expert layer (:class:`ExpertLayer`): ``s = sigmoid(x W_r)`` in float32
  over all ``num_experts``; the ``top_k`` largest of ``s + b`` are chosen
  (``b``: the selection bias, which selects and does not weigh, and gets
  no gradient); weights ``scale * s_i / (sum over the chosen of s +
  1e-20)``; ``y = sum over the chosen experts held here of w_i E_i(x) +
  Shared(x)``.

A chip may hold a share of the model: ``depth`` of the published layers (a
pipeline stage's) and ``held`` of every layer's experts (an expert-parallel
rank's); the router, the normaliser and the choice stay over all experts.
On one chip the layer runs without its exchange: what the absent experts
would add is left out.

The routed sum does the work of the pairs routed to held experts and no
more (:func:`routed_sum`): the ``T x top_k`` choices are flattened, those on
held experts stable-sorted by expert into a static buffer, and two grouped
matrix products (``jax.lax.ragged_dot``: gate|up, then down) run over the
groups. Rows move between tokens and buffer by two gathers, each the
other's transpose under a hand-written VJP: a buffer row is gathered from
its token (:func:`to_buffer`), and each token gathers its ``top_k`` slots
from the buffer through the inverse of the sort, weighs them and sums them
in float32 (:func:`to_tokens`); the rows past the groups are masked on the
way in and never read on the way out, and no rows are scattered, forward
or backward. Where the slots outnumber the buffer's rows by more than
``SLOTS_OVER_BUFFER`` the gathers out read more than the scatter-adds
write, and there the buffer's rows are scatter-added into their tokens
instead. No pair is ever dropped: the buffer holds twice the even load's
rows, and a step whose count exceeds it takes the worst-case buffer (``T *
min(top_k, held)`` rows) under ``lax.cond``. A grouped product's time
follows the rows that lie in groups, so a step's time follows its routing;
the buffer's empty rows cost their gather in, and every slot of every
token, held here or not, its gather out.

Activations are what grows with the batch: the memory mode is per-block
rematerialisation (``ModelConfig.remat_policy='blocks'``).
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from tpuic.models.layers import GatedMlp, RMSNorm, patch_tokens, proj

# The seeded selection bias is normal and this wide: of the scores' size
# near the top-k boundary (about 0.01 between neighbours under random
# weights), so that leaving it out changes about 15 % of the choices.
SELECTION_BIAS_SIGMA = 0.02
# The buffer of the routed sum, over the rows an even load would send to
# the experts held (T * top_k * held / num_experts): room for a router
# that is off balance by a factor of two, past which a step pays for the
# worst case's gathers instead.
BUFFER_OVER_EVEN_LOAD = 2
# A worst case of up to this many rows goes through one buffer of its own;
# a larger one (16,384 tokens choosing 8 of 8 held experts are 131,072 rows,
# 7.9 GiB of reserved temporaries at width 2,304) through the usual buffer
# as many times over as it takes. ROADMAP Queue 1: the passes alone.
ONE_BUFFER_WORST_ROWS = 65_536
# Each token's slots, T * top_k rows, are gathered back from the buffer
# where they number at most this many times the buffer's rows; past it the
# buffer's rows are scatter-added into their tokens instead. Measured on a
# v5e, whole steps beside the parent: at 4 times (16,384 tokens, top-8, a
# 32,768-row buffer) the gathers took 19.4 ms off a 375 ms step; at 7.9
# times (6,272 tokens, top-6, 4,736 rows) they put 3.3 ms on a 168 ms one.
SLOTS_OVER_BUFFER = 6
# A gather reads its source's rows near the memory's rate where the
# compiler can hold the source in the core's own memory, and at about a
# fifth of it where it cannot (on a v5e, whose core holds 128 MiB: 131,072
# rows of a 151 MB buffer in 4.97 ms, of its 75 MB half in 0.59 ms). A
# larger source is gathered by blocks of columns of at most this many bytes.
GATHER_SOURCE_BYTES = 96 * 2**20
HIGHEST = jax.lax.Precision.HIGHEST


def interleaved_rotary(x: jnp.ndarray, theta: float) -> jnp.ndarray:
    """``x`` [B, N, ..., D] with the pairs ``(x[2i], x[2i+1])`` turned by
    ``position * theta^(-2i/D)``, in float32."""
    n, d = x.shape[1], x.shape[-1]
    inv_freq = (1.0 / theta ** (np.arange(0, d, 2, dtype=np.float32)
                                / np.float32(d))).astype(np.float32)
    angles = np.arange(n, dtype=np.float32)[:, None] * inv_freq[None]
    shape = (1, n) + (1,) * (x.ndim - 3) + (d // 2,)
    cos, sin = np.cos(angles).reshape(shape), np.sin(angles).reshape(shape)
    with jax.named_scope("rotary"):
        x = x.astype(jnp.float32)
        even, odd = x[..., 0::2], x[..., 1::2]
        return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                         axis=-1).reshape(x.shape)


def standardized(images: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    """``images`` [B, H, W, C] with each image's channels at zero mean and
    unit variance over its own pixels, float32: what an image's patches
    share (its brightness, its contrast) goes, what tells them apart
    stays."""
    x = images.astype(jnp.float32)
    mean = jnp.mean(x, axis=(1, 2), keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=(1, 2), keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps)


class LatentAttention(nn.Module):
    num_heads: int
    nope: int           # un-rotated query-key dimensions a head
    rope: int           # rotary dimensions: a head's of q, the one shared key
    v_dim: int
    kv_rank: int
    rope_theta: float = 1e6
    eps: float = 1e-6
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        b, n, d = x.shape
        h, nope, rope, v_dim = self.num_heads, self.nope, self.rope, self.v_dim

        def dense(width, name, logical=("embed", "model")):
            return proj(width, name, self.dtype, self.param_dtype, logical)
        q = dense(h * (nope + rope), "q")(x).reshape(b, n, h, nope + rope)
        kv = dense(self.kv_rank + rope, "kv_a", ("embed", "unsharded"))(x)
        latent = RMSNorm(self.eps, self.dtype, self.param_dtype,
                         name="kv_norm")(kv[..., :self.kv_rank])
        kv_up = dense(h * (nope + v_dim), "kv_b", ("unsharded", "model"))(
            latent).reshape(b, n, h, nope + v_dim)
        q_nope = q[..., :nope]
        q_rope = interleaved_rotary(
            q[..., nope:], self.rope_theta).astype(self.dtype)
        k_rope = interleaved_rotary(
            kv[..., self.kv_rank:], self.rope_theta).astype(self.dtype)
        # Pallas is imported when a model is traced, not with the registry
        from tpuic.kernels import causal_attention
        # by shape alone: heads of whole lane tiles whose scores fit VMEM
        fused = causal_attention.supports(
            n, h, nope, rope, v_dim, jnp.dtype(self.dtype).itemsize)

        scale = 1.0 / np.sqrt(nope + rope)

        def dense_core():
            q = jnp.concatenate([q_nope, q_rope], -1)
            k = jnp.concatenate([kv_up[..., :nope], jnp.broadcast_to(
                k_rope[:, :, None], (b, n, h, rope))], -1)
            logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(
                jnp.float32) * scale
            causal = np.tril(np.ones((n, n), bool))
            logits = jnp.where(causal[None, None], logits,
                               jnp.finfo(jnp.float32).min)
            probs = nn.softmax(logits, axis=-1).astype(self.dtype)
            return jnp.einsum("bhqk,bkhd->bqhd", probs, kv_up[..., nope:])

        with jax.named_scope("attention_core"):
            # the kernel reads a head's key beside its value where kv_b
            # wrote them
            out = (causal_attention.causal_attention(
                q_nope, q_rope=q_rope, k_rope=k_rope, kv=kv_up) if fused
                else dense_core()).reshape(b, n, h * v_dim)
        if not self.is_initializing():
            # a counter of the step, as the routed layer's (ExpertLayer)
            self.sow("counters", "attention_core_fused", jnp.float32(fused))
        return dense(d, "o", ("model", "embed"))(out)


def dispatch(group: jnp.ndarray, held: int, rank: bool = False):
    """``group`` [P] int32: each routed pair's expert among those held
    here, ``held`` for a pair whose expert is absent. Returns ``(order [P],
    rank [P] or None, sizes [held])``: the pairs with those on held experts
    first and in expert order (a stable sort), where ``rank`` each pair's
    place in that order (the inverse permutation), and the rows each held
    expert got."""
    order = jnp.argsort(group, stable=True)
    return (order, jnp.argsort(order) if rank else None,
            jnp.sum(jax.nn.one_hot(group, held, dtype=jnp.int32), axis=0))


class Moves(NamedTuple):
    """Where a buffer's rows come from and go. Row ``r`` holds pair
    ``pair[r]`` (of token ``pair[r] // top_k``) where ``valid[r]``: the rows
    past the groups hold none. Slot ``j`` of token ``t``, pair ``t * top_k
    + j``, lies at row ``slot[j, t]`` where ``held[j, t]`` (elsewhere
    ``slot`` is any row, read and selected away)."""
    pair: jnp.ndarray       # [rows] int32
    valid: jnp.ndarray      # [rows] bool
    slot: jnp.ndarray       # [top_k, T] int32 in [0, rows)
    held: jnp.ndarray       # [top_k, T] bool


def buffer_moves(pairs: jnp.ndarray, rank: jnp.ndarray, count,
                 top_k: int) -> Moves:
    """The :class:`Moves` of a buffer of ``pairs`` [rows] (a run of the
    sorted order) whose first ``count`` rows lie in groups; ``rank`` [T *
    top_k] is each pair's row, its place in the sorted order less the
    run's start."""
    rows = pairs.shape[0]
    held = (rank >= 0) & (rank < count)
    # a slot that is not held reads a row of its own, not one row for all:
    # a gather that reads one row over and over runs at a fraction of the
    # rate of one that reads rows spread over the source
    spread = jnp.arange(rank.shape[0], dtype=jnp.int32) % rows
    slot = jnp.where(held, rank, spread)
    return Moves(pairs, jnp.arange(rows, dtype=jnp.int32) < count,
                 slot.reshape(-1, top_k).T, held.reshape(-1, top_k).T)


def column_blocks(source: jnp.ndarray):
    """``[(lo, hi)]``: the columns of ``source`` [N, D] in blocks of whole
    lane tiles of at most :data:`GATHER_SOURCE_BYTES` each (one block where
    the whole source is under it)."""
    width = source.shape[1]
    blocks = -(-source.size * source.dtype.itemsize // GATHER_SOURCE_BYTES)
    step = min(-(-width // (128 * blocks)) * 128, width)
    return [(lo, min(lo + step, width)) for lo in range(0, width, step)]


def gather_rows(source: jnp.ndarray, index: jnp.ndarray) -> jnp.ndarray:
    """``source[index]``: rows of ``source`` [N, D] at ``index`` (any
    shape), gathered by :func:`column_blocks`, each cut from the source
    before it is gathered."""
    blocks = column_blocks(source)
    if len(blocks) == 1:
        return source[index]
    parts = jax.lax.optimization_barrier(
        [source[:, lo:hi] for lo, hi in blocks])
    return jnp.concatenate([part[index] for part in parts], axis=-1)


def _slot_sum(buf, moves, weights):
    """:func:`to_tokens` of one block of the buffer's columns."""
    rows = buf[moves.slot]
    y = 0.0
    for j in range(rows.shape[0]):
        row = rows[j].astype(jnp.float32)
        if weights is not None:
            row = row * weights[:, j, None]
        y = y + jnp.where(moves.held[j, :, None], row, 0.0)
    return y


@jax.custom_vjp
def to_buffer(x: jnp.ndarray, moves: Moves) -> jnp.ndarray:
    """``buf[r] = valid[r] * x[pair[r] // top_k]``: the buffer's rows
    gathered from their tokens ``x`` [T, D], in ``x``'s dtype. Its
    transpose is :func:`to_tokens`, a gather too: no scatter either way."""
    token = moves.pair // moves.slot.shape[0]
    return jnp.where(moves.valid[:, None], gather_rows(x, token), 0)


@jax.custom_vjp
def to_tokens(buf: jnp.ndarray, moves: Moves,
              weights: Any = None) -> jnp.ndarray:
    """``y[t] = sum over j of held[j, t] * weights[t, j] * buf[slot[j,
    t]]``: each token's slots gathered from the buffer ``buf`` [rows, D],
    weighed (``weights`` [T, top_k] float32, or none) and summed in
    float32, [T, D]. A row that no held slot names (one past the groups)
    is never read. Its transpose is :func:`to_buffer`, each row times its
    pair's weight. The rows are weighed as gathered, in float32: weighed
    first, the buffer would be written in float32 and gathered so."""
    blocks = column_blocks(buf)
    if len(blocks) == 1:
        return _slot_sum(buf, moves, weights)
    # a block of columns is cut only once the one before it is summed, so
    # that one block at a time is held in the core's memory
    y = jnp.zeros((moves.slot.shape[1], buf.shape[1]), jnp.float32)
    for lo, hi in blocks:
        part, y = jax.lax.optimization_barrier((buf[:, lo:hi], y))
        y = jax.lax.dynamic_update_slice(
            y, _slot_sum(part, moves, weights), (0, lo))
    return y


def _to_buffer_bwd(moves, d_buf):
    # summed in float32, returned in the dtype of x, which is d_buf's
    return to_tokens(d_buf, moves).astype(d_buf.dtype), None


def _to_tokens_bwd(residuals, dy):
    buf, moves, weights = residuals
    d_buf = to_buffer(dy, moves)
    if weights is None:
        return d_buf.astype(buf.dtype), None, None
    # a weight's cotangent is its row's dot with dy, read at its slot: a
    # row past the groups (anything, NaN too) is never selected
    d_pair = jnp.sum(d_buf * buf.astype(jnp.float32), axis=-1)
    d_weights = jnp.where(moves.held, d_pair[moves.slot], 0.0).T
    w_buf = weights.reshape(-1)[moves.pair]
    return (d_buf * w_buf[:, None]).astype(buf.dtype), None, d_weights


to_buffer.defvjp(lambda x, moves: (to_buffer(x, moves), moves),
                 _to_buffer_bwd)
to_tokens.defvjp(lambda buf, moves, weights: (
    to_tokens(buf, moves, weights), (buf, moves, weights)), _to_tokens_bwd)


def expert_matmul(rows: jnp.ndarray, gate_up: jnp.ndarray,
                  down: jnp.ndarray, sizes: jnp.ndarray) -> jnp.ndarray:
    """``down_e(silu(gate_e(x)) * up_e(x))`` for the rows of each group
    ``e``; rows past the groups' sum are not computed, and what they hold
    is whatever was there."""
    hidden = jax.lax.ragged_dot(rows, gate_up, sizes)
    width = hidden.shape[-1] // 2
    return jax.lax.ragged_dot(
        nn.silu(hidden[:, :width]) * hidden[:, width:], down, sizes)


def combine(out: jnp.ndarray, valid: jnp.ndarray, weights: jnp.ndarray,
            token: jnp.ndarray, tokens: int) -> jnp.ndarray:
    """The buffer's rows, those past the groups read as nothing, each
    times its weight, added to its token: [tokens, D] float32."""
    out = jnp.where(valid[:, None], out.astype(jnp.float32), 0.0)
    return jnp.zeros((tokens, out.shape[-1]), jnp.float32).at[token].add(
        out * weights[:, None])


def _routed_rows(x, order, sizes, weights, gate_up, down, *, rows: int):
    """``routed_sum`` through a buffer of the first ``rows`` of ``order``,
    the token-side sums scatter-adds; also how many pairs it had room
    for."""
    tokens, top_k = weights.shape
    count = jnp.sum(sizes)
    with jax.named_scope("dispatch"):
        pairs = order[:rows]
        valid = jnp.arange(rows, dtype=jnp.int32) < count
        token = pairs // top_k
        # masked on the way in as on the way out: the backward pass of a
        # grouped product leaves the rows past its groups as they were
        taken = jnp.where(valid[:, None], x[token], 0)
    with jax.named_scope("expert_matmul"):
        out = expert_matmul(taken, gate_up, down, sizes)
    with jax.named_scope("combine"):
        y = combine(out, valid, weights.reshape(-1)[pairs], token, tokens)
    return y, jnp.minimum(count, rows)


def _gathered_rows(x, pairs, rank, sizes, weights, gate_up, down):
    """``routed_sum`` through a buffer of the sorted pairs ``pairs``
    [rows], ``rank`` [T * top_k] each pair's row there (off it for a pair
    elsewhere), the rows moved both ways by gathers; also how many pairs
    it had room for."""
    top_k = weights.shape[1]
    count = jnp.minimum(jnp.sum(sizes), pairs.shape[0])
    with jax.named_scope("dispatch"):
        # masked on the way in as on the way out: the backward pass of a
        # grouped product leaves the rows past its groups as they were
        moves = buffer_moves(pairs, rank, count, top_k)
        taken = to_buffer(x, moves)
    with jax.named_scope("expert_matmul"):
        out = expert_matmul(taken, gate_up, down, sizes)
    with jax.named_scope("combine"):
        y = to_tokens(out, moves, weights)
    return y, count


def buffer_rows(tokens: int, top_k: int, held: int, num_experts: int):
    """``(rows, worst)``: the buffer a step's routed pairs go through
    (``BUFFER_OVER_EVEN_LOAD`` times the rows an even load would send to
    the experts held, to a multiple of 128), and the one that no routing
    overflows."""
    worst = tokens * min(top_k, held)
    even = -(-tokens * top_k * held // num_experts)
    rows = -(-BUFFER_OVER_EVEN_LOAD * even // 128) * 128
    return min(rows, worst), worst


def _routed_passes(x, order, rank, sizes, weights, gate_up, down, *,
                   rows: int, worst: int):
    """The worst case through the buffer of ``rows``, as many times over
    as it takes: pass ``c`` carries the sorted pairs ``[c rows, (c + 1)
    rows)`` and the part of each expert's group that lies there. One pass
    is alive at a time, in the backward pass too (each is recomputed), so
    the worst case costs the memory of the usual one."""
    passes = -(-worst // rows)
    order = jnp.pad(order, (0, max(0, passes * rows - order.shape[0])))
    ends = jnp.cumsum(sizes)
    starts = ends - sizes

    def one(y, lo):
        part = jnp.clip(ends, lo, lo + rows) - jnp.clip(starts, lo, lo + rows)
        run = jax.lax.dynamic_slice(order, (lo,), (rows,))
        more, _ = _routed_rows(
            x, run, part, weights, gate_up, down, rows=rows) if rank is None \
            else _gathered_rows(x, run, rank - lo, part, weights, gate_up,
                                down)
        return y + more, None
    y, _ = jax.lax.scan(
        jax.checkpoint(one), jnp.zeros((x.shape[0], down.shape[-1]),
                                       jnp.float32),
        rows * jnp.arange(passes, dtype=jnp.int32))
    return y, jnp.minimum(jnp.sum(sizes), passes * rows)


def routed_sum(x: jnp.ndarray, chosen: jnp.ndarray, weights: jnp.ndarray,
               gate_up: jnp.ndarray, down: jnp.ndarray, first: int,
               num_experts: int):
    """``sum over the chosen experts held here of w E(x)`` for tokens ``x``
    [T, D]: ``chosen`` [T, top_k] among ``num_experts``, ``weights`` [T,
    top_k] float32, the held experts ``first .. first + held`` as
    ``gate_up`` [held, D, 2W] and ``down`` [held, W, D]. Returns ``(y [T,
    D] float32, sizes [held], computed, over)``: the rows each held expert
    got, how many pairs were computed (all of them: nothing is dropped),
    and whether they exceeded the buffer and went through the worst
    case's: one buffer that no routing overflows or, where that one
    would hold more than ``ONE_BUFFER_WORST_ROWS``, the usual buffer as
    many times over as it takes."""
    tokens, top_k = chosen.shape
    held = gate_up.shape[0]
    local = chosen - first
    group = jnp.where((local >= 0) & (local < held), local,
                      held).reshape(-1).astype(jnp.int32)
    rows, worst = buffer_rows(tokens, top_k, held, num_experts)
    with jax.named_scope("dispatch"):
        order, rank, sizes = dispatch(
            group, held, rank=tokens * top_k <= SLOTS_OVER_BUFFER * rows)

    def through(rows):
        if rank is None:
            return _routed_rows(x, order, sizes, weights, gate_up, down,
                                rows=rows)
        return _gathered_rows(x, order[:rows], rank, sizes, weights, gate_up,
                              down)
    over = jnp.sum(sizes) > rows
    if rows == worst:
        y, computed = through(worst)
    else:
        worst_case = functools.partial(
            _routed_passes, x, order, rank, sizes, weights, gate_up, down,
            rows=rows, worst=worst) if worst > ONE_BUFFER_WORST_ROWS \
            else functools.partial(through, worst)
        y, computed = jax.lax.cond(over, worst_case,
                                   functools.partial(through, rows))
    return y, sizes, computed, over


def held_experts(module: nn.Module, held: int, d: int, width: int):
    """``(gate_up [held, d, 2 width], down [held, width, d])`` of
    ``module`` (which has ``dtype`` and ``param_dtype``), in its compute
    dtype: the experts a routed layer holds, as ``routed_sum`` takes
    them."""
    def experts(name, shape, logical):
        return module.param(
            name, nn.with_logical_partitioning(
                nn.initializers.variance_scaling(
                    1.0, "fan_avg", "uniform", in_axis=-2, out_axis=-1,
                    batch_axis=(0,)), logical),
            (held,) + shape, module.param_dtype).astype(module.dtype)
    return (experts("experts_gate_up", (d, 2 * width),
                    ("unsharded", "embed", "unsharded")),
            experts("experts_down", (width, d),
                    ("unsharded", "unsharded", "embed")))


def sow_routing_counters(module: nn.Module, pairs: int, sizes, computed, over,
                         scores) -> None:
    """A routed layer's counters of the step (train/step.py), under the
    names every family's routed layer sows; nothing outside a step.
    ``scores`` [T, experts] are the router's, of any scale."""
    if module.is_initializing():
        return
    load = sizes.astype(jnp.float32)
    share = scores / jnp.sum(scores, axis=-1, keepdims=True)
    for name, value in (
            ("routed_pairs", jnp.float32(pairs)),
            ("routed_pairs_held", jnp.sum(load)),
            ("routed_pairs_dropped", jnp.sum(load) - computed),
            ("routed_layers_over_buffer", over.astype(jnp.float32)),
            ("expert_load_max_over_mean",
             jnp.max(load) / jnp.maximum(jnp.mean(load), 1e-9)),
            ("router_entropy", jnp.mean(-jnp.sum(
                share * jnp.log(jnp.maximum(share, 1e-30)), axis=-1)))):
        module.sow("counters", name, jax.lax.stop_gradient(value))


class ExpertLayer(nn.Module):
    """The routed experts held here, under the router over all of them,
    beside the shared experts (one gated MLP of their summed width)."""

    num_experts: int
    held: Tuple[int, int]       # (first, how many) of num_experts
    width: int
    top_k: int
    shared_width: int
    routed_scale: float
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        b, n, d = x.shape
        first, held = self.held
        if not (0 <= first and 0 < held and first + held <= self.num_experts):
            raise ValueError(f"held experts {self.held} of "
                             f"{self.num_experts}")
        xf = x.reshape(b * n, d)

        with jax.named_scope("router"):
            router = self.param(
                "router", nn.with_logical_partitioning(
                    nn.initializers.xavier_uniform(), ("embed", "unsharded")),
                (d, self.num_experts), self.param_dtype)
            bias = self.param(
                "selection_bias", lambda key, shape: SELECTION_BIAS_SIGMA
                * jax.random.normal(key, shape, jnp.float32),
                (self.num_experts,))
            scores = jax.nn.sigmoid(jnp.dot(
                xf.astype(jnp.float32), router.astype(jnp.float32),
                precision=HIGHEST))
            _, chosen = jax.lax.top_k(
                scores + jax.lax.stop_gradient(bias), self.top_k)
            picked = jnp.take_along_axis(scores, chosen, axis=-1)
            weights = self.routed_scale * picked / (
                jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)

        gate_up, down = held_experts(self, held, d, self.width)
        with jax.named_scope("routed_experts"):
            y, sizes, computed, over = routed_sum(
                xf.astype(self.dtype), chosen, weights, gate_up, down, first,
                self.num_experts)
        with jax.named_scope("shared_experts"):
            shared = GatedMlp(self.shared_width, self.dtype,
                              self.param_dtype, name="shared")(x)

        sow_routing_counters(self, b * n * self.top_k, sizes, computed, over,
                             scores)
        return shared + y.astype(self.dtype).reshape(b, n, d)


class LatentMoeBlock(nn.Module):
    """One published layer; ``dense_width`` None makes it an expert layer."""

    dense_width: Any
    num_heads: int
    nope: int
    rope: int
    v_dim: int
    kv_rank: int
    num_experts: int
    held: Tuple[int, int]
    expert_width: int
    top_k: int
    shared_width: int
    routed_scale: float
    rope_theta: float = 1e6
    eps: float = 1e-6
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        def norm(name):
            return RMSNorm(self.eps, self.dtype, self.param_dtype, name=name)
        with jax.named_scope("mla_attention"):
            x = x + LatentAttention(
                self.num_heads, self.nope, self.rope, self.v_dim,
                self.kv_rank, self.rope_theta, self.eps, self.dtype,
                self.param_dtype, name="attn")(norm("attn_norm")(x))
        y = norm("mlp_norm")(x)
        if self.dense_width is not None:
            with jax.named_scope("dense_mlp"):
                return x + GatedMlp(self.dense_width, self.dtype,
                                    self.param_dtype, name="mlp")(y)
        return x + ExpertLayer(
            self.num_experts, tuple(self.held), self.expert_width, self.top_k,
            self.shared_width, self.routed_scale, self.dtype,
            self.param_dtype, name="moe")(y)


class LatentMoeStack(nn.Module):
    """Returns the read-out [B, hidden] float32: the mean over positions
    of the closing norm's output."""

    patch: int = 16
    hidden: int = 2048
    depth: int = 48
    dense_layers: int = 1
    num_heads: int = 32
    nope: int = 128
    rope: int = 64
    v_dim: int = 128
    kv_rank: int = 512
    dense_width: int = 6144
    num_experts: int = 128
    held: Tuple[int, int] = (0, 128)
    expert_width: int = 768
    top_k: int = 6
    shared_experts: int = 2
    routed_scale: float = 2.448
    rope_theta: float = 1e6
    eps: float = 1e-6
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    # Per-block remat (ModelConfig.remat_policy='blocks'): the residuals of
    # the backward pass are the block inputs; one block is recomputed at a
    # time, its routing and its grouped products included.
    remat_blocks: bool = False

    @nn.compact
    def __call__(self, x: jnp.ndarray, train: bool = False) -> jnp.ndarray:
        del train       # no dropout, no statistics: one forward for both
        with jax.named_scope("standardize"):
            x = standardized(x)
        h = patch_tokens(x, self.hidden, self.patch, self.dtype,
                         self.param_dtype)
        block_cls = nn.remat(LatentMoeBlock) if self.remat_blocks \
            else LatentMoeBlock
        for i in range(self.depth):
            h = block_cls(
                self.dense_width if i < self.dense_layers else None,
                self.num_heads, self.nope, self.rope, self.v_dim,
                self.kv_rank, self.num_experts, tuple(self.held),
                self.expert_width, self.top_k,
                self.shared_experts * self.expert_width, self.routed_scale,
                self.rope_theta, self.eps, self.dtype, self.param_dtype,
                name=f"layer{i}")(h)
        h = RMSNorm(self.eps, self.dtype, self.param_dtype,
                    name="norm_final")(h)
        return jnp.mean(h.astype(jnp.float32), axis=1)


def kanana_2_30b_a3b(depth: int = 48, held: Tuple[int, int] = (0, 128),
                     **kw) -> LatentMoeStack:
    """Kanana-2-30B-A3B's published widths, router and counts; ``depth`` is
    how many of its 48 layers are held (a pipeline stage's share when cut)
    and ``held`` this chip's experts of every expert layer."""
    return LatentMoeStack(depth=depth, held=tuple(held), **kw)


def kanana_tiny(depth: int = 3, held: Tuple[int, int] = (0, 8),
                **kw) -> LatentMoeStack:
    """Test-scale stack (fast CI): one dense layer, head sizes that differ
    between query-key and value, top-3 of 32 experts with 8 held."""
    return LatentMoeStack(patch=4, hidden=64, depth=depth, dense_layers=1,
                          num_heads=4, nope=16, rope=8, v_dim=12, kv_rank=24,
                          dense_width=160, num_experts=32, held=tuple(held),
                          expert_width=24, top_k=3, shared_experts=2, **kw)
