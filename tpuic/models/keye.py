"""Keye-VL-2.0-30B-A3B's language stack (Kwai-Keye, ``config.json`` of
``Keye-VL-2.0-30B-A3B``, ``model_type`` ``KeyeVL2``) as a classifier
backbone: grouped-head attention over a *learned* selection of keys (a
DeepSeek-style lightning indexer picks each query's ``top_k`` keys, a
sparse flash kernel attends to those alone, and the indexer trains on a
KL objective of its own), rotary positions in two dimensions over the
patch grid (M-RoPE), and a top-8 softmax router over the experts this chip
holds.

As with the other decoder stacks (models/mellum.py, models/kanana.py) the
patch embedding of the standardised image stands where the token table
stood, a row of the batch is an image of ``(size / patch)**2`` tokens in
raster order, the read-out is the mean over positions of the closing
norm's output, and the ``Classifier`` head stands where the LM head stood.
Every width is the published one; no projection has a bias.

A block: ``u = RMSNorm(x)``; ``x += Attn(u)``; ``x += MoE(RMSNorm(x))``;
one closing RMSNorm after the last block. ``Attn`` (:class:`SparseAttention`):

- **main heads**: ``num_heads`` query heads over ``kv_heads`` key-value
  heads of ``head_dim`` (query head ``h`` reads ``h // (num_heads /
  kv_heads)``); a per-head RMSNorm of ``head_dim`` on queries and keys (the
  Qwen3-MoE family's); queries and keys turned by M-RoPE
  (:func:`mrope_tables`): a token's position is ``(0, row, col)`` on the
  patch grid, frequency pairs ``i`` of the rotate-half layout take the
  coordinate of their section of ``mrope_section`` (the first section's
  coordinate is always 0, so those pairs do not turn);
- **indexer** (:class:`Indexer`), on ``stop_gradient(u)``: ``J`` query
  heads ``q^I_j = u W^I_q,j`` and ONE key ``k^I = LayerNorm(u W^I_k)`` of
  ``E`` dimensions, the first half of each turned by M-RoPE at that width,
  weights ``w_j = (u W^I_w)_j / sqrt(J E)``; scores ``I[t, s] = sum_j w[t,
  j] ReLU(q^I[t, j] . k^I[s])`` for ``s <= t``, in float32;
- **selection** (:func:`select`): ``S_t`` is the ``min(t + 1, top_k)``
  causal keys of largest score, ties to the lower key (``lax.top_k``'s
  rule), exactly; computed in chunks of ``block_q`` queries, so that no
  ``[N, N]`` float is held, into a byte mask. No sort: each row's
  ``top_k``-th score and the key that cuts a run of scores equal to it
  are counted out of the scores' float bits by bisection
  (``kernels/row_select.py``), and bound the set;
- **core**: ``softmax over S_t`` of ``q . k / sqrt(head_dim)`` times ``v``,
  through ``kernels/sparse_attention.py`` (tiles with no selected pair are
  neither computed nor fetched); then ``W_o``;
- **indexer loss**: ``p_t`` = the core's probabilities over ``S_t`` summed
  over the heads and L1-normalised (from the kernel's log-sum-exp, under
  ``stop_gradient``); ``L^I = mean_t KL(p_t || softmax over S_t of I_t)``,
  computed in chunks under ``jax.checkpoint`` with the target saved. The
  selection is discrete and the indexer reads ``stop_gradient(u)``, so the
  classification loss reaches no indexer parameter and ``L^I`` no other.

``MoE`` is ``mellum.SoftmaxExpertLayer`` as it stands (softmax over all
``num_experts``, top-8 renormalised, the experts held here, no shared
expert).

The stack sows, once a step: ``attention_key_blocks_visited`` (the
(query block, key block) tiles holding a selected pair, which the kernel
computes, for a head of one image, summed over the layers),
``attention_key_blocks_square`` (every tile of those squares),
``attention_pairs_selected`` (``sum_t |S_t|`` of one image, summed over the
layers), ``selection_tie_rows`` (the rows of one image whose cut fell inside
a run of equal scores, so that the tie key decided, summed over the layers)
and ``indexer_kl`` (``L^I`` summed over the layers) into
``counters``, and the same ``L^I`` sum, with its gradient, into
``intermediates`` under ``auxiliary_objective``: the training step
differentiates it with the classifier's loss and reports the loss without
it (train/step.py), so the step's ``loss`` stays the cross-entropy, which
starts at ``ln(classes)``, and ``indexer_kl`` carries the rest.

Activations are what grows with the batch: the memory mode is per-block
rematerialisation (``ModelConfig.remat_policy='blocks'``); the selection
mask and the tile map are rebuilt with the block.
"""

from __future__ import annotations

import math
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn
from jax.ad_checkpoint import checkpoint_name

from tpuic.models.kanana import standardized
from tpuic.models.layers import RMSNorm, patch_tokens, proj, rotate
from tpuic.models.mellum import SoftmaxExpertLayer

MROPE_SECTION = (16, 24, 24)    # frequency pairs by coordinate (t, row, col)
TARGET = "indexer_target"       # the saved residual of the loss's chunks


def mrope_tables(tokens: int, dim: int, theta: float,
                 section: Tuple[int, ...] = MROPE_SECTION):
    """``(cos, sin)`` [tokens, dim] float32 in the rotate-half layout for
    tokens in raster order on a square grid: pair ``i`` (of ``dim / 2``)
    turns by ``theta^(-2i/dim)`` times the coordinate of its section of
    ``section`` (scaled to ``dim / 2`` pairs) of the position ``(0, row,
    col)``."""
    side = math.isqrt(tokens)
    if side * side != tokens:
        raise ValueError(f"{tokens} tokens are no square grid of patches")
    pairs = dim // 2
    scaled = [s * pairs // sum(section) for s in section]
    if sum(scaled) != pairs:
        raise ValueError(f"sections {section} do not scale to {pairs} pairs")
    t = np.arange(tokens)
    coords = np.stack([np.zeros_like(t), t // side, t % side])  # [3, N]
    axis = np.repeat(np.arange(len(scaled)), scaled)            # [pairs]
    inv_freq = (1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64)
                                / dim)).astype(np.float32)
    angles = coords[axis].T.astype(np.float32) * inv_freq[None]
    angles = np.concatenate([angles, angles], axis=-1)
    return np.cos(angles), np.sin(angles)


def index_scores(qi, ki, w, first: int):
    """``I`` [B, C, N] float32 of the queries ``first .. first + C - 1``:
    ``qi`` [B, C, J, E], ``ki`` [B, N, E], ``w`` [B, C, J] float32; keys
    past the query at ``-inf``."""
    dots = jnp.einsum("bqje,bke->bqjk", qi, ki,
                      preferred_element_type=jnp.float32)
    scores = jnp.sum(jax.nn.relu(dots) * w[..., None], axis=2)
    c, n = scores.shape[1:]
    t = first + jax.lax.broadcasted_iota(jnp.int32, (c, n), 0)
    s = jax.lax.broadcasted_iota(jnp.int32, (c, n), 1)
    return jnp.where(s <= t, scores, -jnp.inf)


def select(scores, top_k: int, first=None):
    """The selection bool [B, C, N] of ``scores`` [B, C, N] (``-inf`` past
    each query): its ``min(t + 1, top_k)`` causal keys of largest score,
    ties to the lower key, as ``lax.top_k`` picks them. ``first``, where
    given, is the first row's query: no key past the last row's is read."""
    return _select(scores, top_k, first)[0]


def _select(scores, top_k: int, first):
    """``(selection, tie int32 [B, C, 1])``. The ``top_k``-th value and a
    key bound the set: every key above that value, and those equal to it
    up to that key, both counted out of the scores' float bits by
    ``kernels/row_select.py`` (no sort); ``tie`` is 1 where that key cut a
    run of equal scores. ``-0.0`` is read as ``0.0`` first: a score whose
    ReLUs are all 0 is a zero of either sign, and ``top_k`` orders
    ``-0.0`` below ``0.0`` where ``==`` calls them a tie."""
    # Pallas is imported when a model is traced, not with the registry
    from tpuic.kernels.row_select import kth_largest
    scores = jnp.where(scores == 0, 0.0, scores)
    n = scores.shape[-1]
    bound, last, tie = kth_largest(scores, min(top_k, n),
                                   n if first is None else first)
    s = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 2)
    return (scores > -jnp.inf) & ((scores > bound)
                                  | ((scores == bound) & (s <= last))), tie


def _chunks(x, size: int):
    """[B, N, ...] -> [N / size, B, size, ...]."""
    b, n = x.shape[:2]
    return jnp.moveaxis(x.reshape((b, n // size, size) + x.shape[2:]), 1, 0)


def selection(qi, ki, w, top_k: int, block_q: int, block_k: int):
    """``(mask int8 [B, N, N], live int32 [B, N/bq, N/bk], pairs [B],
    tie_rows [B])``: the selection, query chunk by query chunk, the tiles
    that hold a selected pair, the count of selected pairs and the count
    of rows whose cut fell inside a run of equal scores."""
    b, n = ki.shape[:2]

    def chunk(args):
        qi_c, w_c, first = args
        with jax.named_scope("index_scores"):
            scores = index_scores(qi_c, ki, w_c, first)
        with jax.named_scope("select"):
            chosen, tie = _select(scores, top_k, first)
            live = jnp.any(chosen.reshape(b, block_q, n // block_k, block_k),
                           axis=(1, 3))
            return (chosen.astype(jnp.int8), live.astype(jnp.int32),
                    jnp.sum(chosen, axis=(1, 2), dtype=jnp.int32),
                    jnp.sum(tie, axis=(1, 2)))
    firsts = jnp.arange(0, n, block_q, dtype=jnp.int32)
    mask, live, pairs, ties = jax.lax.map(
        chunk, (_chunks(qi, block_q), _chunks(w, block_q), firsts))
    return (jnp.moveaxis(mask, 0, 1).reshape(b, n, n),
            jnp.moveaxis(live, 0, 1), jnp.sum(pairs, axis=0),
            jnp.sum(ties, axis=0))


def selection_mask(qi, ki, w, top_k: int, block_q: int, block_k: int):
    """``(mask, live, pairs)`` of :func:`selection`."""
    return selection(qi, ki, w, top_k, block_q, block_k)[:3]


def indexer_loss(qi, ki, w, mask, q, k, lse, block_q: int):
    """``sum_t KL(p_t || softmax over S_t of I_t)`` over every query of
    the batch: ``qi``, ``ki``, ``w`` the indexer's (differentiated), ``mask``
    the selection, ``q`` [B, N, H, D], ``k`` [B, N, Hkv, D] and ``lse`` [B,
    H, N] the core's (constants). Chunk by chunk under ``jax.checkpoint``,
    the head-summed target saved for the backward pass."""
    b, n, h, d = q.shape
    hkv = k.shape[2]
    group = h // hkv
    scale = 1.0 / math.sqrt(d)

    def target(q_c, lse_c, chosen):
        """``p`` [B, C, N]: the heads' probabilities summed, over S_t."""
        p = 0.0
        for g in range(hkv):
            logits = jnp.einsum(
                "bqhd,bkd->bhqk", q_c[:, :, g * group:(g + 1) * group],
                k[:, :, g], preferred_element_type=jnp.float32) * scale
            p = p + jnp.sum(jnp.exp(
                logits - lse_c[:, g * group:(g + 1) * group, :, None]),
                axis=1)
        p = jnp.where(chosen, p, 0.0)
        return p / jnp.sum(p, axis=-1, keepdims=True)

    def chunk(qi_c, w_c, mask_c, q_c, lse_c, first):
        chosen = mask_c != 0
        p = checkpoint_name(
            jax.lax.stop_gradient(target(q_c, lse_c, chosen)), TARGET)
        with jax.named_scope("index_scores"):
            scores = index_scores(qi_c, ki, w_c, first)
        logq = jax.nn.log_softmax(jnp.where(chosen, scores, -jnp.inf),
                                  axis=-1)
        logq = jnp.where(chosen, logq, 0.0)
        plogp = jnp.where(p > 0, p * jnp.log(jnp.where(p > 0, p, 1.0)), 0.0)
        return jnp.sum(plogp - p * logq)

    chunk = jax.checkpoint(
        chunk, policy=jax.checkpoint_policies.save_only_these_names(TARGET))
    firsts = jnp.arange(0, n, block_q, dtype=jnp.int32)
    lse_chunks = jnp.moveaxis(
        lse.reshape(b, h, n // block_q, block_q), 2, 0)
    return jnp.sum(jax.lax.map(
        lambda a: chunk(*a),
        (_chunks(qi, block_q), _chunks(w, block_q), _chunks(mask, block_q),
         _chunks(q, block_q), lse_chunks, firsts)))


class Indexer(nn.Module):
    """``(q^I [B, N, J, E], k^I [B, N, E], w [B, N, J] float32)``, the
    first half of ``q^I`` and ``k^I`` turned by M-RoPE."""

    heads: int
    head_dim: int
    rope_theta: float
    eps: float = 1e-6
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, u: jnp.ndarray):
        b, n, _ = u.shape
        e, half = self.head_dim, self.head_dim // 2
        qi = proj(self.heads * e, "q", self.dtype, self.param_dtype,
                  ("embed", "model"))(u).reshape(b, n, self.heads, e)
        ki = nn.LayerNorm(self.eps, dtype=self.dtype,
                          param_dtype=self.param_dtype, name="k_norm")(
            proj(e, "k", self.dtype, self.param_dtype,
                 ("embed", "model"))(u))
        w = proj(self.heads, "w", self.dtype, self.param_dtype,
                 ("embed", "unsharded"))(u).astype(jnp.float32)
        w = w * (self.heads * e) ** -0.5
        cos, sin = mrope_tables(n, half, self.rope_theta)
        turned = rotate(qi[..., :half].reshape(b, n, self.heads * half),
                        cos, sin).reshape(b, n, self.heads, half)
        qi = jnp.concatenate([turned, qi[..., half:]], axis=-1)
        ki = jnp.concatenate([rotate(ki[..., :half], cos, sin),
                              ki[..., half:]], axis=-1)
        return qi, ki, w


class SparseAttention(nn.Module):
    """Returns ``(y [B, N, d], L^I summed over the batch's queries, tiles
    visited a head of one image, selected pairs of one image, rows of one
    image whose cut fell inside a run of equal scores)``."""

    num_heads: int
    kv_heads: int
    head_dim: int
    rope_theta: float
    indexer_heads: int
    indexer_dim: int
    top_k: int
    blocks: Tuple[int, int] = (512, 512)
    with_loss: bool = True
    eps: float = 1e-6
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    mesh: Any = None

    @nn.compact
    def __call__(self, u: jnp.ndarray):
        b, n, d = u.shape
        hd = self.head_dim

        def heads(name, count):
            return proj(count * hd, name, self.dtype, self.param_dtype,
                        ("embed", "model"))(u).reshape(b, n, count, hd)

        def normed(name, x):
            return RMSNorm(self.eps, self.dtype, self.param_dtype,
                           name=name)(x).reshape(b, n, -1)
        q = normed("q_norm", heads("q", self.num_heads))
        k = normed("k_norm", heads("k", self.kv_heads))
        v = heads("v", self.kv_heads)
        cos, sin = mrope_tables(n, hd, self.rope_theta)
        q, k = rotate(q, cos, sin), rotate(k, cos, sin)
        q = q.reshape(b, n, self.num_heads, hd)
        k = k.reshape(b, n, self.kv_heads, hd)
        block_q, block_k = self.blocks
        with jax.named_scope("indexer"):
            qi, ki, w = Indexer(self.indexer_heads, self.indexer_dim,
                                self.rope_theta, self.eps, self.dtype,
                                self.param_dtype, name="indexer")(
                jax.lax.stop_gradient(u))
            mask, live, pairs, ties = selection(
                *(jax.lax.stop_gradient(t) for t in (qi, ki, w)),
                self.top_k, block_q, block_k)
        # Pallas is imported when a model is traced, not with the registry
        from tpuic.kernels.sparse_attention import sparse_attention
        with jax.named_scope("attention_core"):
            out, lse = sparse_attention(q, k, v, mask, live, block_q,
                                        block_k, None, self.mesh)
        loss = jnp.float32(0.0)
        if self.with_loss:
            with jax.named_scope("indexer"), \
                    jax.named_scope("indexer_loss"):
                loss = indexer_loss(
                    qi, ki, w, mask,
                    *(jax.lax.stop_gradient(t) for t in (q, k, lse)),
                    block_q)
        y = proj(d, "o", self.dtype, self.param_dtype,
                 ("model", "embed"))(out.reshape(b, n, -1))
        visited = jnp.sum(live).astype(jnp.float32) / b
        return (y, loss, visited, jnp.sum(pairs).astype(jnp.float32) / b,
                jnp.sum(ties).astype(jnp.float32) / b)


class SparseMoeBlock(nn.Module):
    """One published layer: ``(x, L^I, tiles visited, pairs selected, tie
    rows)``."""

    num_heads: int
    kv_heads: int
    head_dim: int
    rope_theta: float
    indexer_heads: int
    indexer_dim: int
    top_k: int
    num_experts: int
    held: Tuple[int, int]
    expert_width: int
    experts_per_token: int
    norm_topk: bool = True
    blocks: Tuple[int, int] = (512, 512)
    with_loss: bool = True
    eps: float = 1e-6
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    mesh: Any = None

    @nn.compact
    def __call__(self, x: jnp.ndarray):
        def norm(name):
            return RMSNorm(self.eps, self.dtype, self.param_dtype, name=name)
        with jax.named_scope("sparse_attention"):
            y, loss, visited, pairs, ties = SparseAttention(
                self.num_heads, self.kv_heads, self.head_dim,
                self.rope_theta, self.indexer_heads, self.indexer_dim,
                self.top_k, tuple(self.blocks), self.with_loss, self.eps,
                self.dtype, self.param_dtype, self.mesh, name="attn")(
                    norm("attn_norm")(x))
        x = x + y
        x = x + SoftmaxExpertLayer(
            self.num_experts, tuple(self.held), self.expert_width,
            self.experts_per_token, self.norm_topk, self.dtype,
            self.param_dtype, name="moe")(norm("mlp_norm")(x))
        return x, loss, visited, pairs, ties


class SparseMoeStack(nn.Module):
    """Returns the read-out [B, hidden] float32: the mean over positions
    of the closing norm's output."""

    patch: int = 16
    hidden: int = 2048
    depth: int = 48
    num_heads: int = 32
    kv_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1e7
    indexer_heads: int = 16
    indexer_dim: int = 64
    top_k: int = 2048
    num_experts: int = 128
    held: Tuple[int, int] = (0, 128)
    expert_width: int = 768
    experts_per_token: int = 8
    norm_topk: bool = True
    blocks: Tuple[int, int] = (512, 512)
    eps: float = 1e-6
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    mesh: Any = None
    # Per-block remat (ModelConfig.remat_policy='blocks'): the residuals of
    # the backward pass are the block inputs; one block is recomputed at a
    # time, its selection, kernel and routing included.
    remat_blocks: bool = False

    @nn.compact
    def __call__(self, x: jnp.ndarray, train: bool = False) -> jnp.ndarray:
        with jax.named_scope("standardize"):
            x = standardized(x)
        h = patch_tokens(x, self.hidden, self.patch, self.dtype,
                         self.param_dtype)
        block_cls = nn.remat(SparseMoeBlock) if self.remat_blocks \
            else SparseMoeBlock
        total = {"loss": 0.0, "visited": 0.0, "pairs": 0.0, "ties": 0.0}
        for i in range(self.depth):
            h, loss, visited, pairs, ties = block_cls(
                self.num_heads, self.kv_heads, self.head_dim,
                self.rope_theta, self.indexer_heads, self.indexer_dim,
                self.top_k, self.num_experts, tuple(self.held),
                self.expert_width, self.experts_per_token, self.norm_topk,
                tuple(self.blocks), train, self.eps, self.dtype,
                self.param_dtype, self.mesh, name=f"layer{i}")(h)
            for key, value in (("loss", loss), ("visited", visited),
                               ("pairs", pairs), ("ties", ties)):
                total[key] = total[key] + value
        if not self.is_initializing():
            self._sow(h.shape[0], h.shape[1], total, train)
        h = RMSNorm(self.eps, self.dtype, self.param_dtype,
                    name="norm_final")(h)
        return jnp.mean(h.astype(jnp.float32), axis=1)

    def _sow(self, batch: int, tokens: int, total: dict, train: bool):
        block_q, block_k = self.blocks
        kl = total["loss"] / (batch * tokens)     # L^I: the mean over t
        if train:
            self.sow("intermediates", "auxiliary_objective", kl)
        for name, value in (
                ("attention_key_blocks_visited", total["visited"]),
                ("attention_key_blocks_square",
                 self.depth * (tokens // block_q) * (tokens // block_k)),
                ("attention_pairs_selected", total["pairs"]),
                ("selection_tie_rows", total["ties"]),
                ("indexer_kl", kl)):
            self.sow("counters", name,
                     jax.lax.stop_gradient(jnp.float32(value)))


def keye_vl2_30b_a3b(depth: int = 48, held: Tuple[int, int] = (0, 128),
                     **kw) -> SparseMoeStack:
    """Keye-VL-2.0-30B-A3B's language stack at its published widths, heads,
    indexer, selection, tables, router and counts; ``depth`` is how many
    of its 48 layers are held (a pipeline stage's share when cut) and
    ``held`` this chip's experts of every layer."""
    return SparseMoeStack(depth=depth, held=tuple(held), **kw)


def keye_tiny(depth: int = 2, held: Tuple[int, int] = (0, 8),
              **kw) -> SparseMoeStack:
    """Test-scale stack (fast CI): two layers of 256 tokens at 128 px over
    patches of 8, two key-value heads under four of 16, an indexer of 4
    heads of 32 that keeps 48 keys a query (so that most rows choose),
    blocks of 64, top-3 of 16 experts with 8 held."""
    return SparseMoeStack(
        patch=8, hidden=64, depth=depth, num_heads=4, kv_heads=2,
        head_dim=16, rope_theta=10000.0, indexer_heads=4, indexer_dim=32,
        top_k=48, num_experts=16, held=tuple(held), expert_width=24,
        experts_per_token=3, blocks=(64, 64), **kw)
