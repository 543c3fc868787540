"""kernels/row_select.py's ``kth_largest`` against ``lax.top_k``, in
interpret mode: the ``k``-th largest score of each row, the key at which
``top_k`` cuts a run of equal scores, and the rows on which that cut
decided, over distinct scores, planted ties, all-zero, all-negative and
denormal rows, ``k == N``, and rows whose causal reach ends in different
key blocks; and one query chunk at the learned-sparse-attention cell's
shape."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpuic.kernels.row_select import kth_largest


def _scores(kind: str, rng, b: int, c: int, n: int) -> np.ndarray:
    """[b, c, n] float32 of one kind, before the causal cut; ``-0.0`` read
    as ``0.0``, as the selection gives them to the kernel."""
    x = rng.standard_normal((b, c, n)).astype(np.float32)
    if kind == "ties":              # eighths, and a run of zeros of both signs
        x = np.round(x * 2) / 8
        x[:, :, 40:300] = 0.0
        x[:, :, 100:160] = -0.0
    elif kind == "zero_rows":       # every ReLU of a row 0, beside others
        x = np.maximum(x, 0)
        x[:, ::3] = 0.0
    elif kind == "negative":
        x = -np.abs(x) - 1
    elif kind == "denormal":        # below float32's smallest normal
        x = (x * 1e-39).astype(np.float32)
        x[:, :, ::7] = 0.0
    return np.where(x == 0, np.float32(0), x).astype(np.float32)


def _causal(x: np.ndarray, first: int) -> np.ndarray:
    c, n = x.shape[1:]
    t = first + np.arange(c)[:, None]
    return np.where(np.arange(n)[None] <= t, x, -np.inf).astype(np.float32)


def _check(scores: np.ndarray, k: int, first: int) -> int:
    """``kth_largest`` against ``top_k`` row by row; returns the tie
    rows."""
    n = scores.shape[-1]
    bound, last, tie = (np.asarray(a)[..., 0] for a in
                        kth_largest(jnp.asarray(scores), k, first))
    values, keys = (np.asarray(a) for a in jax.lax.top_k(scores, k))
    taken = np.zeros(scores.shape, bool)
    np.put_along_axis(taken, keys, True, axis=-1)
    whole = np.isfinite(scores).sum(-1) >= k
    np.testing.assert_array_equal(bound[whole], values[..., -1][whole])
    assert np.all(bound[~whole] == -np.inf)
    # the cut falls inside a run: a score equal to the k-th is left out
    inside = whole & np.any((scores == values[..., -1:]) & ~taken, axis=-1)
    np.testing.assert_array_equal(tie, inside.astype(np.int32))
    np.testing.assert_array_equal(last[inside], keys[..., -1][inside])
    assert np.all(last[~inside] == n - 1)
    # and the mask the selection builds from them is top_k's set
    s = np.arange(n)
    got = (scores > -np.inf) & ((scores > bound[..., None]) | (
        (scores == bound[..., None]) & (s <= last[..., None])))
    np.testing.assert_array_equal(got, taken & np.isfinite(scores))
    return int(tie.sum())


@pytest.mark.parametrize("first", [0, 480, 960], ids=["first", "mid",
                                                      "last"])
@pytest.mark.parametrize("kind", ["distinct", "ties", "zero_rows",
                                  "negative", "denormal"])
def test_the_bound_and_tie_key_are_top_ks(kind, first):
    """Two images of 64 query rows over 1,024 keys, k = 48, in blocks of 32
    rows counted over key blocks of 512: the first offset's rows hold
    fewer than k keys at first, the middle one's blocks end in either key
    block, the last one's in the second."""
    rng = np.random.default_rng([first, len(kind)])
    scores = _causal(_scores(kind, rng, 2, 64, 1024), first)
    ties = _check(scores, 48, first)
    if kind in ("distinct", "negative"):
        assert ties == 0
    if kind == "ties" and first:
        assert ties > 0


@pytest.mark.parametrize("kind", ["distinct", "ties"])
def test_k_equal_to_n(kind):
    """Rows with every key finite (``first`` past the last key) and k = N:
    the bound is each row's least score; no cut is inside a run."""
    scores = _scores(kind, np.random.default_rng(5), 2, 32, 256)
    assert _check(scores, 256, 256) == 0


def test_one_chunk_at_the_cells_shape():
    """[1, 512, 16384], k = 2,048, the chunk of queries 8,192–8,703."""
    rng = np.random.default_rng(11)
    scores = _causal(_scores("distinct", rng, 1, 512, 16384), 8192)
    assert _check(scores, 2048, 8192) == 0


def test_k_out_of_range_is_refused():
    with pytest.raises(ValueError, match="k = 0"):
        kth_largest(jnp.zeros((1, 8, 128)), 0)
