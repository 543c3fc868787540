"""The whole-sequence causal attention kernel (kernels/causal_attention.py)
in interpret mode, against the plain formulation it computes (its
``reference``) and against latent attention's concatenated dense core:
forward, every gradient, causality, a sequence that is not whole tiles,
the core without rotary pieces, bfloat16, and the dispatch rule's arithmetic.
tests/test_chip_compile.py compiles it for a described v5e at the cell's
shapes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpuic.kernels import causal_attention as ca

NAMES = ("q_nope", "k_nope", "v", "q_rope", "k_rope")
# (N, H): a sequence that is whole tiles and one that is not (20 rows are
# a tile and a quarter), one head group of 2 and one of 4
SHAPES = [(16, 2), (20, 2), (20, 4)]


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _pieces(n, heads, dtype=jnp.float32, seed=0, b=2, nope=128, rope=64,
            v_dim=128):
    rng = np.random.default_rng(seed)

    def draw(*shape):
        return jnp.asarray(rng.standard_normal(shape).astype(np.float32)
                           ).astype(dtype)
    return (draw(b, n, heads, nope), draw(b, n, heads, nope),
            draw(b, n, heads, v_dim), draw(b, n, heads, rope),
            draw(b, n, rope))


def _concatenated_core(q_nope, k_nope, v, q_rope, k_rope):
    """``LatentAttention``'s dense core: the rotary key broadcast to every
    head and concatenated, scores rounded to the compute dtype."""
    b, n, h, rope = q_rope.shape
    q = jnp.concatenate([q_nope, q_rope], -1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_rope[:, :, None], (b, n, h, rope))], -1)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(
        jnp.float32) / np.sqrt(q.shape[-1])
    logits = jnp.where(np.tril(np.ones((n, n), bool))[None, None], logits,
                       jnp.finfo(jnp.float32).min)
    return jnp.einsum("bhqk,bkhd->bqhd",
                      jax.nn.softmax(logits, axis=-1).astype(v.dtype), v)


def _gradients(core, pieces, weigh):
    return jax.grad(lambda *a: jnp.sum(core(*a).astype(jnp.float32) * weigh),
                    argnums=tuple(range(len(pieces))))(*pieces)


@pytest.mark.parametrize("oracle", [ca.reference, _concatenated_core],
                         ids=["plain_formulation", "concatenated_core"])
@pytest.mark.parametrize("n,heads", SHAPES)
def test_forward_agrees_in_float32(n, heads, oracle):
    pieces = _pieces(n, heads)
    got = ca.causal_attention(*pieces)
    assert got.shape == pieces[2].shape and got.dtype == jnp.float32
    np.testing.assert_allclose(got, oracle(*pieces), atol=5e-6)


@pytest.mark.parametrize("oracle", [ca.reference, _concatenated_core],
                         ids=["plain_formulation", "concatenated_core"])
@pytest.mark.parametrize("n,heads", SHAPES)
def test_every_gradient_agrees_in_float32(n, heads, oracle):
    pieces = _pieces(n, heads, seed=1)
    weigh = jnp.asarray(np.random.default_rng(2).standard_normal(
        pieces[2].shape).astype(np.float32))
    got = _gradients(ca.causal_attention, pieces, weigh)
    want = _gradients(oracle, pieces, weigh)
    for name, g, w, piece in zip(NAMES, got, want, pieces):
        assert g.shape == piece.shape and g.dtype == piece.dtype, name
        assert float(jnp.abs(w).max()) > 0.1, name
        np.testing.assert_allclose(g, w, atol=2e-5, err_msg=name)


def test_the_shared_keys_gradient_is_the_sum_over_heads():
    """Each head alone against the one key, added up: what the broadcast's
    transpose was."""
    pieces = _pieces(20, 4, seed=3)
    weigh = jnp.ones(pieces[2].shape, jnp.float32)
    whole = _gradients(ca.causal_attention, pieces, weigh)[4]
    by_pair = sum(
        _gradients(ca.causal_attention,
                   tuple(p[:, :, h:h + 2] for p in pieces[:4]) + pieces[4:],
                   weigh[:, :, h:h + 2])[4] for h in (0, 2))
    np.testing.assert_allclose(whole, by_pair, atol=2e-5)


@pytest.mark.parametrize("rotary", [True, False])
def test_a_heads_key_beside_its_value_is_read_in_place(rotary):
    """``kv``: latent attention's up-projection as it lies, [B, N, H, nope
    + v_dim]; the same numbers as with the two halves apart, and one
    gradient of its shape."""
    q_nope, k_nope, v, q_rope, k_rope = _pieces(20, 4, seed=11)
    rest = dict(q_rope=q_rope, k_rope=k_rope) if rotary else {}
    kv = jnp.concatenate([k_nope, v], -1)
    weigh = jnp.asarray(np.random.default_rng(12).standard_normal(
        v.shape).astype(np.float32))
    np.testing.assert_array_equal(
        ca.causal_attention(q_nope, kv=kv, **rest),
        ca.causal_attention(q_nope, k_nope, v, **rest))
    packed = jax.grad(lambda q, kv, r: jnp.sum(ca.causal_attention(
        q, kv=kv, **r) * weigh), argnums=(0, 1, 2))(q_nope, kv, rest)
    apart = jax.grad(lambda q, k, v, r: jnp.sum(ca.causal_attention(
        q, k, v, **r) * weigh), argnums=(0, 1, 2, 3))(q_nope, k_nope, v, rest)
    assert packed[1].shape == kv.shape
    np.testing.assert_array_equal(packed[0], apart[0])
    np.testing.assert_array_equal(
        packed[1], jnp.concatenate([apart[1], apart[2]], -1))
    for name in rest:
        np.testing.assert_array_equal(packed[2][name], apart[3][name])
    for wrong in (dict(), dict(kv=kv, v=v), dict(k_nope=k_nope),
                  dict(kv=kv, k_nope=k_nope, v=v)):
        with pytest.raises(ValueError, match="k_nope and v, or"):
            ca.causal_attention(q_nope, **wrong)


@pytest.mark.parametrize("j", [0, 7, 19])
def test_a_token_moves_no_output_before_it(j):
    pieces = _pieces(20, 2, seed=4)
    moved = tuple(p.at[:, j].add(1.0) for p in pieces)
    before, after = (ca.causal_attention(*p) for p in (pieces, moved))
    np.testing.assert_array_equal(before[:, :j], after[:, :j])
    assert float(jnp.abs(before[:, j:] - after[:, j:]).max()) > 1e-3


def test_a_sequence_that_is_not_whole_tiles_is_a_longer_ones_first_rows():
    """20 rows are a tile and a quarter; nothing is padded. The first 20
    tokens of a 32-token sequence see the same keys (causality), so both
    give the same rows, and the same gradients when the cotangent of the
    other 12 is zero."""
    long = _pieces(32, 2, seed=5)
    short = tuple(p[:, :20] for p in long)
    np.testing.assert_allclose(ca.causal_attention(*short),
                               ca.causal_attention(*long)[:, :20], atol=1e-6)
    weigh = jnp.asarray(np.random.default_rng(6).standard_normal(
        long[2].shape).astype(np.float32)).at[:, 20:].set(0.0)
    g_long = _gradients(ca.causal_attention, long, weigh)
    g_short = _gradients(ca.causal_attention, short, weigh[:, :20])
    for name, a, b in zip(NAMES, g_short, g_long):
        np.testing.assert_allclose(a, b[:, :20], atol=1e-5, err_msg=name)
        assert not np.any(np.asarray(b[:, 20:])), name


@pytest.mark.parametrize("n,heads", [(20, 1), (20, 4)])
def test_without_the_rotary_pieces(n, heads):
    """``q_rope=None, k_rope=None``: plain causal attention over 128-wide
    heads (the looped stack's core), scaled by 1 / sqrt(128)."""
    pieces = _pieces(n, heads, seed=7)[:3]
    np.testing.assert_allclose(ca.causal_attention(*pieces),
                               ca.reference(*pieces), atol=5e-6)
    weigh = jnp.ones(pieces[2].shape, jnp.float32)
    for g, w in zip(_gradients(ca.causal_attention, pieces, weigh),
                    _gradients(ca.reference, pieces, weigh)):
        np.testing.assert_allclose(g, w, atol=2e-5)
    with pytest.raises(ValueError, match="come together"):
        ca.causal_attention(*pieces, q_rope=_pieces(n, heads)[3])


def test_bfloat16_stays_within_its_rounding():
    """Stated tolerance: the kernel is as near the float32 result as the
    plain formulation in bfloat16 is, to within a bfloat16 rounding of the
    largest value (2^-8), forward and in every gradient."""
    exact = _pieces(20, 4, seed=8)
    rounded = tuple(p.astype(jnp.bfloat16) for p in exact)
    exact = tuple(p.astype(jnp.float32) for p in rounded)
    weigh = jnp.asarray(np.random.default_rng(9).standard_normal(
        exact[2].shape).astype(np.float32))
    got = ca.causal_attention(*rounded)
    assert got.dtype == jnp.bfloat16
    want = ca.reference(*exact)
    assert float(jnp.abs(got.astype(jnp.float32) - want).max()) \
        < 2 ** -8 * float(jnp.abs(want).max()) * 2
    for name, g, w in zip(NAMES,
                          _gradients(ca.causal_attention, rounded, weigh),
                          _gradients(ca.reference, exact, weigh)):
        assert g.dtype == jnp.bfloat16, name
        assert float(jnp.abs(g.astype(jnp.float32) - w).max()) \
            < 2 ** -8 * float(jnp.abs(w).max()) * 4, name


def test_under_jit_and_remat_the_gradients_are_the_same():
    pieces = _pieces(20, 2, seed=10)
    weigh = jnp.ones(pieces[2].shape, jnp.float32)
    plain = _gradients(ca.causal_attention, pieces, weigh)
    again = jax.jit(lambda *a: _gradients(
        jax.checkpoint(ca.causal_attention), a, weigh))(*pieces)
    for a, b in zip(plain, again):
        np.testing.assert_allclose(a, b, atol=1e-6)


@pytest.mark.parametrize("shape,takes", [
    # N, heads, nope, rope, v_dim
    ((196, 32, 128, 64, 128), True),      # Kanana-2-30B-A3B at 224 px
    ((196, 16, 128, 0, 128), True),       # the looped stack's heads
    ((196, 4, 16, 8, 12), False),         # kanana_tiny
    ((196, 32, 192, 64, 128), False),     # a head that is 1.5 lane tiles
    ((196, 32, 128, 32, 128), False),     # a rotary piece under half a tile
    ((196, 32, 128, 64, 64), False),
    ((2305, 32, 128, 64, 128), False),    # 768 px: the scores leave VMEM
], ids=["kanana_30b", "ouro", "kanana_tiny", "nope_192", "rope_32",
        "v_64", "n_2305"])
def test_the_dispatch_rule(shape, takes):
    assert ca.supports(*shape) is takes
    if not takes:
        n, heads, nope, rope, v_dim = shape

        def piece(*dims):
            return jax.ShapeDtypeStruct((1, n) + dims, jnp.bfloat16)
        with pytest.raises(ValueError, match="supports"):
            jax.eval_shape(ca.causal_attention, piece(heads, nope),
                           piece(heads, nope), piece(heads, v_dim),
                           piece(heads, rope), piece(rope))


def test_the_vmem_limit_is_arithmetic_on_the_sequence_and_the_group():
    """The cell holds every block twice and the score-sized temporaries of
    one head: at the published widths in bfloat16 8 heads a cell fit up to
    N ~ 600, 4 to ~ 800, 2 to ~ 900, nothing past ~ 950."""
    widths = (128, 64, 128, 2)
    rows = 208      # 196 to whole bfloat16 tiles, in VMEM only
    assert ca.cell_bytes(196, 8, *widths) == 2 * (
        8 * rows * 2 * (4 * 128 + 4 * 128 + 2 * 64)
        + rows * (64 * 6 + 512)) + 6 * rows * 256 * 4
    assert ca.cell_bytes(196, 8, *widths) < ca.VMEM_LIMIT_BYTES // 2
    assert ca.head_group(196, 32, *widths) == 8
    assert ca.head_group(196, 32, 128, 64, 128, 4) == 8     # float32
    assert ca.head_group(196, 3, 128, 0, 128, 2) == 3       # all of them
    assert ca.head_group(196, 3, 128, 64, 128, 2) == 3
    sizes = (512, 700, 900, 1000)
    groups = [ca.head_group(n, 32, *widths) for n in sizes]
    assert groups == [8, 4, 2, 0]
    for n, g in zip(sizes[:-1], groups):
        assert ca.cell_bytes(n, g, *widths) <= ca.VMEM_LIMIT_BYTES \
            < ca.cell_bytes(n, 2 * g, *widths)
    assert ca.cell_bytes(1000, 2, *widths) > ca.VMEM_LIMIT_BYTES
