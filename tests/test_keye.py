"""The learned-sparse-attention stack (models/keye.py) and its kernel
(kernels/sparse_attention.py) against the plain reference and on the
program's own terms: the published counts, the 2-D rotary table, a
selection that is exactly ``top_k``'s, the kernel against a masked dense
softmax, the two objectives each reaching its own parameters, the share of
a layer's experts tied to the whole layer, and the step that
differentiates the indexer's objective without reporting it."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from benchmark.reference import keye as ref
from benchmark.reference.resnet import cross_entropy
from tpuic.kernels.sparse_attention import sparse_attention
from tpuic.models import create_model, family, keye

SPEC = harness.load_spec()
CELL = "keye_vl2_30b_a3b_train_resident_px2048"
CONFIG = harness.resolve_cell(SPEC, CELL, tiny=True)["config"]
FULL = harness.resolve_cell(SPEC, CELL)["config"]
SIZE = CONFIG["image_size"]
ATTENTION = 18_874_624      # q 2048x4096, k and v 2048x512, o, q/k norms
INDEXER = 2_261_120         # q 2048x1024, k 2048x64, LayerNorm, w 2048x16
EXPERT = 4_718_592          # 3 x 2048 x 768
LAYER_8 = 59_150_720        # attention, indexer, router, 8 experts, 2 norms
LAYER = 625_381_760         # all 128


def _model(dtype="float32", depth=2):
    """The tiny stack, two layers deep (the reference reads the depth off
    the tree), in the Classifier."""
    from tpuic.models.classifier import Classifier
    return Classifier(keye.keye_tiny(depth=depth, dtype=dtype),
                      CONFIG["num_classes"], dtype=dtype)


def _variables(seed=1, model=None):
    """Seeded weights with nothing left at its initial value (norm scales
    of 1 and biases of 0 would hide how they enter)."""
    v = harness.plain_variables((model or _model()).init(
        jax.random.key(seed), jnp.zeros((1, SIZE, SIZE, 3)), train=False))
    rng = np.random.default_rng(seed)
    return jax.tree_util.tree_map(
        lambda a: a + 0.05 * rng.standard_normal(a.shape).astype(np.float32),
        v)


def _batch(rows=2, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal((rows, SIZE, SIZE, 3)).astype(np.float32),
            rng.integers(0, CONFIG["num_classes"], rows).astype(np.int32))


def _program(model, params, images, labels):
    """``(cross-entropy, L^I)`` of the program's train-mode forward."""
    out, sown = model.apply({"params": params}, images, train=True,
                            mutable=["intermediates", "counters"])
    kl = sown["intermediates"]["backbone"]["auxiliary_objective"][0]
    return jnp.stack([cross_entropy(out, labels), kl])


@pytest.fixture(scope="module")
def float32_pass():
    """One float32 pass of program and reference on the same seeded
    weights and batch, each one compiled program: the program's eval
    logits, its two objectives and the gradient of each; the reference's
    logits and KL, and train_loss's value and gradient."""
    from benchmark.reference.resnet import Mode
    with jax.default_matmul_precision("highest"):
        v, (images, labels) = _variables(), _batch()
        model = _model()

        @jax.jit
        def program(v):
            objectives, pull = jax.vjp(
                lambda p: _program(model, p, images, labels), v["params"])
            return (model.apply(v, images, train=False), objectives,
                    pull(jnp.array([1.0, 0.0]))[0],
                    pull(jnp.array([0.0, 1.0]))[0])

        @jax.jit
        def reference(v):
            logits, kl = ref._logits(v, images, CONFIG,
                                     Mode(train=True, remat=True), True)
            return logits, kl, jax.value_and_grad(
                lambda p: ref.train_loss({"params": p}, images, labels,
                                         CONFIG))(v["params"])
        logits, (ce, kl), by_ce, by_kl = program(v)
        ref_logits, ref_kl, (ref_loss, ref_grad) = reference(v)
        return {"variables": v, "images": images, "logits": logits,
                "ce": float(ce), "kl": float(kl), "by_ce": by_ce,
                "by_kl": by_kl, "ref_logits": ref_logits,
                "ref_ce": float(cross_entropy(ref_logits, labels)),
                "ref_kl": float(ref_kl), "ref_loss": ref_loss,
                "ref_grad": ref_grad}


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


# -- against the reference --------------------------------------------------

def test_float32_eval_logits_agree_with_the_reference(float32_pass):
    """The same mathematics in another order of summation, and the same
    selection."""
    got = float32_pass["logits"]
    assert got.dtype == jnp.float32 and got.shape == (2, 10)
    assert harness.centred_error(got, float32_pass["ref_logits"]) < 2e-5


def test_bfloat16_eval_logits_stay_near_the_reference(float32_pass):
    """Rounding through two layers, a choice of keys and of experts that
    rounding moves."""
    v, images = float32_pass["variables"], float32_pass["images"]
    got = _model("bfloat16").apply(v, images, train=False)
    assert harness.centred_error(got, float32_pass["ref_logits"]) < 0.1


def test_loss_kl_and_gradient_agree_leaf_by_leaf(float32_pass):
    """The cross-entropy the step reports, the indexer's KL, and the
    gradient of their sum, leaf by leaf; the reference's train_loss has
    the cross-entropy's value and the sum's gradient."""
    f = float32_pass
    assert abs(f["ce"] - f["ref_ce"]) < 1e-5
    assert abs(f["kl"] / f["ref_kl"] - 1) < 1e-4 and f["kl"] > 0.05
    assert abs(float(f["ref_loss"]) - f["ce"]) < 1e-5
    got = jax.tree_util.tree_map(lambda a, b: a + b, f["by_ce"], f["by_kl"])
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(f["ref_grad"])):
        scale = float(jnp.max(jnp.abs(w))) + 1e-12
        assert float(jnp.max(jnp.abs(g - w))) / scale < 2e-3, \
            jax.tree_util.keystr(path)


def test_each_objective_reaches_its_own_parameters_and_no_other(
        float32_pass):
    """The selection is discrete and the indexer reads the stream under
    stop_gradient: the cross-entropy gives every indexer leaf a zero
    gradient, the KL gives a gradient to those leaves only."""
    counted = [0, 0]
    for (name, c), k in zip(
            ((jax.tree_util.keystr(p), leaf) for p, leaf in
             jax.tree_util.tree_leaves_with_path(float32_pass["by_ce"])),
            jax.tree_util.tree_leaves(float32_pass["by_kl"])):
        if "'indexer'" in name:
            counted[0] += 1
            assert float(jnp.max(jnp.abs(c))) == 0.0, name
            assert float(jnp.max(jnp.abs(k))) > 0.0, name
        else:
            counted[1] += 1
            assert float(jnp.max(jnp.abs(k))) == 0.0, name
    # a layer's indexer: q, k, k_norm's scale and bias, w; beside it q, k,
    # v, o, the q and k norms, two block norms, the router, two expert
    # tensors; the patch embedding, the closing norm and the head's 8
    assert counted == [2 * 5, 2 * 11 + 3 + 8]


# -- the selection ----------------------------------------------------------

def _top_k_set(scores, k):
    """The reference's rule: ``lax.top_k``'s indices, past-the-query ones
    dropped (zeros of either sign are one value)."""
    scores = jnp.where(scores == 0, 0.0, scores)
    n = scores.shape[-1]
    _, idx = jax.lax.top_k(scores, min(k, n))
    sel = np.zeros(scores.shape, bool)
    np.put_along_axis(sel, np.asarray(idx), True, axis=-1)
    return sel & np.isfinite(np.asarray(scores))


@pytest.mark.parametrize("ties", [False, True], ids=["distinct", "ties"])
@pytest.mark.parametrize("first", [0, 64, 192])
def test_the_selection_is_top_ks_set(first, ties):
    """Rows before the top_k-th key (all their causal keys), rows past it,
    and planted ties (scores on a grid of eighths, many equal at the
    boundary), including the -inf past each query."""
    rng = np.random.default_rng(first + ties)
    b, c, n, k = 2, 64, 256, 48
    scores = rng.standard_normal((b, c, n)).astype(np.float32)
    if ties:
        scores = np.round(scores * 2) / 8
        scores[:, :, 40:120] = 0.0          # a run of equal keys
        scores[:, :, 60:80] = -0.0          # of both signs
    t = first + np.arange(c)[:, None]
    scores = np.where(np.arange(n)[None] <= t, scores, -np.inf)
    got = np.asarray(keye.select(jnp.asarray(scores), k))
    want = _top_k_set(jnp.asarray(scores), k)
    np.testing.assert_array_equal(got, want)
    assert (got.sum(-1) == np.minimum(t[:, 0] + 1, k)[None]).all()


def _tiles_held(sel, block_q: int, block_k: int) -> np.ndarray:
    """By hand: 1 where the tile of ``sel`` [B, N, N] holds a pair."""
    b, n, _ = sel.shape
    live = np.zeros((b, n // block_q, n // block_k), np.int32)
    for i in range(n // block_q):
        for j in range(n // block_k):
            live[:, i, j] = np.asarray(sel)[:, i * block_q:(i + 1) * block_q,
                                            j * block_k:(j + 1) * block_k
                                            ].any(axis=(1, 2))
    return live


def test_the_selection_mask_is_the_scores_top_k_chunk_by_chunk():
    """``selection_mask`` over four query chunks against ``top_k`` of the
    whole score matrix, and its tile map and pair count."""
    rng = np.random.default_rng(3)
    b, n, j, e, k = 1, 256, 4, 32, 48
    qi = jnp.asarray(rng.standard_normal((b, n, j, e)), jnp.float32)
    ki = jnp.asarray(rng.standard_normal((b, n, e)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((b, n, j)), jnp.float32)
    mask, live, pairs = keye.selection_mask(qi, ki, w, k, 64, 64)
    scores = keye.index_scores(qi, ki, w, 0)
    want = _top_k_set(scores, k)
    np.testing.assert_array_equal(np.asarray(mask) != 0, want)
    assert int(pairs[0]) == want.sum() == \
        k * (k + 1) // 2 + (n - k) * k
    np.testing.assert_array_equal(live, _tiles_held(want, 64, 64))


@pytest.mark.parametrize("planted", [False, True], ids=["distinct",
                                                        "planted"])
def test_the_tie_rows_are_the_rows_cut_inside_a_run_by_hand(planted):
    """``selection`` counts, an image, the rows whose ``top_k``-th score
    has an equal one left out. Positive queries, keys and weights keep
    every ReLU open, so the scores are distinct (0); with keys planted in
    pairs every score has a twin, and the rows that cut between twins are
    counted by hand from the scores sorted in float64."""
    rng = np.random.default_rng(4)
    b, n, j, e, k = 2, 256, 4, 32, 48
    qi = np.abs(rng.standard_normal((b, n, j, e))).astype(np.float32)
    ki = np.abs(rng.standard_normal((b, n, e))).astype(np.float32)
    w = np.abs(rng.standard_normal((b, n, j))).astype(np.float32)
    if planted:
        ki[:, 1::2] = ki[:, 0::2]
    *_, ties = keye.selection(*(jnp.asarray(t) for t in (qi, ki, w)), k,
                              64, 64)
    scores = np.einsum("bqj,bqje,bke->bqk", w.astype(np.float64),
                       qi.astype(np.float64), ki.astype(np.float64))
    want = np.zeros(b, int)
    for i in range(b):
        for t in range(k - 1, n):       # rows that hold k keys or more
            row = np.sort(scores[i, t, :t + 1])[::-1]
            want[i] += (row >= row[k - 1]).sum() > k
    np.testing.assert_array_equal(ties, want)
    assert (want > 0).all() if planted else (want == 0).all()


# -- the kernel -------------------------------------------------------------

def _dense(q, k, v, sel):
    group = q.shape[2] // k.shape[2]
    kk, vv = (jnp.repeat(t, group, axis=2) for t in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kk) / math.sqrt(q.shape[-1])
    s = jnp.where(sel[:, None], s, -jnp.inf)
    return (jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), vv),
            jax.scipy.special.logsumexp(s, axis=-1))


@pytest.mark.parametrize("empty", [False, True], ids=["every_tile",
                                                      "empty_tiles"])
def test_the_kernel_is_a_masked_softmax_forward_and_backward(empty):
    """Interpret mode, 2 images of 256 tokens, 4 query heads over 2, tiles
    of 64; with tiles that hold no selected pair (not computed, their
    blocks not fetched) and rows whose first tiles hold none."""
    rng = np.random.default_rng(7)
    b, n, h, hkv, d = 2, 256, 4, 2, 16
    q, k, v = (jnp.asarray(rng.standard_normal(s), jnp.float32)
               for s in ((b, n, h, d), (b, n, hkv, d), (b, n, hkv, d)))
    t = np.arange(n)
    sel = (rng.random((b, n, n)) < 0.15) & (t[None] <= t[:, None])
    sel[:, t, t] = True
    if empty:
        sel[0, 128:192, :128] = False
        sel[1, 192:, 64:192] = False
        sel[1, :, 0:64] = False
        sel[1, t[:64], t[:64]] = True
    mask = jnp.asarray(sel.astype(np.int8))
    live = jnp.asarray(_tiles_held(sel, 64, 64))
    assert int(live.sum()) == (2 * 10 - 7 if empty else 2 * 10)
    out, lse = sparse_attention(q, k, v, mask, live, 64, 64)
    want, want_lse = _dense(q, k, v, jnp.asarray(sel))
    np.testing.assert_allclose(out, want, atol=2e-5)
    np.testing.assert_allclose(lse, want_lse, atol=2e-5)
    cot = jnp.asarray(rng.standard_normal(out.shape), jnp.float32)
    got = jax.grad(lambda *a: jnp.sum(sparse_attention(
        *a, mask, live, 64, 64)[0] * cot), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(_dense(*a, jnp.asarray(sel))[0]
                                       * cot), (0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=5e-5)


# -- the rotary table -------------------------------------------------------

def test_the_mrope_table_turns_row_and_column_by_their_sections():
    """Against a table written out by hand for the 128 x 128 grid at the
    published width: pairs 0-15 read the first coordinate (0: unturned),
    16-39 the row, 40-63 the column; the second half repeats the first."""
    cos, sin = keye.mrope_tables(16384, 128, 1e7)
    assert cos.shape == sin.shape == (16384, 128)
    token = 5 * 128 + 77                    # row 5, column 77
    for i in range(64):
        f = 1e7 ** (-2 * i / 128)
        coord = 0 if i < 16 else (5 if i < 40 else 77)
        assert cos[token, i] == pytest.approx(math.cos(coord * f), abs=1e-6)
        assert sin[token, i] == pytest.approx(math.sin(coord * f), abs=1e-6)
        assert cos[token, 64 + i] == cos[token, i]
    np.testing.assert_array_equal(cos[:, :16], 1.0)
    np.testing.assert_array_equal(sin[:, :16], 0.0)
    # the indexer's 32 rotated dimensions: 16 pairs in sections 4/6/6
    cos, sin = keye.mrope_tables(16384, 32, 1e7)
    np.testing.assert_array_equal(sin[:, :4], 0.0)
    assert sin[token, 4] == pytest.approx(math.sin(5 * 1e7 ** (-8 / 32)),
                                          abs=1e-6)
    assert sin[token, 10] == pytest.approx(math.sin(77 * 1e7 ** (-20 / 32)),
                                           abs=1e-6)
    # the reference's own table is the same
    c, s = ref.mrope_angles(16384, 128, 1e7, [16, 24, 24])
    np.testing.assert_allclose(keye.mrope_tables(16384, 128, 1e7)[1][:, :64],
                               s, atol=1e-6)


# -- the cut ------------------------------------------------------------------

def test_the_shares_of_a_layer_add_up_to_the_uncut_reference():
    """Expert parallelism's rule for a cut: the routed parts that the two
    shares of 8 experts give, plus the attention branch once (every chip
    computes it, the indexer and the router alike), are the whole layer as
    the plain reference computes it uncut."""
    from benchmark.reference.mellum import expert_layer
    from benchmark.reference.resnet import EVAL
    from tpuic.models.mellum import SoftmaxExpertLayer
    x = jnp.asarray(np.random.default_rng(3).standard_normal((2, 256, 64)),
                    jnp.float32)
    block = keye.SparseMoeBlock(4, 2, 16, 1e4, 4, 32, 48, 16, (0, 16), 24, 3,
                                blocks=(64, 64), with_loss=False)
    p = harness.plain_variables(block.init(jax.random.key(5), x))["params"]
    uncut = {**CONFIG, "experts_held_first": 0}
    whole = ref._block(x, p, uncut, EVAL, False)[0]
    eps = CONFIG["rms_norm_eps"]
    h = x + jax.lax.map(lambda row: ref._image_attention(
        row, p["attn"], CONFIG, EVAL, False)[0],
        ref._rms_norm(x, p["attn_norm"], eps))
    y = ref._rms_norm(h, p["mlp_norm"], eps)
    total = h
    for first in (0, 8):
        mine = {**p["moe"],
                "experts_gate_up": p["moe"]["experts_gate_up"][first:first + 8],
                "experts_down": p["moe"]["experts_down"][first:first + 8]}
        part = SoftmaxExpertLayer(16, (first, 8), 24, 3).apply(
            {"params": mine}, y, mutable=["counters"])[0]
        # the reference is given the same share and agrees on it
        np.testing.assert_allclose(
            part, expert_layer(y, mine, {**CONFIG,
                                         "experts_held_first": first}),
            rtol=2e-4, atol=2e-5)
        assert float(jnp.max(jnp.abs(part))) > 0.01
        total = total + part
    np.testing.assert_allclose(total, whole, rtol=2e-4, atol=2e-5)
    # and the program's own block, holding all 16, is that layer too
    np.testing.assert_allclose(
        block.apply({"params": p}, x, mutable=["counters"])[0][0], whole,
        rtol=2e-4, atol=2e-5)


def test_parameter_counts_are_the_published_models():
    """The published widths and counts by hand (by hand): a
    layer with 8 experts held, four of them with the patch embedding, the
    closing norm and the head, and a layer with all 128."""
    def count(model, size=512):
        shapes = jax.eval_shape(lambda: model.init(
            jax.random.key(0), jnp.zeros((1, size, size, 3)), train=False))
        return sum(math.prod(a.shape) for a in jax.tree_util.tree_leaves(
            harness.plain_variables(shapes)))

    here = create_model("keye-vl2-30b-a3b-l4e8", 1000)
    assert count(here) == 238_485_448 == FULL["parameters_here"]
    one = create_model("keye-vl2-30b-a3b", 1000)
    assert one.backbone.depth == 48 and one.backbone.held == (0, 128)
    stack = keye.keye_vl2_30b_a3b(depth=1, held=(0, 8))
    layer = count(stack) - 16 * 16 * 3 * 2048 - 2048 - 2048
    assert layer == LAYER_8 == ATTENTION + INDEXER + 2048 * 128 \
        + 8 * EXPERT + 2 * 2048
    assert LAYER == LAYER_8 + 120 * EXPERT
    assert 48 * LAYER == 30_018_324_480


def test_the_family_is_registered_with_block_remat():
    assert family("keye-vl2-30b-a3b-l4e8").remat_policies == {"blocks"}
    stack = create_model("keye-vl2-30b-a3b-l4e8", 1000).backbone
    assert (stack.hidden, stack.num_heads, stack.kv_heads, stack.head_dim,
            stack.indexer_heads, stack.indexer_dim, stack.top_k,
            stack.rope_theta, stack.expert_width, stack.experts_per_token,
            stack.num_experts, stack.held, stack.depth, stack.blocks) == (
        2048, 32, 4, 128, 16, 64, 2048, 1e7, 768, 8, 128, (0, 8), 4,
        (512, 512))
    sa = FULL["sa_config"]
    assert (sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"],
            sa["q_chunk_size"], sa["kv_chunk_size"]) == (16, 64, 2048, 512,
                                                         512)


# -- the step -----------------------------------------------------------------

def test_the_step_differentiates_the_kl_reports_the_loss_and_the_counters(
        float32_pass):
    """The real train step: its loss is the cross-entropy, its gradient
    that of the cross-entropy plus the KL (plain SGD at rate 1 moves each
    parameter by it on the first step, momentum or not), and the stack's counters reach its metrics."""
    import train
    from tpuic.train.optimizer import make_optimizer
    from tpuic.train.state import TrainState
    from tpuic.train.step import make_train_step
    v, (images, labels) = _variables(), _batch()
    args = train.build_parser().parse_args(
        [*CONFIG["train_flags"], "--optimizer", "sgd",
         "--lr", "1", "--no-class-weights", "--milestones", "--datadir",
         "x", "--dtype", "float32"])
    cfg = train.config_from_args(args)
    model = _model()
    tx = make_optimizer(cfg.optim, 8, 1, global_batch=2)
    state = TrainState(step=jnp.zeros((), jnp.int32), params=v["params"],
                       batch_stats={}, opt_state=tx.init(v["params"]),
                       apply_fn=model.apply, tx=tx, ema_params=None,
                       skip_count=jnp.zeros((), jnp.int32))
    step = make_train_step(cfg.optim, cfg.model, mesh=None, donate=False)
    after, metrics = step(state, {"image": jnp.asarray(images),
                                  "label": jnp.asarray(labels),
                                  "mask": jnp.ones(2, jnp.float32)})
    assert abs(float(metrics["loss"]) - float32_pass["ce"]) < 1e-5
    assert abs(float(metrics["indexer_kl"]) - float32_pass["kl"]) < 1e-5
    assert float(metrics["attention_key_blocks_square"]) == 2 * 16
    assert float(metrics["attention_key_blocks_visited"]) <= 2 * 10
    assert float(metrics["attention_pairs_selected"]) == \
        2 * (48 * 49 // 2 + (256 - 48) * 48)
    # the rows cut inside a run of equal scores, as the forward counts them
    _, sown = model.apply(v, jnp.asarray(images), train=False,
                          mutable=["counters"])
    ties = float(sown["counters"]["backbone"]["selection_tie_rows"][0])
    assert float(metrics["selection_tie_rows"]) == ties
    assert ties == int(ties) and 0 <= ties <= 2 * 256
    want = jax.tree_util.tree_map(lambda a, b: a + b, float32_pass["by_ce"],
                                  float32_pass["by_kl"])
    moved = jax.tree_util.tree_map(lambda a, b: a - b, v["params"],
                                   after.params)
    for (path, m), g in zip(jax.tree_util.tree_leaves_with_path(moved),
                            jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(m, g, rtol=2e-3, atol=1e-6,
                                   err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("cell, want", [
    ("resnet50_train_resident", "b8da1f3f9aacad57"),
    ("vit_b16_train_resident", "7679fcdd4beddb84")])
def test_a_step_that_sows_no_auxiliary_term_lowers_as_before(cell, want):
    """The step adds the terms a backbone sows under
    ``auxiliary_objective``; a family that sows none lowers the step it
    lowered before the hook was written. Pinned here for the convolutional
    and the ViT cells, at their cells' flags and batch 2, to the byte
    (sha256 with this installation's jax, 0.9.0; the decoder cells' steps
    are pinned in tests/test_mellum.py). After an upgrade of jax the
    digests go stale with no fault in the tree: unpack the commit that
    last wrote them (``git archive <commit> | tar -x -C <dir>``), run this
    test's body there under the new jax, and write the digests it gives
    here."""
    import hashlib
    from flax.core import meta
    import train
    from tpuic.models import create_model_from_config
    from tpuic.train.optimizer import make_optimizer
    from tpuic.train.state import TrainState
    from tpuic.train.step import make_train_step
    resolved = harness.resolve_cell(SPEC, cell)
    cfg = train.config_from_args(train.build_parser().parse_args(
        [*resolved["config"]["train_flags"],
         *resolved["traffic"]["train_flags"], "--datadir", "x"]))
    model = create_model_from_config(cfg.model)
    side = int(resolved["config"]["image_size"])
    x = jnp.zeros((2, side, side, 3))
    v = meta.unbox(jax.eval_shape(
        lambda: model.init(jax.random.key(0), x, train=False)))
    tx = make_optimizer(cfg.optim, 8, 1, global_batch=2)
    state = jax.eval_shape(lambda v: TrainState(
        step=jnp.zeros((), jnp.int32), params=v["params"],
        batch_stats=v.get("batch_stats", {}), opt_state=tx.init(v["params"]),
        apply_fn=model.apply, tx=tx, ema_params=None,
        skip_count=jnp.zeros((), jnp.int32)), v)
    batch = {"image": jax.ShapeDtypeStruct(x.shape, jnp.float32),
             "label": jax.ShapeDtypeStruct((2,), jnp.int32),
             "mask": jax.ShapeDtypeStruct((2,), jnp.float32)}
    with jax.default_matmul_precision(None):    # as a run lowers it
        text = make_train_step(cfg.optim, cfg.model, mesh=None,
                               donate=False).lower(state, batch).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == want, cell
