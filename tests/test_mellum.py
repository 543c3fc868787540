"""The sliding-window, grouped-head, softmax-routed stack (models/mellum.py)
against its plain reference and on the program's own terms: the published
counts, a rotary table per kind of layer, the band the kernel visits, the
share of a layer's experts tied to the whole layer, and counters that reach
every sink."""

import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from benchmark.reference import mellum as ref
from tpuic.models import create_model, family, mellum

SPEC = harness.load_spec()
CELL = "mellum2_12b_a2.5b_train_resident_px1024"
CONFIG = harness.resolve_cell(SPEC, CELL, tiny=True)["config"]
FULL = harness.resolve_cell(SPEC, CELL)["config"]
SIZE = CONFIG["image_size"]
ATTENTION = 21_233_664          # q 2304x4096, k and v 2304x512, o 4096x2304
EXPERT = 6_193_152              # 3 x 2304 x 896
LAYER_8 = 70_930_944            # attention, two norms, router, 8 experts
LAYER = 417_747_456             # all 64


def _model(dtype="float32", **fields):
    return create_model("mellum-tiny", CONFIG["num_classes"], dtype=dtype,
                        **fields)


def _variables(seed=1):
    """Seeded weights with nothing left at its initial value (norm scales
    of 1 would hide how they enter)."""
    v = harness.plain_variables(_model().init(
        jax.random.key(seed), jnp.zeros((1, SIZE, SIZE, 3)), train=False))
    rng = np.random.default_rng(seed)
    return jax.tree_util.tree_map(
        lambda a: a + 0.05 * rng.standard_normal(a.shape).astype(np.float32),
        v)


def _batch(rows=4, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal((rows, SIZE, SIZE, 3)).astype(np.float32),
            rng.integers(0, CONFIG["num_classes"], rows).astype(np.int32))


def _program_loss(model, params, images, labels):
    from benchmark.reference.resnet import cross_entropy
    return cross_entropy(model.apply({"params": params}, images, train=True),
                         labels)


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


# -- against the reference --------------------------------------------------

@pytest.mark.parametrize("dtype,tolerance", [
    # float32: the same mathematics in another order of summation
    ("float32", 2e-5),
    # bfloat16: rounding through four layers, and a choice of expert that
    # rounding moves (the CPU readings of the configuration's tiny block
    # read 0.017-0.033)
    ("bfloat16", 0.08)])
def test_eval_logits_agree_with_the_reference(dtype, tolerance):
    v, (images, _) = _variables(), _batch()
    got = _model(dtype).apply(v, images, train=False)
    assert got.dtype == jnp.float32 and got.shape == (4, 10)
    assert harness.centred_error(got, ref.forward(v, images, CONFIG)) \
        < tolerance


def test_loss_and_gradient_agree_leaf_by_leaf():
    """float32: every leaf's gradient within 1e-4 of the leaf's largest
    entry (summation order; the kernel in interpret mode against the dense
    masked softmax of the reference)."""
    v, (images, labels) = _variables(), _batch()
    model = _model(remat=True, remat_policy="blocks")
    loss, grads = jax.value_and_grad(
        lambda p: _program_loss(model, p, images, labels))(v["params"])
    want, want_grads = jax.value_and_grad(
        lambda p: ref.train_loss({"params": p}, images, labels, CONFIG))(
            v["params"])
    assert abs(float(loss) - float(want)) < 1e-5 * abs(float(want))
    worst = jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))
                           / (jnp.max(jnp.abs(b)) + 1e-12)),
        grads, want_grads)
    assert max(jax.tree_util.tree_leaves(worst)) < 1e-4, worst


def test_bfloat16_gradients_stay_near_the_reference():
    """bfloat16 compute: the norm of each leaf's gradient within a quarter
    of the reference's, and of the median leaf's within 5 % (the cell's
    limit on the median leaf is read on the chip; this is the CPU's
    plumbing check that no leaf is lost or doubled)."""
    v, (images, labels) = _variables(), _batch()
    model = _model("bfloat16")
    grads = jax.grad(lambda p: _program_loss(model, p, images, labels))(
        v["params"])
    want = jax.grad(lambda p: ref.train_loss({"params": p}, images, labels,
                                             CONFIG))(v["params"])
    ratio = jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        lambda a, b: float(jnp.linalg.norm(a) / (jnp.linalg.norm(b) + 1e-30)),
        grads, want))
    assert 0.75 < min(ratio) and max(ratio) < 1.25, ratio
    assert abs(float(np.median(ratio)) - 1.0) < 0.05


def _layer_sizes(name):
    from flax.core import meta
    model = create_model(name, 1000)
    shapes = meta.unbox(jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 64, 64, 3)), train=False)))["params"]

    def count(tree):
        return sum(math.prod(a.shape) for a in jax.tree_util.tree_leaves(tree))
    return ({k: count(v) for k, v in shapes["backbone"].items()},
            count(shapes["head"]), count(shapes))


def test_parameter_counts_are_the_published_models():
    """By ``eval_shape`` alone (nothing is allocated): 28 layers of
    417,747,456 = 11.70 B in the stack; the published 12.15 B has 453 M of
    vocabulary beside it (2 x 98,304 x 2,304), which no backbone here
    holds."""
    whole, _, _ = _layer_sizes("mellum2-12b-a2.5b")
    assert [whole[f"layer{i}"] for i in range(28)] == [LAYER] * 28
    assert "layer28" not in whole
    assert LAYER == ATTENTION + 2 * 2304 + 2304 * 64 + 64 * EXPERT
    assert LAYER - LAYER_8 == 56 * EXPERT
    assert abs((28 * LAYER + 2 * 98_304 * 2304) / 12.15e9 - 1) < 0.005
    cut, head, here = _layer_sizes("mellum2-12b-a2.5b-l4e8")
    assert [cut[f"layer{i}"] for i in range(4)] == [LAYER_8] * 4
    assert "layer4" not in cut
    assert cut["patch_embed"] == 16 * 16 * 3 * 2304 + 2304 == 1_771_776
    assert cut["norm_final"] == 2304 and head == 338_376
    assert here == 4 * LAYER_8 + 1_771_776 + 2304 + 338_376 \
        == FULL["parameters_here"] == 285_836_232


def test_the_flags_build_the_configurations_widths_and_the_count_is_by_hand():
    import train
    from benchmark.layer_metrics import _banded
    args = train.build_parser().parse_args(
        [*FULL["train_flags"], "--datadir", "x"])
    cfg = train.config_from_args(args)
    assert cfg.model.remat and cfg.model.remat_policy == "blocks"
    assert cfg.data.resize_size == FULL["image_size"] == 1024
    b = create_model(cfg.model.name, 1000).backbone
    yarn = FULL["rope_parameters"]["full_attention"]
    assert (b.hidden, b.layer_types, b.num_heads, b.kv_heads, b.head_dim,
            b.window, b.rope_theta, b.yarn, b.num_experts, b.held,
            b.expert_width, b.top_k, b.norm_topk, b.eps, b.patch) == (
        FULL["hidden_size"],
        tuple(FULL["layer_types"][:FULL["num_hidden_layers"]]),
        FULL["num_attention_heads"], FULL["num_key_value_heads"],
        FULL["head_dim"], FULL["sliding_window"],
        FULL["rope_parameters"]["sliding_attention"]["rope_theta"],
        (yarn["factor"], yarn["original_max_position_embeddings"],
         yarn["beta_fast"], yarn["beta_slow"], yarn["attention_factor"]),
        FULL["published"]["num_experts"],
        (FULL["experts_held_first"], FULL["num_experts"]),
        FULL["moe_intermediate_size"], FULL["num_experts_per_tok"],
        FULL["norm_topk_prob"], FULL["rms_norm_eps"], FULL["patch"])
    assert yarn["rope_theta"] == b.rope_theta and b.blocks is None
    assert FULL["layer_types"] == list(mellum.BandMoeStack.layer_types)
    # by hand, a token a layer (multiply-adds, forward)
    tokens = FULL["tokens"]
    assert tokens == (1024 // 16) ** 2 == 4096
    sliding = _banded.core_macs_per_token_per_layer(FULL, "sliding_attention")
    full = _banded.core_macs_per_token_per_layer(FULL, "full_attention")
    assert _banded.pairs_seen(4096, 1024) == 3_670_528
    assert sliding == 32 * 2 * 128 * 3_670_528 / 4096 == 7_341_056
    assert full == 32 * 2 * 128 * 2048.5 == 16_781_312
    assert abs((3 * sliding + full) / 4 / 9.70e6 - 1) < 0.001
    routed = 8 * 8 / 64 * EXPERT
    layer = ATTENTION + 2304 * 64 + routed
    period = 3 * (layer + sliding) + layer + full
    assert abs(period / 149.1e6 - 1) < 0.001
    by_hand = period * tokens + tokens * 16 * 16 * 3 * 2304
    assert abs(by_hand / (FULL["forward_gmacs_per_image_here"] * 1e9) - 1) \
        < 0.002
    # the core's required work a step, as the roofline reader takes it
    step = _banded.core_flops_per_step(FULL, 4)
    assert step == 6 * (3 * sliding + full) * tokens * 4
    assert abs(step / (6 * by_hand * 4) - 0.26) < 0.005
    assert _banded.core_flops_per_step({"image_size": 224}, 4) is None


# -- the rotary tables -------------------------------------------------------

def test_a_table_a_kind_of_layer_yarn_on_the_full_ones():
    """The published YaRN by hand: of 64 frequencies the first 18 as they
    are, those from the 35th on divided by 16, a linear ramp between; cos
    and sin times the attention factor, which is 0.1 ln 16 + 1."""
    freq = mellum.yarn_inv_freq(128, 500000.0, 16.0, 8192, 32.0, 1.0)
    plain = 500000.0 ** (-np.arange(64) / 64.0)
    np.testing.assert_allclose(freq[:19], plain[:19], rtol=1e-6)
    np.testing.assert_allclose(freq[35:], plain[35:] / 16, rtol=1e-6)
    mid = 1 - (26 - 18) / (35 - 18)
    np.testing.assert_allclose(freq[26], plain[26] * (mid + (1 - mid) / 16),
                               rtol=1e-6)
    assert abs(mellum.YARN[4] - (0.1 * math.log(16) + 1)) < 1e-12
    cos, sin = mellum.layer_rotary_tables(4096, 128, 500000.0, mellum.YARN)
    plain_cos, plain_sin = mellum.layer_rotary_tables(4096, 128, 500000.0,
                                                      None)
    from tpuic.models.ouro import rotary_tables
    np.testing.assert_array_equal(plain_cos,
                                  rotary_tables(4096, 128, 500000.0)[0])
    np.testing.assert_allclose(cos ** 2 + sin ** 2, mellum.YARN[4] ** 2,
                               rtol=1e-5)
    np.testing.assert_allclose(plain_cos ** 2 + plain_sin ** 2, 1.0,
                               rtol=1e-5)
    # against the reference's own table, which shares no code with it
    want_cos, want_sin = ref.rotary_table(
        FULL["rope_parameters"]["full_attention"], 4096, 128)
    np.testing.assert_allclose(cos[:, :64], want_cos, atol=2e-4)
    np.testing.assert_allclose(sin[:, 64:], want_sin, atol=2e-4)


@pytest.mark.parametrize("kind,window,yarn", [
    ("sliding_attention", 64, None),
    ("full_attention", None, (16.0, 64, 32.0, 1.0, mellum.YARN[4]))])
def test_a_layer_of_each_kind_agrees_with_the_references(kind, window, yarn):
    from benchmark.reference.resnet import EVAL
    x = jnp.asarray(np.random.default_rng(0).standard_normal((2, 256, 64)),
                    jnp.float32)
    layer = mellum.GroupedBandAttention(4, 2, 16, window, 10000.0, yarn,
                                        (64, 64))
    v = harness.plain_variables(layer.init(jax.random.key(0), x))
    np.testing.assert_allclose(
        layer.apply(v, x), ref._attention(x, v["params"], kind, CONFIG, EVAL),
        rtol=1e-4, atol=1e-5)


# -- the share of a layer's experts ------------------------------------------

def _expert_layer(held, first=0, experts=16):
    return mellum.SoftmaxExpertLayer(experts, (first, held), 24, 3)


def test_the_shares_of_a_layer_add_up_to_the_uncut_reference():
    """The model-configs guide, section 4: the routed parts that the two
    shares of 8 experts give, plus the attention branch once (every chip
    computes it alike), are the whole layer as the plain reference computes
    it uncut."""
    from benchmark.reference.resnet import EVAL
    x = jnp.asarray(np.random.default_rng(3).standard_normal((2, 256, 64)),
                    jnp.float32)
    block = mellum.BandMoeBlock(4, 2, 16, 64, 10000.0, None, 16, (0, 16), 24,
                                3, blocks=(64, 64))
    p = harness.plain_variables(block.init(jax.random.key(5), x))["params"]
    whole = ref._block(x, p, "sliding_attention",
                       {**CONFIG, "experts_held_first": 0}, EVAL)
    eps = CONFIG["rms_norm_eps"]
    h = x + ref._attention(ref._rms_norm(x, p["attn_norm"], eps), p["attn"],
                           "sliding_attention", CONFIG, EVAL)
    y = ref._rms_norm(h, p["mlp_norm"], eps)
    total = h
    for first in (0, 8):
        mine = {**p["moe"],
                "experts_gate_up": p["moe"]["experts_gate_up"][first:first + 8],
                "experts_down": p["moe"]["experts_down"][first:first + 8]}
        part = _expert_layer(8, first).apply({"params": mine}, y)
        # the reference is given the same share and agrees on it
        np.testing.assert_allclose(
            part, ref.expert_layer(y, mine, {**CONFIG,
                                             "experts_held_first": first}),
            rtol=2e-4, atol=2e-5)
        assert float(jnp.max(jnp.abs(part))) > 0.01
        total = total + part
    np.testing.assert_allclose(total, whole, rtol=2e-4, atol=2e-5)
    # and the program's own block, holding all 16, is that layer too
    np.testing.assert_allclose(block.apply({"params": p}, x), whole,
                               rtol=2e-4, atol=2e-5)


def test_the_weights_of_a_token_are_its_top_ks_share_of_the_softmax():
    x = jnp.asarray(np.random.default_rng(1).standard_normal((1, 8, 64)),
                    jnp.float32)
    layer = _expert_layer(16)
    p = harness.plain_variables(layer.init(jax.random.key(2), x))["params"]
    weights = np.asarray(ref.routing_weights(x, p, CONFIG))
    assert ((weights > 0).sum(-1) == 3).all()
    np.testing.assert_allclose(weights.sum(-1), 1.0, rtol=1e-6)
    probs = np.asarray(jax.nn.softmax(x @ p["router"], axis=-1))
    np.testing.assert_array_equal(np.argsort(weights, -1)[..., -3:],
                                  np.argsort(probs, -1)[..., -3:])
    plain = np.asarray(ref.routing_weights(
        x, p, {**CONFIG, "norm_topk_prob": False}))
    np.testing.assert_allclose(plain, np.where(weights > 0, probs, 0.0),
                               rtol=1e-5)
    unnormed = mellum.SoftmaxExpertLayer(16, (0, 16), 24, 3, norm_topk=False)
    np.testing.assert_allclose(
        unnormed.apply({"params": p}, x),
        ref.expert_layer(x, p, {**CONFIG, "norm_topk_prob": False}),
        rtol=2e-4, atol=2e-5)


def test_an_overflowing_layer_goes_through_the_buffer_in_passes(monkeypatch):
    """``routed_sum`` where the worst case is over
    ``ONE_BUFFER_WORST_ROWS``: a step whose held pairs exceed the buffer
    takes the usual buffer several times over, and computes what the one
    worst-case buffer computes (a smaller layer's), values and gradients,
    nothing dropped."""
    from tpuic.models import kanana
    rng = np.random.default_rng(0)
    tokens, d, width, held, top_k = 500, 32, 16, 8, 3
    rows, worst = kanana.buffer_rows(tokens, top_k, held, 64)
    assert (rows, worst) == (384, 1500)
    # which layer takes which: the long-sequence cell's (16,384 tokens, 8
    # of 8 held) the passes, the routed cell's (6,272 tokens, top-6) one
    # buffer, as its pinned step text has it
    assert kanana.buffer_rows(16384, 8, 8, 64) == (32768, 131072)
    assert kanana.buffer_rows(6272, 6, 8, 128)[1] == 37632
    assert 37632 <= kanana.ONE_BUFFER_WORST_ROWS < 131072
    x = jnp.asarray(rng.standard_normal((tokens, d)), jnp.float32)
    gate_up = jnp.asarray(rng.standard_normal((held, d, 2 * width)) * 0.2,
                          jnp.float32)
    down = jnp.asarray(rng.standard_normal((held, width, d)) * 0.2,
                       jnp.float32)
    weights = jnp.asarray(rng.random((tokens, top_k)), jnp.float32)
    # every token chooses among experts 0-9 of 64: four fifths held
    chosen = jnp.asarray(np.stack([rng.permutation(10)[:top_k]
                                   for _ in range(tokens)]))

    def run(passes):
        monkeypatch.setattr(kanana, "ONE_BUFFER_WORST_ROWS",
                            worst - 1 if passes else worst)

        def loss(x, gate_up, down, weights):
            y, sizes, computed, over = kanana.routed_sum(
                x, chosen, weights, gate_up, down, 0, 64)
            return jnp.sum(y * y), (y, sizes, computed, over)
        return jax.value_and_grad(loss, (0, 1, 2, 3), has_aux=True)(
            x, gate_up, down, weights)
    (_, (y, sizes, computed, over)), grads = run(True)
    (_, (want, _, want_computed, _)), want_grads = run(False)
    assert bool(over) and int(jnp.sum(sizes)) > rows
    assert int(computed) == int(want_computed) == int(jnp.sum(sizes))
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-6)
    for a, b in zip(grads, want_grads):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


# -- the band -----------------------------------------------------------------

def test_the_stack_counts_the_tiles_its_kernels_visit():
    """256 tokens in blocks of 64: a sliding layer's rows see their own
    block and the far edge's, 1 + 3 x 2 = 7 tiles; the full layer's the
    diagonal and below, 10; of 16 each."""
    from tpuic.train.step import _sown_counters
    v, (images, _) = _variables(), _batch(2)
    _, sown = _model().apply(v, images, train=False, mutable=["counters"])
    counters = _sown_counters(sown["counters"])
    assert counters["attention_key_blocks_visited"] == 3 * 7 + 10
    assert counters["attention_key_blocks_square"] == 4 * 16
    assert counters["attention_window_layers"] == 3
    assert counters["attention_full_layers"] == 1
    assert counters["routed_pairs"] == 2 * 256 * 3
    assert counters["routed_pairs_dropped"] == 0
    # at the cell's size, by the default blocks of 512: 38.7 %
    from tpuic.kernels.flash_attention import blocks_visited
    visited = 3 * blocks_visited(4096, None, None, True, 1024)[0] \
        + blocks_visited(4096, None, None, True, None)[0]
    assert (visited, 4 * 64) == (3 * 21 + 36, 256)


def test_remat_changes_nothing_but_the_residuals():
    v, (images, labels) = _variables(), _batch(2)
    plain, remat = _model(), _model(remat=True, remat_policy="blocks")
    assert not plain.backbone.remat_blocks and remat.backbone.remat_blocks
    a = jax.grad(lambda p: _program_loss(plain, p, images, labels))(
        v["params"])
    b = jax.grad(lambda p: _program_loss(remat, p, images, labels))(
        v["params"])
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_allclose(x, y, rtol=1e-5, atol=1e-7)
    assert family("mellum2-12b-a2.5b-l4e8").remat_policies == {"blocks"}


def test_the_routed_cells_and_the_looped_cells_steps_lower_as_before():
    """The lowered train steps of the two routed cells and of the looped
    cell, at batch 2, to the byte (sha256 with this installation's jax,
    0.9.0). The long-sequence cell's digest was written when its routed
    sum's token-side sums became gathers through the inverse permutation
    under a VJP that makes the two moves each other's transpose
    (``kanana.to_buffer`` / ``kanana.to_tokens``): its step text moved on
    purpose, and this pin holds it at the program that was measured. The
    routed cell, whose slots are 7.9 times its buffer's rows (over
    ``kanana.SLOTS_OVER_BUFFER``), keeps the scatter-adds, and its step is
    its parent's to the byte; so is the looped cell's, the one its
    rotary's single pass each way under a hand-written VJP
    (``layers.rotate``) gave it. After an upgrade of jax the digests go
    stale with no fault in the tree: unpack the commit that last wrote
    them (``git archive <commit> | tar -x -C <dir>``), run this test's
    body there under the new jax, and write the digests it gives here."""
    import hashlib
    from flax.core import meta
    from tpuic.config import ModelConfig, OptimConfig
    from tpuic.models import create_model_from_config
    from tpuic.train.optimizer import make_optimizer
    from tpuic.train.state import TrainState
    from tpuic.train.step import make_train_step
    for name, want in (("kanana-2-30b-a3b-l6e8", "d81c45772d1655c0"),
                       ("ouro-2.6b-l6", "e05ef1f4ce254bae"),
                       ("mellum2-12b-a2.5b-l4e8", "9d11239a8a33ecdc")):
        mc = ModelConfig(name=name, num_classes=1000, dtype="bfloat16",
                         remat=True, remat_policy="blocks")
        oc = OptimConfig(optimizer="adam", class_weights=(), milestones=())
        model = create_model_from_config(mc)
        side = 1024 if name.startswith("mellum") else 224
        x = jnp.zeros((2, side, side, 3))
        v = meta.unbox(jax.eval_shape(
            lambda: model.init(jax.random.key(0), x, train=False)))
        tx = make_optimizer(oc, 8, 1, global_batch=2)
        state = jax.eval_shape(lambda params: TrainState(
            step=jnp.zeros((), jnp.int32), params=params, batch_stats={},
            opt_state=tx.init(params), apply_fn=model.apply, tx=tx,
            ema_params=None, skip_count=jnp.zeros((), jnp.int32)),
            v["params"])
        batch = {"image": jax.ShapeDtypeStruct(x.shape, jnp.float32),
                 "label": jax.ShapeDtypeStruct((2,), jnp.int32),
                 "mask": jax.ShapeDtypeStruct((2,), jnp.float32)}
        with jax.default_matmul_precision(None):    # as a run lowers it
            text = make_train_step(oc, mc, mesh=None, donate=False).lower(
                state, batch).as_text()
        assert hashlib.sha256(text.encode()).hexdigest()[:16] == want, name


# -- the normal path --------------------------------------------------------

def test_counters_reach_the_log_the_prometheus_rows_and_the_span(tmp_path):
    import train
    from benchmark.datagen import ensure_imagefolder
    from tpuic.config import MeshConfig
    from tpuic.runtime.mesh import make_mesh
    from tpuic.telemetry import spans
    from tpuic.telemetry.prom import train_exposition
    from tpuic.train.loop import Trainer
    data = ensure_imagefolder(str(tmp_path / "data"), size=SIZE,
                              train_images=16, val_images=8, classes=8,
                              unique_per_class=2, corpus_seed=1)
    args = train.build_parser().parse_args([
        "--model", "mellum-tiny", "--num-classes", "10", "--resize",
        str(SIZE), "--datadir", data, "--batchsize", "4", "--epochs", "1",
        "--log-every-steps", "2", "--no-class-weights", "--workers", "1",
        "--remat", "--remat-policy", "blocks", "--milestones",
        "--ckpt-dir", str(tmp_path / "ckpt"),
        "--log-dir", str(tmp_path / "log")])
    cfg = train.config_from_args(args)
    mesh = make_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
    trainer = Trainer(cfg, mesh=mesh, log_dir=args.log_dir)
    trainer.fit()
    assert int(trainer.state.step) == 4
    with open(tmp_path / "log" / "metrics.jsonl") as f:
        rows = [json.loads(ln) for ln in f]
    logged = [r for r in rows if "routed_pairs" in r]
    names = ("routed_pairs", "routed_pairs_held", "routed_pairs_dropped",
             "routed_layers_over_buffer", "expert_load_max_over_mean",
             "router_entropy", "attention_key_blocks_visited",
             "attention_key_blocks_square", "attention_window_layers",
             "attention_full_layers")
    assert len(logged) == 2 and all(n in r for r in logged for n in names)
    for r in logged:
        assert r["routed_pairs"] == 4 * 256 * 3     # T x top_k, every layer
        assert 0 < r["routed_pairs_held"] < r["routed_pairs"]
        assert r["routed_pairs_dropped"] == 0
        assert 0 <= r["routed_layers_over_buffer"] <= 1
        assert r["expert_load_max_over_mean"] >= 1.0
        assert 0 < r["router_entropy"] <= math.log(16) + 1e-6
        assert (r["attention_key_blocks_visited"],
                r["attention_key_blocks_square"]) == (31, 64)
        assert (r["attention_window_layers"],
                r["attention_full_layers"]) == (3, 1)
    assert any("val_accuracy" in r for r in rows)
    assert trainer.last_counters == {n: logged[-1][n] for n in names}
    text = train_exposition({}, counters=trainer.last_counters)
    for n in names:
        assert f"tpuic_train_{n} " in text
    epoch = [r for r in spans.ledger.snapshot()
             if r["name"] == "train_epoch"][-1]
    assert all(epoch["attrs"][n] == logged[-1][n] for n in names)
    from tpuic.train.step import STEP_METRICS
    assert not set(names) & STEP_METRICS
    # the step the run registered: its rotary, the one-pass way, under the
    # scope the device trace's reader charges it to
    from tpuic.telemetry.profile import scope_map, scope_path
    rotary = [n for n, _, _ in scope_map("step")["ops"].values()
              if "rotary" in scope_path(n)]
    assert rotary and any("_rotate_once" in n for n in rotary)
