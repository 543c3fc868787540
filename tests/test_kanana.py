"""The latent-attention, routed-expert stack (models/kanana.py) against its
plain reference and on the program's own terms: the published counts, the
share of a layer's experts tied to the whole layer, a routed sum that drops
nothing and reads no row it did not compute, a bias that selects and does
not weigh, and counters that reach every sink."""

import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from benchmark.reference import kanana as ref
from tpuic.models import create_model, family, kanana

SPEC = harness.load_spec()
CELL = "kanana2_30b_a3b_train_resident"
CONFIG = harness.resolve_cell(SPEC, CELL, tiny=True)["config"]
FULL = harness.resolve_cell(SPEC, CELL)["config"]
DENSE_LAYER = 64_098_816        # attention 26,345,984 + norms 4,096 + MLP
EXPERT = 4_718_592              # 3 x 2048 x 768
EXPERT_LAYER_8 = 73_798_272     # 8 experts held
EXPERT_LAYER = 640_029_312      # all 128


def _model(dtype="float32", **fields):
    return create_model("kanana-tiny", CONFIG["num_classes"], dtype=dtype,
                        **fields)


def _variables(seed=1):
    """Seeded weights with nothing left at its initial value (norm scales
    of 1 would hide how they enter)."""
    v = harness.plain_variables(_model().init(
        jax.random.key(seed), jnp.zeros((1, 32, 32, 3)), train=False))
    rng = np.random.default_rng(seed)
    return jax.tree_util.tree_map(
        lambda a: a + 0.05 * rng.standard_normal(a.shape).astype(np.float32),
        v)


def _batch(rows=8, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal((rows, 32, 32, 3)).astype(np.float32),
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
    ("float32", 1e-4), ("bfloat16", CONFIG["reference_tolerance"])])
def test_eval_logits_agree_with_the_reference(dtype, tolerance):
    """float32 compute agrees tightly; bfloat16 inside the rehearsal's
    tolerance (the CPU rounds bfloat16 products more coarsely than the
    chip, and a rounded score moves a token's choice)."""
    v, (x, _) = _variables(), _batch()
    want = ref.forward(v, x, CONFIG)
    assert want.shape == (8, CONFIG["num_classes"])
    got = _model(dtype, remat=True, remat_policy="blocks").apply(
        v, x, train=False)
    assert harness.centred_error(got, want) < tolerance


def test_loss_and_gradient_agree_leaf_by_leaf():
    model, v, (x, y) = _model(), _variables(), _batch()
    got, g_got = jax.value_and_grad(
        lambda p: _program_loss(model, p, x, y))(v["params"])
    want, g_want = jax.value_and_grad(lambda p: ref.train_loss(
        {"params": p}, x, y, CONFIG))(v["params"])
    np.testing.assert_allclose(got, want, rtol=1e-5)
    flat_got = {jax.tree_util.keystr(k): a for k, a in
                jax.tree_util.tree_leaves_with_path(g_got)}
    flat_want = {jax.tree_util.keystr(k): a for k, a in
                 jax.tree_util.tree_leaves_with_path(g_want)}
    assert flat_got.keys() == flat_want.keys() and len(flat_want) == 49
    for name, w in flat_want.items():
        if "selection_bias" in name:
            # it selects and does not weigh: no gradient, in either
            assert not np.any(flat_got[name]) and not np.any(w)
            continue
        assert float(jnp.linalg.norm(w)) > 1e-7, name
        assert float(jnp.linalg.norm(flat_got[name] - w)
                     / jnp.linalg.norm(w)) < 1e-3, name


def test_bfloat16_gradients_stay_near_the_reference():
    """Stated tolerance for bfloat16 compute on the CPU: the median leaf's
    norm within 5 % of the reference's."""
    model, v, (x, y) = _model("bfloat16"), _variables(), _batch()
    g_got = jax.grad(lambda p: _program_loss(model, p, x, y))(v["params"])
    g_want = jax.grad(lambda p: ref.train_loss(
        {"params": p}, x, y, CONFIG))(v["params"])
    gaps = [abs(float(jnp.linalg.norm(a)) - float(jnp.linalg.norm(b)))
            / float(jnp.linalg.norm(b))
            for a, b in zip(jax.tree_util.tree_leaves(g_got),
                            jax.tree_util.tree_leaves(g_want))
            if float(jnp.linalg.norm(b)) > 0]
    assert np.median(gaps) < 0.05, sorted(gaps)[-5:]


def test_remat_changes_nothing_but_the_residuals():
    v, (x, y) = _variables(), _batch()
    plain = jax.grad(lambda p: _program_loss(_model(), p, x, y))(v["params"])
    remat = jax.grad(lambda p: _program_loss(
        _model(remat=True, remat_policy="blocks"), p, x, y))(v["params"])
    for a, b in zip(jax.tree_util.tree_leaves(plain),
                    jax.tree_util.tree_leaves(remat)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-7)
    assert family("kanana-tiny").remat_policies == {"blocks"}


# -- the published counts ---------------------------------------------------

def _layer_sizes(name):
    model = create_model(name, 1000)
    v = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 224, 224, 3)), train=False))
    stack = v["params"]["backbone"]
    sizes = {k: sum(math.prod(leaf.shape) for leaf in
                    jax.tree_util.tree_leaves(sub))
             for k, sub in stack.items()}
    return model, sizes, sum(math.prod(leaf.shape) for leaf in
                             jax.tree_util.tree_leaves(v["params"]))


def test_parameter_counts_are_the_published_models():
    """By ``eval_shape`` alone (nothing is allocated)."""
    _, whole, total = _layer_sizes("kanana-2-30b-a3b")
    layers = [whole[f"layer{i}"] for i in range(48)]
    assert layers[0] == DENSE_LAYER
    assert set(layers[1:]) == {EXPERT_LAYER}
    assert EXPERT_LAYER - EXPERT_LAYER_8 == 120 * EXPERT
    # 30.15 B without a token table or LM head
    assert abs(total / 30.15e9 - 1.0) < 0.01
    _, cut, here = _layer_sizes("kanana-2-30b-a3b-l6e8")
    assert [cut[f"layer{i}"] for i in range(6)] == (
        [DENSE_LAYER] + [EXPERT_LAYER_8] * 5) and "layer6" not in cut
    assert abs(here / (FULL["parameters_millions_here"] * 1e6) - 1) < 0.001


def test_the_flags_build_the_configurations_widths_and_the_count_is_by_hand():
    from benchmark.flops import model_forward_macs_per_image
    import train
    args = train.build_parser().parse_args(
        [*FULL["train_flags"], "--datadir", "x"])
    cfg = train.config_from_args(args)
    assert cfg.model.remat and cfg.model.remat_policy == "blocks"
    model = create_model(cfg.model.name, 1000)
    b = model.backbone
    assert (b.hidden, b.depth, b.dense_layers, b.num_heads, b.nope, b.rope,
            b.v_dim, b.kv_rank, b.dense_width, b.num_experts, b.held,
            b.expert_width, b.top_k, b.shared_experts, b.routed_scale,
            b.rope_theta, b.eps, b.patch) == (
        FULL["hidden_size"], FULL["num_hidden_layers"],
        FULL["first_k_dense_replace"], FULL["num_attention_heads"],
        FULL["qk_nope_head_dim"], FULL["qk_rope_head_dim"],
        FULL["v_head_dim"], FULL["kv_lora_rank"], FULL["intermediate_size"],
        FULL["published"]["n_routed_experts"],
        (FULL["experts_held_first"], FULL["n_routed_experts"]),
        FULL["moe_intermediate_size"], FULL["num_experts_per_tok"],
        FULL["n_shared_experts"], FULL["routed_scaling_factor"],
        FULL["rope_theta"], FULL["rms_norm_eps"], FULL["patch"])
    assert b.nope + b.rope == FULL["qk_head_dim"]
    assert b.rope == FULL["head_dim"]
    # benchmark/flops.py counts dot_general and convolutions: everything
    # but the grouped products of the routed pairs (PERF.md section 7)
    counted = model_forward_macs_per_image(model, FULL["image_size"])
    routed = 196 * 5 * (6 * 8 / 128) * EXPERT
    attention = 26_345_984 - 512 + 32 * 196 * (192 + 128)
    by_hand = 196 * (6 * attention + 3 * 2048 * 6144
                     + 5 * (3 * 2048 * 1536 + 2048 * 128)) + 196 * 768 * 2048
    # ... and, since PR 35, of the attention core's contractions all but
    # one grid cell's: flops.py counts a pallas_call's body once, which is
    # one head group of 8 where 32 heads are computed (PERF.md section
    # 7: count a pallas_call at cells x body)
    core = 32 * 196 * 196 * (192 + 128)
    assert abs(counted / (by_hand - 6 * core * 3 / 4) - 1.0) < 0.001
    assert abs(6 * core * 3 / 4 / by_hand - 0.0350) < 0.001
    assert abs((by_hand + routed)
               / (FULL["forward_gmacs_per_image_here"] * 1e9) - 1) < 0.005


# -- the attention core: one kernel or the dense path, by shape -------------

def _attention(heads=2, nope=128, rope=64, v_dim=128):
    return kanana.LatentAttention(heads, nope, rope, v_dim, kv_rank=32)


def _applied(module, x, seed=0):
    """``(output, counters)`` of ``module`` on ``x`` with seeded weights."""
    v = harness.plain_variables(module.init(jax.random.key(seed), x))
    out, sown = module.apply(v, x, mutable=["counters"])
    from tpuic.train.step import _sown_counters
    return out, _sown_counters(sown["counters"]), v


@pytest.mark.parametrize("tokens,widths,fused", [
    (20, dict(), True),                             # the published head
    (20, dict(nope=16, rope=8, v_dim=12), False),   # kanana_tiny's
    (1200, dict(), False),                          # the scores leave VMEM
], ids=["published_widths", "tiny_widths", "over_the_vmem_limit"])
def test_the_core_is_chosen_by_shape_and_says_which_ran(tokens, widths,
                                                        fused):
    module = _attention(**widths)
    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        (1, tokens, 64)).astype(np.float32))
    v = jax.eval_shape(lambda: module.init(jax.random.key(0), x))
    program = str(jax.make_jaxpr(lambda v, x: module.apply(
        v, x, mutable=["counters"]))(harness.plain_variables(v), x))
    assert ("pallas_call" in program) is fused
    # the dense path's [B, H, N, N] scores
    assert (f"f32[1,2,{tokens},{tokens}]" in program) is not fused
    if tokens < 100:
        _, counters, _ = _applied(module, x)
        assert counters == {"attention_core_fused": float(fused)}


def test_the_registered_names_take_the_core_their_widths_allow():
    from tpuic.kernels import causal_attention
    for name, fused in (("kanana-2-30b-a3b", True),
                        ("kanana-2-30b-a3b-l6e8", True),
                        ("kanana-tiny", False)):
        b = create_model(name, 10, dtype="bfloat16").backbone
        assert causal_attention.supports(
            (224 // b.patch) ** 2, b.num_heads, b.nope, b.rope, b.v_dim,
            2) is fused, name


def test_a_stack_of_aligned_widths_counts_every_layer_fused():
    stack = kanana.LatentMoeStack(
        patch=8, hidden=64, depth=2, dense_layers=1, num_heads=2,
        kv_rank=32, dense_width=96, num_experts=8, held=(0, 4),
        expert_width=16, top_k=2, remat_blocks=True)
    x = jnp.asarray(np.random.default_rng(1).standard_normal(
        (2, 32, 32, 3)).astype(np.float32))
    _, counters, _ = _applied(stack, x)
    assert counters["attention_core_fused"] == 1.0
    assert counters["routed_pairs"] == 2 * 16 * 2


def test_a_remat_block_has_the_same_gradients_through_either_core(
        monkeypatch):
    from flax import linen as nn
    from tpuic.kernels import causal_attention
    block = nn.remat(kanana.LatentMoeBlock)(
        dense_width=None, num_heads=2, nope=128, rope=64, v_dim=128,
        kv_rank=32, num_experts=8, held=(0, 4), expert_width=16, top_k=2,
        shared_width=32, routed_scale=2.448)
    x = jnp.asarray(np.random.default_rng(2).standard_normal(
        (2, 20, 64)).astype(np.float32))
    v = harness.plain_variables(block.init(jax.random.key(2), x))
    weigh = jnp.asarray(np.random.default_rng(3).standard_normal(
        x.shape).astype(np.float32))

    def gradients():
        return jax.grad(lambda p, x: jnp.sum(block.apply(
            {"params": p}, x) * weigh), argnums=(0, 1))(v["params"], x)
    through_kernel = gradients()
    monkeypatch.setattr(causal_attention, "supports", lambda *a: False)
    through_dense = gradients()
    leaves = jax.tree_util.tree_leaves_with_path
    assert len(leaves(through_kernel)) == len(leaves(through_dense)) > 8
    for (path, a), (_, b) in zip(leaves(through_kernel),
                                 leaves(through_dense)):
        # the selection bias selects and does not weigh: no gradient
        assert (float(jnp.abs(b).max()) > 0) is (
            "selection_bias" not in jax.tree_util.keystr(path)), path
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-6)


# -- a share of the experts -------------------------------------------------

def _expert_layer(held, first=0, num_experts=32):
    return kanana.ExpertLayer(num_experts, (first, held), width=24, top_k=3,
                              shared_width=48, routed_scale=2.448)


def _expert_layer_params(seed=3):
    layer = _expert_layer(32)
    x = jnp.asarray(np.random.default_rng(seed).standard_normal(
        (2, 64, 64)).astype(np.float32))
    p = harness.plain_variables(layer.init(jax.random.key(seed), x))["params"]
    return x, p


def test_the_shares_of_a_layer_add_up_to_the_uncut_reference():
    """The model-configs guide, section 4: the routed parts of all four
    shares of 8 experts, with what every chip computes alike (the shared
    experts) counted once, are the whole layer as the plain reference
    computes it uncut."""
    x, p = _expert_layer_params()
    config = {**CONFIG, "experts_held_first": 0}
    whole = ref.expert_layer(x, p, config)
    shared = ref.expert_layer(x, {**p, "experts_gate_up":
                                  p["experts_gate_up"][:0],
                                  "experts_down": p["experts_down"][:0]},
                              config)
    total = shared
    for first in range(0, 32, 8):
        mine = {**p, "experts_gate_up": p["experts_gate_up"][first:first + 8],
                "experts_down": p["experts_down"][first:first + 8]}
        part = _expert_layer(8, first).apply({"params": mine}, x)
        # the reference is given the same share and agrees on it
        np.testing.assert_allclose(
            part, ref.expert_layer(x, mine, {**config,
                                             "experts_held_first": first}),
            rtol=2e-4, atol=2e-5)
        total = total + (part - shared)
    np.testing.assert_allclose(total, whole, rtol=2e-4, atol=2e-5)
    assert float(jnp.max(jnp.abs(whole - shared))) > 0.1


def test_the_bias_selects_and_does_not_weigh():
    x, p = _expert_layer_params()
    layer = _expert_layer(32)
    out = layer.apply({"params": p}, x)
    # a large bias on one expert: every token chooses it, and its weight is
    # still its score over the chosen scores' sum
    pushed = {**p, "selection_bias": jnp.asarray(
        p["selection_bias"]).at[5].add(10.0)}
    config = {**CONFIG, "experts_held_first": 0}
    weights = ref.routing_weights(x, pushed, config)
    assert bool(jnp.all(weights[..., 5] > 0))
    assert bool(jnp.all(jnp.sum(weights > 0, axis=-1) == 3))
    np.testing.assert_allclose(jnp.sum(weights, axis=-1), 2.448, rtol=1e-5)
    np.testing.assert_allclose(layer.apply({"params": pushed}, x),
                               ref.expert_layer(x, pushed, config),
                               rtol=2e-4, atol=2e-5)
    # left out, the choice is another one
    without = {**p, "selection_bias": jnp.zeros_like(p["selection_bias"])}
    assert float(jnp.max(jnp.abs(layer.apply({"params": without}, x)
                                 - out))) > 1e-3
    grads = jax.grad(lambda q: jnp.sum(layer.apply({"params": q}, x) ** 2))(p)
    assert not np.any(grads["selection_bias"])
    assert float(jnp.linalg.norm(grads["router"])) > 0


# -- the routed sum ---------------------------------------------------------

def _routed_case(tokens=512, top_k=3, held=8, num_experts=128, on_held=False,
                 seed=0, d=32):
    rng = np.random.default_rng(seed)
    width = 16
    x = jnp.asarray(rng.standard_normal((tokens, d)).astype(np.float32))
    span = held if on_held else num_experts
    chosen = jnp.asarray(np.stack([rng.choice(span, top_k, replace=False)
                                   for _ in range(tokens)]).astype(np.int32))
    weights = jnp.asarray(rng.random((tokens, top_k)).astype(np.float32))
    gate_up = jnp.asarray(rng.standard_normal((held, d, 2 * width)).astype(
        np.float32)) / 6
    down = jnp.asarray(rng.standard_normal((held, width, d)).astype(
        np.float32)) / 4
    return x, chosen, weights, gate_up, down, num_experts


def _dense_routed(x, chosen, weights, gate_up, down):
    width = down.shape[1]
    y = jnp.zeros_like(x)
    for e in range(gate_up.shape[0]):
        w = jnp.sum(jnp.where(chosen == e, weights, 0.0), axis=-1)
        h = x @ gate_up[e]
        y = y + w[:, None] * ((jax.nn.silu(h[:, :width]) * h[:, width:])
                              @ down[e])
    return y


@pytest.mark.parametrize("on_held", [False, True],
                         ids=["even_load", "every_token_on_held_experts"])
def test_the_routed_sum_is_dropless(on_held):
    """With the load an even router sends, the pairs fit the buffer; with
    every token forced onto held experts they exceed it and the step takes
    the worst-case buffer: nothing is dropped either way."""
    x, chosen, weights, gate_up, down, n = _routed_case(on_held=on_held)
    rows, worst = kanana.buffer_rows(512, 3, 8, n)
    assert rows == 256 < worst == 1536
    assert kanana.buffer_rows(6272, 6, 8, 128) == (4736, 37632)
    assert kanana.buffer_rows(64, 3, 8, 8) == (192, 192)
    y, sizes, computed, over = jax.jit(
        lambda *a: kanana.routed_sum(*a, 0, n))(x, chosen, weights, gate_up,
                                                down)
    held_pairs = int(jnp.sum(chosen < 8))
    assert int(jnp.sum(sizes)) == int(computed) == held_pairs
    assert (held_pairs > rows) == bool(over) == on_held
    np.testing.assert_allclose(
        y, _dense_routed(x, chosen, weights, gate_up, down), rtol=2e-4,
        atol=2e-5)
    # and so are the gradients, through either branch
    g = jax.grad(lambda x, gu, dn: jnp.sum(kanana.routed_sum(
        x, chosen, weights, gu, dn, 0, n)[0] ** 2), argnums=(0, 1, 2))(
        x, gate_up, down)
    g_want = jax.grad(lambda x, gu, dn: jnp.sum(_dense_routed(
        x, chosen, weights, gu, dn) ** 2), argnums=(0, 1, 2))(
        x, gate_up, down)
    for a, b in zip(g, g_want):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-4)


def test_rows_past_the_groups_are_masked_on_the_way_in_and_out():
    """On the CPU a grouped product writes zeros past its groups; on the
    chip those rows hold whatever was there (finite and not zero, on a
    v5e). So the mask is tested with the rows poisoned, here through the
    token-side sums as scatter-adds."""
    _rows_past_the_groups_are_masked(gathers=False)


def test_rows_past_the_groups_are_masked_through_the_gathers():
    """The same, through the token-side sums as gathers of each token's
    slots (``to_buffer`` / ``to_tokens``)."""
    _rows_past_the_groups_are_masked(gathers=True)


def _rows_past_the_groups_are_masked(gathers):
    group = jnp.asarray([8, 2, 8, 0, 8, 2, 8, 8], jnp.int32)
    order, rank, sizes = kanana.dispatch(group, 8, rank=gathers)
    pairs, valid = order[:6], jnp.arange(6) < jnp.sum(sizes)
    assert pairs[:3].tolist() == [3, 1, 5]
    assert sizes.tolist() == [1, 0, 2, 0, 0, 0, 0, 0]
    assert (rank is not None) == gathers
    if gathers:
        assert rank[order].tolist() == list(range(8))
        moves = kanana.buffer_moves(pairs, rank, jnp.sum(sizes), 2)
    out = jnp.ones((6, 4)).at[3:].set(jnp.nan).at[4].set(1e30)
    weights = jnp.arange(1.0, 9.0).reshape(4, 2)
    token = pairs // 2

    def total(out, weights):
        if gathers:
            return kanana.to_tokens(out, moves, weights)
        return kanana.combine(out, valid, weights.reshape(-1)[pairs], token,
                              tokens=4)
    y = total(out, weights)
    assert bool(jnp.all(jnp.isfinite(y)))
    np.testing.assert_array_equal(
        y[:, 0], jnp.zeros(4).at[token[:3]].add(
            weights.reshape(-1)[pairs[:3]]))
    d_out, d_w = jax.grad(lambda o, w: jnp.sum(total(o, w)), argnums=(0, 1))(
        out, weights)
    # a row past the groups and the weight of a pair on an absent expert
    # get nothing
    assert not np.any(d_out[3:])
    np.testing.assert_array_equal(d_w.reshape(-1) != 0, group < 8)
    assert bool(jnp.all(jnp.isfinite(d_out))) and bool(
        jnp.all(jnp.isfinite(d_w)))
    # on the way in: what the gather took for a row past the groups is
    # zero, and no cotangent comes back through it
    x = jnp.arange(16.0).reshape(4, 4) + 1
    gate_up = jnp.ones((8, 4, 6))
    down = jnp.ones((8, 3, 4))
    seen = {}

    def spy(rows, gate_up, down, sizes):
        seen["rows"], seen["sizes"] = rows, sizes
        return jnp.full((rows.shape[0], 4), jnp.nan).at[:3].set(1.0)

    def through(x):
        if gathers:
            return kanana._gathered_rows(x, pairs, rank, sizes,
                                         jnp.ones((4, 2)), gate_up, down)
        return kanana._routed_rows(x, order, sizes, jnp.ones((4, 2)),
                                   gate_up, down, rows=6)
    real, kanana.expert_matmul = kanana.expert_matmul, spy
    try:
        d_x = jax.grad(lambda x: jnp.sum(through(x)[0]))(x)
        y, computed = through(x)
    finally:
        kanana.expert_matmul = real
    assert int(computed) == 3 and bool(jnp.all(jnp.isfinite(y)))
    assert not np.any(seen["rows"][3:]) and bool(
        jnp.all(seen["rows"][:3] > 0))
    assert bool(jnp.all(jnp.isfinite(d_x)))
    # the grouped products are given the pairs' own groups and no more
    assert seen["sizes"].tolist() == sizes.tolist()


def _moves_case(lo, tokens=64, top_k=3, held=4, rows=96, d=8):
    """A buffer of ``rows`` of the sorted pairs from ``lo`` on, as the
    routed sum's passes make one (``lo`` 0: its one buffer), with the rows
    past the groups poisoned."""
    rng = np.random.default_rng(lo)
    group = jnp.asarray(rng.integers(0, 3 * held, tokens * top_k)
                        .clip(max=held), jnp.int32)
    order, rank, sizes = kanana.dispatch(group, held, rank=True)
    count = int(jnp.clip(jnp.sum(sizes) - lo, 0, rows))
    assert 0 < count < rows - 2
    pairs = order[lo:lo + rows]
    moves = kanana.buffer_moves(pairs, rank - lo, count, top_k)
    buf = jnp.asarray(rng.standard_normal((rows, d)), jnp.float32)
    buf = buf.at[count:].set(jnp.nan).at[count + 1].set(1e30)
    weights = jnp.asarray(rng.random((tokens, top_k)), jnp.float32)
    return moves, pairs, count, buf, weights


@pytest.mark.parametrize("weighed", [False, True], ids=["plain", "weighed"])
@pytest.mark.parametrize("lo", [0, 40], ids=["first_rows", "a_later_pass"])
def test_to_tokens_is_the_scatter_add_it_replaces(lo, weighed):
    """``to_tokens`` (a gather of each token's slots, summed) against the
    scatter-add of the buffer's rows in groups into their tokens that it
    replaces: values and both cotangents, with the rows past the groups
    poisoned (NaN, 1e30)."""
    moves, pairs, count, buf, weights = _moves_case(lo)
    tokens, top_k = weights.shape

    def got(buf, weights):
        return kanana.to_tokens(buf, moves, weights if weighed else None)

    def want(buf, weights):
        rows = jnp.where((jnp.arange(buf.shape[0]) < count)[:, None], buf,
                         0.0)
        if weighed:
            rows = rows * weights.reshape(-1)[pairs][:, None]
        return jnp.zeros((tokens, buf.shape[1])).at[pairs // top_k].add(rows)
    y, pull = jax.vjp(got, buf, weights)
    y_want, pull_want = jax.vjp(want, buf, weights)
    assert bool(jnp.all(jnp.isfinite(y)))
    np.testing.assert_allclose(y, y_want, rtol=1e-6, atol=1e-6)
    dy = jnp.asarray(np.random.default_rng(7).standard_normal(y.shape),
                     jnp.float32)
    for a, b in zip(pull(dy), pull_want(dy)):
        assert bool(jnp.all(jnp.isfinite(a)))
        np.testing.assert_allclose(a, b if weighed or a.shape != weights.shape
                                   else jnp.zeros_like(b), rtol=1e-6,
                                   atol=1e-6)


def test_the_two_moves_are_each_others_transpose():
    """The backward pass of ``to_buffer`` is ``to_tokens`` and that of
    ``to_tokens`` is ``to_buffer``, and the pair are transposes of each
    other as linear maps: <to_buffer(x), b> = <x, to_tokens(b)>. Each
    cotangent comes back in its input's dtype."""
    moves, _, _, buf, _ = _moves_case(40)
    rng = np.random.default_rng(3)
    buf = jnp.where(moves.valid[:, None], buf, 0.0)
    x = jnp.asarray(rng.standard_normal((64, buf.shape[1])), jnp.float32)
    dy = jnp.asarray(rng.standard_normal(x.shape), jnp.float32)
    _, pull_in = jax.vjp(lambda x: kanana.to_buffer(x, moves), x)
    _, pull_out = jax.vjp(lambda b: kanana.to_tokens(b, moves), buf)
    np.testing.assert_array_equal(pull_in(buf)[0],
                                  kanana.to_tokens(buf, moves))
    np.testing.assert_array_equal(pull_out(dy)[0],
                                  kanana.to_buffer(dy, moves))
    np.testing.assert_allclose(
        jnp.vdot(kanana.to_buffer(x, moves), buf),
        jnp.vdot(x, kanana.to_tokens(buf, moves)), rtol=1e-5)
    # summed in float32 and handed back in bfloat16, as x came
    xb = x.astype(jnp.bfloat16)
    _, pull_in = jax.vjp(lambda x: kanana.to_buffer(x, moves), xb)
    assert pull_in(buf.astype(jnp.bfloat16))[0].dtype == jnp.bfloat16
    _, pull_out = jax.vjp(lambda b: kanana.to_tokens(b, moves),
                          buf.astype(jnp.bfloat16))
    assert pull_out(dy)[0].dtype == jnp.bfloat16


def test_a_large_source_is_gathered_by_blocks_of_columns(monkeypatch):
    """Over ``GATHER_SOURCE_BYTES`` a source's rows are gathered by blocks
    of whole lane tiles of columns, each under the limit, and come out as
    the one gather would give them; the routed sum through such gathers
    computes what it computes through one (values and gradients)."""
    rng = np.random.default_rng(5)
    source = jnp.asarray(rng.standard_normal((64, 384)), jnp.float32)
    index = jnp.asarray(rng.integers(0, 64, (3, 50)), jnp.int32)
    monkeypatch.setattr(kanana, "GATHER_SOURCE_BYTES", 40_000)
    got = kanana.gather_rows(source, index)
    np.testing.assert_array_equal(got, source[index])
    gathers = [eqn for eqn in _equations(jax.make_jaxpr(
        kanana.gather_rows)(source, index).jaxpr)
        if eqn.primitive.name == "gather"]
    assert [g.invars[0].aval.shape for g in gathers] == [(64, 128)] * 3
    x, chosen, weights, gate_up, down, n = _routed_case(tokens=64, d=256)
    moves = kanana.buffer_moves(jnp.arange(128), jnp.arange(192), 100, 3)
    assert [g.invars[0].aval.shape for g in _equations(jax.make_jaxpr(
        kanana.to_buffer)(x, moves).jaxpr) if g.primitive.name == "gather"] \
        == [(64, 128)] * 2

    def run():
        def loss(x, weights, gate_up, down):
            y = kanana.routed_sum(x, chosen, weights, gate_up, down, 0, n)[0]
            return jnp.sum(y * y), y
        return jax.value_and_grad(loss, (0, 1, 2, 3), has_aux=True)(
            x, weights, gate_up, down)
    (_, y), grads = run()
    monkeypatch.setattr(kanana, "GATHER_SOURCE_BYTES", 96 * 2**20)
    (_, want), want_grads = run()
    np.testing.assert_allclose(y, want, rtol=1e-6, atol=1e-6)
    for a, b in zip(grads, want_grads):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)


def test_the_token_side_sums_are_gathers_up_to_six_slots_a_buffer_row():
    """Where each token's slots are at most ``SLOTS_OVER_BUFFER`` times the
    buffer's rows the token-side sums are gathers (the long-sequence
    cell's 16,384 tokens, top-8, a 32,768-row buffer: 4 times), past it
    scatter-adds (the routed cell's 6,272 tokens, top-6, 4,736 rows: 7.9
    times), each where it was the faster on the chip."""
    assert kanana.SLOTS_OVER_BUFFER == 6
    for tokens, top_k, experts, gathers in ((16384, 8, 64, True),
                                            (6272, 6, 128, False)):
        args = [jax.ShapeDtypeStruct(s, d) for s, d in (
            ((tokens, 64), jnp.float32), ((tokens, top_k), jnp.float32),
            ((8, 64, 32), jnp.float32), ((8, 16, 64), jnp.float32),
            ((tokens, top_k), jnp.int32))]
        eqns = list(_equations(jax.make_jaxpr(jax.grad(
            lambda x, w, gate_up, down, chosen: jnp.sum(kanana.routed_sum(
                x, chosen, w, gate_up, down, 0, experts)[0]),
            argnums=(0, 1)))(*args).jaxpr))
        scatters = [e for e in eqns if "scatter" in e.primitive.name
                    and e.invars[0].aval.ndim == 2]
        assert (not scatters) == gathers, (tokens, top_k, experts)


@pytest.mark.parametrize("on_held", [False, True],
                         ids=["even_load", "every_token_on_held_experts"])
def test_the_two_forms_of_the_token_side_sums_agree(on_held, monkeypatch):
    """The routed sum through gathers and through scatter-adds: the same
    values and gradients (the weights' too), through either branch."""
    x, chosen, weights, gate_up, down, n = _routed_case(on_held=on_held)

    def run(slots_over_buffer):
        monkeypatch.setattr(kanana, "SLOTS_OVER_BUFFER", slots_over_buffer)

        def loss(x, weights, gate_up, down):
            y = kanana.routed_sum(x, chosen, weights, gate_up, down, 0, n)[0]
            return jnp.sum(y * y), y
        return jax.value_and_grad(loss, (0, 1, 2, 3), has_aux=True)(
            x, weights, gate_up, down)
    (_, y), grads = run(6)
    (_, want), want_grads = run(0)
    np.testing.assert_allclose(y, want, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(
        y, _dense_routed(x, chosen, weights, gate_up, down), rtol=2e-4,
        atol=2e-5)
    for a, b in zip(grads, want_grads):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def _equations(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs inside it (branches,
    loop bodies, remat, custom rules)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for item in (v if isinstance(v, (tuple, list)) else (v,)):
                inner = getattr(item, "jaxpr", item)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


def test_the_routed_sum_is_grouped_products_and_nothing_dense():
    """``jax.lax.ragged_dot`` traces to ``ragged_dot_general`` (jax 0.9);
    no ``dot_general`` (a pass over every held expert, a one-hot dispatch)
    anywhere in the routed sum, its branches included."""
    x, chosen, weights, gate_up, down, n = _routed_case()
    names = [eqn.primitive.name for eqn in _equations(jax.make_jaxpr(
        lambda *a: kanana.routed_sum(*a, 0, n))(
            x, chosen, weights, gate_up, down).jaxpr)]
    assert names.count("ragged_dot_general") == 4      # two a branch
    assert "cond" in names and "sort" in names
    assert "dot_general" not in names and "conv_general_dilated" not in names


@pytest.mark.parametrize("worst_case", ["one_buffer", "passes"])
def test_the_routed_sum_scatters_no_rows(worst_case, monkeypatch):
    """Forward and backward (``jax.value_and_grad``), both branches of the
    ``cond``, and the passes where the worst case takes them: no
    scatter-add of rows (an operand of rank 2, ``[rows, D]`` or ``[T,
    D]``) anywhere in the routed sum: each token-side sum is a gather
    through the inverse permutation. Still no ``dot_general``."""
    if worst_case == "passes":
        monkeypatch.setattr(kanana, "ONE_BUFFER_WORST_ROWS", 0)
    x, chosen, weights, gate_up, down, n = _routed_case()

    def loss(x, weights, gate_up, down):
        return jnp.sum(kanana.routed_sum(x, chosen, weights, gate_up, down,
                                         0, n)[0] ** 2)
    eqns = list(_equations(jax.make_jaxpr(jax.value_and_grad(
        loss, argnums=(0, 1, 2, 3)))(x, weights, gate_up, down).jaxpr))
    names = [eqn.primitive.name for eqn in eqns]
    assert ("scan" in names) == (worst_case == "passes")
    assert "cond" in names and "ragged_dot_general" in names
    assert [eqn.invars[0].aval.shape for eqn in eqns
            if "scatter" in eqn.primitive.name
            and eqn.invars[0].aval.ndim >= 2] == []
    assert "dot_general" not in names


def test_interleaved_rotary_turns_neighbouring_pairs():
    x = np.random.default_rng(0).standard_normal((2, 5, 3, 8)).astype(
        np.float32)
    got = kanana.interleaved_rotary(jnp.asarray(x), 1e6)
    z = x[..., 0::2] + 1j * x[..., 1::2]
    angles = (np.arange(5)[:, None]
              * 1e6 ** (-np.arange(0, 8, 2) / 8)[None])[None, :, None, :]
    want = z * np.exp(1j * angles)
    np.testing.assert_allclose(got[..., 0::2], want.real, atol=1e-5)
    np.testing.assert_allclose(got[..., 1::2], want.imag, atol=1e-5)
    np.testing.assert_allclose(got[:, 0], x[:, 0], atol=1e-6)
    # the one shared key's shape as well
    assert kanana.interleaved_rotary(jnp.asarray(x[:, :, 0]), 1e6).shape == (
        2, 5, 8)


def test_the_read_out_is_the_mean_over_positions():
    model, v, (x, _) = _model(), _variables(), _batch(2)
    feats = model.backbone.apply({"params": v["params"]["backbone"]}, x)
    assert feats.shape == (2, 64) and feats.dtype == jnp.float32
    # a closing RMSNorm with scale g: every position's features have mean
    # square g^2 / ... so the mean over positions stays under max |g|
    assert float(jnp.max(jnp.abs(feats))) < float(
        jnp.max(jnp.abs(v["params"]["backbone"]["norm_final"]["scale"]))) * 8


def _flat_images(rows=4, seed=0):
    """A brightness an image plus noise: the benchmark corpus's kind."""
    rng = np.random.default_rng(seed)
    return (np.linspace(-0.8, 1.8, rows, dtype=np.float32)[:, None, None, None]
            + 0.3 * rng.standard_normal((rows, 32, 32, 3)).astype(np.float32))


def test_standardized_images_have_no_brightness_and_no_contrast():
    x = _flat_images()
    got = np.asarray(kanana.standardized(jnp.asarray(x)))
    np.testing.assert_allclose(got.mean(axis=(1, 2)), 0.0, atol=1e-5)
    np.testing.assert_allclose(got.std(axis=(1, 2)), 1.0, atol=1e-4)
    # an image of one colour has nothing to tell its patches apart
    flat = kanana.standardized(jnp.full((1, 8, 8, 3), 0.7))
    assert float(jnp.max(jnp.abs(flat))) < 1e-3     # rounding of the mean


@pytest.mark.parametrize("gain,shift", [(3.0, 2.0), (0.25, -1.5)])
def test_an_images_brightness_and_contrast_do_not_reach_the_logits(gain,
                                                                   shift):
    model, v, x = _model(), _variables(), _flat_images()
    scale = np.asarray([gain, 1.0, gain / 2], np.float32)    # by channel
    want = model.apply(v, x, train=False)
    got = model.apply(v, x * scale + shift, train=False)
    np.testing.assert_allclose(got, want, atol=2e-4)
    # and the reference standardises as the program does
    np.testing.assert_allclose(
        ref.forward(v, x * scale + shift, CONFIG), want, atol=2e-4)


def test_the_tokens_of_a_flat_image_point_apart():
    """Patches that share their image's brightness embed to one token many
    times over; standardised, they differ as a token table's rows do."""
    v, x = _variables(), _flat_images()
    embed = v["params"]["backbone"]["patch_embed"]

    def cosine(images):
        t = jax.lax.conv_general_dilated(
            images, embed["kernel"], (4, 4), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC")).reshape(-1, 64, 64)
        t = t / jnp.linalg.norm(t, axis=-1, keepdims=True)
        off = 1.0 - jnp.eye(t.shape[1])
        return float(jnp.sum(jnp.abs(jnp.einsum("bnd,bmd->bnm", t, t))
                             * off) / (t.shape[0] * jnp.sum(off)))
    bright = jnp.asarray(x[-1:])                # brightness 1.8, noise 0.3
    assert cosine(bright) > 0.9
    assert cosine(kanana.standardized(bright)) < 0.3


# -- the normal path --------------------------------------------------------

def test_counters_reach_the_log_the_prometheus_rows_and_the_span(tmp_path):
    import train
    from benchmark.datagen import ensure_imagefolder
    from tpuic.config import MeshConfig
    from tpuic.runtime.mesh import make_mesh
    from tpuic.telemetry import spans
    from tpuic.telemetry.prom import train_exposition
    from tpuic.train.loop import Trainer
    data = ensure_imagefolder(str(tmp_path / "data"), size=32,
                              train_images=32, val_images=8, classes=8,
                              unique_per_class=4, corpus_seed=1)
    args = train.build_parser().parse_args([
        "--model", "kanana-tiny", "--num-classes", "10", "--resize", "32",
        "--datadir", data, "--batchsize", "8", "--epochs", "1",
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
             "router_entropy", "attention_core_fused")
    assert len(logged) == 2 and all(n in r for r in logged for n in names)
    for r in logged:
        assert r["attention_core_fused"] == 0.0     # 16 / 8 / 12-wide heads
        assert r["routed_pairs"] == 8 * 64 * 3      # T x top_k, every layer
        assert 0 < r["routed_pairs_held"] < r["routed_pairs"]
        assert r["routed_pairs_dropped"] == 0
        assert 0 <= r["routed_layers_over_buffer"] <= 1
        assert r["expert_load_max_over_mean"] >= 1.0
        assert 0 < r["router_entropy"] <= math.log(32) + 1e-6
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
    # the frozen bias got no gradient in any step: Adam's moments are zero
    moe = trainer.state.params["backbone"]["layer1"]["moe"]
    mu = [leaf for path, leaf in jax.tree_util.tree_leaves_with_path(
        trainer.state.opt_state) if "selection_bias" in
        jax.tree_util.keystr(path) and "layer1" in jax.tree_util.keystr(path)]
    assert mu and all(not np.any(np.asarray(m)) for m in mu)
    assert np.asarray(moe["selection_bias"]).std() > 0.005
