"""The looped stack (models/ouro.py) on the program's own terms: the loop
adds no weights, every part of the objective reaches every weight, the
exit distribution is one, per-block remat saves the block inputs only, the
cut is tied to the published model and the FLOP count to the scan, and the
Trainer path trains, evaluates, checkpoints and resumes it."""

import dataclasses
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpuic.config import ModelConfig, OptimConfig
from tpuic.data.synthetic import synthetic_batch
from tpuic.models import create_model, create_model_from_config
from tpuic.models.classifier import ExitOutputs
from tpuic.train.loss import (exit_distribution, exit_expected_loss,
                              weighted_cross_entropy)
from tpuic.train.optimizer import make_optimizer
from tpuic.train.state import create_train_state
from tpuic.train.step import (make_eval_step, make_train_step,
                              resolve_remat_policy)

MCFG = ModelConfig(name="ouro-tiny", num_classes=10, dtype="float32")
OCFG = OptimConfig(optimizer="adam", learning_rate=1e-3, class_weights=(),
                   milestones=())
LAYER_PARAMS = 51_388_416       # 4 x 2048^2 + 3 x 2048 x 5632 + 4 x 2048


def _state(mcfg=MCFG, size=32):
    model = create_model_from_config(mcfg)
    return create_train_state(model, make_optimizer(OCFG), jax.random.key(0),
                              (4, size, size, 3))


def _batch(n=4, classes=10):
    return {k: jnp.asarray(v)
            for k, v in synthetic_batch(n, 32, classes).items()}


def _perturbed(params, seed=0, by=0.1):
    """Nothing left at its initial value: scales of 1 and a gate bias of 0
    would hide an error in how they enter."""
    rng = np.random.default_rng(seed)
    return jax.tree_util.tree_map(
        lambda a: a + by * rng.standard_normal(a.shape).astype(a.dtype),
        params)


# -- the loop ---------------------------------------------------------------

def test_the_loop_adds_no_weights_and_changes_the_output():
    from tpuic.models import ouro
    x = _batch()["image"]
    four = ouro.ouro_tiny(passes=4)
    one = ouro.ouro_tiny(passes=1)
    v = four.init(jax.random.key(0), x)
    v1 = jax.eval_shape(lambda: one.init(jax.random.key(0), x))
    shapes = jax.tree_util.tree_map(lambda a: a.shape, v)
    assert shapes == jax.tree_util.tree_map(lambda a: a.shape, v1)
    f4, f1 = four.apply(v, x), one.apply(v, x)
    assert f4.features.shape == (4, 4, 64) and f1.features.shape == (1, 4, 64)
    assert f4.gate_logits.shape == (4, 4)
    # the first pass is the same computation; the last is not the first
    np.testing.assert_allclose(f4.features[0], f1.features[0], atol=1e-5)
    assert float(jnp.abs(f4.features[-1] - f1.features[0]).max()) > 1e-2


def test_the_loop_is_one_scan_of_the_passes():
    """``benchmark/flops.py`` multiplies a scan's body by its length and
    counts a while loop as one trip: the loop has to stay a scan."""
    model = create_model("ouro-tiny", 10)
    x = jnp.zeros((1, 32, 32, 3))
    v = jax.eval_shape(lambda: model.init(jax.random.key(0), x))
    jaxpr = jax.make_jaxpr(lambda v, x: model.apply(v, x, train=False))(v, x)
    scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    assert [e.params["length"] for e in scans] == [4]
    assert not any(e.primitive.name == "while" for e in jaxpr.jaxpr.eqns)


def test_train_mode_gives_every_pass_and_eval_the_last():
    state = _state()
    x = _batch()["image"]
    variables = {"params": state.params}
    out = state.apply_fn(variables, x, train=True)
    assert isinstance(out, ExitOutputs) and not isinstance(out, tuple)
    assert out.logits.shape == (4, 4, 10) and out.gate_logits.shape == (4, 4)
    np.testing.assert_allclose(state.apply_fn(variables, x, train=False),
                               out.logits[-1], atol=1e-6)


def test_attention_is_causal_and_the_read_out_sees_every_token():
    from tpuic.models import ouro
    model = ouro.ouro_tiny(passes=2)
    x = np.asarray(_batch()["image"])
    v = model.init(jax.random.key(0), x)
    base = model.apply(v, x).features

    def moved(rows, cols):
        y = x.copy()
        y[:, rows, cols] += 1.0
        return float(jnp.abs(model.apply(v, y).features - base).max())
    assert moved(slice(0, 4), slice(0, 4)) > 1e-4     # the first token
    assert moved(slice(28, 32), slice(28, 32)) > 1e-4     # the last
    block = ouro.LoopedBlock(4, 16, 176)
    h = jax.random.normal(jax.random.key(1), (2, 64, 64))
    bv = block.init(jax.random.key(2), h)
    out = block.apply(bv, h)
    later = block.apply(bv, h.at[:, 40:].add(1.0))
    np.testing.assert_allclose(out[:, :40], later[:, :40], atol=1e-5)
    assert float(jnp.abs(out[:, 40:] - later[:, 40:]).max()) > 1e-2


def test_rotary_turns_pairs_by_position_and_keeps_their_length():
    from tpuic.models.layers import rotate
    from tpuic.models.ouro import rotary_tables
    cos, sin = rotary_tables(196, 128, 1e6)
    assert cos.shape == sin.shape == (196, 128) and cos.dtype == np.float32
    np.testing.assert_allclose(cos[0], 1.0)
    np.testing.assert_allclose(sin[0], 0.0)
    np.testing.assert_allclose(cos[:, :64], cos[:, 64:])
    # pair 0 turns by one radian a position; the last by theta^(-126/128)
    np.testing.assert_allclose(cos[5, 0], math.cos(5.0), rtol=1e-5)
    np.testing.assert_allclose(sin[7, 63], math.sin(7.0 * 1e6 ** (-126 / 128)),
                               rtol=1e-4)
    x = jax.random.normal(jax.random.key(0), (2, 196, 3 * 128))
    y = rotate(x, cos, sin)
    np.testing.assert_allclose(
        jnp.linalg.norm(y.reshape(2, 196, 3, 128), axis=-1),
        jnp.linalg.norm(x.reshape(2, 196, 3, 128), axis=-1), rtol=1e-5)
    # q.k depends on the distance alone
    q, k = x[:, :1, :128], x[:, 1:2, :128]
    def dot(i, j):
        qi = rotate(q, cos[i:i + 1], sin[i:i + 1])
        kj = rotate(k, cos[j:j + 1], sin[j:j + 1])
        return jnp.sum(qi * kj)
    np.testing.assert_allclose(dot(9, 4), dot(105, 100), rtol=1e-4)


def _rotary_in_float32_passes(x, cos, sin):
    """The rotary as it was before PR 38, the oracle of the one-pass one:
    ``x`` [B, N, H, D] upcast, split, negated, concatenated, multiplied
    and added in float32 (autodiff builds its gradient)."""
    x = x.astype(jnp.float32)
    x1, x2 = jnp.split(x, 2, axis=-1)
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return x * cos[None, :, None, :] + rotated * sin[None, :, None, :]


@pytest.mark.parametrize("yarn", [False, True], ids=["plain", "yarn"])
@pytest.mark.parametrize("n,h,d", [(196, 16, 128), (64, 4, 128), (7, 3, 8)])
def test_the_one_pass_rotary_is_the_float32_passes(n, h, d, yarn):
    """``layers.rotate`` against the passes it replaced: bfloat16 out
    within one bfloat16 ulp of their float32 result, its hand-written VJP
    their ``jax.vjp`` to float32 rounding (and to one ulp in bfloat16),
    on the plain and the YaRN tables, at a head of 128 and a tiny one, at
    row counts a tile's 8 divides and does not. Where the two terms
    cancel, the ulp of the result is below float32's rounding of the
    terms, so that rounding (``2^-20`` of the largest input) is allowed
    beside it."""
    from tpuic.models.layers import rotate
    from tpuic.models.mellum import YARN, layer_rotary_tables
    cos, sin = layer_rotary_tables(n, d, 500000.0, YARN if yarn else None)
    b = 1 if n == 7 else 2      # 7 rows: no tile of 8 to view them by
    x = 2.0 * jax.random.normal(jax.random.key(n + d), (b, n, h * d))
    g = jax.random.normal(jax.random.key(n + d + 1), (b, n, h * d))

    def before(x):
        return _rotary_in_float32_passes(
            x.reshape(b, n, h, d), cos, sin).reshape(b, n, h * d)

    def after(x):
        return rotate(x, cos, sin)

    def ulp(v, of):     # of bfloat16 at |v|, and float32's of the terms
        return (2.0 ** (np.floor(np.log2(np.maximum(np.abs(v), 1e-30))) - 7)
                + 2.0 ** -20 * float(jnp.max(jnp.abs(of))))

    xb, gb = x.astype(jnp.bfloat16), g.astype(jnp.bfloat16)
    want = np.asarray(before(xb))
    got = after(xb)
    assert got.dtype == jnp.bfloat16
    assert np.all(np.abs(np.asarray(got, np.float32) - want)
                  <= ulp(want, xb))
    want_dx = np.asarray(jax.vjp(before, xb.astype(jnp.float32))[1](
        gb.astype(jnp.float32))[0])
    got_dx = jax.vjp(after, xb)[1](gb)[0]
    assert got_dx.dtype == jnp.bfloat16
    assert np.all(np.abs(np.asarray(got_dx, np.float32) - want_dx)
                  <= ulp(want_dx, gb))
    # float32 in, float32 out: the same numbers to rounding, both ways
    np.testing.assert_allclose(after(x), before(x), rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(jax.vjp(after, x)[1](g)[0],
                               jax.vjp(before, x)[1](g)[0],
                               rtol=1e-6, atol=1e-6)


def test_each_rotary_path_is_counted_as_a_model_traces_it():
    """Each rotary path lies under the scope ``rotary`` in the compiled
    train step, where the device trace's reader finds it, forward and
    backward: the looped stack's through the one-pass ``rotate`` (ops
    inside ``jit(_rotate_once)``), latent attention's through
    ``interleaved_rotary`` (none there)."""
    from tpuic.telemetry.profile import Programs, scope_path
    for name, one_pass in (("ouro-tiny", True), ("kanana-tiny", False)):
        mcfg = dataclasses.replace(MCFG, name=name)
        state = jax.eval_shape(lambda: _state(mcfg))
        progs = Programs()
        progs.note("step", make_train_step(OCFG, mcfg, donate=False),
                   (state, jax.eval_shape(_batch)))
        rotary = [n for n, _, _ in progs.scope_map("step")["ops"].values()
                  if "rotary" in scope_path(n)]
        assert any("transpose(" in n for n in rotary), name
        assert any("transpose(" not in n for n in rotary), name
        assert any("_rotate_once" in n for n in rotary) == one_pass, name


def test_tracing_the_looped_step_imports_no_pallas():
    """PR 37 lost its gain to set-up seconds in this cell; the likely
    cause was Pallas lowered inside the looped stack's scan. Tracing and
    lowering the cell's train step (batch 2, CPU) must leave
    ``jax.experimental.pallas`` unimported."""
    import subprocess
    import sys
    script = """
import sys
import jax, jax.numpy as jnp
from flax.core import meta
from tpuic.config import ModelConfig, OptimConfig
from tpuic.models import create_model_from_config
from tpuic.train.optimizer import make_optimizer
from tpuic.train.state import TrainState
from tpuic.train.step import make_train_step
mc = ModelConfig(name="ouro-2.6b-l6", num_classes=1000, dtype="bfloat16",
                 remat=True, remat_policy="blocks", attention="dense")
oc = OptimConfig(optimizer="adam", class_weights=(), milestones=())
model = create_model_from_config(mc)
x = jnp.zeros((2, 224, 224, 3))
v = meta.unbox(jax.eval_shape(
    lambda: model.init(jax.random.key(0), x, train=False)))
tx = make_optimizer(oc, 8, 1, global_batch=2)
state = jax.eval_shape(lambda p: TrainState(
    step=jnp.zeros((), jnp.int32), params=p, batch_stats={},
    opt_state=tx.init(p), apply_fn=model.apply, tx=tx, ema_params=None,
    skip_count=jnp.zeros((), jnp.int32)), v["params"])
batch = {"image": jax.ShapeDtypeStruct(x.shape, jnp.float32),
         "label": jax.ShapeDtypeStruct((2,), jnp.int32),
         "mask": jax.ShapeDtypeStruct((2,), jnp.float32)}
make_train_step(oc, mc, mesh=None, donate=False).lower(state, batch)
print("PALLAS", sorted(m for m in sys.modules if "pallas" in m))
"""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=root)
    out = subprocess.run([sys.executable, "-c", script], cwd=root, env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "PALLAS []" in out.stdout, out.stdout[-2000:]


# -- the objective ----------------------------------------------------------

def test_exit_distribution_sums_to_one_and_the_last_pass_takes_the_rest():
    g = jax.random.normal(jax.random.key(0), (4, 6)) * 3.0
    p = jnp.exp(exit_distribution(g))
    np.testing.assert_allclose(p.sum(axis=0), 1.0, atol=1e-6)
    lam = jax.nn.sigmoid(g)
    np.testing.assert_allclose(p[0], lam[0], rtol=1e-6)
    np.testing.assert_allclose(p[2], lam[2] * (1 - lam[0]) * (1 - lam[1]),
                               rtol=1e-3, atol=1e-7)
    np.testing.assert_allclose(
        p[3], (1 - lam[0]) * (1 - lam[1]) * (1 - lam[2]), rtol=1e-3,
        atol=1e-7)
    # a saturated gate: no nan in the loss or its gradient
    hard = jnp.array([[80.0], [-80.0], [0.0], [0.0]])
    out = ExitOutputs(logits=jnp.zeros((4, 1, 3)), gate_logits=hard)
    loss, grad = jax.value_and_grad(
        lambda o: exit_expected_loss(o, jnp.array([1]))[0])(out)
    assert bool(jnp.isfinite(loss))
    assert all(bool(jnp.isfinite(leaf).all())
               for leaf in jax.tree_util.tree_leaves(grad))
    assert jnp.exp(exit_distribution(jnp.zeros((1, 3)))).tolist() == [[1.0] * 3]


def test_expected_loss_is_the_expectation_less_the_entropy_and_masks_rows():
    rng = np.random.default_rng(0)
    out = ExitOutputs(logits=jnp.asarray(rng.standard_normal((4, 6, 5)),
                                         jnp.float32),
                      gate_logits=jnp.asarray(rng.standard_normal((4, 6)),
                                              jnp.float32))
    labels = jnp.asarray(rng.integers(0, 5, 6))
    loss, stats = exit_expected_loss(out, labels, entropy_weight=0.05)
    p = np.exp(np.asarray(exit_distribution(out.gate_logits)))
    logp = np.asarray(jax.nn.log_softmax(out.logits, axis=-1))
    nll = -np.take_along_axis(
        logp, np.broadcast_to(np.asarray(labels)[None, :, None], (4, 6, 1)),
        axis=-1)[..., 0]
    entropy = -(p * np.log(p)).sum(0)
    np.testing.assert_allclose(
        loss, ((p * nll).sum(0) - 0.05 * entropy).mean(), rtol=1e-5)
    assert {f"exit_p{t}" for t in range(1, 5)} | {
        f"loss_pass{t}" for t in range(1, 5)} | {
        "exit_expected_pass", "exit_entropy"} == set(stats)
    np.testing.assert_allclose(
        sum(float(stats[f"exit_p{t}"]) for t in range(1, 5)), 1.0, atol=1e-6)
    np.testing.assert_allclose(stats["exit_entropy"], entropy.mean(),
                               rtol=1e-5)
    np.testing.assert_allclose(
        stats["exit_expected_pass"],
        (p * np.arange(1, 5)[:, None]).sum(0).mean(), rtol=1e-5)
    for t in range(4):      # each pass's CE as the plain loss computes it
        np.testing.assert_allclose(
            stats[f"loss_pass{t + 1}"],
            weighted_cross_entropy(out.logits[t], labels), rtol=1e-5)
    # a masked row plays no part; class weights weigh rows
    mask = jnp.array([1, 1, 1, 0, 1, 1.0])
    masked, _ = exit_expected_loss(out, labels, mask=mask)
    other = ExitOutputs(out.logits.at[:, 3].set(7.0),
                        out.gate_logits.at[:, 3].set(-2.0))
    np.testing.assert_allclose(
        masked, exit_expected_loss(other, labels, mask=mask)[0], rtol=1e-6)
    cw = jnp.array([1.0, 2.0, 3.0, 1.0, 0.5])
    weighted, _ = exit_expected_loss(out, labels, class_weights=cw)
    w = np.asarray(cw)[np.asarray(labels)]
    np.testing.assert_allclose(
        weighted, (w * ((p * nll).sum(0) - 0.05 * entropy)).sum() / w.sum(),
        rtol=1e-5)


def test_every_weight_gets_a_gradient_and_the_step_reports_the_counters():
    state = _state()
    state = state.replace(params=_perturbed(state.params))
    batch = _batch()
    step = make_train_step(OCFG, MCFG, mesh=None, donate=False)
    new, metrics = step(state, batch)
    moved = jax.tree_util.tree_map(
        lambda a, b: float(jnp.abs(a - b).max()), new.params, state.params)
    still = [jax.tree_util.keystr(k) for k, v in
             jax.tree_util.tree_leaves_with_path(moved) if v == 0.0]
    assert not still, still      # the gate, every block, every norm
    out = state.apply_fn({"params": state.params}, batch["image"], train=True)
    want, stats = exit_expected_loss(out, batch["label"], mask=batch["mask"],
                                     entropy_weight=MCFG.exit_entropy_weight)
    np.testing.assert_allclose(metrics["loss"], want, rtol=1e-5)
    for k, v in stats.items():
        np.testing.assert_allclose(metrics[k], v, rtol=1e-5)
    # accuracy and the eval step read the last pass
    ev = make_eval_step(OCFG, MCFG, mesh=None)(state, batch)
    last = jnp.argmax(out.logits[-1], axis=-1) == batch["label"]
    assert float(ev["correct"]) == float(jnp.sum(last))
    np.testing.assert_allclose(metrics["accuracy"], jnp.mean(last))
    # the last pass's loss alone leaves the gate without a gradient
    g = jax.grad(lambda p: weighted_cross_entropy(
        state.apply_fn({"params": p}, batch["image"], train=True).logits[-1],
        batch["label"]))(state.params)
    assert float(jnp.abs(g["backbone"]["exit_gate"]["kernel"]).max()) == 0.0


def test_the_plain_loss_refuses_exit_outputs():
    from tpuic.train.loss import classification_loss
    out = ExitOutputs(jnp.zeros((4, 2, 3)), jnp.zeros((4, 2)))
    with pytest.raises(TypeError, match="exit_expected_loss"):
        classification_loss(out, jnp.array([0, 1]))


# -- rematerialisation ------------------------------------------------------

def _residual_sizes(state, x):
    def fwd(params, x):
        return state.apply_fn({"params": params}, x, train=True).logits
    _, vjp_fn = jax.vjp(fwd, state.params, x)
    return [leaf.size for leaf in jax.tree_util.tree_leaves(vjp_fn)
            if hasattr(leaf, "size")]


def test_blocks_remat_saves_the_block_inputs_of_every_pass_only():
    # ouro-tiny at 32 px: B 4, N 64 tokens, 4 heads, hidden 64, MLP 176;
    # the scan stacks every residual over its 4 passes
    quad, mlp, boundary = (4 * 4 * 4 * 64 * 64, 4 * 4 * 64 * 176,
                           4 * 4 * 64 * 64)
    x = _batch()["image"]
    blk = dataclasses.replace(MCFG, remat=True, remat_policy="blocks")
    plain = _residual_sizes(_state(MCFG), x)
    saved = _residual_sizes(_state(blk), x)
    for inner in (quad, mlp):
        assert inner in plain and inner not in saved
    assert 2 <= saved.count(boundary) < plain.count(boundary)   # one a block
    assert sum(saved) < sum(plain) / 3


def test_blocks_remat_is_the_same_step_and_no_longer_warns(recwarn):
    blk = dataclasses.replace(MCFG, remat=True, remat_policy="blocks")
    assert resolve_remat_policy(blk) is None        # it lives in the model
    assert not [w for w in recwarn if "no effect" in str(w.message)]
    assert create_model_from_config(blk).backbone.remat_blocks
    batch = _batch()
    _, m1 = make_train_step(OCFG, MCFG, mesh=None, donate=False)(
        _state(MCFG), batch)
    _, m2 = make_train_step(OCFG, blk, mesh=None, donate=False)(
        _state(blk), batch)
    np.testing.assert_allclose(m1["loss"], m2["loss"], rtol=1e-6)
    np.testing.assert_allclose(m1["grad_norm"], m2["grad_norm"], rtol=1e-5)
    for policy in ("attention", "gelu"):        # those stay the ViT's
        with pytest.warns(UserWarning, match="no effect"):
            resolve_remat_policy(dataclasses.replace(
                MCFG, remat=True, remat_policy=policy))


# -- the cut, the count -----------------------------------------------------

def _config():
    from benchmark import harness
    spec = harness.load_spec()
    entry = next(c for c in spec["configs"] if c["source"].endswith(
        "ByteDance/Ouro-2.6B/blob/main/config.json"))
    with open(os.path.join(harness.REPO, entry["file"])) as f:
        return json.load(f)


def _layer_sizes(name):
    model = create_model(name, 1000)
    v = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 224, 224, 3)), train=True))
    stack = v["params"]["backbone"]["loop_pass"]
    return model, {k: sum(math.prod(leaf.shape) for leaf in
                          jax.tree_util.tree_leaves(sub))
                   for k, sub in stack.items() if k.startswith("block")}


def test_the_cut_is_the_published_model_less_layers_and_the_count_is_pinned():
    """By ``eval_shape`` alone (nothing is allocated): depth 48 holds 48
    published layers; the configuration's flags build its
    ``num_hidden_layers`` of them at its widths; and the forward count that
    ``device_mfu`` divides by is four times one pass's."""
    from benchmark.flops import model_forward_macs_per_image
    import train
    _, full = _layer_sizes("ouro-2.6b")
    assert len(full) == 48 and set(full.values()) == {LAYER_PARAMS}
    body = _config()
    assert body["published"]["num_hidden_layers"] == 48
    args = train.build_parser().parse_args(
        [*body["train_flags"], "--datadir", "x"])
    cfg = train.config_from_args(args)
    assert cfg.model.remat and cfg.model.remat_policy == "blocks"
    assert cfg.model.exit_entropy_weight == body["exit_entropy_weight"]
    model, held = _layer_sizes(cfg.model.name)
    assert len(held) == body["num_hidden_layers"]
    assert set(held.values()) == {LAYER_PARAMS}
    b = model.backbone
    assert (b.hidden, b.mlp_width, b.num_heads, b.head_dim, b.passes, b.patch,
            b.rope_theta, b.eps) == (
        body["hidden_size"], body["intermediate_size"],
        body["num_attention_heads"], body["head_dim"],
        body["total_ut_steps"], body["patch"], body["rope_theta"],
        body["rms_norm_eps"])
    macs = model_forward_macs_per_image(model, body["image_size"])
    assert abs(macs / 245.8e9 - 1.0) < 0.005
    assert abs(macs / (body["forward_gmacs_per_image_here"] * 1e9) - 1) < 0.005
    # by hand: weights 51.38 M and dense attention over 196 keys a token a
    # block application, 24 applications, the patch embedding, the head
    by_hand = 196 * 24 * (51_380_224 + 2 * 196 * 2048) + 196 * 768 * 2048
    assert abs(macs / by_hand - 1.0) < 0.001


# -- the normal path --------------------------------------------------------

def _run_cfg(tmp_path, data, epochs):
    import train
    args = train.build_parser().parse_args([
        "--model", "ouro-tiny", "--num-classes", "10", "--resize", "32",
        "--datadir", data, "--batchsize", "8", "--epochs", str(epochs),
        "--log-every-steps", "2", "--no-class-weights", "--workers", "1",
        "--remat", "--remat-policy", "blocks", "--milestones",
        "--ckpt-dir", str(tmp_path / "ckpt"),
        "--log-dir", str(tmp_path / "log")])
    return train.config_from_args(args), args


def test_trainer_trains_evaluates_checkpoints_and_resumes(tmp_path):
    from benchmark.datagen import ensure_imagefolder
    from tpuic.telemetry import spans
    from tpuic.telemetry.prom import train_exposition
    from tpuic.config import MeshConfig
    from tpuic.runtime.mesh import make_mesh
    from tpuic.train.loop import Trainer
    data = ensure_imagefolder(str(tmp_path / "data"), size=32,
                              train_images=32, val_images=8, classes=8,
                              unique_per_class=4, corpus_seed=1)
    cfg, args = _run_cfg(tmp_path, data, epochs=1)
    mesh = make_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
    trainer = Trainer(cfg, mesh=mesh, log_dir=args.log_dir)
    trainer.fit()
    step = int(trainer.state.step)
    assert step == 4
    with open(tmp_path / "log" / "metrics.jsonl") as f:
        rows = [json.loads(ln) for ln in f]
    logged = [r for r in rows if "exit_expected_pass" in r]
    assert len(logged) == 2 and all(
        abs(sum(r[f"exit_p{t}"] for t in range(1, 5)) - 1.0) < 1e-5
        and 1.0 <= r["exit_expected_pass"] <= 4.0 and "loss_pass4" in r
        for r in logged)
    assert any("val_accuracy" in r for r in rows)
    assert trainer.last_counters["exit_expected_pass"] == logged[-1][
        "exit_expected_pass"]
    text = train_exposition({}, counters=trainer.last_counters)
    assert 'tpuic_train_exit_probability{pass="4"}' in text
    assert "tpuic_train_exit_expected_pass" in text
    assert 'tpuic_train_pass_loss{pass="1"}' in text
    records = spans.ledger.snapshot()
    init = [r for r in records if r["name"] == "trainer.state_init"][-1]
    from tpuic.utils import tree_bytes
    assert init["attrs"]["param_bytes"] == tree_bytes(trainer.state.params)
    assert init["attrs"]["opt_state_bytes"] == tree_bytes(
        trainer.state.opt_state) >= 2 * init["attrs"]["param_bytes"]
    epoch = [r for r in records if r["name"] == "train_epoch"][-1]
    assert epoch["attrs"]["exit_expected_pass"] == logged[-1][
        "exit_expected_pass"]
    # resume: the second epoch starts from the first's state
    cfg2, args2 = _run_cfg(tmp_path, data, epochs=2)
    resumed = Trainer(cfg2, mesh=mesh, log_dir=args2.log_dir)
    resumed.fit()
    assert int(resumed.state.step) == 8
    with open(tmp_path / "log" / "metrics.jsonl") as f:
        steps = [json.loads(ln)["step"] for ln in f if "exit_entropy" in ln]
    assert steps == [2, 4, 6, 8]    # epoch 1 alone ran again: it resumed
