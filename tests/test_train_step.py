"""Compiled train/eval step: single device and 8-device DP mesh.

The 8-device cases are the CI stand-in for pod runs (SURVEY.md §4): gradient
averaging, global-batch BN statistics (SyncBN semantics), and exact global
eval accuracy all exercise real multi-device sharding.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpuic.config import MeshConfig, ModelConfig, OptimConfig
from tpuic.data.synthetic import synthetic_batch
from tpuic.models import create_model
from tpuic.runtime.mesh import make_mesh
from tpuic.train.optimizer import make_optimizer
from tpuic.train.state import create_train_state
from tpuic.train.step import make_eval_step, make_train_step

MCFG = ModelConfig(name="resnet18-cifar", num_classes=3, dtype="float32")
OCFG = OptimConfig(optimizer="adam", learning_rate=1e-3, class_weights=(),
                   milestones=())


def _state(mcfg=MCFG, ocfg=OCFG, batch=8, size=32):
    model = create_model(mcfg.name, mcfg.num_classes, dtype=mcfg.dtype)
    tx = make_optimizer(ocfg)
    return create_train_state(model, tx, jax.random.key(0),
                              (batch, size, size, 3))


def test_train_step_single_device_updates_params():
    state = _state()
    step = make_train_step(OCFG, MCFG, mesh=None, donate=False)
    batch = {k: jnp.asarray(v) for k, v in
             synthetic_batch(8, 32, 3).items()}
    new_state, metrics = step(state, batch)
    assert float(metrics["loss"]) > 0
    assert 0.0 <= float(metrics["accuracy"]) <= 1.0
    assert int(new_state.step) == 1
    before = jax.tree_util.tree_leaves(state.params)
    after = jax.tree_util.tree_leaves(new_state.params)
    assert any(not np.allclose(a, b) for a, b in zip(before, after))


def test_train_step_loss_decreases():
    state = _state()
    step = make_train_step(OCFG, MCFG, mesh=None, donate=False)
    batch = {k: jnp.asarray(v) for k, v in synthetic_batch(8, 32, 3).items()}
    first = None
    for _ in range(12):
        state, metrics = step(state, batch)
        if first is None:
            first = float(metrics["loss"])
    assert float(metrics["loss"]) < first


def test_mesh_step_matches_single_device(devices8):
    """DP over 8 devices must be numerically the same program as 1 device."""
    mesh = make_mesh(MeshConfig(), devices8)
    batch_np = synthetic_batch(16, 32, 3, seed=7)

    state1 = _state(batch=16)
    step1 = make_train_step(OCFG, MCFG, mesh=None, donate=False)
    _, m1 = step1(state1, {k: jnp.asarray(v) for k, v in batch_np.items()})

    state8 = _state(batch=16)
    step8 = make_train_step(OCFG, MCFG, mesh=mesh, donate=False)
    _, m8 = step8(state8, batch_np)

    assert abs(float(m1["loss"]) - float(m8["loss"])) < 1e-4
    assert abs(float(m1["accuracy"]) - float(m8["accuracy"])) < 1e-6


def test_bn_stats_are_global_batch_stats(devices8):
    """SyncBN parity (reference train.py:124): BN batch statistics under the
    sharded step must equal the UNSHARDED global-batch statistics, not
    per-shard statistics."""
    mesh = make_mesh(MeshConfig(), devices8)
    # Make per-device shards wildly different so local != global stats.
    batch_np = synthetic_batch(16, 32, 3, seed=1)
    scale = np.repeat(np.arange(1, 9, dtype=np.float32), 2)
    batch_np["image"] = (batch_np["image"]
                         * scale[:, None, None, None]).astype(np.float32)

    state1 = _state(batch=16)
    step1 = make_train_step(OCFG, MCFG, mesh=None, donate=False)
    s1, _ = step1(state1, {k: jnp.asarray(v) for k, v in batch_np.items()})

    state8 = _state(batch=16)
    step8 = make_train_step(OCFG, MCFG, mesh=mesh, donate=False)
    s8, _ = step8(state8, batch_np)

    stats1 = jax.tree_util.tree_leaves(jax.device_get(s1.batch_stats))
    stats8 = jax.tree_util.tree_leaves(jax.device_get(s8.batch_stats))
    for a, b in zip(stats1, stats8):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-3)


def test_eval_step_exact_counts(devices8):
    mesh = make_mesh(MeshConfig(), devices8)
    state = _state()
    estep = make_eval_step(OCFG, MCFG, mesh=mesh)
    batch = synthetic_batch(16, 32, 3)
    batch["mask"] = np.array([1.0] * 10 + [0.0] * 6, np.float32)
    m = estep(state, batch)
    assert float(m["count"]) == 10.0
    assert 0.0 <= float(m["correct"]) <= 10.0


def test_eval_step_per_sample_wrong_vector_is_global(devices8):
    """per_sample=True returns the GLOBAL misclassification vector,
    replicated (GSPMD all-gathers it over the data axis) — the fixed-shape
    redesign of the reference's ragged pickle all_gather
    (ddp_utils.py:16-56)."""
    mesh = make_mesh(MeshConfig(), devices8)
    state = _state()
    estep = make_eval_step(OCFG, MCFG, mesh=mesh, per_sample=True)
    batch = synthetic_batch(16, 32, 3)
    batch["mask"] = np.array([1.0] * 12 + [0.0] * 4, np.float32)
    m = estep(state, batch)
    wrong = np.asarray(m["wrong"])
    assert wrong.shape == (16,)
    assert m["wrong"].sharding.is_fully_replicated
    # padded rows can never be counted wrong; the sums are consistent
    assert np.all(wrong[12:] == 0.0)
    assert float(np.sum(wrong)) == 12.0 - float(m["correct"])
    # single-device path agrees
    single = make_eval_step(OCFG, MCFG, mesh=None, per_sample=True)(
        _state(), {k: jnp.asarray(v) for k, v in batch.items()})
    np.testing.assert_allclose(np.asarray(single["wrong"]), wrong)


def test_eval_step_confusion_matrix_exact(devices8):
    """per_class=True: the [C,C] one-hot contraction must equal the numpy
    confusion matrix over VALID samples only, and its marginals must agree
    with the step's own correct/count sums — on the 8-device mesh, where
    the contraction is a GSPMD-reduced matmul like every other eval sum."""
    mesh = make_mesh(MeshConfig(), devices8)
    state = _state()
    estep = make_eval_step(OCFG, MCFG, mesh=mesh, per_class=True)
    batch = synthetic_batch(16, 32, 3)
    batch["mask"] = np.array([1.0] * 13 + [0.0] * 3, np.float32)
    m = estep(state, batch)
    conf = np.asarray(m["confusion"])
    assert conf.shape == (3, 3)

    logits = _state().apply_fn(
        {"params": state.params, "batch_stats": state.batch_stats},
        jnp.asarray(batch["image"]), train=False)
    preds = np.argmax(np.asarray(logits), axis=-1)
    want = np.zeros((3, 3))
    for t, p, valid in zip(batch["label"], preds, batch["mask"]):
        want[int(t), int(p)] += valid
    np.testing.assert_allclose(conf, want)
    assert float(conf.sum()) == float(m["count"]) == 13.0
    np.testing.assert_allclose(np.trace(conf), float(m["correct"]))

    # single-device path agrees
    single = make_eval_step(OCFG, MCFG, mesh=None, per_class=True)(
        _state(), {k: jnp.asarray(v) for k, v in batch.items()})
    np.testing.assert_allclose(np.asarray(single["confusion"]), conf)


def test_remat_step_matches_plain_step():
    """remat must change memory behavior, never numerics."""
    state = _state()
    batch = {k: jnp.asarray(v) for k, v in synthetic_batch(8, 32, 3).items()}
    plain = make_train_step(OCFG, MCFG, mesh=None, donate=False)
    remat = make_train_step(OCFG, dataclasses.replace(MCFG, remat=True),
                            mesh=None, donate=False)
    s1, m1 = plain(state, batch)
    s2, m2 = remat(_state(), batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-6)
    np.testing.assert_allclose(float(m1["grad_norm"]), float(m2["grad_norm"]),
                               rtol=1e-5)


def _vit_state(mcfg, batch=4, size=32):
    """Build via create_model_from_config so remat_core flows from the
    config (the production path — the Trainer does the same)."""
    from tpuic.models import create_model_from_config
    model = create_model_from_config(mcfg)
    return create_train_state(model, make_optimizer(OCFG), jax.random.key(0),
                              (batch, size, size, 3))


def test_attention_remat_policy_matches_plain_step():
    """remat_policy='attention' (ViT remat_core: the logits->softmax->
    probs@v core under jax.checkpoint) must be identical numerics to the
    un-remat step."""
    mcfg = ModelConfig(name="vit-tiny", num_classes=3, dtype="float32")
    sel_cfg = dataclasses.replace(mcfg, remat=True, remat_policy="attention")
    batch = {k: jnp.asarray(v) for k, v in synthetic_batch(4, 32, 3).items()}
    plain = make_train_step(OCFG, mcfg, mesh=None, donate=False)
    sel = make_train_step(OCFG, sel_cfg, mesh=None, donate=False)
    _, m1 = plain(_vit_state(mcfg), batch)
    _, m2 = sel(_vit_state(sel_cfg), batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=1e-6)
    np.testing.assert_allclose(float(m1["grad_norm"]), float(m2["grad_norm"]),
                               rtol=1e-5)


def _residual_sizes(state, x):
    """Leaf sizes of the vjp residuals of the forward pass."""
    def fwd(params, x):
        return state.apply_fn({"params": params}, x, train=False)
    _, vjp_fn = jax.vjp(fwd, state.params, x)
    return [l.size for l in jax.tree_util.tree_leaves(vjp_fn)
            if hasattr(l, "size")]


# vit-tiny at 32px, patch 4, batch 4: N = 65 tokens, 4 heads, hidden 64.
_VIT_QUAD = 4 * 4 * 65 * 65         # B * heads * N * N
_VIT_MLP_HIDDEN = 4 * 65 * 4 * 64   # B * N * 4*hidden (GELU input)
_VIT_BOUNDARY = 4 * 65 * 64         # B * N * hidden (block input)


def test_attention_remat_drops_quadratic_residuals_only():
    """Both halves of the remat_core contract, driven through the
    PRODUCTION config path (create_model_from_config sets ViT.remat_core):
    (a) no [B,H,N,N]-sized residual survives to the backward; (b) the
    linear-sized MLP activations ARE still saved — full remat (what the
    feature must NOT degenerate into) would drop those too."""
    mcfg = ModelConfig(name="vit-tiny", num_classes=3, dtype="float32")
    sel_cfg = dataclasses.replace(mcfg, remat=True, remat_policy="attention")
    x = jnp.asarray(synthetic_batch(4, 32, 3)["image"])

    plain = _residual_sizes(_vit_state(mcfg), x)
    selective = _residual_sizes(_vit_state(sel_cfg), x)
    assert any(s == _VIT_QUAD for s in plain)
    assert any(s == _VIT_MLP_HIDDEN for s in plain)
    assert not any(s == _VIT_QUAD for s in selective)
    assert any(s == _VIT_MLP_HIDDEN for s in selective)


def test_gelu_remat_policy_matches_plain_step():
    """remat_policy='gelu' (save-anything-except the tagged ViT MLP
    pre-activations) must be identical numerics to the un-remat step."""
    mcfg = ModelConfig(name="vit-tiny", num_classes=3, dtype="float32")
    g_cfg = dataclasses.replace(mcfg, remat=True, remat_policy="gelu")
    batch = {k: jnp.asarray(v) for k, v in synthetic_batch(4, 32, 3).items()}
    plain = make_train_step(OCFG, mcfg, mesh=None, donate=False)
    gel = make_train_step(OCFG, g_cfg, mesh=None, donate=False)
    _, m1 = plain(_vit_state(mcfg), batch)
    _, m2 = gel(_vit_state(g_cfg), batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=1e-6)
    np.testing.assert_allclose(float(m1["grad_norm"]), float(m2["grad_norm"]),
                               rtol=1e-5)


def test_gelu_remat_drops_only_mlp_preactivation():
    """The 'gelu' contract (ViT remat_mlp -> MlpUpGelu under nn.remat,
    driven through the production config path): per block, the plain
    forward keeps SEVERAL [B,N,4D] residuals (pre-activation, its casts,
    erf internals, gelu output); under the policy only the region OUTPUT
    survives (one per block — mlp_down's backward operand), while the
    [B,H,N,N] attention residuals are untouched — the policy must not
    degenerate into broader remat."""
    mcfg = ModelConfig(name="vit-tiny", num_classes=3, dtype="float32")
    g_cfg = dataclasses.replace(mcfg, remat=True, remat_policy="gelu")
    x = jnp.asarray(synthetic_batch(4, 32, 3)["image"])

    plain = _residual_sizes(_vit_state(mcfg), x)
    gelu = _residual_sizes(_vit_state(g_cfg), x)
    depth = 2  # vit-tiny
    n_plain = sum(1 for s in plain if s == _VIT_MLP_HIDDEN)
    n_gelu = sum(1 for s in gelu if s == _VIT_MLP_HIDDEN)
    assert n_plain >= 2 * depth, n_plain
    assert n_gelu == depth, (n_plain, n_gelu)
    # Attention residuals untouched by this policy.
    assert any(s == _VIT_QUAD for s in gelu)


def test_gelu_remat_noop_warns_for_non_vit():
    from tpuic.train.step import resolve_remat_policy

    cfg = ModelConfig(name="resnet18-cifar", num_classes=3,
                      dtype="float32", remat=True, remat_policy="gelu")
    with pytest.warns(UserWarning, match="no effect"):
        assert resolve_remat_policy(cfg) is None


def test_blocks_remat_policy_matches_plain_step():
    """remat_policy='blocks' (ViT remat_blocks: each encoder block under
    nn.remat) must be identical numerics to the un-remat step."""
    mcfg = ModelConfig(name="vit-tiny", num_classes=3, dtype="float32")
    blk_cfg = dataclasses.replace(mcfg, remat=True, remat_policy="blocks")
    batch = {k: jnp.asarray(v) for k, v in synthetic_batch(4, 32, 3).items()}
    plain = make_train_step(OCFG, mcfg, mesh=None, donate=False)
    blk = make_train_step(OCFG, blk_cfg, mesh=None, donate=False)
    _, m1 = plain(_vit_state(mcfg), batch)
    _, m2 = blk(_vit_state(blk_cfg), batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=1e-6)
    np.testing.assert_allclose(float(m1["grad_norm"]), float(m2["grad_norm"]),
                               rtol=1e-5)


def test_blocks_remat_drops_all_block_internal_residuals():
    """The 'blocks' contract (the long-context memory mode,
    PERF_ANALYSIS.md §10f): NEITHER the [B,H,N,N] attention tensors NOR
    the [B,N,4D] MLP activations survive to the backward — only
    block-boundary [B,N,D] activations do. This is exactly the split that
    separates it from 'attention' (drops quad only) and 'dots' (keeps
    matmul outputs)."""
    mcfg = ModelConfig(name="vit-tiny", num_classes=3, dtype="float32")
    blk_cfg = dataclasses.replace(mcfg, remat=True, remat_policy="blocks")
    x = jnp.asarray(synthetic_batch(4, 32, 3)["image"])

    blocks = _residual_sizes(_vit_state(blk_cfg), x)
    assert not any(s == _VIT_QUAD for s in blocks)
    assert not any(s == _VIT_MLP_HIDDEN for s in blocks)
    assert any(s == _VIT_BOUNDARY for s in blocks)


def test_ineffective_blocks_remat_warns():
    """--remat --remat-policy blocks on a model without the ViT encoder
    applies NO remat; loud beats a silent OOM."""
    with pytest.warns(UserWarning, match="no effect"):
        make_train_step(
            OCFG,
            dataclasses.replace(MCFG, remat=True, remat_policy="blocks"),
            mesh=None, donate=False)


def test_unknown_remat_policy_rejected():
    with pytest.raises(ValueError, match="remat_policy"):
        make_train_step(
            OCFG, dataclasses.replace(MCFG, remat=True, remat_policy="nope"),
            mesh=None, donate=False)


def test_ineffective_attention_remat_warns():
    """--remat --remat-policy attention on a model/impl with no dense
    attention core applies NO remat; that must be loud, not a silent OOM."""
    with pytest.warns(UserWarning, match="no effect"):
        make_train_step(
            OCFG,
            dataclasses.replace(MCFG, remat=True, remat_policy="attention"),
            mesh=None, donate=False)


def test_weighted_ce_in_step_with_class_weights():
    ocfg = dataclasses.replace(OCFG, class_weights=(3.0, 1.0, 5.0))
    state = _state(ocfg=ocfg)
    step = make_train_step(ocfg, MCFG, mesh=None, donate=False)
    batch = {k: jnp.asarray(v) for k, v in synthetic_batch(8, 32, 3).items()}
    _, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))


def test_sharded_top5_exact():
    """Top-5 sums ride the same sharded reduction as top-1: 8-device mesh
    equals a single-device numpy recomputation exactly."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = make_mesh(MeshConfig(), jax.devices())
    mcfg = ModelConfig(name="resnet18-cifar", num_classes=7, dtype="float32")
    ocfg = OptimConfig(class_weights=())
    model = create_model(mcfg.name, mcfg.num_classes, dtype="float32")
    state = create_train_state(model, make_optimizer(ocfg),
                               jax.random.key(0), (16, 24, 24, 3))
    batch = synthetic_batch(16, 24, mcfg.num_classes)
    batch["mask"][-3:] = 0.0  # padding rows must not count
    sh = NamedSharding(mesh, P("data"))
    dev_batch = {k: jax.device_put(v, sh) for k, v in batch.items()}
    ev = make_eval_step(ocfg, mcfg, mesh)
    m = ev(state, dev_batch)
    assert "correct5" in m
    # Recompute on host from the model's own logits.
    logits = np.asarray(model.apply(
        {"params": state.params, "batch_stats": state.batch_stats},
        batch["image"], train=False))
    top5 = np.argsort(-logits, axis=-1)[:, :5]
    hit = (top5 == batch["label"][:, None]).any(axis=1)
    want = float((hit * batch["mask"]).sum())
    # The sharded sum semantics are exact; the forward itself may differ
    # from op-by-op host apply at float ulp level, which can flip a
    # near-tied rank-5/6 pair — allow one sample of slack.
    assert abs(float(m["correct5"]) - want) <= 1.0
    assert float(m["correct5"]) >= float(m["correct"])


class TestMixup:
    """On-device mixup (OptimConfig.mixup_alpha) inside the jitted step."""

    def _mix_cfg(self, alpha):
        return dataclasses.replace(OCFG, mixup_alpha=alpha)

    def test_identical_batch_is_identity(self):
        """Every sample identical: convex mixing is a no-op, so the mixup
        loss equals the plain loss exactly (any lambda, any permutation)."""
        b = synthetic_batch(8, 32, 3)
        one = {k: np.repeat(np.asarray(v)[:1], 8, axis=0) for k, v in b.items()}
        one["mask"] = np.ones((8,), np.float32)
        batch = {k: jnp.asarray(v) for k, v in one.items()}
        plain = make_train_step(OCFG, MCFG, mesh=None, donate=False)
        mixed = make_train_step(self._mix_cfg(0.2), MCFG, mesh=None,
                                donate=False)
        _, m0 = plain(_state(), batch)
        _, m1 = mixed(_state(), batch)
        np.testing.assert_allclose(float(m0["loss"]), float(m1["loss"]),
                                   rtol=1e-6)

    def test_mixed_batch_changes_loss_and_trains(self):
        batch = {k: jnp.asarray(v) for k, v in
                 synthetic_batch(8, 32, 3).items()}
        plain = make_train_step(OCFG, MCFG, mesh=None, donate=False)
        mixed = make_train_step(self._mix_cfg(0.2), MCFG, mesh=None,
                                donate=False)
        _, m0 = plain(_state(), batch)
        state, m1 = mixed(_state(), batch)
        assert np.isfinite(float(m1["loss"]))
        assert float(m0["loss"]) != float(m1["loss"])
        # trains: loss over a few steps stays finite and moves
        losses = [float(m1["loss"])]
        step = mixed
        for _ in range(4):
            state, m = step(state, batch)
            losses.append(float(m["loss"]))
        assert all(np.isfinite(l) for l in losses)
        assert losses[-1] != losses[0]  # per-step lambda varies + learning

    @pytest.mark.slow  # ~16 s CPU: 8-way mesh Mixup parity; single-device Mixup tests stay tier-1
    def test_mesh_matches_single_device(self, devices8):
        """The permutation gather composes with batch sharding: 8-device
        mixup step == single-device mixup step bitwise-close."""
        mesh = make_mesh(MeshConfig(), devices8)
        batch_np = synthetic_batch(8, 32, 3)
        b1 = {k: jnp.asarray(v) for k, v in batch_np.items()}
        from jax.sharding import NamedSharding, PartitionSpec as P
        sh = NamedSharding(mesh, P("data"))
        b8 = {k: jax.device_put(v, sh) for k, v in batch_np.items()}
        ocfg = self._mix_cfg(0.2)
        s1, m1 = make_train_step(ocfg, MCFG, mesh=None, donate=False)(
            _state(), b1)
        s8, m8 = make_train_step(ocfg, MCFG, mesh=mesh, donate=False)(
            _state(), b8)
        np.testing.assert_allclose(float(m1["loss"]), float(m8["loss"]),
                                   rtol=1e-5)


class TestCutMix:
    def _cfg(self, cutmix=1.0, mixup=0.0):
        return dataclasses.replace(OCFG, cutmix_alpha=cutmix,
                                   mixup_alpha=mixup)

    def test_identical_batch_is_identity(self):
        """Identical samples: pasting a box from an identical partner is a
        no-op, so the cutmix loss equals the plain loss exactly."""
        b = synthetic_batch(8, 32, 3)
        one = {k: np.repeat(np.asarray(v)[:1], 8, axis=0) for k, v in b.items()}
        one["mask"] = np.ones((8,), np.float32)
        batch = {k: jnp.asarray(v) for k, v in one.items()}
        _, m0 = make_train_step(OCFG, MCFG, mesh=None, donate=False)(
            _state(), batch)
        _, m1 = make_train_step(self._cfg(), MCFG, mesh=None, donate=False)(
            _state(), batch)
        np.testing.assert_allclose(float(m0["loss"]), float(m1["loss"]),
                                   rtol=1e-6)

    def test_trains_finite_and_step_varying(self):
        batch = {k: jnp.asarray(v) for k, v in
                 synthetic_batch(8, 32, 3).items()}
        step = make_train_step(self._cfg(), MCFG, mesh=None, donate=False)
        state = _state()
        losses = []
        for _ in range(4):
            state, m = step(state, batch)
            losses.append(float(m["loss"]))
        assert all(np.isfinite(l) for l in losses)
        assert len(set(losses)) > 1  # per-step box varies

    def test_both_enabled_chooses_per_step(self):
        """mixup+cutmix together compile (lax.cond branch) and train."""
        batch = {k: jnp.asarray(v) for k, v in
                 synthetic_batch(8, 32, 3).items()}
        step = make_train_step(self._cfg(cutmix=1.0, mixup=0.2), MCFG,
                               mesh=None, donate=False)
        state, m = step(_state(), batch)
        assert np.isfinite(float(m["loss"]))


def test_mixup_padded_rows_fall_back_to_self_partner():
    """A partial batch (mask zeros) under mixup must equal plain CE for
    rows whose pair involves padding — the partner defaults to SELF, so
    the padded-partner rows are unmixed, not trained on garbage."""
    rng = np.random.default_rng(0)
    b = synthetic_batch(8, 32, 3)
    b["mask"] = np.array([1, 1, 1, 1, 0, 0, 0, 0], np.float32)
    # poison padded rows: if they leak into valid rows' mixing, the loss
    # shifts far away from the all-self reference below.
    imgs = np.asarray(b["image"]).copy()
    imgs[4:] = 1e3
    b["image"] = imgs
    batch = {k: jnp.asarray(v) for k, v in b.items()}
    mix = dataclasses.replace(OCFG, mixup_alpha=0.2)
    _, m = make_train_step(mix, MCFG, mesh=None, donate=False)(
        _state(), batch)
    assert np.isfinite(float(m["loss"]))
    # Reference: identical batch where every VALID row's partner is
    # itself (the guaranteed fallback when the permutation pairs a valid
    # row with padding). Can't fix the permutation from outside, so
    # assert the self-contained property instead: loss is finite and not
    # dominated by the poisoned magnitude.
    assert float(m["loss"]) < 1e3


class TestRandomErase:
    def test_zero_prob_is_identity_and_trains_when_on(self):
        batch = {k: jnp.asarray(v) for k, v in
                 synthetic_batch(8, 32, 3).items()}
        plain = make_train_step(OCFG, MCFG, mesh=None, donate=False)
        off = make_train_step(
            dataclasses.replace(OCFG, random_erase=0.0), MCFG, mesh=None,
            donate=False)
        _, m0 = plain(_state(), batch)
        _, m1 = off(_state(), batch)
        np.testing.assert_allclose(float(m0["loss"]), float(m1["loss"]),
                                   rtol=1e-7)
        on = make_train_step(
            dataclasses.replace(OCFG, random_erase=1.0), MCFG, mesh=None,
            donate=False)
        state, m2 = on(_state(), batch)
        assert np.isfinite(float(m2["loss"]))
        assert float(m2["loss"]) != float(m0["loss"])  # boxes erased
        # Per-STEP randomness, isolated from learning: the SAME fresh
        # params at different step counters must see different boxes.
        s5 = _state().replace(step=jnp.asarray(5, jnp.int32))
        _, m5 = on(s5, batch)
        assert float(m5["loss"]) != float(m2["loss"])
