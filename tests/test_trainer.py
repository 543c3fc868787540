"""Integration: full train+val+checkpoint+resume cycle on a tiny ImageFolder
tree over the 8-device mesh (SURVEY.md §4 'Integration')."""

import dataclasses
import os

import pytest

from tpuic.config import (Config, DataConfig, MeshConfig, ModelConfig,
                          OptimConfig, RunConfig)
from tpuic.train.loop import Trainer


def _config(imagefolder, tmp_path, epochs=2):
    return Config(
        data=DataConfig(data_dir=imagefolder, resize_size=32, batch_size=2,
                        num_workers=2, shuffle_seed=0),
        model=ModelConfig(name="resnet18-cifar", num_classes=0,
                          dtype="float32"),
        optim=OptimConfig(optimizer="adam", learning_rate=1e-3,
                          class_weights=(), milestones=()),
        run=RunConfig(epochs=epochs, ckpt_dir=str(tmp_path / "cp"),
                      save_period=2, resume=True),
        mesh=MeshConfig(),
    )


@pytest.mark.slow  # full 2-epoch fit + resume: ~30 s CPU training
def test_fit_end_to_end_and_resume(imagefolder, tmp_path, devices8):
    cfg = _config(imagefolder, tmp_path, epochs=2)
    trainer = Trainer(cfg, log_dir=str(tmp_path / "logs"))
    # num_classes inferred from the folder tree (3 classes).
    assert trainer.model.num_classes == 3
    best = trainer.fit()
    assert 0.0 <= best <= 100.0
    assert os.path.isdir(os.path.join(str(tmp_path / "cp"),
                                      "resnet18-cifar", "best"))
    # metrics.jsonl written
    assert os.path.isfile(str(tmp_path / "logs" / "metrics.jsonl"))

    # Resume: a fresh trainer picks up the best checkpoint and starts at the
    # saved epoch + 1 (the reference restarts at 0 — train.py:161 bug, fixed).
    trainer2 = Trainer(_config(imagefolder, tmp_path, epochs=2))
    assert trainer2.start_epoch > 0
    assert trainer2.best_score == pytest.approx(best)
    # fit() with epochs already passed is a no-op, not a retrain.
    assert trainer2.fit() == pytest.approx(best)


@pytest.mark.slow  # full fit watching log cadence: ~30 s CPU training
def test_deferred_logging_emits_every_interval(imagefolder, tmp_path,
                                               devices8):
    """The deferred-readback log path must not
    change logging semantics: one record per log interval including the
    epoch's last (drained while the bar is open), host-tracked step numbers
    identical to what reading state.step used to produce, and the standard
    field set in every record."""
    import json

    cfg = _config(imagefolder, tmp_path, epochs=2)
    cfg = dataclasses.replace(
        cfg,
        data=dataclasses.replace(cfg.data, batch_size=1),  # 2 steps/epoch
        run=dataclasses.replace(cfg.run, log_every_steps=1))
    trainer = Trainer(cfg, log_dir=str(tmp_path / "logs"))
    assert trainer.train_loader.steps_per_epoch() == 2
    trainer.fit()
    train_recs, val_recs = [], []
    with open(str(tmp_path / "logs" / "metrics.jsonl")) as f:
        for line in f:
            rec = json.loads(line)
            (train_recs if "loss" in rec else val_recs).append(rec)
    # 2 epochs x 2 steps at log_every=1: every interval logged exactly once,
    # step numbers matching the optimizer step counter (1-based after the
    # step that completed the interval).
    assert [r["step"] for r in train_recs] == [1, 2, 3, 4]
    for r in train_recs:
        assert {"loss", "accuracy", "lr", "images_per_sec"} <= set(r)
        # >= 0 for the first record: with log_every=1 its interval carries
        # the train-step compile, and a cold-cache CPU compile can be slow
        # enough that round(rate, 1) lands on 0.0.
        assert r["images_per_sec"] >= 0
    assert train_recs[-1]["images_per_sec"] > 0
    # One val record per epoch, stamped with the epoch-final step.
    assert [r["step"] for r in val_recs] == [2, 4]
    assert all("val_accuracy" in r for r in val_recs)
    import jax
    assert int(jax.device_get(trainer.state.step)) == 4


def test_init_from_torch_checkpoint(imagefolder, tmp_path, devices8):
    """--init-from: pretrained torch weights land in the live state
    (reference starts every backbone pretrained, nn/classifier.py:9-21)."""
    torch = pytest.importorskip("torch")
    import numpy as np
    from tpuic.checkpoint.torch_ref import build_resnet

    torch.manual_seed(11)
    tm = build_resnet("resnet18", num_classes=3)
    ckpt = str(tmp_path / "best_model")
    torch.save({"epoch": 7, "best_score": 66.0,
                "state_dict": {f"module.encoder.{k}": v
                               for k, v in tm.state_dict().items()}}, ckpt)

    cfg = _config(imagefolder, tmp_path)
    cfg = dataclasses.replace(
        cfg, model=dataclasses.replace(cfg.model, name="resnet18"),
        run=dataclasses.replace(cfg.run, init_from=ckpt))
    trainer = Trainer(cfg)
    got = np.asarray(trainer.state.params["backbone"]["conv1"]["kernel"])
    want = np.transpose(tm.conv1.weight.detach().numpy(), (2, 3, 1, 0))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def test_collect_misclassified_ids(imagefolder, tmp_path, devices8):
    """RunConfig.collect_misclassified: after a val epoch every misclassified
    sample is named by image id, the count reconciles with val accuracy, and
    the ids are real dataset ids — the reference's per-sample all_gather
    capability (train.py:92, ddp_utils.py:16-56) without the pickle."""
    cfg = _config(imagefolder, tmp_path, epochs=1)
    cfg = dataclasses.replace(
        cfg, run=dataclasses.replace(cfg.run, collect_misclassified=True,
                                     resume=False))
    trainer = Trainer(cfg)
    score = trainer.val_epoch(0)
    n_val = len(trainer.val_ds)
    expected_wrong = round(n_val * (1.0 - score / 100.0))
    assert len(trainer.last_misclassified) == expected_wrong
    valid = {trainer.val_ds.image_id(i) for i in range(n_val)}
    assert set(trainer.last_misclassified) <= valid
    # Every id unique: padding duplicates must not leak in.
    assert len(set(trainer.last_misclassified)) == \
        len(trainer.last_misclassified)


@pytest.mark.slow  # trains to compare weighted losses: ~15 s CPU
def test_auto_class_weights(tmp_path):
    """--class-weights auto derives inverse-frequency weights from the
    train fold; rarer classes get proportionally larger weights."""
    import numpy as np
    from tpuic.data.synthetic import make_synthetic_imagefolder

    root = str(tmp_path / "imb")
    make_synthetic_imagefolder(root, classes=("rare",), per_class=4, size=24)
    make_synthetic_imagefolder(root, classes=("common",), per_class=12,
                               size=24)
    cfg = Config(
        data=DataConfig(data_dir=root, resize_size=24, batch_size=2),
        model=ModelConfig(name="resnet18-cifar", num_classes=0,
                          dtype="float32"),
        optim=OptimConfig(optimizer="sgd", learning_rate=0.01,
                          class_weights=(), auto_class_weights=True,
                          milestones=()),
        run=RunConfig(epochs=1, ckpt_dir=str(tmp_path / "ck"), resume=False),
        mesh=MeshConfig(),
    )
    trainer = Trainer(cfg)
    w = dict(zip(trainer.train_ds.classes, trainer.cfg.optim.class_weights))
    # classes sorted: common(12) -> idx 0, rare(4) -> idx 1; N=16, K=2.
    assert w["common"] == pytest.approx(16 / (2 * 12), abs=1e-5)
    assert w["rare"] == pytest.approx(16 / (2 * 4), abs=1e-5)
    assert w["rare"] > w["common"]
    # The derived weights flow into the jitted step (finite weighted loss).
    batch = next(iter(trainer.train_loader.epoch(0)))
    _, m = trainer.train_step(
        trainer.state, {k: batch[k] for k in ("image", "label", "mask")})
    assert np.isfinite(float(m["loss"]))


def test_auto_class_weights_pads_to_model_head(tmp_path):
    """--num-classes wider than the fold's class count: absent classes get
    weight 1.0 instead of a trace-time shape error."""
    from tpuic.data.synthetic import make_synthetic_imagefolder
    root = str(tmp_path / "pad")
    make_synthetic_imagefolder(root, classes=("a", "b"), per_class=8,
                               size=24)
    cfg = Config(
        data=DataConfig(data_dir=root, resize_size=24, batch_size=2),
        model=ModelConfig(name="resnet18-cifar", num_classes=4,
                          dtype="float32"),
        optim=OptimConfig(optimizer="sgd", learning_rate=0.01,
                          class_weights=(), auto_class_weights=True,
                          milestones=()),
        run=RunConfig(epochs=1, ckpt_dir=str(tmp_path / "ck"), resume=False),
        mesh=MeshConfig(),
    )
    trainer = Trainer(cfg)
    w = trainer.cfg.optim.class_weights
    assert len(w) == 4
    assert w[2] == 1.0 and w[3] == 1.0
    assert w[0] == w[1] == 1.0  # balanced present classes -> ~1 each


@pytest.mark.slow  # one sharded epoch end to end: ~30 s CPU training
def test_trainer_zero1_wiring(tmp_path):
    """MeshConfig.zero1 engages state sharding: params replicated, at least
    one optimizer moment sharded over 'data'; one epoch runs."""
    import jax
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from tpuic.data.synthetic import make_synthetic_imagefolder

    root = str(tmp_path / "z1")
    make_synthetic_imagefolder(root, classes=("a", "b"), per_class=8,
                               size=24)
    cfg = Config(
        data=DataConfig(data_dir=root, resize_size=24, batch_size=2),
        model=ModelConfig(name="resnet18-cifar", num_classes=0,
                          dtype="float32"),
        optim=OptimConfig(optimizer="adam", learning_rate=1e-3,
                          class_weights=(), milestones=()),
        run=RunConfig(epochs=1, ckpt_dir=str(tmp_path / "ck"), resume=False),
        mesh=MeshConfig(zero1=True),
    )
    trainer = Trainer(cfg)
    assert trainer.state_sharding is not None
    assert all(s.spec == P() for s in
               jax.tree_util.tree_leaves(trainer.state_sharding.params))
    assert any(s.spec != P() for s in
               jax.tree_util.tree_leaves(trainer.state_sharding.opt_state))
    assert trainer.fit() >= 0.0


def test_trainer_threads_no_augment(imagefolder, tmp_path, devices8):
    """DataConfig.augment=False (CLI --no-augment) reaches the train
    loader: the fold-default is augment-on, the override serves clean
    loads (the packed path then ships identity augment params)."""
    cfg = _config(imagefolder, tmp_path)
    assert Trainer(cfg).train_loader.augment is True
    cfg = dataclasses.replace(cfg,
                              data=dataclasses.replace(cfg.data,
                                                       augment=False))
    assert Trainer(cfg).train_loader.augment is False


def test_trainer_rejects_fold_smaller_than_global_batch(imagefolder):
    """drop_last + a train fold smaller than one global batch would train
    ZERO steps per epoch while still checkpointing — refuse loudly."""
    from tpuic.config import Config, DataConfig, ModelConfig, OptimConfig, RunConfig
    from tpuic.train.loop import Trainer

    cfg = Config(
        data=DataConfig(data_dir=imagefolder, resize_size=16, batch_size=64,
                        pack=False),
        model=ModelConfig(name="resnet18-cifar", num_classes=0),
        optim=OptimConfig(class_weights=(), milestones=()),
        run=RunConfig(epochs=1, ckpt_dir="/tmp/never-used"),
    )
    with pytest.raises(ValueError, match="ZERO steps"):
        Trainer(cfg)


def test_epoch_boundary_runs_ahead_and_fit_closes_the_loaders(imagefolder,
                                                              tmp_path):
    """ISSUE 32: over a packed corpus the second of two consecutive
    train_epoch calls finds its first batches made (the epoch.first_batch
    span says how many: 0 cold, then ``prefetch``), and fit() leaves no
    loader thread behind."""
    import threading
    import time

    import jax
    from tpuic.runtime.mesh import make_mesh
    from tpuic.telemetry import spans

    before = set(threading.enumerate())
    cfg = _config(imagefolder, tmp_path, epochs=1)
    cfg = dataclasses.replace(
        cfg, data=dataclasses.replace(cfg.data, batch_size=4),
        run=dataclasses.replace(cfg.run, resume=False))
    trainer = Trainer(cfg, mesh=make_mesh(MeshConfig(data=1),
                                          devices=jax.devices()[:1]))
    loader = trainer.train_loader
    assert loader.packed and loader.augment
    mark = len(spans.ledger.snapshot())
    trainer.train_epoch(0)
    deadline = time.monotonic() + 20.0
    while not (loader._parked is not None and loader._parked.q.full()):
        assert time.monotonic() < deadline, "the producer did not park"
        time.sleep(0.005)
    trainer.train_epoch(1)
    first = [r["attrs"] for r in spans.ledger.snapshot()[mark:]
             if r["name"] == "epoch.first_batch"]
    assert first == [{"epoch": 0, "ahead": 0},
                     {"epoch": 1, "ahead": loader.prefetch}]
    assert loader._parked is not None
    trainer.fit()                       # epoch 0 again: a miss, then val
    assert loader._parked is None and trainer.val_loader._parked is None
    mine = [t for t in threading.enumerate()
            if t not in before and t.name == "tpuic-loader"]
    for t in mine:
        t.join(timeout=10.0)
    assert [t for t in mine if t.is_alive()] == []
