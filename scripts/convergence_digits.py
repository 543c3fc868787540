#!/usr/bin/env python
"""Train to convergence on real data and report top-1 (VERDICT r4 item 2).

The accuracy half of the BASELINE.md north star has had no end-to-end
evidence: no model was ever trained to convergence on a real dataset by
this framework. This script closes that. Dataset: sklearn's handwritten
digits — the only real image-classification set reachable in this
zero-egress environment (scripts/make_digits_dataset.py documents why) —
materialized as a reference-layout ImageFolder and fed through the FULL
production path (glob index -> packed uint8 memmap -> device-resident
cache -> Trainer.fit with checkpointing/val/logging).

Recipe (recipes/README.md #1 adapted to the dataset): resnet18-cifar,
32px, global batch 128, SGD momentum 0.9, warmup-cosine, --no-augment
(digits are orientation-sensitive: the reference's always-on rot90/flip
chain aliases 6<->9).

Control: the SAME architecture (torch_ref.build_resnet('resnet18-cifar'),
the replica family used for checkpoint-conversion parity), SAME data
tensors (loaded via the tpuic dataset so normalization is bitwise
identical), SAME schedule (linear warmup -> cosine, mirrored from
tpuic/train/schedule.py), trained with torch SGD on CPU. Writes
perf/convergence_digits.json.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

DATA_ROOT = os.path.join(_REPO, ".data", "digits")
OUT = os.path.join(_REPO, "perf", "convergence_digits.json")

EPOCHS = 40
BATCH = 128
LR = 0.05
WARMUP_EPOCHS = 3
WEIGHT_DECAY = 5e-4


def ensure_dataset() -> None:
    if not os.path.isdir(os.path.join(DATA_ROOT, "train")):
        from scripts.make_digits_dataset import build
        counts = build(DATA_ROOT)
        print(f"built digits ImageFolder: {counts}")


def run_tpuic(epochs: int, model: str = "resnet18-cifar",
              optimizer: str = "sgd", lr: float = LR,
              mixup: float = 0.0, cutmix: float = 0.0) -> dict:
    import jax

    from tpuic.compiled.cache import enable_compile_cache
    enable_compile_cache()

    from tpuic.config import (Config, DataConfig, MeshConfig, ModelConfig,
                              OptimConfig, RunConfig)
    from tpuic.train.loop import Trainer

    on_cpu = jax.devices()[0].platform == "cpu"
    ckpt = tempfile.mkdtemp(prefix="tpuic_digits_ckpt_")
    log_dir = os.path.join(_REPO, "perf", "convergence_digits_logs")
    os.makedirs(log_dir, exist_ok=True)
    cfg = Config(
        data=DataConfig(data_dir=DATA_ROOT, resize_size=32, batch_size=BATCH,
                        augment=False),
        model=ModelConfig(name=model, num_classes=10,
                          dtype="float32" if on_cpu else "bfloat16"),
        optim=OptimConfig(optimizer=optimizer, learning_rate=lr,
                          warmup_epochs=WARMUP_EPOCHS,
                          weight_decay=WEIGHT_DECAY,
                          mixup_alpha=mixup, cutmix_alpha=cutmix,
                          class_weights=(), milestones=()),
        run=RunConfig(epochs=epochs, ckpt_dir=ckpt, save_period=20,
                      resume=False, log_every_steps=10),
        mesh=MeshConfig(),
    )
    t0 = time.perf_counter()
    trainer = Trainer(cfg, log_dir=log_dir)
    best = trainer.fit()
    wall = time.perf_counter() - t0
    return {
        "framework": "tpuic",
        "model": model, "resize": 32, "batch": BATCH,
        "optimizer": f"{optimizer}(wd={WEIGHT_DECAY})",
        "schedule": f"warmup_cosine(lr={lr}, warmup={WARMUP_EPOCHS}ep)",
        "epochs": epochs, "augment": False,
        "mixup": mixup, "cutmix": cutmix,
        "n_train": len(trainer.train_ds), "n_val": len(trainer.val_ds),
        "best_val_top1": best,
        "wall_s": round(wall, 1),
        "platform": jax.devices()[0].platform,
        "device": getattr(jax.devices()[0], "device_kind", "unknown"),
        "dtype": cfg.model.dtype,
    }


def _load_fold_arrays(fold: str):
    """Load a fold through the tpuic dataset (clean decode path) so the
    control sees bitwise-identical normalized tensors."""
    import numpy as np

    from tpuic.config import DataConfig
    from tpuic.data.folder import ImageFolderDataset

    ds = ImageFolderDataset(DATA_ROOT, fold, 32, DataConfig(resize_size=32))
    xs, ys = [], []
    for i in range(len(ds)):
        img, label, _ = ds.load(i)  # no rng -> clean (matches augment=False)
        xs.append(img)
        ys.append(label)
    return np.stack(xs), np.asarray(ys, np.int64)


def run_torch_control(epochs: int) -> dict:
    import numpy as np
    import torch
    import torch.nn.functional as F

    from tpuic.checkpoint.torch_ref import build_resnet

    torch.manual_seed(0)
    xtr, ytr = _load_fold_arrays("train")
    xva, yva = _load_fold_arrays("val")
    # NHWC float32 -> NCHW torch tensors.
    xtr_t = torch.from_numpy(np.transpose(xtr, (0, 3, 1, 2))).contiguous()
    ytr_t = torch.from_numpy(ytr)
    xva_t = torch.from_numpy(np.transpose(xva, (0, 3, 1, 2))).contiguous()
    yva_t = torch.from_numpy(yva)

    model = build_resnet("resnet18-cifar", num_classes=10)
    opt = torch.optim.SGD(model.parameters(), lr=LR, momentum=0.9,
                          weight_decay=WEIGHT_DECAY)
    steps_per_epoch = len(xtr_t) // BATCH  # drop_last, as the tpuic loader
    # THE schedule, not a re-implementation: evaluate the same
    # warmup_cosine_schedule object the tpuic optimizer runs (pre-computed
    # per step so torch never touches jax mid-training).
    from tpuic.train.schedule import warmup_cosine_schedule
    sched = warmup_cosine_schedule(LR, WARMUP_EPOCHS, epochs,
                                   steps_per_epoch)
    lr_table = [float(sched(t)) for t in range(epochs * steps_per_epoch)]

    def lr_at(t: int) -> float:
        return lr_table[min(t, len(lr_table) - 1)]

    g = torch.Generator().manual_seed(0)
    t0 = time.perf_counter()
    best = 0.0
    step = 0
    for _epoch in range(epochs):
        model.train()
        order = torch.randperm(len(xtr_t), generator=g)
        for b in range(steps_per_epoch):
            idx = order[b * BATCH:(b + 1) * BATCH]
            for pg in opt.param_groups:
                pg["lr"] = lr_at(step)
            opt.zero_grad()
            loss = F.cross_entropy(model(xtr_t[idx]), ytr_t[idx])
            loss.backward()
            opt.step()
            step += 1
        model.eval()
        with torch.no_grad():
            correct = 0
            for lo in range(0, len(xva_t), 256):
                pred = model(xva_t[lo:lo + 256]).argmax(1)
                correct += int((pred == yva_t[lo:lo + 256]).sum())
        best = max(best, 100.0 * correct / len(xva_t))
    wall = time.perf_counter() - t0
    return {
        "framework": "torch (torch_ref replica, CPU)",
        "model": "resnet18-cifar", "resize": 32, "batch": BATCH,
        "optimizer": f"sgd(momentum=0.9, wd={WEIGHT_DECAY})",
        "schedule": f"warmup_cosine(lr={LR}, warmup={WARMUP_EPOCHS}ep)",
        "epochs": epochs, "augment": False,
        "n_train": int(len(xtr_t)), "n_val": int(len(xva_t)),
        "best_val_top1": round(best, 2),
        "wall_s": round(wall, 1),
        "platform": "cpu", "dtype": "float32",
    }


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--epochs", type=int, default=EPOCHS)
    p.add_argument("--model", default="resnet18-cifar",
                   help="secondary models (e.g. vit-tiny) are recorded "
                        "under 'tpuic_<model>'; the torch control pairs "
                        "with the primary resnet18-cifar entry only")
    p.add_argument("--optimizer", default="sgd")
    p.add_argument("--lr", type=float, default=LR)
    p.add_argument("--mixup", type=float, default=0.0,
                   help="orientation-SAFE augmentation for ViT-family "
                        "runs (rot/flip alias digit classes; mixup/cutmix "
                        "do not)")
    p.add_argument("--cutmix", type=float, default=0.0)
    p.add_argument("--skip-tpuic", action="store_true")
    p.add_argument("--skip-control", action="store_true")
    args = p.parse_args()

    ensure_dataset()

    result = {}
    if os.path.exists(OUT):
        with open(OUT) as f:
            try:
                result = json.load(f)
            except ValueError:
                result = {}
    result.setdefault("dataset", {
        "name": "sklearn handwritten digits (UCI)",
        "why": "only real image dataset reachable under zero egress; "
               "CIFAR-10/ImageNet have no local copy "
               "(scripts/make_digits_dataset.py)",
        "n_images": 1797, "classes": 10, "native_size": "8x8",
    })
    if not args.skip_tpuic:
        key = ("tpuic" if args.model == "resnet18-cifar"
               else f"tpuic_{args.model}")
        result[key] = run_tpuic(args.epochs, model=args.model,
                                optimizer=args.optimizer, lr=args.lr,
                                mixup=args.mixup, cutmix=args.cutmix)
        print(json.dumps(result[key], indent=2))
    if not args.skip_control:
        result["torch_control"] = run_torch_control(args.epochs)
        print(json.dumps(result["torch_control"], indent=2))
    if "tpuic" in result and "torch_control" in result:
        result["top1_delta_tpuic_minus_torch"] = round(
            result["tpuic"]["best_val_top1"]
            - result["torch_control"]["best_val_top1"], 2)
    with open(OUT, "w") as f:
        json.dump(result, f, indent=2)
    print(f"wrote {OUT}")


if __name__ == "__main__":
    main()
