#!/usr/bin/env python
"""Prove Trainer.fit() end-to-end on the chip at bench-grade throughput.

VERDICT r2 weak #4 / next-round item 4: the r2 BENCH number was produced by
bench.py's hand-rolled loop; `Trainer.fit()` as shipped logged (and
device-synced) every step and had never run on the TPU. This script builds
a synthetic ImageFolder, runs `train.py`'s Trainer (packed loader + device
augmentation + the default log cadence) for a few epochs on the chip, and
reports the in-loop steady-state images/sec next to bench.py's number.

Writes perf/fit_proof.json. Done criterion: loop throughput within ~10% of
the round-3 sweep's row (perf/sweep.json) at the same (resnet50, b128,
bf16, sgd) config.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


def steady_rate(rates, logs_per_epoch):
    """True median of the logged rates with each epoch's FIRST interval
    dropped (epoch 0's carries compile; every epoch's carries queue ramp).

    Guards the degenerate cases that would silently zero the round's key
    artifact: logs_per_epoch < 1 (fewer steps than the log cadence) keeps
    everything; an all-dropped list falls back to the raw median."""
    if logs_per_epoch < 1:
        keep = list(rates)
    else:
        keep = [r for i, r in enumerate(rates) if i % logs_per_epoch != 0]
    if not keep:
        keep = list(rates)
    if not keep:
        return 0.0
    import statistics
    return float(statistics.median(keep))


def main():
    import jax

    from tpuic.compiled.cache import enable_compile_cache
    enable_compile_cache()

    from tpuic.config import (Config, DataConfig, MeshConfig, ModelConfig,
                              OptimConfig, RunConfig)
    from tpuic.data.synthetic import make_synthetic_imagefolder
    from tpuic.train.loop import Trainer

    n_per_class = int(os.environ.get("TPUIC_FIT_PER_CLASS", "1536"))
    epochs = int(os.environ.get("TPUIC_FIT_EPOCHS", "3"))
    batch = int(os.environ.get("TPUIC_FIT_BATCH", "128"))

    root = tempfile.mkdtemp(prefix="tpuic_fitproof_")
    t0 = time.perf_counter()
    # Val is 1/8 of train: the proof measures the TRAIN loop's throughput;
    # a full-size val fold only adds pack time and resident-cache upload.
    make_synthetic_imagefolder(root, classes=("a", "b", "c", "d"),
                               per_class=n_per_class, size=224,
                               folds=("train",))
    make_synthetic_imagefolder(root, classes=("a", "b", "c", "d"),
                               per_class=max(64, n_per_class // 8), size=224,
                               folds=("val",))
    make_time = time.perf_counter() - t0
    ckpt = os.path.join(root, "ckpt")
    log_dir = os.path.join(_REPO, "perf", "fit_proof_logs")
    os.makedirs(log_dir, exist_ok=True)
    cfg = Config(
        data=DataConfig(data_dir=root, resize_size=224, batch_size=batch),
        model=ModelConfig(name="resnet50", num_classes=4, dtype="bfloat16"),
        # lr 0.01: flat 0.1 on a from-scratch resnet50 diverges to NaN in a
        # few steps on this synthetic set (round-3 run) — the proof should
        # show a loss that MOVES, not just steps that execute.
        optim=OptimConfig(optimizer="sgd", learning_rate=0.01,
                          class_weights=(), milestones=()),
        run=RunConfig(epochs=epochs, ckpt_dir=ckpt, save_period=100,
                      resume=False, log_every_steps=10),
        mesh=MeshConfig(),
    )
    t1 = time.perf_counter()
    trainer = Trainer(cfg, log_dir=log_dir)
    setup_time = time.perf_counter() - t1
    t2 = time.perf_counter()
    best = trainer.fit()
    fit_time = time.perf_counter() - t2

    # Steady-state: the logged images_per_sec samples, dropping each epoch's
    # first interval (contains compile on epoch 0 and queue ramp).
    rates = []
    with open(os.path.join(log_dir, "metrics.jsonl")) as f:
        for line in f:
            rec = json.loads(line)
            if "images_per_sec" in rec:
                rates.append(rec["images_per_sec"])
    steps_per_epoch = trainer.train_loader.steps_per_epoch()
    logs_per_epoch = steps_per_epoch // cfg.run.log_every_steps
    rate = steady_rate(rates, logs_per_epoch)

    # "bench img/s" is the round-3 sweep's b128 row (perf/sweep.json, a
    # builder number dated 2026-07-30); no newer chip line exists yet.
    bench_rate, bench_src = 2674.0, "perf/sweep.json b128"
    result = {
        "model": "resnet50", "batch": batch, "epochs": epochs,
        "n_train_images": n_per_class * 4,
        "dataset_gen_s": round(make_time, 1),
        "trainer_setup_s": round(setup_time, 1),
        "fit_s": round(fit_time, 1),
        "best_val_acc": best,
        "loop_images_per_sec_median_steady": rate,
        "bench_images_per_sec": bench_rate,
        "bench_source": bench_src,
        "loop_vs_bench": round(rate / bench_rate, 4),
        "all_logged_rates": rates,
        "platform": jax.devices()[0].platform,
    }
    with open(os.path.join(_REPO, "perf", "fit_proof.json"), "w") as f:
        json.dump(result, f, indent=2)
    print(json.dumps({k: v for k, v in result.items()
                      if k != "all_logged_rates"}, indent=2))
    assert result["loop_vs_bench"] > 0.85, \
        f"loop at {rate} img/s is >15% below bench {bench_rate}"
    print("FIT PROOF OK")


if __name__ == "__main__":
    main()
