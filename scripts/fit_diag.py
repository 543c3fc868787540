#!/usr/bin/env python
"""Decompose Trainer-loop time on the chip: loader vs H2D+prep vs step vs
log sync. Diagnoses the fit_proof gap (loop 440 img/s vs bench 2674)."""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpuic.compiled.cache import enable_compile_cache
    enable_compile_cache()

    from tpuic.config import DataConfig, ModelConfig, OptimConfig
    from tpuic.data.folder import ImageFolderDataset
    from tpuic.data.pack import pack_dataset
    from tpuic.data.pipeline import Loader
    from tpuic.data.synthetic import make_synthetic_imagefolder
    from tpuic.models import create_model
    from tpuic.train.optimizer import make_optimizer
    from tpuic.train.state import create_train_state
    from tpuic.train.step import make_train_step

    B, S = 128, 224
    root = tempfile.mkdtemp(prefix="tpuic_diag_")
    make_synthetic_imagefolder(root, classes=("a", "b", "c", "d"),
                               per_class=512, size=S, folds=("train",))
    cfg = DataConfig(data_dir=root, resize_size=S, batch_size=B)
    ds = ImageFolderDataset(root, "train", S, cfg)
    packed = pack_dataset(ds, os.path.join(root, ".p"), verbose=False)
    loader = Loader(packed, B, mesh=None, seed=0, prefetch=2)

    mcfg = ModelConfig(name="resnet50", num_classes=4, dtype="bfloat16")
    ocfg = OptimConfig(optimizer="sgd", learning_rate=0.01, class_weights=(),
                       milestones=())
    model = create_model(mcfg.name, mcfg.num_classes, dtype=mcfg.dtype)
    state = create_train_state(model, make_optimizer(ocfg), jax.random.key(0),
                               (B, S, S, 3))
    step = make_train_step(ocfg, mcfg, None, donate=True)
    out = {}

    # 1. producer-only rate (drain the queue, no device work)
    t0 = time.perf_counter()
    n = 0
    for batch in loader.epoch(0):
        jax.block_until_ready(batch["image"])
        n += B
    out["loader_only_img_s"] = round(n / (time.perf_counter() - t0), 1)

    # 2. fixed-batch step rate (bench.py equivalent, loader out of the loop)
    const = {"image": jnp.zeros((B, S, S, 3), jnp.float32),
             "label": jnp.zeros((B,), jnp.int32),
             "mask": jnp.ones((B,), jnp.float32)}
    const = {k: jax.device_put(v) for k, v in const.items()}
    state, m = step(state, const)
    float(m["loss"])
    t0 = time.perf_counter()
    for _ in range(20):
        state, m = step(state, const)
    float(m["loss"])
    out["const_batch_step_img_s"] = round(20 * B / (time.perf_counter() - t0),
                                          1)

    # 3. loader + step, NO logging sync
    t0 = time.perf_counter()
    n = 0
    for batch in loader.epoch(1):
        state, m = step(state, {k: batch[k]
                                for k in ("image", "label", "mask")})
        n += B
    float(m["loss"])
    out["loop_no_log_img_s"] = round(n / (time.perf_counter() - t0), 1)

    # 4. loader + step + per-10-step sync (fit_proof cadence)
    t0 = time.perf_counter()
    n = 0
    for i, batch in enumerate(loader.epoch(2)):
        state, m = step(state, {k: batch[k]
                                for k in ("image", "label", "mask")})
        n += B
        if (i + 1) % 10 == 0:
            float(m["loss"])
            float(m["accuracy"])
            int(jax.device_get(state.step))
    out["loop_log10_img_s"] = round(n / (time.perf_counter() - t0), 1)

    # 5. single scalar readback latency after idle device
    time.sleep(0.5)
    t0 = time.perf_counter()
    float(m["loss"])
    out["idle_readback_ms"] = round(1000 * (time.perf_counter() - t0), 2)

    out["loss_after_60_steps"] = float(m["loss"])
    print(json.dumps(out, indent=2))


if __name__ == "__main__":
    main()
