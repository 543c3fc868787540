#!/usr/bin/env python
"""Input-pipeline throughput benchmark, per host.

SURVEY.md §7 names the input pipeline the #1 hard part (the reference's
analogue is ``DataLoader(num_workers=6, pin_memory=True)``, train.py:114).
Round-3 context: this host has ONE core (nproc=1), so the per-epoch-decode
path tops out around ~220 img/s no matter the worker count — the production
path is the packed uint8 cache (tpuic/data/pack.py): decode once, serve
epochs from a memmap with augmentation/normalization on the accelerator
(tpuic/data/device_prep.py).

Measures, over a synthetic ImageFolder tree:
  - decode-per-epoch Loader grid (native C++ prep on/off x workers) — the
    legacy path, kept for comparison;
  - one-time pack build rate (native libjpeg/libpng decode);
  - the packed Loader's steady-state images/sec/host (headline value).

Prints one JSON line:
  {"metric": "loader_images_per_sec_per_host", "value": N, "unit": ...,
   "detail": {...}}
"""

from __future__ import annotations

import json
import os
import shutil
import tempfile
import time

# The decode-path grid needs no accelerator, but the packed path's
# augment/normalize runs on the platform JAX gives this process (TPU when
# present, matching production; JAX_PLATFORMS=cpu for a host-only run).
import jax


def _measure(loader, epochs=2, start=1) -> float:
    n = 0
    # epoch 0 warms file cache, thread pools, and jit caches; then timed.
    for batch in loader.epoch(0):
        last = batch["image"]
    jax.block_until_ready(last) if hasattr(last, "devices") else None
    t0 = time.perf_counter()
    for e in range(start, start + epochs):
        for batch in loader.epoch(e):
            n += int(batch["image"].shape[0])
            last = batch["image"]
        if hasattr(last, "devices"):
            jax.block_until_ready(last)
    return n / (time.perf_counter() - t0)


def main() -> None:
    from tpuic.config import DataConfig
    from tpuic.data.folder import ImageFolderDataset
    from tpuic.data.pack import pack_dataset
    from tpuic.data.pipeline import Loader
    from tpuic.data.synthetic import make_synthetic_imagefolder
    from tpuic.native import available as native_available

    size = int(os.environ.get("TPUIC_DATA_BENCH_SIZE", "224"))
    per_class = int(os.environ.get("TPUIC_DATA_BENCH_PER_CLASS", "64"))
    batch = int(os.environ.get("TPUIC_DATA_BENCH_BATCH", "32"))
    packed_epochs = int(os.environ.get("TPUIC_DATA_BENCH_EPOCHS", "8"))

    root = tempfile.mkdtemp(prefix="tpuic_databench_")
    try:
        make_synthetic_imagefolder(root, classes=("a", "b", "c", "d"),
                                   per_class=per_class, size=size)
        results = {}
        for native in ([True, False] if native_available() else [False]):
            cfg = DataConfig(data_dir=root, resize_size=size, native=native,
                             pack=False)
            ds = ImageFolderDataset(root, "train", size, cfg)
            for workers in (1, 6):
                loader = Loader(ds, batch, mesh=None, shuffle=True,
                                num_workers=workers, prefetch=4)
                key = f"decode,native={native},workers={workers}"
                results[key] = round(_measure(loader), 1)

        # Production path: pack once (decode cost paid once per dataset),
        # then serve from the memmap with device-side augmentation.
        cfg = DataConfig(data_dir=root, resize_size=size)
        ds = ImageFolderDataset(root, "train", size, cfg)
        t0 = time.perf_counter()
        packed = pack_dataset(ds, os.path.join(root, ".tpuic_pack"),
                              verbose=False)
        results["pack_build"] = round(len(ds) / (time.perf_counter() - t0), 1)
        loader = Loader(packed, batch, mesh=None, shuffle=True, prefetch=4)
        packed_rate = round(_measure(loader, epochs=packed_epochs), 1)
        results["packed"] = packed_rate

        print(json.dumps({
            "metric": "loader_images_per_sec_per_host",
            "value": packed_rate,
            "unit": "images/sec/host",
            "detail": {"image_size": size, "batch": batch,
                       "n_images": per_class * 4,
                       "platform": jax.devices()[0].platform,
                       "grid": results},
        }))
    finally:
        shutil.rmtree(root, ignore_errors=True)


if __name__ == "__main__":
    main()
