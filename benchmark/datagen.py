"""The benchmark's own ImageFolder generator.

A train cell needs an ImageFolder the way a user has one: PNG files under
``{root}/{fold}/{class}/``. Writing thousands of PNGs costs minutes, so a
few hundred distinct images are drawn from the corpus seed and the tree is
filled out to the corpus size with hard links (copies where the file system
has none). The packed cache the program builds on first use sees one row
per file either way, so the corpus is full-sized where it matters: in the
pack, and resident on the device.

The tree is keyed by what determines its bytes (size, counts, classes,
corpus seed), and a finished tree carries a marker file: only a cell's
first run in a checkout generates (and lets the program pack); later runs
reuse both, as a user's second ``train.py`` does.
"""

from __future__ import annotations

import json
import os
import shutil

import numpy as np

_MARKER = "GENERATED.json"


def _link_or_copy(src: str, dst: str) -> None:
    try:
        os.link(src, dst)
    except OSError:
        shutil.copyfile(src, dst)


def _write_fold(root: str, fold: str, classes: int, per_class: int,
                unique_per_class: int, size: int,
                rng: np.random.Generator) -> None:
    from PIL import Image
    for ci in range(classes):
        cdir = os.path.join(root, fold, f"c{ci:03d}")
        os.makedirs(cdir)
        # Class-correlated brightness under noise, like the program's own
        # synthetic folder: a model can tell the classes apart.
        base = 40 + 150 * ci // max(1, classes - 1)
        firsts = []
        for i in range(min(unique_per_class, per_class)):
            noise = rng.integers(0, 60, (size, size, 3), np.uint8)
            img = np.clip(base + noise.astype(np.int32), 0, 255)
            path = os.path.join(cdir, f"{fold}_{ci:03d}_{i:05d}.png")
            Image.fromarray(img.astype(np.uint8)).save(path,
                                                       compress_level=1)
            firsts.append(path)
        for i in range(len(firsts), per_class):
            _link_or_copy(firsts[i % len(firsts)],
                          os.path.join(cdir, f"{fold}_{ci:03d}_{i:05d}.png"))


def ensure_imagefolder(cache_root: str, *, size: int, train_images: int,
                       val_images: int, classes: int, unique_per_class: int,
                       corpus_seed: int) -> str:
    """Return the ImageFolder root for these parameters, generating it under
    ``cache_root`` if no finished tree is there."""
    if train_images % classes or val_images % classes:
        raise ValueError(f"{train_images} train / {val_images} val images "
                         f"do not divide over {classes} classes")
    spec = {"size": size, "train_images": train_images,
            "val_images": val_images, "classes": classes,
            "unique_per_class": unique_per_class,
            "corpus_seed": corpus_seed}
    root = os.path.join(cache_root, "imagefolder-s{size}-n{train_images}-"
                        "v{val_images}-c{classes}-u{unique_per_class}-"
                        "seed{corpus_seed}".format(**spec))
    marker = os.path.join(root, _MARKER)
    try:
        with open(marker) as f:
            if json.load(f) == spec:
                return root
    except (OSError, ValueError):
        pass
    shutil.rmtree(root, ignore_errors=True)   # a tree a killed run left
    rng = np.random.default_rng(corpus_seed)
    _write_fold(root, "train", classes, train_images // classes,
                unique_per_class, size, rng)
    _write_fold(root, "val", classes, val_images // classes,
                unique_per_class, size, rng)
    with open(marker, "w") as f:
        json.dump(spec, f)
    return root
