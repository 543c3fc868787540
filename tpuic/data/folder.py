"""ImageFolder dataset: index + per-sample load.

Re-design of reference ``ImageDataset`` (dp/loader.py:15-61):

- Layout: ``data_dir/{fold}/{class_name}/{image}.png`` globbed the same way
  (dp/loader.py:20-21).
- Class mapping: the reference initializes ``self.mapping = {}`` and never
  populates it (dp/loader.py:29) — a latent bug that makes ``num_classes`` 0
  and ``__getitem__`` raise. The intended behavior, built here: class names are
  the sorted subdirectory names of the TRAIN fold, mapped to contiguous ids
  (sorted => identical on every host; the train fold is canonical so val
  shares the mapping).
- ``image_id``: filename stem (dp/loader.py:43 strips '.png'; here any
  extension is stripped).
- The reference shuffles its file list unseeded, per-rank, at init
  (dp/loader.py:23) — ranks disagree about the index order, so
  DistributedSampler shards overlap/miss samples. Here the index order is
  deterministic (sorted); shuffling belongs to the sampler (pipeline.py) with
  an epoch-folded global seed.
- **Sample quarantine** (docs/robustness.md): a decode failure (truncated
  JPEG, bit-rot, file mid-copy) used to propagate out of the Loader's
  producer thread and abort the whole epoch. Now ``load`` retries with a
  short backoff (the transient-read case), then substitutes a
  deterministic same-class replacement sample and counts the event
  (``quarantine_count`` / ``quarantined``) — one corrupt file out of a
  million degrades the epoch by one sample instead of killing the run.
  ``DataConfig.quarantine=False`` restores fail-fast propagation.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Dict, List, Optional, Tuple

import numpy as np
from PIL import Image

from tpuic.config import DataConfig
from tpuic.data import transforms as T
from tpuic.runtime import faults as _faults

# Everything PIL raises for unreadable/corrupt image bytes:
# UnidentifiedImageError and "image file is truncated" are OSError
# subclasses; zlib/decoder failures surface as ValueError; ancient PIL
# raised SyntaxError for broken PNG chunks.
_DECODE_ERRORS = (OSError, ValueError, SyntaxError)


def quarantined_decode(dataset, index: int, decode):
    """THE quarantine policy, shared by the per-sample path (``load``) and
    the pack build (pack.py): try ``decode(index)``; on a decode error
    retry ``cfg.quarantine_retries`` times with ``cfg.quarantine_backoff_s``
    between attempts (a file mid-copy becomes readable), then — with
    ``cfg.quarantine`` on — record the event and walk up to 8 same-class
    replacement candidates (corruption is correlated: interrupted copies
    land on neighbors, so the first candidate may be corrupt too).

    Returns ``(value, actual_index)`` — the caller takes the REPLACEMENT's
    label/id when ``actual_index != index``. Re-raises the original error
    when quarantine is off or every candidate fails. Only
    ``_DECODE_ERRORS`` engage the policy: programming errors (bad shapes,
    type bugs) propagate immediately instead of masquerading as mass
    corruption."""
    cfg = dataset.cfg
    try:
        return decode(index), index
    except _DECODE_ERRORS:
        for _ in range(max(0, int(cfg.quarantine_retries))):
            time.sleep(max(0.0, float(cfg.quarantine_backoff_s)))
            try:
                return decode(index), index
            except _DECODE_ERRORS:
                continue
        if not cfg.quarantine:
            raise
        dataset._record_quarantine(dataset.samples[index][0])
        j = index
        for _ in range(8):
            j = dataset.quarantine_replacement(j)
            try:
                return decode(j), j
            except _DECODE_ERRORS:
                continue
        raise  # every candidate corrupt: surface the original error

_IMAGE_EXTS = {".png", ".jpg", ".jpeg", ".bmp", ".ppm", ".webp"}


def _is_image(path: str) -> bool:
    return os.path.splitext(path)[1].lower() in _IMAGE_EXTS


class ImageFolderDataset:
    def __init__(self, data_dir: str, fold: str, resize_size: int,
                 cfg: Optional[DataConfig] = None,
                 class_to_idx: Optional[Dict[str, int]] = None,
                 allow_unlabeled: bool = False) -> None:
        self.cfg = cfg or DataConfig()
        self.data_dir = data_dir
        self.fold = fold
        self.train = fold == "train"
        self.resize_size = resize_size
        root = os.path.join(data_dir, fold)
        if not os.path.isdir(root):
            raise FileNotFoundError(f"no such fold: {root}")
        # Canonical class mapping from the train fold (see module docstring).
        if class_to_idx is None:
            map_root = os.path.join(data_dir, "train")
            if not os.path.isdir(map_root):
                map_root = root
            classes = sorted(d for d in os.listdir(map_root)
                             if os.path.isdir(os.path.join(map_root, d)))
            class_to_idx = {c: i for i, c in enumerate(classes)}
        self.class_to_idx: Dict[str, int] = dict(class_to_idx)
        self.classes: List[str] = sorted(self.class_to_idx,
                                         key=self.class_to_idx.get)
        samples: List[Tuple[str, int]] = []
        for cls in sorted(os.listdir(root)):
            cdir = os.path.join(root, cls)
            if not os.path.isdir(cdir) or cls not in self.class_to_idx:
                continue
            for fname in sorted(os.listdir(cdir)):
                fpath = os.path.join(cdir, fname)
                if _is_image(fpath):
                    samples.append((fpath, self.class_to_idx[cls]))
        # Flat (unlabeled) fold: images directly under the fold dir, no
        # class subdirectories. Label is -1. Opt-in (tpuic.predict passes
        # allow_unlabeled=True): training on label -1 would silently
        # produce a zero one-hot target and a degenerate loss, so for the
        # Trainer a flat fold stays the hard error it always was.
        self.labeled = bool(samples)
        if not samples and allow_unlabeled:
            samples = [(os.path.join(root, f), -1)
                       for f in sorted(os.listdir(root))
                       if _is_image(os.path.join(root, f))]
        if not samples:
            raise ValueError(f"no images under {root}")
        self.samples = samples
        # Quarantine bookkeeping: total replacement events and per-path
        # counts (a path appearing here means its bytes failed to decode
        # after retries and a substitute was served). Lock because loads
        # run on the Loader's worker threads.
        self.quarantine_count = 0
        self.quarantined: Dict[str, int] = {}
        self._quarantine_lock = threading.Lock()

    def __len__(self) -> int:
        return len(self.samples)

    @property
    def num_classes(self) -> int:
        """Reference dp/loader.py:34-36 (fixed: mapping is populated)."""
        return len(self.class_to_idx)

    def image_id(self, index: int) -> str:
        path, _ = self.samples[index]
        return os.path.splitext(os.path.basename(path))[0]

    def class_counts(self) -> np.ndarray:
        """[num_classes] int64 sample count per class id."""
        labels = np.asarray([lb for _, lb in self.samples])
        return np.bincount(labels[labels >= 0],
                           minlength=self.num_classes).astype(np.int64)

    def _decode(self, path: str) -> np.ndarray:
        with Image.open(path) as im:
            return np.asarray(im.convert("RGB") if im.mode not in ("RGB",)
                              else im)

    def _decode_sized(self, index: int) -> np.ndarray:
        """Decode sample ``index`` — through the native core when built
        (``native.decode_resize``: libjpeg/libpng + the SAME
        nearest-resize index math as ``transforms.resize_nearest``, so
        PNG output is bitwise the PIL+NumPy path's), else PIL at full
        resolution (the downstream ``resize_nearest`` no-ops when the
        native path already returned target-size pixels).

        This is the prefetch-worker decode (Loader workers call ``load``
        off-thread): with the native core the per-sample cost drops to
        one C decode+gather, so the telemetry ``input`` bucket on the
        decode (--no-pack) path shrinks toward zero.  JPEG decodes DCT-scaled — the
        same pixels the packed cache (pack.py) already serves.  A
        corrupt/truncated file makes the native decoder return None and
        the PIL fallback raise, so the quarantine ladder engages
        exactly as on the pure-NumPy path (tests/test_native.py)."""
        path = self.samples[index][0]
        if self.cfg.native:
            from tpuic import native
            if native.decode_available():
                try:
                    with open(path, "rb") as f:
                        data = f.read()
                except OSError:
                    data = b""
                if data:
                    out = native.decode_resize(data, self.resize_size)
                    if out is not None:
                        return out
        return self._decode(path)

    def quarantine_replacement(self, index: int) -> int:
        """Deterministic substitute for a sample whose file won't decode:
        the next index (cyclic) carrying the SAME label — the label stays
        honest and the batch stays in-distribution — falling back to the
        plain next index for a single-sample class (its real label rides
        along, so training never sees a mislabeled row)."""
        label = self.samples[index][1]
        n = len(self.samples)
        for off in range(1, n):
            j = (index + off) % n
            if self.samples[j][1] == label:
                return j
        return (index + 1) % n

    def _record_quarantine(self, path: str) -> None:
        with self._quarantine_lock:
            self.quarantine_count += 1
            self.quarantined[path] = self.quarantined.get(path, 0) + 1
            count = self.quarantine_count
        # Typed event (docs/observability.md): fires from the producer
        # thread at the moment of replacement, so the TensorBoard bridge
        # and JSONL sink see corruption when it happens, not only at the
        # trainer's per-epoch summary line.
        from tpuic.telemetry.events import publish as _tm_publish
        _tm_publish("quarantine", path=path, count=count)

    def load(self, index: int, rng: Optional[np.random.Generator] = None
             ) -> Tuple[np.ndarray, int, str]:
        """Decode → RGB → resize → [augment] → normalize. Returns
        (HWC float32 image, label, image_id) — reference dp/loader.py:39-61,
        minus the CHW transpose (TPU convs are NHWC).

        Augment decisions are drawn ONCE (transforms.draw_augment, the single
        source of the RNG stream) and then executed either by the fused
        native pass (tpuic/native, when built and cfg.native) or by the NumPy
        transforms — identical output per (seed, epoch, index) either way.

        An undecodable file goes through ``quarantined_decode``: retry with
        backoff, then serve a deterministic same-class replacement — its
        image, ITS label, its id — and count the event. The augment RNG
        stream is the caller's (seed, epoch, index) generator either way,
        so the substitution is bitwise deterministic too."""
        def _decode_index(i: int) -> np.ndarray:
            # Deterministic injection point ('decode_error' keyed by
            # dataset index) — a corrupt file without a corrupt file.
            # Checked per ATTEMPT: armed without a times cap it models
            # persistent corruption (retries fail too -> quarantine);
            # armed with times=1 it models a transient read (the retry
            # recovers).
            if _faults.fire("decode_error", step=i):
                raise OSError(f"injected decode error for index {i}")
            return self._decode_sized(i)

        img, index = quarantined_decode(self, index, _decode_index)
        path, label = self.samples[index]
        c = self.cfg
        img = T.to_rgb(img)
        if self.train and rng is not None:
            k, vflip, hflip, color, factor = T.draw_augment(
                rng, p_vflip=c.p_vflip, p_hflip=c.p_hflip,
                p_saturation=c.p_saturation, p_brightness=c.p_brightness,
                p_contrast=c.p_contrast, jitter_lo=c.jitter_lo,
                jitter_hi=c.jitter_hi)
        else:
            k = vflip = hflip = color = 0
            factor = 1.0
        if c.native:
            from tpuic import native
            out = native.prep_image(
                np.ascontiguousarray(img), self.resize_size, rot_k=k,
                vflip=vflip, hflip=hflip, color_op=color, factor=factor,
                mean=c.mean, std=c.std)
            if out is not None:
                return out, label, self.image_id(index)
        img = T.resize_nearest(img, self.resize_size)
        img = T.apply_augment(img, k, vflip, hflip, color, factor)
        img = T.normalize(img, c.mean, c.std)
        return img, label, self.image_id(index)
