"""Packed-cache pipeline: native decode, pack/reuse, device-side augment.

Round-3 input-pipeline redesign (tpuic/data/pack.py docstring): decode once
into a memory-mapped uint8 cache, augment/normalize on the accelerator. The
parity bar: a (seed, epoch, index)-identified sample must be (near-)identical
whichever path produced it — NumPy decode-per-epoch, native C++, or packed +
device prep. Geometry is a pure permutation (exact); the float math may
differ from NumPy at the last ulp (XLA fuses x/255-mean into fma), pinned
here at 1e-5.
"""

import io
import os

import numpy as np
import pytest
from PIL import Image

from tpuic.config import DataConfig
from tpuic.data import transforms as T
from tpuic.data.device_prep import (apply_batch_augment, identity_params,
                                    make_device_prep)
from tpuic.data.folder import ImageFolderDataset
from tpuic.data.pack import pack_dataset
from tpuic.data.pipeline import Loader


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("packdata"))
    rng = np.random.default_rng(0)
    for fold, per in (("train", 6), ("val", 4)):
        for cls in ("ant", "bee"):
            d = os.path.join(root, fold, cls)
            os.makedirs(d)
            for i in range(per):
                img = rng.integers(0, 256, (40, 52, 3), np.uint8)
                Image.fromarray(img).save(os.path.join(d, f"{cls}{i}.png"))
    return root


# -- native decode ----------------------------------------------------------

def test_native_decode_png_bitwise_matches_numpy_path():
    from tpuic import native
    if not native.decode_available():
        pytest.skip("native decode core unavailable")
    rng = np.random.default_rng(1)
    img = rng.integers(0, 256, (120, 90, 3), np.uint8)
    buf = io.BytesIO()
    Image.fromarray(img).save(buf, "PNG")
    out = native.decode_resize(buf.getvalue(), 64)
    assert np.array_equal(out, T.resize_nearest(img, 64))


def test_native_decode_grayscale_and_palette_png():
    from tpuic import native
    if not native.decode_available():
        pytest.skip("native decode core unavailable")
    rng = np.random.default_rng(2)
    gray = rng.integers(0, 256, (50, 60), np.uint8)
    buf = io.BytesIO()
    Image.fromarray(gray, mode="L").save(buf, "PNG")
    out = native.decode_resize(buf.getvalue(), 32)
    ref = T.resize_nearest(T.to_rgb(gray), 32)
    assert np.array_equal(out, ref)
    pal = Image.fromarray(
        rng.integers(0, 256, (50, 60, 3), np.uint8)).convert(
        "P", palette=Image.ADAPTIVE)
    buf = io.BytesIO()
    pal.save(buf, "PNG")
    out = native.decode_resize(buf.getvalue(), 32)
    ref = T.resize_nearest(T.to_rgb(np.asarray(pal.convert("RGB"))), 32)
    assert np.array_equal(out, ref)


def test_native_decode_jpeg_full_scale_matches_pil():
    """At full IDCT scale libjpeg output is bitwise PIL's (same library);
    decode_resize additionally DCT-scales, so compare via tpuic_decode."""
    import ctypes
    from tpuic import native
    if not native.decode_available():
        pytest.skip("native decode core unavailable")
    rng = np.random.default_rng(3)
    img = rng.integers(0, 256, (96, 128, 3), np.uint8)
    buf = io.BytesIO()
    Image.fromarray(img).save(buf, "JPEG", quality=92)
    data = np.frombuffer(buf.getvalue(), np.uint8)
    lib = native._load_decode()
    out = np.empty(96 * 128 * 3, np.uint8)
    h, w = ctypes.c_int(), ctypes.c_int()
    rc = lib.tpuic_decode(
        data.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        ctypes.c_int64(data.size),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        ctypes.c_int64(out.size), ctypes.byref(h), ctypes.byref(w))
    assert rc == 0 and (h.value, w.value) == (96, 128)
    pil = np.asarray(Image.open(io.BytesIO(buf.getvalue())).convert("RGB"))
    assert np.array_equal(out.reshape(96, 128, 3), pil)


def test_native_decode_rejects_garbage():
    from tpuic import native
    if not native.decode_available():
        pytest.skip("native decode core unavailable")
    assert native.decode_resize(b"\x00" * 64, 32) is None
    assert native.decode_resize(b"\xff\xd8corrupt jpeg!", 32) is None


# -- pack / reuse / invalidation -------------------------------------------

def test_pack_roundtrip_and_reuse(tree, tmp_path):
    cfg = DataConfig(data_dir=tree, resize_size=32)
    ds = ImageFolderDataset(tree, "train", 32, cfg)
    cache = str(tmp_path / "cache")
    packed = pack_dataset(ds, cache, verbose=False)
    assert len(packed) == len(ds)
    assert packed.num_classes == ds.num_classes
    assert packed.classes == ds.classes
    for i in range(len(ds)):
        img, label, image_id = ds.load(i)  # no-aug float path
        pimg, plabel, pid = packed.load(i)
        assert (label, image_id) == (plabel, pid)
        np.testing.assert_array_equal(img, pimg)
    # Reuse: same fingerprint loads without rebuilding (mtime preserved).
    mtime = os.path.getmtime(packed.bin_path)
    again = pack_dataset(ds, cache, verbose=False)
    assert os.path.getmtime(again.bin_path) == mtime
    # Invalidation: touching a source rebuilds.
    path0 = ds.samples[0][0]
    os.utime(path0, (0, 0))
    rebuilt = pack_dataset(ImageFolderDataset(tree, "train", 32, cfg), cache,
                           verbose=False)
    assert os.path.getmtime(rebuilt.bin_path) != mtime


def test_pack_row_crc_detects_bin_bitrot(tree, tmp_path):
    """v2 packs carry per-row CRC32s: flipping bytes inside ONE row of
    the .bin (silent at-rest rot — size unchanged, fingerprint covers
    only the SOURCE files) fails verify_row for exactly that row."""
    from tpuic.runtime import faults
    cfg = DataConfig(data_dir=tree, resize_size=32)
    ds = ImageFolderDataset(tree, "val", 32, cfg)
    packed = pack_dataset(ds, str(tmp_path / "cache"), verbose=False)
    n = len(packed)
    assert all(packed.verify_row(i) for i in range(n))
    assert all(packed.row_crc32(i) is not None for i in range(n))
    row = 32 * 32 * 3
    victim = 2
    faults.corrupt_file(packed.bin_path, offset=victim * row + 11, nbytes=8)
    # Fresh mmap so the reread sees the rotted bytes, reuse path intact.
    reread = pack_dataset(ImageFolderDataset(tree, "val", 32, cfg),
                          str(tmp_path / "cache"), verbose=False)
    assert os.path.getmtime(reread.bin_path) \
        == os.path.getmtime(packed.bin_path)  # cache hit, no rebuild
    bad = [i for i in range(n) if not reread.verify_row(i)]
    assert bad == [victim]


def test_pack_version_bump_invalidates_v1_meta(tree, tmp_path):
    """A pre-v2 meta (no row CRCs) must not be reused as-is: the version
    check rebuilds it into a v2 pack, while a hand-loaded v1 meta stays
    readable and verifies as trusted-unverifiable (True)."""
    import json
    from tpuic.data.pack import PackedDataset, _PACK_VERSION
    cfg = DataConfig(data_dir=tree, resize_size=32)
    ds = ImageFolderDataset(tree, "val", 32, cfg)
    cache = str(tmp_path / "cache")
    packed = pack_dataset(ds, cache, verbose=False)
    meta_path = packed.bin_path[:-len(".bin")] + ".json"
    meta = json.load(open(meta_path))
    assert meta["version"] == _PACK_VERSION >= 2
    # Downgrade the meta to the v1 shape a pre-upgrade run left behind.
    v1 = dict(meta, version=1)
    v1.pop("row_crc32")
    json.dump(v1, open(meta_path, "w"))
    old = PackedDataset(packed.bin_path, v1, train=False, cfg=cfg)
    assert old.row_crc32(0) is None
    assert old.verify_row(0)  # absence of evidence is not a quarantine
    rebuilt = pack_dataset(ImageFolderDataset(tree, "val", 32, cfg), cache,
                           verbose=False)
    assert json.load(open(meta_path))["version"] == _PACK_VERSION
    assert rebuilt.row_crc32(0) is not None


def test_pack_quarantines_corrupt_source_with_honest_accounting(
        tree, tmp_path):
    """Pack-time quarantine on the packed path: one truncated source
    file in the corpus packs a same-class replacement row — with the
    replacement's label, id, AND row CRC — and the event is counted."""
    import shutil
    from tpuic.runtime import faults
    root = str(tmp_path / "rotted")
    shutil.copytree(tree, root)
    cfg = DataConfig(data_dir=root, resize_size=32, quarantine_retries=0,
                     quarantine_backoff_s=0.0)
    ds = ImageFolderDataset(root, "val", 32, cfg)
    victim_path, victim_label = ds.samples[1]
    faults.truncate_file(victim_path, keep=8)
    packed = pack_dataset(ds, str(tmp_path / "cache"), verbose=False)
    assert packed.quarantine_count == 1
    # The replacement row is honest: its id is a real same-class sample's
    # (not the victim's), its label matches, and its CRC verifies.
    assert packed.image_id(1) != ds.image_id(1)
    assert packed.label(1) == int(victim_label)
    assert all(packed.verify_row(i) for i in range(len(packed)))


# -- device-side augmentation ----------------------------------------------

def test_device_prep_matches_numpy_all_paths():
    rng = np.random.default_rng(4)
    B, S = 12, 48
    imgs = rng.integers(0, 256, (B, S, S, 3), np.uint8)
    params = {k: [] for k in ("rot", "vflip", "hflip", "color", "factor")}
    refs = []
    # Force coverage of every rot/flip/color combination.
    for i in range(B):
        k, c = i % 4, i % 4
        vf, hf = bool(i % 2), bool((i // 2) % 2)
        f = 0.9 + 0.02 * i
        for key, v in zip(("rot", "vflip", "hflip", "color", "factor"),
                          (k, int(vf), int(hf), c, f)):
            params[key].append(v)
        refs.append(T.normalize(T.apply_augment(imgs[i], k, vf, hf, c, f)))
    params = {k: np.asarray(v, np.float32 if k == "factor" else np.int32)
              for k, v in params.items()}
    out = np.asarray(apply_batch_augment(imgs, params))
    assert np.abs(out - np.stack(refs)).max() < 1e-5


def test_device_prep_identity_params_is_normalize():
    from tpuic.data.device_prep import pack_params
    rng = np.random.default_rng(5)
    imgs = rng.integers(0, 256, (4, 16, 16, 3), np.uint8)
    out = np.asarray(make_device_prep()(imgs, pack_params(identity_params(4))))
    ref = np.stack([T.normalize(im) for im in imgs])
    assert np.abs(out - ref).max() < 1e-5


_DECISIONS = [(k, vf, hf) for k in range(4) for vf in (0, 1) for hf in (0, 1)]


def _reference_geometry(img, k, vf, hf):
    """The reference's own order (dp/loader.py:63-71): rot90^k, then the
    vertical flip, then the horizontal one."""
    g = np.rot90(img, k, axes=(0, 1))
    g = np.flipud(g) if vf else g
    return np.ascontiguousarray(np.fliplr(g) if hf else g)


def _decision_params(decisions, colors, factors):
    k, vf, hf = (np.asarray(c, np.int32) for c in zip(*decisions))
    return {"rot": k, "vflip": vf, "hflip": hf,
            "color": np.asarray(colors, np.int32),
            "factor": np.asarray(factors, np.float32)}


@pytest.mark.parametrize("size", [7, 32])
@pytest.mark.parametrize("k,vf,hf", _DECISIONS,
                         ids=[f"rot{k}v{vf}h{hf}" for k, vf, hf in _DECISIONS])
def test_device_geometry_is_the_references_permutation(k, vf, hf, size):
    """Every (rot, vflip, hflip) decision under every colour branch, at an
    odd and an even size: the one transpose and two reversals the device
    composes on the uint8 batch move each pixel where np.rot90 / flipud /
    fliplr put it. Bitwise against the device's own arithmetic on the
    NumPy-permuted image (the geometry is a permutation, nothing else), and
    against the NumPy chain at the tolerance
    test_device_prep_matches_numpy_all_paths pins."""
    rng = np.random.default_rng(1000 * size + 100 * k + 10 * vf + hf)
    colors, factors = [0, 1, 2, 3], [1.0, 0.7, 1.2, 0.85]
    imgs = rng.integers(0, 256, (4, size, size, 3), np.uint8)
    out = np.asarray(apply_batch_augment(
        imgs, _decision_params([(k, vf, hf)] * 4, colors, factors)))
    moved = np.stack([_reference_geometry(im, k, vf, hf) for im in imgs])
    np.testing.assert_array_equal(out, np.asarray(apply_batch_augment(
        moved, _decision_params([(0, 0, 0)] * 4, colors, factors))))
    ref = np.stack([T.normalize(T.apply_augment(im, k, bool(vf), bool(hf),
                                                c, f))
                    for im, c, f in zip(imgs, colors, factors)])
    assert np.abs(out - ref).max() < 1e-5


@pytest.mark.parametrize("path", ["resident", "streaming"])
def test_device_geometry_through_the_preps_on_two_devices(path):
    """The same parity through the two jitted callers, sharded over a
    two-device mesh: the resident prep (rows gathered from the held form)
    and the streaming prep (sharded, donating). All 16 decisions x 4
    colour branches in one global batch of 64."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from tpuic.data.device_prep import (make_resident_prep, pack_params,
                                        resident_rows)
    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    shard, repl = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())
    size, rng = 30, np.random.default_rng(30)
    corpus = rng.integers(0, 256, (80, size, size, 3), np.uint8)
    idx = rng.permutation(80)[:64].astype(np.int32)
    decisions = [d for d in _DECISIONS for _ in range(4)]
    colors = [0, 1, 2, 3] * 16
    factors = rng.uniform(0.6, 1.4, 64)
    params = _decision_params(decisions, colors, factors)
    still = dict(params, rot=0 * params["rot"], vflip=0 * params["vflip"],
                 hflip=0 * params["hflip"])
    if path == "resident":
        prep = make_resident_prep(size, sharding=shard, replicated=repl)

        def run(images, rows, p):
            return prep(jax.device_put(resident_rows(images), repl),
                        jax.device_put(rows, shard),
                        jax.device_put(pack_params(p), shard))
    else:
        prep = make_device_prep(sharding=shard)

        def run(images, rows, p):
            return prep(jax.device_put(images[rows], shard),
                        jax.device_put(pack_params(p), shard))
    out = run(corpus, idx, params)
    assert out.sharding.spec == P("data") and out.shape == (64, size, size, 3)
    moved = np.stack([_reference_geometry(corpus[i], *d)
                      for i, d in zip(idx, decisions)])
    np.testing.assert_array_equal(
        np.asarray(out),
        np.asarray(run(moved, np.arange(64, dtype=np.int32), still)))
    ref = np.stack([T.normalize(T.apply_augment(corpus[i], k, bool(vf),
                                                bool(hf), c, f))
                    for i, (k, vf, hf), c, f in zip(idx, decisions, colors,
                                                    factors)])
    assert np.abs(np.asarray(out) - ref).max() < 1e-5


# -- packed Loader end-to-end ----------------------------------------------

@pytest.mark.parametrize("cache_mb", [4096, 0])
def test_packed_loader_matches_decode_loader(tree, tmp_path, cache_mb):
    """Both packed flavors — resident (HBM dataset + index gather) and
    streaming (per-batch uint8 upload) — must match the decode path."""
    cfg = DataConfig(data_dir=tree, resize_size=32, device_cache_mb=cache_mb)
    ds = ImageFolderDataset(tree, "train", 32, cfg)
    packed = pack_dataset(ds, str(tmp_path / "c2"), verbose=False)
    legacy = Loader(ds, global_batch=4, seed=7, num_workers=2)
    fast = Loader(packed, global_batch=4, seed=7)
    assert fast.packed and not legacy.packed
    assert fast.resident == (cache_mb > 0)
    n = 0
    for a, b in zip(legacy.epoch(2), fast.epoch(2)):
        np.testing.assert_allclose(a["image"], np.asarray(b["image"]),
                                   atol=1e-5)
        np.testing.assert_array_equal(a["label"], np.asarray(b["label"]))
        np.testing.assert_array_equal(a["mask"], np.asarray(b["mask"]))
        assert a.image_ids == b.image_ids
        n += 1
    assert n == len(legacy)


def test_resident_loader_under_mesh(tree, tmp_path):
    """Resident cache under an 8-device mesh: dataset replicated, indices
    and output batch sharded over 'data' — gather is shard-local."""
    import jax
    from jax.sharding import PartitionSpec as P
    from tpuic.config import MeshConfig
    from tpuic.runtime.mesh import make_mesh

    mesh = make_mesh(MeshConfig(), jax.devices())
    cfg = DataConfig(data_dir=tree, resize_size=32)
    ds = ImageFolderDataset(tree, "train", 32, cfg)
    packed = pack_dataset(ds, str(tmp_path / "c4"), verbose=False)
    sharded = Loader(packed, global_batch=8, mesh=mesh, seed=7)
    assert sharded.resident
    plain = Loader(packed, global_batch=8, seed=7)
    for a, b in zip(sharded.epoch(1), plain.epoch(1)):
        img = a["image"]
        assert img.sharding.spec == P("data")
        np.testing.assert_allclose(np.asarray(img), np.asarray(b["image"]),
                                   atol=1e-6)
        np.testing.assert_array_equal(np.asarray(a["label"]),
                                      np.asarray(b["label"]))


@pytest.mark.parametrize("fold,loader_kw", [
    ("val", {}),                      # eval fold: clean by default
    ("train", {"augment": False}),    # predict --fold train (ADVICE r3)
])
def test_packed_loader_serves_clean_images(tree, tmp_path, fold, loader_kw):
    """Whenever augmentation is off (fold-derived or overridden), packed
    batches equal normalize(raw) exactly — identity device prep."""
    cfg = DataConfig(data_dir=tree, resize_size=32)
    train_ds = ImageFolderDataset(tree, "train", 32, cfg)
    ds = (train_ds if fold == "train" else
          ImageFolderDataset(tree, "val", 32, cfg,
                             class_to_idx=train_ds.class_to_idx))
    packed = pack_dataset(ds, str(tmp_path / f"c3{fold}"), verbose=False)
    assert packed.train == (fold == "train")
    id_to_idx = {ds.image_id(j): j for j in range(len(ds))}
    for batch in Loader(packed, global_batch=4, shuffle=False,
                        **loader_kw).epoch(0):
        got = np.asarray(batch["image"])
        for i, image_id in enumerate(batch.image_ids):
            if batch["mask"][i] == 0:
                continue
            ref = T.normalize(np.asarray(packed.raw(id_to_idx[image_id])))
            np.testing.assert_allclose(got[i], ref, atol=1e-5)


def test_resident_upload_chunked(tree, tmp_path, monkeypatch):
    """Chunked resident upload (slow-link robustness): with a chunk budget
    smaller than the dataset, the device copy is assembled from several
    slices and must equal the memmap bit-for-bit — in the held form, each
    image one dense run of bytes."""
    from tpuic.data import pipeline as pl

    cfg = DataConfig(data_dir=tree, resize_size=32)
    ds = ImageFolderDataset(tree, "train", 32, cfg)
    packed = pack_dataset(ds, str(tmp_path / "c5"), verbose=False)
    row_bytes = 32 * 32 * 3
    # 5 rows per chunk -> 5+5+2 for the 12-image train fold: covers both
    # the full-chunk and the tail-chunk write compiles.
    monkeypatch.setattr(pl, "_UPLOAD_CHUNK_BYTES", 5 * row_bytes)
    loader = Loader(packed, global_batch=4, seed=7)
    assert loader.resident
    held = np.asarray(loader._data_dev)
    assert held.shape == (len(packed), row_bytes // 128, 128)
    np.testing.assert_array_equal(
        held.reshape(len(packed), -1),
        np.asarray(packed.array()).reshape(len(packed), -1))
    # The loader still serves correct batches through the chunked copy.
    batches = list(loader.epoch(0))
    assert len(batches) == len(loader)


def _cpu_mesh():
    import jax
    from tpuic.config import MeshConfig
    from tpuic.runtime.mesh import make_mesh
    return make_mesh(MeshConfig(), jax.devices())


@pytest.mark.parametrize("meshed", [False, True], ids=["nomesh", "mesh8"])
@pytest.mark.parametrize("size,row_bytes", [(32, 3072), (30, 3072),
                                            (299, 268288)],
                         ids=["row3072B", "row2700B", "row268203B"])
def test_resident_matches_streaming_bitwise(tree, tmp_path, size, row_bytes,
                                            meshed):
    """The resident path moves bytes and nothing else: for the same seed,
    epoch and indices its batches are the streaming path's bit for bit,
    augmentation on — for a row that is whole (8,128) tiles (32 px), one
    that is padded (30 px: 2,700 B) and one gathered as two pieces (299 px,
    the reference's default model), with and without a mesh."""
    mesh = _cpu_mesh() if meshed else None
    cfg = DataConfig(data_dir=tree, resize_size=size)
    ds = ImageFolderDataset(tree, "train", size, cfg)
    packed = pack_dataset(ds, str(tmp_path / "c6"), verbose=False)
    resident = Loader(packed, global_batch=8, mesh=mesh, seed=11)
    streaming = Loader(packed, global_batch=8, mesh=mesh, seed=11,
                       device_cache_bytes=0)
    assert resident.resident and not streaming.resident
    assert resident.augment
    # Padding is counted, and what is held is what is counted.
    assert resident.resident_bytes == len(packed) * row_bytes
    assert resident._data_dev.nbytes == resident.resident_bytes
    n = 0
    for a, b in zip(resident.epoch(3), streaming.epoch(3)):
        assert a["image"].shape == (8, size, size, 3)
        np.testing.assert_array_equal(np.asarray(a["image"]),
                                      np.asarray(b["image"]))
        np.testing.assert_array_equal(a.indices, b.indices)
        n += 1
    assert n == len(streaming) == 2


def test_resident_rows_view_or_padded_copy():
    """The held form costs the host nothing when a row needs no padding (a
    view of the memmap, for the chunked upload and the mesh callback
    alike) and one zero-padded copy when it does."""
    from tpuic.data.device_prep import resident_rows
    rng = np.random.default_rng(6)
    whole = rng.integers(0, 256, (5, 32, 32, 3), np.uint8)
    held = resident_rows(whole)
    assert held.shape == (5, 24, 128) and np.shares_memory(held, whole)
    assert np.shares_memory(resident_rows(whole[1:3]), whole)
    ragged = rng.integers(0, 256, (5, 30, 30, 3), np.uint8)
    held = resident_rows(ragged).reshape(5, -1)
    assert held.shape == (5, 3072)
    np.testing.assert_array_equal(held[:, :2700], ragged.reshape(5, -1))
    assert not held[:, 2700:].any()


@pytest.mark.parametrize("size,tiles,pieces", [
    (32, 3, 1), (30, 3, 1), (224, 147, 1), (295, 255, 1), (296, 129, 2),
    (299, 131, 2), (768, 247, 7)])
def test_resident_geometry(size, tiles, pieces):
    """A row is whole 1 KiB tiles, gathered in equal pieces of at most 256
    (the largest slice the TPU's compiler gathers in place); the padding
    that costs is counted in resident_row_bytes (296 px: 257 tiles held as
    2 x 129)."""
    from tpuic.data import device_prep as dp
    assert dp._resident_geometry(size) == (tiles, pieces)
    assert tiles <= dp._MAX_GATHER_TILES
    row = dp.resident_row_bytes(size)
    assert row == tiles * pieces * 1024 >= size * size * 3
    assert row - size * size * 3 < 1024 * pieces
    assert dp.resident_shape(7, size) == (7, row // 128, 128)


@pytest.mark.parametrize("meshed", [False, True], ids=["nomesh", "mesh8"])
@pytest.mark.parametrize("size", [32, 30])
def test_resident_prep_holds_no_corpus_sized_temporary(size, meshed):
    """A batch reads B rows of the corpus in place: the compiled program's
    temporaries are of the order of the batch, not of the corpus. On a CPU
    this catches a whole-corpus astype or transpose; chip_smoke.py runs the
    same check on the TPU, where it catches a relayout of the operand."""
    from tpuic.data.device_prep import check_resident_prep
    facts = check_resident_prep(size, rows=4096, batch=8,
                                mesh=_cpu_mesh() if meshed else None)
    assert facts["corpus_bytes"] == 4096 * 3072
    assert facts["temp_bytes"] < facts["corpus_bytes"] // 4


def test_resident_prep_guard_trips_on_a_corpus_sized_temporary(monkeypatch):
    """The guard itself: a gather written as a one-hot matmul (the form
    the step uses for tables indexed by batch-sharded labels) converts the
    whole corpus first, and is refused."""
    import jax
    import jax.numpy as jnp
    from tpuic.data import device_prep as dp

    def bad_prep(size, **_):
        return jax.jit(lambda data, idx, packed: jnp.einsum(
            "bn,nrl->brl", jax.nn.one_hot(idx, len(data), dtype=jnp.float32),
            data.astype(jnp.float32)))
    monkeypatch.setattr(dp, "make_resident_prep", bad_prep)
    with pytest.raises(AssertionError, match="corpus-sized temporary"):
        dp.check_resident_prep(32, rows=4096, batch=8)


def test_resident_prep_guard_trips_on_a_float32_reversal(monkeypatch):
    """The guard's other half: geometry done after the conversion to
    float32 (as it was until PR 30, four times the bytes on the chip) is
    refused by the compiled program's own text."""
    import jax.numpy as jnp
    from tpuic.data import device_prep as dp

    def late_flip(images_u8, params, **_):
        x = images_u8.astype(jnp.float32)
        return jnp.where(params["vflip"].astype(bool)[:, None, None, None],
                         jnp.flip(x, axis=1), x)
    monkeypatch.setattr(dp, "apply_batch_augment", late_flip)
    with pytest.raises(AssertionError, match="reverses the batch in float32"):
        dp.check_resident_prep(32, rows=4096, batch=8)


def test_packed_loader_start_step_serves_identical_remainder(tree, tmp_path):
    """Step-exact resume on the packed path (the production loader):
    epoch(e, start_step=s) == batches s.. of epoch(e), including the
    on-device augment output (same (seed, epoch, index) draws)."""
    cfg = DataConfig(data_dir=tree, resize_size=32)
    ds = ImageFolderDataset(tree, "train", 32, cfg)
    packed = pack_dataset(ds, str(tmp_path / "c5"), verbose=False)
    loader = Loader(packed, global_batch=4, seed=7)
    full = list(loader.epoch(3))
    tail = list(loader.epoch(3, start_step=2))
    assert len(tail) == len(full) - 2
    for want, got in zip(full[2:], tail):
        np.testing.assert_array_equal(np.asarray(want["image"]),
                                      np.asarray(got["image"]))
        np.testing.assert_array_equal(np.asarray(want["label"]),
                                      np.asarray(got["label"]))
        assert want.image_ids == got.image_ids
