"""The benchmark's ImageFolder generator."""

import os

from benchmark.datagen import ensure_imagefolder


def test_tree_is_full_sized_linked_keyed_and_reused(tmp_path):
    kw = dict(size=16, train_images=48, val_images=8, classes=4,
              unique_per_class=3, corpus_seed=5)
    root = ensure_imagefolder(str(tmp_path), **kw)
    train = os.path.join(root, "train")
    assert sorted(os.listdir(train)) == ["c000", "c001", "c002", "c003"]
    files = sorted(os.listdir(os.path.join(train, "c001")))
    assert len(files) == 12 and all(f.endswith(".png") for f in files)
    paths = [os.path.join(train, "c001", f) for f in files]
    # three distinct images, the rest hard links (or copies) of them
    assert len({open(p, "rb").read() for p in paths}) == 3
    assert open(paths[0], "rb").read() == open(paths[3], "rb").read()
    assert len(os.listdir(os.path.join(root, "val", "c003"))) == 2

    from tpuic.data.folder import ImageFolderDataset
    ds = ImageFolderDataset(root, "train", 16)
    assert len(ds) == 48 and ds.num_classes == 4

    stamp = os.stat(paths[0]).st_mtime_ns
    assert ensure_imagefolder(str(tmp_path), **kw) == root      # reused
    assert os.stat(paths[0]).st_mtime_ns == stamp
    other = ensure_imagefolder(str(tmp_path), **{**kw, "corpus_seed": 6})
    assert other != root                                        # keyed
    assert open(os.path.join(other, "train", "c001", files[0]),
                "rb").read() != open(paths[0], "rb").read()


def test_an_unfinished_tree_is_made_again(tmp_path):
    kw = dict(size=8, train_images=8, val_images=4, classes=2,
              unique_per_class=2, corpus_seed=1)
    root = ensure_imagefolder(str(tmp_path), **kw)
    os.remove(os.path.join(root, "GENERATED.json"))     # a killed run
    os.remove(os.path.join(root, "train", "c000",
                           sorted(os.listdir(os.path.join(
                               root, "train", "c000")))[0]))
    assert ensure_imagefolder(str(tmp_path), **kw) == root
    assert len(os.listdir(os.path.join(root, "train", "c000"))) == 4
