"""Real-format parse branches for CIFAR-10 and LFW, exercised hermetically
(the ``write_*`` inverse-format trick from tests/test_mnist_idx.py, applied
to the two remaining image datasets).

Reference formats: CIFAR binary batches (1 label byte + 3072 CHW RGB bytes
per record, ``CifarDataSetIterator.java``/``CifarLoader``) and the LFW
archive layout (one directory per person, images resized to a fixed side,
person index as label, ``LFWDataFetcher.java``).
"""

import numpy as np
import pytest

from deeplearning4j_tpu.datasets.cifar import (
    CifarDataFetcher, CifarDataSetIterator, _synthetic_cifar,
    write_cifar_batch,
)
from deeplearning4j_tpu.datasets.lfw import (
    LFWDataFetcher, LFWDataSetIterator, _synthetic_faces, read_pgm,
    write_pgm, SIDE,
)


# ------------------------------------------------------------------- CIFAR
def _write_cifar_corpus(root, n_train=128, n_test=32):
    imgs, labels = _synthetic_cifar(n_train, seed=7)
    u8 = np.round(imgs * 255.0).astype(np.uint8)
    # spread across two train batch files like the real archive's five
    write_cifar_batch(root / "data_batch_1.bin", u8[: n_train // 2],
                      labels[: n_train // 2])
    write_cifar_batch(root / "data_batch_2.bin", u8[n_train // 2:],
                      labels[n_train // 2:])
    timgs, tlabels = _synthetic_cifar(n_test, seed=8)
    write_cifar_batch(root / "test_batch.bin",
                      np.round(timgs * 255.0).astype(np.uint8), tlabels)
    return u8, labels


def test_cifar_batch_write_read_round_trip(tmp_path):
    u8, labels = _write_cifar_corpus(tmp_path)
    fetcher = CifarDataFetcher(train=True, data_dir=str(tmp_path),
                               allow_synthetic=False)
    assert fetcher.is_synthetic is False
    assert fetcher.features.shape == (128, 3072)
    np.testing.assert_allclose(fetcher.features,
                               u8.astype(np.float32) / 255.0)
    np.testing.assert_array_equal(np.argmax(fetcher.labels, 1), labels)


def test_cifar_record_layout_is_the_reference_format(tmp_path):
    # 1 label byte then 3072 image bytes, back to back — byte-level check
    img = np.arange(3072, dtype=np.uint8).reshape(1, 3072)
    write_cifar_batch(tmp_path / "data_batch_1.bin", img, np.array([3]))
    raw = (tmp_path / "data_batch_1.bin").read_bytes()
    assert len(raw) == 3073
    assert raw[0] == 3
    assert np.array_equal(np.frombuffer(raw, np.uint8)[1:], img[0])


def test_cifar_iterator_real_branch_and_subdir_layout(tmp_path, monkeypatch):
    sub = tmp_path / "cifar-10-batches-bin"
    sub.mkdir()
    _write_cifar_corpus(sub)
    monkeypatch.setenv("DL4J_TPU_CIFAR_DIR", str(tmp_path))
    it = CifarDataSetIterator(batch_size=32, train=True)
    assert it.is_synthetic is False
    ds = next(iter(it))
    assert ds.features.shape == (32, 3072)


def test_cifar_test_split_real_branch(tmp_path):
    _write_cifar_corpus(tmp_path)
    fetcher = CifarDataFetcher(train=False, data_dir=str(tmp_path),
                               allow_synthetic=False)
    assert fetcher.is_synthetic is False
    assert len(fetcher.features) == 32


# --------------------------------------------------------------------- LFW
def _write_lfw_corpus(root, people=4, per_person=6):
    """The reference archive layout: root/<person>/<person>_NNNN.pgm, at a
    non-native size so the resize path runs too."""
    rs = np.random.RandomState(11)
    raw = {}
    for p in range(people):
        d = root / f"person_{p:02d}"
        d.mkdir(parents=True)
        imgs, _ = _synthetic_faces(per_person, 1, seed=100 + p)
        for i, img in enumerate(imgs.reshape(per_person, SIDE, SIDE)):
            big = np.kron(np.round(img * 255).astype(np.uint8),
                          np.ones((2, 2), np.uint8))  # 80x80 -> resize
            write_pgm(d / f"person_{p:02d}_{i:04d}.pgm", big)
            raw[(p, i)] = big
    return raw


def test_pgm_write_read_round_trip(tmp_path):
    img = np.arange(np.uint8(200), dtype=np.uint8).reshape(10, 20)
    write_pgm(tmp_path / "x.pgm", img)
    back = read_pgm(tmp_path / "x.pgm")
    np.testing.assert_array_equal(back, img)
    # header robustness: comments + multi-whitespace, like real tools emit
    (tmp_path / "c.pgm").write_bytes(
        b"P5\n# made by a scanner\n20  10\n255\n" + img.tobytes())
    np.testing.assert_array_equal(read_pgm(tmp_path / "c.pgm"), img)


def test_pgm_rejects_ascii_and_16bit(tmp_path):
    (tmp_path / "a.pgm").write_bytes(b"P2\n2 2\n255\n0 1 2 3\n")
    with pytest.raises(ValueError, match="P5"):
        read_pgm(tmp_path / "a.pgm")
    (tmp_path / "w.pgm").write_bytes(b"P5\n2 2\n65535\n" + bytes(8))
    with pytest.raises(ValueError, match="16-bit"):
        read_pgm(tmp_path / "w.pgm")


def test_lfw_person_dir_real_branch(tmp_path):
    _write_lfw_corpus(tmp_path, people=4, per_person=6)
    fetcher = LFWDataFetcher(data_dir=str(tmp_path), allow_synthetic=False)
    assert fetcher.is_synthetic is False
    assert fetcher.num_classes == 4
    assert fetcher.features.shape == (24, SIDE * SIDE)
    # labels follow sorted directory order, per the reference fetcher
    np.testing.assert_array_equal(np.argmax(fetcher.labels, 1),
                                  np.repeat(np.arange(4), 6))
    # 2x-upscaled PGMs resized back to SIDE: nearest-neighbour on an even
    # factor reproduces the original pixels exactly
    orig, _ = _synthetic_faces(6, 1, seed=100)
    np.testing.assert_allclose(
        fetcher.features[0],
        np.round(orig[0] * 255).astype(np.uint8).astype(np.float32) / 255.0)


def test_lfw_iterator_env_var(tmp_path, monkeypatch):
    _write_lfw_corpus(tmp_path, people=3, per_person=4)
    monkeypatch.setenv("DL4J_TPU_LFW_DIR", str(tmp_path))
    it = LFWDataSetIterator(batch_size=4)
    assert it.is_synthetic is False
    assert it.num_classes == 3
    ds = next(iter(it))
    assert ds.features.shape == (4, SIDE * SIDE)


def test_lfw_npy_branch_still_works(tmp_path):
    feats, labels = _synthetic_faces(12, 3, seed=5)
    np.save(tmp_path / "faces.npy", feats)
    np.save(tmp_path / "labels.npy", labels)
    fetcher = LFWDataFetcher(data_dir=str(tmp_path), allow_synthetic=False)
    assert fetcher.is_synthetic is False
    assert fetcher.num_classes == int(labels.max()) + 1
    np.testing.assert_allclose(fetcher.features, feats)
