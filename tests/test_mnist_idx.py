"""Real-IDX parse path, exercised hermetically.

The reference downloads and parses real MNIST IDX binaries
(``deeplearning4j-core/.../base/MnistFetcher.java:35``, readers
``datasets/mnist/MnistManager.java``).  This image has no egress, so the
REAL parse branch (``is_synthetic=False``) is driven by writing valid IDX
files (``write_idx``, the format inverse) from the synthetic corpus and
round-tripping them through the fetcher — both plain and gzipped, exactly
the two forms the reference's fetcher produces.
"""

import gzip

import numpy as np
import pytest

from deeplearning4j_tpu.datasets.mnist import (
    MnistDataFetcher, MnistDataSetIterator, _read_idx, _synthetic_mnist,
    write_idx,
)


def _write_corpus(root, n_train=256, n_test=64, suffix=""):
    imgs, labels = _synthetic_mnist(n_train, seed=123)
    timgs, tlabels = _synthetic_mnist(n_test, seed=124)
    u8 = lambda a: np.round(a * 255.0).astype(np.uint8)
    write_idx(root / ("train-images-idx3-ubyte" + suffix), u8(imgs))
    write_idx(root / ("train-labels-idx1-ubyte" + suffix),
              labels.astype(np.uint8))
    write_idx(root / ("t10k-images-idx3-ubyte" + suffix), u8(timgs))
    write_idx(root / ("t10k-labels-idx1-ubyte" + suffix),
              tlabels.astype(np.uint8))
    return u8(imgs), labels


@pytest.mark.parametrize("suffix", ["", ".gz"])
def test_idx_write_read_round_trip(tmp_path, suffix):
    imgs, labels = _write_corpus(tmp_path, suffix=suffix)
    back = _read_idx(tmp_path / ("train-images-idx3-ubyte" + suffix))
    assert back.dtype == np.uint8 and back.shape == (256, 28, 28)
    np.testing.assert_array_equal(back, imgs)
    if suffix == ".gz":  # actually gzipped, not just renamed
        raw = (tmp_path / ("train-labels-idx1-ubyte" + suffix)).read_bytes()
        assert raw[:2] == b"\x1f\x8b"
        assert gzip.decompress(raw)[:4] == b"\x00\x00\x08\x01"


def test_fetcher_real_branch(tmp_path):
    imgs, labels = _write_corpus(tmp_path)
    fetcher = MnistDataFetcher(train=True, data_dir=str(tmp_path),
                               allow_synthetic=False)
    assert fetcher.is_synthetic is False
    assert fetcher.features.shape == (256, 784)
    np.testing.assert_allclose(
        fetcher.features, imgs.reshape(256, 784).astype(np.float32) / 255.0)
    np.testing.assert_array_equal(np.argmax(fetcher.labels, 1), labels)


def test_fetcher_env_var_and_iterator(tmp_path, monkeypatch):
    _write_corpus(tmp_path)
    monkeypatch.setenv("DL4J_TPU_MNIST_DIR", str(tmp_path))
    it = MnistDataSetIterator(batch_size=32, num_examples=64, train=True)
    assert it.is_synthetic is False
    ds = next(iter(it))
    assert ds.features.shape == (32, 784)


def test_missing_files_still_raise_without_synthetic(tmp_path):
    with pytest.raises(FileNotFoundError, match="DL4J_TPU_MNIST_DIR"):
        MnistDataFetcher(train=True, data_dir=str(tmp_path / "nope"),
                         allow_synthetic=False)


def test_accuracy_parity_real_vs_synthetic_branch(tmp_path, monkeypatch):
    """End-to-end through the REAL parse branch: same corpus, same model,
    same training — accuracy must match the synthetic-branch e2e result
    (the data is identical up to uint8 quantization, so this isolates the
    parse path as the only variable)."""
    from deeplearning4j_tpu.evaluation import Evaluation
    from deeplearning4j_tpu.models.zoo import lenet

    # 1024 x 3 epochs is the synthetic-branch e2e recipe for the 0.85 bar
    # (tests/test_mnist_e2e.py); same recipe here isolates the parse path
    _write_corpus(tmp_path, n_train=1024, n_test=128)
    monkeypatch.setenv("DL4J_TPU_MNIST_DIR", str(tmp_path))
    train_iter = MnistDataSetIterator(batch_size=64, num_examples=1024,
                                      train=True)
    test_iter = MnistDataSetIterator(batch_size=64, num_examples=128,
                                     train=False)
    assert train_iter.is_synthetic is False
    net = lenet(updater="adam", lr=1e-3)
    net.fit(train_iter, epochs=3)
    ev = Evaluation(10)
    for ds in test_iter:
        ev.eval(ds.labels, np.asarray(net.output(ds.features)))
    assert ev.accuracy() > 0.85, ev.stats()
