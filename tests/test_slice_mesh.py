"""Multi-slice (DCN-spanning) mesh helper: device order is the whole
mechanism — each slice's chips contiguous along the data axis so XLA's
hierarchical all-reduce rides ICI within a slice and crosses DCN once.
Every existing TrainingMaster accepts the mesh unchanged."""

import jax
import numpy as np
import pytest

from deeplearning4j_tpu import backend
from deeplearning4j_tpu.backend import slice_mesh


def test_virtual_slices_group_contiguously():
    mesh = slice_mesh(2)
    devs = list(mesh.devices.flatten())
    assert len(devs) == 8
    # first four ids then last four: slice blocks stay contiguous
    ids = [d.id for d in devs]
    assert ids == sorted(ids)
    assert set(ids[:4]) == set(range(4))


def test_model_axis_stays_inside_a_slice():
    mesh = slice_mesh(2, model=2)
    assert mesh.shape[backend.AXIS_DATA] == 4
    assert mesh.shape[backend.AXIS_MODEL] == 2
    # the model-pair for each data row must come from ONE slice group
    arr = mesh.devices  # [data, model, seq]
    for d in range(arr.shape[0]):
        pair = {dev.id // 4 for dev in arr[d].flatten()}
        assert len(pair) == 1, f"model group straddles slices: {pair}"


def test_rejects_model_group_straddling_dcn():
    with pytest.raises(ValueError, match="ICI"):
        slice_mesh(8, model=2)  # 1 device/slice cannot hold a model pair


def test_rejects_wrong_slice_count():
    with pytest.raises(ValueError, match="n_slices"):
        slice_mesh(3)  # 8 devices cannot form 3 equal virtual slices


def test_dp_training_over_two_virtual_slices_matches_serial():
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterator import ListDataSetIterator
    from deeplearning4j_tpu.models.sequential import MultiLayerNetwork
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.parallel import (
        DistributedNetwork, SyncTrainingMaster,
    )

    def make():
        b = (NeuralNetConfiguration.builder().seed(9)
             .updater("sgd", learning_rate=0.1).list()
             .layer(DenseLayer(n_in=6, n_out=12, activation="tanh"))
             .layer(OutputLayer(n_in=12, n_out=3)))
        return MultiLayerNetwork(b.build()).init()

    rs = np.random.RandomState(0)
    x = rs.rand(32, 6).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rs.randint(0, 3, 32)]
    serial = make()
    serial.fit(x, y)

    net = make()
    master = SyncTrainingMaster(mesh=slice_mesh(2))
    DistributedNetwork(net, master).fit(
        ListDataSetIterator(DataSet(x, y), 32))
    for ln in serial.params:
        for pn in serial.params[ln]:
            np.testing.assert_allclose(
                np.asarray(serial.params[ln][pn]),
                np.asarray(net.params[ln][pn]), atol=2e-5,
                err_msg=f"{ln}/{pn}")


class _StubDev:
    def __init__(self, id, slice_index):
        self.id = id
        self.slice_index = slice_index

    def __repr__(self):
        return f"dev{self.id}@s{self.slice_index}"


def test_slice_index_regrouping_reorders_interleaved_devices():
    """The real multi-slice mechanism: jax.devices() may interleave
    slices; grouping must reorder so each slice is contiguous."""
    from deeplearning4j_tpu.backend.device import _group_by_slice

    interleaved = [_StubDev(i, i % 2) for i in range(8)]  # s0,s1,s0,s1...
    ordered, per = _group_by_slice(interleaved, 2)
    assert per == 4
    assert [d.slice_index for d in ordered] == [0] * 4 + [1] * 4
    # original order preserved WITHIN a slice
    assert [d.id for d in ordered] == [0, 2, 4, 6, 1, 3, 5, 7]


def test_slice_index_unequal_groups_rejected():
    from deeplearning4j_tpu.backend.device import _group_by_slice

    lopsided = [_StubDev(i, 0 if i < 3 else 1) for i in range(8)]
    with pytest.raises(ValueError, match="unequal"):
        _group_by_slice(lopsided, 2)


def test_mixed_slice_index_rejected():
    """Some devices with a slice_index and some without is not a layout
    to guess at (they used to be bucketed into a pseudo-slice -1)."""
    from deeplearning4j_tpu.backend.device import _group_by_slice

    mixed = [_StubDev(i, 0 if i < 4 else None) for i in range(8)]
    with pytest.raises(ValueError, match="slice_index"):
        _group_by_slice(mixed, 2)


def test_virtual_split_error_names_the_real_cause():
    from deeplearning4j_tpu.backend.device import _group_by_slice

    with pytest.raises(ValueError, match="virtual slicing"):
        _group_by_slice([object()] * 8, 3)
