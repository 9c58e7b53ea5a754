"""Device/mesh substrate — the TPU-native equivalent of ND4J + AffinityManager.

The reference pins replicas to devices through ND4J's ``AffinityManager``
(``deeplearning4j-nn/.../iterator/AsyncDataSetIterator.java:75-76``) and moves
data host->device implicitly inside every INDArray op. Here the substrate is
JAX itself: arrays are ``jax.Array`` in HBM, placement is declarative through
``jax.sharding``. This module is the single place the framework asks "what
hardware do I have and how do I lay a mesh over it".
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Canonical mesh-axis names used across the framework.  Data parallelism is
# always the leading 'data' axis; 'model' shards weights (TP); 'seq' shards
# the time axis (sequence/context parallelism — ring attention).
AXIS_DATA = "data"
AXIS_MODEL = "model"
AXIS_SEQ = "seq"


def local_devices():
    return jax.local_devices()


def device_count() -> int:
    return jax.device_count()


def default_backend() -> str:
    return jax.default_backend()


def default_mesh(
    n_devices: Optional[int] = None,
    *,
    data: Optional[int] = None,
    model: int = 1,
    seq: int = 1,
    devices: Optional[Sequence] = None,
) -> Mesh:
    """Build a named device mesh laid out so that collectives ride ICI.

    Axes: ('data', 'model', 'seq').  By default every device goes to the
    data axis (pure DP — the reference's only parallelism strategy, see
    SURVEY.md §2 parallelism inventory).  TP/SP are first-class axes so
    shardings compose: a (8,) slice can run as data=2, model=2, seq=2.
    """
    if devices is None:
        devices = jax.devices()[: n_devices] if n_devices else jax.devices()
    n = len(devices)
    if data is None:
        if n % (model * seq) != 0:
            raise ValueError(f"{n} devices not divisible by model*seq={model * seq}")
        data = n // (model * seq)
    if data * model * seq != n:
        raise ValueError(f"mesh {data}x{model}x{seq} != {n} devices")
    import numpy as np

    dev_array = np.asarray(devices).reshape(data, model, seq)
    return Mesh(dev_array, (AXIS_DATA, AXIS_MODEL, AXIS_SEQ))


def slice_mesh(
    n_slices: Optional[int] = None,
    *,
    model: int = 1,
    seq: int = 1,
    devices: Optional[Sequence] = None,
) -> Mesh:
    """Multi-slice (DCN-spanning) mesh with the standard ('data', 'model',
    'seq') axes, laid out so the expensive hop is crossed ONCE.

    On multi-slice TPU, chips within a slice talk over ICI (fast) and
    slices talk over DCN (slow).  XLA lowers a psum over the data axis to
    a hierarchical all-reduce determined purely by DEVICE ORDER: with each
    slice's chips contiguous along the data axis, the reduction runs
    ring/tree within each slice over ICI first and exchanges one
    slice-level partial over DCN — the scaling-book recipe.  This helper
    groups devices by their ``slice_index`` attribute (real multi-slice
    platforms) or into ``n_slices`` contiguous groups (virtual meshes),
    then hands back a mesh every existing TrainingMaster accepts
    unchanged: hierarchical DP needs no new API, only the right order.

    Model/seq axes are kept INSIDE a slice (their collectives are
    per-layer, far too chatty for DCN): each slice must hold a whole
    model*seq block.  Reference analog: none — the reference's Spark
    aggregation tree (``ParameterAveragingTrainingMaster.java:628-645``)
    is the closest concept, with the driver as the (single) slow hop.
    """
    if devices is None:
        devices = list(jax.devices())
    ordered, per_slice = _group_by_slice(devices, n_slices)
    if per_slice % (model * seq) != 0:
        raise ValueError(
            f"model*seq={model * seq} must divide the {per_slice} "
            "devices of each slice (TP/SP collectives must stay on "
            "ICI — a model/seq group cannot straddle DCN)")
    return default_mesh(devices=ordered, model=model, seq=seq)


def _group_by_slice(devices: Sequence, n_slices: Optional[int]):
    """Order devices slice-contiguously; returns (ordered, per_slice).

    Real multi-slice platforms carry a ``slice_index`` device attribute —
    devices regroup by it (sorted by slice, original order within a
    slice) even when ``jax.devices()`` interleaves slices.  Without the
    attribute (CPU/virtual meshes), devices split into ``n_slices`` equal
    contiguous groups.  Kept as a pure function so the regrouping is
    testable with stub devices.  (Deliberately NOT
    ``mesh_utils.create_hybrid_device_mesh``: that helper exposes DCN as
    a SEPARATE mesh axis, while this layout folds slices into the data
    axis so every existing TrainingMaster works unchanged — hierarchical
    reduction then comes from device order alone.)
    """
    has_attr = [getattr(d, "slice_index", None) for d in devices]
    if all(si is None for si in has_attr):
        k = n_slices or 1
        if len(devices) % k != 0:
            raise ValueError(
                f"{len(devices)} devices (no slice_index attribute — "
                f"virtual slicing) are not divisible into n_slices={k} "
                "equal groups")
        per = len(devices) // k
        return list(devices), per
    if any(si is None for si in has_attr):
        missing = [str(d) for d, si in zip(devices, has_attr) if si is None]
        raise ValueError(
            "some devices report a slice_index and others do not "
            f"(without: {missing}); refusing to guess which slice they "
            "belong to")
    groups: dict = {}
    for d, si in zip(devices, has_attr):
        groups.setdefault(si, []).append(d)
    if n_slices is not None and len(groups) != n_slices:
        raise ValueError(
            f"n_slices={n_slices} but the platform reports "
            f"{len(groups)} slice(s) (slice_index values: "
            f"{sorted(groups)})")
    sizes = {len(g) for g in groups.values()}
    if len(sizes) != 1:
        raise ValueError("unequal devices per slice: "
                         f"{[len(groups[s]) for s in sorted(groups)]}")
    ordered: list = []
    for si in sorted(groups):
        ordered.extend(groups[si])
    return ordered, sizes.pop()


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def data_sharded(mesh: Mesh) -> NamedSharding:
    """Shard the leading (batch) dim over the data axis."""
    return NamedSharding(mesh, P(AXIS_DATA))


@dataclasses.dataclass(frozen=True)
class DTypePolicy:
    """Mixed-precision policy.

    TPU MXU natively computes bf16 x bf16 -> f32.  The policy keeps params
    and optimizer state in f32 (master weights), casts activations/compute
    to ``compute_dtype``, and accumulates in f32.  The reference is f32/f64
    via ND4J's global dtype (no mixed precision existed); ``float32`` policy
    reproduces that exactly for parity tests.
    """

    param_dtype: jnp.dtype = jnp.float32
    compute_dtype: jnp.dtype = jnp.float32
    accum_dtype: jnp.dtype = jnp.float32

    def cast_input(self, x):
        return jax.tree_util.tree_map(
            lambda a: a.astype(self.compute_dtype)
            if hasattr(a, "dtype") and jnp.issubdtype(a.dtype, jnp.floating)
            else a,
            x,
        )


_POLICIES = {
    "float32": DTypePolicy(),
    "bfloat16": DTypePolicy(compute_dtype=jnp.bfloat16),
}
_current_policy = _POLICIES[os.environ.get("DL4J_TPU_DTYPE", "float32")]


def dtype_policy() -> DTypePolicy:
    return _current_policy


def set_dtype_policy(name: str) -> DTypePolicy:
    global _current_policy
    _current_policy = _POLICIES[name]
    return _current_policy
