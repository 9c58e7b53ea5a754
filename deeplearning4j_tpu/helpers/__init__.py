"""Accelerated-helper plugin layer (≙ deeplearning4j-cuda).

Reference: the cuDNN helper SPI — ``deeplearning4j-nn/.../convolution/
ConvolutionHelper.java:30-35`` (interface declared in core),
``CudnnConvolutionHelper.java:51`` etc. (implementation in the acceleration
module), discovered via ``Class.forName`` at layer construction
(``ConvolutionLayer.java:58-65``) and transparently intercepting
forward/backward.

TPU translation: XLA already lowers conv/matmul/BN optimally onto the MXU,
so the helper layer holds *Pallas* kernels only where a hand-fused VMEM pass
beats stock XLA fusion (LRN's cross-channel window walk, fused BN-inference
affine), plus the same discovery seam: layers ask ``get_helper(kind)`` and
fall back to the pure-jnp path when helpers are disabled or unavailable —
exactly how the reference degrades without cuDNN on the classpath.

Toggle: ``enable_helpers(False)`` or env DL4J_TPU_DISABLE_HELPERS=1.
Kernels run compiled on TPU and in interpret mode elsewhere, so the parity
gradient-check suite (``tests/test_helpers.py``, ≙ CuDNNGradientChecks)
exercises the same code path everywhere.
"""

from __future__ import annotations

import contextlib
import contextvars
import os
from typing import Dict, Optional

_enabled = os.environ.get("DL4J_TPU_DISABLE_HELPERS", "0") != "1"
_registry: Dict[str, object] = {}
_partition_mesh = contextvars.ContextVar("dl4j_tpu_partition_mesh",
                                         default=None)


@contextlib.contextmanager
def auto_partitioned(mesh):
    """Trace-time scope (context manager or decorator): the code inside is
    traced into a program that XLA partitions BY ITSELF over ``mesh`` —
    ``jax.jit`` with shardings, as the data/tensor-parallel masters build
    their step.  A Pallas kernel cannot be partitioned that way (the TPU
    lowering raises "Mosaic kernels cannot be automatically partitioned.
    Please wrap the call in a shard_map."), so inside the scope
    ``get_helper`` offers only helpers that wrap themselves in
    ``shard_map`` over :func:`partition_mesh` (flash attention); the rest
    give way to the stock jnp path, which XLA partitions fine.  Code that
    already runs under ``shard_map`` needs no scope.  A one-device mesh is
    no partitioning at all and changes nothing."""
    token = _partition_mesh.set(mesh if mesh.size > 1 else None)
    try:
        yield
    finally:
        _partition_mesh.reset(token)


def partition_mesh():
    """The mesh of the enclosing :func:`auto_partitioned` scope, or None."""
    return _partition_mesh.get()


def interpret_mode() -> bool:
    """Pallas kernels compile on TPU and run ``interpret=True`` elsewhere
    (single policy for every kernel in this package)."""
    import jax

    return jax.default_backend() != "tpu"


def enable_helpers(on: bool = True) -> None:
    """Toggle helper discovery.  NOTE: discovery happens at TRACE time, so
    already-jitted programs (e.g. a model's cached train/output step) keep
    whichever path they were traced with — toggle BEFORE first use, or use a
    fresh model/jit cache when comparing helper vs built-in paths."""
    global _enabled
    _enabled = on


def helpers_enabled() -> bool:
    return _enabled


def register_helper(kind: str, helper: object) -> None:
    _registry[kind] = helper


def get_helper(kind: str) -> Optional[object]:
    """≙ the Class.forName discovery: None when disabled/absent, in which
    case the layer uses its built-in path."""
    if not _enabled:
        return None
    helper = _registry.get(kind)
    if helper is None:
        # lazy registration on first ask
        from deeplearning4j_tpu.helpers import pallas_ops

        pallas_ops.register_default_helpers()
        helper = _registry.get(kind)
    if (partition_mesh() is not None
            and not getattr(helper, "partitions_itself", False)):
        return None
    return helper
