"""Shared facade plumbing for MultiLayerNetwork / ComputationGraph.

``LazyScoreMixin`` removes the per-step host sync from every training hot
loop (the reference's score update ``BaseOptimizer.java`` feeds listeners a
host double every iteration; on TPU a per-step ``float(loss)`` blocks step
N+1's dispatch behind step N's execution).  Training loops store the
*on-device* loss scalar; the transfer happens only when somebody actually
reads ``score_value`` — a listener, early stopping, a test — and the fetched
float is cached until the next step overwrites it.
"""

from __future__ import annotations

from typing import Any


class LazyScoreMixin:
    """Lazy ``score_value``: assign device arrays freely, pay the
    device->host sync only on read."""

    _score: Any = None

    @property
    def score_value(self) -> float:
        s = getattr(self, "_score", None)
        if s is None:
            return float("nan")
        if not isinstance(s, float):
            s = float(s)  # device -> host sync happens here, on demand
            self._score = s
        return s

    @score_value.setter
    def score_value(self, value) -> None:
        # accepts a python float OR an on-device scalar (no sync either way)
        self._score = value


def cast_to_compute(tree, compute_dtype):
    """``tree`` with every floating leaf in ``compute_dtype`` and everything
    else untouched: the one mixed-precision rule of both facades.

    Inside a traced forward the cast is part of the graph, so gradients
    flow back to the f32 parameters (loss and updater math stay f32).  A
    leaf already in the compute dtype passes through (``astype`` to the
    same dtype emits nothing), which is how a generation program takes
    the serving snapshot ``GenerationPrograms`` casts once per version.
    With ``compute_dtype`` None the tree is returned as it came."""
    if compute_dtype is None:
        return tree
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(compute_dtype)

    def _cast(a):
        return (a.astype(dt)
                if hasattr(a, "dtype")
                and jnp.issubdtype(a.dtype, jnp.floating) else a)

    with jax.named_scope("param_cast"):
        return jax.tree_util.tree_map(_cast, tree)


def notify_listeners(model, batch_size=None) -> None:
    """Fire ``iteration_done`` on the model's listeners, first wiring the
    actual minibatch size into any listener that wants it (fixes
    ``PerformanceListener`` reporting no samples/sec unless the user called
    ``set_batch_size`` by hand — the fit loop knows the batch, so it tells
    the listeners).  Also mirrors it as ``model.last_batch_size``."""
    if batch_size is not None:
        model.last_batch_size = int(batch_size)
    for lst in model.listeners:
        if batch_size is not None:
            setter = getattr(lst, "set_batch_size", None)
            if setter is not None:
                setter(int(batch_size))
        lst.iteration_done(model, model.iteration)


def seed_stream_caches(named_layers, rnn_state, batch, compute_dtype):
    """Streaming-cache seeding shared by both facades' ``rnn_time_step``:
    for every (name, layer) with an ``init_cache`` and no existing carry,
    allocate a KV cache in the model's compute dtype.  Returns the carries
    dict (may be empty)."""
    import jax.numpy as jnp

    cache_dtype = jnp.dtype(compute_dtype) if compute_dtype else jnp.float32
    carries = dict(rnn_state) if rnn_state else {}
    for name, layer in named_layers:
        if hasattr(layer, "init_cache") and name not in carries:
            cache = layer.init_cache(int(batch), dtype=cache_dtype)
            if cache is not None:
                carries[name] = cache
    return carries


def check_cache_capacity(carries, t_new: int, pos: int | None = None) -> None:
    """Raise before dispatch when a streamed chunk would overflow any
    attention KV cache — ``dynamic_update_slice`` clamps out-of-range
    writes and would silently relocate keys instead of failing.

    ``pos`` is the facade's host-side stream-position counter; passing it
    keeps this check free of device->host syncs in the decode hot loop
    (all caches advance in lockstep with the streamed input)."""
    from deeplearning4j_tpu.nn.layers.attention import SelfAttentionLayer

    def walk(name, c):
        if not isinstance(c, dict):
            return
        if "pos" in c and "k" in c:
            if SelfAttentionLayer.cache_overflow(c, t_new, pos=pos):
                at = pos if pos is not None else int(c["pos"])
                raise ValueError(
                    f"rnn_time_step: streaming past the KV cache of "
                    f"'{name}' (pos={at} + {t_new} > "
                    f"max_cache={c['k'].shape[1]}); raise the layer's "
                    "max_cache or rnn_clear_previous_state()")
        else:
            for k, v in c.items():
                walk(f"{name}.{k}", v)

    for name, c in (carries or {}).items():
        walk(name, c)
