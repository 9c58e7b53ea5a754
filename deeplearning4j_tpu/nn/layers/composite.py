"""Composite layers: ResidualBlock (sequential sublayers + skip connection).

The reference expresses residual topology only through the ComputationGraph
ElementWiseVertex DAG (``nn/graph/vertex/impl/ElementWiseVertex.java``); this
composite gives the Sequential facade the same capability for uniform-width
blocks (transformers, ResNet-style MLPs) — XLA fuses the add into the
surrounding elementwise chain, so it costs nothing at runtime.

Sublayers must be shape-preserving end-to-end and stateless (LayerNorm,
SelfAttention, Dense are; BatchNorm is not — use the graph facade there).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.inputs import InputType
from deeplearning4j_tpu.nn.layers.base import Layer, layer_from_dict, register_layer


@register_layer
@dataclasses.dataclass(frozen=True)
class ResidualBlock(Layer):
    """y = x + f(x) where f = sublayers applied in order.

    ``remat=True`` wraps f in ``jax.checkpoint``: activations inside the
    block are recomputed during the backward pass instead of stored —
    the standard long-context memory trade (activation memory per block
    drops from O(sublayers) to O(1) at ~1.3x FLOPs), composing with the
    sequence-parallel path for sequences that would not otherwise fit HBM."""

    layers: Tuple[Layer, ...] = ()
    remat: bool = False

    def setup(self, input_type: InputType) -> "ResidualBlock":
        done, it = [], input_type
        for sub in self.layers:
            sub = sub.setup(it)
            it = sub.output_type(it)
            done.append(sub)
        return dataclasses.replace(self, layers=tuple(done))

    def output_type(self, input_type: InputType) -> InputType:
        return input_type

    def init(self, key, dtype=jnp.float32):
        ks = jax.random.split(key, max(len(self.layers), 1))
        params: Dict[str, Any] = {}
        for i, (sub, k) in enumerate(zip(self.layers, ks)):
            if sub.has_params():
                params[f"sub{i}"] = sub.init(k, dtype)
        return params

    def init_state(self):
        for sub in self.layers:
            if sub.init_state():
                raise ValueError(
                    "ResidualBlock sublayers must be stateless "
                    f"(got state from {type(sub).__name__})")
        return {}

    def _fused_prologue_helper(self, x):
        """The train-side fusion seam (roadmap item 1): a pre-norm block
        opens LayerNorm -> sublayer, i.e. the sublayer consumes
        ``dropout(LayerNorm(x))`` — exactly the fused
        dropout+residual+norm kernel's prologue form
        (``helpers/fused_epilogue.py``).  Returns the helper when the
        block shape and input qualify, else None (stock jnp path —
        which IS the parity reference)."""
        if len(self.layers) < 2:
            return None
        from deeplearning4j_tpu.nn.layers.normalization import LayerNorm

        ln = self.layers[0]
        if not isinstance(ln, LayerNorm) or ln.activation != "identity":
            return None
        from deeplearning4j_tpu.helpers import get_helper

        helper = get_helper("epilogue")
        if helper is None or not helper.supports(x):
            return None
        return helper

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        import inspect

        rngs = (jax.random.split(rng, len(self.layers))
                if rng is not None else [None] * len(self.layers))
        fused = self._fused_prologue_helper(x)

        def body(params, x, rngs, mask):
            h = x
            start = 0
            if fused is not None:
                ln, sub1 = self.layers[0], self.layers[1]
                # fold sub1's INPUT dropout (reference applyDropout
                # semantics — see Layer.maybe_dropout) into the fused
                # norm; the mask key is sub1's own rng, so the drawn
                # mask is bit-identical to the unfused path's
                rate = (sub1.dropout if train and sub1.dropout > 0.0
                        and not sub1.drop_connect else 0.0)
                h = fused.prologue(
                    h, params["sub0"]["gamma"], params["sub0"]["beta"],
                    eps=ln.eps, rate=rate, rng=rngs[1], train=train)
                sub1r = (dataclasses.replace(sub1, dropout=0.0)
                         if rate > 0.0 else sub1)
                kw = ({"mask": mask} if mask is not None and "mask" in
                      inspect.signature(sub1r.apply).parameters else {})
                h, _ = sub1r.apply(params.get("sub1", {}), {}, h,
                                   train=train, rng=rngs[1], **kw)
                start = 2
            for i in range(start, len(self.layers)):
                sub = self.layers[i]
                kw = ({"mask": mask} if mask is not None
                      and "mask" in inspect.signature(sub.apply).parameters else {})
                h, _ = sub.apply(params.get(f"sub{i}", {}), {}, h,
                                 train=train, rng=rngs[i], **kw)
            return x + h

        if self.remat and train:
            body = jax.checkpoint(body)
        return body(params, x, rngs, mask), state

    def init_cache(self, batch: int, dtype=jnp.float32):
        """Streaming carries for cache-bearing sublayers (attention KV
        caches).  Returns a dict (possibly empty) whenever ANY sublayer is
        carryable — recurrent sublayers seed their own state on first
        apply_with_carry(None), but the block must enter the carry path for
        that to happen — and None when the block holds none."""
        carry = {}
        carryable = False
        for i, sub in enumerate(self.layers):
            if hasattr(sub, "init_cache"):
                carryable = True
                c = sub.init_cache(batch, dtype)
                if c is not None:
                    carry[f"sub{i}"] = c
            elif hasattr(sub, "apply_with_carry"):
                carryable = True
        return carry if carryable else None

    def init_paged_cache(self, num_pages: int, page_size: int,
                         dtype=jnp.float32, window_pages=None):
        """Paged-pool carries for pageable sublayers (attention KV pools —
        see ``SelfAttentionLayer.init_paged_cache``).  A sublayer that is
        carryable but NOT pageable (recurrent state) makes the whole block
        unpageable: the continuous-batching engine needs every carry to be
        slot-addressable through the block table, and recurrent hidden
        state is not — it raises so the engine fails loudly at setup.
        ``window_pages`` sizes the pools of window sublayers."""
        carry = {}
        pageable = False
        for i, sub in enumerate(self.layers):
            if hasattr(sub, "init_paged_cache"):
                pageable = True
                c = sub.init_paged_cache(num_pages, page_size, dtype,
                                         window_pages=window_pages)
                if c is not None:
                    carry[f"sub{i}"] = c
            elif hasattr(sub, "apply_with_carry"):
                raise ValueError(
                    f"ResidualBlock sublayer {type(sub).__name__} carries "
                    "state but has no paged-cache form; the generation "
                    "engine only serves fully pageable (attention-cached) "
                    "stacks")
        return carry if pageable else None

    def apply_with_carry(self, params, state, x, carry, *, train=False,
                         rng=None, mask=None):
        """carry=None -> exact ``apply`` (training/batch paths untouched).
        With a carry dict: thread each sublayer's cache through; remat is
        irrelevant here (streaming is forward-only)."""
        if carry is None:
            y, st = self.apply(params, state, x, train=train, rng=rng,
                               mask=mask)
            return y, st, None
        import inspect

        rngs = (jax.random.split(rng, len(self.layers))
                if rng is not None else [None] * len(self.layers))
        h = x
        new_carry = {}
        for i, sub in enumerate(self.layers):
            p = params.get(f"sub{i}", {})
            if hasattr(sub, "apply_with_carry"):
                # thread the seeded cache (attention) or None (recurrent
                # sublayers initialize their own state and return it — they
                # must NOT be applied statelessly here, or their hidden
                # state would reset every streamed chunk)
                h, _, nc = sub.apply_with_carry(
                    p, {}, h, carry.get(f"sub{i}"), train=train,
                    rng=rngs[i], mask=mask)
                if nc is not None:
                    new_carry[f"sub{i}"] = nc
            else:
                kw = ({"mask": mask} if mask is not None
                      and "mask" in inspect.signature(sub.apply).parameters
                      else {})
                h, _ = sub.apply(p, {}, h, train=train, rng=rngs[i], **kw)
        return x + h, state, new_carry

    def reg_score(self, params):
        total = jnp.zeros(())
        for i, sub in enumerate(self.layers):
            if sub.has_params():
                total = total + sub.reg_score(params[f"sub{i}"])
        return total

    def to_dict(self) -> Dict[str, Any]:
        return {
            "type": "ResidualBlock",
            "name": self.name,
            "remat": self.remat,
            "layers": [sub.to_dict() for sub in self.layers],
        }

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "ResidualBlock":
        return cls(name=d.get("name"), remat=d.get("remat", False),
                   layers=tuple(layer_from_dict(s) for s in d["layers"]))
