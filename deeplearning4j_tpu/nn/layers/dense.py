"""Feed-forward layers: Dense, Output, Activation, Dropout, Embedding.

Reference impls: ``nn/layers/feedforward/dense/DenseLayer.java``,
``nn/layers/BaseOutputLayer.java`` / ``OutputLayer.java``,
``nn/layers/feedforward/embedding/EmbeddingLayer.java``.
Param names follow the reference ("W", "b") so checkpoints/tests read naturally.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn import activations, initializers, losses
from deeplearning4j_tpu.nn.inputs import InputType
from deeplearning4j_tpu.nn.layers.base import Layer, register_layer


@register_layer
@dataclasses.dataclass(frozen=True)
class DenseLayer(Layer):
    kind = "ffn"

    n_in: Optional[int] = None
    n_out: Optional[int] = None
    _SUPPORTS_DROP_CONNECT = True  # apply() masks W via maybe_drop_connect

    def setup(self, input_type: InputType) -> "DenseLayer":
        if self.n_in is None:
            return dataclasses.replace(self, n_in=input_type.flat_size())
        return self

    def output_type(self, input_type: InputType) -> InputType:
        if input_type.kind == "rnn":
            # dense applied per-timestep (reference wraps via preprocessor;
            # here batched matmul handles [B,T,F] natively)
            return InputType.recurrent(self.n_out, input_type.timesteps)
        return InputType.feed_forward(self.n_out)

    def init(self, key, dtype=jnp.float32):
        from deeplearning4j_tpu.nn.initializers import distribution_from_dict

        w = initializers.init(
            self.weight_init, key, (self.n_in, self.n_out), dtype,
            distribution=distribution_from_dict(self.dist),
        )
        b = jnp.full((self.n_out,), self.bias_init, dtype)
        return {"W": w, "b": b}

    def apply(self, params, state, x, *, train=False, rng=None):
        x = self.maybe_dropout(x, train=train, rng=rng)
        w = self.maybe_drop_connect(params["W"], train=train, rng=rng)
        z = x @ w + params["b"]
        return activations.get(self.activation)(z), state

    def pre_output(self, params, x):
        return x @ params["W"] + params["b"]


@register_layer
@dataclasses.dataclass(frozen=True)
class GatedMLP(Layer):
    """Bias-free gated feed-forward block (SwiGLU with the default
    activation): ``(act(x W_gate) * (x W_up)) W_down``.  ``hidden`` is the
    width of the gate and up projections."""

    kind = "ffn"

    n_in: Optional[int] = None
    n_out: Optional[int] = None
    hidden: int = 0
    activation: str = "silu"

    def setup(self, input_type: InputType) -> "GatedMLP":
        n_in = self.n_in if self.n_in is not None else input_type.flat_size()
        n_out = self.n_out if self.n_out is not None else n_in
        return dataclasses.replace(self, n_in=n_in, n_out=n_out)

    def output_type(self, input_type: InputType) -> InputType:
        if input_type.kind == "rnn":
            return InputType.recurrent(self.n_out, input_type.timesteps)
        return InputType.feed_forward(self.n_out)

    def init(self, key, dtype=jnp.float32):
        if self.hidden < 1:
            raise ValueError("GatedMLP needs hidden >= 1")
        kg, ku, kd = jax.random.split(key, 3)

        def w(k, shape):
            return initializers.init(self.weight_init, k, shape, dtype)

        return {"W_gate": w(kg, (self.n_in, self.hidden)),
                "W_up": w(ku, (self.n_in, self.hidden)),
                "W_down": w(kd, (self.hidden, self.n_out))}

    def apply(self, params, state, x, *, train=False, rng=None):
        x = self.maybe_dropout(x, train=train, rng=rng)
        return gated_mlp(x, params["W_gate"], params["W_up"],
                         params["W_down"], self.activation), state


def gated_mlp(x, w_gate, w_up, w_down, activation="silu"):
    """One gated feed-forward product, shared with the expert layer's
    shared expert."""
    return (activations.get(activation)(x @ w_gate) * (x @ w_up)) @ w_down


@register_layer
@dataclasses.dataclass(frozen=True)
class OutputLayer(DenseLayer):
    """Dense + loss head (reference ``nn/layers/OutputLayer.java``).
    ``loss`` names a function in :mod:`deeplearning4j_tpu.nn.losses`."""

    kind = "head"

    loss: str = "mcxent"
    # default differs from the base "sigmoid": with the default mcxent loss
    # sigmoid degenerates (see validate); softmax is the classification
    # default users expect
    activation: str = "softmax"

    def validate(self) -> None:
        super().validate()
        losses.get(self.loss)
        if self.loss == "mcxent" and self.activation == "sigmoid":
            import warnings

            # mcxent lacks the (1-y)log(1-p) term, so with independent
            # sigmoid outputs the loss is minimised by saturating ALL units
            # to 1 — training silently degenerates (later reference versions
            # warn on this exact pairing too)
            warnings.warn(
                "OutputLayer: loss 'mcxent' with activation 'sigmoid' "
                "degenerates (all outputs ->1). Use activation='softmax' "
                "for classification or loss='xent' for multi-label.",
                stacklevel=2)

    def score(self, params, x, labels, mask=None):
        pre = self.pre_output(params, x)
        return losses.score(self.loss, labels, pre, self.activation, mask)


@register_layer
@dataclasses.dataclass(frozen=True)
class ActivationLayer(Layer):
    """Pure activation layer (reference ``nn/conf/layers/ActivationLayer``)."""

    kind = "ffn"

    def output_type(self, input_type: InputType) -> InputType:
        return input_type

    def has_params(self) -> bool:
        return False

    def init(self, key, dtype=jnp.float32):
        return {}

    def apply(self, params, state, x, *, train=False, rng=None):
        return activations.get(self.activation)(x), state


@register_layer
@dataclasses.dataclass(frozen=True)
class DropoutLayer(Layer):
    """Standalone dropout (reference DropoutLayer)."""

    kind = "ffn"

    def output_type(self, input_type: InputType) -> InputType:
        return input_type

    def has_params(self) -> bool:
        return False

    def init(self, key, dtype=jnp.float32):
        return {}

    def apply(self, params, state, x, *, train=False, rng=None):
        return self.maybe_dropout(x, train=train, rng=rng), state


@register_layer
@dataclasses.dataclass(frozen=True)
class EmbeddingLayer(Layer):
    """Index lookup layer (reference ``EmbeddingLayer.java``: input is a
    column of indices; forward = row gather, a TPU-native one-hot-free
    ``jnp.take``)."""

    kind = "embed"

    n_in: Optional[int] = None   # vocab size
    n_out: Optional[int] = None
    activation: str = "identity"
    # reference semantics: a [B, 1] input is a COLUMN of indices and embeds
    # to [B, n_out].  Sequence models (ids [B, T]) must turn this off, or a
    # length-1 sequence is indistinguishable from a column and loses its
    # time axis (zoo.transformer_char_lm sets False).
    collapse_column: bool = True

    def setup(self, input_type: InputType) -> "EmbeddingLayer":
        if self.n_in is None:
            return dataclasses.replace(self, n_in=input_type.flat_size())
        return self

    def output_type(self, input_type: InputType) -> InputType:
        if input_type.kind == "rnn":
            return InputType.recurrent(self.n_out, input_type.timesteps)
        return InputType.feed_forward(self.n_out)

    def init(self, key, dtype=jnp.float32):
        from deeplearning4j_tpu.nn.initializers import distribution_from_dict

        w = initializers.init(
            self.weight_init, key, (self.n_in, self.n_out), dtype,
            distribution=distribution_from_dict(self.dist),
        )
        b = jnp.full((self.n_out,), self.bias_init, dtype)
        return {"W": w, "b": b}

    def apply(self, params, state, x, *, train=False, rng=None):
        idx = x.astype(jnp.int32)
        if self.collapse_column and idx.ndim >= 2 and idx.shape[-1] == 1:
            idx = idx[..., 0]
        z = jnp.take(params["W"], idx, axis=0) + params["b"]
        return activations.get(self.activation)(z), state
