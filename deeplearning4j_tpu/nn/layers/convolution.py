"""Convolution + pooling layers.

Reference: ``nn/layers/convolution/ConvolutionLayer.java:141-172`` implements
conv as im2col -> gemm -> col2im on ND4J, with a cuDNN fast path
(``deeplearning4j-cuda/.../CudnnConvolutionHelper.java``).  TPU-native design:
one ``lax.conv_general_dilated`` in NHWC/HWIO, which XLA lowers straight onto
the MXU — the im2col materialization and the helper-plugin seam both dissolve
(XLA *is* the fast path; see deeplearning4j_tpu/ops for the Pallas escape
hatch when fusion is insufficient).

Layouts: activations NHWC, kernels HWIO.  Padding is explicit ints like the
reference (kernel/stride/padding triples), not just SAME/VALID.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.nn import activations, initializers
from deeplearning4j_tpu.nn.inputs import InputType
from deeplearning4j_tpu.nn.layers.base import Layer, register_layer


def _pair(v) -> Tuple[int, int]:
    if isinstance(v, (list, tuple)):
        return (int(v[0]), int(v[1]))
    return (int(v), int(v))


def _out_size(size, k, s, p):
    return (size + 2 * p - k) // s + 1


@register_layer
@dataclasses.dataclass(frozen=True)
class ConvolutionLayer(Layer):
    kind = "conv"

    n_in: Optional[int] = None    # input channels (inferred)
    n_out: Optional[int] = None   # output channels
    kernel_size: Tuple[int, int] = (5, 5)
    stride: Tuple[int, int] = (1, 1)
    padding: Tuple[int, int] = (0, 0)
    activation: str = "identity"
    weight_init: str = "xavier"

    def __post_init__(self):
        object.__setattr__(self, "kernel_size", _pair(self.kernel_size))
        object.__setattr__(self, "stride", _pair(self.stride))
        object.__setattr__(self, "padding", _pair(self.padding))

    def setup(self, input_type: InputType) -> "ConvolutionLayer":
        if self.n_in is None:
            if input_type.kind not in ("cnn", "cnn_flat"):
                raise ValueError(f"ConvolutionLayer expects CNN input, got {input_type}")
            return dataclasses.replace(self, n_in=input_type.channels)
        return self

    def output_type(self, input_type: InputType) -> InputType:
        kh, kw = self.kernel_size
        sh, sw = self.stride
        ph, pw = self.padding
        h = _out_size(input_type.height, kh, sh, ph)
        w = _out_size(input_type.width, kw, sw, pw)
        if h <= 0 or w <= 0:
            raise ValueError(
                f"Conv output size {h}x{w} invalid for input "
                f"{input_type.height}x{input_type.width} kernel {self.kernel_size} "
                f"stride {self.stride} pad {self.padding}"
            )
        return InputType.convolutional(h, w, self.n_out)

    def init(self, key, dtype=jnp.float32):
        kh, kw = self.kernel_size
        from deeplearning4j_tpu.nn.initializers import distribution_from_dict

        w = initializers.init(
            self.weight_init, key, (kh, kw, self.n_in, self.n_out), dtype,
            distribution=distribution_from_dict(self.dist),
        )
        b = jnp.full((self.n_out,), self.bias_init, dtype)
        return {"W": w, "b": b}

    def apply(self, params, state, x, *, train=False, rng=None):
        x = self.maybe_dropout(x, train=train, rng=rng)
        x = x.astype(params["W"].dtype)
        ph, pw = self.padding
        z = lax.conv_general_dilated(
            x,
            params["W"],
            window_strides=self.stride,
            padding=((ph, ph), (pw, pw)),
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        z = z + params["b"]
        return activations.get(self.activation)(z), state


@register_layer
@dataclasses.dataclass(frozen=True)
class SubsamplingLayer(Layer):
    """Pooling (reference ``SubsamplingLayer.java``: MAX/AVG/SUM + cuDNN
    helper). TPU-native: ``lax.reduce_window`` — XLA fuses and the backward
    pass (scatter for max, uniform spread for avg) comes from autodiff."""

    kind = "conv"

    pooling_type: str = "max"  # max | avg | sum
    kernel_size: Tuple[int, int] = (2, 2)
    stride: Tuple[int, int] = (2, 2)
    padding: Tuple[int, int] = (0, 0)
    activation: str = "identity"

    def __post_init__(self):
        object.__setattr__(self, "kernel_size", _pair(self.kernel_size))
        object.__setattr__(self, "stride", _pair(self.stride))
        object.__setattr__(self, "padding", _pair(self.padding))

    def has_params(self) -> bool:
        return False

    def init(self, key, dtype=jnp.float32):
        return {}

    def output_type(self, input_type: InputType) -> InputType:
        kh, kw = self.kernel_size
        sh, sw = self.stride
        ph, pw = self.padding
        h = _out_size(input_type.height, kh, sh, ph)
        w = _out_size(input_type.width, kw, sw, pw)
        return InputType.convolutional(h, w, input_type.channels)

    def apply(self, params, state, x, *, train=False, rng=None):
        kh, kw = self.kernel_size
        sh, sw = self.stride
        ph, pw = self.padding
        window = (1, kh, kw, 1)
        strides = (1, sh, sw, 1)
        pads = ((0, 0), (ph, ph), (pw, pw), (0, 0))
        pt = self.pooling_type.lower()
        if pt == "max":
            y = lax.reduce_window(x, -jnp.inf, lax.max, window, strides, pads)
        elif pt in ("avg", "mean"):
            s = lax.reduce_window(x, 0.0, lax.add, window, strides, pads)
            y = s / float(kh * kw)
        elif pt == "sum":
            y = lax.reduce_window(x, 0.0, lax.add, window, strides, pads)
        else:
            raise ValueError(f"Unknown pooling type {self.pooling_type}")
        return y, state


@register_layer
@dataclasses.dataclass(frozen=True)
class GlobalPoolingLayer(Layer):
    """Global spatial (or temporal) pooling: [B,H,W,C]->[B,C] or
    [B,T,F]->[B,F].  TPU-native reduction; used by ResNet-style heads."""

    kind = "conv"

    pooling_type: str = "avg"  # avg | max | sum

    def has_params(self) -> bool:
        return False

    def init(self, key, dtype=jnp.float32):
        return {}

    def output_type(self, input_type: InputType) -> InputType:
        if input_type.kind == "cnn":
            return InputType.feed_forward(input_type.channels)
        return InputType.feed_forward(input_type.size)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        axes = tuple(range(1, x.ndim - 1))
        pt = self.pooling_type.lower()
        if mask is not None and x.ndim == 3:
            # masked temporal pooling: exclude padded timesteps
            m = mask[..., None]
            if pt in ("avg", "mean"):
                denom = jnp.maximum(jnp.sum(m, axis=1), 1.0)
                return jnp.sum(x * m, axis=1) / denom, state
            if pt == "max":
                neg = jnp.asarray(-jnp.inf, x.dtype)
                return jnp.max(jnp.where(m > 0, x, neg), axis=1), state
            if pt == "sum":
                return jnp.sum(x * m, axis=1), state
        if pt in ("avg", "mean"):
            return jnp.mean(x, axis=axes), state
        if pt == "max":
            return jnp.max(x, axis=axes), state
        if pt == "sum":
            return jnp.sum(x, axis=axes), state
        raise ValueError(f"Unknown pooling type {self.pooling_type}")
