"""Normalization layers: BatchNorm and LRN.

Reference: ``nn/layers/normalization/BatchNormalization.java`` (rank-2 dense
and rank-4 conv paths, running mean/var with decay, gamma/beta optionally
locked), ``LocalResponseNormalization.java`` (k, n, alpha, beta across-channel
LRN), both with cuDNN helper hooks.  TPU-native: pure jnp reductions that XLA
fuses; running stats live in the layer *state* pytree (the functional answer
to the reference's mutable fields), updated only when ``train=True``.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.nn import activations
from deeplearning4j_tpu.nn.inputs import InputType
from deeplearning4j_tpu.nn.layers.base import Layer, register_layer


@register_layer
@dataclasses.dataclass(frozen=True)
class BatchNormalization(Layer):
    kind = "norm"

    n_out: Optional[int] = None   # feature/channel count (inferred)
    decay: float = 0.9            # running-average decay (reference default)
    eps: float = 1e-5
    lock_gamma_beta: bool = False # reference lockGammaBeta: fixed gamma/beta
    gamma: float = 1.0
    beta: float = 0.0
    activation: str = "identity"

    def setup(self, input_type: InputType) -> "BatchNormalization":
        if self.n_out is None:
            n = input_type.channels if input_type.kind == "cnn" else input_type.flat_size()
            return dataclasses.replace(self, n_out=n)
        return self

    def output_type(self, input_type: InputType) -> InputType:
        return input_type

    def init(self, key, dtype=jnp.float32):
        if self.lock_gamma_beta:
            return {}
        return {
            "gamma": jnp.full((self.n_out,), self.gamma, dtype),
            "beta": jnp.full((self.n_out,), self.beta, dtype),
        }

    def init_state(self):
        return {
            "mean": jnp.zeros((self.n_out,), jnp.float32),
            "var": jnp.ones((self.n_out,), jnp.float32),
        }

    def apply(self, params, state, x, *, train=False, rng=None):
        # reduce over all axes except the trailing feature/channel axis —
        # covers both the rank-2 dense and rank-4 NHWC conv paths uniformly
        # (reference needed two separate code paths, BatchNormalization.java:116)
        axes = tuple(range(x.ndim - 1))
        if train:
            # helper fast path (≙ cudnnBatchNormalizationForwardTraining):
            # fused mean/var/normalize in one VMEM pass, fused backward VJP
            from deeplearning4j_tpu import helpers as _h

            helper = _h.get_helper("batch_norm")
            if (helper is not None and hasattr(helper, "apply_training")
                    and helper.supports(x) and x.ndim == 2):
                gamma = (jnp.full((self.n_out,), self.gamma, x.dtype)
                         if self.lock_gamma_beta else params["gamma"])
                beta = (jnp.full((self.n_out,), self.beta, x.dtype)
                        if self.lock_gamma_beta else params["beta"])
                y, mean, var = helper.apply_training(x, gamma, beta, self.eps)
                new_state = {
                    "mean": self.decay * state["mean"]
                            + (1 - self.decay) * jax.lax.stop_gradient(mean),
                    "var": self.decay * state["var"]
                           + (1 - self.decay) * jax.lax.stop_gradient(var),
                }
                return activations.get(self.activation)(y), new_state
            mean = jnp.mean(x, axis=axes)
            var = jnp.var(x, axis=axes)
            new_state = {
                "mean": self.decay * state["mean"] + (1 - self.decay) * mean,
                "var": self.decay * state["var"] + (1 - self.decay) * var,
            }
        else:
            mean, var = state["mean"], state["var"]
            new_state = state
            # helper fast path (≙ cuDNN BN helper hook, BatchNormalization
            # .java:116-121): fused Pallas inference pass when available
            from deeplearning4j_tpu import helpers as _h

            helper = _h.get_helper("batch_norm")
            if helper is not None and helper.supports(x):
                gamma = (jnp.full((self.n_out,), self.gamma, x.dtype)
                         if self.lock_gamma_beta else params["gamma"])
                beta = (jnp.full((self.n_out,), self.beta, x.dtype)
                        if self.lock_gamma_beta else params["beta"])
                y = helper.apply_inference(x, mean, var, gamma, beta, self.eps)
                return activations.get(self.activation)(y), new_state
        xhat = (x - mean) * lax.rsqrt(var + self.eps)
        if self.lock_gamma_beta:
            y = self.gamma * xhat + self.beta
        else:
            y = params["gamma"] * xhat + params["beta"]
        return activations.get(self.activation)(y), new_state


@register_layer
@dataclasses.dataclass(frozen=True)
class LocalResponseNormalization(Layer):
    """Across-channel LRN: y = x / (k + alpha*sum_{j in window} x_j^2)^beta.
    Reference defaults k=2, n=5, alpha=1e-4, beta=0.75
    (``nn/conf/layers/LocalResponseNormalization``)."""

    kind = "norm"

    k: float = 2.0
    n: int = 5
    alpha: float = 1e-4
    beta: float = 0.75

    def has_params(self) -> bool:
        return False

    def init(self, key, dtype=jnp.float32):
        return {}

    def output_type(self, input_type: InputType) -> InputType:
        return input_type

    def apply(self, params, state, x, *, train=False, rng=None):
        # helper fast path (≙ CudnnLocalResponseNormalizationHelper hook)
        from deeplearning4j_tpu import helpers as _h

        helper = _h.get_helper("lrn")
        if helper is not None and helper.supports(x):
            return helper.apply(x, self.k, self.n, self.alpha, self.beta), state
        # NHWC: window-sum x^2 along the channel axis via reduce_window
        half = self.n // 2
        sq = x * x
        window_sum = lax.reduce_window(
            sq, 0.0, lax.add,
            window_dimensions=(1, 1, 1, self.n),
            window_strides=(1, 1, 1, 1),
            padding=((0, 0), (0, 0), (0, 0), (half, half)),
        )
        denom = jnp.power(self.k + self.alpha * window_sum, self.beta)
        return x / denom, state


@register_layer
@dataclasses.dataclass(frozen=True)
class LayerNorm(Layer):
    """Per-example feature normalization (no reference analog — the
    reference is pre-transformer; needed by the attention stack).
    Normalizes over the trailing feature axis, so it is exactly
    sequence-shard-safe: under sequence parallelism every timestep
    normalizes locally with no collective."""

    kind = "norm"

    n_in: Optional[int] = None
    eps: float = 1e-5
    activation: str = "identity"

    def setup(self, input_type: InputType) -> "LayerNorm":
        if self.n_in is None:
            return dataclasses.replace(self, n_in=input_type.size)
        return self

    def output_type(self, input_type: InputType) -> InputType:
        return input_type

    def init(self, key, dtype=jnp.float32):
        return {
            "gamma": jnp.ones((self.n_in,), dtype),
            "beta": jnp.zeros((self.n_in,), dtype),
        }

    def apply(self, params, state, x, *, train=False, rng=None):
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        y = (x - mu) * lax.rsqrt(var + self.eps)
        y = params["gamma"] * y + params["beta"]
        return activations.get(self.activation)(y), state


@register_layer
@dataclasses.dataclass(frozen=True)
class RMSNorm(Layer):
    """Root-mean-square normalization over the trailing feature axis
    (Zhang & Sennrich 2019): ``x / sqrt(mean(x^2) + eps) * gamma`` — no
    mean subtraction, no bias.  The statistics are taken in float32
    whatever the compute dtype and the result is cast back."""

    kind = "norm"

    n_in: Optional[int] = None
    eps: float = 1e-5
    activation: str = "identity"

    def setup(self, input_type: InputType) -> "RMSNorm":
        if self.n_in is None:
            return dataclasses.replace(self, n_in=input_type.size)
        return self

    def output_type(self, input_type: InputType) -> InputType:
        return input_type

    def init(self, key, dtype=jnp.float32):
        return {"gamma": jnp.ones((self.n_in,), dtype)}

    def apply(self, params, state, x, *, train=False, rng=None):
        y = rms_norm(x, params["gamma"], self.eps)
        return activations.get(self.activation)(y), state


def rms_norm(x, gamma, eps):
    """The RMS norm itself, shared with the layers that norm inside
    (latent attention's two compressed streams)."""
    acc = jnp.promote_types(x.dtype, jnp.float32)
    xf = x.astype(acc)
    y = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * gamma.astype(acc)).astype(x.dtype)
