"""Pretrain layers: denoising AutoEncoder and RBM with contrastive divergence.

Reference: ``nn/layers/feedforward/autoencoder/AutoEncoder.java`` (corruption +
reconstruction loss, tied weights with separate visible bias "vb") and
``nn/layers/feedforward/rbm/RBM.java:66-282`` (CD-k, Gibbs sampling,
binary/gaussian units).  The reference's stateful RNG Gibbs chains are
re-derived key-threaded (keys as explicit arguments), so pretraining jits and
remains reproducible — SURVEY.md §7 hard-part 6.

Both act as an encoder (dense forward) inside a supervised stack; their
unsupervised objective is exposed as ``pretrain_loss`` consumed by the model
facade's layerwise ``pretrain`` loop (reference ``MultiLayerNetwork.java:164``).
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
class AutoEncoder(Layer):
    kind = "ffn"

    n_in: Optional[int] = None
    n_out: Optional[int] = None
    corruption_level: float = 0.3
    loss: str = "mse"  # reconstruction loss (reference RECONSTRUCTION_CROSSENTROPY or MSE)

    def setup(self, input_type: InputType) -> "AutoEncoder":
        if self.n_in is None:
            return dataclasses.replace(self, n_in=input_type.flat_size())
        return self

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.feed_forward(self.n_out)

    def init(self, key, dtype=jnp.float32):
        from deeplearning4j_tpu.nn.initializers import distribution_from_dict

        w = initializers.init(self.weight_init, key, (self.n_in, self.n_out), dtype,
                              distribution=distribution_from_dict(self.dist))
        return {
            "W": w,
            "b": jnp.full((self.n_out,), self.bias_init, dtype),
            "vb": jnp.zeros((self.n_in,), dtype),  # visible bias for decode
        }

    def encode(self, params, x):
        return activations.get(self.activation)(x @ params["W"] + params["b"])

    def decode(self, params, y):
        # tied weights: decoder = W^T (reference PretrainParamInitializer)
        return activations.get(self.activation)(y @ params["W"].T + params["vb"])

    def apply(self, params, state, x, *, train=False, rng=None):
        x = self.maybe_dropout(x, train=train, rng=rng)
        return self.encode(params, x), state

    def pretrain_loss(self, params, x, rng):
        if self.corruption_level > 0.0:
            k1, _ = jax.random.split(rng)
            keep = jax.random.bernoulli(k1, 1.0 - self.corruption_level, x.shape)
            x_in = jnp.where(keep, x, 0.0)
        else:
            x_in = x
        recon = self.decode(params, self.encode(params, x_in))
        return losses.score(self.loss, x, recon, "identity")


@register_layer
@dataclasses.dataclass(frozen=True)
class RBM(Layer):
    """Restricted Boltzmann machine trained by CD-k.

    hidden/visible unit kinds: "binary" | "gaussian" (reference HiddenUnit /
    VisibleUnit enums; RECTIFIED/SOFTMAX variants are gated behind the same
    field and can be added without API change).
    """

    kind = "ffn"

    n_in: Optional[int] = None
    n_out: Optional[int] = None
    hidden_unit: str = "binary"
    visible_unit: str = "binary"
    k: int = 1                      # Gibbs steps (CD-k)
    activation: str = "sigmoid"

    def setup(self, input_type: InputType) -> "RBM":
        if self.n_in is None:
            return dataclasses.replace(self, n_in=input_type.flat_size())
        return self

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.feed_forward(self.n_out)

    def init(self, key, dtype=jnp.float32):
        from deeplearning4j_tpu.nn.initializers import distribution_from_dict

        w = initializers.init(self.weight_init, key, (self.n_in, self.n_out), dtype,
                              distribution=distribution_from_dict(self.dist))
        return {
            "W": w,
            "b": jnp.zeros((self.n_out,), dtype),   # hidden bias
            "vb": jnp.zeros((self.n_in,), dtype),   # visible bias
        }

    def apply(self, params, state, x, *, train=False, rng=None):
        x = self.maybe_dropout(x, train=train, rng=rng)
        return self.prop_up(params, x), state

    def prop_up(self, params, v):
        pre = v @ params["W"] + params["b"]
        return jax.nn.sigmoid(pre) if self.hidden_unit == "binary" else pre

    def prop_down(self, params, h):
        pre = h @ params["W"].T + params["vb"]
        return jax.nn.sigmoid(pre) if self.visible_unit == "binary" else pre

    def _sample(self, key, probs, kind):
        if kind == "binary":
            return jax.random.bernoulli(key, probs).astype(probs.dtype)
        # gaussian units: mean + unit noise (reference Gaussian sampling)
        return probs + jax.random.normal(key, probs.shape, probs.dtype)

    def pretrain_loss(self, params, v0, rng):
        """CD-k free-energy surrogate.  The gradient of this scalar equals the
        CD update <v0 h0> - <vk hk> because the sampled chain is treated as
        constant (lax.stop_gradient), matching reference
        ``RBM.java:99`` contrastiveDivergence."""
        keys = jax.random.split(rng, 2 * self.k + 1)
        h_prob = self.prop_up(params, v0)
        h_sample = self._sample(keys[0], h_prob, self.hidden_unit)
        vk = v0
        hk = h_sample
        for i in range(self.k):
            vk_prob = self.prop_down(params, hk)
            vk = self._sample(keys[2 * i + 1], vk_prob, self.visible_unit)
            hk_prob = self.prop_up(params, vk)
            hk = self._sample(keys[2 * i + 2], hk_prob, self.hidden_unit)
        vk = jax.lax.stop_gradient(vk)
        # free energy F(v) = -v.vb - sum softplus(v W + b); CD grad = dF(v0) - dF(vk)
        return jnp.mean(self._free_energy(params, v0) - self._free_energy(params, vk))

    def _free_energy(self, params, v):
        pre = v @ params["W"] + params["b"]
        return -v @ params["vb"] - jnp.sum(jax.nn.softplus(pre), axis=-1)

    def reconstruction_error(self, params, v, rng):
        h = self.prop_up(params, v)
        recon = self.prop_down(params, h)
        return jnp.mean(jnp.sum((v - recon) ** 2, axis=-1))
