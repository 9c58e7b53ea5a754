"""Activation zoo.

Mirrors the reference's string-named activations (default "sigmoid",
``nn/conf/NeuralNetConfiguration.java:413-449``; dispatched through ND4J
transform ops).  Names are the reference's lowercase strings so configs
round-trip.  All functions are jit-safe elementwise ops that XLA fuses into
the surrounding matmul epilogue — no custom kernels needed (VPU work).
"""

from __future__ import annotations

from typing import Callable, Dict

import jax
import jax.numpy as jnp


def identity(x):
    return x


def sigmoid(x):
    return jax.nn.sigmoid(x)


def tanh(x):
    return jnp.tanh(x)


def relu(x):
    return jax.nn.relu(x)


def leakyrelu(x, alpha: float = 0.01):
    return jax.nn.leaky_relu(x, negative_slope=alpha)


def elu(x):
    return jax.nn.elu(x)


def softplus(x):
    return jax.nn.softplus(x)


def softsign(x):
    return jax.nn.soft_sign(x)


def hardtanh(x):
    return jnp.clip(x, -1.0, 1.0)


def hardsigmoid(x):
    return jnp.clip(0.2 * x + 0.5, 0.0, 1.0)


def cube(x):
    return x ** 3


def rationaltanh(x):
    # Reference "rationaltanh": 1.7159 * tanh(2x/3) rational approximation.
    return 1.7159 * jnp.tanh(2.0 * x / 3.0)


def softmax(x):
    return jax.nn.softmax(x, axis=-1)


def gelu(x):
    return jax.nn.gelu(x)


def swish(x):
    return jax.nn.silu(x)


_REGISTRY: Dict[str, Callable] = {
    "identity": identity,
    "linear": identity,
    "sigmoid": sigmoid,
    "tanh": tanh,
    "relu": relu,
    "leakyrelu": leakyrelu,
    "elu": elu,
    "softplus": softplus,
    "softsign": softsign,
    "hardtanh": hardtanh,
    "hardsigmoid": hardsigmoid,
    "cube": cube,
    "rationaltanh": rationaltanh,
    "softmax": softmax,
    "gelu": gelu,
    "swish": swish,
    "silu": swish,
}


def get(name: str) -> Callable:
    try:
        return _REGISTRY[name.lower()]
    except KeyError:
        raise ValueError(f"Unknown activation '{name}'. Known: {sorted(_REGISTRY)}")


def register(name: str, fn: Callable) -> None:
    _REGISTRY[name.lower()] = fn
