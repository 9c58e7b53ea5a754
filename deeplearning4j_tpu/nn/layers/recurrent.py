"""Recurrent layers: Graves LSTM (with peepholes), bidirectional variant,
plain LSTM, and the RNN output head.

Reference: ``nn/layers/recurrent/LSTMHelpers.java:144-181`` — per-timestep
Java loop doing one gemm + gate slicing per step, peephole connections on
input/forget/output gates; ``GravesBidirectionalLSTM.java:218`` sums the two
directions.  TPU-native redesign: the input projection for ALL timesteps is
one big [B*T, n_in] x [n_in, 4H] matmul (MXU-friendly), then a ``lax.scan``
carries (h, c) with only the [B, H] x [H, 4H] recurrent matmul inside the
loop — static shapes, no per-step Python.

Sequence layout is [batch, time, features] (reference: [batch, features, time]).
Masking: per reference semantics, masked steps freeze the carried state and
zero the emitted activation (``GradientCheckTestsMasking`` contract).
Streaming inference (reference ``rnnTimeStep``/``stateMap``,
``BaseRecurrentLayer.java``) is the pure ``step`` method — the model facade
owns the state pytree.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.nn import activations, initializers, losses
from deeplearning4j_tpu.nn.inputs import InputType
from deeplearning4j_tpu.nn.layers.base import Layer, register_layer
from deeplearning4j_tpu.nn.layers.dense import OutputLayer

# Gate block order inside the fused 4H dimension: input, forget, cell(g), output.
_I, _F, _G, _O = 0, 1, 2, 3


def _lstm_init(key, n_in, n_out, weight_init, dist, peephole, dtype, prefix=""):
    from deeplearning4j_tpu.nn.initializers import distribution_from_dict

    k1, k2, k3 = jax.random.split(key, 3)
    d = distribution_from_dict(dist)
    p = {
        prefix + "W": initializers.init(weight_init, k1, (n_in, 4 * n_out), dtype,
                                        fan_in=n_in, fan_out=n_out, distribution=d),
        prefix + "RW": initializers.init(weight_init, k2, (n_out, 4 * n_out), dtype,
                                         fan_in=n_out, fan_out=n_out, distribution=d),
        # forget-gate bias init (reference forgetGateBiasInit, default 1.0)
        prefix + "b": jnp.zeros((4 * n_out,), dtype).at[n_out : 2 * n_out].set(1.0),
    }
    if peephole:
        pk = jax.random.split(k3, 3)
        for i, gate in enumerate(("pI", "pF", "pO")):
            p[prefix + gate] = initializers.init(
                weight_init, pk[i], (n_out,), dtype, fan_in=n_out, fan_out=n_out, distribution=d
            )
    return p


def _cell_step(params, act_fn, gate_act, peephole, h_prev, c_prev, xproj_t, prefix=""):
    """One LSTM cell step given the precomputed input projection for step t."""
    H = h_prev.shape[-1]
    z = xproj_t + h_prev @ params[prefix + "RW"]  # [B, 4H]
    zi, zf, zg, zo = (z[..., i * H : (i + 1) * H] for i in range(4))
    if peephole:
        zi = zi + c_prev * params[prefix + "pI"]
        zf = zf + c_prev * params[prefix + "pF"]
    i_g = gate_act(zi)
    f_g = gate_act(zf)
    g = act_fn(zg)
    c = f_g * c_prev + i_g * g
    if peephole:
        zo = zo + c * params[prefix + "pO"]
    o_g = gate_act(zo)
    h = o_g * act_fn(c)
    return h, c


def _scan_lstm(params, act_fn, gate_act, peephole, x, mask, reverse=False,
               h0=None, c0=None, prefix=""):
    """Scan over [B, T, n_in] -> [B, T, H] with state freezing on masked steps."""
    B, T, _ = x.shape
    H = params[prefix + "RW"].shape[0]
    xproj = x.reshape(B * T, -1) @ params[prefix + "W"] + params[prefix + "b"]
    xproj = xproj.reshape(B, T, 4 * H)
    h0 = jnp.zeros((B, H), x.dtype) if h0 is None else h0
    c0 = jnp.zeros((B, H), x.dtype) if c0 is None else c0

    def body(carry, inp):
        h_prev, c_prev = carry
        xp_t, m_t = inp
        h, c = _cell_step(params, act_fn, gate_act, peephole, h_prev, c_prev, xp_t, prefix)
        if m_t is not None:
            m = m_t[:, None]
            h = jnp.where(m > 0, h, h_prev)
            c = jnp.where(m > 0, c, c_prev)
            out = h * m
        else:
            out = h
        return (h, c), out

    xs = (jnp.swapaxes(xproj, 0, 1), jnp.swapaxes(mask, 0, 1) if mask is not None else None)
    if mask is None:
        xs = (xs[0], jnp.ones((T, B), x.dtype))

        def body2(carry, inp):
            return body(carry, (inp[0], None))

        (hT, cT), ys = lax.scan(body2, (h0, c0), xs, reverse=reverse)
    else:
        (hT, cT), ys = lax.scan(body, (h0, c0), xs, reverse=reverse)
    return jnp.swapaxes(ys, 0, 1), (hT, cT)


@register_layer
@dataclasses.dataclass(frozen=True)
class GravesLSTM(Layer):
    """Graves-style LSTM with peephole connections
    (reference ``nn/layers/recurrent/GravesLSTM.java:38``)."""

    kind = "recurrent"
    # served by the generation engine through state slots (init_paged_cache)
    holds_state_slots = True

    n_in: Optional[int] = None
    n_out: Optional[int] = None
    activation: str = "tanh"
    gate_activation: str = "sigmoid"
    peephole: bool = True

    def setup(self, input_type: InputType) -> "GravesLSTM":
        if self.n_in is None:
            return dataclasses.replace(self, n_in=input_type.size)
        return self

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.recurrent(self.n_out, input_type.timesteps)

    def init(self, key, dtype=jnp.float32):
        return _lstm_init(key, self.n_in, self.n_out, self.weight_init, self.dist,
                          self.peephole, dtype)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        y, _st, _carry = self.apply_with_carry(params, state, x, None,
                                               train=train, rng=rng, mask=mask)
        return y, _st

    def apply_with_carry(self, params, state, x, carry, *, train=False, rng=None, mask=None):
        """Sequence forward exposing the final (h, c) carry — the functional
        form of the reference's TBPTT state plumbing
        (``MultiLayerNetwork.java:1176`` rnnActivateUsingStoredState)."""
        x = self.maybe_dropout(x, train=train, rng=rng)
        slots = carry if isinstance(carry, dict) else None
        if slots is not None:
            # the engine's state slots (``MambaLayer``'s contract): a row
            # at position 0 starts from zero, padding past ``live`` freezes
            # the state as a masked step does
            rows = slots.get("rows")
            if rows is None:     # the decode step: lane i owns row i + 1
                rows = jnp.where(slots["lanes"],
                                 jnp.arange(1, x.shape[0] + 1), 0)
            fresh = (slots["pos"] == 0)[:, None]
            carry = tuple(jnp.where(fresh, 0.0, slots[k][rows]).astype(x.dtype)
                          for k in ("sh", "sc"))
            if "live" in slots:
                mask = (jnp.arange(x.shape[1])[None]
                        < slots["live"][:, None]).astype(x.dtype)
        h0, c0 = carry if carry is not None else (None, None)
        ys, (hT, cT) = _scan_lstm(
            params, activations.get(self.activation),
            activations.get(self.gate_activation), self.peephole, x, mask,
            h0=h0, c0=c0,
        )
        if slots is not None:
            return ys, state, {
                **slots, "sh": slots["sh"].at[rows].set(hT.astype(
                    slots["sh"].dtype)),
                "sc": slots["sc"].at[rows].set(cT.astype(slots["sc"].dtype))}
        return ys, state, (hT, cT)

    def init_paged_cache(self, num_pages: int, page_size: int,
                         dtype=jnp.float32, window_pages=None,
                         state_slots: Optional[int] = None):
        """The layer's STATE SLOTS for the generation engine: ``sh`` / ``sc``
        [slots + 1, n_out], a slot's ``(h, c)`` and the trash row (row 0)."""
        if state_slots is None:
            raise ValueError(
                "a recurrent layer keeps one row of state a slot: "
                "init_paged_cache needs state_slots, the engine's slot count")
        shape = (int(state_slots) + 1, self.n_out)
        return {"sh": jnp.zeros(shape, dtype), "sc": jnp.zeros(shape, dtype)}

    # -- streaming inference (reference rnnTimeStep / stateMap) ------------
    def initial_carry(self, batch: int, dtype=jnp.float32):
        return (jnp.zeros((batch, self.n_out), dtype), jnp.zeros((batch, self.n_out), dtype))

    def step(self, params, carry, x_t):
        """One timestep: x_t [B, n_in] -> (y [B, H], new_carry)."""
        h_prev, c_prev = carry
        xproj = x_t @ params["W"] + params["b"]
        h, c = _cell_step(
            params, activations.get(self.activation),
            activations.get(self.gate_activation), self.peephole, h_prev, c_prev, xproj,
        )
        return h, (h, c)


@register_layer
@dataclasses.dataclass(frozen=True)
class LSTM(GravesLSTM):
    """Standard LSTM without peepholes (XLA fuses gates into two matmuls per
    step; the fast default for new models)."""

    peephole: bool = False


@register_layer
@dataclasses.dataclass(frozen=True)
class GravesBidirectionalLSTM(Layer):
    """Bidirectional Graves LSTM; directions are summed
    (reference ``GravesBidirectionalLSTM.java:218`` ``fwdOutput.addi(backOutput)``)."""

    kind = "recurrent"

    n_in: Optional[int] = None
    n_out: Optional[int] = None
    activation: str = "tanh"
    gate_activation: str = "sigmoid"
    peephole: bool = True

    def setup(self, input_type: InputType) -> "GravesBidirectionalLSTM":
        if self.n_in is None:
            return dataclasses.replace(self, n_in=input_type.size)
        return self

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.recurrent(self.n_out, input_type.timesteps)

    def init(self, key, dtype=jnp.float32):
        kf, kb = jax.random.split(key)
        p = _lstm_init(kf, self.n_in, self.n_out, self.weight_init, self.dist,
                       self.peephole, dtype, prefix="f_")
        p.update(_lstm_init(kb, self.n_in, self.n_out, self.weight_init, self.dist,
                            self.peephole, dtype, prefix="b_"))
        return p

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        x = self.maybe_dropout(x, train=train, rng=rng)
        act = activations.get(self.activation)
        gact = activations.get(self.gate_activation)
        fwd, _ = _scan_lstm(params, act, gact, self.peephole, x, mask, prefix="f_")
        bwd, _ = _scan_lstm(params, act, gact, self.peephole, x, mask, reverse=True, prefix="b_")
        return fwd + bwd, state


@register_layer
@dataclasses.dataclass(frozen=True)
class RnnOutputLayer(OutputLayer):
    """Per-timestep dense + loss head (reference ``RnnOutputLayer.java``).
    Input [B, T, n_in] -> [B, T, n_out]; loss masks over [B, T]."""

    def setup(self, input_type: InputType) -> "RnnOutputLayer":
        if self.n_in is None:
            return dataclasses.replace(self, n_in=input_type.size)
        return self

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.recurrent(self.n_out, input_type.timesteps)
