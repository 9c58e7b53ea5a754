"""The Gated DeltaNet mixer (Yang, Kautz and Hatamizadeh, arXiv:2412.06464;
FLA's ``GatedDeltaNet``), as Olmo-Hybrid carries it: linear attention whose
state is a matrix a head, updated by a gated delta rule.

For a sequence ``u`` [T, n_in], ``H`` heads of key width ``d_k`` and value
width ``d_v``:

1. ``q = u W_q``, ``k = u W_k`` (``H d_k`` wide), ``v = u W_v`` (``H d_v``),
   ``a = u W_a``, ``b = u W_b`` (``H`` each), ``z = u W_g`` (``H d_v``).
2. ``q``, ``k`` and ``v`` each through their own causal depthwise
   convolution of width ``d_conv`` (no bias), then SiLU.
3. ``q`` and ``k`` L2-normalised per head (``x / sqrt(|x|^2 + 1e-6)``), ``q``
   scaled by ``d_k^-1/2``; ``beta = sigmoid(b)``, doubled with
   ``allow_neg_eigval`` (FLA's: the eigenvalue of ``I - beta k k^T`` then
   reaches -1); ``g = -exp(A_log) softplus(a + dt_bias)``, the decay
   ``exp(g)``.
4. Per head, ``S_t = exp(g_t) (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t
   v_t^T`` (``S`` [d_k, d_v]); ``o_t = S_t^T q_t``.
5. ``y = RMSNorm_{d_v}(o) * w * silu(z)`` (``w`` [d_v] shared by the heads,
   eps ``eps``), then ``y W_o``.

Steps 3-4 run in float32 whatever the compute dtype (the state accumulates
over every position served), the small products at ``highest`` precision;
the state is float32 everywhere, the convolution's tail — the last ``d_conv
- 1`` rows of ``[q, k, v]`` ahead of the convolution — in the compute dtype.
The state lies in the SLOT layout ``[H / G, d_k, G d_v]``
(``helpers/delta_rule.py``): whole lanes, nothing padded.

Three carries, one layer, as ``MambaLayer``'s:

- ``carry=None``: the whole sequence from zero state (``apply``).
- a CONTIGUOUS carry ``(S [B, H / G, d_k, G d_v], tail [B, d_conv - 1,
  2 H d_k + H d_v])``: ``initial_carry`` / ``step`` / ``apply_with_carry``.
- a PAGED carry, the generation engine's STATE SLOTS
  (``init_paged_cache``: ``{"sh": [slots + 1, H / G, d_k, G d_v] f32, "sc":
  [slots + 1, d_conv - 1, 2 H d_k + H d_v]}``, row 0 the trash row) with the
  dispatch's ``rows`` or ``lanes``, ``pos`` and, where it has padding,
  ``live``: a row at ``pos`` 0 starts from zero state, positions at or past
  ``live`` move neither state nor tail, and the decode step updates the
  pool in place by ``lanes``.

A chunk or a sequence goes through one seam, ``get_helper("delta_rule")``
(the plain recurrence where it gives way: ``delta_rule_path``).
So does the decode step on the state slots where the seam offers its
kernel (the TPU): one Pallas kernel reads each lane's row once and writes
it once, in place (``delta_rule.step_slots``, the ``delta_kernel`` path).
Every other single-token step — the contiguous carry, another backend — is
plain ``jnp`` on the slot layout (``delta_rule.single_step``).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu import helpers
from deeplearning4j_tpu.helpers import delta_rule as dr
from deeplearning4j_tpu.nn import initializers
from deeplearning4j_tpu.nn.inputs import InputType
from deeplearning4j_tpu.nn.layers.base import Layer, register_layer
from deeplearning4j_tpu.nn.layers.normalization import rms_norm
from deeplearning4j_tpu.nn.layers.state_space import describe_slots

DELTA_RULE_PATHS = ("delta_step", "delta_chunk", "delta_kernel",
                    "delta_stepwise")
# the same four paths of the per-channel decay (KimiDeltaAttentionLayer)
KDA_PATHS = ("kda_step", "kda_chunk", "kda_kernel", "kda_stepwise")
# FLA's init: A uniform in (0, A_INIT_MAX], the step log-uniform in this range
A_INIT_MAX = 16.0
DT_INIT_MIN, DT_INIT_MAX = 1e-3, 1e-1
L2_EPS = 1e-6


def delta_rule_path(t: int, kernel: bool = False, seam: bool = True,
                    paths=DELTA_RULE_PATHS) -> str:
    """Which of ``paths`` (``DELTA_RULE_PATHS``, or ``KDA_PATHS`` for the
    per-channel decay, in that order) a call of ``t`` positions a row on the
    state slots takes: for a single token ``"delta_kernel"`` where the
    helper seam offers its kernel (``kernel``: the pool stepped in place by
    ``delta_rule.step_slots``), else ``"delta_step"`` (the slot layout in
    ``jnp``, no loop); for more, ``"delta_chunk"``, the seam's WY form,
    where the seam is there (``seam``), and ``"delta_stepwise"``, the plain
    recurrence one position a trip, where it gives way.  Pure: the layer
    branches on it while it is traced, the engine calls it on the host to
    count ``dl4j_layer_path_steps_total``."""
    step, chunk, kern, stepwise = paths
    if t == 1:
        return kern if kernel else step
    return chunk if seam else stepwise


def _l2_normalize(x):
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


@register_layer
@dataclasses.dataclass(frozen=True)
class GatedDeltaNetLayer(Layer):
    """The Gated DeltaNet mixer over ``[B, T, F]`` (module docstring)."""

    kind = "recurrent"
    # served by the generation engine through state slots (init_paged_cache)
    holds_state_slots = True
    # the names of its paths, delta_rule_path's order
    PATHS = DELTA_RULE_PATHS

    n_in: Optional[int] = None
    n_out: Optional[int] = None
    activation: str = "silu"
    n_heads: int = 4
    d_k: int = 16
    d_v: int = 32
    d_conv: int = 4
    # beta = 2 sigmoid(b) (FLA's allow_neg_eigval); False: sigmoid(b)
    allow_neg_eigval: bool = False
    # the gated output norm's eps
    eps: float = 1e-6

    def setup(self, input_type: InputType) -> "GatedDeltaNetLayer":
        upd = {}
        if self.n_in is None:
            upd["n_in"] = input_type.size
        if self.n_out is None:
            upd["n_out"] = upd.get("n_in", self.n_in)
        return dataclasses.replace(self, **upd) if upd else self

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.recurrent(self.n_out, input_type.timesteps)

    def validate(self) -> None:
        super().validate()
        if self.activation != "silu":
            raise ValueError("GatedDeltaNetLayer's gate and convolutions are "
                             "silu")
        if min(self.n_heads, self.d_k, self.d_v) < 1 or self.d_conv < 2:
            raise ValueError("GatedDeltaNetLayer needs n_heads, d_k, d_v >= 1 "
                             "and d_conv >= 2")

    @property
    def group(self) -> int:
        """Heads side by side in a row of the slot layout."""
        return dr.slot_group(self.n_heads, self.d_v)

    @property
    def _widths(self):
        """(q, k, v) channels."""
        hk = self.n_heads * self.d_k
        return hk, hk, self.n_heads * self.d_v

    def state_shape(self):
        """One row's state in the slot layout."""
        g = self.group
        return (self.n_heads // g, self.d_k, g * self.d_v)

    def init(self, key, dtype=jnp.float32):
        h, (wq, wk, wv) = self.n_heads, self._widths
        ks = jax.random.split(key, 12)

        def w(k, shape):
            return initializers.init(self.weight_init, k, shape, dtype)

        a = A_INIT_MAX * (1.0 - jax.random.uniform(ks[10], (h,), jnp.float32))
        step = jnp.exp(jax.random.uniform(ks[11], (h,), jnp.float32)
                       * (math.log(DT_INIT_MAX) - math.log(DT_INIT_MIN))
                       + math.log(DT_INIT_MIN))
        return {"W_q": w(ks[0], (self.n_in, wq)),
                "W_k": w(ks[1], (self.n_in, wk)),
                "W_v": w(ks[2], (self.n_in, wv)),
                "W_a": w(ks[3], (self.n_in, h)),
                "W_b": w(ks[4], (self.n_in, h)),
                "W_g": w(ks[5], (self.n_in, wv)),
                "W_o": w(ks[6], (wv, self.n_out)),
                "conv_q": w(ks[7], (wq, self.d_conv)),
                "conv_k": w(ks[8], (wk, self.d_conv)),
                "conv_v": w(ks[9], (wv, self.d_conv)),
                "A_log": jnp.log(a).astype(dtype),
                "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dtype),
                "o_norm": jnp.ones((self.d_v,), dtype)}

    # ------------------------------------------------------------ the parts
    def path(self, t: int) -> str:
        """``delta_rule_path`` of a call of ``t`` positions a row on the
        state slots, as the process stands (the kernel only where the
        helper seam offers it)."""
        helper = helpers.get_helper("delta_rule")
        return delta_rule_path(t, helper is not None and helper.kernel,
                               helper is not None, self.PATHS)

    def _forms(self):
        """The rule's forms: (the decode step in ``jnp`` on the slot layout,
        the plain recurrence, the seam's chunked form and its kernel, by
        the helper's attribute names)."""
        return dr.single_step, dr.stepwise, "chunked", "step_slots"

    def serving_path(self, call) -> str:
        return self.path(call.t)

    def describe_serving(self, call) -> str:
        return describe_slots(
            call, f"delta-rule layers of {self.n_heads} heads x "
            f"[{self.d_k}, {self.d_v}] (rows {list(self.state_shape())})",
            self.serving_path(call), helpers.get_helper("delta_rule"),
            self.n_heads * self.d_k * self.d_v, self.d_conv)

    def _conv(self, params, window):
        """Step 2 on ``window`` [B, d_conv - 1 + T, q + k + v channels] (the
        tail, then the chunk): [B, T, channels] float32."""
        with jax.named_scope("gdn_conv"):
            t = window.shape[1] - (self.d_conv - 1)
            w = jnp.concatenate([params["conv_q"], params["conv_k"],
                                 params["conv_v"]]).astype(jnp.float32)
            win = window.astype(jnp.float32)
            y = sum(win[:, j:j + t] * w[:, j] for j in range(self.d_conv))
            return jax.nn.silu(y)

    def _heads(self, x):
        """``q`` (normalised, scaled), ``k`` (normalised) [B, T, H, d_k] and
        ``v`` [B, T, H, d_v] of the convolved ``x``."""
        bsz, t, _ = x.shape
        wq, wk, _ = self._widths
        q = x[..., :wq].reshape(bsz, t, self.n_heads, self.d_k)
        k = x[..., wq:wq + wk].reshape(bsz, t, self.n_heads, self.d_k)
        v = x[..., wq + wk:].reshape(bsz, t, self.n_heads, self.d_v)
        return _l2_normalize(q) * self.d_k ** -0.5, _l2_normalize(k), v

    def _rule(self, params, x, a, b):
        """Step 3 on the convolved ``x`` [B, T, channels] and ``a``, ``b``
        [B, T, H]: ``(q, k [B, T, H, d_k], v [B, T, H, d_v], g, beta [B, T,
        H])``, float32."""
        f32 = jnp.float32
        q, k, v = self._heads(x)
        beta = jax.nn.sigmoid(b.astype(f32))
        if self.allow_neg_eigval:
            beta = 2.0 * beta
        g = -jnp.exp(params["A_log"].astype(f32)) * jax.nn.softplus(
            a.astype(f32) + params["dt_bias"].astype(f32))
        return q, k, v, g, beta

    def _out(self, params, o, z):
        """Step 5: ``o`` [B, T, H, d_v] float32, ``z`` the gate's input in
        the compute dtype."""
        with jax.named_scope("gdn_proj"):
            bsz, t = o.shape[:2]
            o = rms_norm(o, params["o_norm"], self.eps)
            y = o.reshape(bsz, t, -1) * jax.nn.silu(z.astype(jnp.float32))
            return y.astype(z.dtype) @ params["W_o"]

    def _sequence(self, params, u, s0, tail, live=None, step=None):
        """A chunk ``u`` [B, T, F] from state ``s0`` (slot layout) and
        ``tail`` [B, d_conv - 1, channels]: ``(out, S, tail')``; positions
        at or past ``live`` [B] move neither.  ``step``: a single token's
        rule ``(q, k, v, g, beta) -> (o, S)`` in place of ``single_step``
        from ``s0``."""
        k_rows = self.d_conv - 1
        t = u.shape[1]
        with jax.named_scope("gdn_proj"):
            qkv = jnp.concatenate([u @ params["W_q"], u @ params["W_k"],
                                   u @ params["W_v"]], axis=-1)
            a, b = u @ params["W_a"], u @ params["W_b"]
            z = u @ params["W_g"]
        window = jnp.concatenate([tail.astype(qkv.dtype), qkv], axis=1)
        x = self._conv(params, window)
        single, plain, chunked, _ = self._forms()
        with jax.named_scope("gdn_state" if t == 1 else "gdn_chunk"):
            q, k, v, g, beta = self._rule(params, x, a, b)
            if t == 1:
                g, beta = dr.mask_padding(g, beta, live)
                one = (q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
                o, s = (step(*one) if step is not None
                        else single(*one, s0))
                o = o[:, None]
            else:
                rule = (getattr(helpers.get_helper("delta_rule"), chunked)
                        if self.path(t) == self.PATHS[1] else plain)
                o, s = rule(q, k, v, g, beta, dr.to_heads(s0, self.n_heads),
                            live)
                s = dr.to_slots(s, self.group)
        with jax.named_scope("gdn_conv"):
            # the last d_conv - 1 rows ahead of the convolution, of the REAL
            # tokens: window rows [live, live + k)
            if live is None:
                new_tail = window[:, t:]
            else:
                new_tail = jax.vmap(
                    lambda w, at: lax.dynamic_slice_in_dim(w, at, k_rows,
                                                           axis=0)
                )(window, live.astype(jnp.int32))
        return self._out(params, o, z), s, new_tail.astype(tail.dtype)

    # ------------------------------------------------------------- forward
    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        y, st, _ = self.apply_with_carry(params, state, x, None, train=train,
                                         rng=rng, mask=mask)
        return y, st

    def initial_carry(self, batch: int, dtype=jnp.float32):
        return (jnp.zeros((batch,) + self.state_shape(), jnp.float32),
                jnp.zeros((batch, self.d_conv - 1, sum(self._widths)), dtype))

    def step(self, params, carry, x_t):
        """One timestep: ``x_t`` [B, n_in] -> (y [B, n_out], new carry)."""
        out, s, tail = self._sequence(params, x_t[:, None], *carry)
        return out[:, 0], (s, tail)

    def init_paged_cache(self, num_pages: int, page_size: int,
                         dtype=jnp.float32, window_pages=None,
                         state_slots: Optional[int] = None
                         ) -> Dict[str, jax.Array]:
        """The layer's STATE SLOTS (module docstring): one row a slot and
        the trash row, not pages — ``num_pages``, ``page_size`` and
        ``window_pages`` size the other kinds of pool."""
        if state_slots is None:
            raise ValueError(
                "a delta-rule layer keeps one row of state a slot: "
                "init_paged_cache needs state_slots, the engine's slot count")
        rows = int(state_slots) + 1
        return {"sh": jnp.zeros((rows,) + self.state_shape(), jnp.float32),
                "sc": jnp.zeros((rows, self.d_conv - 1, sum(self._widths)),
                                dtype)}

    def apply_with_carry(self, params, state, x, carry, *, train=False,
                         rng=None, mask=None):
        if mask is not None:
            raise ValueError("GatedDeltaNetLayer takes no padding mask")
        x = self.maybe_dropout(x, train=train, rng=rng)
        if carry is None:
            carry = self.initial_carry(x.shape[0], x.dtype)
        if not isinstance(carry, dict):
            out, s, tail = self._sequence(params, x, *carry)
            return out, state, (s, tail)
        return self._apply_slots(params, state, x, carry)

    def _apply_slots(self, params, state, x, carry):
        """The paged carry (module docstring), as ``MambaLayer``'s: a
        prefill's ``rows`` are gathered and scattered; the decode step
        steps the whole pool past the trash row where it lies, an idle
        lane's row kept as it was — on the ``delta_kernel`` path in one
        kernel that reads and writes each row once."""
        sh, sc, lanes = carry["sh"], carry["sc"], carry.get("lanes")
        fresh = carry["pos"] == 0
        rows = carry.get("rows")
        scope = "gdn_state" if x.shape[1] == 1 else "gdn_chunk"
        step = s0 = None
        if rows is None and self.path(x.shape[1]) == self.PATHS[2]:
            kernel = getattr(helpers.get_helper("delta_rule"),
                             self._forms()[3])

            def step(*one):
                return kernel(*one, sh, fresh, lanes)
        else:
            with jax.named_scope(scope):
                s_was = sh[1:] if rows is None else sh[rows]
                s0 = jnp.where(fresh[:, None, None, None], 0.0,
                               s_was).astype(jnp.float32)
        with jax.named_scope("gdn_conv"):
            tail_was = sc[1:] if rows is None else sc[rows]
            tail = jnp.where(fresh[:, None, None], jnp.zeros((), sc.dtype),
                             tail_was)
        out, s, tail = self._sequence(params, x, s0, tail, carry.get("live"),
                                      step)
        with jax.named_scope(scope):
            if step is not None:
                sh = s
            elif rows is None:
                sh = sh.at[1:].set(jnp.where(lanes[:, None, None, None], s,
                                             s_was))
            else:
                sh = sh.at[rows].set(s)
        with jax.named_scope("gdn_conv"):
            sc = (sc.at[1:].set(jnp.where(lanes[:, None, None], tail,
                                          tail_was))
                  if rows is None else sc.at[rows].set(tail))
        return out, state, {**carry, "sh": sh, "sc": sc}


@register_layer
@dataclasses.dataclass(frozen=True)
class KimiDeltaAttentionLayer(GatedDeltaNetLayer):
    """Kimi Delta Attention (KDA; Kimi Linear, arXiv:2510.26692, FLA's
    ``KimiDeltaAttention``) as Ling-3.0 carries it: ``GatedDeltaNetLayer``'s
    projections, convolutions, state slots, scopes and carries, with a
    decay a ``d_k`` channel and a gate a head.

    In place of steps 3-5 of the module docstring:

    - ``beta = sigmoid(u W_b)`` [H]; the log decay per channel, the SAFE
      GATE ``g = lower_bound * sigmoid(exp(A_log_h) (u W_a + dt_bias))`` in
      ``(lower_bound, 0)^{d_k}`` (``W_a`` [n_in, H d_k] full rank,
      ``dt_bias`` [H d_k], ``A_log`` [H]).
    - per head, ``S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} +
      beta_t k_t v_t^T``; ``o_t = S_t^T q_t`` (``helpers/delta_rule.py``'s
      ``kda_`` forms, paths ``KDA_PATHS``).
    - ``y_h = RMSNorm_{d_v}(o_h) w_h sigmoid(u W_g)_h``: a norm over each
      head's ``d_v`` with a gain of its own (``o_norm`` [H d_v]) and one
      gate a head (``W_g`` [n_in, H]); then ``y W_o``.

    The state's slot layout, tail and pools are the parent's."""

    # the per-channel forms' sub-blocks hold exp(KDA_SUB * -lower_bound)
    lower_bound: float = -5.0

    PATHS = KDA_PATHS

    def validate(self) -> None:
        super().validate()
        if self.allow_neg_eigval:
            raise ValueError("KimiDeltaAttentionLayer's beta is sigmoid(b)")
        if not 0 < -self.lower_bound * dr.KDA_SUB < 88:
            raise ValueError(
                f"lower_bound={self.lower_bound}: the chunked form needs "
                f"exp({-dr.KDA_SUB} * lower_bound) inside float32")

    def init(self, key, dtype=jnp.float32):
        h, hk = self.n_heads, self.n_heads * self.d_k
        p = super().init(key, dtype)
        ks = jax.random.split(jax.random.fold_in(key, 1), 4)

        def w(k, shape):
            return initializers.init(self.weight_init, k, shape, dtype)

        step = jnp.exp(jax.random.uniform(ks[3], (hk,), jnp.float32)
                       * (math.log(DT_INIT_MAX) - math.log(DT_INIT_MIN))
                       + math.log(DT_INIT_MIN))
        p.update({"W_a": w(ks[0], (self.n_in, hk)),
                  "W_g": w(ks[1], (self.n_in, h)),
                  "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dtype),
                  "o_norm": jnp.ones((h * self.d_v,), dtype)})
        return p

    def _forms(self):
        return (dr.kda_single_step, dr.kda_stepwise, "kda_chunked",
                "kda_step_slots")

    def _rule(self, params, x, a, b):
        """Step 3 with the safe gate: ``g`` [B, T, H, d_k]."""
        f32 = jnp.float32
        bsz, t, _ = x.shape
        q, k, v = self._heads(x)
        beta = jax.nn.sigmoid(b.astype(f32))
        arg = (a.astype(f32).reshape(bsz, t, self.n_heads, self.d_k)
               + params["dt_bias"].astype(f32).reshape(self.n_heads,
                                                       self.d_k))
        g = self.lower_bound * jax.nn.sigmoid(
            jnp.exp(params["A_log"].astype(f32))[:, None] * arg)
        return q, k, v, g, beta

    def _out(self, params, o, z):
        """The norm a head and the gate a head; ``z`` [B, T, H]."""
        with jax.named_scope("gdn_proj"):
            bsz, t = o.shape[:2]
            o = rms_norm(o, params["o_norm"].reshape(self.n_heads, self.d_v),
                         self.eps)
            y = o * jax.nn.sigmoid(z.astype(jnp.float32))[..., None]
            return y.reshape(bsz, t, -1).astype(z.dtype) @ params["W_o"]
