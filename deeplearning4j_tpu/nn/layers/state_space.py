"""State-space layers: the Mamba-1 mixer (Gu & Dao, arXiv:2312.00752) as
Jamba carries it (Lieber et al., arXiv:2403.19887; HF ``JambaMambaMixer``).

For a sequence ``u`` [T, n_in], ``d = expand * n_in`` channels, ``N``
state columns, ``R`` = ``dt_rank``:

1. ``[x, z] = u W_in`` (each [T, d]).
2. ``x = silu(conv(x) + b_conv)``: causal depthwise convolution of width
   ``d_conv`` (``x_t`` from ``x_{t-d_conv+1..t}``).
3. ``[dt_r, B, C] = x W_x`` (R, N, N); with ``inner_norms`` (Jamba's own
   step) each through an RMSNorm with a gain.
4. ``dt = softplus(dt_r W_dt + b_dt)``; ``A = -exp(A_log)``.
5. ``h_t = exp(dt_t (x) A) * h_{t-1} + (dt_t * x_t) (x) B_t``;
   ``y_t = h_t C_t + D * x_t``.
6. ``out = (y * silu(z)) W_out``.

Steps 3-5 run in float32 whatever the compute dtype (the recurrence
accumulates over every position served), the two small products at
``highest`` precision; the state ``h`` is float32 everywhere, the
convolution's tail — the last ``d_conv - 1`` rows of ``x`` ahead of the
convolution — in the compute dtype.  The state lies ``[N, d]``, channels on
the minor axis (``helpers/selective_scan.py``).

Three carries, one layer:

- ``carry=None``: the whole sequence from zero state (``apply``).
- a CONTIGUOUS carry ``(h [B, N, d], tail [B, d_conv - 1, d])``, as
  ``GravesLSTM`` carries ``(h, c)``: ``initial_carry`` / ``step`` /
  ``apply_with_carry``, what ``rnn_time_step`` and
  ``models.decode.generate`` thread.
- a PAGED carry, the generation engine's STATE SLOTS
  (``init_paged_cache``: ``{"sh": [slots + 1, N, d] f32, "sc": [slots + 1,
  d_conv - 1, d]}``, row 0 the trash row) with, beside them, the
  dispatch's ``rows`` [B] (a prefill's row, ``slot + 1``) or ``lanes`` [B]
  bool (the decode step: lane ``i`` owns row ``i + 1`` and moves it only
  where its lane runs a request), ``pos`` [B] and, where the dispatch has
  padding, ``live`` [B].  A row whose ``pos`` is 0 starts from ZERO state
  whatever its pool row holds (admission resets by construction); positions
  at or past ``live`` leave the state and the tail untouched; the rows are
  read and written in place.

The scan over a chunk or a sequence goes through one seam,
``get_helper("selective_scan")``, and where the seam gives way through the
plain scan of one time step a trip (``state_space_path``); the
single-token step is plain ``jnp``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu import helpers
from deeplearning4j_tpu.helpers import selective_scan as ss
from deeplearning4j_tpu.nn import initializers
from deeplearning4j_tpu.nn.inputs import InputType
from deeplearning4j_tpu.nn.layers.base import Layer, register_layer

# how a call runs the recurrence (``state_space_path``)
STATE_SPACE_PATHS = ("step", "scan", "stepwise")
# the DSL's own init of the step size (Mamba's): log-uniform in this range
DT_INIT_MIN, DT_INIT_MAX = 1e-3, 1e-1
_HIGHEST = lax.Precision.HIGHEST


def state_space_path(t: int, seam: bool = True) -> str:
    """Which of ``STATE_SPACE_PATHS`` a call of ``t`` positions a row takes:
    ``"step"`` for a single token (one pass over the rows' states, no loop),
    else ``"scan"``, the helper seam's chunked scan, where the seam offers
    it (``seam``), and ``"stepwise"``, the plain scan of one time step a
    trip, where it gives way.  Pure: the layer branches on it while it is
    traced, the engine calls it on the host to count
    ``dl4j_layer_path_steps_total``."""
    if t == 1:
        return "step"
    return "scan" if seam else "stepwise"


def describe_slots(call, label: str, path: str, helper, entries: int,
                   d_conv: int) -> str:
    """The warm-up line of a layer that keeps state slots: how ``call``'s
    program runs its recurrence (``helper.describe`` for the seam's
    chunked form) and what a slot's state weighs."""
    t = call.t
    how = ("one pass over the rows' states" if t == 1
           else helper.describe(t) if helper is not None
           else f"lax scan, {t} trips of 1 time step")
    return (f"{label} ({path}): {how}; {call.slots} state slots + the trash "
            f"row, {entries * 4 / 1e3:.1f} kB of float32 state and a tail "
            f"of {d_conv - 1} rows a slot a layer")


def _gain_norm(x, g, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


@register_layer
@dataclasses.dataclass(frozen=True)
class MambaLayer(Layer):
    """The Mamba-1 mixer over ``[B, T, F]`` (module docstring)."""

    kind = "recurrent"
    # served by the generation engine through state slots (init_paged_cache)
    holds_state_slots = True

    n_in: Optional[int] = None
    n_out: Optional[int] = None
    activation: str = "silu"
    expand: int = 2
    d_state: int = 16
    d_conv: int = 4
    # None: ceil(n_in / 16), Mamba's "auto"
    dt_rank: Optional[int] = None
    conv_bias: bool = True
    # RMSNorms with a gain on dt_r, B and C (Jamba); False: plain Mamba-1
    inner_norms: bool = True
    eps: float = 1e-6

    def setup(self, input_type: InputType) -> "MambaLayer":
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
            raise ValueError("MambaLayer's gate and convolution are silu")
        if self.expand < 1 or self.d_state < 1 or self.d_conv < 2:
            raise ValueError("MambaLayer needs expand >= 1, d_state >= 1, "
                             "d_conv >= 2")

    @property
    def d_inner(self) -> int:
        return self.expand * self.n_in

    @property
    def _rank(self) -> int:
        return (math.ceil(self.n_in / 16) if self.dt_rank is None
                else self.dt_rank)

    def init(self, key, dtype=jnp.float32):
        d, n, r = self.d_inner, self.d_state, self._rank
        ks = jax.random.split(key, 6)

        def w(k, shape):
            return initializers.init(self.weight_init, k, shape, dtype)

        # S4D-real A, D = 1, the step's bias the inverse softplus of a step
        # drawn log-uniform in [DT_INIT_MIN, DT_INIT_MAX] (Mamba's init)
        step = jnp.exp(jax.random.uniform(ks[5], (d,), jnp.float32)
                       * (math.log(DT_INIT_MAX) - math.log(DT_INIT_MIN))
                       + math.log(DT_INIT_MIN))
        p = {"W_in": w(ks[0], (self.n_in, 2 * d)),
             "conv_W": w(ks[1], (d, self.d_conv)),
             "W_x": w(ks[2], (d, r + 2 * n)),
             "W_dt": w(ks[3], (r, d)),
             "b_dt": (step + jnp.log(-jnp.expm1(-step))).astype(dtype),
             "A_log": jnp.log(jnp.broadcast_to(
                 jnp.arange(1, n + 1, dtype=jnp.float32), (d, n))
             ).astype(dtype),
             "D": jnp.ones((d,), dtype),
             "W_out": w(ks[4], (d, self.n_out))}
        if self.conv_bias:
            p["conv_b"] = jnp.zeros((d,), dtype)
        if self.inner_norms:
            p.update(dt_norm=jnp.ones((r,), dtype), b_norm=jnp.ones((n,), dtype),
                     c_norm=jnp.ones((n,), dtype))
        return p

    # ------------------------------------------------------------ the parts
    def path(self, t: int) -> str:
        """``state_space_path`` of a call of ``t`` positions a row on this
        layer as the process stands."""
        return state_space_path(
            t, helpers.get_helper("selective_scan") is not None)

    def serving_path(self, call) -> str:
        return self.path(call.t)

    def describe_serving(self, call) -> str:
        return describe_slots(
            call, f"state-space layers of {self.d_inner} channels x "
            f"{self.d_state} state columns", self.serving_path(call),
            helpers.get_helper("selective_scan"),
            self.d_inner * self.d_state, self.d_conv)

    def _in(self, params, u):
        """Step 1: ``u`` [B, T, F] -> ``x``, ``z`` [B, T, d]."""
        with jax.named_scope("ssm_proj"):
            xz = u @ params["W_in"]
        return xz[..., :self.d_inner], xz[..., self.d_inner:]

    def _conv(self, params, window):
        """Step 2 on ``window`` [B, d_conv - 1 + T, d] (the tail, then the
        chunk): [B, T, d] float32."""
        with jax.named_scope("ssm_conv"):
            t = window.shape[1] - (self.d_conv - 1)
            w = params["conv_W"].astype(jnp.float32)          # [d, d_conv]
            win = window.astype(jnp.float32)
            y = sum(win[:, k:k + t] * w[:, k] for k in range(self.d_conv))
            if self.conv_bias:
                y = y + params["conv_b"].astype(jnp.float32)
            return jax.nn.silu(y)

    def _selection(self, params, x):
        """Steps 3-4 on ``x`` [..., d] float32: ``(dt [..., d], B [..., N],
        C [..., N], A [N, d])``, float32."""
        r, n = self._rank, self.d_state
        with jax.named_scope("ssm_proj"):
            f32 = jnp.float32
            sel = jnp.matmul(x, params["W_x"].astype(f32),
                             precision=_HIGHEST)
            dt_r, b, c = sel[..., :r], sel[..., r:r + n], sel[..., r + n:]
            if self.inner_norms:
                dt_r = _gain_norm(dt_r, params["dt_norm"].astype(f32),
                                  self.eps)
                b = _gain_norm(b, params["b_norm"].astype(f32), self.eps)
                c = _gain_norm(c, params["c_norm"].astype(f32), self.eps)
            dt = jax.nn.softplus(
                jnp.matmul(dt_r, params["W_dt"].astype(f32),
                           precision=_HIGHEST) + params["b_dt"].astype(f32))
            a = -jnp.exp(params["A_log"].astype(f32)).T
        return dt, b, c, a

    def _out(self, params, y, x, z):
        """Step 6: the skip ``D x``, the gate and ``W_out``; ``y``, ``x``
        float32, ``z`` in the compute dtype."""
        with jax.named_scope("ssm_proj"):
            y = y + params["D"].astype(jnp.float32) * x
            y = y * jax.nn.silu(z.astype(jnp.float32))
            return y.astype(z.dtype) @ params["W_out"]

    def _sequence(self, params, u, h0, tail, live=None):
        """A chunk ``u`` [B, T, F] from state ``h0`` [B, N, d] f32 and
        ``tail`` [B, d_conv - 1, d]: ``(out, h, tail')``; positions at or
        past ``live`` [B] move neither."""
        k = self.d_conv - 1
        t = u.shape[1]
        x_in, z = self._in(params, u)
        window = jnp.concatenate([tail.astype(x_in.dtype), x_in], axis=1)
        x = self._conv(params, window)
        dt, b, c, a = self._selection(params, x)
        path = self.path(t)
        with jax.named_scope("ssm_scan"):
            if path == "step":
                y, h = ss.single_step(x[:, 0], dt[:, 0], a, b[:, 0], c[:, 0],
                                      h0)
                y = y[:, None]
            else:
                scan = (helpers.get_helper("selective_scan").scan
                        if path == "scan" else ss.stepwise_scan)
                y, h = scan(x, dt, a, b, c, h0, live)
        with jax.named_scope("ssm_conv"):
            # the last d_conv - 1 rows ahead of the convolution, of the REAL
            # tokens: window rows [live, live + k)
            if live is None:
                new_tail = window[:, t:]
            else:
                new_tail = jax.vmap(
                    lambda w, at: lax.dynamic_slice_in_dim(w, at, k, axis=0)
                )(window, live.astype(jnp.int32))
        return self._out(params, y, x, z), h, new_tail.astype(tail.dtype)

    # ------------------------------------------------------------- forward
    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        y, st, _ = self.apply_with_carry(params, state, x, None, train=train,
                                         rng=rng, mask=mask)
        return y, st

    def initial_carry(self, batch: int, dtype=jnp.float32):
        return (jnp.zeros((batch, self.d_state, self.d_inner), jnp.float32),
                jnp.zeros((batch, self.d_conv - 1, self.d_inner), dtype))

    def step(self, params, carry, x_t):
        """One timestep: ``x_t`` [B, n_in] -> (y [B, n_out], new carry)."""
        out, h, tail = self._sequence(params, x_t[:, None], *carry)
        return out[:, 0], (h, tail)

    def init_paged_cache(self, num_pages: int, page_size: int,
                         dtype=jnp.float32, window_pages=None,
                         state_slots: Optional[int] = None
                         ) -> Dict[str, jax.Array]:
        """The layer's STATE SLOTS (module docstring): one row a slot and
        the trash row, not pages — ``num_pages``, ``page_size`` and
        ``window_pages`` size the other kinds of pool."""
        if state_slots is None:
            raise ValueError(
                "a state-space layer keeps one row of state a slot: "
                "init_paged_cache needs state_slots, the engine's slot count")
        rows = int(state_slots) + 1
        return {"sh": jnp.zeros((rows, self.d_state, self.d_inner),
                                jnp.float32),
                "sc": jnp.zeros((rows, self.d_conv - 1, self.d_inner), dtype)}

    def apply_with_carry(self, params, state, x, carry, *, train=False,
                         rng=None, mask=None):
        if mask is not None:
            raise ValueError("MambaLayer takes no padding mask")
        x = self.maybe_dropout(x, train=train, rng=rng)
        if carry is None:
            carry = self.initial_carry(x.shape[0], x.dtype)
        if not isinstance(carry, dict):
            out, h, tail = self._sequence(params, x, *carry)
            return out, state, (h, tail)
        return self._apply_slots(params, state, x, carry)

    def _apply_slots(self, params, state, x, carry):
        """The paged carry (module docstring).  A prefill names its ``rows``
        and the rows are gathered and scattered; the decode step names the
        ``lanes`` that run a request, lane ``i`` owning row ``i + 1``, and
        the whole pool past the trash row is stepped where it lies — one
        pass, no gather, an idle lane's row kept as it was."""
        sh, sc, lanes = carry["sh"], carry["sc"], carry.get("lanes")
        fresh = (carry["pos"] == 0)[:, None, None]
        rows = carry.get("rows")
        with jax.named_scope("ssm_scan"):
            h_was = sh[1:] if rows is None else sh[rows]
            h0 = jnp.where(fresh, 0.0, h_was)
        with jax.named_scope("ssm_conv"):
            tail_was = sc[1:] if rows is None else sc[rows]
            tail = jnp.where(fresh, jnp.zeros((), sc.dtype), tail_was)
        out, h, tail = self._sequence(params, x, h0, tail, carry.get("live"))
        with jax.named_scope("ssm_scan"):
            sh = (sh.at[1:].set(jnp.where(lanes[:, None, None], h, h_was))
                  if rows is None else sh.at[rows].set(h))
        with jax.named_scope("ssm_conv"):
            sc = (sc.at[1:].set(jnp.where(lanes[:, None, None], tail,
                                          tail_was))
                  if rows is None else sc.at[rows].set(tail))
        return out, state, {**carry, "sh": sh, "sc": sc}
