"""Composite layers: ResidualBlock (sequential sublayers + skip connection)
and HyperConnectionBlock (the same sublayers inside manifold-constrained
hyper-connections over several residual streams), with the two ends of a
stack of the latter, HyperStreamExpand and HyperStreamReduce.

The reference expresses residual topology only through the ComputationGraph
ElementWiseVertex DAG (``nn/graph/vertex/impl/ElementWiseVertex.java``); this
composite gives the Sequential facade the same capability for uniform-width
blocks (transformers, ResNet-style MLPs) — XLA fuses the add into the
surrounding elementwise chain, so it costs nothing at runtime.

Sublayers must be shape-preserving end-to-end and stateless (LayerNorm,
SelfAttention, Dense are; BatchNorm is not — use the graph facade there).
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import inspect
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nn.inputs import InputType
from deeplearning4j_tpu.nn.layers.base import Layer, layer_from_dict, register_layer


def _call_forms(layers) -> Tuple[Tuple[bool, bool], ...]:
    """``(takes a carry, takes a mask)`` of each sublayer: which call it
    gets inside a composite.  Read off the classes once, when the composite
    is made (``setup`` makes a new one, and a builder without an input type
    never calls ``setup``), not at every trace."""
    return tuple((hasattr(sub, "apply_with_carry"),
                  "mask" in inspect.signature(sub.apply).parameters)
                 for sub in layers)


@dataclasses.dataclass(frozen=True)
class _SublayerChain(Layer):
    """What both composites share: sublayers ``layers`` applied in order,
    their parameters under ``sub<i>``, their caches threaded through."""

    layers: Tuple[Layer, ...] = ()

    def __post_init__(self):
        object.__setattr__(self, "_forms", _call_forms(self.layers))

    def _setup_chain(self, input_type: InputType) -> Tuple[Layer, ...]:
        done, it = [], input_type
        for sub in self.layers:
            sub = sub.setup(it)
            it = sub.output_type(it)
            done.append(sub)
        return tuple(done)

    def output_type(self, input_type: InputType) -> InputType:
        return input_type

    def _init_chain(self, key, dtype) -> Dict[str, Any]:
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
                    f"{type(self).__name__} sublayers must be stateless "
                    f"(got state from {type(sub).__name__})")
        return {}

    def _apply_sub(self, i, params, h, *, train, rng, mask, sub=None):
        """Sublayer ``i`` (or ``sub`` in its place) without a carry."""
        sub = self.layers[i] if sub is None else sub
        kw = {"mask": mask} if mask is not None and self._forms[i][1] else {}
        with sub.kind_scope():
            h, _ = sub.apply(params.get(f"sub{i}", {}), {}, h, train=train,
                             rng=rng, **kw)
        return h

    def _chain_with_carry(self, params, h, carry, *, train, rngs, mask):
        """Thread each sublayer's cache through; ``(h, new carry)``."""
        new_carry = {}
        for i, sub in enumerate(self.layers):
            if self._forms[i][0]:
                # thread the seeded cache (attention) or None (recurrent
                # sublayers initialize their own state and return it — they
                # must NOT be applied statelessly here, or their hidden
                # state would reset every streamed chunk)
                with sub.kind_scope():
                    h, _, nc = sub.apply_with_carry(
                        params.get(f"sub{i}", {}), {}, h,
                        carry.get(f"sub{i}"), train=train, rng=rngs[i],
                        mask=mask)
                if nc is not None:
                    new_carry[f"sub{i}"] = nc
            else:
                h = self._apply_sub(i, params, h, train=train, rng=rngs[i],
                                    mask=mask)
        return h, new_carry

    def _rngs(self, rng):
        return (jax.random.split(rng, len(self.layers))
                if rng is not None else [None] * len(self.layers))

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
            elif self._forms[i][0]:
                carryable = True
        return carry if carryable else None

    def init_paged_cache(self, num_pages: int, page_size: int,
                         dtype=jnp.float32, window_pages=None,
                         state_slots=None):
        """The pools of the sublayers that keep state while streaming
        (attention K/V pages — ``SelfAttentionLayer.init_paged_cache`` —
        or a recurrent sublayer's state slots,
        ``MambaLayer.init_paged_cache``).  A sublayer that takes a carry and
        has no ``init_paged_cache`` makes the whole block unservable: the
        engine must reach every carry through a dispatch's block table or
        slot rows, so it raises and the engine fails at set-up.
        ``window_pages`` sizes the pools of window sublayers,
        ``state_slots`` those of state sublayers."""
        carry = {}
        pageable = False
        for i, sub in enumerate(self.layers):
            if hasattr(sub, "init_paged_cache"):
                pageable = True
                c = sub.init_paged_cache(num_pages, page_size, dtype,
                                         window_pages=window_pages,
                                         state_slots=state_slots)
                if c is not None:
                    carry[f"sub{i}"] = c
            elif self._forms[i][0]:
                raise ValueError(
                    f"{type(self).__name__} sublayer {type(sub).__name__} "
                    "takes a carry (apply_with_carry) but has no "
                    "init_paged_cache; the generation engine serves a layer "
                    "that keeps state only through pools it can address")
        return carry if pageable else None

    def _reg_chain(self, params):
        total = jnp.zeros(())
        for i, sub in enumerate(self.layers):
            if sub.has_params():
                total = total + sub.reg_score(params[f"sub{i}"])
        return total


@register_layer
@dataclasses.dataclass(frozen=True)
class ResidualBlock(_SublayerChain):
    """y = x + f(x) where f = sublayers applied in order.

    ``remat=True`` wraps f in ``jax.checkpoint``: activations inside the
    block are recomputed during the backward pass instead of stored —
    the standard long-context memory trade (activation memory per block
    drops from O(sublayers) to O(1) at ~1.3x FLOPs), composing with the
    sequence-parallel path for sequences that would not otherwise fit HBM."""

    remat: bool = False

    def setup(self, input_type: InputType) -> "ResidualBlock":
        return dataclasses.replace(self,
                                   layers=self._setup_chain(input_type))

    def init(self, key, dtype=jnp.float32):
        return self._init_chain(key, dtype)

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
        rngs = self._rngs(rng)
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
                with ln.kind_scope():
                    h = fused.prologue(
                        h, params["sub0"]["gamma"], params["sub0"]["beta"],
                        eps=ln.eps, rate=rate, rng=rngs[1], train=train)
                sub1r = (dataclasses.replace(sub1, dropout=0.0)
                         if rate > 0.0 else sub1)
                h = self._apply_sub(1, params, h, train=train, rng=rngs[1],
                                    mask=mask, sub=sub1r)
                start = 2
            for i in range(start, len(self.layers)):
                h = self._apply_sub(i, params, h, train=train, rng=rngs[i],
                                    mask=mask)
            return x + h

        if self.remat and train:
            body = jax.checkpoint(body)
        return body(params, x, rngs, mask), state

    def apply_with_carry(self, params, state, x, carry, *, train=False,
                         rng=None, mask=None):
        """carry=None -> exact ``apply`` (training/batch paths untouched).
        With a carry dict: thread each sublayer's cache through; remat is
        irrelevant here (streaming is forward-only)."""
        if carry is None:
            y, st = self.apply(params, state, x, train=train, rng=rng,
                               mask=mask)
            return y, st, None
        h, new_carry = self._chain_with_carry(
            params, x, carry, train=train, rngs=self._rngs(rng), mask=mask)
        return x + h, state, new_carry

    def reg_score(self, params):
        return self._reg_chain(params)

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


# ---------------------------------------------------------------------------
# manifold-constrained hyper-connections (mHC, arXiv:2512.24880)
# ---------------------------------------------------------------------------

# H_post = POST_SCALE * sigmoid(.): a stream takes between none and twice
# the sublayer's output
POST_SCALE = 2.0
# the DSL's own init (a benchmark installs its leaves over it): alpha small
# and B_res leaning on the diagonal, so that a fresh block is close to one
# plain residual connection a stream
ALPHA_INIT = 0.01
RES_DIAG_INIT = 4.0

_gauging = contextvars.ContextVar("dl4j_tpu_mhc_gauging", default=None)


@contextlib.contextmanager
def gauging(valid):
    """Trace-time scope, the float twin of ``nn.layers.moe.counting``: every
    ``HyperConnectionBlock`` traced inside appends to the yielded list one
    float32 scalar, the largest distance from 1 of a row sum or a column sum
    of its ``H_res`` over the real rows.  ``valid`` is a thunk that gives
    the boolean mask of real rows, shaped like the input's leading axes; it
    is called only if such a block is there."""
    sink = []
    token = _gauging.set((valid, sink))
    try:
        yield sink
    finally:
        _gauging.reset(token)


def sinkhorn(z, n: int, iters: int, eps: float):
    """``z`` [n * n, N] (row-major entries of one matrix a column, already
    clamped) -> ``M`` [n, n, N]: ``exp(z)``, then ``iters`` times every
    column divided by its sum + ``eps`` and every row by its sum + ``eps``.
    Tokens lie on the minor axis, so each of the 16 entries is a whole
    vector of lanes.  A ``fori_loop`` over the array: on a v5e its 20 trips
    cost 14.4 us a sub-layer at 64 tokens and 14.8 at 2048, against 15.0 /
    17.0 for the same loop unrolled and 15.2 / 41.9 for one unrolled over 16
    separate vectors, which also took 3 s a sub-layer to compile against
    0.03 (PERF.md, PR 35)."""
    m = jnp.exp(z.reshape(n, n, -1))

    def normalise(_, m):
        m = m / (jnp.sum(m, axis=0, keepdims=True) + eps)      # columns
        return m / (jnp.sum(m, axis=1, keepdims=True) + eps)   # rows

    return jax.lax.fori_loop(0, iters, normalise, m)


def doubly_stochastic_error(m):
    """[N]: the largest ``|sum - 1|`` over the rows and columns of ``m``
    [n, n, N].  The loop's last division is over rows, so they are exact to
    ``eps`` and the columns carry what it left undone."""
    sums = jnp.concatenate([jnp.sum(m, axis=1), jnp.sum(m, axis=0)])
    return jnp.max(jnp.abs(sums - 1.0), axis=0)


@register_layer
@dataclasses.dataclass(frozen=True)
class HyperConnectionBlock(_SublayerChain):
    """One sublayer ``f`` (``layers``, applied in order: a norm, then
    attention or an FFN) inside manifold-constrained hyper-connections
    over ``streams`` residual streams.  The input is the streams side by
    side, ``[B, T, streams * C]`` (``n_in``; a stream is one whole-lane
    slice of the last axis, so no input type of rank 4 is needed and the
    coefficients' product runs over the axis as it lies); the sublayers see
    ``[B, T, C]``.  Per token, with ``n = streams`` and ``X`` its n x C
    state, in float32 whatever the compute dtype:

      r = rsqrt(mean(vec(X)^2) + eps);   m = (vec(X) r) phi      [2n + n^2]
      H_pre  = sigmoid(a_pre m[:n] + b_pre)
      H_post = 2 sigmoid(a_post m[n:2n] + b_post)
      H_res  = Sinkhorn(exp(clip(a_res mat(m[2n:]) + B_res, res_clamp)))
               (``sinkhorn_iters`` times: columns, then rows, ``sinkhorn_eps``
               in the denominators): doubly stochastic
      u = sum_j H_pre[j] X_j;   y = f(u)
      X'_i = sum_j H_res[i, j] X_j + H_post[i] y

    Params: the sublayers' under ``sub<i>``; ``phi`` [n C, 2n + n^2];
    ``alpha`` [3] = (a_pre, a_post, a_res); ``beta`` [2n + n^2] = b_pre,
    b_post and B_res row by row.  Nothing of it is cached: a decoded token
    does the same on ``[B, 1, n C]``.  Device scopes ``mhc_coeffs``,
    ``mhc_sinkhorn``, ``mhc_mix``; inside ``gauging`` it reports how far
    ``H_res`` is from doubly stochastic."""

    n_in: Optional[int] = None
    streams: int = 4
    sinkhorn_iters: int = 20
    sinkhorn_eps: float = 1e-6
    res_clamp: Tuple[float, float] = (-30.0, 30.0)
    eps: float = 1e-6
    activation: str = "identity"

    @property
    def _width(self) -> int:
        return self.n_in // self.streams

    @property
    def _coeffs(self) -> int:
        return 2 * self.streams + self.streams ** 2

    def setup(self, input_type: InputType) -> "HyperConnectionBlock":
        n_in = self.n_in if self.n_in is not None else input_type.size
        inner = InputType.recurrent(n_in // self.streams,
                                    input_type.timesteps)
        return dataclasses.replace(self, n_in=n_in,
                                   layers=self._setup_chain(inner))

    def validate(self) -> None:
        super().validate()
        if self.streams < 1 or self.n_in % self.streams:
            raise ValueError(
                f"HyperConnectionBlock: n_in={self.n_in} is not "
                f"{self.streams} streams side by side")
        if self.sinkhorn_iters < 1 or len(self.res_clamp) != 2:
            raise ValueError("HyperConnectionBlock needs sinkhorn_iters >= 1 "
                             "and res_clamp = (min, max)")

    def init(self, key, dtype=jnp.float32):
        n = self.streams
        k_sub, k_phi = jax.random.split(key)
        params = self._init_chain(k_sub, dtype)
        params["phi"] = (jax.random.normal(k_phi, (self.n_in, self._coeffs))
                         * self.n_in ** -0.5).astype(dtype)
        params["alpha"] = jnp.full((3,), ALPHA_INIT, dtype)
        params["beta"] = jnp.concatenate([
            jnp.full((n,), -jnp.log(max(n - 1, 1))),     # H_pre = 1 / n
            jnp.zeros((n,)),                             # H_post = 1
            (RES_DIAG_INIT * jnp.eye(n)).reshape(-1)]).astype(dtype)
        return params

    # ------------------------------------------------------------ the parts
    def coefficients(self, params, x):
        """x [N, n C] float32 -> (H_pre [n, N], H_post [n, N],
        H_res [n, n, N]): every coefficient a vector of tokens."""
        n, f32 = self.streams, jnp.float32
        with jax.named_scope("mhc_coeffs"):
            r = jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1) + self.eps)
            m = jnp.dot(x, params["phi"].astype(f32),
                        precision=jax.lax.Precision.HIGHEST).T * r
            # a_pre, a_post, a_res against m's three parts
            alpha = params["alpha"].astype(f32)[
                np.repeat(np.arange(3), [n, n, n * n])]
            z = m * alpha[:, None] + params["beta"].astype(f32)[:, None]
            h_pre = jax.nn.sigmoid(z[:n])
            h_post = POST_SCALE * jax.nn.sigmoid(z[n:2 * n])
            z_res = jnp.clip(z[2 * n:], *self.res_clamp)
        with jax.named_scope("mhc_sinkhorn"):
            h_res = sinkhorn(z_res, n, self.sinkhorn_iters,
                             self.sinkhorn_eps)
        return h_pre, h_post, h_res

    def _gauge(self, h_res, lead):
        scope = _gauging.get()
        if scope is None:
            return
        valid, sink = scope
        err = doubly_stochastic_error(h_res)
        real = jnp.broadcast_to(valid(), lead).reshape(-1)
        sink.append(jnp.max(jnp.where(real, err, 0.0)))

    def _around(self, params, x, f):
        """The block around ``f``: u [B, T, C] -> (y [B, T, C], aux)."""
        n, c = self.streams, self._width
        lead = x.shape[:-1]
        xs = x.reshape(-1, n, c).astype(jnp.float32)
        h_pre, h_post, h_res = self.coefficients(
            params, xs.reshape(-1, n * c))
        self._gauge(h_res, lead)
        with jax.named_scope("mhc_mix"):
            u = sum(h_pre[j][:, None] * xs[:, j] for j in range(n))
            u = u.astype(x.dtype).reshape(lead + (c,))
        y, aux = f(u)
        with jax.named_scope("mhc_mix"):
            y32 = y.reshape(-1, c).astype(jnp.float32)
            out = [sum(h_res[i, j][:, None] * xs[:, j] for j in range(n))
                   + h_post[i][:, None] * y32 for i in range(n)]
            out = jnp.stack(out, axis=1).astype(x.dtype)
        return out.reshape(x.shape), aux

    # ------------------------------------------------------------- forward
    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        rngs = self._rngs(rng)

        def f(u):
            for i in range(len(self.layers)):
                u = self._apply_sub(i, params, u, train=train, rng=rngs[i],
                                    mask=mask)
            return u, None

        return self._around(params, x, f)[0], state

    def apply_with_carry(self, params, state, x, carry, *, train=False,
                         rng=None, mask=None):
        """carry=None -> ``apply``; with a carry dict each sublayer's cache
        is threaded through, as in ``ResidualBlock``."""
        if carry is None:
            y, st = self.apply(params, state, x, train=train, rng=rng,
                               mask=mask)
            return y, st, None
        y, new_carry = self._around(
            params, x, lambda u: self._chain_with_carry(
                params, u, carry, train=train, rngs=self._rngs(rng),
                mask=mask))
        return y, state, new_carry

    def reg_score(self, params):
        return self._reg_chain(params)

    def to_dict(self) -> Dict[str, Any]:
        d = {f.name: getattr(self, f.name) for f in dataclasses.fields(self)
             if f.name != "layers"}
        d.update(type="HyperConnectionBlock", res_clamp=list(self.res_clamp),
                 layers=[sub.to_dict() for sub in self.layers])
        return d

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "HyperConnectionBlock":
        d = dict(d)
        d["layers"] = tuple(layer_from_dict(s) for s in d["layers"])
        d["res_clamp"] = tuple(d["res_clamp"])
        return super().from_dict(d)


@register_layer
@dataclasses.dataclass(frozen=True)
class HyperStreamExpand(Layer):
    """``[B, T, C] -> [B, T, streams * C]``: the residual streams of a stack
    of ``HyperConnectionBlock`` s start as ``streams`` copies of the
    embedding, side by side."""

    kind = "mhc_mix"

    n_in: Optional[int] = None
    streams: int = 4
    activation: str = "identity"

    def setup(self, input_type: InputType) -> "HyperStreamExpand":
        n_in = self.n_in if self.n_in is not None else input_type.size
        return dataclasses.replace(self, n_in=n_in)

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.recurrent(self.n_in * self.streams,
                                   input_type.timesteps)

    def has_params(self) -> bool:
        return False

    def init(self, key, dtype=jnp.float32):
        return {}

    def apply(self, params, state, x, *, train=False, rng=None):
        return jnp.tile(x, self.streams), state


@register_layer
@dataclasses.dataclass(frozen=True)
class HyperStreamReduce(Layer):
    """``[B, T, streams * C] -> [B, T, C]``: the streams summed (in
    float32), ahead of the final norm."""

    kind = "mhc_mix"

    n_in: Optional[int] = None
    streams: int = 4
    activation: str = "identity"

    def setup(self, input_type: InputType) -> "HyperStreamReduce":
        n_in = self.n_in if self.n_in is not None else input_type.size
        return dataclasses.replace(self, n_in=n_in)

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.recurrent(self.n_in // self.streams,
                                   input_type.timesteps)

    def validate(self) -> None:
        super().validate()
        if self.n_in % self.streams:
            raise ValueError(f"HyperStreamReduce: n_in={self.n_in} is not "
                             f"{self.streams} streams side by side")

    def has_params(self) -> bool:
        return False

    def init(self, key, dtype=jnp.float32):
        return {}

    def apply(self, params, state, x, *, train=False, rng=None):
        xs = x.reshape(x.shape[:-1] + (self.streams, -1))
        return jnp.sum(xs, axis=-2, dtype=jnp.float32).astype(x.dtype), state
