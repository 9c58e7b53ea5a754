"""Mixture-of-Experts layer — expert parallelism (EP) building block.

Beyond-reference extension (the reference predates MoE; SURVEY.md §2 lists
EP as absent).  TPU-first design: top-1 "switch" routing with a fixed
per-expert capacity so every shape is static — dispatch and combine are
one-hot einsums that lower to MXU matmuls, and the expert dimension of
every parameter is sharded over the mesh's model axis by the tensor/expert
parallel training master (``parallel/model_parallel.py``), putting each
expert's FFN on its own chips with all-to-all dispatch inserted by GSPMD.

Tokens over a full expert's capacity are dropped (contribute the residual
path only) — standard Switch-Transformer semantics that keeps the program
shape-static under jit.

``RoutedMoELayer`` is the dropless top-k layer of the DeepSeek-V3 family:
sigmoid scores, a selection bias, normalised weights, a shared expert, and
a layer that is TOLD WHICH EXPERTS IT HOLDS — one chip's share of an
expert-parallel deployment.  It routes over all ``n_experts``, computes
the part of the result its own experts give, and adds nothing for the
others; on one chip it runs without its exchange.
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu import helpers
from deeplearning4j_tpu.nn import activations, initializers
from deeplearning4j_tpu.nn.inputs import InputType
from deeplearning4j_tpu.nn.layers.base import Layer, register_layer
from deeplearning4j_tpu.nn.layers.dense import gated_mlp


@register_layer
@dataclasses.dataclass(frozen=True)
class MoELayer(Layer):
    """Switch-routed expert FFN: x -> router -> expert MLP -> combine.

    n_in/n_out: model width (input preserved: experts are hidden FFNs with a
    residual add, transformer-style).  hidden: per-expert FFN width.
    """

    kind = "experts"

    n_in: Optional[int] = None
    n_out: Optional[int] = None
    num_experts: int = 4
    hidden: int = 0                   # default 4*n_in
    capacity_factor: float = 1.25
    activation: str = "relu"
    residual: bool = True

    def setup(self, input_type: InputType) -> "MoELayer":
        n_in = self.n_in if self.n_in is not None else input_type.flat_size()
        n_out = self.n_out if self.n_out is not None else n_in
        return dataclasses.replace(self, n_in=n_in, n_out=n_out)

    def output_type(self, input_type: InputType) -> InputType:
        if input_type.kind == "rnn":
            return InputType.recurrent(self.n_out, input_type.timesteps)
        return InputType.feed_forward(self.n_out)

    def validate(self) -> None:
        super().validate()
        if self.residual and self.n_in != self.n_out:
            raise ValueError("MoE residual path needs n_in == n_out")

    def init(self, key, dtype=jnp.float32) -> Dict[str, jax.Array]:
        h = self.hidden or 4 * self.n_in
        k1, k2, k3, k4 = jax.random.split(key, 4)
        E = self.num_experts

        def w(k, shape, fan_in, fan_out):
            return initializers.init(self.weight_init, k, shape, dtype,
                                     fan_in=fan_in, fan_out=fan_out)

        return {
            "W_router": w(k1, (self.n_in, E), self.n_in, E),
            "W_up": w(k2, (E, self.n_in, h), self.n_in, h),
            "b_up": jnp.zeros((E, h), dtype),
            "W_down": w(k3, (E, h, self.n_out), h, self.n_out),
            "b_down": jnp.zeros((E, self.n_out), dtype),
        }

    def _capacity(self, n_tokens: int) -> int:
        return max(1, int(self.capacity_factor * n_tokens
                          / self.num_experts))

    def apply(self, params, state, x, *, train=False, rng=None):
        x = self.maybe_dropout(x, train=train, rng=rng)
        orig_shape = x.shape
        tokens = x.reshape(-1, orig_shape[-1])           # [T, d]
        T = tokens.shape[0]
        E = self.num_experts
        C = self._capacity(T)

        logits = tokens @ params["W_router"]             # [T, E]
        gates = jax.nn.softmax(logits, axis=-1)
        expert = jnp.argmax(gates, axis=-1)              # [T]
        gate = jnp.take_along_axis(gates, expert[:, None], 1)[:, 0]

        # position of each token within its expert's capacity buffer
        onehot = jax.nn.one_hot(expert, E, dtype=tokens.dtype)   # [T, E]
        pos = (jnp.cumsum(onehot, axis=0) - 1.0) * onehot        # [T, E]
        in_cap = (pos < C) & (onehot > 0)                        # [T, E]
        # dispatch tensor [T, E, C]: token t -> slot (e, c)
        slot = jax.nn.one_hot(pos.astype(jnp.int32), C,
                              dtype=tokens.dtype) * in_cap[..., None]
        expert_in = jnp.einsum("tec,td->ecd", slot, tokens)      # [E, C, d]

        act = activations.get(self.activation)
        hdn = act(jnp.einsum("ecd,edh->ech", expert_in, params["W_up"])
                  + params["b_up"][:, None, :])
        out = (jnp.einsum("ech,eho->eco", hdn, params["W_down"])
               + params["b_down"][:, None, :])                   # [E, C, o]

        combined = jnp.einsum("tec,eco->to", slot, out)          # [T, o]
        combined = combined * gate[:, None]
        if self.residual:
            combined = combined + tokens
        return combined.reshape(orig_shape[:-1] + (self.n_out,)), state


# ---------------------------------------------------------------------------
# trace-time counting scope (the generation programs' counters)
# ---------------------------------------------------------------------------

_counting = contextvars.ContextVar("dl4j_tpu_moe_counting", default=None)


@contextlib.contextmanager
def counting(valid):
    """Trace-time scope, like ``helpers.auto_partitioned``: every
    ``RoutedMoELayer`` traced inside appends to the yielded list one int32
    vector ``[real tokens, assignments to held expert 0, 1, ...]``.
    ``valid`` is a thunk that gives the boolean mask of real rows, shaped
    like the layer input's leading axes; it is called only if an expert
    layer is there, so a net without one traces nothing more."""
    sink = []
    token = _counting.set((valid, sink))
    try:
        yield sink
    finally:
        _counting.reset(token)


# ---------------------------------------------------------------------------
# which way the held experts are multiplied (RoutedMoELayer._held_experts)
# ---------------------------------------------------------------------------

EXPERT_PATHS = ("streamed", "sorted", "ragged")

# Rows up to which a call streams its experts (helpers/grouped_experts.py).
# The kernel multiplies EVERY row by every touched expert: 6 * d * hidden
# operations a row against 6 * d * hidden bytes of bf16 weights an expert,
# whatever the widths, so its arithmetic stays under the bytes while
# rows / peak FLOP/s < 1 / peak B/s: 240 rows at a v5e's peaks.  Measured
# alone on the chip (PERF.md PR 36) an expert layer at Xing's widths costs
# 1.86 / 1.90 / 2.03 ms at 64 / 128 / 256 rows (1.72 ms of bytes) and 3.02 /
# 4.17 at 384 / 512, where the arithmetic has taken over; at 256 rows it
# is ahead of the sorted groups at all three served widths (2.03 against
# 4.74 ms, 1.66 against 2.38, 0.86 against 1.42).  Past the bound each row
# meets the experts it chose alone (top_k * held / n_experts of the dense
# form's arithmetic), in the sorted kernel.
STREAMED_ROWS = 256


def expert_path(rows: int, train: bool = False, kernel: bool = True) -> str:
    """Which of ``EXPERT_PATHS`` a call of ``rows`` rows (static: the
    flattened leading axes of the layer's input) takes.  With the kernel
    (``kernel``: the helper seam offers it and takes the widths) and no
    gradient wanted (``train``: a ``fit`` step keeps the sorted groups of
    ``ragged_dot``, whose backward it needs): ``"streamed"`` — every row
    against each touched held expert, whose weights one kernel reads once
    — up to ``STREAMED_ROWS`` rows, ``"sorted"`` — rows sorted by expert,
    each expert's rows against its weights read once, in one kernel —
    past them; else ``"ragged"``, rows sorted by expert through
    ``jax.lax.ragged_dot`` in blocks.  Pure: the layer calls it while it
    is traced, the engine on the host to count
    ``dl4j_layer_path_steps_total``."""
    if train or not kernel:
        return "ragged"
    return "streamed" if rows <= STREAMED_ROWS else "sorted"


@register_layer
@dataclasses.dataclass(frozen=True)
class RoutedMoELayer(Layer):
    """Dropless top-k expert FFN with a shared expert (DeepSeek-V3's
    ``MoE`` with ``noaux_tc`` routing in one group), as one share of it.

    In float32 whatever the compute dtype: ``s = sigmoid(x W_router)``
    over all ``n_experts``; the ``top_k`` largest of ``s + b_router`` are
    chosen; their weights are the chosen ``s`` (without the bias), divided
    by their sum when ``norm_topk_prob``, times ``routed_scaling_factor``.
    With ``n_group`` > 1 the choice is GROUP-LIMITED (DeepSeek-V3's
    ``noaux_tc``): the experts fall in ``n_group`` groups of consecutive
    ids, a group scores the sum of its two largest ``s + b_router``, and
    the ``top_k`` are chosen among the experts of the ``topk_group``
    best-scoring groups only.
    ``scoring="softmax"`` (the Qwen-MoE family's gate): ``s = softmax(x
    W_router)`` over all ``n_experts``, the ``top_k`` largest chosen, no
    selection bias (no ``b_router`` leaf), the rest alike.
    ``y = sum_i w_i E_i(x) + E_shared(x)``, every expert a bias-free gated
    MLP of width ``hidden`` (the shared one of width ``shared``; 0 = none).
    No capacity: no token is dropped.

    ``experts_held = (first, count)`` names the experts whose weights this
    layer has (``W_gate/W_up/W_down`` are ``[count, ...]``); None holds
    all.  One algorithm under three schedules (``expert_path`` picks by
    the call's row count): a few rows — a decode step — are all multiplied
    by every expert one of them chose, whose weights one kernel streams
    once; many rows — a prefill — are sorted by held expert and each
    expert's rows multiplied by its weights, read once, in another
    (``helpers/grouped_experts.py``); a training call, or one where the
    kernels give way, sorts them and multiplies group by group
    (``jax.lax.ragged_dot``), the other two's backward.  Assignments to
    experts held elsewhere add nothing either way."""

    kind = "experts"

    n_in: Optional[int] = None
    n_out: Optional[int] = None
    n_experts: int = 8
    top_k: int = 2
    hidden: int = 0
    shared: int = 0
    experts_held: Optional[Tuple[int, int]] = None
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    activation: str = "silu"
    scoring: str = "sigmoid"
    # group-limited choice: n_group groups, the top_k from topk_group of them
    n_group: int = 1
    topk_group: int = 1

    def setup(self, input_type: InputType) -> "RoutedMoELayer":
        n_in = self.n_in if self.n_in is not None else input_type.flat_size()
        n_out = self.n_out if self.n_out is not None else n_in
        return dataclasses.replace(self, n_in=n_in, n_out=n_out)

    def output_type(self, input_type: InputType) -> InputType:
        if input_type.kind == "rnn":
            return InputType.recurrent(self.n_out, input_type.timesteps)
        return InputType.feed_forward(self.n_out)

    @property
    def held(self) -> Tuple[int, int]:
        if self.experts_held is None:
            return 0, self.n_experts
        return int(self.experts_held[0]), int(self.experts_held[1])

    def validate(self) -> None:
        super().validate()
        first, count = self.held
        if not (0 <= first and count >= 1
                and first + count <= self.n_experts):
            raise ValueError(
                f"experts_held={self.experts_held} lies outside the "
                f"{self.n_experts} experts")
        if not 1 <= self.top_k <= self.n_experts or self.hidden < 1:
            raise ValueError("RoutedMoELayer needs 1 <= top_k <= n_experts "
                             "and hidden >= 1")
        if self.scoring not in ("sigmoid", "softmax"):
            raise ValueError(f"scoring={self.scoring!r} not one of "
                             "'sigmoid', 'softmax'")
        if self.n_group > 1 and (
                self.scoring != "sigmoid" or self.n_experts % self.n_group
                or self.n_experts // self.n_group < 2
                or not 1 <= self.topk_group <= self.n_group
                or self.top_k > self.topk_group * (self.n_experts
                                                   // self.n_group)):
            raise ValueError(
                f"n_group={self.n_group}, topk_group={self.topk_group}: "
                f"sigmoid scores, groups of 2 or more of the {self.n_experts}"
                f" experts, and top_k={self.top_k} inside the groups kept")

    def init(self, key, dtype=jnp.float32) -> Dict[str, jax.Array]:
        count = self.held[1]
        d, h = self.n_in, self.hidden
        ks = jax.random.split(key, 7)

        def w(k, shape, fan_in, fan_out):
            return initializers.init(self.weight_init, k, shape, dtype,
                                     fan_in=fan_in, fan_out=fan_out)

        p = {"W_router": w(ks[0], (d, self.n_experts), d, self.n_experts),
             "W_gate": w(ks[1], (count, d, h), d, h),
             "W_up": w(ks[2], (count, d, h), d, h),
             "W_down": w(ks[3], (count, h, self.n_out), h, self.n_out)}
        if self.scoring == "sigmoid":
            p["b_router"] = jnp.zeros((self.n_experts,), dtype)
        if self.shared:
            p["Ws_gate"] = w(ks[4], (d, self.shared), d, self.shared)
            p["Ws_up"] = w(ks[5], (d, self.shared), d, self.shared)
            p["Ws_down"] = w(ks[6], (self.shared, self.n_out), self.shared,
                             self.n_out)
        return p

    def route(self, params, tokens):
        """tokens [T, d] -> (expert ids [T, k] int32, weights [T, k]
        float32), over all ``n_experts``."""
        with jax.named_scope("moe_router"):
            f32 = jnp.float32
            logits = jnp.dot(tokens, params["W_router"],
                             preferred_element_type=f32).astype(f32)
            if self.scoring == "softmax":
                scores = jax.nn.softmax(logits, axis=-1)
                _, ids = jax.lax.top_k(scores, self.top_k)
            else:
                scores = jax.nn.sigmoid(logits)
                choice = scores + params["b_router"].astype(f32)
                if self.n_group > 1:
                    choice = limit_to_groups(choice, self.n_group,
                                             self.topk_group)
                _, ids = jax.lax.top_k(choice, self.top_k)
            w = jnp.take_along_axis(scores, ids, axis=1)
            if self.norm_topk_prob:
                w = w / (jnp.sum(w, axis=1, keepdims=True) + 1e-20)
            return ids.astype(jnp.int32), w * self.routed_scaling_factor

    def path(self, rows: int, train: bool = False) -> str:
        """``expert_path`` of a call of ``rows`` rows on this layer as the
        process stands: with the kernel only if the helper seam offers it
        (not under ``helpers.auto_partitioned``, not with helpers
        disabled) for these widths."""
        kernel = helpers.get_helper("grouped_experts")
        return expert_path(rows, train, kernel is not None and kernel.supports(
            self.n_in, self.hidden, self.n_out))

    def serving_path(self, call) -> str:
        return self.path(call.batch * call.t)

    def describe_serving(self, call) -> Optional[str]:
        """How ``grouped_experts`` tiles the program's call, where it runs
        the ``streamed`` or the ``sorted`` path."""
        from deeplearning4j_tpu.helpers import grouped_experts as ge

        path, t = self.serving_path(call), call.batch * call.t
        count, n, k = self.held[1], self.n_experts, self.top_k
        d, hidden, n_out = self.n_in, self.hidden, self.n_out
        if path == "streamed":
            rows, tf, vmem = ge.expert_tiling(t, d, hidden, n_out,
                                              call.dtype)
            return (f"grouped_experts tokens [{t}, {d}] over {count} held "
                    f"experts of width {hidden}: {rows} rows, hidden tiles "
                    f"of {tf}, grid ({count}, {hidden // tf}), "
                    f"{vmem / 2 ** 20:.2f} MB of VMEM")
        if path == "sorted":
            tm, tf, r, vmem = ge.sorted_tiling(d, hidden, n_out, call.dtype,
                                               t * k // n)
            return (f"sorted_experts tokens [{t}, {d}] over {count} held "
                    f"experts of width {hidden}: blocks of "
                    f"{ge.sorted_block(t * k, count, n)} sorted rows, row "
                    f"tiles of {tm}, hidden tiles of {tf}, {r} row tiles a "
                    f"visit, {vmem / 2 ** 20:.2f} MB of VMEM")
        return None

    def _held_experts(self, params, tokens, ids, w, train=False):
        """The held experts' part of the result, [T, n_out] float32, by
        the path ``expert_path`` names."""
        args = (params["W_gate"], params["W_up"], params["W_down"], tokens,
                ids, w)
        path = self.path(tokens.shape[0], train)
        if path == "ragged":
            return self._held_ragged(*args)
        return _held_kernel(self, path, *args)

    def _held_ragged(self, w_gate, w_up, w_down, tokens, ids, w):
        """The ``ragged`` path.  Assignments are sorted by held expert
        (those held elsewhere last) and multiplied in blocks of ``rows`` sorted rows, as many blocks as
        the held assignments fill: a block is four times the share
        ``count / n_experts`` of all assignments, so uniform routing takes
        one block and a skewed batch takes more (dropless either way), and
        the rows gathered at once stay a fraction of ``T * top_k``."""
        first, count = self.held
        t, k = ids.shape
        a = t * k
        local = ids - first
        mine = (local >= 0) & (local < count)
        key = jnp.where(mine, local, count).reshape(a)    # elsewhere: last
        rows = min(a, -(-4 * a * count // self.n_experts))
        blocks = -(-a // rows)
        order = jnp.pad(jnp.argsort(key, stable=True),
                        (0, blocks * rows - a))
        ends = jnp.cumsum(jnp.sum(
            key[:, None] == jnp.arange(count)[None, :], axis=0,
            dtype=jnp.int32))        # the sorted row where e's group ends
        n_mine = ends[-1]
        weight = jnp.where(mine, w, 0.0).reshape(a)
        act = activations.get(self.activation)

        def block(j, acc):
            lo = j * rows
            at = jax.lax.dynamic_slice(order, (lo,), (rows,))
            cut = jnp.clip(ends, lo, lo + rows) - lo
            sizes = jnp.diff(cut, prepend=0)   # each group's rows in here
            tok = at // k
            x = tokens[tok]
            hid = (act(jax.lax.ragged_dot(x, w_gate, sizes))
                   * jax.lax.ragged_dot(x, w_up, sizes))
            y = jax.lax.ragged_dot(hid, w_down, sizes,
                                   preferred_element_type=jnp.float32)
            # rows past the held ones are no group's: whatever they hold
            y = jnp.where((lo + jnp.arange(rows) < n_mine)[:, None],
                          y * weight[at][:, None], 0.0)
            return acc.at[tok].add(y)

        return jax.lax.fori_loop(
            0, (n_mine + rows - 1) // rows, block,
            jnp.zeros((t, self.n_out), jnp.float32))

    def _count(self, ids):
        scope = _counting.get()
        if scope is None:
            return
        valid, sink = scope
        first, count = self.held
        real = valid().reshape(-1)
        local = jnp.where(real[:, None], ids - first, -1)
        per = jnp.sum(local[..., None] == jnp.arange(count), axis=(0, 1),
                      dtype=jnp.int32)
        sink.append(jnp.concatenate(
            [jnp.sum(real, dtype=jnp.int32)[None], per]))

    def apply(self, params, state, x, *, train=False, rng=None):
        x = self.maybe_dropout(x, train=train, rng=rng)
        tokens = x.reshape(-1, x.shape[-1])
        ids, w = self.route(params, tokens)
        self._count(ids)
        with jax.named_scope("moe_experts"):
            y = self._held_experts(params, tokens, ids, w, train)
        if self.shared:
            with jax.named_scope("moe_shared_expert"):
                y = y + gated_mlp(tokens, params["Ws_gate"],
                                  params["Ws_up"], params["Ws_down"],
                                  self.activation)
        return y.astype(x.dtype).reshape(x.shape[:-1] + (self.n_out,)), state

    @classmethod
    def from_dict(cls, d):
        d = dict(d)
        if d.get("experts_held") is not None:
            d["experts_held"] = tuple(d["experts_held"])
        return super().from_dict(d)


def limit_to_groups(choice, n_group: int, topk_group: int):
    """``choice`` [T, E] with every expert outside the ``topk_group`` groups
    of highest score set to -inf: ``n_group`` groups of consecutive ids, a
    group's score the sum of its two largest entries."""
    t, e = choice.shape
    grouped = choice.reshape(t, n_group, e // n_group)
    best, _ = jax.lax.top_k(grouped, 2)
    _, kept = jax.lax.top_k(jnp.sum(best, axis=-1), topk_group)
    keep = jnp.any(kept[:, :, None] == jnp.arange(n_group), axis=1)
    return jnp.where(keep[:, :, None], grouped, -jnp.inf).reshape(t, e)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _held_kernel(layer: RoutedMoELayer, path: str, w_gate, w_up, w_down,
                 tokens, ids, w):
    """The ``streamed`` or ``sorted`` path: ``layer``'s held experts
    through the helper's kernels.  Differentiated (``jax.grad`` through an
    inference call), its backward is the ``ragged`` path's: the three
    compute one function."""
    helper = helpers.get_helper("grouped_experts")
    if path == "sorted":
        return helper.apply_sorted(tokens, w_gate, w_up, w_down, ids, w,
                                   layer.held, layer.n_experts,
                                   layer.activation)
    return helper.apply(tokens, w_gate, w_up, w_down, ids, w, layer.held,
                        layer.activation)


def _held_kernel_fwd(layer, path, *args):
    return _held_kernel(layer, path, *args), args


def _held_kernel_bwd(layer, path, args, g):
    w_gate, w_up, w_down, tokens, ids, w = args
    _, vjp = jax.vjp(
        lambda wg, wu, wd, x, ww: layer._held_ragged(wg, wu, wd, x, ids, ww),
        w_gate, w_up, w_down, tokens, w)
    *floats, dw = vjp(g)
    return (*floats, np.zeros(ids.shape, jax.dtypes.float0), dw)


_held_kernel.defvjp(_held_kernel_fwd, _held_kernel_bwd)
