"""Multi-head self-attention layers (TPU-first long-context extension).

The reference is pre-transformer — its only long-sequence tools are
truncated BPTT + masking (``nn/multilayer/MultiLayerNetwork.java:1176``,
``:711``).  This framework makes long-context first-class: a fused-friendly
local attention layer here, and ring / Ulysses sequence-parallel execution in
:mod:`deeplearning4j_tpu.parallel.sequence_parallel` for sequences that do
not fit one chip.

Design notes (TPU):
  - attention is computed head-batched as one ``jnp.einsum`` pair so XLA maps
    it onto the MXU; no per-head Python loops.
  - the layer is time-layout ``[B, T, F]`` like the rest of the recurrent
    stack; masks broadcast ``[B, T]``.
  - when ``seq_axis`` is set the layer computes ring attention over that
    mesh axis (caller runs the step under ``shard_map`` — see
    ``SequenceParallelTrainingMaster``); sequence shards never gather.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nn import activations, initializers
from deeplearning4j_tpu.nn.inputs import InputType
from deeplearning4j_tpu.nn.layers.base import Layer, register_layer
from deeplearning4j_tpu.nn.layers.normalization import rms_norm


def split_heads(x: jax.Array, n_heads: int) -> jax.Array:
    """[B, T, H*D] -> [B, T, H, D]"""
    b, t, f = x.shape
    return x.reshape(b, t, n_heads, f // n_heads)


def merge_heads(x: jax.Array) -> jax.Array:
    """[B, T, H, D] -> [B, T, H*D]"""
    b, t, h, d = x.shape
    return x.reshape(b, t, h * d)


def check_window(causal: bool, window: Optional[int]) -> None:
    """Single source of truth for the sliding-window contract: every entry
    point (flash, einsum, ring, layer config) fails loudly the same way."""
    if window is not None and (not causal or window < 1):
        raise ValueError(
            f"window={window} requires causal=True and window >= 1")


def yarn_inv_freq(dim: int, theta: float, factor: float,
                  original_max: int, beta_fast: float = 32.0,
                  beta_slow: float = 1.0) -> np.ndarray:
    """YaRN's rotary frequencies (Peng et al. 2023, as DeepSeek-V2/V3
    apply them) for ``dim`` rotated columns: each pair's frequency is
    blended between the extrapolated one (``theta^(-2i/dim)``) and the
    interpolated one (the same over ``factor``) by a linear ramp over the
    pair index, from the pair that turns ``beta_fast`` times within
    ``original_max`` positions down to the one that turns ``beta_slow``
    times.  Static numbers, computed on the host in float64."""
    half = dim // 2
    extra = theta ** (-np.arange(half, dtype=np.float64) / half)
    inter = extra / factor

    def pair_at(rotations):
        return (dim * math.log(original_max / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(pair_at(beta_fast)), 0)
    high = min(math.ceil(pair_at(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half, dtype=np.float64) - low) / (high - low),
                   0.0, 1.0)
    return inter * ramp + extra * (1.0 - ramp)


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature ``0.1 * mscale * ln(factor) + 1``."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def rope(x: jax.Array, positions: jax.Array,
         theta: float = 10000.0, inv_freq=None,
         mscale: float = 1.0) -> jax.Array:
    """Rotary position embedding on ``[B, T, H, D]`` (RoFormer; public
    standard).  ``inv_freq`` ([D // 2], e.g. ``yarn_inv_freq``) replaces
    the frequencies ``theta`` gives; ``mscale`` multiplies cos and sin
    (YaRN's ``attention_factor`` as ``rope_type: yarn`` applies it).
    ``positions`` is the [T] vector of
    GLOBAL positions —
    or, for the paged continuous-batching decode path where every batch
    row sits at a different stream position, a per-row [B, T] matrix —
    which is what makes the same function serve the full-sequence path,
    the streaming KV-cache path (q at ``pos + arange``, k rotated at
    write time), paged decode (per-slot positions), and ring attention
    (shard offsets).  Odd tail dims (D not a multiple of 2) pass through
    unrotated."""
    d = x.shape[-1]
    half = d // 2
    acc = jnp.promote_types(x.dtype, jnp.float32)
    if inv_freq is not None:
        freqs = jnp.asarray(inv_freq, acc)
    else:
        freqs = jnp.power(jnp.asarray(theta, acc),
                          -jnp.arange(0, half, dtype=acc) / max(half, 1))
    ang = positions.astype(acc)[..., :, None] * freqs  # [(B,) T, half]
    if positions.ndim == 1:
        cos = jnp.cos(ang)[None, :, None, :]
        sin = jnp.sin(ang)[None, :, None, :]
    else:
        cos = jnp.cos(ang)[:, :, None, :]
        sin = jnp.sin(ang)[:, :, None, :]
    if mscale != 1.0:
        cos, sin = cos * mscale, sin * mscale
    x1 = x[..., :half].astype(acc)
    x2 = x[..., half:2 * half].astype(acc)
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., 2 * half:].astype(acc)],
        axis=-1)
    return out.astype(x.dtype)


def dot_product_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    window: Optional[int] = None,
    mask: Optional[jax.Array] = None,
    q_offset: int | jax.Array = 0,
    k_offset: int | jax.Array = 0,
    q_positions: Optional[jax.Array] = None,
    k_positions: Optional[jax.Array] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    """Scaled dot-product attention on ``[B, T, H, D]`` tensors; ``scale``
    replaces the ``1/sqrt(D)`` on the scores, and ``v`` may be narrower or
    wider than ``q`` and ``k`` (ungrouped heads only).

    ``q_offset``/``k_offset`` give the global time positions of the local
    q/k blocks — this is what lets the same function serve as the per-block
    kernel of ring attention (blockwise causal masking by global position).
    Accumulates in float32 regardless of input dtype (MXU-friendly inputs,
    stable softmax).

    Grouped-query attention: when q carries MORE heads than k/v
    (H = G * H_kv) the contraction shares each KV head across its G query
    heads WITHOUT materializing an expanded K/V — the bandwidth this mode
    exists to save.
    """
    check_window(causal, window)
    d = q.shape[-1]
    hq, hkv = q.shape[2], k.shape[2]
    acc = jnp.promote_types(q.dtype, jnp.float32)   # f32 accumulate, f64 for gradchecks
    grouped = hq != hkv
    if grouped:
        if hq % hkv:
            raise ValueError(f"q heads {hq} not a multiple of kv heads {hkv}")
        qg = q.reshape(q.shape[0], q.shape[1], hkv, hq // hkv, d)
        scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).astype(acc)
    else:
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(acc)
    if scale is None:
        scores = scores / jnp.sqrt(jnp.asarray(d, acc))
    else:
        scores = scores * jnp.asarray(scale, acc)
    neg = jnp.asarray(-1e30, acc)
    head_dims = (None,) * (scores.ndim - 3)   # axes between batch and [q,k]
    if causal:
        # explicit position vectors override the contiguous offset+arange
        # convention (rolling KV caches store keys out of order)
        qpos = (q_positions if q_positions is not None
                else q_offset + jnp.arange(q.shape[1]))
        kpos = (k_positions if k_positions is not None
                else k_offset + jnp.arange(k.shape[1]))
        cm = qpos[:, None] >= kpos[None, :]
        if window is not None:
            # sliding window: keep kpos in [qpos - window + 1, qpos]
            cm &= kpos[None, :] > qpos[:, None] - window
        scores = jnp.where(cm[(None,) + head_dims], scores, neg)
    if mask is not None:
        idx = (slice(None),) + head_dims + (None, slice(None))
        scores = jnp.where(mask[idx].astype(bool), scores, neg)
    w = jax.nn.softmax(scores, axis=-1)
    if grouped:
        o = jnp.einsum("bhgqk,bkhd->bqhgd", w.astype(v.dtype), v)
        return o.reshape(q.shape[0], q.shape[1], hq, d)
    return jnp.einsum("bhqk,bkhd->bqhd", w.astype(v.dtype), v)


def gather_pages(pages: jax.Array, block: jax.Array) -> jax.Array:
    """Materialize one batch's logical KV view from a paged pool.

    ``pages`` [P, Hkv, page_size, D] (the pool, see
    ``SelfAttentionLayer.init_paged_cache`` for the layout), ``block``
    [B, MAXP] int32 per-row page ids: returns [B, MAXP * page_size, Hkv,
    D] where flat position ``i`` of row ``b`` is global stream position
    ``i`` of that row's sequence.  This is the paged-gather seam — the
    fused decode-attention helper (``helpers/paged_attention.py``)
    replaces exactly this gather + the softmax that follows, and is the
    DEFAULT decode path; this function + ``paged_attention`` remain the
    bit-compatible oracle the tests compare against, and the path where the
    helper seam gives way (``paged_path``)."""
    b, maxp = block.shape
    _, hkv, ps, d = pages.shape
    return (pages[block].transpose(0, 1, 3, 2, 4)
            .reshape(b, maxp * ps, hkv, d))


def paged_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    q_positions: jax.Array,
                    k_positions: Optional[jax.Array] = None,
                    window: Optional[int] = None) -> jax.Array:
    """Causal attention of ``q`` [B, T, H, D] over a gathered paged view
    ``k``/``v`` [B, L, Hkv, D] whose flat index IS the global position
    (see ``gather_pages``) -- or, over a ring table's view, whose positions
    are ``k_positions`` [B, L] (``helpers.paged_attention.ring_pages``;
    negative = never written),
    banded to the last ``window`` positions of each query.  ``q_positions`` [B, T] are per-row global
    query positions — every batch row sits at a different point of its
    own stream, which is the whole point of continuous batching, so the
    causal mask is per-row (``dot_product_attention`` masks by a single
    shared position vector and cannot express this).  Pages past a row's
    current position hold garbage (unwritten, or bucket-padding scratch);
    ``kpos > qpos`` masks every one of them.  GQA contracts the
    UNEXPANDED kv heads, same as the other paths."""
    d = q.shape[-1]
    hq, hkv = q.shape[2], k.shape[2]
    acc = jnp.promote_types(q.dtype, jnp.float32)
    if hq % hkv:
        raise ValueError(f"q heads {hq} not a multiple of kv heads {hkv}")
    grouped = hq != hkv
    if k_positions is None:
        kpos = jnp.arange(k.shape[1])
        cm = q_positions[:, :, None] >= kpos[None, None, :]   # [B, T, L]
    else:
        kpos = k_positions[:, None, :]
        cm = (q_positions[:, :, None] >= kpos) & (kpos >= 0)
    if window is not None:
        cm &= kpos > q_positions[:, :, None] - window
    neg = jnp.asarray(-1e30, acc)
    if grouped:
        qg = q.reshape(q.shape[0], q.shape[1], hkv, hq // hkv, d)
        scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k).astype(acc)
        scores = scores / jnp.sqrt(jnp.asarray(d, acc))
        scores = jnp.where(cm[:, None, None], scores, neg)
        w = jax.nn.softmax(scores, axis=-1)
        o = jnp.einsum("bhgqk,bkhd->bqhgd", w.astype(v.dtype), v)
        return o.reshape(q.shape[0], q.shape[1], hq, d)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(acc)
    scores = scores / jnp.sqrt(jnp.asarray(d, acc))
    scores = jnp.where(cm[:, None], scores, neg)
    w = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", w.astype(v.dtype), v)


@register_layer
@dataclasses.dataclass(frozen=True)
class SelfAttentionLayer(Layer):
    """Multi-head self-attention over ``[B, T, F]``.

    Params follow the framework's reference-style short names:
    ``Wq/Wk/Wv/Wo`` + ``bq/bk/bv/bo`` (``bias=False``: no bias vectors),
    ``Wg`` with ``gate``.  ``causal=True`` gives decoder (language-model)
    masking.  ``seq_axis`` switches the inner product to ring attention
    over that mesh axis (requires shard_map execution).
    """

    kind = "attention"

    n_in: Optional[int] = None
    n_out: Optional[int] = None
    n_heads: int = 4
    causal: bool = False
    activation: str = "identity"
    seq_axis: Optional[str] = None
    # fused Pallas flash-attention path via the helper seam
    # (helpers.get_helper("attention")) — used automatically on TPU when the
    # shape qualifies (T tiles into blocks) and no padding mask is present;
    # set False (or DL4J_TPU_DISABLE_HELPERS=1) to force the einsum path
    flash: bool = True
    # streaming-inference KV cache capacity (rnn_time_step); static so the
    # decode step compiles once
    max_cache: int = 1024
    # rotary position embedding (RoPE) on q/k before attention; parameter-
    # free, composes with the flash kernel (rotation happens outside it),
    # the KV cache (keys rotated at write by global position), and the
    # ring/Ulysses sequence-parallel paths (global shard offsets)
    rope: bool = False
    rope_theta: float = 10000.0
    # grouped-query attention: project K/V to this many heads (must divide
    # n_heads) and share each KV head across n_heads/n_kv_heads query
    # heads.  Shrinks the KV projections AND the streaming cache by the
    # same factor — the decode-bandwidth win; None = standard MHA
    n_kv_heads: Optional[int] = None
    # sliding-window (banded causal) attention: each query attends only the
    # last `window` positions.  The flash kernel skips out-of-band blocks'
    # compute AND HBM fetches; the einsum/ring paths apply the band as
    # masking (full score matrices); streaming decode uses a window-length
    # ROLLING cache (position-tracked ring buffer) — O(window) memory for
    # unbounded decode; the paged engine gives a window layer a RING of
    # ceil(window / page) + 1 pages a slot (init_paged_cache)
    window: Optional[int] = None
    # width of one head; None = n_out // n_heads.  Set, the heads' total
    # width n_heads * head_dim is free of n_out: Wq [n_in, H * D],
    # Wo [H * D, n_out]
    head_dim: Optional[int] = None
    # False: no bq/bk/bv/bo
    bias: bool = True
    # partial rotary: rotate the first rotary_dim columns of a head
    # (rotate-half pairing inside them), pass the rest; None = all of them
    rotary_dim: Optional[int] = None
    # YaRN (rope_factor > 1), named as LatentAttentionLayer names them:
    # frequencies blended by yarn_inv_freq, cos and sin times
    # yarn_mscale(rope_factor, rope_mscale) (HF's attention_factor)
    rope_factor: float = 1.0
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    # "per_head": head h's output times sigmoid(x Wg)[h] ahead of Wo
    # (a head-wise output gate computed from the layer's input)
    gate: Optional[str] = None
    # Olmo's whole-width q/k norm: an RMSNorm of this eps with a gain
    # (q_norm [H * D], k_norm [Hkv * D]) over all of q and all of k, ahead
    # of the heads' split and the rotation; None = no norm
    qk_norm_eps: Optional[float] = None

    def setup(self, input_type: InputType) -> "SelfAttentionLayer":
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
        if self.gate not in (None, "per_head"):
            raise ValueError(f"gate={self.gate!r} not one of None, "
                             "'per_head'")
        if self.rotary_dim is not None and (
                self.rotary_dim % 2 or self.rotary_dim < 2
                or (self.n_out is not None
                    and self.rotary_dim > self._d_head)):
            raise ValueError(
                f"rotary_dim={self.rotary_dim} must be even and within the "
                f"head width")

    @property
    def _kv_heads(self) -> int:
        return self.n_heads if self.n_kv_heads is None else self.n_kv_heads

    @property
    def _d_head(self) -> int:
        return (self.n_out // self.n_heads if self.head_dim is None
                else self.head_dim)

    def _expand_kv(self, x: jax.Array) -> jax.Array:
        """[B, T, Hkv, D] -> [B, T, H, D]: share each KV head across its
        query-head group (GQA)."""
        groups = self.n_heads // self._kv_heads
        return x if groups == 1 else jnp.repeat(x, groups, axis=2)

    def init(self, key, dtype=jnp.float32):
        if self.head_dim is None and self.n_out % self.n_heads:
            raise ValueError(
                f"n_out={self.n_out} not divisible by n_heads={self.n_heads}")
        if self._kv_heads < 1 or self.n_heads % self._kv_heads:
            raise ValueError(
                f"n_kv_heads={self.n_kv_heads} must be a positive divisor "
                f"of n_heads={self.n_heads}")
        check_window(self.causal, self.window)
        q_out = self.n_heads * self._d_head
        kv_out = self._kv_heads * self._d_head
        ks = jax.random.split(key, 4)
        p: Dict[str, jax.Array] = {}
        for name, k, (fi, fo) in (
            ("Wq", ks[0], (self.n_in, q_out)),
            ("Wk", ks[1], (self.n_in, kv_out)),
            ("Wv", ks[2], (self.n_in, kv_out)),
            ("Wo", ks[3], (q_out, self.n_out)),
        ):
            p[name] = initializers.init(self.weight_init, k, (fi, fo), dtype)
            if self.bias:
                p["b" + name[1].lower()] = jnp.zeros((fo,), dtype)
        if self.gate is not None:
            # a fifth key derived from the fourth, so that the four above
            # stay what a layer without a gate draws
            p["Wg"] = initializers.init(
                self.weight_init, jax.random.fold_in(ks[3], 1),
                (self.n_in, self.n_heads), dtype)
        if self.qk_norm_eps is not None:
            p["q_norm"] = jnp.ones((q_out,), dtype)
            p["k_norm"] = jnp.ones((kv_out,), dtype)
        return p

    # ------------------------------------------------------------ the parts
    def _project(self, params, x):
        """x [B, T, F] -> q [B, T, H, D], k and v [B, T, Hkv, D]."""
        def lin(w, b):
            y = x @ params[w]
            return y + params[b] if self.bias else y

        def normed(w, b, gain):
            y = lin(w, b)
            if self.qk_norm_eps is None:
                return y
            with jax.named_scope("qk_norm"):
                return rms_norm(y, params[gain], self.qk_norm_eps)

        return (split_heads(normed("Wq", "bq", "q_norm"), self.n_heads),
                split_heads(normed("Wk", "bk", "k_norm"), self._kv_heads),
                split_heads(lin("Wv", "bv"), self._kv_heads))

    def _rotate(self, x, positions):
        """RoPE of q or k [B, T, H, D] at ``positions`` ([T] or [B, T]):
        plain, or YaRN's frequencies and cos / sin scale, over the first
        ``rotary_dim`` columns."""
        if not self.rope:
            return x
        if self.rotary_dim is None and self.rope_factor <= 1:
            return rope(x, positions, self.rope_theta)
        rd = x.shape[-1] if self.rotary_dim is None else self.rotary_dim
        inv_freq, mscale = None, 1.0
        if self.rope_factor > 1:
            inv_freq = yarn_inv_freq(
                rd, self.rope_theta, self.rope_factor,
                self.rope_original_max, self.rope_beta_fast,
                self.rope_beta_slow)
            mscale = yarn_mscale(self.rope_factor, self.rope_mscale)
        y = rope(x[..., :rd], positions, self.rope_theta, inv_freq=inv_freq,
                 mscale=mscale)
        return jnp.concatenate([y, x[..., rd:]], axis=-1)

    def _out(self, params, o, x):
        """Heads [B, T, H, D] -> [B, T, n_out]: the per-head gate (from the
        layer's input ``x``), ``Wo``, the activation."""
        if self.gate is not None:
            with jax.named_scope("attn_gate"):
                g = jax.nn.sigmoid((x @ params["Wg"]).astype(jnp.float32))
                o = (o * g[..., None]).astype(o.dtype)
        y = merge_heads(o) @ params["Wo"]
        if self.bias:
            y = y + params["bo"]
        return activations.get(self.activation)(y)

    def _attend_sequence(self, q, k, v, mask=None):
        """Attention of a whole sequence over its own keys (``apply``, and a
        window layer's paged chunk): the flash kernel where it engages, the
        grouped einsum elsewhere."""
        with jax.named_scope("attention_core"):
            if self.flash and mask is None and q.dtype != jnp.float64:
                from deeplearning4j_tpu.helpers import get_helper

                helper = get_helper("attention")
                if helper is not None and helper.supports(q.shape[1],
                                                          q.shape[3]):
                    return helper.attend(q, self._expand_kv(k),
                                         self._expand_kv(v),
                                         causal=self.causal,
                                         window=self.window)
            # grouped contraction: no KV expansion materialized
            return dot_product_attention(q, k, v, causal=self.causal,
                                         window=self.window, mask=mask)

    def init_cache(self, batch: int, dtype=jnp.float32) -> Dict[str, jax.Array]:
        """KV cache for streaming inference (``rnn_time_step`` on
        transformer stacks — the attention analog of the reference's RNN
        ``stateMap``, ``BaseRecurrentLayer.java``).

        Linear mode (no ``window``): ``max_cache`` slots, ``pos`` counts
        filled timesteps, overflow is a hard error.  Rolling mode
        (``window`` set): ``window`` slots written modulo, each slot's
        GLOBAL position tracked in ``kpos`` — unbounded decode length in
        O(window) memory (out-of-band keys are overwritten exactly when
        they leave the band)."""
        # GQA caches store the UNEXPANDED kv heads — the decode-memory win
        length = self.window if self.window is not None else self.max_cache
        shape = (batch, length, self._kv_heads, self._d_head)
        cache = {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
                 "pos": jnp.zeros((), jnp.int32)}
        if self.window is not None:
            # sentinel far below any reachable qpos - window bound
            cache["kpos"] = jnp.full((length,), jnp.iinfo(jnp.int32).min // 2,
                                     jnp.int32)
        return cache

    def paged_ring(self, page_size: int) -> Optional[int]:
        """Pages a slot of this layer's paged pool when it is a WINDOW
        layer: ``ceil(window / page_size)`` to hold the band and one more,
        the page being written; None for a layer that keeps every
        position."""
        if self.window is None:
            return None
        return -(-self.window // page_size) + 1

    def serving_path(self, call) -> Optional[str]:
        """``paged_path`` of the program's paged call: over the ring in
        the decode step for a window layer (whose prefill chunk attends
        over its own keys: None), over the global table otherwise."""
        from deeplearning4j_tpu.helpers.paged_attention import paged_path

        if self.window is not None and call.t > 1:
            return None
        return paged_path(call.t, self.n_heads, self._kv_heads,
                          call.page_size,
                          call.ring if self.window else call.pages,
                          self.window)

    def describe_serving(self, call) -> Optional[str]:
        """How ``fused_paged_attention`` tiles the program's call, where
        the kernel runs it."""
        from deeplearning4j_tpu.helpers import paged_attention as pa

        form = self.serving_path(call)
        if form not in ("heads", "rows"):
            return None
        hq, hkv, d, window = (self.n_heads, self._kv_heads, self._d_head,
                              self.window)
        pages = call.ring if window else call.pages
        b, t = call.batch, call.t
        ppb, tq, vmem = pa.paged_tiling(b, t, hq, hkv, d, call.page_size,
                                        pages, call.dtype, window=window)
        return (
            f"fused_paged_attention q [{b}, {t}, {hq}, {d}] over {pages} "
            f"pages of {call.page_size}"
            f"{f' (a ring, window {window})' if window else ''}: {ppb} "
            f"pages a block, {tq} query positions a tile, grid ({b}, "
            f"{-(-t // tq)}), {vmem / 2 ** 20:.2f} MB of VMEM, the "
            f"{form} form")

    def init_paged_cache(self, num_pages: int, page_size: int,
                         dtype=jnp.float32, window_pages: Optional[int] = None,
                         state_slots: Optional[int] = None
                         ) -> Dict[str, jax.Array]:
        """KV pool for PAGED streaming inference (the continuous-batching
        generation engine, ``deeplearning4j_tpu/generation/``): instead of
        one contiguous [B, max_cache] cache per stream, K/V live in a
        shared pool of ``num_pages`` fixed-size pages; each running
        request addresses its pages through an int32 block table the
        engine passes per dispatch (``carry["block"]``/``carry["pos"]``
        alongside these pools).  Pool shapes are the ONLY shapes XLA ever
        sees, so slot count and pool size close the decode shape set.
        Like the linear cache, GQA pools store the UNEXPANDED kv heads.

        Layout ``[num_pages, Hkv, page_size, D]``: one page is a
        contiguous ``[Hkv, page_size, D]`` slab — what the fused Pallas
        kernel copies per page, every kv head at once — and one (page,
        kv head) a whole ``(page_size, D)`` tile to multiply.
        (Token-major ``[.., page_size, Hkv, D]`` would leave a kv head 1
        of Hkv rows in the tiled second-minor dimension, which the TPU
        lowering rejects.)

        A WINDOW layer's pools are another kind, ``wk``/``wv``
        ``[window_pages, Hkv, page_size, D]``: the engine's page manager
        gives every request a RING of at most ``paged_ring(page_size)``
        of these pages, addressed through a ring table of its own; position
        ``p`` lives in column ``(p // page_size) % ring``, so the pages a
        request holds here never grow with its context.  ``window_pages``
        is the count the manager keeps for this kind (``num_pages`` is the
        other kind's)."""
        if not self.causal or self.seq_axis is not None:
            raise ValueError(
                "paged KV caching requires causal=True attention without "
                f"seq_axis (got causal={self.causal}, "
                f"seq_axis={self.seq_axis})")
        if self.window is not None:
            if window_pages is None:
                raise ValueError(
                    f"a window layer (window={self.window}) pages through "
                    "a ring of its own kind of pages: init_paged_cache "
                    "needs window_pages, the count the page manager keeps "
                    "for window layers")
            shape = (window_pages, self._kv_heads, page_size, self._d_head)
            return {"wk": jnp.zeros(shape, dtype),
                    "wv": jnp.zeros(shape, dtype)}
        shape = (num_pages, self._kv_heads, page_size, self._d_head)
        return {"pk": jnp.zeros(shape, dtype), "pv": jnp.zeros(shape, dtype)}

    def _apply_paged(self, params, state, x, q, k, v, carry):
        """The paged-gather decode path (sibling of the rolling/linear
        branches below): write this chunk's K/V into the pool at the
        rows' global positions through the block table, gather each
        row's logical view back, attend causally by per-row position.
        Write-before-gather is correct here (pages never overwrite
        in-band keys, unlike the rolling ring) and makes the chunk's own
        keys visible to its own later queries."""
        block, pos = carry["block"], carry["pos"]      # [B, MAXP], [B]
        ps = carry["pk"].shape[2]
        t_new = q.shape[1]
        new_pos = pos[:, None] + jnp.arange(t_new, dtype=pos.dtype)
        # rotate by each ROW's global positions (rows sit at different
        # points of their own streams)
        q = self._rotate(q, new_pos)
        k = self._rotate(k, new_pos)
        page = jnp.take_along_axis(block, new_pos // ps, axis=1).reshape(-1)
        off = (new_pos % ps).reshape(-1)
        hkv, dh = k.shape[2], k.shape[3]
        from deeplearning4j_tpu.helpers import get_helper
        from deeplearning4j_tpu.helpers.paged_attention import (
            paged_path, write_token_rows)

        # one [Hkv, D] slab per new token at (page, :, offset, :), as Hkv
        # rows of the pool seen as a table
        pk = write_token_rows(carry["pk"], page, off, k.reshape(-1, hkv, dh))
        pv = write_token_rows(carry["pv"], page, off, v.reshape(-1, hkv, dh))
        # one scope whichever path does the work, so a trace reader can
        # find attention by its scope and not by a kernel's name
        with jax.named_scope("attention_core"):
            if paged_path(t_new, self.n_heads, hkv, ps,
                          block.shape[1]) == "gather":
                # the seam gives way: the legacy gather+softmax pair
                gk = gather_pages(pk, block).astype(q.dtype)
                gv = gather_pages(pv, block).astype(q.dtype)
                o = paged_attention(q, gk, gv, new_pos)
            else:
                # fused paged attention: straight off the pool + block
                # table, never materializing the gathered
                # [B, MAXP*page_size, Hkv, D] view
                o = get_helper("paged_attention").attend(q, pk, pv, block,
                                                         new_pos)
        new_carry = {"pk": pk, "pv": pv, "block": block, "pos": pos + t_new}
        return self._out(params, o, x), state, new_carry

    def _apply_window_paged(self, params, state, x, q, k, v, carry):
        """A window layer's paged path.  ``carry["block"]`` [B, R] is the
        rows' RING table into the ``wk``/``wv`` pools: position ``p`` lives
        in column ``ring_column(p)``, ``R * page >= window + page`` positions
        a row.

        One token a row (decode): write it, then attend over the ring, the
        keys' positions recovered from the row's own (``ring_pages``), the
        band as a mask.  A longer chunk is a prompt prefilled whole FROM
        POSITION 0 (the engine shares no prefix under window layers, so no
        chunk starts behind one): it attends over its own keys, banded
        (flash where it engages), and only the last ``R * page`` of its
        ``carry["live"]`` real tokens are written -- bucket padding and
        what has already left the band go to the trash page, 0."""
        from deeplearning4j_tpu.helpers.paged_attention import (
            paged_decode_attention, paged_path, ring_column,
            write_token_rows)

        ring_tbl, pos = carry["block"], carry["pos"]   # [B, R], [B]
        ps, ring = carry["wk"].shape[2], ring_tbl.shape[1]
        b, t_new = q.shape[:2]
        new_pos = pos[:, None] + jnp.arange(t_new, dtype=pos.dtype)
        q = self._rotate(q, new_pos)
        k = self._rotate(k, new_pos)
        if t_new > 1:
            o = self._attend_sequence(q, k, v)
        # the chunk's real tokens; what is written is the last ring's worth
        # of them at most
        last = (carry["live"].astype(pos.dtype) if "live" in carry
                else jnp.full((b,), t_new, pos.dtype))
        cap = ring * ps
        if t_new > cap:
            idx = (jnp.maximum(last - cap, 0)[:, None]
                   + jnp.arange(cap, dtype=pos.dtype))
            k_w = jnp.take_along_axis(k, idx[:, :, None, None], axis=1)
            v_w = jnp.take_along_axis(v, idx[:, :, None, None], axis=1)
        else:
            idx = jnp.broadcast_to(jnp.arange(t_new, dtype=pos.dtype),
                                   (b, t_new))
            k_w, v_w = k, v
        w_pos = pos[:, None] + idx
        page = jnp.where(
            idx < last[:, None],
            jnp.take_along_axis(ring_tbl, ring_column(w_pos, ps, ring),
                                axis=1), 0)
        page, off = page.reshape(-1), (w_pos % ps).reshape(-1)
        hkv, dh = k.shape[2], k.shape[3]
        wk = write_token_rows(carry["wk"], page, off,
                              k_w.reshape(-1, hkv, dh))
        wv = write_token_rows(carry["wv"], page, off,
                              v_w.reshape(-1, hkv, dh))
        if t_new == 1:
            from deeplearning4j_tpu.helpers import get_helper

            with jax.named_scope("attention_core"):
                if paged_path(1, self.n_heads, hkv, ps, ring,
                              self.window) == "gather":
                    o = paged_decode_attention(
                        q, wk, wv, ring_tbl, new_pos, window=self.window,
                        impl="gather")
                else:
                    o = get_helper("paged_attention").attend(
                        q, wk, wv, ring_tbl, new_pos, window=self.window)
        new_carry = {"wk": wk, "wv": wv, "block": ring_tbl,
                     "pos": pos + t_new}
        return self._out(params, o, x), state, new_carry

    @staticmethod
    def cache_overflow(carry, t_new: int, pos: Optional[int] = None) -> bool:
        """Would appending ``t_new`` steps exceed the cache?  Checked
        host-side before dispatch: ``dynamic_update_slice`` CLAMPS an
        out-of-range start index, which would silently relocate keys.
        Rolling (windowed) caches never overflow.

        ``pos`` is the host-side stream position the facades track; when
        omitted, falls back to syncing the device scalar (fine for one-off
        checks, a per-token round-trip in a decode loop)."""
        if "kpos" in carry:
            return False
        if pos is None:
            pos = int(carry["pos"])
        return pos + t_new > carry["k"].shape[1]

    def apply_with_carry(self, params, state, x, carry, *, train=False,
                         rng=None, mask=None):
        """carry=None -> exact full-sequence apply (training and batch
        inference paths are untouched).  With a cache carry: append this
        call's K/V and attend the new queries over the cached prefix —
        O(T_new · pos) per call on linear caches, O(T_new · window) on
        rolling (windowed) ones."""
        if carry is None:
            y, st = self.apply(params, state, x, train=train, rng=rng,
                               mask=mask)
            return y, st, None
        if not self.causal or self.seq_axis is not None or mask is not None:
            raise ValueError(
                "KV-cache streaming requires causal=True attention without "
                "seq_axis or padding masks (a non-causal layer would attend "
                "into the unfilled cache tail); got "
                f"causal={self.causal}, seq_axis={self.seq_axis}, "
                f"mask={'set' if mask is not None else None}")
        x = self.maybe_dropout(x, train=train, rng=rng)
        q, k, v = self._project(params, x)
        if "pk" in carry:
            # paged mode (continuous batching): per-ROW positions and a
            # block-table-addressed pool; see _apply_paged
            return self._apply_paged(params, state, x, q, k, v, carry)
        if "wk" in carry:
            # paged mode, window kind: a ring of pages a row
            return self._apply_window_paged(params, state, x, q, k, v, carry)
        t_new = q.shape[1]
        pos = carry["pos"]
        new_pos = pos + jnp.arange(t_new, dtype=pos.dtype)
        # rotate by GLOBAL position; cached keys are stored rotated
        q = self._rotate(q, new_pos)
        k = self._rotate(k, new_pos)
        if "kpos" in carry:
            # rolling mode: attend over [old ring buffer || this chunk]
            # (writing first would clobber keys still in-band for the
            # chunk's earlier rows), then write the chunk's tail modulo
            # the window-sized buffer for the next call
            L = carry["k"].shape[1]
            k_all = jnp.concatenate(
                [carry["k"].astype(q.dtype), k.astype(q.dtype)], axis=1)
            v_all = jnp.concatenate(
                [carry["v"].astype(q.dtype), v.astype(q.dtype)], axis=1)
            kpos_all = jnp.concatenate([carry["kpos"], new_pos])
            o = dot_product_attention(
                q, k_all, v_all, causal=True, window=self.window,
                q_positions=new_pos, k_positions=kpos_all)
            if t_new > L:   # only the last L positions can stay cached
                k, v, wpos = k[:, -L:], v[:, -L:], new_pos[-L:]
            else:
                wpos = new_pos
            slots = wpos % L   # consecutive positions -> distinct slots
            kc = carry["k"].at[:, slots].set(k.astype(carry["k"].dtype))
            vc = carry["v"].at[:, slots].set(v.astype(carry["v"].dtype))
            kposc = carry["kpos"].at[slots].set(wpos)
            new_carry = {"k": kc, "v": vc, "pos": pos + t_new, "kpos": kposc}
        else:
            zero = jnp.zeros((), pos.dtype)
            kc = jax.lax.dynamic_update_slice(
                carry["k"], k.astype(carry["k"].dtype),
                (zero, pos, zero, zero))
            vc = jax.lax.dynamic_update_slice(
                carry["v"], v.astype(carry["v"].dtype),
                (zero, pos, zero, zero))
            # causal masking by global position also hides the unfilled
            # tail (kpos > qpos).  Overflow past max_cache is a hard error,
            # enforced host-side by rnn_time_step (dynamic_update_slice
            # would clamp the write and silently relocate keys); see
            # cache_overflow().  Grouped contraction over the UNEXPANDED
            # cache — the decode-bandwidth win GQA exists for.
            o = dot_product_attention(
                q, kc.astype(q.dtype), vc.astype(q.dtype),
                causal=True, window=self.window, q_offset=pos, k_offset=0)
            new_carry = {"k": kc, "v": vc, "pos": pos + t_new}
        return self._out(params, o, x), state, new_carry

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        x = self.maybe_dropout(x, train=train, rng=rng)
        q, k, v = self._project(params, x)
        if self.rope:
            if self.seq_axis is not None:
                # inside shard_map each chip holds global timesteps
                # [idx*T_local, (idx+1)*T_local)
                off = jax.lax.axis_index(self.seq_axis) * q.shape[1]
            else:
                off = 0
            positions = off + jnp.arange(q.shape[1])
            q = self._rotate(q, positions)
            k = self._rotate(k, positions)
        if self.seq_axis is not None:
            from deeplearning4j_tpu.parallel.sequence_parallel import ring_attention

            # the ring fold contracts GQA heads directly: the rotating K/V
            # keeps H_kv heads, preserving the ICI/memory shrink
            o = ring_attention(q, k, v, mask, axis_name=self.seq_axis,
                               causal=self.causal, window=self.window)
        else:
            o = self._attend_sequence(q, k, v, mask)
        return self._out(params, o, x), state
