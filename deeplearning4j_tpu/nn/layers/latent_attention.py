"""Multi-head latent attention (MLA; DeepSeek-V2, arXiv:2405.04434, as
DeepSeek-V3 and Kimi-K2 carry it).

Keys and values are not cached.  Each token leaves one compressed vector
``c_kv`` (``kv_rank`` numbers, RMS-normed) and one rotary key ``k_r``
(``rope_dim`` numbers, rotated, shared by every head): the cache holds
``kv_rank + rope_dim`` numbers a token and layer, against
``n_heads * (nope_dim + rope_dim + v_dim)`` for the expanded heads.

Two ways through the same weights:

- *expanded*: ``[k_nope | v] = c_kv W_kvb`` for every position attended,
  then ordinary causal attention with q.k width ``nope_dim + rope_dim``
  and v width ``v_dim``.  Compute-bound; the full-sequence ``apply`` and a
  paged chunk that starts at position 0 (a prompt prefilled whole).
- *absorbed*: ``W_kvb``'s key half folded into the query
  (``q_lat = q_nope W_kvb,k^T``) and its value half applied after the
  softmax, so the scores and the weighted sum run against the cached latent
  rows themselves: multi-query attention with ``n_heads`` query rows over
  one ``kv_rank + rope_dim`` wide key whose first ``kv_rank`` columns are
  also the value.  Memory-bound; paged decode, and any paged chunk that
  does not start at position 0 (a prompt suffix behind a shared prefix).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from deeplearning4j_tpu import helpers
from deeplearning4j_tpu.nn import initializers
from deeplearning4j_tpu.nn.inputs import InputType
from deeplearning4j_tpu.nn.layers.attention import (
    dot_product_attention, rope, yarn_inv_freq, yarn_mscale,
)
from deeplearning4j_tpu.nn.layers.base import Layer, register_layer
from deeplearning4j_tpu.nn.layers.normalization import rms_norm

# query rows of one block of the absorbed path over a chunk (prompt suffix
# behind a shared prefix): the scores held are [B, H, ROWS, context]
ABSORBED_ROWS = 128
LANES = 128

LATENT_PATHS = ("paged", "gathered", "expanded")


def latent_path(t: int, from_zero: bool, kernel: bool = True) -> str:
    """Which of ``LATENT_PATHS`` a paged call of ``t`` query positions a
    row takes (``apply_with_carry``).  A single token (``t == 1``, a decode
    step) attends the absorbed way: ``"paged"`` — one kernel reads the
    rows' live latent pages where they lie — when the helper seam offers it
    (``kernel``), else ``"gathered"``, the absorbed way over
    ``pool[block]``, every page of every row's table.  A chunk goes
    ``"expanded"`` (flash attention over its own decompressed keys) when
    every row starts at position 0 (``from_zero``: the program branches on
    it on the device, the host knows it as a request with nothing shared),
    else ``"gathered"`` (a suffix behind a shared prefix).  Pure: the layer
    calls it while it is traced, the engine on the host to count
    ``dl4j_layer_path_steps_total``."""
    if t == 1:
        return "paged" if kernel else "gathered"
    return "expanded" if from_zero else "gathered"


@register_layer
@dataclasses.dataclass(frozen=True)
class LatentAttentionLayer(Layer):
    """Causal latent self-attention over ``[B, T, F]``, bias-free.

    Params: ``Wqa`` [n_in, q_rank], ``q_norm`` [q_rank], ``Wqb`` [q_rank,
    H * (nope_dim + rope_dim)], ``Wkva`` [n_in, kv_rank + rope_dim],
    ``kv_norm`` [kv_rank], ``Wkvb`` [kv_rank, H * (nope_dim + v_dim)],
    ``Wo`` [H * v_dim, n_out].  ``q_rank`` 0: no low-rank query, ``Wq``
    [n_in, H * (nope_dim + rope_dim)] in place of ``Wqa``, ``q_norm`` and
    ``Wqb``.  ``gate="per_head"``: head ``h``'s output times ``sigmoid(x
    Wg)[h]`` ahead of ``Wo`` (``Wg`` [n_in, H]).  ``rope_factor`` > 1 turns
    on YaRN (frequencies blended by ``yarn_inv_freq``; the softmax scale
    times ``yarn_mscale(rope_factor, rope_mscale_all_dim)`` squared)."""

    kind = "attention"

    n_in: Optional[int] = None
    n_out: Optional[int] = None
    n_heads: int = 4
    q_rank: int = 0
    kv_rank: int = 0
    nope_dim: int = 0
    rope_dim: int = 0
    v_dim: int = 0
    eps: float = 1e-5
    activation: str = "identity"
    rope_theta: float = 10000.0
    rope_factor: float = 1.0
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0
    # "per_head": a head-wise sigmoid output gate from the layer's input
    gate: Optional[str] = None

    def setup(self, input_type: InputType) -> "LatentAttentionLayer":
        n_in = self.n_in if self.n_in is not None else input_type.size
        n_out = self.n_out if self.n_out is not None else n_in
        return dataclasses.replace(self, n_in=n_in, n_out=n_out)

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.recurrent(self.n_out, input_type.timesteps)

    def validate(self) -> None:
        super().validate()
        sizes = (self.q_rank, self.kv_rank, self.nope_dim, self.rope_dim,
                 self.v_dim)
        if min(sizes[1:]) < 1 or self.q_rank < 0 or self.rope_dim % 2:
            raise ValueError(
                "LatentAttentionLayer needs q_rank >= 0 (0: no low-rank "
                "query), kv_rank, nope_dim, v_dim >= 1 and an even rope_dim "
                f">= 2; got {sizes}")
        if self.gate not in (None, "per_head"):
            raise ValueError(f"gate={self.gate!r} not one of None, "
                             "'per_head'")

    def init(self, key, dtype=jnp.float32) -> Dict[str, jax.Array]:
        h, qk = self.n_heads, self.nope_dim + self.rope_dim
        shapes = {"Wqa": (self.n_in, self.q_rank),
                  "Wqb": (self.q_rank, h * qk),
                  "Wkva": (self.n_in, self.kv_rank + self.rope_dim),
                  "Wkvb": (self.kv_rank, h * (self.nope_dim + self.v_dim)),
                  "Wo": (h * self.v_dim, self.n_out)}
        if not self.q_rank:
            del shapes["Wqa"], shapes["Wqb"]
            shapes = {"Wq": (self.n_in, h * qk), **shapes}
        keys = jax.random.split(key, len(shapes))
        p = {name: initializers.init(self.weight_init, k, shape, dtype)
             for (name, shape), k in zip(shapes.items(), keys)}
        if self.q_rank:
            p["q_norm"] = jnp.ones((self.q_rank,), dtype)
        p["kv_norm"] = jnp.ones((self.kv_rank,), dtype)
        if self.gate is not None:
            # a key derived from the last, so that the others stay what a
            # layer without a gate draws
            p["Wg"] = initializers.init(
                self.weight_init, jax.random.fold_in(keys[-1], 1),
                (self.n_in, h), dtype)
        return p

    # ------------------------------------------------------------ the parts
    @property
    def softmax_scale(self) -> float:
        scale = (self.nope_dim + self.rope_dim) ** -0.5
        if self.rope_factor > 1:
            scale *= yarn_mscale(self.rope_factor,
                                 self.rope_mscale_all_dim) ** 2
        return scale

    def _rope(self, x, positions):
        """Rotate-half RoPE; under YaRN the blended frequencies, and cos
        and sin times ``mscale / mscale_all_dim``'s ratio (1 as published)."""
        if self.rope_factor <= 1:
            return rope(x, positions, self.rope_theta)
        y = rope(x, positions, inv_freq=yarn_inv_freq(
            self.rope_dim, self.rope_theta, self.rope_factor,
            self.rope_original_max, self.rope_beta_fast,
            self.rope_beta_slow))
        ratio = (yarn_mscale(self.rope_factor, self.rope_mscale)
                 / yarn_mscale(self.rope_factor, self.rope_mscale_all_dim))
        return y if ratio == 1.0 else (y * ratio).astype(y.dtype)

    def _project(self, params, x, positions):
        """x [B, T, F] -> q_nope [B, T, H, nope], q_rope [B, T, H, rope]
        (rotated), latent [B, T, kv_rank + rope_dim]: the normed ``c_kv``
        beside the rotated ``k_r``, which is what a cache holds."""
        b, t, _ = x.shape
        if self.q_rank:
            cq = rms_norm(x @ params["Wqa"], params["q_norm"], self.eps)
            q = (cq @ params["Wqb"]).reshape(b, t, self.n_heads, -1)
        else:
            q = (x @ params["Wq"]).reshape(b, t, self.n_heads, -1)
        q_nope, q_rope = q[..., :self.nope_dim], q[..., self.nope_dim:]
        kv = x @ params["Wkva"]
        c_kv = rms_norm(kv[..., :self.kv_rank], params["kv_norm"], self.eps)
        k_r = self._rope(kv[..., None, self.kv_rank:], positions)[:, :, 0]
        return (q_nope, self._rope(q_rope, positions),
                jnp.concatenate([c_kv, k_r], axis=-1))

    def _kvb(self, params):
        """``Wkvb`` as its key half and value half, [kv_rank, H, *]."""
        w = params["Wkvb"].reshape(self.kv_rank, self.n_heads, -1)
        return w[..., :self.nope_dim], w[..., self.nope_dim:]

    def _expanded(self, params, q_nope, q_rope, latent):
        """Attention of a whole sequence over its own decompressed keys and
        values; returns [B, T, H, v_dim]."""
        b, t, h = q_nope.shape[:3]
        wk, wv = self._kvb(params)
        c_kv, k_r = latent[..., :self.kv_rank], latent[..., self.kv_rank:]
        k_nope = jnp.einsum("btc,chd->bthd", c_kv, wk)
        v = jnp.einsum("btc,chd->bthd", c_kv, wv)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_r[:, :, None], (b, t, h,
                                                        self.rope_dim))],
            axis=-1)
        with jax.named_scope("attention_core"):
            helper = None
            if q.dtype != jnp.float64:
                helper = helpers.get_helper("attention")
            if helper is not None and helper.supports(t, q.shape[3]):
                return helper.attend(q, k, v, causal=True,
                                     scale=self.softmax_scale)
            return dot_product_attention(q, k, v, causal=True,
                                         scale=self.softmax_scale)

    def _absorbed_query(self, wk, q_nope, q_rope, width):
        """The query against latent rows of ``width`` columns, [B, T, H,
        width]: ``W_kvb``'s key half folded in, the rotary part beside it,
        zero over the pool's padding."""
        q_lat = jnp.einsum("bthd,chd->bthc", q_nope, wk)
        pad = width - self.kv_rank - self.rope_dim
        return jnp.concatenate(
            [q_lat, q_rope, jnp.zeros(q_rope.shape[:-1] + (pad,),
                                      q_rope.dtype)], axis=-1)

    def _absorbed(self, params, q_nope, q_rope, context, q_positions):
        """Attention of ``q`` rows at per-row ``q_positions`` [B, T] over
        latent rows ``context`` [B, L, W] whose index is their position (a
        gathered page view, zero past ``kv_rank + rope_dim``); returns
        [B, T, H, v_dim]."""
        wk, wv = self._kvb(params)
        acc = jnp.promote_types(q_nope.dtype, jnp.float32)
        q = self._absorbed_query(wk, q_nope, q_rope, context.shape[-1])
        with jax.named_scope("attention_core"):
            # scores leave the product in f32, as the flash kernel holds
            # them: rounded to bf16 ahead of the softmax they were the
            # largest single part of this path's distance from the f32
            # reference (PERF.md section 4, `correct`)
            s = jnp.einsum("bthc,blc->bhtl", q, context,
                           preferred_element_type=acc)
            s = s * jnp.asarray(self.softmax_scale, acc)
            seen = (q_positions[:, None, :, None]
                    >= jnp.arange(context.shape[1])[None, None, None, :])
            w = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1)
            o_lat = jnp.einsum("bhtl,blc->bthc", w.astype(context.dtype),
                               context[..., :self.kv_rank])
        return jnp.einsum("bthc,chd->bthd", o_lat, wv)

    def _absorbed_paged(self, params, q_nope, q_rope, pool, block,
                        q_positions, helper):
        """``_absorbed`` over the pages where they lie: the same query
        against the latent pool through the block table, one kernel
        (``helpers/paged_attention.py: paged_latent_attention``) in place
        of the gathered view, its scores and its softmax; only the rows'
        live blocks are read, each page once for keys and values."""
        wk, wv = self._kvb(params)
        q = self._absorbed_query(wk, q_nope, q_rope, pool.shape[-1])
        with jax.named_scope("attention_core"):
            o_lat = helper.attend_latent(
                q, pool, block, q_positions, v_width=self.kv_rank,
                scale=self.softmax_scale)
        return jnp.einsum("bthc,chd->bthd", o_lat.astype(q.dtype), wv)

    def path(self, t: int, from_zero: bool, page_size: int, dtype) -> str:
        """``latent_path`` of a paged call of ``t`` query positions a row
        on this layer as the process stands: with the kernel only if the
        helper seam offers it (helpers enabled, not float64) for a pool of
        these pages."""
        helper = None
        if jnp.dtype(dtype) != jnp.float64:
            helper = helpers.get_helper("paged_attention")
        return latent_path(t, from_zero, helper is not None
                           and helper.supports_latent(
                               self._pool_width, page_size, dtype))

    def serving_path(self, call) -> str:
        return self.path(call.t, call.from_zero, call.page_size, call.dtype)

    def describe_serving(self, call) -> Optional[str]:
        """How ``latent_paged_attention`` tiles the decode step, where the
        kernel runs it (the prefills attend the expanded way or over the
        gathered pages)."""
        from deeplearning4j_tpu.helpers import paged_attention as pa

        if (self.serving_path(call) != "paged"
                or pa.default_impl() != "pallas"):
            return None
        b, h, w, v = call.batch, self.n_heads, self._pool_width, self.kv_rank
        ps, pages, dtype = call.page_size, call.pages, jnp.dtype(call.dtype)
        ppb, _, vmem = pa.paged_tiling(b, 1, h, 1, w, ps, pages, dtype, v)
        return (f"latent_paged_attention q [{b}, 1, {h}, {w}] over {pages} "
                f"pages of {ps}, the value the first {v} columns: {ppb} "
                f"pages a block ({ppb * ps * w * dtype.itemsize / 2 ** 20:.2f}"
                f" MB a copy), grid ({b}, 1), {vmem / 2 ** 20:.2f} MB of "
                "VMEM")

    def _out(self, params, o, x):
        """Heads [B, T, H, v_dim] -> [B, T, n_out]: the per-head gate (from
        the layer's input ``x``) where there is one, then ``Wo``."""
        b, t = o.shape[:2]
        if self.gate is not None:
            with jax.named_scope("attn_gate"):
                g = jax.nn.sigmoid((x @ params["Wg"]).astype(jnp.float32))
                o = (o * g[..., None]).astype(o.dtype)
        return o.reshape(b, t, -1) @ params["Wo"]

    # ------------------------------------------------------------- forward
    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        if mask is not None:
            raise ValueError("LatentAttentionLayer takes no padding mask")
        x = self.maybe_dropout(x, train=train, rng=rng)
        with jax.named_scope("mla_attention"):
            q_nope, q_rope, latent = self._project(
                params, x, jnp.arange(x.shape[1]))
            y = self._out(params, self._expanded(params, q_nope, q_rope,
                                                 latent), x)
        return y, state

    def init_cache(self, batch: int, dtype=jnp.float32):
        raise NotImplementedError(
            "LatentAttentionLayer streams through the paged latent cache "
            "(GenerationEngine) only; it has no contiguous rnn_time_step "
            "cache")

    def init_paged_cache(self, num_pages: int, page_size: int,
                         dtype=jnp.float32, window_pages=None,
                         state_slots=None) -> Dict[str, jax.Array]:
        """ONE latent pool ``pc`` [num_pages, page_size, W]: page-major
        like ``SelfAttentionLayer``'s ``pk``/``pv`` and addressed through
        the same block tables, with no head axis (every head reads the same
        rows).  ``W`` is ``kv_rank + rope_dim`` rounded up to whole lanes
        of 128 (576 -> 640 at the published widths; the columns past the
        latent row stay zero): with a last axis that is not whole lanes the
        TPU's default layout for the pool puts the PAGE axis minor-most,
        and the compiler then re-lays the whole pool out before the
        scatter and again after it, every layer, every step."""
        return {"pc": jnp.zeros((num_pages, page_size, self._pool_width),
                                dtype)}

    @property
    def _pool_width(self) -> int:
        return -(-(self.kv_rank + self.rope_dim) // LANES) * LANES

    def apply_with_carry(self, params, state, x, carry, *, train=False,
                         rng=None, mask=None):
        """carry=None -> the full-sequence ``apply``.  With a paged carry
        (``pc`` + the dispatch's ``block`` / ``pos``): write the chunk's
        latent rows to its pages, then attend — a single token by the
        absorbed path; a longer chunk by the expanded path over its own
        tokens when every row starts at position 0, and else (a suffix
        behind a shared prefix) by the absorbed path over the pages, in
        blocks of ``ABSORBED_ROWS`` query rows."""
        if carry is None:
            y, st = self.apply(params, state, x, train=train, rng=rng,
                               mask=mask)
            return y, st, None
        if mask is not None:
            raise ValueError("paged latent attention takes no padding mask")
        block, pos = carry["block"], carry["pos"]          # [B, MAXP], [B]
        pool = carry["pc"]
        ps, t = pool.shape[1], x.shape[1]
        new_pos = pos[:, None] + jnp.arange(t, dtype=pos.dtype)
        with jax.named_scope("mla_attention"):
            q_nope, q_rope, latent = self._project(params, x, new_pos)
            page = jnp.take_along_axis(block, new_pos // ps,
                                       axis=1).reshape(-1)
            rows = latent.reshape(-1, latent.shape[-1]).astype(pool.dtype)
            pool = pool.at[page, (new_pos % ps).reshape(-1)].set(jnp.pad(
                rows, ((0, 0), (0, pool.shape[-1] - rows.shape[-1]))))

            def over_pages(qn, qr, qpos):
                # [B, MAXP, page, W] -> [B, L, W]: row index = position
                context = pool[block].reshape(
                    block.shape[0], -1, pool.shape[-1]).astype(x.dtype)
                return self._absorbed(params, qn, qr, context, qpos)

            if t == 1:
                if self.path(1, False, ps, x.dtype) == "paged":
                    o = self._absorbed_paged(
                        params, q_nope, q_rope, pool, block, new_pos,
                        helpers.get_helper("paged_attention"))
                else:
                    o = over_pages(q_nope, q_rope, new_pos)
            else:
                rows = ABSORBED_ROWS if t % ABSORBED_ROWS == 0 else t

                def in_blocks(_):
                    def split(a):   # [B, T, ...] -> [T / rows, B, rows, ...]
                        a = a.reshape(a.shape[0], t // rows, rows,
                                      *a.shape[2:])
                        return jnp.moveaxis(a, 1, 0)
                    o = jax.lax.map(lambda a: over_pages(*a),
                                    (split(q_nope), split(q_rope),
                                     split(new_pos)))
                    o = jnp.moveaxis(o, 0, 1)
                    return o.reshape(o.shape[0], t, *o.shape[3:])

                o = jax.lax.cond(
                    jnp.all(pos == 0),
                    lambda _: self._expanded(params, q_nope, q_rope, latent),
                    in_blocks, None)
            y = self._out(params, o, x)
        return y, state, {"pc": pool, "block": block, "pos": pos + t}
