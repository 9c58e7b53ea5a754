"""Fused paged decode attention (the ``gather_pages`` seam, fused).

ROADMAP item 1's decode half: the continuous-batching engine's hot loop
used to materialize every row's logical KV view from the page pool
(``gather_pages`` -> ``paged_attention`` in ``nn/layers/attention.py``)
— a ``[B, MAXP*page_size, Hkv, D]`` round trip through HBM per layer
per decode step, just to immediately reduce it through a softmax.  This
module computes the same per-row causal attention DIRECTLY from the
page pool + int32 block tables, streaming pages block-by-block
with the online-softmax recurrence (running row-max ``m``, normaliser
``l`` — the flash-attention scheme, see ``helpers/flash_attention.py``),
so the gathered view is never built.

Two implementations behind one public op:

- ``impl="pallas"`` (default on TPU): a Pallas kernel on the grid
  ``(B, Hkv, MAXP)`` whose sequential page axis carries the softmax
  scratch.  The block table and each row's highest query position ride
  scalar prefetch (``PrefetchScalarGridSpec``), so each page's HBM->VMEM
  DMA — one contiguous ``(page_size, D)`` tile of the
  ``[P, Hkv, page_size, D]`` pool — is issued straight off
  ``block[b, p]``: the kernel IS the gather.  Pages that
  lie wholly above every live position of a row batch are skipped:
  their compute is predicated off and their DMA index clamps to the
  last live page (the Pallas pipeline elides copies whose index did
  not change), so a 3-page row in a 32-page table pays for 3 pages.
- ``impl="lax"`` (default elsewhere): a compiled ``lax.fori_loop`` over
  pages with the same online-softmax accumulator, gathering only one
  ``[B, Hkv, page_size, D]`` page slab per iteration.  The loop bound
  is the live-page watermark ``max(q_positions)//page_size + 1`` — a
  traced value (no recompiles; decode is inference-only so the dynamic
  ``while_loop`` lowering needs no reverse pass), which is where the
  measured CPU decode win comes from: the legacy gather always pays
  all MAXP pages.

Semantics match the legacy pair exactly (the flag-selectable oracle):
GQA contracts the UNEXPANDED kv heads, masking is per-row
``q_positions >= key_position`` where a key's global position is its
logical slot index ``p*page_size + i`` — which also hides unwritten
pages and trash-page-0 padding entries (their logical slots sit past
the row's position).  See docs/serving.md "The fused decode kernel"
for the seam contract, including the plan to dequantize int8/fp8 pages
(ROADMAP item 3) inside this kernel.

Mode toggle (trace-time, like ``enable_helpers``):
``set_paged_attention_mode("gather")`` or env DL4J_TPU_PAGED_GATHER=1
routes ``SelfAttentionLayer._apply_paged`` back through the legacy
gather+softmax path — the bit-compatible oracle the parity tests and
the bench's before/after arm compare against.
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.helpers import interpret_mode as _interpret

LANES = 128
NEG_INF = -1e30

_VALID_MODES = ("fused", "gather")
_mode = ("gather" if os.environ.get("DL4J_TPU_PAGED_GATHER", "0") == "1"
         else "fused")


def set_paged_attention_mode(mode: str) -> None:
    """Select the paged decode path: ``"fused"`` (default — this module)
    or ``"gather"`` (the legacy gather+softmax oracle).  NOTE: routing
    happens at TRACE time; already-compiled decode programs (a started
    GenerationEngine's warmed program set) keep whichever path they were
    traced with — toggle BEFORE building the engine."""
    if mode not in _VALID_MODES:
        raise ValueError(f"paged attention mode {mode!r} not in "
                         f"{_VALID_MODES}")
    global _mode
    _mode = mode


def paged_attention_mode() -> str:
    return _mode


def _sds(shape, dtype, like):
    """ShapeDtypeStruct carrying ``like``'s varying-mesh-axes set (see
    flash_attention._sds)."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(like).vma)


def _dot_f32(a, b, trans_b=False):
    cb = 1 if trans_b else 0
    return jax.lax.dot_general(
        a, b, (((1,), (cb,)), ((), ())),
        preferred_element_type=jnp.float32)


def _check_shapes(q, pk, pv, block, q_positions):
    b, t, hq, d = q.shape
    if pk.ndim != 4 or pk.shape != pv.shape or pk.shape[3] != d:
        raise ValueError(
            f"paged pools must be [P, Hkv, page_size, D={d}]; got "
            f"pk {pk.shape}, pv {pv.shape}")
    hkv = pk.shape[1]
    if hq % hkv:
        raise ValueError(f"q heads {hq} not a multiple of kv heads {hkv}")
    if block.shape[0] != b or block.ndim != 2:
        raise ValueError(
            f"block table {block.shape} does not match batch {b}")
    if q_positions.shape != (b, t):
        raise ValueError(
            f"q_positions {q_positions.shape} must be [B, T] = {(b, t)}")


# ---------------------------------------------------------------------------
# lax fallback: fori_loop over live pages, online softmax
# ---------------------------------------------------------------------------

def _lax_paged(q, pk, pv, block, q_positions):
    """Compiled page-streaming fallback for non-TPU backends.  One
    ``[B, Hkv, page_size, D]`` slab in flight at a time; loop bound is
    the dynamic live-page watermark (traced -> while_loop -> zero
    steady-state recompiles)."""
    b, t, hq, d = q.shape
    hkv, page_size = pk.shape[1], pk.shape[2]
    g = hq // hkv
    maxp = block.shape[1]
    acc_dt = jnp.promote_types(q.dtype, jnp.float32)
    scale = 1.0 / (d ** 0.5)
    offs = jnp.arange(page_size, dtype=block.dtype)
    # [B, T, Hkv, G, D] — contract the UNEXPANDED kv heads (GQA)
    qg = q.reshape(b, t, hkv, g, d).astype(acc_dt)
    m0 = jnp.full((b, hkv, g, t), NEG_INF, acc_dt)
    l0 = jnp.zeros((b, hkv, g, t), acc_dt)
    a0 = jnp.zeros((b, t, hkv, g, d), acc_dt)

    def body(p, carry):
        m, l, acc = carry
        k = pk[block[:, p]].astype(acc_dt)            # [B, Hkv, ps, D]
        v = pv[block[:, p]].astype(acc_dt)
        kpos = p * page_size + offs
        s = jnp.einsum("bthgd,bhkd->bhgtk", qg, k) * scale
        keep = (q_positions[:, None, None, :, None]
                >= kpos[None, None, None, None, :])
        s = jnp.where(keep, s, NEG_INF)
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, m_cur)
        p_exp = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p_exp, axis=-1)
        acc_new = (acc * alpha.transpose(0, 3, 1, 2)[..., None]
                   + jnp.einsum("bhgtk,bhkd->bthgd", p_exp, v))
        return m_new, l_new, acc_new

    live = jnp.minimum(jnp.max(q_positions) // page_size + 1, maxp)
    m, l, acc = jax.lax.fori_loop(0, live, body, (m0, l0, a0))
    safe = jnp.where(l > 0, l, 1.0)                   # NaN-safe idle rows
    o = acc / safe.transpose(0, 3, 1, 2)[..., None]
    return o.reshape(b, t, hq, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas kernel: grid (B, Hkv, MAXP), scalar-prefetched block table
# ---------------------------------------------------------------------------

def _decode_kernel(blk_ref, qmax_ref, qp_ref, q_ref, k_ref, v_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, scale, page_size):
    b = pl.program_id(0)
    p = pl.program_id(2)
    npages = pl.num_programs(2)

    @pl.when(p == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # pages wholly above every row position contribute nothing; their
    # DMA already clamped to the last live page (kv_idx in _pallas_paged)
    run = p * page_size <= qmax_ref[b]

    @pl.when(run)
    def _step():
        s = _dot_f32(q_ref[:], k_ref[:], trans_b=True) * scale  # [R, ps]
        kpos = p * page_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        # per-row global query positions, lane-broadcast like m/l
        s = jnp.where(qp_ref[:, :1] >= kpos, s, NEG_INF)
        m_prev = m_scr[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p_exp = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_scr[:, :1] + jnp.sum(p_exp, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + _dot_f32(
            p_exp.astype(v_ref.dtype), v_ref[:])
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(p == npages - 1)
    def _finish():
        l = l_scr[:, :1]
        safe = jnp.where(l > 0, l, 1.0)               # idle / trash rows
        o_ref[:] = (acc_scr[:] / safe).astype(o_ref.dtype)


def _sublanes(dtype) -> int:
    """Rows of one TPU tile for ``dtype``: 8 at 32 bits, 16 at 16, 32 at
    8 (narrower types pack along sublanes)."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def _pallas_paged(q, pk, pv, block, q_positions, interpret):
    b, t, hq, d = q.shape
    hkv, page_size = pk.shape[1], pk.shape[2]
    g = hq // hkv
    gt = g * t
    maxp = block.shape[1]
    scale = 1.0 / (d ** 0.5)
    if not interpret and page_size % _sublanes(pk.dtype):
        raise ValueError(
            f"page_size={page_size} cannot tile a {pk.dtype} KV pool on "
            f"TPU: one page is one (page_size, D) tile per kv head, so "
            f"page_size must be a multiple of {_sublanes(pk.dtype)} for "
            "this dtype")
    dp = (-d) % LANES
    if dp:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, dp)))
        pk = jnp.pad(pk, ((0, 0), (0, 0), (0, 0), (0, dp)))
        pv = jnp.pad(pv, ((0, 0), (0, 0), (0, 0), (0, dp)))
    dpad = d + dp
    # [B, Hkv, G*T, D]: one grid step owns one (batch row, kv head); its
    # q rows are laid out [G, T] flattened (t = row % T).  Rows pad up to
    # whole tiles (MHA decode has G*T = 1); padded rows sit at position 0
    # and are sliced off below.
    rows = -(-gt // _sublanes(q.dtype)) * _sublanes(q.dtype)
    qb = (q.reshape(b, t, hkv, g, dpad).transpose(0, 2, 3, 1, 4)
          .reshape(b, hkv, gt, dpad))
    qb = jnp.pad(qb, ((0, 0), (0, 0), (0, rows - gt), (0, 0)))
    qpos = q_positions.astype(jnp.int32)
    qrows = jnp.pad(jnp.tile(qpos, (1, g)), ((0, 0), (0, rows - gt)))
    qrows = jnp.broadcast_to(qrows[:, :, None], (b, rows, LANES))
    qmax = jnp.max(qpos, axis=1)

    def kv_idx(bi, h, p, blk, qmax):
        # dead pages clamp to the last live one so their copies are elided
        return (blk[bi, jnp.minimum(p, qmax[bi] // page_size)], h, 0, 0)

    def row_idx(bi, h, p, blk, qmax):
        return (bi, h, 0, 0)

    kern = functools.partial(_decode_kernel, scale=scale,
                             page_size=page_size)
    o = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, hkv, maxp),
            in_specs=[
                pl.BlockSpec((None, rows, LANES),
                             lambda bi, h, p, blk, qmax: (bi, 0, 0)),
                pl.BlockSpec((None, None, rows, dpad), row_idx),
                pl.BlockSpec((None, None, page_size, dpad), kv_idx),
                pl.BlockSpec((None, None, page_size, dpad), kv_idx),
            ],
            out_specs=pl.BlockSpec((None, None, rows, dpad), row_idx),
            scratch_shapes=[
                pltpu.VMEM((rows, LANES), jnp.float32),
                pltpu.VMEM((rows, LANES), jnp.float32),
                pltpu.VMEM((rows, dpad), jnp.float32),
            ],
        ),
        out_shape=_sds((b, hkv, rows, dpad), q.dtype, q),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="fused_paged_attention",
    )(block.astype(jnp.int32), qmax, qrows, qb, pk, pv)
    o = (o[:, :, :gt].reshape(b, hkv, g, t, dpad).transpose(0, 3, 1, 2, 4)
         .reshape(b, t, hq, dpad))
    return o[..., :d] if dp else o


# ---------------------------------------------------------------------------
# public op
# ---------------------------------------------------------------------------

def paged_decode_attention(q: jax.Array, pk: jax.Array, pv: jax.Array,
                           block: jax.Array, q_positions: jax.Array, *,
                           impl: Optional[str] = None,
                           interpret: Optional[bool] = None) -> jax.Array:
    """Per-row causal attention of ``q`` [B, T, Hq, D] directly over the
    page pool ``pk``/``pv`` [P, Hkv, page_size, D] through the int32
    block table ``block`` [B, MAXP] — never materializing the gathered
    [B, MAXP*page_size, Hkv, D] view.

    A key's global position is its logical slot index
    ``p * page_size + i``; masking is ``q_positions >= key position``
    per row, which (exactly as the legacy ``paged_attention`` documents)
    also hides unwritten pages and trash-page-0 padding entries.  GQA
    contracts the unexpanded kv heads.

    ``impl``: None picks ``"pallas"`` on TPU and ``"lax"`` elsewhere;
    ``"gather"`` routes through the legacy gather+softmax pair (the
    bit-compatible oracle).  ``interpret`` only applies to the Pallas
    path (defaults to the package policy: interpret off-TPU).
    """
    _check_shapes(q, pk, pv, block, q_positions)
    if impl is None:
        impl = "pallas" if jax.default_backend() == "tpu" else "lax"
    if impl == "gather":
        from deeplearning4j_tpu.nn.layers.attention import (
            gather_pages, paged_attention)

        gk = gather_pages(pk, block).astype(q.dtype)
        gv = gather_pages(pv, block).astype(q.dtype)
        return paged_attention(q, gk, gv, q_positions)
    if impl == "lax":
        return _lax_paged(q, pk, pv, block, q_positions)
    if impl != "pallas":
        raise ValueError(f"impl={impl!r} not one of pallas/lax/gather")
    if interpret is None:
        interpret = _interpret()
    return _pallas_paged(q, pk, pv, block, q_positions, interpret)


class PagedAttentionHelper:
    """Discovery-seam wrapper for the paged decode path (≙ the cuDNN
    helper SPI, like FlashAttentionHelper): ``SelfAttentionLayer.
    _apply_paged`` asks ``helpers.get_helper("paged_attention")`` and
    falls back to the legacy gather+softmax pair when this returns
    unsupported.  Unlike the flash helper, the fused path is the
    DEFAULT on every backend — off TPU it routes to the compiled lax
    page-streaming fallback, not the Pallas interpreter, so CPU decode
    gets the live-page watermark win too."""

    name = "PagedAttentionHelper"

    def supports(self, q, page_size: int) -> bool:
        return paged_attention_mode() == "fused"

    def attend(self, q, pk, pv, block, q_positions) -> jax.Array:
        return paged_decode_attention(q, pk, pv, block, q_positions)
