"""Fused paged attention (the ``gather_pages`` seam, fused).

The continuous-batching engine's hot loop used to materialize every
row's logical KV view from the page pool (``gather_pages`` ->
``paged_attention`` in ``nn/layers/attention.py``) — a
``[B, MAXP*page_size, Hkv, D]`` round trip through HBM per layer per
step, just to immediately reduce it through a softmax.  This module
computes the same per-row causal attention DIRECTLY from the page pool +
int32 block tables, streaming pages with the online-softmax recurrence
(running row-max ``m``, normaliser ``l`` — the flash-attention scheme,
see ``helpers/flash_attention.py``), so the gathered view is never
built.  The op serves every paged call of ``SelfAttentionLayer``: the
decode step (``[slots, 1]``) and each prefill chunk (``[1, bucket]``,
behind a shared prefix or not).

Two implementations behind one public op:

- ``impl="pallas"`` (default on TPU): a Pallas kernel on the grid
  ``(B, row tiles)``.  A ROW TILE is ``row_tile`` consecutive query
  positions of one batch row with all their heads: per kv head the
  ``G = Hq // Hkv`` query heads stack into ``G * row_tile`` rows, so GQA
  multiplies the unexpanded K once for the whole group.  A BLOCK is
  ``pages_per_block`` pages of the row, about 128 key positions (8 pages
  of 16, 2 of 64, never more than the table has), so scores are
  ``[rows, 128]``: whole lanes.  The pools stay in HBM
  (``memory_space=pl.ANY``); the block table and each tile's highest
  query position ride scalar prefetch; a page of every kv head —
  ``[Hkv, page_size, D]`` is one contiguous slab of the
  ``[P, Hkv, page_size, D]`` pool — comes in by one
  ``pltpu.make_async_copy`` into a two-slot VMEM buffer, the next block
  in flight while the current one is multiplied.  Dead-block rule: a
  tile's loop over blocks ends at the block that holds its highest
  position, so a 3-block row in a 36-page table pays for 3 blocks, and a
  tile of a prefill chunk skips the blocks wholly above it (causal
  skip); inside the last live block the per-row position mask does the
  rest.  ``paged_tiling`` picks both parameters from the shapes alone,
  ``row_tile`` the largest that keeps one grid step's buffers within
  ``VMEM_BUDGET`` (8 MB of the chip's 16 MB scoped limit) at any group
  size and bucket; no argument or environment variable tunes it.
  That is the ``"rows"`` form.  Where each kv head has ONE query row
  (``t == 1`` and ``Hq == Hkv``: multi-head attention's decode step) a
  kv head's tile would be one real row in a padded sublane tile and the
  block's work 30 short chains of two products and a softmax, one a
  head, bound by their latencies and not by the copy they overlap.  The
  ``"heads"`` form (``paged_form``, from the shapes alone) stacks every
  kv head's row into ONE tile instead: a block's pages land head-major
  in VMEM, so K of every head is one ``[Hkv * bk, D]`` operand; one
  product gives each row its scores against all heads' keys, an iota
  mask keeps the row's own head's, one online-softmax update serves all
  heads, and one product of the block-diagonal probabilities with V
  updates every head's accumulator.  Copies, dead-block rule, masking
  and f32 arithmetic are the ``"rows"`` form's.  At
  ``olmoh.serve-think``'s decode shape (30 heads, pages of 64, 128
  lanes) a call took 3.20 ms in ``"rows"`` and takes 1.76 in
  ``"heads"``, against 1.64 for a kernel that only copies the same
  blocks (one TPU v5e; PERF.md §6, the ``heads`` form).
- ``impl="lax"`` (default elsewhere): a compiled ``lax.fori_loop`` over
  pages with the same online-softmax accumulator, gathering only one
  ``[B, Hkv, page_size, D]`` page slab per iteration.  The loop bound
  is the live-page watermark ``max(q_positions)//page_size + 1`` — a
  traced value (no recompiles; decode is inference-only so the dynamic
  ``while_loop`` lowering needs no reverse pass), which is where the
  measured CPU decode win comes from: the legacy gather always pays
  all MAXP pages.

A WINDOW layer's pool is addressed through a RING table instead
(``window=``; ``SelfAttentionLayer.init_paged_cache``): ``R = ceil(window
/ page_size) + 1`` pages a row, position ``p`` in column ``(p //
page_size) % R``.  The three implementations then recover each column's
logical page from the row's highest query position (``ring_pages``; for
the kernel a third scalar prefetch), give every key its absolute
position, and mask to ``q - window < k <= q``; a row's live blocks are the
columns it has reached, all of the ring once it has wrapped.  The engine
calls it so for decoded tokens only (a window layer's prefill chunk
attends over its own keys).

A LATENT pool (``LatentAttentionLayer``: ``pc`` [P, page_size, W], one
latent row a token, read by every head) is this layout with one kv head,
and its value is a prefix of its key: ``paged_latent_attention`` runs the
same kernel (named ``latent_paged_attention`` there) and the same lax loop
with ``v_width`` set — no V pool and no V buffer, the second product
against the first ``v_width`` columns of the key slab just copied, so a
page crosses from HBM once — a scale handed in, and a block sized by its
bytes (``paged_tiling(v_width=)``, ``SLAB_BLOCK_BYTES``).  It serves the
layer's single-token step; the oracle there is the layer's own
``pool[block]`` + ``_absorbed``.  See docs/serving.md "Latent pages while
decoding".

Semantics match the legacy pair exactly (the oracle the tests compare
against, and the path where the seam gives way):
GQA contracts the UNEXPANDED kv heads, masking is per-row
``q_positions >= key_position`` where a key's global position is its
logical slot index ``p*page_size + i`` — which also hides unwritten
pages and trash-page-0 padding entries (their logical slots sit past
the row's position).  See docs/serving.md "The fused decode kernel"
for the seam contract, including the plan to dequantize int8/fp8 pages
(ROADMAP item 3) inside this kernel.

Where the seam gives way (helpers disabled, or no ``paged_attention``
helper) ``SelfAttentionLayer``'s paged calls take the legacy
gather+softmax pair and ``LatentAttentionLayer``'s single-token step its
own gather (``paged_path``, ``latent_path``).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.helpers import interpret_mode as _interpret

LANES = 128
NEG_INF = -1e30

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
# the pool's layout: what writes a page and what reads it agree on here
# ---------------------------------------------------------------------------

# leaves of a WINDOW layer's pool (``SelfAttentionLayer.init_paged_cache``);
# every other pool is of the global kind
WINDOW_POOL_LEAVES = ("wk", "wv")
# leaves of a STATE pool (``MambaLayer.init_paged_cache``, ``GravesLSTM``'s):
# one row a slot, not pages
STATE_POOL_LEAVES = ("sh", "sc")


def pool_kind(pool) -> str:
    """The kind of a layer's pool dict, one of three: ``"window"`` where
    a request holds a ring of pages, ``"state"`` where it holds one row of
    recurrent state for its life (addressed by its slot, not by the block
    table), ``"global"`` where it holds its whole context in pages."""
    if any(n in pool for n in WINDOW_POOL_LEAVES):
        return "window"
    if any(n in pool for n in STATE_POOL_LEAVES):
        return "state"
    return "global"


def write_token_rows(pool: jax.Array, page: jax.Array, off: jax.Array,
                     rows: jax.Array) -> jax.Array:
    """``pool`` [P, Hkv, page_size, D] with ``rows`` [N, Hkv, D] written at
    ``(page[n], :, off[n], :)``, as a scatter of ``N * Hkv`` whole rows into
    the pool seen as a table ``[P * Hkv * page_size, D]``.  The 4-D scatter
    ``pool.at[page, :, off].set(rows)`` says the same, but the TPU compiler
    re-lays the WHOLE pool out token-major ahead of it and back after it
    (two pool-sized copies a pool: 0.57 GB each at 4,353 pages of 8 x 64 x
    128 bf16; compiled for a described v5e, PERF.md PR 33); rows of a table
    scatter in place."""
    p_, hkv, ps, d = pool.shape
    idx = ((page[:, None] * hkv + jnp.arange(hkv, dtype=page.dtype)[None, :])
           * ps + off[:, None]).reshape(-1)
    flat = pool.reshape(p_ * hkv * ps, d).at[idx].set(
        rows.reshape(-1, d).astype(pool.dtype))
    return flat.reshape(pool.shape)


def ring_column(positions: jax.Array, page_size: int, ring: int) -> jax.Array:
    """The column of a row's RING table that holds ``positions``: a window
    layer's pool keeps ``ring`` pages a slot, written modulo."""
    return (positions // page_size) % ring


def ring_pages(q_positions: jax.Array, page_size: int,
               ring: int) -> jax.Array:
    """[B, R] int32: the logical page each column of a row's ring table
    holds (``ring_column`` read backwards).  Once a row has been written up
    to its highest query position, column ``r`` holds the newest logical
    page ``L <= that position // page_size`` with ``L % R == r`` --
    negative where the row has not reached column ``r`` yet (never written;
    the ``kpos >= 0`` mask hides it)."""
    top = jnp.max(q_positions, axis=1).astype(jnp.int32) // page_size
    r = jnp.arange(ring, dtype=jnp.int32)
    return top[:, None] - (top[:, None] - r[None, :]) % ring


# ---------------------------------------------------------------------------
# lax fallback: fori_loop over live pages, online softmax
# ---------------------------------------------------------------------------

def _lax_paged(q, pk, pv, block, q_positions, window=None, scale=None,
               v_width=None):
    """Compiled page-streaming fallback for non-TPU backends.  One
    ``[B, Hkv, page_size, D]`` slab in flight at a time; loop bound is
    the dynamic live-page watermark (traced -> while_loop -> zero
    steady-state recompiles).  With ``window`` the table is a ring
    (``ring_pages`` gives each column's logical page).  With ``v_width``
    there is no ``pv``: a page's value is the first ``v_width`` columns
    of its key slab (a latent pool), and the result is that wide."""
    b, t, hq, d = q.shape
    hkv, page_size = pk.shape[1], pk.shape[2]
    g = hq // hkv
    maxp = block.shape[1]
    acc_dt = jnp.promote_types(q.dtype, jnp.float32)
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    dv = d if v_width is None else v_width
    offs = jnp.arange(page_size, dtype=block.dtype)
    # [B, T, Hkv, G, D] — contract the UNEXPANDED kv heads (GQA)
    qg = q.reshape(b, t, hkv, g, d).astype(acc_dt)
    m0 = jnp.full((b, hkv, g, t), NEG_INF, acc_dt)
    l0 = jnp.zeros((b, hkv, g, t), acc_dt)
    a0 = jnp.zeros((b, t, hkv, g, dv), acc_dt)
    if window is not None:
        lpage = ring_pages(q_positions, page_size, maxp)      # [B, R]

    def body(p, carry):
        m, l, acc = carry
        k = pk[block[:, p]].astype(acc_dt)            # [B, Hkv, ps, D]
        v = (pv[block[:, p]].astype(acc_dt) if v_width is None
             else k[..., :v_width])
        kpos = p * page_size + offs
        s = jnp.einsum("bthgd,bhkd->bhgtk", qg, k) * scale
        qp = q_positions[:, None, None, :, None]
        if window is None:
            keep = qp >= kpos[None, None, None, None, :]
        else:
            kpos = (jax.lax.dynamic_index_in_dim(lpage, p, 1) * page_size
                    + offs[None, :])[:, None, None, None, :]
            keep = (qp >= kpos) & (kpos >= 0) & (kpos > qp - window)
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
    return o.reshape(b, t, hq, dv).astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas kernel: grid (B, row tiles), a block of pages a loop step
# ---------------------------------------------------------------------------

BLOCK_KEYS = 128             # key positions a block of pages aims at
VMEM_BUDGET = 8 * 2 ** 20    # bytes one grid step's buffers may take
# bytes of one block of a latent pool's pages (``paged_tiling(v_width=)``):
# 8 pages of 64 x 640 bf16.  A LatentAttentionLayer decode call alone on
# the chip (PERF.md PR 38; ms a layer at Xing's / k2's shape, the gather
# 1.45 / 1.81): 0.47 / 0.80 at 2 pages (what BLOCK_KEYS alone gives), 0.36
# / 0.66 at 4, 0.33 / 0.63 at 6, 0.33 / 0.61 at 8, 0.35 / 0.62 at 12
SLAB_BLOCK_BYTES = 640 * 2 ** 10


def _sublanes(dtype) -> int:
    """Rows of one TPU tile for ``dtype``: 8 at 32 bits, 16 at 16, 32 at
    8 (narrower types pack along sublanes)."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


# how a program attends over a SelfAttentionLayer's pages: the kernel in one
# of its two forms (``paged_form``), the compiled lax page loop, or the
# gather oracle
PAGED_PATHS = ("heads", "rows", "lax", "gather")


def paged_form(t: int, hq: int, hkv: int, page_size: int, maxp: int,
               window: Optional[int] = None,
               v_width: Optional[int] = None) -> str:
    """How the Pallas kernel lays out its rows, from the shapes alone:
    ``"heads"`` where each kv head has ONE query row (``t == 1``, a group
    of one: multi-head attention's decode step) over a global pool whose
    block is whole lanes of keys — every kv head's row stacked in one tile,
    one product for all heads a block; ``"rows"`` otherwise — each kv
    head's ``G * row_tile`` rows in a tile of their own (a window's ring
    and a latent pool always take it)."""
    bk = max(1, min(BLOCK_KEYS // page_size, maxp)) * page_size   # a block
    if (t == 1 and hq == hkv and window is None and v_width is None
            and bk % LANES == 0):
        return "heads"
    return "rows"


def paged_path(t: int, hq: int, hkv: int, page_size: int, maxp: int,
               window: Optional[int] = None) -> str:
    """How a paged call of ``SelfAttentionLayer`` attends, one of
    ``PAGED_PATHS``: the gather oracle where the seam gives way (helpers
    disabled, or no helper), the lax page loop off the TPU, else the kernel
    in its ``paged_form``.  The rule ``SelfAttentionLayer``'s paged
    branches follow when a program is traced; the engine counts
    ``dl4j_layer_path_steps_total`` by it."""
    from deeplearning4j_tpu.helpers import get_helper

    if get_helper("paged_attention") is None:
        return "gather"
    if default_impl() != "pallas":
        return "lax"
    return paged_form(t, hq, hkv, page_size, maxp, window)


def paged_tiling(b: int, t: int, hq: int, hkv: int, d: int, page_size: int,
                 maxp: int, dtype, v_width: Optional[int] = None,
                 window: Optional[int] = None) -> Tuple[int, int, int]:
    """How the Pallas kernel tiles ``q`` [b, t, hq, d] over pools
    [P, hkv, page_size, d] of ``dtype`` behind a block table [b, maxp]:
    ``(pages_per_block, row_tile, vmem_bytes)``.

    ``pages_per_block`` pages (about ``BLOCK_KEYS`` key positions, never
    more than the table has) are copied and multiplied per loop step.
    ``row_tile`` query positions of the ``t`` form one grid step — with
    the ``hq // hkv`` query heads of a kv head stacked, ``G * row_tile``
    rows a kv head — and it is the largest power-of-two multiple of the
    dtype's sublane tile (or ``t`` itself when that is smaller) whose
    ``vmem_bytes`` stay within ``VMEM_BUDGET``: the q and output tiles
    (double-buffered by the pipeline), the per-row positions, the f32
    softmax state (m, l, accumulator) of every kv head, the two-slot K
    and V page buffers, and one head's scores and probabilities.  The
    grid is ``(b, cdiv(t, row_tile))``.  A pure function of the shapes:
    the kernel calls it, and so can whoever wants to know how it engaged.

    ``v_width`` (a latent pool: no V pool, a page's value is the first
    ``v_width`` columns of its key slab) sizes the block by its BYTES
    instead: a latent pool has one kv head, so ``BLOCK_KEYS`` keys are a
    fifth of what a GQA block moves, and the fixed cost of a loop step
    (its copies' issue and wait, the softmax state's update) would be most
    of the step.  A block is then the pages ``SLAB_BLOCK_BYTES`` hold
    (never under ``BLOCK_KEYS`` keys, never more than the table has).

    In the ``"heads"`` form (``paged_form``) the row tile is the one
    position and the buffers are the page slots, the tile of every kv
    head's row (q, output, f32 softmax state) and the scores and
    probabilities of all heads' keys of a block.
    """
    item = jnp.dtype(dtype).itemsize
    sub = _sublanes(dtype)
    g = hq // hkv
    dpad = _round_up(d, LANES)
    ppb = BLOCK_KEYS // page_size
    pools = 2                                          # K and V
    dvpad = dpad
    if v_width is not None:
        ppb = max(ppb, SLAB_BLOCK_BYTES // (hkv * page_size * dpad * item))
        pools, dvpad = 1, _round_up(v_width, LANES)
    ppb = max(1, min(ppb, maxp))
    bk = ppb * page_size
    pages = 2 * pools * ppb * hkv * page_size * dpad * item
    if paged_form(t, hq, hkv, page_size, maxp, window, v_width) == "heads":
        rows = _round_up(hkv, sub)
        return ppb, 1, (pages + rows * (2 * 2 * dpad * item    # q, o
                                        + dpad * 4 + 2 * LANES * 4
                                        + hkv * bk * (4 + 4 + item)))

    def vmem(tq):
        rows = _round_up(g * tq, sub)
        state = dvpad * 4 + 2 * LANES * 4              # acc, m, l
        tiles = 2 * (dpad + dvpad) * item              # q and o, 2 buffers
        return (pages + hkv * rows * (state + tiles)
                + 2 * rows * LANES * 4                 # positions
                + rows * bk * (4 + 4 + item))          # s, p, p cast

    tq = min(t, sub)
    while tq * 2 <= t and vmem(tq * 2) <= VMEM_BUDGET:
        tq *= 2
    return ppb, tq, vmem(tq)


def _paged_kernel(blk_ref, qmax_ref, *refs, scale, page_size, maxp,
                  window=None, value_in_key=False):
    if window is not None:
        # a ring table: the logical page of each of its columns rides a
        # third scalar prefetch (``ring_pages``)
        lp_ref, *refs = refs
    if value_in_key:
        # a latent pool: the value is a prefix of the key slab, so a page
        # is copied once and there is no V pool and no V buffer
        (qp_ref, q_ref, k_hbm, o_ref,
         kbuf, sem, m_scr, l_scr, acc_scr) = refs
    else:
        (qp_ref, q_ref, k_hbm, v_hbm, o_ref,
         kbuf, vbuf, sem, m_scr, l_scr, acc_scr) = refs
    b, ti = pl.program_id(0), pl.program_id(1)
    _, ppb, hkv, _, dpad = kbuf.shape
    rows = q_ref.shape[1]
    bk = ppb * page_size
    qmax = qmax_ref[b * pl.num_programs(1) + ti]
    if window is None:
        # live blocks of this row tile: up to the one holding its highest
        # query position (a dead block costs neither a copy nor a step)
        nblk = jnp.minimum(qmax // bk + 1, pl.cdiv(maxp, ppb))
    else:
        # the columns the row has reached (qmax is the ROW's highest
        # position here): all of the ring once it has wrapped
        nblk = (jnp.minimum(qmax // page_size + 1, maxp) + ppb - 1) // ppb

    def copies(j, slot):
        out = []
        for i in range(ppb):
            p = j * ppb + i
            if maxp % ppb:
                # the table is not whole blocks: slots past its end repeat
                # the last page, under key positions no query reaches
                p = jnp.minimum(p, maxp - 1)
            page = blk_ref[b * maxp + p]
            out.append(pltpu.make_async_copy(
                k_hbm.at[page], kbuf.at[slot, i], sem.at[0, slot]))
            if not value_in_key:
                out.append(pltpu.make_async_copy(
                    v_hbm.at[page], vbuf.at[slot, i], sem.at[1, slot]))
        return out

    for c in copies(0, 0):
        c.start()
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)

    def block_step(j, _):
        slot = j % 2

        @pl.when(j + 1 < nblk)
        def _prefetch():
            for c in copies(j + 1, 1 - slot):
                c.start()

        for c in copies(j, slot):
            c.wait()
        if window is None:
            kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (rows, bk), 1)
            # per-row global query positions, lane-broadcast like m/l
            keep = qp_ref[:, :1] >= kpos
        else:
            col = jax.lax.broadcasted_iota(jnp.int32, (rows, bk), 1)
            qp = qp_ref[:, :1]
            # a column past the table's end repeats the last page (see
            # copies): its keys get no position
            kpos = jnp.full((rows, bk), -1, jnp.int32)
            for i in range(ppb):
                p = j * ppb + i
                if maxp % ppb:
                    first = jnp.where(
                        p < maxp,
                        lp_ref[b * maxp + jnp.minimum(p, maxp - 1)]
                        * page_size, -(2 ** 30))
                else:
                    first = lp_ref[b * maxp + p] * page_size
                kpos = jnp.where(
                    (col >= i * page_size) & (col < (i + 1) * page_size),
                    first + col - i * page_size, kpos)
            keep = (qp >= kpos) & (kpos >= 0) & (kpos > qp - window)
        for h in range(hkv):
            k = kbuf[slot, :, h].reshape(bk, dpad)
            v = (k[:, :acc_scr.shape[2]] if value_in_key
                 else vbuf[slot, :, h].reshape(bk, dpad))
            s = _dot_f32(q_ref[h], k, trans_b=True) * scale   # [rows, bk]
            s = jnp.where(keep, s, NEG_INF)
            m_prev = m_scr[h, :, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p_exp = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_new = (alpha * l_scr[h, :, :1]
                     + jnp.sum(p_exp, axis=1, keepdims=True))
            acc_scr[h] = acc_scr[h] * alpha + _dot_f32(
                p_exp.astype(v.dtype), v)
            m_scr[h] = jnp.broadcast_to(m_new, m_scr.shape[1:])
            l_scr[h] = jnp.broadcast_to(l_new, l_scr.shape[1:])

    jax.lax.fori_loop(0, nblk, block_step, None)
    l = l_scr[:, :, :1]
    safe = jnp.where(l > 0, l, 1.0)                   # idle / trash rows
    o_ref[...] = (acc_scr[...] / safe).astype(o_ref.dtype)


def _heads_kernel(blk_ref, qpos_ref, q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf,
                  sem, m_scr, l_scr, acc_scr, *, scale, page_size, maxp):
    """The ``"heads"`` form: one query row a kv head, the rows of ``q_ref``
    [rows, D] being the heads.  A block's pages land head-major in
    ``kbuf`` [2, Hkv, bk, D], so K of every head is one [Hkv * bk, D]
    operand: one product gives every row's scores against every head's
    keys, of which row ``h`` keeps its own ``bk`` columns; the online
    softmax runs once for all heads; the probabilities go back into row
    ``h``'s own columns of a block-diagonal [rows, Hkv * bk] (zeros
    elsewhere) for one product with V.  Copies, dead-block rule and
    masking are the ``"rows"`` form's."""
    b = pl.program_id(0)
    _, hkv, bk, dpad = kbuf.shape
    ppb = bk // page_size
    rows = q_ref.shape[0]
    qpos = qpos_ref[b]
    nblk = jnp.minimum(qpos // bk + 1, pl.cdiv(maxp, ppb))

    def copies(j, slot):
        out = []
        for i in range(ppb):
            p = j * ppb + i
            if maxp % ppb:
                p = jnp.minimum(p, maxp - 1)
            page = blk_ref[b * maxp + p]
            keys = pl.ds(i * page_size, page_size)
            out.append(pltpu.make_async_copy(
                k_hbm.at[page], kbuf.at[slot, :, keys], sem.at[0, slot]))
            out.append(pltpu.make_async_copy(
                v_hbm.at[page], vbuf.at[slot, :, keys], sem.at[1, slot]))
        return out

    for c in copies(0, 0):
        c.start()
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)
    head = jax.lax.broadcasted_iota(jnp.int32, (rows, bk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (rows, bk), 1)

    def block_step(j, _):
        slot = j % 2

        @pl.when(j + 1 < nblk)
        def _prefetch():
            for c in copies(j + 1, 1 - slot):
                c.start()

        for c in copies(j, slot):
            c.wait()
        s_all = _dot_f32(q_ref[...], kbuf[slot].reshape(hkv * bk, dpad),
                         trans_b=True)                 # [rows, Hkv * bk]
        s = s_all[:, :bk]
        for h in range(1, hkv):
            s = jnp.where(head == h, s_all[:, h * bk:(h + 1) * bk], s)
        s = jnp.where(j * bk + col <= qpos, s * scale, NEG_INF)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p_exp = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_scr[:, :1] + jnp.sum(p_exp, axis=1, keepdims=True)
        p_bd = jnp.concatenate([jnp.where(head == h, p_exp, 0.0)
                                for h in range(hkv)], axis=1)
        acc_scr[...] = acc_scr[...] * alpha + _dot_f32(
            p_bd.astype(vbuf.dtype), vbuf[slot].reshape(hkv * bk, dpad))
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    jax.lax.fori_loop(0, nblk, block_step, None)
    l = l_scr[:, :1]
    safe = jnp.where(l > 0, l, 1.0)                   # the padding rows
    o_ref[...] = (acc_scr[...] / safe).astype(o_ref.dtype)


def _pallas_heads(q, pools, block, q_positions, interpret, scale, ppb):
    """The ``"heads"`` form's call: ``q`` [B, 1, H, D] (D whole lanes) over
    ``pools`` (K, V) [P, H, page_size, D], grid ``(B,)``, a lane's position
    by scalar prefetch."""
    b, _, h, dpad = q.shape
    page_size, maxp = pools[0].shape[2], block.shape[1]
    rows = _round_up(h, _sublanes(q.dtype))
    qh = jnp.pad(q.reshape(b, h, dpad), ((0, 0), (0, rows - h), (0, 0)))
    prefetch = (block.astype(jnp.int32).reshape(-1),
                q_positions[:, 0].astype(jnp.int32))
    tile = pl.BlockSpec((None, rows, dpad), lambda bi, *prefetched: (bi, 0, 0))
    o = pl.pallas_call(
        functools.partial(_heads_kernel, scale=scale, page_size=page_size,
                          maxp=maxp),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(b,),
            in_specs=[tile] + [pl.BlockSpec(memory_space=pl.ANY)
                               for _ in pools],
            out_specs=tile,
            scratch_shapes=[
                pltpu.VMEM((2, h, ppb * page_size, dpad), p.dtype)
                for p in pools
            ] + [
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((rows, LANES), jnp.float32),
                pltpu.VMEM((rows, LANES), jnp.float32),
                pltpu.VMEM((rows, dpad), jnp.float32),
            ],
        ),
        out_shape=_sds((b, rows, dpad), q.dtype, q),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="fused_paged_attention",
    )(*prefetch, qh, *pools)
    return o[:, None, :h]


# jitted so that the layers of a program that call it at one shape share
# one trace and one lowering: the kernel's unrolled copies and heads make
# those the dear part (six layers x three programs took 3 s of the serve
# cell's set-up without it; XLA inlines the calls, the program is the same)
@functools.partial(jax.jit, static_argnames=("interpret", "window", "scale",
                                             "v_width"))
def _pallas_paged(q, pk, pv, block, q_positions, interpret, window=None,
                  scale=None, v_width=None):
    """``v_width`` (with ``pv`` None): a latent pool, whose pages' values
    are the first ``v_width`` columns of their key slabs; the kernel
    multiplies whole lanes (``dvpad`` columns: the extra ones are further
    output columns nobody reads) and the result is sliced to ``v_width``."""
    b, t, hq, d = q.shape
    hkv, page_size = pk.shape[1], pk.shape[2]
    g = hq // hkv
    maxp = block.shape[1]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    sub = _sublanes(pk.dtype)
    if not interpret and page_size % sub:
        raise ValueError(
            f"page_size={page_size} cannot tile a {pk.dtype} KV pool on "
            f"TPU: one page is one (page_size, D) tile per kv head, so "
            f"page_size must be a multiple of {sub} for this dtype")
    latent = v_width is not None
    pools = (pk,) if latent else (pk, pv)
    dp = (-d) % LANES
    if dp:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, dp)))
        pools = tuple(jnp.pad(p, ((0, 0), (0, 0), (0, 0), (0, dp)))
                      for p in pools)
    dpad = d + dp
    dv = v_width if latent else d
    dvpad = _round_up(dv, LANES)
    ppb, tq, _ = paged_tiling(b, t, hq, hkv, d, page_size, maxp, pk.dtype,
                              v_width, window)
    if paged_form(t, hq, hkv, page_size, maxp, window, v_width) == "heads":
        return _pallas_heads(q, pools, block, q_positions, interpret, scale,
                             ppb)[..., :d]
    nt = -(-t // tq)
    tpad = nt * tq - t
    # [B, tiles, Hkv, G*tq, D]: one grid step owns one (batch row, tile of
    # tq query positions) with every kv head; a head's rows are laid out
    # [G, tq] flattened (position = row % tq).  Rows pad up to whole
    # sublane tiles (MHA decode has G*tq = 1); padded rows and padded
    # positions sit at position 0 and are sliced off below.
    rows = _round_up(g * tq, _sublanes(q.dtype))
    rpad = rows - g * tq
    qb = jnp.pad(q, ((0, 0), (0, tpad), (0, 0), (0, 0)))
    qb = (qb.reshape(b, nt, tq, hkv, g, dpad).transpose(0, 1, 3, 4, 2, 5)
          .reshape(b, nt, hkv, g * tq, dpad))
    qb = jnp.pad(qb, ((0, 0), (0, 0), (0, 0), (0, rpad), (0, 0)))
    qpos = jnp.pad(q_positions.astype(jnp.int32), ((0, 0), (0, tpad)))
    qpos = qpos.reshape(b, nt, tq)
    qrows = jnp.pad(jnp.tile(qpos, (1, 1, g)), ((0, 0), (0, 0), (0, rpad)))
    qrows = jnp.broadcast_to(qrows[..., None], (b, nt, rows, LANES))
    qmax = jnp.max(qpos, axis=2).reshape(-1)
    prefetch = (block.astype(jnp.int32).reshape(-1), qmax)
    kern = functools.partial(_paged_kernel, scale=scale,
                             page_size=page_size, maxp=maxp,
                             value_in_key=latent)
    if window is not None:
        # the ring is written up to the ROW's highest position, whatever
        # tile a query sits in
        prefetch = (prefetch[0],
                    jnp.repeat(jnp.max(q_positions.astype(jnp.int32),
                                       axis=1), nt),
                    ring_pages(q_positions, page_size, maxp).reshape(-1))
        kern = functools.partial(kern, window=window)

    def tile_idx(bi, ti, *prefetched):
        return (bi, ti, 0, 0, 0)

    o = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(b, nt),
            in_specs=[
                pl.BlockSpec((None, None, rows, LANES),
                             lambda bi, ti, *prefetched: (bi, ti, 0, 0)),
                pl.BlockSpec((None, None, hkv, rows, dpad), tile_idx),
            ] + [pl.BlockSpec(memory_space=pl.ANY) for _ in pools],
            out_specs=pl.BlockSpec((None, None, hkv, rows, dvpad), tile_idx),
            scratch_shapes=[
                pltpu.VMEM((2, ppb, hkv, page_size, dpad), p.dtype)
                for p in pools
            ] + [
                pltpu.SemaphoreType.DMA((len(pools), 2)),
                pltpu.VMEM((hkv, rows, LANES), jnp.float32),
                pltpu.VMEM((hkv, rows, LANES), jnp.float32),
                pltpu.VMEM((hkv, rows, dvpad), jnp.float32),
            ],
        ),
        out_shape=_sds((b, nt, hkv, rows, dvpad), q.dtype, q),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="latent_paged_attention" if latent else "fused_paged_attention",
    )(*prefetch, qrows, qb, *pools)
    o = (o[:, :, :, :g * tq].reshape(b, nt, hkv, g, tq, dvpad)
         .transpose(0, 1, 4, 2, 3, 5).reshape(b, nt * tq, hq, dvpad))
    return o[:, :t, :, :dv]


# ---------------------------------------------------------------------------
# public op
# ---------------------------------------------------------------------------

def default_impl() -> str:
    """What ``impl=None`` means here: the Pallas kernel on a TPU, the
    compiled lax page loop elsewhere."""
    return "pallas" if jax.default_backend() == "tpu" else "lax"


def paged_decode_attention(q: jax.Array, pk: jax.Array, pv: jax.Array,
                           block: jax.Array, q_positions: jax.Array, *,
                           window: Optional[int] = None,
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

    ``window`` makes ``block`` a RING table [B, R] over a window layer's
    pool (``SelfAttentionLayer.init_paged_cache``): position ``p`` lives in
    column ``(p // page_size) % R``, every row written up to its highest
    query position; a key's position is recovered from that
    (``ring_pages``) and a query sees the keys at
    ``q - window < k <= q``.  The caller keeps ``R * page_size`` at least
    ``window`` plus the positions written in the call.

    ``impl``: None picks ``"pallas"`` on TPU and ``"lax"`` elsewhere;
    ``"gather"`` routes through the legacy gather+softmax pair (the
    bit-compatible oracle).  ``interpret`` only applies to the Pallas
    path (defaults to the package policy: interpret off-TPU).
    """
    _check_shapes(q, pk, pv, block, q_positions)
    if impl is None:
        impl = default_impl()
    if impl == "gather":
        from deeplearning4j_tpu.nn.layers.attention import (
            gather_pages, paged_attention)

        gk = gather_pages(pk, block).astype(q.dtype)
        gv = gather_pages(pv, block).astype(q.dtype)
        if window is None:
            return paged_attention(q, gk, gv, q_positions)
        ps = pk.shape[2]
        kpos = (ring_pages(q_positions, ps, block.shape[1])[:, :, None] * ps
                + jnp.arange(ps)).reshape(block.shape[0], -1)
        return paged_attention(q, gk, gv, q_positions, k_positions=kpos,
                               window=window)
    if impl == "lax":
        return _lax_paged(q, pk, pv, block, q_positions, window)
    if impl != "pallas":
        raise ValueError(f"impl={impl!r} not one of pallas/lax/gather")
    if interpret is None:
        interpret = _interpret()
    return _pallas_paged(q, pk, pv, block, q_positions, interpret, window)


def paged_latent_attention(q: jax.Array, pc: jax.Array, block: jax.Array,
                           q_positions: jax.Array, *, v_width: int,
                           scale: float, impl: Optional[str] = None,
                           interpret: Optional[bool] = None) -> jax.Array:
    """Per-row causal attention of ``q`` [B, T, H, W] directly over a LATENT
    pool ``pc`` [P, page_size, W] (``LatentAttentionLayer.
    init_paged_cache``) through the block table ``block`` [B, MAXP]:
    multi-query attention of the ``H`` query rows over one key of width
    ``W`` whose first ``v_width`` columns are also the value; returns
    [B, T, H, v_width].  Scores are ``q . k * scale`` in float32, masked by
    ``q_positions >= key position`` (which hides trash-page-0 entries and
    unwritten pages as in ``paged_decode_attention``), and stay float32
    through the online softmax.

    The pool is PR 32's layout with one kv head (``[P, page, W]`` is
    ``[P, 1, page, W]`` for free), so this is ``fused_paged_attention``'s
    kernel with a group of ``H`` rows, no V pool (a page is copied once
    and multiplied twice) and a block sized by its bytes
    (``paged_tiling(v_width=)``); it is named ``latent_paged_attention``
    in a trace.  ``impl``: None picks ``"pallas"`` on TPU and ``"lax"``
    (the compiled page loop) elsewhere; the gather oracle is the layer's
    own ``pool[block]`` + ``_absorbed``."""
    b, t, h, w = q.shape
    if pc.ndim != 3 or pc.shape[2] != w or not 0 < v_width <= w:
        raise ValueError(
            f"a latent pool must be [P, page_size, W={w}] with a value "
            f"width in (0, W]; got pc {pc.shape}, v_width {v_width}")
    if block.ndim != 2 or block.shape[0] != b or q_positions.shape != (b, t):
        raise ValueError(
            f"block table {block.shape} / q_positions {q_positions.shape} "
            f"do not match q [B, T] = {(b, t)}")
    pk = pc[:, None]
    q = q.astype(pc.dtype)
    if impl is None:
        impl = default_impl()
    if impl == "lax":
        return _lax_paged(q, pk, None, block, q_positions, scale=scale,
                          v_width=v_width)
    if impl != "pallas":
        raise ValueError(f"impl={impl!r} not one of pallas/lax")
    if interpret is None:
        interpret = _interpret()
    return _pallas_paged(q, pk, None, block, q_positions, interpret,
                         scale=float(scale), v_width=int(v_width))


class PagedAttentionHelper:
    """Discovery-seam wrapper for the paged decode path (≙ the cuDNN
    helper SPI, like FlashAttentionHelper): ``SelfAttentionLayer``'s paged
    branches take ``attend`` wherever ``helpers.get_helper(
    "paged_attention")`` offers it (``paged_path``) and the legacy
    gather+softmax pair where it gives way.  Unlike the flash helper, the
    fused path is the DEFAULT on every backend — off TPU it routes to the
    compiled lax page-streaming fallback, not the Pallas interpreter, so
    CPU decode gets the live-page watermark win too.  ``LatentAttentionLayer``'s
    single-token step asks the same seam (``supports_latent`` /
    ``attend_latent``) and falls back to its own gather + ``_absorbed``."""

    name = "PagedAttentionHelper"

    def attend(self, q, pk, pv, block, q_positions,
               window: Optional[int] = None) -> jax.Array:
        return paged_decode_attention(q, pk, pv, block, q_positions,
                                      window=window)

    def supports_latent(self, width: int, page_size: int, dtype) -> bool:
        """Whether a latent pool [P, page_size, width] of ``dtype`` can be
        read in place: always by the lax page loop; by the compiled kernel
        when a page is whole tiles (whole lanes wide, whole sublane tiles
        long)."""
        return default_impl() != "pallas" or (
            width % LANES == 0 and page_size % _sublanes(dtype) == 0)

    def attend_latent(self, q, pc, block, q_positions, *, v_width: int,
                      scale: float) -> jax.Array:
        return paged_latent_attention(q, pc, block, q_positions,
                                      v_width=v_width, scale=scale)
