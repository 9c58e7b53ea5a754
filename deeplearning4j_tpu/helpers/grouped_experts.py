"""Held experts through one Pallas kernel, every touched expert's weights
read once: ``RoutedMoELayer``'s ``streamed`` path for a call of few rows and
its ``sorted`` path for a call of many.

A decode step gives an expert layer a few dozen rows.  Sorted by expert and
multiplied group by group (``jax.lax.ragged_dot``, the layer's ``ragged``
path) each of the three weight stacks is one operation that starts every
group's matrix cold: at 64 groups of 7.3 MB the chip read them at 62% of
its bandwidth, ~5 us lost at every boundary (PERF.md, PR 35 / PR 36).  With
few rows the bytes ARE the cost: multiplying EVERY row by a touched expert
is cheaper on the MXU than that expert's weights are to read (64 rows x 6 x
3584 x 1024 = 1.4 GFLOP against 22 MB: 7 us of arithmetic at peak, 27 us of
bytes), so no row is sorted, gathered or counted.  One kernel, *dense over
the rows*:

- grid ``(held experts, hidden tiles)``; ``tokens`` [T, d] resident in
  VMEM; a step takes ``W_gate[e][:, f]``, ``W_up[e][:, f]`` ([d, tf]) and
  ``W_down[e][f, :]`` ([tf, n_out]) as they are stored (no leaf is copied
  or re-laid) and computes ``h = act(x Wg) * (x Wu)``, ``y_e += h Wd`` with
  f32 accumulation; the pipeline has the next step's three tiles in flight
  meanwhile, across an expert's boundary like inside it;
- at an expert's last tile ``out += c[:, e] * y_e`` in f32, a SELECT where
  ``c`` is 0: a row that did not choose ``e`` takes nothing of it, not even
  a non-finite product;
- **untouched experts are not read.**  The experts some row chose, in
  index order, ride scalar prefetch and drive the weights' index maps; the
  entries past them repeat the last block (the pipeline copies a block only
  when its index changes) and skip the arithmetic (``pl.when``).

``c`` [T, count] f32 is the combine matrix (``combine_matrix``): the routing
weight of row ``t`` on held expert ``first + e``, 0 where it did not choose
it; assignments to experts held elsewhere are in no column.

``expert_tiling`` picks the hidden tile from the shapes alone: the largest
multiple of 128 lanes dividing ``hidden`` whose buffers stay within
``VMEM_BUDGET``; ``vmem_limit_bytes`` follows from it.  No argument or
environment variable tunes it.

**Many rows** (a prefill bucket) turn the balance over: multiplying every
row by every touched expert grows with rows x experts, so the rows are
SORTED by held expert as ``jax.lax.ragged_dot`` takes them, and each
expert's rows alone meet its weights (``sorted_experts``):

- each expert's rows start at a whole row tile (``SORTED_TILE``) of a
  padded layout, so no tile holds two experts; a block of the sorted
  assignments (``sorted_block``: twice the held share, so a skewed batch
  takes more blocks, dropless either way) is gathered into it, the padding
  rows zero;
- grid ``(visits, hidden tiles, tiles a visit)``, its first extent read at
  run time: a visit is one expert's run of up to ``r`` row tiles, and its
  expert rides scalar prefetch into the weights' index maps, so the
  pipeline fetches each touched expert's three tiles once a visit and
  keeps the next expert's in flight; **tiles past the held rows are not
  in the grid**, neither copied nor multiplied;
- where ``hidden`` takes one tile (``r`` = 1) consecutive tiles of an
  expert keep its weights resident; where it takes several, a visit's
  ``r`` tiles accumulate ``y`` in VMEM across the hidden tiles;
- a tile's rows leave weighted by their routing weight, f32, and come
  back to ``[T, n_out]`` at their tokens WITHOUT a scatter (on the chip a
  scatter-add cost 0.13-0.95 us a row, PR 40): the held rows alone are
  gathered into token order, and a second kernel (``_combine``) sums each
  token tile's rows on the MXU, a one-hot of token against row times the
  rows split into three bf16 pieces (their 24 bits), f32 accumulation.

``sorted_tiling`` picks ``(rows, hidden tile, r)`` from the shapes alone
as ``expert_tiling`` does.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.helpers import interpret_mode as _interpret
from deeplearning4j_tpu.helpers.paged_attention import _round_up, _sds

LANES = 128
ROW_TILE = 16                 # rows pad to whole sublane tiles of any dtype
VMEM_BUDGET = 56 * 2 ** 20    # bytes a grid step's buffers may take (of 128 MiB)
VMEM_HEADROOM = 8 * 2 ** 20   # the compiler's own temporaries on top
SORTED_TILE = 128             # rows of a tile of the sorted kernel
COMBINE_TOKENS = 128          # tokens of an output tile of the combine


def expert_tiling(t: int, d: int, hidden: int, n_out: int,
                  dtype) -> Tuple[int, int, int]:
    """How the kernel tiles ``tokens`` [t, d] against experts of width
    ``hidden``: ``(rows, hidden_tile, vmem_bytes)``.

    ``rows`` is ``t`` padded to whole sublane tiles.  ``hidden_tile`` columns
    of ``W_gate`` / ``W_up`` and rows of ``W_down`` form one grid step: the
    largest multiple of 128 that divides ``hidden`` (``hidden`` itself where
    128 does not divide it) whose ``vmem_bytes`` stay within
    ``VMEM_BUDGET`` — the three weight tiles, double-buffered by the
    pipeline, the resident tokens and result, the expert's f32 accumulator
    and one tile's intermediates.  A pure function of the shapes: the
    kernel calls it, and so can whoever wants to know how it engaged."""
    item = jnp.dtype(dtype).itemsize
    rows = _round_up(t, ROW_TILE)

    def vmem(tf):
        weights = 2 * (2 * d + n_out) * tf * item
        resident = 2 * rows * d * item + 3 * rows * n_out * 4
        step = rows * tf * (3 * 4 + item) + rows * n_out * 4
        return weights + resident + step

    if hidden % LANES:
        return rows, hidden, vmem(hidden)
    tiles = [tf for tf in range(hidden, 0, -LANES) if hidden % tf == 0]
    tf = next((tf for tf in tiles if vmem(tf) <= VMEM_BUDGET), tiles[-1])
    return rows, tf, vmem(tf)


def combine_matrix(ids: jax.Array, w: jax.Array, first: int,
                   count: int) -> Tuple[jax.Array, jax.Array]:
    """``(c [T, count] float32, touched [count] bool)`` from the router's
    ``ids`` / ``w`` [T, k]: ``c[t, e]`` is row ``t``'s weight on held expert
    ``first + e`` (0 where it did not choose it), ``touched[e]`` whether any
    row chose it."""
    local = ids - first
    chose = local[:, :, None] == jnp.arange(count, dtype=ids.dtype)
    c = jnp.sum(jnp.where(chose, w.astype(jnp.float32)[:, :, None], 0.0),
                axis=1)
    return c, jnp.any(chose, axis=(0, 1))


def _kernel(lst_ref, n_ref, x_ref, c_ref, wg_ref, wu_ref, wd_ref, o_ref,
            y_scr, *, act):
    i, f = pl.program_id(0), pl.program_id(1)
    last = pl.num_programs(1) - 1

    @pl.when((i == 0) & (f == 0))
    def _start():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(i < n_ref[0])
    def _tile():
        x = x_ref[...]
        gate = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
        up = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
        hid = (act(gate) * up).astype(x.dtype)
        y = jnp.dot(hid, wd_ref[...], preferred_element_type=jnp.float32)

        @pl.when(f == 0)
        def _first():
            y_scr[...] = y

        @pl.when(f > 0)
        def _more():
            y_scr[...] += y

        @pl.when(f == last)
        def _combine():
            cw = c_ref[...]                       # [rows, 1]
            o_ref[...] += jnp.where(cw != 0.0, cw * y_scr[...], 0.0)


# jitted so that the layers of a program that call it at one shape share one
# trace and one lowering (as _pallas_paged)
@functools.partial(jax.jit, static_argnames=("activation", "interpret"))
def _pallas_grouped(tokens, wg, wu, wd, c, touched, activation, interpret):
    from deeplearning4j_tpu.nn import activations

    t, d = tokens.shape
    count, _, hidden = wg.shape
    n_out = wd.shape[2]
    rows, tf, vmem = expert_tiling(t, d, hidden, n_out, wg.dtype)
    nf = hidden // tf
    # the touched experts first, in index order; the entries past them
    # repeat the last one, so their blocks are the block already there
    n = jnp.sum(touched, dtype=jnp.int32)
    order = jnp.argsort(~touched, stable=True).astype(jnp.int32)
    lst = jnp.where(jnp.arange(count) < n, order,
                    order[jnp.maximum(n - 1, 0)])
    x = jnp.pad(tokens.astype(wg.dtype), ((0, rows - t), (0, 0)))
    # a column of c a step, as a [rows, 1] tile
    cols = jnp.pad(c.T, ((0, 0), (0, rows - t)))[:, :, None]

    def tile(i, f, n_ref):
        return jnp.where(i < n_ref[0], f, nf - 1)

    columns = pl.BlockSpec(                      # of W_gate, of W_up
        (None, d, tf), lambda i, f, lst, n: (lst[i], 0, tile(i, f, n)))
    out = pl.pallas_call(
        functools.partial(_kernel, act=activations.get(activation)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(count, nf),
            in_specs=[
                pl.BlockSpec((rows, d), lambda i, f, lst, n: (0, 0)),
                pl.BlockSpec((None, rows, 1),
                             lambda i, f, lst, n: (lst[i], 0, 0)),
                columns, columns,
                pl.BlockSpec((None, tf, n_out),
                             lambda i, f, lst, n: (lst[i], tile(i, f, n), 0)),
            ],
            out_specs=pl.BlockSpec((rows, n_out),
                                   lambda i, f, lst, n: (0, 0)),
            scratch_shapes=[pltpu.VMEM((rows, n_out), jnp.float32)],
        ),
        out_shape=_sds((rows, n_out), jnp.float32, tokens),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem + VMEM_HEADROOM),
        interpret=interpret,
        name="grouped_experts",
    )(lst, n[None], x, cols, wg, wu, wd)
    return out[:t]


def grouped_experts(tokens: jax.Array, wg: jax.Array, wu: jax.Array,
                    wd: jax.Array, c: jax.Array, touched: jax.Array, *,
                    activation: str = "silu", interpret=None) -> jax.Array:
    """``sum_e c[:, e] * (act(x Wg[e]) * (x Wu[e])) Wd[e]`` over the experts
    ``touched``, [T, n_out] float32, for ``tokens`` [T, d], weight stacks
    ``wg`` / ``wu`` [count, d, hidden] and ``wd`` [count, hidden, n_out]
    and the combine matrix ``c`` [T, count] (``combine_matrix``).  An expert
    not ``touched`` is not read; ``c`` must be 0 in its column."""
    count, d, hidden = wg.shape
    if (tokens.ndim != 2 or tokens.shape[1] != d or wu.shape != wg.shape
            or wd.shape[:2] != (count, hidden)
            or c.shape != (tokens.shape[0], count)
            or touched.shape != (count,)):
        raise ValueError(
            f"grouped_experts: tokens {tokens.shape}, W_gate {wg.shape}, "
            f"W_up {wu.shape}, W_down {wd.shape}, c {c.shape}, touched "
            f"{touched.shape} do not fit [T, d], [count, d, hidden] x 2, "
            "[count, hidden, n_out], [T, count], [count]")
    if interpret is None:
        interpret = _interpret()
    return _pallas_grouped(tokens, wg, wu, wd, c.astype(jnp.float32),
                           touched, activation, interpret)


def sorted_tiling(d: int, hidden: int, n_out: int, dtype,
                  expert_rows: int = 0) -> Tuple[int, int, int, int]:
    """How the sorted kernel tiles experts of width ``hidden`` against
    rows of width ``d``: ``(rows, hidden_tile, r, vmem_bytes)``.

    ``rows`` = ``SORTED_TILE`` a tile.  The largest ``hidden_tile`` (a
    multiple of 128 dividing ``hidden``, or ``hidden`` itself where 128
    does not divide it) whose buffers stay within ``VMEM_BUDGET``: the
    three weight tiles and a row tile in and out, double-buffered, one
    tile's intermediates, and where ``hidden`` takes several tiles the f32
    accumulators of ``r`` row tiles, so that a visit reads its expert's
    weights once for up to ``r`` tiles of rows: as many as the rows an
    expert takes on average (``expert_rows``) fill, at most 4 (a tile a
    visit does not fill is a grid step that fetches nothing for the next
    one, and the weights' copies stop overlapping the products)."""
    item = jnp.dtype(dtype).itemsize
    tm = SORTED_TILE
    fill = max(1, min(4, -(-expert_rows // tm)))

    def vmem(tf, r):
        weights = 2 * (2 * d + n_out) * tf * item
        tiles = 2 * tm * (d * item + n_out * 4 + LANES * 4)
        step = tm * tf * (3 * 4 + item) + tm * n_out * 4
        acc = r * tm * n_out * 4 if tf < hidden else 0
        return weights + tiles + step + acc

    if hidden % LANES:
        return tm, hidden, 1, vmem(hidden, 1)
    for tf in range(hidden, 0, -LANES):
        if hidden % tf:
            continue
        for r in ((1,) if tf == hidden else range(fill, 0, -1)):
            if vmem(tf, r) <= VMEM_BUDGET:
                return tm, tf, r, vmem(tf, r)
    return tm, LANES, 1, vmem(LANES, 1)


def sorted_block(assignments: int, count: int, n_experts: int) -> int:
    """Sorted assignments a block of the sorted path takes: twice the held
    share ``count / n_experts`` of all ``assignments`` (all of them where
    every expert is held), in whole row tiles."""
    if count >= n_experts:
        return assignments
    share = -(-2 * assignments * count // n_experts)
    return min(assignments, _round_up(share, SORTED_TILE))


def _sorted_kernel(e_ref, s_ref, n_ref, x_ref, w_ref, wg_ref, wu_ref,
                   wd_ref, o_ref, *acc, act, nf):
    v, f, r = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(r < n_ref[v])
    def _tile():
        x = x_ref[...]
        gate = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
        up = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
        hid = (act(gate) * up).astype(x.dtype)
        y = jnp.dot(hid, wd_ref[...], preferred_element_type=jnp.float32)
        if nf == 1:
            o_ref[...] = w_ref[...] * y
            return
        y_scr = acc[0]

        @pl.when(f == 0)
        def _first():
            y_scr[r] = y

        @pl.when(f > 0)
        def _more():
            y_scr[r] += y

        @pl.when(f == nf - 1)
        def _store():
            o_ref[...] = w_ref[...] * y_scr[r]


def _owner(counts, n: int):
    """[n]: for each of ``n`` slots laid out group after group, ``counts``
    [g] of them a group, the group it falls in (the last past them all):
    ``jnp.repeat`` with a traced count, as a compare and a sum (the
    library's form sorts and scatters)."""
    ends = jnp.cumsum(counts)
    at = jnp.sum(jnp.arange(n, dtype=ends.dtype)[:, None] >= ends[None, :],
                 axis=1, dtype=jnp.int32)
    return jnp.minimum(at, counts.shape[0] - 1)


def _pick(table, idx):
    """``table[idx]`` for a table of a few dozen entries, as a compare and
    a sum: XLA expands such a gather into a chain of selects, one a table
    entry, so the program would grow with every lookup."""
    hit = idx[:, None] == jnp.arange(table.shape[0], dtype=idx.dtype)
    return jnp.sum(jnp.where(hit, table[None, :], 0), axis=1,
                   dtype=table.dtype)


def _layout(sizes, cut, token_of, weight_of, tm: int, r: int, rows: int,
            t: int):
    """One block of sorted assignments in the padded layout: each held
    expert's rows from a whole tile on, ``rows`` rows.  ``sizes`` / ``cut``
    [count]: each expert's held rows in the block and where they end;
    ``token_of`` / ``weight_of`` [block]: each sorted assignment's token and
    routing weight.  Built from gathers alone (a scatter on the chip costs
    several times a gather a row).

    Returns ``(expert, tile, tiles, visits)`` — per visit its expert,
    first row tile and number of tiles (1..``r``), ``visits`` of them, in
    arrays of ``rows // tm`` entries, the most there can be — then ``src``
    [rows], the token a row holds (``t``: padding), and ``wts`` [rows, 1],
    its routing weight (0 for padding)."""
    n_tiles = rows // tm
    tiles = (sizes + tm - 1) // tm
    start = (jnp.cumsum(tiles) - tiles) * tm
    tile = jnp.arange(n_tiles, dtype=jnp.int32)
    e = _owner(tiles, n_tiles)                   # each layout tile's expert
    rank = ((tile * tm - _pick(start, e))[:, None]
            + jnp.arange(tm, dtype=jnp.int32)[None, :])
    held = ((tile < jnp.sum(tiles))[:, None]
            & (rank < _pick(sizes, e)[:, None])).reshape(rows)
    at = jnp.where(held, (_pick(cut - sizes, e)[:, None] + rank).reshape(rows),
                   0)
    src = jnp.where(held, token_of[at], t)
    wts = jnp.where(held, weight_of[at], 0.0)[:, None]
    per = (tiles + r - 1) // r
    expert = _owner(per, n_tiles)
    j = tile - _pick(jnp.cumsum(per) - per, expert)
    return (expert, _pick(start, expert) // tm + j * r,
            jnp.minimum(r, _pick(tiles, expert) - j * r), jnp.sum(per), src,
            wts)


def _sorted_call(expert, tile, tiles, visits, x, wts, wg, wu, wd, *, tiling,
                 activation, interpret):
    """The kernel over one block's padded layout: ``x`` [rows, d] ->
    ``wts * y`` [rows, n_out] float32 in every tile a visit reached (the
    others hold whatever the buffer held); ``tiling`` is
    ``sorted_tiling``'s."""
    from deeplearning4j_tpu.nn import activations

    rows, d = x.shape
    hidden, n_out = wd.shape[1:]
    tm, tf, r, vmem = tiling
    nf = hidden // tf

    def row_block(i, j, tile, tiles):   # a dead step repeats the last tile
        return tile[i] + jnp.minimum(j, tiles[i] - 1)

    def row_spec(width):
        return pl.BlockSpec(
            (tm, width),
            lambda i, f, j, e, s, n: (row_block(i, j, s, n), 0))

    columns = pl.BlockSpec((None, d, tf),
                           lambda i, f, j, e, s, n: (e[i], 0, f))
    return pl.pallas_call(
        functools.partial(_sorted_kernel, act=activations.get(activation),
                          nf=nf),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(visits, nf, r),
            in_specs=[row_spec(d), row_spec(1), columns, columns,
                      pl.BlockSpec((None, tf, n_out),
                                   lambda i, f, j, e, s, n: (e[i], f, 0))],
            # a visit's first tile until its last hidden tile: no block
            # leaves VMEM before its rows are whole
            out_specs=pl.BlockSpec((tm, n_out), lambda i, f, j, e, s, n: (
                row_block(i, jnp.where(f == nf - 1, j, 0), s, n), 0)),
            scratch_shapes=([pltpu.VMEM((r, tm, n_out), jnp.float32)]
                            if nf > 1 else []),
        ),
        out_shape=_sds((rows, n_out), jnp.float32, x),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem + VMEM_HEADROOM),
        interpret=interpret,
        name="sorted_experts",
    )(expert, tile, tiles, x, wts, wg, wu, wd)


def _combine_kernel(tile_ref, y_ref, tok_ref, *refs, tt, add):
    """One chunk of rows in token order into its token tile: ``part = S
    y`` with ``S`` [tt, rows] the chunk's one-hot of token against row, so
    the MXU does the sum; ``y`` goes in as three bf16 pieces that hold its
    24 bits, each product accumulated in f32."""
    *acc_ref, o_ref = refs
    c = pl.program_id(0)
    tok = tok_ref[...]                                     # [1, rows]
    s = (tok == tile_ref[c] * tt + jax.lax.broadcasted_iota(
        jnp.int32, (tt, tok.shape[1]), 0)).astype(jnp.bfloat16)
    y = y_ref[...]
    hi = y.astype(jnp.bfloat16)
    rest = y - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    part = sum(jnp.dot(s, v, preferred_element_type=jnp.float32)
               for v in (hi, mid, lo))
    first = (c == 0) | (tile_ref[c] != tile_ref[jnp.maximum(c - 1, 0)])

    @pl.when(first)
    def _start():
        o_ref[...] = acc_ref[0][...] + part if add else part

    @pl.when(jnp.logical_not(first))
    def _more():
        o_ref[...] += part


def _combine(y, src, t: int, most: int, acc, interpret):
    """Each row of ``y`` [rows, n_out] at its token ``src`` (none: ``t``;
    at most ``most`` rows have one) summed into [T', n_out] (the ``t``
    tokens in whole tiles of ``COMBINE_TOKENS``), plus ``acc`` unless it is
    None — without a
    scatter: the held rows are gathered into token order, each token tile's
    rows padded to whole chunks of ``SORTED_TILE`` (every tile at least
    one, so every tile is written), and one kernel sums a chunk's rows
    into its tile (``_combine_kernel``) over a grid of the chunks there
    are.  A product, not a select: a non-finite row reaches the other
    tokens of its tile as NaN."""
    rows, n_out = y.shape
    add = acc is not None
    tt, cr = COMBINE_TOKENS, SORTED_TILE
    tp = _round_up(t, tt)
    nt = tp // tt
    tile = jnp.where(src < t, src // tt, nt)           # no token: nt
    order = jnp.argsort(tile, stable=True)
    held = jnp.sum(tile[:, None] == jnp.arange(nt)[None, :], axis=0,
                   dtype=jnp.int32)
    chunks = jnp.maximum(1, (held + cr - 1) // cr)
    n_chunks = -(-most // cr) + nt
    chunk_tile = _owner(chunks, n_chunks)
    chunk = jnp.arange(n_chunks, dtype=jnp.int32)
    rank = (((chunk - _pick(jnp.cumsum(chunks) - chunks, chunk_tile)) * cr)
            [:, None] + jnp.arange(cr, dtype=jnp.int32)[None, :])
    live = ((chunk < jnp.sum(chunks))[:, None]
            & (rank < _pick(held, chunk_tile)[:, None])).reshape(-1)
    at = order[jnp.where(live, (_pick(jnp.cumsum(held) - held, chunk_tile)
                                [:, None] + rank).reshape(-1), 0)]
    ytok = jnp.take(y, jnp.where(live, at, rows), axis=0, mode="fill",
                    fill_value=0)
    tok = jnp.where(live, src[at], -1).reshape(n_chunks, 1, cr)
    out_spec = pl.BlockSpec((tt, n_out), lambda c, ti: (ti[c], 0))
    return pl.pallas_call(
        functools.partial(_combine_kernel, tt=tt, add=add),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(jnp.sum(chunks),),
            in_specs=[pl.BlockSpec((cr, n_out), lambda c, ti: (c, 0)),
                      pl.BlockSpec((None, 1, cr), lambda c, ti: (c, 0, 0))]
            + ([out_spec] if add else []),
            out_specs=out_spec),
        out_shape=_sds((tp, n_out), jnp.float32, y),
        input_output_aliases={3: 0} if add else {},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_BUDGET),
        interpret=interpret,
        name="sorted_experts_combine",
    )(chunk_tile, ytok, tok, *([acc] if add else []))


# jitted so that the layers of a program that call it at one shape share one
# trace and one lowering (as _pallas_grouped)
@functools.partial(jax.jit, static_argnames=(
    "first", "n_experts", "activation", "interpret"))
def _pallas_sorted(tokens, wg, wu, wd, ids, w, first, n_experts, activation,
                   interpret):
    t, d = tokens.shape
    k = ids.shape[1]
    count, _, hidden = wg.shape
    a = t * k
    tiling = sorted_tiling(d, hidden, wd.shape[2], wg.dtype, a // n_experts)
    tm, _, r, _ = tiling
    cap = sorted_block(a, count, n_experts)
    blocks = -(-a // cap)
    rows = _round_up(cap + count * (tm - 1), tm)

    # the assignments sorted by held expert, those held elsewhere last
    local = ids - first
    mine = (local >= 0) & (local < count)
    key = jnp.where(mine, local, count).reshape(a)
    order = jnp.argsort(key, stable=True)
    ends = jnp.cumsum(jnp.sum(key[:, None] == jnp.arange(count)[None, :],
                              axis=0, dtype=jnp.int32))
    n_mine = ends[-1]
    pad = blocks * cap - a
    token_of = jnp.pad((order // k).astype(jnp.int32), (0, pad))
    weight_of = jnp.pad(
        jnp.where(mine, w, 0.0).reshape(a)[order].astype(jnp.float32),
        (0, pad))
    x = tokens.astype(wg.dtype)

    def block(b, acc=None):
        lo = b * cap
        cut = jnp.clip(ends, lo, lo + cap) - lo
        *visits, src, wts = _layout(
            jnp.diff(cut, prepend=0), cut,
            jax.lax.dynamic_slice(token_of, (lo,), (cap,)),
            jax.lax.dynamic_slice(weight_of, (lo,), (cap,)), tm, r, rows, t)
        y = _sorted_call(*visits,
                         jnp.take(x, src, axis=0, mode="fill", fill_value=0),
                         wts, wg, wu, wd, tiling=tiling,
                         activation=activation, interpret=interpret)
        # padding rows and tiles no visit reached go to no token
        return _combine(y, src, t, cap, acc, interpret)

    acc = block(0)
    if blocks > 1:
        acc = jax.lax.fori_loop(1, (n_mine + cap - 1) // cap, block, acc)
    return acc[:t]


def sorted_experts(tokens: jax.Array, wg: jax.Array, wu: jax.Array,
                   wd: jax.Array, ids: jax.Array, w: jax.Array, *,
                   first: int = 0, n_experts: int, activation: str = "silu",
                   interpret=None) -> jax.Array:
    """The part of the result that held experts ``first ..
    first + count - 1`` of ``n_experts`` give to ``tokens`` [T, d] routed
    by ``ids`` / ``w`` [T, k]: ``sum over the held choices of w * (act(x
    Wg[e]) * (x Wu[e])) Wd[e]``, [T, n_out] float32, each row multiplied by
    the experts it chose alone (see the module's docstring)."""
    count, d, hidden = wg.shape
    if (tokens.ndim != 2 or tokens.shape[1] != d or wu.shape != wg.shape
            or wd.shape[:2] != (count, hidden) or ids.ndim != 2
            or ids.shape != w.shape or ids.shape[0] != tokens.shape[0]):
        raise ValueError(
            f"sorted_experts: tokens {tokens.shape}, W_gate {wg.shape}, "
            f"W_up {wu.shape}, W_down {wd.shape}, ids {ids.shape}, w "
            f"{w.shape} do not fit [T, d], [count, d, hidden] x 2, "
            "[count, hidden, n_out], [T, k] x 2")
    if interpret is None:
        interpret = _interpret()
    return _pallas_sorted(tokens, wg, wu, wd, ids.astype(jnp.int32), w,
                          first, n_experts, activation, interpret)


class GroupedExpertsHelper:
    """Discovery-seam wrapper (≙ the cuDNN helper SPI, like
    PagedAttentionHelper): ``RoutedMoELayer`` asks
    ``helpers.get_helper("grouped_experts")`` for its ``streamed`` path and
    keeps its ``ragged`` blocks where this is absent or does not support
    the widths."""

    name = "GroupedExpertsHelper"

    def supports(self, d: int, hidden: int, n_out: int) -> bool:
        """Compiled, a weight tile is whole lanes of every width;
        interpreted, any width goes."""
        return _interpret() or not any(
            v % LANES for v in (d, hidden, n_out))

    def apply(self, tokens, wg, wu, wd, ids, w, held: Tuple[int, int],
              activation: str) -> jax.Array:
        """The part of the result that the experts ``held = (first, count)``
        give to ``tokens`` routed by ``ids`` / ``w`` [T, k]: every row
        against every touched expert (the ``streamed`` path)."""
        c, touched = combine_matrix(ids, w, *held)
        return grouped_experts(tokens, wg, wu, wd, c, touched,
                               activation=activation)

    def apply_sorted(self, tokens, wg, wu, wd, ids, w, held: Tuple[int, int],
                     n_experts: int, activation: str) -> jax.Array:
        """The same part, each row against the experts it chose alone (the
        ``sorted`` path)."""
        return sorted_experts(tokens, wg, wu, wd, ids, w, first=held[0],
                              n_experts=n_experts, activation=activation)
