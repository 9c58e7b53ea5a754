"""Held experts for a call of few rows: every touched expert's weights
streamed once (``RoutedMoELayer``'s ``streamed`` path).

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
        give to ``tokens`` routed by ``ids`` / ``w`` [T, k]."""
        c, touched = combine_matrix(ids, w, *held)
        return grouped_experts(tokens, wg, wu, wd, c, touched,
                               activation=activation)
