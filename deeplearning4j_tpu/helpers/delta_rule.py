"""The gated delta rule of a Gated DeltaNet mixer (Yang, Kautz and
Hatamizadeh, arXiv:2412.06464), behind the helper seam
``get_helper("delta_rule")``.

Per batch row and head, a state ``S`` [d_k, d_v], all in float32::

    S_t = a_t (I - b_t k_t k_t^T) S_{t-1} + b_t k_t v_t^T
    o_t = S_t^T q_t

with the decay ``a_t = exp(g_t)`` handed in as its logarithm ``g_t <= 0``.

Two layouts of the state.  THE HEAD LAYOUT ``[B, H, d_k, d_v]`` is the one
the mathematics reads, and the chunked form's.  THE SLOT LAYOUT ``[B, P, d_k,
G * d_v]`` is the one a state slot stores and the decode step reads: the
heads in ``P = H / G`` groups of ``G`` (``slot_group``), a group's heads
side by side on the minor axis, so that a group is whole lanes of 128.  At
d_v = 192 a head alone would pad 192 lanes to 256 (a third more bytes on
every decode step); two heads are 384 = 3 x 128 lanes with nothing padded,
and the 96 rows of d_k are whole sublanes.  In that layout the decode step
is elementwise: head ``m`` of a group owns lanes ``[m d_v, (m + 1) d_v)``,
and a head's scalar or d_k-vector is laid along its lanes by a select on
the lane index (``_on_lanes``), so no reshape of the state's minor axis
(which would re-lay the whole state out in memory) is ever made.

Four forms.  ``chunked`` is the form every backend runs for a chunk or a
sequence (the WY form): per chunk of ``DELTA_CHUNK`` positions, with ``G``
the cumulative log decay inside the chunk,

    A = strict_tril((b K K^T) * exp(G_i - G_j)),   T = (I + A)^-1
    W = T (b exp(G) K),   U = T (b V)            (a triangular solve)
    O  = (Q exp(G)) S + tril(Q K^T * exp(G_i - G_j)) (U - W S)
    S' = exp(G_C) S + (K exp(G_C - G))^T (U - W S)

every chunk's ``A``, ``W``, ``U`` and masked ``Q K^T`` made at once, then a
``lax.scan`` over the chunks carrying ``S`` (matrix products only).
``stepwise`` is the recurrence a position at a time, the plain form and the
layer's built-in path where helpers are off.  ``single_step`` is the decode
step on the slot layout, in ``jnp``: XLA makes it a reduction pass over the
rows for ``S^T k`` and ``S^T q`` and an update pass that reads and writes
them again.  ``step_slots`` is the same step as ONE Pallas kernel over a
pool of state slots: a grid over the lanes, each lane's row copied into
VMEM once, stepped there (``S^T k``, ``S^T q`` and the update as exact
float32 elementwise arithmetic, a head's vectors laid along its lanes by
selects as ``_on_lanes`` lays them) and written back once, in place (the
pool is aliased to the result).  The helper offers it on the TPU
(``DeltaRuleHelper.kernel``), where the engine's decode step on the
state slots takes it; elsewhere, and for every other call of a single
token, ``single_step`` runs.  The small products of the other forms run at
``highest`` precision.

``live`` [B] (a prefill bucket's real tokens): positions at or past it leave
the state untouched (a decay of 1 and a ``b`` of 0); their outputs are
finite and mean nothing.

THE PER-CHANNEL DECAY (Kimi Delta Attention, arXiv:2510.26692): the decay
is a vector over ``d_k``, ``D_t = Diag(exp(g_t))`` with ``g_t`` [d_k], and it
acts before the delta::

    S_t = (I - b_t k_t k_t^T) D_t S_{t-1} + b_t k_t v_t^T

Its four forms carry a ``kda_`` prefix and take ``g`` [..., H, d_k].
``kda_chunked`` is the WY form with the decay inside every ``k_i . k_j``
product: ``A_ij = b_i sum_c k_ic k_jc exp(G_ic - G_jc)``.  Factored as
``(K exp(G)) (K exp(-G))^T`` that overflows float32 (``exp(-G)`` reaches
``exp(320)`` over 64 positions at a decay of ``exp(-5)``), so each product
is taken relative to the cumulative decay at the start of the later
sub-block of ``KDA_SUB`` positions: ``exp(G_i - R) exp(R - G_j)``, the first
factor at most 1, the second at most 1 off the diagonal sub-blocks and at
most ``exp(KDA_SUB |g_min|)`` on them (``exp(80)`` at the safe gate's floor
of -5, finite).  ``kda_single_step`` and the kernel ``kda_step_slots``
scale each ``d_k`` row of the state by its own factor, then step it as
above.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.helpers import interpret_mode as _interpret

# positions a chunk of the WY form; chosen on the chip (PERF.md, PR 41)
DELTA_CHUNK = 64
# positions a sub-block of the per-channel WY form: at a decay of at least
# exp(-5) a position, exp(16 * 5) stays inside float32
KDA_SUB = 16
_HIGHEST = lax.Precision.HIGHEST
_LANES = 128
# VMEM for the step kernel's own temporaries on top of its pipeline's rows
STEP_VMEM_HEADROOM = 8 * 2 ** 20


def slot_group(heads: int, d_v: int) -> int:
    """Heads side by side in a group of the slot layout: the fewest that
    divide ``heads`` and fill whole lanes of 128, or 1 where none does."""
    for g in range(1, heads + 1):
        if heads % g == 0 and (g * d_v) % _LANES == 0:
            return g
    return 1


def to_heads(s, heads: int):
    """Slot layout [B, P, d_k, G d_v] -> head layout [B, H, d_k, d_v]."""
    b, p, dk, lanes = s.shape
    g = heads // p
    s = s.reshape(b, p, dk, g, lanes // g)
    return jnp.moveaxis(s, 3, 2).reshape(b, heads, dk, lanes // g)


def to_slots(s, group: int):
    """Head layout [B, H, d_k, d_v] -> slot layout [B, H / G, d_k, G d_v]."""
    b, h, dk, dv = s.shape
    s = jnp.moveaxis(s.reshape(b, h // group, group, dk, dv), 2, 3)
    return s.reshape(b, h // group, dk, group * dv)


def mask_padding(g, beta, live):
    """Decay 1 and no write at positions at or past ``live``."""
    if live is None:
        return g, beta
    valid = (jnp.arange(g.shape[1])[None] < live[:, None])[..., None]
    on_g = valid if g.ndim == beta.ndim else valid[..., None]
    return jnp.where(on_g, g, 0.0), jnp.where(valid, beta, 0.0)


def stepwise(q, k, v, g, beta, s0, live=None):
    """The recurrence one position a trip.  ``q``, ``k`` [B, T, H, d_k];
    ``v`` [B, T, H, d_v]; ``g``, ``beta`` [B, T, H]; ``s0`` [B, H, d_k, d_v]
    (head layout).  Returns ``(o [B, T, H, d_v], S_T)``."""
    g, beta = mask_padding(g, beta, live)

    def step(s, inp):
        q_t, k_t, v_t, g_t, b_t = inp
        ks = jnp.einsum("bhk,bhkv->bhv", k_t, s, precision=_HIGHEST)
        a = jnp.exp(g_t)[..., None]
        w = b_t[..., None] * (v_t - a * ks)
        s = a[..., None] * s + k_t[..., None] * w[:, :, None, :]
        return s, jnp.einsum("bhk,bhkv->bhv", q_t, s, precision=_HIGHEST)

    s, o = lax.scan(step, s0, tuple(jnp.moveaxis(x, 1, 0)
                                    for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), s


def chunked(q, k, v, g, beta, s0, live=None, chunk=None):
    """As ``stepwise``, in the WY form (module docstring), ``chunk``
    (``DELTA_CHUNK``) positions a trip of the loop; a length that ``chunk``
    does not divide is padded with positions that keep the state."""
    bsz, t, h, dk = q.shape
    dv = v.shape[-1]
    c = max(1, min(int(chunk or DELTA_CHUNK), t))
    n = -(-t // c)
    pad = n * c - t
    if live is None and pad:
        live = jnp.full((bsz,), t, jnp.int32)
    if pad:
        q, k, v, g, beta = (jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
                            for x in (q, k, v, g, beta))
    g, beta = mask_padding(g, beta, live)

    def chunks(x):          # [B, n c, H, ...] -> [n, B, H, c, ...]
        x = x.reshape((bsz, n, c) + x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 1, 0), 3, 2)

    q, k, v, g, beta = (chunks(x) for x in (q, k, v, g, beta))
    cum = jnp.cumsum(g, axis=-1)                          # [n, B, H, c]
    i = jnp.arange(c)
    lower = i[:, None] >= i[None, :]
    decay = jnp.exp(jnp.where(lower, cum[..., :, None] - cum[..., None, :],
                              -jnp.inf))                  # [.., c, c]
    kk = jnp.einsum("...ik,...jk->...ij", k, k, precision=_HIGHEST)
    a = jnp.where(i[:, None] > i[None, :],
                  beta[..., :, None] * kk * decay, 0.0)
    rhs = jnp.concatenate([(beta * jnp.exp(cum))[..., None] * k,
                           beta[..., None] * v], axis=-1)
    wu = lax.linalg.triangular_solve(
        a + jnp.eye(c, dtype=a.dtype), rhs, left_side=True, lower=True,
        unit_diagonal=True)
    w, u = wu[..., :dk], wu[..., dk:]
    qk = jnp.einsum("...ik,...jk->...ij", q, k, precision=_HIGHEST) * decay
    qg = q * jnp.exp(cum)[..., None]
    kd = k * jnp.exp(cum[..., -1:] - cum)[..., None]
    last = jnp.exp(cum[..., -1])[..., None, None]          # [n, B, H, 1, 1]

    def trip(s, inp):
        w_c, u_c, qg_c, qk_c, kd_c, last_c = inp
        d = u_c - jnp.einsum("bhck,bhkv->bhcv", w_c, s, precision=_HIGHEST)
        o = (jnp.einsum("bhck,bhkv->bhcv", qg_c, s, precision=_HIGHEST)
             + jnp.einsum("bhcj,bhjv->bhcv", qk_c, d, precision=_HIGHEST))
        s = last_c * s + jnp.einsum("bhck,bhcv->bhkv", kd_c, d,
                                    precision=_HIGHEST)
        return s, o

    s, o = lax.scan(trip, s0, (w, u, qg, qk, kd, last))  # o [n, B, H, c, dv]
    o = jnp.moveaxis(o, 0, 1)                             # [B, n, H, c, dv]
    o = jnp.moveaxis(o, 3, 2).reshape(bsz, n * c, h, dv)
    return (o[:, :t] if pad else o), s


def _on_lanes(x, group: int, d_v: int):
    """``x`` [B, H, ...] (a head's scalar, or its d_k-vector) -> [B, P, ...,
    G d_v]: at lane ``j`` of group ``p`` the value of head ``p G + j //
    d_v``, by selects on the lane index (no reshape of the minor axis)."""
    b, h = x.shape[:2]
    x = x.reshape((b, h // group, group) + x.shape[2:])
    lane = lax.broadcasted_iota(jnp.int32, (group * d_v,), 0)
    out = x[:, :, 0, ..., None]
    for m in range(1, group):
        out = jnp.where(lane >= m * d_v, x[:, :, m, ..., None], out)
    return jnp.broadcast_to(out, out.shape[:-1] + (group * d_v,))


def single_step(q, k, v, g, beta, s):
    """One token a row on the SLOT layout, no loop: ``q``, ``k`` [B, H, d_k];
    ``v`` [B, H, d_v]; ``g``, ``beta`` [B, H]; ``s`` [B, P, d_k, G d_v].
    Returns ``(o [B, H, d_v], s')``: one pass reads the state for ``S^T k``
    and ``S^T q``, a second writes ``a S + k w^T`` with ``w = b (v - a S^T
    k)``; ``o = a S^T q + (k . q) w``."""
    bsz, p, _, lanes = s.shape
    h, dv = q.shape[1], v.shape[-1]
    group = h // p
    k_rows = _on_lanes(k, group, dv)                      # [B, P, d_k, L]
    ks = jnp.sum(k_rows * s, axis=2)                      # [B, P, L]
    qs = jnp.sum(_on_lanes(q, group, dv) * s, axis=2)
    a = _on_lanes(jnp.exp(g), group, dv)                  # [B, P, L]
    w = _on_lanes(beta, group, dv) * (v.reshape(bsz, p, lanes) - a * ks)
    s = a[:, :, None, :] * s + k_rows * w[:, :, None, :]
    kq = _on_lanes(jnp.sum(k * q, axis=-1), group, dv)
    return (a * qs + kq * w).reshape(bsz, h, dv), s


def step_vmem_bytes(row_shape) -> int:
    """VMEM the kernel's pipeline holds for rows of ``row_shape`` [P, d_k,
    G d_v] float32: a row copied in and one copied out, each twice."""
    return 4 * 4 * math.prod(row_shape)


def _lane_parts(fresh_ref, lanes_ref, tab_ref, s_ref, group, d_v):
    """What a step kernel reads of its grid step: the lane's ``fresh`` and
    ``live`` flags over its row [d_k, L], and ``on_lanes(rows, col)``, head
    ``col + m``'s column ``tab[rows, col + m]`` laid over lanes ``[m d_v,
    (m + 1) d_v)``."""
    b = pl.program_id(0)
    _, dk, lanes = s_ref.shape
    fresh = jnp.full((dk, lanes), fresh_ref[b]) != 0
    live = jnp.full((dk, lanes), lanes_ref[b]) != 0
    lane = lax.broadcasted_iota(jnp.int32, (1, lanes), 1)
    tab = tab_ref[...]

    def on_lanes(rows, col):
        out = tab[rows, col:col + 1]
        for m in range(1, group):
            out = jnp.where(lane >= m * d_v, tab[rows, col + m:col + m + 1],
                            out)
        return jnp.broadcast_to(out, (out.shape[0], lanes))

    return fresh, live, on_lanes


def _step_kernel(fresh_ref, lanes_ref, tab_ref, v_ref, s_ref, s_out, o_ref,
                 *, heads, group, d_v):
    """One lane a grid step: ``s_ref`` its row [P, d_k, L] in VMEM, ``tab_ref``
    [d_k + 8, 2 H] its heads' ``k`` (columns ``[0, H)``) and ``q`` (``[H,
    2 H)``) down the first d_k rows, then ``exp(g)``, ``beta`` and ``k . q``
    in three rows under ``k``'s columns; ``v_ref`` [P, L] its ``v`` on the
    slot layout."""
    pairs, dk, _ = s_ref.shape
    fresh, live, on_lanes = _lane_parts(fresh_ref, lanes_ref, tab_ref, s_ref,
                                        group, d_v)
    for p in range(pairs):
        h = p * group
        s_was = s_ref[p]
        s = jnp.where(fresh, 0.0, s_was)
        k = on_lanes(slice(0, dk), h)
        ks = jnp.sum(k * s, axis=0, keepdims=True)          # [1, L]
        qs = jnp.sum(on_lanes(slice(0, dk), heads + h) * s, axis=0,
                     keepdims=True)
        a = on_lanes(slice(dk, dk + 1), h)
        w = on_lanes(slice(dk + 1, dk + 2), h) * (v_ref[p:p + 1, :] - a * ks)
        o_ref[p:p + 1, :] = a * qs + on_lanes(slice(dk + 2, dk + 3), h) * w
        s_out[p] = jnp.where(live, a * s + k * w, s_was)


# jitted so that the layers of a program share one trace and one lowering
@functools.partial(jax.jit, static_argnames=("interpret",))
def _pallas_step(q, k, v, g, beta, sh, fresh, lanes, interpret):
    heads = q.shape[1]
    ext = jnp.stack([jnp.exp(g), beta, jnp.sum(k * q, axis=-1)], axis=1)
    tab = jnp.concatenate(
        [jnp.swapaxes(jnp.concatenate([k, q], axis=1), 1, 2),
         jnp.pad(ext, ((0, 0), (0, 5), (0, heads)))], axis=1)
    return _slots_call(_step_kernel, "delta_state_step", tab, v, sh, fresh,
                       lanes, interpret)


def _slots_call(kernel, name, tab, v, sh, fresh, lanes, interpret):
    """``kernel`` over a grid of the lanes: lane ``b``'s ``tab`` [d_k + 8,
    *] and ``v`` on the slot layout in, its row ``b + 1`` of the pool in and
    out (the pool aliased to the result), its output on the slot layout
    out.  Returns ``(o [B, H, d_v], sh')``."""
    bsz, heads, dv = v.shape
    _, pairs, dk, width = sh.shape
    row = pl.BlockSpec((None, pairs, dk, width),
                       lambda b, fresh, lanes: (b + 1, 0, 0, 0))
    on_slots = pl.BlockSpec((None, pairs, width),
                            lambda b, fresh, lanes: (b, 0, 0))
    s, o = pl.pallas_call(
        functools.partial(kernel, heads=heads, group=heads // pairs, d_v=dv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bsz,),
            in_specs=[pl.BlockSpec((None,) + tab.shape[1:],
                                   lambda b, fresh, lanes: (b, 0, 0)),
                      on_slots, row],
            out_specs=[row, on_slots]),
        out_shape=[jax.ShapeDtypeStruct(sh.shape, sh.dtype),
                   jax.ShapeDtypeStruct((bsz, pairs, width), jnp.float32)],
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=(step_vmem_bytes(sh.shape[1:])
                              + STEP_VMEM_HEADROOM)),
        interpret=interpret,
        name=name,
    )(fresh.astype(jnp.int32), lanes.astype(jnp.int32), tab,
      v.reshape(bsz, pairs, width), sh)
    return o.reshape(bsz, heads, dv), s


def step_slots(q, k, v, g, beta, sh, fresh, lanes, *, interpret=None):
    """``single_step`` on a pool of STATE SLOTS in place, as one Pallas
    kernel: ``sh`` [B + 1, P, d_k, G d_v] float32 (row 0 the trash row, lane
    ``i``'s row ``i + 1``), ``q``, ``k`` [B, H, d_k], ``v`` [B, H, d_v],
    ``g``, ``beta`` [B, H], ``fresh`` / ``lanes`` [B] bool.  A ``fresh``
    lane steps from zero state; a lane not in ``lanes`` keeps its row as it
    was, bit for bit; row 0 is not touched.  Returns ``(o [B, H, d_v],
    sh')``, ``sh'`` aliasing ``sh``'s buffer where the caller donated it."""
    _check_pool("step_slots", q, v, sh)
    if interpret is None:
        interpret = _interpret()
    return _pallas_step(q, k, v, g, beta, sh, fresh, lanes, interpret)


def _check_pool(name, q, v, sh):
    bsz, heads, dk = q.shape
    if (sh.ndim != 4 or sh.shape[0] != bsz + 1 or sh.shape[2] != dk
            or heads % sh.shape[1] or sh.dtype != jnp.float32
            or sh.shape[3] != heads // sh.shape[1] * v.shape[-1]):
        raise ValueError(
            f"{name}: pool {sh.shape} {sh.dtype} does not hold {bsz} "
            f"lanes of {heads} heads x [{dk}, {v.shape[-1]}] float32 on the "
            "slot layout behind a trash row")


# ------------------------------------------------ the per-channel decay (KDA)
def kda_stepwise(q, k, v, g, beta, s0, live=None):
    """``stepwise`` with the per-channel decay (module docstring): ``g``
    [B, T, H, d_k]; ``S~ = exp(g) S`` row by row, ``w = b (v - S~^T k)``,
    ``S' = S~ + k w^T``, ``o = S'^T q``."""
    g, beta = mask_padding(g, beta, live)

    def step(s, inp):
        q_t, k_t, v_t, g_t, b_t = inp
        s = jnp.exp(g_t)[..., None] * s
        ks = jnp.einsum("bhk,bhkv->bhv", k_t, s, precision=_HIGHEST)
        s = s + k_t[..., None] * (b_t[..., None] * (v_t - ks))[:, :, None, :]
        return s, jnp.einsum("bhk,bhkv->bhv", q_t, s, precision=_HIGHEST)

    s, o = lax.scan(step, s0, tuple(jnp.moveaxis(x, 1, 0)
                                    for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), s


def _decayed_products(a, b, cum, sub):
    """``M_ij = sum_c a_ic b_jc exp(cum_ic - cum_jc)`` for ``j`` up to the end
    of ``i``'s sub-block of ``sub`` rows (zero past it; the caller masks the
    triangle it wants).  ``a``, ``b``, ``cum`` [..., c, d_k], ``cum`` the
    cumulative log decay, falling along ``c``.  Each product is taken
    relative to ``R``, the cumulative decay at the first row of ``i``'s
    sub-block: ``(a_i exp(cum_i - R)) . (b_j exp(R - cum_j))``."""
    c, dk = a.shape[-2:]
    nb = c // sub
    blocks = cum.reshape(cum.shape[:-2] + (nb, sub, dk))
    ref = blocks[..., :1, :]                              # [.., nb, 1, dk]
    a_rel = a.reshape(blocks.shape) * jnp.exp(blocks - ref)
    j = jnp.arange(c)
    upto = (j[None, :] < (jnp.arange(nb)[:, None] + 1) * sub)[..., None]
    b_rel = b[..., None, :, :] * jnp.exp(jnp.where(
        upto, ref - cum[..., None, :, :], -jnp.inf))      # [.., nb, c, dk]
    m = jnp.einsum("...nik,...njk->...nij", a_rel, b_rel, precision=_HIGHEST)
    return m.reshape(cum.shape[:-2] + (c, c))


def kda_chunked(q, k, v, g, beta, s0, live=None, chunk=None):
    """``kda_stepwise`` in the WY form, ``chunk`` (``DELTA_CHUNK``) positions
    a trip of the loop, the intra-chunk decay by sub-blocks of ``KDA_SUB``
    (``_decayed_products``).  With ``G`` the cumulative log decay inside
    the chunk, per channel::

        A = strict_tril(b_i sum_c k_ic k_jc exp(G_ic - G_jc)),  T = (I + A)^-1
        W = T (b exp(G) K),   U = T (b V)
        O  = (Q exp(G)) S + tril(sum_c q_ic k_jc exp(G_ic - G_jc)) (U - W S)
        S' = exp(G_C) S + (K exp(G_C - G))^T (U - W S)

    Every exponent but the sub-blocks' own is at most 0.  A length that
    ``chunk`` does not divide is padded with positions that keep the
    state."""
    bsz, t, h, dk = q.shape
    dv = v.shape[-1]
    chunk = int(chunk or DELTA_CHUNK)
    sub = min(KDA_SUB, chunk)
    if chunk % sub:
        raise ValueError(f"kda_chunked: a chunk of {chunk} is not whole "
                         f"sub-blocks of {sub}")
    c = min(chunk, -(-t // sub) * sub)
    n = -(-t // c)
    pad = n * c - t
    if live is None and pad:
        live = jnp.full((bsz,), t, jnp.int32)
    if pad:
        q, k, v, g, beta = (jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
                            for x in (q, k, v, g, beta))
    g, beta = mask_padding(g, beta, live)

    def chunks(x):          # [B, n c, H, ...] -> [n, B, H, c, ...]
        x = x.reshape((bsz, n, c) + x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 1, 0), 3, 2)

    q, k, v, g, beta = (chunks(x) for x in (q, k, v, g, beta))
    cum = jnp.cumsum(g, axis=-2)                          # [n, B, H, c, dk]
    i = jnp.arange(c)
    a = jnp.where(i[:, None] > i[None, :], beta[..., :, None]
                  * _decayed_products(k, k, cum, sub), 0.0)
    rhs = jnp.concatenate([beta[..., None] * jnp.exp(cum) * k,
                           beta[..., None] * v], axis=-1)
    wu = lax.linalg.triangular_solve(
        a + jnp.eye(c, dtype=a.dtype), rhs, left_side=True, lower=True,
        unit_diagonal=True)
    w, u = wu[..., :dk], wu[..., dk:]
    qk = jnp.where(i[:, None] >= i[None, :],
                   _decayed_products(q, k, cum, sub), 0.0)
    qg = q * jnp.exp(cum)
    kd = k * jnp.exp(cum[..., -1:, :] - cum)
    last = jnp.exp(cum[..., -1, :])[..., None]            # [n, B, H, dk, 1]

    def trip(s, inp):
        w_c, u_c, qg_c, qk_c, kd_c, last_c = inp
        d = u_c - jnp.einsum("bhck,bhkv->bhcv", w_c, s, precision=_HIGHEST)
        o = (jnp.einsum("bhck,bhkv->bhcv", qg_c, s, precision=_HIGHEST)
             + jnp.einsum("bhcj,bhjv->bhcv", qk_c, d, precision=_HIGHEST))
        s = last_c * s + jnp.einsum("bhck,bhcv->bhkv", kd_c, d,
                                    precision=_HIGHEST)
        return s, o

    s, o = lax.scan(trip, s0, (w, u, qg, qk, kd, last))  # o [n, B, H, c, dv]
    o = jnp.moveaxis(o, 0, 1)                             # [B, n, H, c, dv]
    o = jnp.moveaxis(o, 3, 2).reshape(bsz, n * c, h, dv)
    return (o[:, :t] if pad else o), s


def kda_single_step(q, k, v, g, beta, s):
    """``single_step`` with the per-channel decay: ``g`` [B, H, d_k]; the
    state's ``d_k`` rows scaled each by its own factor, ``S~ = exp(g) S``,
    then ``w = b (v - S~^T k)``, ``S' = S~ + k w^T``, ``o = S~^T q + (k .
    q) w``."""
    bsz, p, _, lanes = s.shape
    h, dv = q.shape[1], v.shape[-1]
    group = h // p
    s = _on_lanes(jnp.exp(g), group, dv) * s              # [B, P, d_k, L]
    k_rows = _on_lanes(k, group, dv)
    ks = jnp.sum(k_rows * s, axis=2)                      # [B, P, L]
    qs = jnp.sum(_on_lanes(q, group, dv) * s, axis=2)
    w = _on_lanes(beta, group, dv) * (v.reshape(bsz, p, lanes) - ks)
    kq = _on_lanes(jnp.sum(k * q, axis=-1), group, dv)
    return (qs + kq * w).reshape(bsz, h, dv), s + k_rows * w[:, :, None, :]


def _kda_step_kernel(fresh_ref, lanes_ref, tab_ref, v_ref, s_ref, s_out,
                     o_ref, *, heads, group, d_v):
    """``_step_kernel`` with the per-channel decay: ``tab_ref`` [d_k + 8,
    3 H] holds the heads' ``k`` (columns ``[0, H)``), ``q`` (``[H, 2 H)``)
    and ``exp(g)`` (``[2 H, 3 H)``) down the first d_k rows, then ``beta``
    and ``k . q`` in two rows under ``k``'s columns."""
    pairs, dk, _ = s_ref.shape
    fresh, live, on_lanes = _lane_parts(fresh_ref, lanes_ref, tab_ref, s_ref,
                                        group, d_v)
    for p in range(pairs):
        h = p * group
        s_was = s_ref[p]
        s = on_lanes(slice(0, dk), 2 * heads + h) * jnp.where(fresh, 0.0,
                                                              s_was)
        k = on_lanes(slice(0, dk), h)
        ks = jnp.sum(k * s, axis=0, keepdims=True)          # [1, L]
        qs = jnp.sum(on_lanes(slice(0, dk), heads + h) * s, axis=0,
                     keepdims=True)
        w = on_lanes(slice(dk, dk + 1), h) * (v_ref[p:p + 1, :] - ks)
        o_ref[p:p + 1, :] = qs + on_lanes(slice(dk + 1, dk + 2), h) * w
        s_out[p] = jnp.where(live, s + k * w, s_was)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _pallas_kda_step(q, k, v, g, beta, sh, fresh, lanes, interpret):
    heads = q.shape[1]
    ext = jnp.stack([beta, jnp.sum(k * q, axis=-1)], axis=1)
    tab = jnp.concatenate(
        [jnp.swapaxes(jnp.concatenate([k, q, jnp.exp(g)], axis=1), 1, 2),
         jnp.pad(ext, ((0, 0), (0, 6), (0, 2 * heads)))], axis=1)
    return _slots_call(_kda_step_kernel, "kda_state_step", tab, v, sh, fresh,
                       lanes, interpret)


def kda_step_slots(q, k, v, g, beta, sh, fresh, lanes, *, interpret=None):
    """``step_slots`` with the per-channel decay (``g`` [B, H, d_k]): one
    Pallas kernel, each lane's row read once and written once in place, an
    idle lane's row and row 0 untouched bit for bit."""
    _check_pool("kda_step_slots", q, v, sh)
    if interpret is None:
        interpret = _interpret()
    return _pallas_kda_step(q, k, v, g, beta, sh, fresh, lanes, interpret)


class DeltaRuleHelper:
    """The seam's object: ``chunked`` for a chunk or a sequence, the ``lax``
    WY form on every backend; ``step_slots``, the decode step on the state
    slots in one kernel, where ``kernel`` offers it."""

    @property
    def kernel(self) -> bool:
        """Whether the decode step on state slots runs as ``step_slots``:
        compiled, on the TPU (``delta_net.delta_rule_path``)."""
        return not _interpret()

    def step_slots(self, q, k, v, g, beta, sh, fresh, lanes):
        return step_slots(q, k, v, g, beta, sh, fresh, lanes)

    def chunked(self, q, k, v, g, beta, s0, live=None):
        return chunked(q, k, v, g, beta, s0, live)

    def kda_step_slots(self, q, k, v, g, beta, sh, fresh, lanes):
        return kda_step_slots(q, k, v, g, beta, sh, fresh, lanes)

    def kda_chunked(self, q, k, v, g, beta, s0, live=None):
        return kda_chunked(q, k, v, g, beta, s0, live)

    def describe(self, t: int) -> str:
        """How a program of ``t`` positions a row is chunked, for the
        warm-up's log."""
        c = max(1, min(DELTA_CHUNK, t))
        return (f"lax WY form, {-(-t // c)} chunks of {c} positions "
                f"(a triangular solve and matrix products a chunk)")
