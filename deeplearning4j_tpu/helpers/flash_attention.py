"""Flash attention as a Pallas TPU kernel (fwd + bwd custom VJP).

The helper-layer flagship for the transformer path: where the reference's
accelerated module fuses conv/pool/BN through cuDNN
(``deeplearning4j-cuda/.../CudnnConvolutionHelper.java:51``), the TPU
framework's memory-bound hot spot is attention — materialising the
``[B, H, T, T]`` score matrix in HBM is what caps sequence length.  This
kernel computes softmax(QK^T)V blockwise in VMEM with the online-softmax
recurrence (running row-max ``m`` and normaliser ``l``), so HBM traffic is
O(T·D) instead of O(T²), and the backward pass rematerialises attention
probabilities per block from the saved logsumexp instead of storing them.

Layouts follow the TPU tiling rules: blocks are (block_q|block_k, D) VMEM
tiles, the per-row statistics (m, l, logsumexp, delta) are carried
broadcast across a 128-lane minor dimension, and matmuls accumulate in
float32 via ``preferred_element_type`` regardless of input dtype (bf16
inputs ride the MXU at full rate).

Grid convention (sequential minor axis carries scratch):
  forward:  (B*H, nq, nk)  — k-axis 'arbitrary', acc/m/l scratch
  dq:       (B*H, nq, nk)  — k-axis 'arbitrary', dq scratch
  dk/dv:    (B*H, nk, nq)  — q-axis 'arbitrary', dk/dv scratch

On non-TPU backends the same kernels run ``interpret=True`` (CI parity);
`pytest -m tpu` exercises the compiled path on a real chip.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.helpers import interpret_mode as _interpret

LANES = 128
NEG_INF = -1e30


def pick_blocks(t: int, block_q: Optional[int] = None,
                block_k: Optional[int] = None) -> Optional[tuple]:
    """Largest block sizes that tile T exactly, capped at bq 512 and
    bk 1024 but at most T/2 (bk == T leaves the sequential grid axis with
    a single step).  Returns None when T has no usable tiling."""
    def pk(cap):
        # lane-multiple candidates only: the [bq, bk] score tile wants its
        # minor dim on 128-lane boundaries
        for b in (cap, cap // 2, cap // 4, cap // 8, 128):
            if b >= 128 and b % 128 == 0 and t % b == 0:
                return b
        return None

    bq = block_q or pk(512)
    bk = block_k or pk(min(1024, max(128, t // 2)))
    if bq is None or bk is None or t % bq or t % bk:
        return None
    return bq, bk


def supports(t: int, d: int, block_q: Optional[int] = None,
             block_k: Optional[int] = None) -> bool:
    """The fused path needs whole blocks along time (no tail masking in the
    kernel); head_dim is zero-padded to a lane multiple, which is exact."""
    return pick_blocks(t, block_q, block_k) is not None


def _sds(shape, dtype, like):
    """ShapeDtypeStruct carrying the varying-mesh-axes set of ``like`` so
    the kernels also work inside ``shard_map`` (check_vma requires pallas
    out_shapes to declare how outputs vary — they vary like q does)."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(like).vma)


def _dot_f32(a, b, trans_a=False, trans_b=False):
    """dot_general with f32 accumulation; contraction picked by flags so we
    never pay an explicit transpose relayout inside the kernel."""
    ca = 0 if trans_a else 1
    cb = 1 if trans_b else 0
    return jax.lax.dot_general(
        a, b, (((ca,), (cb,)), ((), ())),
        preferred_element_type=jnp.float32)


def _causal_mask(s, qi, ki, block_q, block_k, window=None):
    """Causal (and optionally sliding-window banded) score masking by
    global position: keep kpos in [qpos - window + 1, qpos]."""
    qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    keep = qpos >= kpos
    if window is not None:
        keep &= kpos > qpos - window
    return jnp.where(keep, s, NEG_INF)


def _block_live(qi, ki, block_q, block_k, causal, window):
    """Does block (qi, ki) intersect the (banded) causal region?"""
    if not causal:
        return True
    live = qi * block_q + block_q - 1 >= ki * block_k
    if window is not None:
        live &= ki * block_k + block_k - 1 > qi * block_q - window
    return live


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, scale, causal, window,
                block_q, block_k):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # blocks outside the (banded) causal region contribute nothing
    run = _block_live(qi, ki, block_q, block_k, causal, window)

    @pl.when(run)
    def _step():
        s = _dot_f32(q_ref[:], k_ref[:], trans_b=True) * scale
        if causal:
            s = _causal_mask(s, qi, ki, block_q, block_k, window)
        m_prev = m_scr[:, :1]                      # [bq, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)  # [bq, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                     # [bq, bk] f32
        alpha = jnp.exp(m_prev - m_new)            # [bq, 1]
        l_new = alpha * l_scr[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + _dot_f32(
            p.astype(v_ref.dtype), v_ref[:])
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_scr[:, :1]
        # fully-masked rows (can't happen causally, but keep it NaN-safe)
        safe = jnp.where(l > 0, l, 1.0)
        o_ref[:] = (acc_scr[:] / safe).astype(o_ref.dtype)
        lse_ref[:] = m_scr[:] + jnp.log(safe)


def _kv_index(causal, block_q, block_k, window=None):
    """K/V block index for q-major grids.  Blocks outside the (banded)
    causal region clamp to the nearest live block: the index stops
    changing, so the Pallas pipeline skips their HBM->VMEM copies entirely
    (the compute for those steps is already skipped by the kernels'
    ``run`` predicate)."""
    if not causal:
        return lambda b, qi, ki: (b, ki, 0)

    def idx(b, qi, ki):
        hi = (qi * block_q + block_q - 1) // block_k
        k = jnp.minimum(ki, hi)
        if window is not None:
            lo = jnp.maximum(0, (qi * block_q - window + 1) // block_k)
            k = jnp.maximum(k, lo)
        return (b, k, 0)

    return idx


def _q_index(causal, block_q, block_k, window=None):
    """Q-side block index for the k-major (dk/dv) grid: q blocks outside
    the band clamp to the nearest live one."""
    if not causal:
        return lambda b, ki, qi: (b, qi, 0)

    def idx(b, ki, qi):
        lo = (ki * block_k) // block_q
        q = jnp.maximum(qi, lo)
        if window is not None:
            hi = (ki * block_k + block_k - 1 + window - 1) // block_q
            q = jnp.minimum(q, hi)
        return (b, q, 0)

    return idx


def _fwd_call(q, k, v, *, scale, causal, window, block_q, block_k,
              interpret):
    """q,k: [BH, T, D], v: [BH, T, Dv] (D and Dv already lane-padded).
    Returns (o [BH, T, Dv], lse [BH, T, 128])."""
    bh, t, d = q.shape
    dv = v.shape[-1]
    nq, nk = t // block_q, t // block_k
    grid = (bh, nq, nk)
    kern = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                             window=window, block_q=block_q,
                             block_k=block_k)
    kv_idx = _kv_index(causal, block_q, block_k, window)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((None, block_k, d), kv_idx),
            pl.BlockSpec((None, block_k, dv), kv_idx),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, dv), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((None, block_q, LANES), lambda b, qi, ki: (b, qi, 0)),
        ],
        out_shape=[
            _sds((bh, t, dv), q.dtype, q),
            _sds((bh, t, LANES), jnp.float32, q),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_attention_fwd",
    )(q, k, v)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref,
               dq_scr, *, scale, causal, window, block_q, block_k):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    run = _block_live(qi, ki, block_q, block_k, causal, window)

    @pl.when(run)
    def _step():
        s = _dot_f32(q_ref[:], k_ref[:], trans_b=True) * scale
        if causal:
            s = _causal_mask(s, qi, ki, block_q, block_k, window)
        p = jnp.exp(s - lse_ref[:, :1])                      # [bq, bk]
        dp = _dot_f32(do_ref[:], v_ref[:], trans_b=True)     # [bq, bk]
        ds = p * (dp - di_ref[:, :1])
        dq_scr[:] += _dot_f32(ds.astype(k_ref.dtype), k_ref[:]) * scale

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[:] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref,
                dk_ref, dv_ref, dk_scr, dv_scr,
                *, scale, causal, window, block_q, block_k):
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    run = _block_live(qi, ki, block_q, block_k, causal, window)

    @pl.when(run)
    def _step():
        s = _dot_f32(q_ref[:], k_ref[:], trans_b=True) * scale
        if causal:
            s = _causal_mask(s, qi, ki, block_q, block_k, window)
        p = jnp.exp(s - lse_ref[:, :1])                      # [bq, bk] f32
        pv = p.astype(do_ref.dtype)
        dv_scr[:] += _dot_f32(pv, do_ref[:], trans_a=True)   # [bk, D]
        dp = _dot_f32(do_ref[:], v_ref[:], trans_b=True)     # [bq, bk]
        ds = (p * (dp - di_ref[:, :1])).astype(q_ref.dtype)
        dk_scr[:] += _dot_f32(ds, q_ref[:], trans_a=True) * scale

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[:] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[:] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_call(q, k, v, o, lse, do, *, scale, causal, window, block_q,
              block_k, interpret):
    bh, t, d = q.shape
    nq, nk = t // block_q, t // block_k
    # delta_i = rowsum(dO * O): cheap elementwise+reduce, leave it to XLA,
    # broadcast across lanes for block loading like lse
    di = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    di = jnp.broadcast_to(di[:, :, None], (bh, t, LANES))

    qspec = pl.BlockSpec((None, block_q, d), lambda b, qi, ki: (b, qi, 0))
    kv_idx = _kv_index(causal, block_q, block_k, window)
    kspec = pl.BlockSpec((None, block_k, d), kv_idx)
    rowq = pl.BlockSpec((None, block_q, LANES), lambda b, qi, ki: (b, qi, 0))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          window=window, block_q=block_q,
                          block_k=block_k),
        grid=(bh, nq, nk),
        in_specs=[qspec, kspec, kspec, qspec, rowq, rowq],
        out_specs=qspec,
        out_shape=_sds((bh, t, d), q.dtype, q),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_attention_dq",
    )(q, k, v, do, lse, di)

    # k-major grid: swap the roles of the two minor axes
    q_idx = _q_index(causal, block_q, block_k, window)
    qspec2 = pl.BlockSpec((None, block_q, d), q_idx)
    kspec2 = pl.BlockSpec((None, block_k, d), lambda b, ki, qi: (b, ki, 0))
    rowq2 = pl.BlockSpec((None, block_q, LANES), q_idx)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          window=window, block_q=block_q,
                          block_k=block_k),
        grid=(bh, nk, nq),
        in_specs=[qspec2, kspec2, kspec2, qspec2, rowq2, rowq2],
        out_specs=[kspec2, kspec2],
        out_shape=[_sds((bh, t, d), q.dtype, q),
                   _sds((bh, t, d), q.dtype, q)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_attention_dkv",
    )(q, k, v, do, lse, di)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public op: [B, T, H, D] in, custom VJP
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, scale, causal, window, block_q, block_k, interpret):
    o, _ = _flash_fwd(q, k, v, scale, causal, window, block_q, block_k,
                      interpret)
    return o


def _flash_fwd(q, k, v, scale, causal, window, block_q, block_k,
               interpret):
    o, lse = _fwd_call(q, k, v, scale=scale, causal=causal, window=window,
                       block_q=block_q, block_k=block_k, interpret=interpret)
    return o, (q, k, v, o, lse)


def _flash_bwd(scale, causal, window, block_q, block_k, interpret, res, g):
    q, k, v, o, lse = res
    if v.shape[-1] != q.shape[-1]:
        raise NotImplementedError(
            "flash attention's backward kernels take one head width; "
            f"got q.k {q.shape[-1]} and v {v.shape[-1]} (forward only)")
    dq, dk, dv = _bwd_call(q, k, v, o, lse, g, scale=scale,
                           causal=causal, window=window, block_q=block_q,
                           block_k=block_k, interpret=interpret)
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = False,
                    window: Optional[int] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    scale: Optional[float] = None) -> jax.Array:
    """Fused attention on ``[B, T, H, D]`` tensors (layer layout).  ``v``
    (and so the output) may have another width than ``q`` and ``k``
    (latent attention's expanded path: 192 against 128), forward only;
    ``scale`` replaces the ``1/sqrt(D)`` on the scores.

    Requires T to be a multiple of the block sizes (see :func:`supports`);
    when blocks are not given the largest exact tiling up to 512/1024 is
    chosen.  D is zero-padded to a 128-lane
    multiple internally (exact, including gradients).  Softmax scale is
    1/sqrt(true D).

    ``window`` (requires ``causal``) bands the attention to the last
    ``window`` positions per query; blocks outside the band skip both
    compute and their HBM fetches (two-sided index clamping).
    """
    from deeplearning4j_tpu.nn.layers.attention import check_window

    b, t, h, d = q.shape
    check_window(causal, window)
    picked = pick_blocks(t, block_q, block_k)
    if picked is None:
        raise ValueError(
            f"flash_attention needs T % block == 0 (T={t}, block_q={block_q},"
            f" block_k={block_k}); use dot_product_attention instead")
    block_q, block_k = picked
    if interpret is None:
        interpret = _interpret()
    if scale is None:
        scale = 1.0 / (d ** 0.5)  # softmax scale uses the TRUE head dim

    def to_bh(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, t, x.shape[-1])

    def lane_pad(x, n):
        return jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, n))) if n else x

    dv = v.shape[-1]
    dp, dvp = (-d) % LANES, (-dv) % LANES
    q, k, v = lane_pad(q, dp), lane_pad(k, dp), lane_pad(v, dvp)
    o = _flash(to_bh(q), to_bh(k), to_bh(v), scale, causal, window,
               block_q, block_k, interpret)
    o = o.reshape(b, h, t, dv + dvp).transpose(0, 2, 1, 3)
    return o[..., :dv] if dvp else o


class FlashAttentionHelper:
    """Discovery-seam wrapper (≙ CudnnConvolutionHelper behind the
    ConvolutionHelper SPI): ``SelfAttentionLayer`` asks
    ``helpers.get_helper("attention")`` and uses this when the shape tiles.

    ``allow_interpret`` keeps the fused path OFF the non-TPU hot paths by
    default (the interpreter is for parity tests, not speed); tests flip it
    to exercise the routing end-to-end on the CPU tier.
    """

    # offered inside helpers.auto_partitioned(): attend() shard_maps itself
    partitions_itself = True

    def __init__(self, allow_interpret: bool = False):
        self.allow_interpret = allow_interpret

    def supports(self, t: int, d: int, *, under_shard_map: bool = False) -> bool:
        """Single routing policy for every call site (the attention layer
        and the sequence-parallel paths).  ``under_shard_map=True`` adds
        the constraint that only the compiled path qualifies: the Pallas
        HLO interpreter cannot execute under shard_map's varying-axes
        checks."""
        on_tpu = jax.default_backend() == "tpu"
        if not (on_tpu or self.allow_interpret):
            return False
        if under_shard_map and not on_tpu:
            return False
        return supports(t, d)

    def attend(self, q, k, v, *, causal: bool = False,
               window: Optional[int] = None,
               scale: Optional[float] = None) -> jax.Array:
        from jax.sharding import PartitionSpec as P

        from deeplearning4j_tpu import helpers
        from deeplearning4j_tpu.backend.device import AXIS_DATA, AXIS_MODEL

        def attn(q, k, v):
            return flash_attention(q, k, v, causal=causal, window=window,
                                   scale=scale)

        mesh = helpers.partition_mesh()
        if mesh is None:
            return attn(q, k, v)
        # inside an auto-partitioned program: attention is independent
        # per batch row and per head, so each device runs the kernel on
        # its own rows ('data') and heads ('model'); a dimension the axis
        # does not divide stays whole on every device of that axis

        def axis(name, size):
            n = mesh.shape.get(name, 1)
            return name if n > 1 and size % n == 0 else None

        spec = P(axis(AXIS_DATA, q.shape[0]), None,
                 axis(AXIS_MODEL, q.shape[2]), None)
        return jax.shard_map(attn, mesh=mesh, in_specs=(spec,) * 3,
                             out_specs=spec)(q, k, v)
