"""Fused dropout + residual + norm epilogue (ROADMAP item 1, train side).

The transformer train step's other memory-bound seam: between the
attention/MLP matmuls sit chains of cheap elementwise passes — residual
add, LayerNorm's two reductions + affine, the next sublayer's input
dropout — each a full HBM round trip when left to generic lowering.
This kernel computes

    out = dropout(LayerNorm_affine(res + h))

in ONE VMEM pass (add, mean/var reductions, affine, mask-scale), with
``res=None`` giving the prologue form ``dropout(LayerNorm(x))`` — the
shape that actually occurs INSIDE this repo's pre-norm ResidualBlock
(LayerNorm leads the block; the residual add closes it; the full
res+h form is the cross-block fusion the kerneldiff grid and the tests
exercise).  ``ResidualBlock.apply`` routes its leading LayerNorm + the
second sublayer's input dropout through the prologue when the helper
qualifies (see ``_fused_prologue`` there).

Dropout discipline: the bernoulli keep-mask is drawn OUTSIDE the kernel
with exactly ``Layer.maybe_dropout``'s ops (``jax.random.bernoulli(rng,
1-rate, shape)`` + inverted scaling), so the fused path's mask is
bit-identical to the unfused path's for the same rng key; the kernel
only applies ``mask * y / keep``.  Tests pass an explicit ``mask`` for
exact referencing.

Backward: a custom VJP saving (h, res, gamma, mask); the backward pass
is plain jnp from the recomputed row moments (the standard LayerNorm
adjoint), so the fused forward is fully differentiable — including
under ``jax.checkpoint`` in remat blocks.

Same helper discipline as the rest of the package: registered as kind
``"epilogue"``; ``allow_interpret=False`` keeps the Pallas path off
non-TPU hot paths (the interpreter is for parity tests, not speed) —
off-TPU the layer's stock jnp path runs, which IS the reference.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.helpers import interpret_mode as _interpret

# single-block whole-array VMEM pass: at this cap (1024x1024, f32, with
# residual and mask) it compiles and matches on a v5e under jax 0.9.0
_VMEM_BUDGET_ELEMS = 1 << 20


def _pad2(x, row_mult=8, lane_mult=128):
    M, C = x.shape
    Mp = (M + row_mult - 1) // row_mult * row_mult
    Cp = (C + lane_mult - 1) // lane_mult * lane_mult
    if Mp == M and Cp == C:
        return x, M, C
    return jnp.pad(x, ((0, Mp - M), (0, Cp - C))), M, C


def _drn_kernel(*refs, eps, keep, C, has_res, has_mask):
    """refs: h [, res], gamma, beta [, mask], out.  One VMEM pass:
    x = h (+ res); row moments over the TRUE C lanes; affine; inverted
    dropout scaling by the precomputed keep-mask."""
    i = 0
    h_ref = refs[i]; i += 1
    res_ref = None
    if has_res:
        res_ref = refs[i]; i += 1
    g_ref = refs[i]; b_ref = refs[i + 1]; i += 2
    m_ref = None
    if has_mask:
        m_ref = refs[i]; i += 1
    o_ref = refs[i]

    x = h_ref[:].astype(jnp.float32)
    if has_res:
        x = x + res_ref[:].astype(jnp.float32)
    col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    valid = col < C                      # padded lanes must not bias moments
    xm = jnp.where(valid, x, 0.0)
    mu = jnp.sum(xm, axis=1, keepdims=True) / C
    diff = jnp.where(valid, x - mu, 0.0)
    var = jnp.sum(diff * diff, axis=1, keepdims=True) / C
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    y = y * g_ref[:].astype(jnp.float32) + b_ref[:].astype(jnp.float32)
    if has_mask:
        y = y * m_ref[:].astype(jnp.float32) * (1.0 / keep)
    o_ref[:] = y.astype(o_ref.dtype)


def _drn_call(h2d, res2d, gamma, beta, maskf, eps, keep, has_res,
              has_mask):
    hp, M, C = _pad2(h2d)
    Cp = hp.shape[1]

    def pad_c(v):
        return jnp.pad(v.reshape(1, -1).astype(h2d.dtype),
                       ((0, 0), (0, Cp - C)))

    ops = [hp]
    if has_res:
        ops.append(_pad2(res2d)[0])
    ops += [pad_c(gamma), pad_c(beta)]
    if has_mask:
        ops.append(_pad2(maskf)[0])
    kern = functools.partial(_drn_kernel, eps=eps, keep=keep, C=C,
                             has_res=has_res, has_mask=has_mask)
    y = pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct(hp.shape, hp.dtype),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * len(ops),
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        interpret=_interpret(),
        name="fused_dropout_residual_norm",
    )(*ops)
    return y[:M, :C]


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _drn(h2d, res2d, gamma, beta, maskf, eps, keep, has_res, has_mask):
    return _drn_call(h2d, res2d, gamma, beta, maskf, eps, keep, has_res,
                     has_mask)


def _drn_fwd(h2d, res2d, gamma, beta, maskf, eps, keep, has_res,
             has_mask):
    y = _drn_call(h2d, res2d, gamma, beta, maskf, eps, keep, has_res,
                  has_mask)
    return y, (h2d, res2d, gamma, maskf)


def _drn_bwd(eps, keep, has_res, has_mask, res, g):
    """Standard LayerNorm adjoint from recomputed row moments, with the
    dropout mask-scale folded into the incoming cotangent."""
    h2d, res2d, gamma, maskf = res
    x = h2d.astype(jnp.float32)
    if has_res:
        x = x + res2d.astype(jnp.float32)
    C = x.shape[1]
    mu = jnp.mean(x, axis=1, keepdims=True)
    var = jnp.var(x, axis=1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = (x - mu) * rstd
    g32 = g.astype(jnp.float32)
    if has_mask:
        g32 = g32 * maskf.astype(jnp.float32) * (1.0 / keep)
    dgamma = jnp.sum(g32 * xhat, axis=0).astype(gamma.dtype)
    dbeta = jnp.sum(g32, axis=0).astype(gamma.dtype)
    dxhat = g32 * gamma.astype(jnp.float32)
    dx = rstd * (dxhat
                 - jnp.mean(dxhat, axis=1, keepdims=True)
                 - xhat * jnp.mean(dxhat * xhat, axis=1, keepdims=True))
    dh = dx.astype(h2d.dtype)
    dres = dx.astype(res2d.dtype) if has_res else jnp.zeros_like(res2d)
    return dh, dres, dgamma, dbeta, jnp.zeros_like(maskf)


_drn.defvjp(_drn_fwd, _drn_bwd)


def dropout_residual_norm(h: jax.Array, res: Optional[jax.Array],
                          gamma: jax.Array, beta: jax.Array, *,
                          eps: float = 1e-5, rate: float = 0.0,
                          rng: Optional[jax.Array] = None,
                          train: bool = False,
                          mask: Optional[jax.Array] = None) -> jax.Array:
    """``dropout(LayerNorm_affine(res + h))`` on ``[..., C]`` tensors in
    one fused VMEM pass; ``res=None`` gives the prologue form
    ``dropout(LayerNorm(h))``.

    Dropout applies when ``mask`` is given explicitly, or when ``train``
    and ``rate > 0`` (mask drawn from ``rng`` exactly like
    ``Layer.maybe_dropout`` — bit-identical masks for the same key);
    otherwise the output is the plain fused norm.
    """
    shape = h.shape
    C = shape[-1]
    h2d = h.reshape(-1, C)
    has_res = res is not None
    res2d = (res.reshape(-1, C) if has_res
             else jnp.zeros((0, C), h2d.dtype))
    keep = 1.0 - rate
    if mask is None and train and rate > 0.0:
        if rng is None:
            raise ValueError(
                "dropout_residual_norm: rate > 0 at train time requires "
                "an rng key (or an explicit mask)")
        mask = jax.random.bernoulli(rng, keep, shape)
    has_mask = mask is not None
    maskf = (mask.reshape(-1, C).astype(h2d.dtype) if has_mask
             else jnp.zeros((0, C), h2d.dtype))
    out = _drn(h2d, res2d, gamma, beta, maskf, float(eps), float(keep),
               has_res, has_mask)
    return out.reshape(shape)


class FusedEpilogueHelper:
    """Discovery-seam wrapper (kind ``"epilogue"``).  ``allow_interpret``
    keeps the fused path OFF non-TPU hot paths by default, exactly like
    FlashAttentionHelper — the CPU tier's stock jnp LayerNorm+dropout IS
    the reference; tests flip it to exercise the routing end-to-end."""

    name = "FusedEpilogueHelper"

    def __init__(self, allow_interpret: bool = False):
        self.allow_interpret = allow_interpret

    def supports(self, x) -> bool:
        import numpy as np

        if not (jax.default_backend() == "tpu" or self.allow_interpret):
            return False
        if x.dtype not in (jnp.float32, jnp.bfloat16):
            return False   # f64 gradient checks stay on the exact path
        rows = int(np.prod(x.shape[:-1]))
        cols = x.shape[-1]
        padded = ((rows + 7) // 8 * 8) * ((cols + 127) // 128 * 128)
        return padded <= _VMEM_BUDGET_ELEMS

    def prologue(self, x, gamma, beta, *, eps, rate=0.0, rng=None,
                 train=False):
        return dropout_residual_norm(x, None, gamma, beta, eps=eps,
                                     rate=rate, rng=rng, train=train)

    def epilogue(self, h, resid, gamma, beta, *, eps, rate=0.0, rng=None,
                 train=False, mask=None):
        return dropout_residual_norm(h, resid, gamma, beta, eps=eps,
                                     rate=rate, rng=rng, train=train,
                                     mask=mask)
