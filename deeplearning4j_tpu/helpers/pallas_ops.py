"""Pallas TPU kernels behind the helper seam.

≙ the cuDNN kernel implementations (``CudnnLocalResponseNormalizationHelper``,
``CudnnBatchNormalizationHelper``) — re-derived as Pallas VMEM passes:

- LRN forward + backward: the cross-channel window sum is materialised once
  per block via lane-rolls inside VMEM (one HBM read/write per tensor),
  where the stock XLA lowering builds an n-tap reduce_window; backward
  reuses the same window structure via a custom VJP.
- Fused BN inference: (x - mean) * rsqrt(var+eps) * gamma + beta in a single
  elementwise pass with the per-channel affine computed in-kernel.

Everything is rank-normalised to [rows, channels] blocks; wrappers pad rows
to sublane (8) and channels to lane (128) multiples and slice back.  On
non-TPU backends kernels run with ``interpret=True`` so CI and the parity
gradient checks execute the identical code path.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu import helpers as _helpers


# single interpret policy for every kernel in the package
_interpret = _helpers.interpret_mode


def _pad2(x, row_mult=8, lane_mult=128):
    M, C = x.shape
    Mp = (M + row_mult - 1) // row_mult * row_mult
    Cp = (C + lane_mult - 1) // lane_mult * lane_mult
    if Mp == M and Cp == C:
        return x, M, C
    return jnp.pad(x, ((0, Mp - M), (0, Cp - C))), M, C


# ---------------------------------------------------------------------------
# LRN: y = x * (k + alpha * window_sum(x^2))^(-beta)
# ---------------------------------------------------------------------------

def _window_sum(vals, half: int, C: int):
    """Σ over channel offsets in [-half, half] with edge zeroing; lane rolls
    stay in-register on the VPU."""
    Cp = vals.shape[1]
    acc = jnp.zeros_like(vals)
    col = jax.lax.broadcasted_iota(jnp.int32, vals.shape, 1)
    for w in range(-half, half + 1):
        # circular roll by (-w mod Cp) puts vals[j+w] at lane j (roll shift
        # must be non-negative); edge wrap-around is masked out below
        rolled = pltpu.roll(vals, (-w) % Cp, 1) if w % Cp != 0 else vals
        valid = (col + w >= 0) & (col + w < C)
        acc = acc + jnp.where(valid, rolled, 0.0)
    return acc


def _lrn_fwd_kernel(x_ref, y_ref, s_ref, *, k, n, alpha, beta, C):
    x = x_ref[:]
    s = k + alpha * _window_sum(x * x, n // 2, C)
    y_ref[:] = x * jnp.power(s, -beta)
    s_ref[:] = s


def _lrn_bwd_kernel(x_ref, s_ref, g_ref, dx_ref, *, n, alpha, beta, C):
    x, s, g = x_ref[:], s_ref[:], g_ref[:]
    # dx = g·s^{-β} − 2αβ·x·Σ_win(g·x·s^{-β-1})
    t = g * x * jnp.power(s, -beta - 1.0)
    dx_ref[:] = g * jnp.power(s, -beta) \
        - 2.0 * alpha * beta * x * _window_sum(t, n // 2, C)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def lrn(x2d, k, n, alpha, beta):
    return _lrn_fwd(x2d, k, n, alpha, beta)[0]


def _lrn_fwd(x2d, k, n, alpha, beta):
    xp, M, C = _pad2(x2d)
    kern = functools.partial(_lrn_fwd_kernel, k=k, n=n, alpha=alpha,
                             beta=beta, C=C)
    y, s = pl.pallas_call(
        kern,
        out_shape=(jax.ShapeDtypeStruct(xp.shape, xp.dtype),
                   jax.ShapeDtypeStruct(xp.shape, xp.dtype)),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=(pl.BlockSpec(memory_space=pltpu.VMEM),
                   pl.BlockSpec(memory_space=pltpu.VMEM)),
        interpret=_interpret(),
    )(xp)
    return y[:M, :C], (x2d, s[:M, :C])


def _lrn_fwd_rule(x2d, k, n, alpha, beta):
    y, res = _lrn_fwd(x2d, k, n, alpha, beta)
    return y, res


def _lrn_bwd_rule(k, n, alpha, beta, res, g):
    x2d, s = res
    xp, M, C = _pad2(x2d)
    # pad lanes may compute inf/nan (0^-β etc.) — they are window-masked out
    # of every valid lane and sliced off below, so zero padding is safe
    sp, _, _ = _pad2(s)
    gp, _, _ = _pad2(g)
    kern = functools.partial(_lrn_bwd_kernel, n=n, alpha=alpha, beta=beta, C=C)
    dx = pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct(xp.shape, xp.dtype),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * 3,
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        interpret=_interpret(),
    )(xp, sp, gp)
    return (dx[:M, :C],)


lrn.defvjp(_lrn_fwd_rule, _lrn_bwd_rule)


# ---------------------------------------------------------------------------
# fused BN inference: y = (x - mean) * rsqrt(var + eps) * gamma + beta
# ---------------------------------------------------------------------------

def _bn_inf_kernel(x_ref, mean_ref, var_ref, gamma_ref, beta_ref, y_ref, *, eps):
    scale = gamma_ref[:] * jax.lax.rsqrt(var_ref[:] + eps)
    y_ref[:] = x_ref[:] * scale + (beta_ref[:] - mean_ref[:] * scale)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def bn_inference(x2d, mean, var, gamma, beta, eps):
    """Single fused elementwise pass (helper fast path for serving).
    Custom VJP: the affine backward is analytic, no need to differentiate
    through the pallas_call."""
    return _bn_inference_impl(x2d, mean, var, gamma, beta, eps)


def _bn_inference_fwd(x2d, mean, var, gamma, beta, eps):
    y = _bn_inference_impl(x2d, mean, var, gamma, beta, eps)
    return y, (x2d, mean, var, gamma)


def _bn_inference_bwd(eps, res, g):
    x2d, mean, var, gamma = res
    inv = jax.lax.rsqrt(var + eps)
    xhat = (x2d - mean) * inv
    dx = g * (gamma * inv)
    dgamma = (g * xhat).sum(0)
    dbeta = g.sum(0)
    dmean = -(g.sum(0)) * gamma * inv
    dvar = (g * (x2d - mean)).sum(0) * gamma * (-0.5) * inv ** 3
    return dx, dmean, dvar, dgamma, dbeta


bn_inference.defvjp(_bn_inference_fwd, _bn_inference_bwd)


def _bn_inference_impl(x2d, mean, var, gamma, beta, eps):
    xp, M, C = _pad2(x2d)
    Cp = xp.shape[1]

    def pad_c(v, fill=0.0):
        return jnp.pad(v.reshape(1, -1), ((0, 0), (0, Cp - C)),
                       constant_values=fill)

    kern = functools.partial(_bn_inf_kernel, eps=eps)
    y = pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct(xp.shape, xp.dtype),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * 5,
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        interpret=_interpret(),
    )(xp, pad_c(mean), pad_c(var, 1.0), pad_c(gamma), pad_c(beta))
    return y[:M, :C]


# ---------------------------------------------------------------------------
# fused BN training: one VMEM pass computing batch mean/var + normalize,
# one fused backward pass (≙ cudnnBatchNormalizationForwardTraining/Backward)
# ---------------------------------------------------------------------------

def _bn_train_kernel(x_ref, gamma_ref, beta_ref, y_ref, xhat_ref, stats_ref,
                     *, eps, M):
    x = x_ref[:]
    row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    valid = row < M            # zero-padded rows must not bias the moments
    xm = jnp.where(valid, x, 0.0)
    mean = jnp.sum(xm, 0) / M
    diff = jnp.where(valid, x - mean, 0.0)
    var = jnp.sum(diff * diff, 0) / M
    inv = jax.lax.rsqrt(var + eps)
    xhat = (x - mean) * inv
    y_ref[:] = xhat * gamma_ref[:] + beta_ref[:]
    xhat_ref[:] = xhat
    stats_ref[:] = jnp.stack([mean, var, inv])[:, None, :].reshape(3, -1)


def _bn_train_bwd_kernel(xhat_ref, g_ref, gammainv_ref, dx_ref, dgb_ref,
                         *, M):
    """dx = (gamma*inv/M) * (M*g - Σg - xhat*Σ(g*xhat)); padded rows carry
    g == 0 so the channel sums are already valid-row sums."""
    xhat, g = xhat_ref[:], g_ref[:]
    sum_g = jnp.sum(g, 0)
    sum_gx = jnp.sum(g * xhat, 0)
    dx_ref[:] = (gammainv_ref[:] / M) * (M * g - sum_g - xhat * sum_gx)
    dgb_ref[:] = jnp.stack([sum_gx, sum_g])[:, None, :].reshape(2, -1)


def bn_training(x2d, gamma, beta, eps):
    """Fused training-mode BN: returns (y, batch_mean, batch_var) from one
    VMEM pass; differentiable via a fused backward kernel (custom VJP).
    Gradients flow to (x2d, gamma, beta); the returned moments feed the
    running-stats update, which the reference does not differentiate."""
    return _bn_training_vjp(x2d, gamma, beta, eps)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _bn_training_vjp(x2d, gamma, beta, eps):
    y, _, mean, var, _ = _bn_training_fwd_impl(x2d, gamma, beta, eps)
    return y, mean, var


def _bn_training_fwd_impl(x2d, gamma, beta, eps):
    xp, M, C = _pad2(x2d)
    Cp = xp.shape[1]

    def pad_c(v):
        return jnp.pad(v.reshape(1, -1), ((0, 0), (0, Cp - C)))

    kern = functools.partial(_bn_train_kernel, eps=eps, M=M)
    y, xhat, stats = pl.pallas_call(
        kern,
        out_shape=(jax.ShapeDtypeStruct(xp.shape, xp.dtype),
                   jax.ShapeDtypeStruct(xp.shape, xp.dtype),
                   jax.ShapeDtypeStruct((3, Cp), xp.dtype)),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * 3,
        out_specs=(pl.BlockSpec(memory_space=pltpu.VMEM),) * 3,
        interpret=_interpret(),
    )(xp, pad_c(gamma), pad_c(beta))
    mean, var, inv = stats[0, :C], stats[1, :C], stats[2, :C]
    return y[:M, :C], xhat, mean, var, inv


def _bn_training_fwd_rule(x2d, gamma, beta, eps):
    y, xhat, mean, var, inv = _bn_training_fwd_impl(x2d, gamma, beta, eps)
    return (y, mean, var), (xhat, inv, gamma, x2d.shape)


def _bn_training_bwd_rule(eps, res, cts):
    g = cts[0]  # moments feed running stats only: their cotangents are zero
    xhat_p, inv, gamma, (M, C) = res
    gp, _, _ = _pad2(g)
    Cp = xhat_p.shape[1]
    gammainv = jnp.pad((gamma * inv).reshape(1, -1), ((0, 0), (0, Cp - C)))
    kern = functools.partial(_bn_train_bwd_kernel, M=M)
    dx, dgb = pl.pallas_call(
        kern,
        out_shape=(jax.ShapeDtypeStruct(xhat_p.shape, xhat_p.dtype),
                   jax.ShapeDtypeStruct((2, Cp), xhat_p.dtype)),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * 3,
        out_specs=(pl.BlockSpec(memory_space=pltpu.VMEM),) * 2,
        interpret=_interpret(),
    )(xhat_p, gp, gammainv)
    return dx[:M, :C], dgb[0, :C], dgb[1, :C]


_bn_training_vjp.defvjp(_bn_training_fwd_rule, _bn_training_bwd_rule)


# ---------------------------------------------------------------------------
# helper objects + registration
# ---------------------------------------------------------------------------

# The kernels are single-block whole-array VMEM passes (no grid), so they
# only apply below a VMEM budget; above it the layer's stock XLA path runs
# instead (which tiles fine).  The budgets are what compiled on a v5e
# under jax 0.9.0 / libtpu 0.0.34 (16 MiB scoped-VMEM limit): BN forward
# and backward at 1 << 20 f32 elements per operand; LRN only at 1 << 19 —
# at 6144x128 its backward fails with "Scoped allocation with size 22.49M
# and limit 16.00M exceeded scoped vmem limit by 6.49M" (four operands
# plus the window-sum temporaries).
_BN_BUDGET_ELEMS = 1 << 20    # 4 MiB per f32 buffer
_LRN_BUDGET_ELEMS = 1 << 19   # 2 MiB per f32 buffer


def _fits_vmem(x, budget: int) -> bool:
    rows = int(np.prod(x.shape[:-1]))
    cols = x.shape[-1]
    padded = ((rows + 7) // 8 * 8) * ((cols + 127) // 128 * 128)
    return padded <= budget


class PallasLRNHelper:
    """≙ ``CudnnLocalResponseNormalizationHelper``."""

    name = "PallasLRNHelper"

    def supports(self, x) -> bool:
        return _fits_vmem(x, _LRN_BUDGET_ELEMS)

    def apply(self, x, k, n, alpha, beta):
        shape = x.shape
        x2d = x.reshape(-1, shape[-1])
        return lrn(x2d, float(k), int(n), float(alpha), float(beta)).reshape(shape)


class PallasBatchNormHelper:
    """≙ ``CudnnBatchNormalizationHelper`` (inference + training paths)."""

    name = "PallasBatchNormHelper"

    def supports(self, x) -> bool:
        return _fits_vmem(x, _BN_BUDGET_ELEMS)

    def apply_inference(self, x, mean, var, gamma, beta, eps):
        shape = x.shape
        x2d = x.reshape(-1, shape[-1])
        return bn_inference(x2d, mean, var, gamma, beta, float(eps)).reshape(shape)

    def apply_training(self, x, gamma, beta, eps):
        """Fused forward-training pass; returns (y, batch_mean, batch_var)
        (≙ cudnnBatchNormalizationForwardTraining's saved moments)."""
        shape = x.shape
        x2d = x.reshape(-1, shape[-1])
        y, mean, var = bn_training(x2d, gamma, beta, float(eps))
        return y.reshape(shape), mean, var


def register_default_helpers() -> None:
    if "lrn" not in _helpers._registry:
        _helpers.register_helper("lrn", PallasLRNHelper())
    if "batch_norm" not in _helpers._registry:
        _helpers.register_helper("batch_norm", PallasBatchNormHelper())
    if "attention" not in _helpers._registry:
        from deeplearning4j_tpu.helpers.flash_attention import FlashAttentionHelper

        _helpers.register_helper("attention", FlashAttentionHelper())
    if "paged_attention" not in _helpers._registry:
        from deeplearning4j_tpu.helpers.paged_attention import PagedAttentionHelper

        _helpers.register_helper("paged_attention", PagedAttentionHelper())
    if "grouped_experts" not in _helpers._registry:
        from deeplearning4j_tpu.helpers.grouped_experts import (
            GroupedExpertsHelper)

        _helpers.register_helper("grouped_experts", GroupedExpertsHelper())
    if "selective_scan" not in _helpers._registry:
        from deeplearning4j_tpu.helpers.selective_scan import (
            SelectiveScanHelper)

        _helpers.register_helper("selective_scan", SelectiveScanHelper())
    if "delta_rule" not in _helpers._registry:
        from deeplearning4j_tpu.helpers.delta_rule import DeltaRuleHelper

        _helpers.register_helper("delta_rule", DeltaRuleHelper())
    if "epilogue" not in _helpers._registry:
        from deeplearning4j_tpu.helpers.fused_epilogue import FusedEpilogueHelper

        _helpers.register_helper("epilogue", FusedEpilogueHelper())
