"""Kernel-trust differential harness.

The repo's fused kernels (Pallas flash attention, the fused LRN/BN
passes, the paged-attention decode path, the streamed experts of a decode
step and the sorted experts of a prefill, the delta rule's decode step on
state slots) were validated by their unit
tests — which is trust by sampling.  This module is trust by SWEEP: run
every fused kernel against an independent float64 numpy reference over
a shape × dtype × masking grid, record per-config max-abs / max-rel
error and the ULP distribution in the output dtype, classify every
divergence, and write the whole thing to a machine-readable
``kernel_trust.json`` the regression sentinel can hold the line on
(``regression.KERNEL_TRUST_RULES``).

Divergence classes (docs/observability.md "Numerics" has the triage
runbook):

- ``within_tolerance`` — every config's max rel error is inside the
  dtype's budget; the kernel is trusted;
- ``tolerance_only`` — some configs exceed the budget but stay within a
  small multiple of it: an accumulation-order artifact, loosen the
  budget or tighten the kernel, but nothing is wrong;
- ``shape_dependent`` — the SAME dtype passes on some shapes and fails
  on others: a tiling/padding/masking seam, treat as a bug until
  explained;
- ``kernel_divergence`` — every config of a dtype is out of budget: the
  kernel computes something different from the reference;
- ``reference_setup`` — the config did not produce numbers at all
  because the HARNESS environment broke (jax API drift, missing
  platform); the kernel itself is unjudged.  The incident where 18/37
  flash-attention tests failed under an older jax (``jax.typeof``,
  ``pltpu.CompilerParams``, ``jax.shard_map`` — all import/attribute
  drift, zero numerics involved) is the canonical example, recorded in
  ``FLASH_TEST_TRIAGE`` and embedded in every report.

Metric family: ``dl4j_kernel_max_rel_error{kernel}``.

CLI::

    JAX_PLATFORMS=cpu python -m deeplearning4j_tpu.observability.kerneldiff \
        --out kernel_trust.json [--full] [--baseline kernel_trust.json]

``--baseline`` re-runs the sweep and fails (exit 1) if any kernel's
worst-config error regressed past the sentinel rules.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_KERNEL_ERR = "dl4j_kernel_max_rel_error"

# Per-dtype max-rel-error budgets vs the float64 reference.  float32
# budgets absorb accumulation-order differences (blockwise online
# softmax vs one-shot); bfloat16 budgets absorb the 8-bit mantissa.
# A config within TOLERANCE_SLACK × budget is "tolerance_only", beyond
# that it is a divergence.
DTYPE_BUDGET = {"float32": 5e-5, "bfloat16": 3e-2}
TOLERANCE_SLACK = 16.0

# ---------------------------------------------------------------------------
# the 18-failure triage (committed evidence; see module docstring)
# ---------------------------------------------------------------------------

FLASH_TEST_TRIAGE = {
    "incident": ("tests/test_flash_attention.py: 18 of 37 tests failing "
                 "under jax 0.4.37"),
    "classification": "reference_setup",
    "kernel_bug_count": 0,
    "causes": [
        {
            "symptom": "AttributeError: module 'jax' has no attribute "
                       "'typeof'",
            "where": "helpers/flash_attention.py out-shape construction",
            "root_cause": "jax.typeof (varying-mesh-axes metadata) landed "
                          "after 0.4.x; the helper assumed it "
                          "unconditionally",
            "fix": "was a getattr guard; removed once the installed jax "
                   "(0.9.0) had jax.typeof — the helpers call it directly",
        },
        {
            "symptom": "AttributeError: module 'jax.experimental.pallas."
                       "tpu' has no attribute 'CompilerParams'",
            "where": "helpers/flash_attention.py pallas_call sites (3)",
            "root_cause": "the Pallas TPU params class had another name "
                          "on 0.4.x (renamed CompilerParams later)",
            "fix": "was an import-time name lookup; removed — the helpers "
                   "use pltpu.CompilerParams",
        },
        {
            "symptom": "ImportError: cannot import name 'shard_map' from "
                       "'jax'",
            "where": "tests/test_flash_attention.py shard_map cases (2)",
            "root_cause": "top-level jax.shard_map is post-0.4.x; 0.4.37 "
                          "exposes it via jax.experimental.shard_map",
            "fix": "was a version shim module; removed — callers use "
                   "jax.shard_map",
        },
    ],
    "verdict": ("all 18 failures were harness/API drift between jax "
                "versions; a per-config numerics sweep (this file) on the "
                "repaired setup shows the kernel itself within float32 "
                "tolerance on every config"),
}


# ---------------------------------------------------------------------------
# float64 numpy references (independent of the jnp implementations)
# ---------------------------------------------------------------------------

def _np_softmax(x: np.ndarray, axis: int = -1) -> np.ndarray:
    m = x.max(axis=axis, keepdims=True)
    e = np.exp(x - m)
    return e / e.sum(axis=axis, keepdims=True)

def _np_attention(q, k, v, *, causal=False, window=None,
                  q_positions=None) -> np.ndarray:
    """float64 attention over [B, T, H, D] q and [B, L, Hkv, D] k/v with
    GQA head sharing, optional causal/window masking by global position,
    and optional PER-ROW query positions (the paged-decode convention:
    key index IS the global position)."""
    q = np.asarray(q, np.float64)
    k = np.asarray(k, np.float64)
    v = np.asarray(v, np.float64)
    b, t, hq, d = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    qg = q.reshape(b, t, hkv, g, d)
    scores = np.einsum("bqhgd,bkhd->bhgqk", qg, k) / math.sqrt(d)
    if causal:
        kpos = np.arange(k.shape[1])
        if q_positions is None:
            qpos = np.broadcast_to(np.arange(t), (b, t))
        else:
            qpos = np.asarray(q_positions)
        cm = qpos[:, :, None] >= kpos[None, None, :]        # [B, T, L]
        if window is not None:
            cm &= kpos[None, None, :] > qpos[:, :, None] - window
        scores = np.where(cm[:, None, None], scores, -1e30)
    w = _np_softmax(scores)
    o = np.einsum("bhgqk,bkhd->bqhgd", w, v)
    return o.reshape(b, t, hq, d)

def _np_gather_pages(pages, block) -> np.ndarray:
    """[P, Hkv, ps, D] pool -> [B, MAXP*ps, Hkv, D] logical view."""
    pages = np.asarray(pages, np.float64)
    block = np.asarray(block)
    out = pages[block].transpose(0, 1, 3, 2, 4)       # [B, MAXP, ps, Hkv, D]
    b, maxp = block.shape
    return out.reshape((b, maxp * out.shape[2]) + out.shape[3:])

def _np_dropout_residual_norm(h, res, gamma, beta, eps, mask,
                              keep) -> np.ndarray:
    """float64 dropout(LayerNorm_affine(res + h)) — the fused train-step
    epilogue's reference (``helpers/fused_epilogue.py``)."""
    x = np.asarray(h, np.float64)
    if res is not None:
        x = x + np.asarray(res, np.float64)
    mu = x.mean(-1, keepdims=True)
    var = x.var(-1, keepdims=True)
    y = ((x - mu) / np.sqrt(var + eps) * np.asarray(gamma, np.float64)
         + np.asarray(beta, np.float64))
    if mask is not None:
        y = np.where(np.asarray(mask), y / keep, 0.0)
    return y

def _np_grouped_experts(x, wg, wu, wd, c) -> np.ndarray:
    """float64 ``sum_e c[:, e] * (silu(x Wg[e]) * (x Wu[e])) Wd[e]``, one
    expert at a time (``helpers/grouped_experts.py``)."""
    x, c = np.asarray(x, np.float64), np.asarray(c, np.float64)
    y = np.zeros((x.shape[0], wd.shape[2]))
    for e in range(wg.shape[0]):
        g = x @ np.asarray(wg[e], np.float64)
        h = g / (1.0 + np.exp(-g)) * (x @ np.asarray(wu[e], np.float64))
        y += c[:, e:e + 1] * (h @ np.asarray(wd[e], np.float64))
    return y

def _np_delta_step(q, k, v, g, beta, s) -> Tuple[np.ndarray, np.ndarray]:
    """float64 gated delta rule, one token a head on the HEAD layout
    (``helpers/delta_rule.py``): ``S' = a S + k w^T`` with ``w = beta (v -
    a S^T k)``, ``o = S'^T q``."""
    q, k, v, s = (np.asarray(x, np.float64) for x in (q, k, v, s))
    a = np.exp(np.asarray(g, np.float64))[..., None]
    w = np.asarray(beta, np.float64)[..., None] * (
        v - a * np.einsum("bhk,bhkv->bhv", k, s))
    s = a[..., None] * s + k[..., None] * w[:, :, None, :]
    return np.einsum("bhk,bhkv->bhv", q, s), s

def _np_kda_step(q, k, v, g, beta, s) -> Tuple[np.ndarray, np.ndarray]:
    """float64 delta rule with a per-channel decay (Kimi Delta Attention),
    one token a head on the HEAD layout: ``S~ = Diag(exp(g)) S``, ``S' = S~ +
    k w^T`` with ``w = beta (v - S~^T k)``, ``o = S'^T q``."""
    q, k, v, s = (np.asarray(x, np.float64) for x in (q, k, v, s))
    s = np.exp(np.asarray(g, np.float64))[..., None] * s
    w = np.asarray(beta, np.float64)[..., None] * (
        v - np.einsum("bhk,bhkv->bhv", k, s))
    s = s + k[..., None] * w[:, :, None, :]
    return np.einsum("bhk,bhkv->bhv", q, s), s

def _np_lrn(x2d, k, n, alpha, beta) -> np.ndarray:
    x = np.asarray(x2d, np.float64)
    half = n // 2
    sq = np.pad(x * x, ((0, 0), (half, half)))
    win = np.zeros_like(x)
    for j in range(n):
        win += sq[:, j:j + x.shape[1]]
    return x / np.power(k + alpha * win, beta)

def _np_bn_inference(x2d, mean, var, gamma, beta, eps) -> np.ndarray:
    x = np.asarray(x2d, np.float64)
    inv = 1.0 / np.sqrt(np.asarray(var, np.float64) + eps)
    return ((x - np.asarray(mean, np.float64)) * inv
            * np.asarray(gamma, np.float64) + np.asarray(beta, np.float64))

def _np_bn_training(x2d, gamma, beta, eps):
    x = np.asarray(x2d, np.float64)
    mean = x.mean(0)
    var = ((x - mean) ** 2).mean(0)
    y = ((x - mean) / np.sqrt(var + eps) * np.asarray(gamma, np.float64)
         + np.asarray(beta, np.float64))
    return y, mean, var


# ---------------------------------------------------------------------------
# error measurement
# ---------------------------------------------------------------------------

def _bits(a: np.ndarray, dtype: str) -> np.ndarray:
    """Sign-ordered integer ordinals of float values in ``dtype`` — the
    space in which ``|ord(a) - ord(b)|`` counts representable values
    between a and b (ULP distance)."""
    if dtype == "bfloat16":
        import ml_dtypes
        raw = np.asarray(a, ml_dtypes.bfloat16).view(np.uint16)
        sign = np.int64(1) << 15
    else:
        raw = np.asarray(a, np.float32).view(np.uint32)
        sign = np.int64(1) << 31
    b = raw.astype(np.int64)
    # negative floats (sign bit set) map below zero, -0.0 coincides with
    # +0.0's neighborhood: ordinal(-x) = sign - bits(x)
    return np.where(b >= sign, sign - b, b)

def measure(out, ref64: np.ndarray, dtype: str) -> Dict[str, float]:
    """Error stats of one kernel output vs its float64 reference.

    The headline ``max_rel_error`` is SCALE-NORMALIZED: max-abs
    difference over the reference's max-abs value.  Elementwise
    ``diff/|ref|`` is the wrong metric here — attention outputs are
    weighted averages with near-zero elements whose relative error is
    unbounded even for a perfect-to-the-ULP kernel.  ULP distance is
    measured against the reference ROUNDED to the output dtype (the
    best any ``dtype`` kernel could do); ``ulp_p99`` is the robust
    summary, ``ulp_max`` inherits the same near-zero caveat."""
    o = np.asarray(jax.device_get(out), np.float64)
    r = np.asarray(ref64, np.float64)
    diff = np.abs(o - r)
    max_ref = float(np.abs(r).max()) if r.size else 0.0
    ulp = np.abs(_bits(o, dtype) - _bits(r, dtype))
    return {
        "max_abs_error": float(diff.max()) if diff.size else 0.0,
        "max_rel_error": (float(diff.max() / (max_ref + 1e-30))
                          if diff.size else 0.0),
        "ulp_max": int(ulp.max()) if ulp.size else 0,
        "ulp_p99": float(np.percentile(ulp, 99)) if ulp.size else 0.0,
        "ref_max_abs": max_ref,
    }


# ---------------------------------------------------------------------------
# the sweep grid
# ---------------------------------------------------------------------------

def _rng(*shape, dtype, seed):
    x = np.random.default_rng(seed).standard_normal(shape)
    return jnp.asarray(x, dtype)

def _flash_configs(full: bool):
    shapes = [(1, 128, 2, 32), (2, 128, 2, 64)]
    if full:
        shapes += [(1, 256, 4, 32), (2, 256, 2, 128)]
    for b, t, h, d in shapes:
        for dtype in ("float32", "bfloat16"):
            for causal, window in ((False, None), (True, None), (True, 64)):
                yield {"shape": [b, t, h, d], "dtype": dtype,
                       "causal": causal, "window": window}

def _run_flash(cfg) -> Tuple[Any, np.ndarray]:
    from deeplearning4j_tpu.helpers.flash_attention import flash_attention
    b, t, h, d = cfg["shape"]
    dt = jnp.dtype(cfg["dtype"])
    q = _rng(b, t, h, d, dtype=dt, seed=0)
    k = _rng(b, t, h, d, dtype=dt, seed=1)
    v = _rng(b, t, h, d, dtype=dt, seed=2)
    out = flash_attention(q, k, v, causal=cfg["causal"],
                          window=cfg["window"], interpret=True)
    ref = _np_attention(q, k, v, causal=cfg["causal"], window=cfg["window"])
    return out, ref

def _dpa_configs(full: bool):
    # the einsum path itself, incl. GQA head grouping vs the f64 reference
    shapes = [(2, 48, 4, 2, 32)]           # (B, T, Hq, Hkv, D)
    if full:
        shapes += [(1, 96, 8, 2, 64), (2, 64, 4, 4, 32)]
    for b, t, hq, hkv, d in shapes:
        for dtype in ("float32", "bfloat16"):
            for causal, window in ((False, None), (True, None), (True, 16)):
                yield {"shape": [b, t, hq, hkv, d], "dtype": dtype,
                       "causal": causal, "window": window}

def _run_dpa(cfg) -> Tuple[Any, np.ndarray]:
    from deeplearning4j_tpu.nn.layers.attention import dot_product_attention
    b, t, hq, hkv, d = cfg["shape"]
    dt = jnp.dtype(cfg["dtype"])
    q = _rng(b, t, hq, d, dtype=dt, seed=0)
    k = _rng(b, t, hkv, d, dtype=dt, seed=1)
    v = _rng(b, t, hkv, d, dtype=dt, seed=2)
    out = dot_product_attention(q, k, v, causal=cfg["causal"],
                                window=cfg["window"])
    ref = _np_attention(q, k, v, causal=cfg["causal"], window=cfg["window"])
    return out, ref

def _paged_configs(full: bool):
    grids = [{"pages": 8, "page_size": 16, "hq": 4, "hkv": 2, "d": 32,
              "b": 2, "t": 1}]
    if full:
        grids += [{"pages": 16, "page_size": 8, "hq": 4, "hkv": 4, "d": 64,
                   "b": 3, "t": 2}]
    for g in grids:
        for dtype in ("float32", "bfloat16"):
            yield dict(g, dtype=dtype)

def _run_gather(cfg) -> Tuple[Any, np.ndarray]:
    from deeplearning4j_tpu.nn.layers.attention import gather_pages
    dt = jnp.dtype(cfg["dtype"])
    pool = _rng(cfg["pages"], cfg["hkv"], cfg["page_size"], cfg["d"],
                dtype=dt, seed=3)
    rng = np.random.default_rng(4)
    block = jnp.asarray(
        rng.integers(0, cfg["pages"], size=(cfg["b"], 4)), jnp.int32)
    out = gather_pages(pool, block)
    return out, _np_gather_pages(pool, block)

def _run_paged_attention(cfg) -> Tuple[Any, np.ndarray]:
    from deeplearning4j_tpu.nn.layers.attention import paged_attention
    dt = jnp.dtype(cfg["dtype"])
    L = 4 * cfg["page_size"]
    q = _rng(cfg["b"], cfg["t"], cfg["hq"], cfg["d"], dtype=dt, seed=0)
    k = _rng(cfg["b"], L, cfg["hkv"], cfg["d"], dtype=dt, seed=1)
    v = _rng(cfg["b"], L, cfg["hkv"], cfg["d"], dtype=dt, seed=2)
    rng = np.random.default_rng(5)
    qpos = np.sort(rng.integers(0, L, size=(cfg["b"], cfg["t"])), axis=1)
    out = paged_attention(q, k, v, jnp.asarray(qpos, jnp.int32))
    ref = _np_attention(q, k, v, causal=True, q_positions=qpos)
    return out, ref

def _fused_paged_configs(full: bool):
    # engine-shaped grids: page 0 is the TRASH page (unassigned block-table
    # slots point at it), per-row positions are mixed, and row 0 is the
    # all-padding row (fresh slot: block all-trash, position 0)
    grids = [{"pages": 10, "page_size": 8, "maxp": 4, "hq": 4, "hkv": 2,
              "d": 32, "b": 3, "t": 1}]
    if full:
        grids += [
            # non-GQA, multi-token chunk (speculative/chunked decode shape)
            {"pages": 12, "page_size": 8, "maxp": 4, "hq": 4, "hkv": 4,
             "d": 64, "b": 2, "t": 2},
            # non-lane-multiple head dim exercises the Pallas lane padding
            {"pages": 8, "page_size": 16, "maxp": 3, "hq": 8, "hkv": 2,
             "d": 48, "b": 4, "t": 1},
            # a served width (group of 9, head 128) behind a table that is
            # not whole blocks of 8 pages, and a chunk over two row tiles
            {"pages": 50, "page_size": 16, "maxp": 20, "hq": 18, "hkv": 2,
             "d": 128, "b": 4, "t": 1},
            {"pages": 50, "page_size": 16, "maxp": 20, "hq": 18, "hkv": 2,
             "d": 128, "b": 2, "t": 40},
            # multi-head decode (one query row a kv head): the kernel's
            # ``heads`` form, blocks of 2 pages of 64 over a table that is
            # not whole blocks
            {"pages": 30, "page_size": 64, "maxp": 5, "hq": 6, "hkv": 6,
             "d": 128, "b": 4, "t": 1},
        ]
    for g in grids:
        for dtype in ("float32", "bfloat16"):
            yield dict(g, dtype=dtype)

def _run_fused_paged(cfg) -> Tuple[Any, np.ndarray]:
    """Both fused impls (lax fallback AND the Pallas kernel interpreted)
    against one f64 gather+softmax reference, concatenated into a single
    flat comparison — the bn_training precedent: one registry entry
    certifies every implementation behind the seam."""
    from deeplearning4j_tpu.helpers.paged_attention import (
        paged_decode_attention)
    dt = jnp.dtype(cfg["dtype"])
    ps, maxp, t = cfg["page_size"], cfg["maxp"], cfg["t"]
    pool_k = _rng(cfg["pages"], cfg["hkv"], ps, cfg["d"], dtype=dt, seed=20)
    pool_v = _rng(cfg["pages"], cfg["hkv"], ps, cfg["d"], dtype=dt, seed=21)
    q = _rng(cfg["b"], t, cfg["hq"], cfg["d"], dtype=dt, seed=22)
    rng = np.random.default_rng(23)
    block = rng.integers(1, cfg["pages"], size=(cfg["b"], maxp))
    qlast = rng.integers(t - 1, maxp * ps, size=(cfg["b"],))
    qlast[0] = t - 1
    block[0] = 0                                 # all-padding trash row
    for bi in range(cfg["b"]):
        live = int(qlast[bi]) // ps + 1
        block[bi, live:] = 0                     # trash-page-0 padding
    qpos = (qlast - (t - 1))[:, None] + np.arange(t)[None]
    blockj = jnp.asarray(block, jnp.int32)
    qposj = jnp.asarray(qpos, jnp.int32)
    out_lax = paged_decode_attention(q, pool_k, pool_v, blockj, qposj,
                                     impl="lax")
    out_pl = paged_decode_attention(q, pool_k, pool_v, blockj, qposj,
                                    impl="pallas", interpret=True)
    gk = _np_gather_pages(pool_k, block)
    gv = _np_gather_pages(pool_v, block)
    ref = _np_attention(q, gk, gv, causal=True, q_positions=qpos)
    out = jnp.concatenate([out_lax.reshape(-1), out_pl.reshape(-1)])
    return out, np.concatenate([ref.reshape(-1), ref.reshape(-1)])

def _epilogue_configs(full: bool):
    shapes = [(24, 96)]
    if full:
        shapes += [(64, 128), (17, 40)]          # incl. pad-heavy odd shape
    for m, c in shapes:
        for dtype in ("float32", "bfloat16"):
            for variant in ("residual_dropout", "prologue", "norm_only"):
                yield {"shape": [m, c], "dtype": dtype, "variant": variant}

def _run_epilogue(cfg) -> Tuple[Any, np.ndarray]:
    from deeplearning4j_tpu.helpers.fused_epilogue import (
        dropout_residual_norm)
    m, c = cfg["shape"]
    dt = jnp.dtype(cfg["dtype"])
    h = _rng(m, c, dtype=dt, seed=30)
    gamma = _rng(c, dtype=jnp.float32, seed=32)
    beta = _rng(c, dtype=jnp.float32, seed=33)
    variant = cfg["variant"]
    res = (_rng(m, c, dtype=dt, seed=31)
           if variant == "residual_dropout" else None)
    mask, keep, rate = None, 1.0, 0.0
    if variant != "norm_only":
        keep, rate = 0.75, 0.25
        # explicit mask so the f64 reference sees the exact keep pattern
        mask = jnp.asarray(
            np.random.default_rng(34).random((m, c)) < keep)
    out = dropout_residual_norm(h, res, gamma, beta, eps=1e-5, rate=rate,
                                mask=mask)
    ref = _np_dropout_residual_norm(
        h, res, gamma, beta, 1e-5,
        np.asarray(mask) if mask is not None else None, keep)
    return out, ref

def _grouped_experts_configs(full: bool):
    # the three expert cells' decode ratios (rows, top-k, held of all) at toy
    # widths: every expert held and ~4 rows on each (xing.serve-reason), a
    # 1/16 share with ~1 row an expert (k2.serve-docqa), a 1/8 share under
    # top-10 (laguna.serve-mixed-8k); in both an expert no row chose
    grids = [{"shape": [8, 64, 128], "top_k": 4, "held": [0, 8], "experts": 8}]
    # the sorted form (a prefill's schedule): rows over several tiles of an
    # expert and over blocks of twice the held share
    grids += [{"shape": [300, 64, 128], "top_k": 4, "held": [2, 4],
               "experts": 8, "form": "sorted"}]
    if full:
        grids += [
            {"shape": [12, 128, 256], "top_k": 8, "held": [40, 6],
             "experts": 96},
            {"shape": [8, 96, 128], "top_k": 10, "held": [8, 4],
             "experts": 32},
            # one row; rows past a sublane tile
            {"shape": [1, 64, 128], "top_k": 2, "held": [0, 4], "experts": 4},
            {"shape": [37, 64, 128], "top_k": 2, "held": [2, 4],
             "experts": 8},
            # the sorted form at the three cells' widths (d, hidden: k2's
            # four hidden tiles and y held for four row tiles, Laguna's
            # and Xing's one) and their top-k, toy rows and held experts
            {"shape": [96, 7168, 2048], "top_k": 8, "held": [5, 2],
             "experts": 24, "form": "sorted"},
            {"shape": [160, 3072, 1024], "top_k": 10, "held": [8, 3],
             "experts": 24, "form": "sorted"},
            {"shape": [200, 3584, 1024], "top_k": 4, "held": [0, 4],
             "experts": 4, "form": "sorted"},
        ]
    for g in grids:
        for dtype in ("float32", "bfloat16"):
            yield dict(g, dtype=dtype)

def _run_grouped_experts(cfg) -> Tuple[Any, np.ndarray]:
    """The interpreted Pallas kernel (the streamed form, or with ``"form":
    "sorted"`` the sorted one) on ids drawn as a router draws them (top-k
    of random scores over ALL experts, weights normalised) against the f64
    per-expert loop on the same rounded inputs."""
    from deeplearning4j_tpu.helpers.grouped_experts import (
        combine_matrix, grouped_experts, sorted_experts)
    t, d, hidden = cfg["shape"]
    first, count = cfg["held"]
    dt = jnp.dtype(cfg["dtype"])
    x = _rng(t, d, dtype=dt, seed=40)
    wg = _rng(count, d, hidden, dtype=jnp.float32, seed=41) * d ** -0.5
    wu = _rng(count, d, hidden, dtype=jnp.float32, seed=42) * d ** -0.5
    wd = _rng(count, hidden, d, dtype=jnp.float32, seed=43) * hidden ** -0.5
    wg, wu, wd = (w.astype(dt) for w in (wg, wu, wd))
    scores = np.random.default_rng(44).random((t, cfg["experts"]))
    ids = np.argsort(-scores, axis=1)[:, :cfg["top_k"]]
    w = np.take_along_axis(scores, ids, axis=1)
    w = (w / w.sum(axis=1, keepdims=True)).astype(np.float32)
    ids = jnp.asarray(ids, jnp.int32)
    c, touched = combine_matrix(ids, jnp.asarray(w), first, count)
    if cfg.get("form") == "sorted":
        out = sorted_experts(x, wg, wu, wd, ids, jnp.asarray(w), first=first,
                             n_experts=cfg["experts"], interpret=True)
    else:
        out = grouped_experts(x, wg, wu, wd, c, touched, interpret=True)
    return out, _np_grouped_experts(x, wg, wu, wd, c)

def _delta_step_configs(full: bool):
    # the toy's heads (two of d_v 64 to a row of 128 lanes) and Olmo-Hybrid's
    # row [15, 96, 384]; lanes fresh, stepped and idle in each
    grids = [{"shape": [4, 4, 16, 64]}, {"shape": [4, 30, 96, 192]}]
    if full:
        grids += [{"shape": [6, 3, 8, 128]}]     # a group of one head
    for g in grids:
        yield dict(g, dtype="float32")

def _run_delta_step(cfg) -> Tuple[Any, np.ndarray]:
    """The decode step on a pool of state slots both ways behind the seam
    (``single_step`` with the pool's selects, and the interpreted Pallas
    kernel ``step_slots``) against one f64 step on the head layout, output
    and pool in one flat comparison: lane 0 steps from zero state (fresh),
    lane 1 keeps its row (idle), the others step."""
    from deeplearning4j_tpu.helpers import delta_rule as dr
    b, h, dk, dv = cfg["shape"]
    group = dr.slot_group(h, dv)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(_rng(b, h, dk, dtype=jnp.float32, seed=50)) * dk ** -0.5
    k = unit(_rng(b, h, dk, dtype=jnp.float32, seed=51))
    v = _rng(b, h, dv, dtype=jnp.float32, seed=52)
    g = -jax.nn.softplus(_rng(b, h, dtype=jnp.float32, seed=53))
    beta = 2.0 * jax.nn.sigmoid(_rng(b, h, dtype=jnp.float32, seed=54))
    sh = _rng(b + 1, h // group, dk, group * dv, dtype=jnp.float32, seed=55)
    fresh = jnp.arange(b) == 0
    lanes = jnp.arange(b) != 1
    s_was = sh[1:]
    o_jnp, s_jnp = dr.single_step(
        q, k, v, g, beta, jnp.where(fresh[:, None, None, None], 0.0, s_was))
    s_jnp = jnp.where(lanes[:, None, None, None], s_jnp, s_was)
    o_pl, pool = dr.step_slots(q, k, v, g, beta, sh, fresh, lanes,
                               interpret=True)
    s0 = np.asarray(dr.to_heads(s_was, h), np.float64)
    s0[0] = 0.0
    ro, rs = _np_delta_step(q, k, v, g, beta, s0)
    rs[1] = np.asarray(dr.to_heads(s_was, h))[1]
    # to the slot layout (dr.to_slots, kept in float64)
    rs = np.moveaxis(rs.reshape(b, h // group, group, dk, dv), 2, 3).reshape(
        b, h // group, dk, group * dv)
    out = jnp.concatenate([x.reshape(-1) for x in
                           (o_jnp, s_jnp, o_pl, pool[1:])])
    ref = np.concatenate([x.reshape(-1) for x in (ro, rs, ro, rs)])
    return out, ref

def _kda_step_configs(full: bool):
    # four heads of d_v 32 to a row of 128 lanes, and Ling-3.0-flash's row
    # [32, 128, 128]; lanes fresh, stepped and idle in each
    grids = [{"shape": [4, 4, 16, 32]}, {"shape": [4, 32, 128, 128]}]
    if full:
        grids += [{"shape": [6, 3, 8, 128]}]     # a group of one head
    for g in grids:
        yield dict(g, dtype="float32")

def _run_kda_step(cfg) -> Tuple[Any, np.ndarray]:
    """``_run_delta_step`` for the per-channel decay: ``kda_single_step``
    with the pool's selects and the interpreted kernel ``kda_step_slots``
    against one f64 step on the head layout, decays over the safe gate's
    range (-5, 0) a channel."""
    from deeplearning4j_tpu.helpers import delta_rule as dr
    b, h, dk, dv = cfg["shape"]
    group = dr.slot_group(h, dv)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(_rng(b, h, dk, dtype=jnp.float32, seed=60)) * dk ** -0.5
    k = unit(_rng(b, h, dk, dtype=jnp.float32, seed=61))
    v = _rng(b, h, dv, dtype=jnp.float32, seed=62)
    g = -5.0 * jax.nn.sigmoid(3.0 * _rng(b, h, dk, dtype=jnp.float32,
                                         seed=63))
    beta = jax.nn.sigmoid(_rng(b, h, dtype=jnp.float32, seed=64))
    sh = _rng(b + 1, h // group, dk, group * dv, dtype=jnp.float32, seed=65)
    fresh = jnp.arange(b) == 0
    lanes = jnp.arange(b) != 1
    s_was = sh[1:]
    o_jnp, s_jnp = dr.kda_single_step(
        q, k, v, g, beta, jnp.where(fresh[:, None, None, None], 0.0, s_was))
    s_jnp = jnp.where(lanes[:, None, None, None], s_jnp, s_was)
    o_pl, pool = dr.kda_step_slots(q, k, v, g, beta, sh, fresh, lanes,
                                   interpret=True)
    s0 = np.asarray(dr.to_heads(s_was, h), np.float64)
    s0[0] = 0.0
    ro, rs = _np_kda_step(q, k, v, g, beta, s0)
    rs[1] = np.asarray(dr.to_heads(s_was, h))[1]
    rs = np.moveaxis(rs.reshape(b, h // group, group, dk, dv), 2, 3).reshape(
        b, h // group, dk, group * dv)
    out = jnp.concatenate([x.reshape(-1) for x in
                           (o_jnp, s_jnp, o_pl, pool[1:])])
    ref = np.concatenate([x.reshape(-1) for x in (ro, rs, ro, rs)])
    return out, ref

def _pallas2d_configs(full: bool):
    shapes = [(32, 24)]
    if full:
        shapes += [(64, 48), (17, 5)]      # incl. a pad-heavy odd shape
    for m, c in shapes:
        yield {"shape": [m, c], "dtype": "float32"}

def _run_lrn(cfg) -> Tuple[Any, np.ndarray]:
    from deeplearning4j_tpu.helpers.pallas_ops import lrn
    m, c = cfg["shape"]
    x = _rng(m, c, dtype=jnp.float32, seed=6)
    out = lrn(x, 2.0, 5, 1e-4, 0.75)
    return out, _np_lrn(x, 2.0, 5, 1e-4, 0.75)

def _run_bn_inference(cfg) -> Tuple[Any, np.ndarray]:
    from deeplearning4j_tpu.helpers.pallas_ops import bn_inference
    m, c = cfg["shape"]
    x = _rng(m, c, dtype=jnp.float32, seed=7)
    mean = _rng(c, dtype=jnp.float32, seed=8)
    var = jnp.abs(_rng(c, dtype=jnp.float32, seed=9)) + 0.1
    gamma = _rng(c, dtype=jnp.float32, seed=10)
    beta = _rng(c, dtype=jnp.float32, seed=11)
    out = bn_inference(x, mean, var, gamma, beta, 1e-5)
    return out, _np_bn_inference(x, mean, var, gamma, beta, 1e-5)

def _run_bn_training(cfg) -> Tuple[Any, np.ndarray]:
    from deeplearning4j_tpu.helpers.pallas_ops import bn_training
    m, c = cfg["shape"]
    x = _rng(m, c, dtype=jnp.float32, seed=12)
    gamma = _rng(c, dtype=jnp.float32, seed=13)
    beta = _rng(c, dtype=jnp.float32, seed=14)
    y, mean, var = bn_training(x, gamma, beta, 1e-5)
    ry, rm, rv = _np_bn_training(x, gamma, beta, 1e-5)
    # one flat comparison covers the output AND both returned moments
    out = jnp.concatenate([y.reshape(-1), mean, var])
    ref = np.concatenate([ry.reshape(-1), rm, rv])
    return out, ref

# kernel registry: name -> (config generator, runner, exact?)
KERNELS: Dict[str, Tuple[Callable, Callable, bool]] = {
    "flash_attention": (_flash_configs, _run_flash, False),
    "dot_product_attention": (_dpa_configs, _run_dpa, False),
    "gather_pages": (_paged_configs, _run_gather, True),
    "paged_attention": (_paged_configs, _run_paged_attention, False),
    "fused_paged_attention": (_fused_paged_configs, _run_fused_paged, False),
    "fused_dropout_residual_norm": (_epilogue_configs, _run_epilogue, False),
    "grouped_experts": (_grouped_experts_configs, _run_grouped_experts,
                        False),
    "delta_state_step": (_delta_step_configs, _run_delta_step, False),
    "kda_state_step": (_kda_step_configs, _run_kda_step, False),
    "pallas_lrn": (_pallas2d_configs, _run_lrn, False),
    "pallas_bn_inference": (_pallas2d_configs, _run_bn_inference, False),
    "pallas_bn_training": (_pallas2d_configs, _run_bn_training, False),
}


# ---------------------------------------------------------------------------
# classification + report
# ---------------------------------------------------------------------------

_SETUP_ERRORS = (ImportError, AttributeError, NotImplementedError)

def _config_status(stats: Dict[str, float], dtype: str,
                   exact: bool) -> str:
    budget = 0.0 if exact else DTYPE_BUDGET[dtype]
    err = stats["max_rel_error"]
    if err <= budget:
        return "pass"
    if budget and err <= TOLERANCE_SLACK * budget:
        return "tolerance_only"
    return "fail"

def classify(configs: List[Dict[str, Any]]) -> str:
    """Kernel-level divergence class from its per-config results (see
    module docstring for the taxonomy)."""
    statuses = [c["status"] for c in configs]
    if statuses and all(s == "error" for s in statuses):
        return "reference_setup"
    if "fail" in statuses:
        by_dtype: Dict[str, set] = {}
        for c in configs:
            by_dtype.setdefault(c.get("dtype", "float32"),
                                set()).add(c["status"])
        for sts in by_dtype.values():
            if "fail" in sts and "pass" in sts:
                return "shape_dependent"
        return "kernel_divergence"
    if "tolerance_only" in statuses:
        return "tolerance_only"
    return "within_tolerance"

def run_sweep(kernels: Optional[Sequence[str]] = None,
              full: bool = False) -> Dict[str, Any]:
    """Run the differential grid and build the kernel_trust document."""
    report: Dict[str, Any] = {"schema": 1, "platform": jax.devices()[0]
                              .platform, "jax_version": jax.__version__,
                              "dtype_budgets": dict(DTYPE_BUDGET),
                              "kernels": {}, "all": []}
    for name in (kernels or KERNELS):
        gen, run, exact = KERNELS[name]
        entries: List[Dict[str, Any]] = []
        for cfg in gen(full):
            entry = dict(cfg)
            try:
                out, ref = run(cfg)
                entry.update(measure(out, ref, cfg["dtype"]))
                entry["status"] = _config_status(entry, cfg["dtype"], exact)
            except _SETUP_ERRORS as e:
                entry.update(status="error", classification=(
                    "reference_setup"), error=f"{type(e).__name__}: {e}")
            entries.append(entry)
        cls = classify(entries)
        measured = [e for e in entries if "max_rel_error" in e]
        worst = (max(measured, key=lambda e: e["max_rel_error"])
                 if measured else None)
        kd = {
            "configs": entries,
            "classification": cls,
            "trusted": cls in ("within_tolerance", "tolerance_only"),
            "max_rel_error": worst["max_rel_error"] if worst else None,
            "worst_config": ({k: worst[k] for k in
                              ("shape", "dtype", "causal", "window",
                               "variant", "page_size", "pages")
                              if k in worst} if worst else None),
        }
        report["kernels"][name] = kd
        if worst is not None:
            report["all"].append({
                "metric": f"Kernel max rel error ({name})",
                "value": worst["max_rel_error"],
                "unit": "rel", "classification": cls,
                "configs": len(entries),
                "failing_configs": sum(
                    1 for e in entries if e["status"] == "fail"),
            })
    report["summary"] = {
        "kernels": len(report["kernels"]),
        "untrusted": sorted(n for n, k in report["kernels"].items()
                            if not k["trusted"]),
        "failing_configs": sum(
            e.get("failing_configs", 0) for e in report["all"]),
    }
    report["triage"] = {"flash_attention_tests": FLASH_TEST_TRIAGE}
    return report

def publish_metrics(report: Dict[str, Any], registry=None) -> None:
    """Mirror each kernel's worst-config error into the gauge family."""
    if registry is None:
        from deeplearning4j_tpu.observability import get_registry
        registry = get_registry()
    g = registry.gauge(
        _KERNEL_ERR, "Worst-config max relative error of each fused "
        "kernel vs its float64 reference, from the most recent "
        "kernel-trust sweep (observability.kerneldiff)",
        labels=("kernel",))
    for name, k in report["kernels"].items():
        if k["max_rel_error"] is not None:
            g.set(k["max_rel_error"], kernel=name)

def format_report(report: Dict[str, Any]) -> str:
    lines = [f"kernel trust sweep ({report['platform']}, "
             f"jax {report['jax_version']})"]
    for name, k in report["kernels"].items():
        err = (f"{k['max_rel_error']:.3g}"
               if k["max_rel_error"] is not None else "n/a")
        lines.append(
            f"  {'ok ' if k['trusted'] else 'BAD'} {name:<24} "
            f"max_rel={err:<10} {k['classification']} "
            f"({len(k['configs'])} configs)")
    return "\n".join(lines)


def check_registry(trust_path: str) -> int:
    """CI gate: every kernel in the committed trust document must exist
    in this registry and vice versa — a fused kernel that is not swept
    has no claim to trust, and a trust entry with no surviving kernel is
    stale evidence.  Returns a nonzero exit code on any mismatch."""
    with open(trust_path) as f:
        doc = json.load(f)
    in_doc = set(doc.get("kernels", {}))
    in_reg = set(KERNELS)
    rc = 0
    for name in sorted(in_reg - in_doc):
        print(f"kernel '{name}' is registered in kerneldiff but absent "
              f"from {trust_path} — regenerate the trust document "
              "(python -m deeplearning4j_tpu.observability.kerneldiff "
              f"--full --out {trust_path})", file=sys.stderr)
        rc = 1
    for name in sorted(in_doc - in_reg):
        print(f"kernel '{name}' appears in {trust_path} but has no "
              "kerneldiff registry entry — its trust evidence is stale",
              file=sys.stderr)
        rc = 1
    if rc == 0:
        print(f"registry <-> {trust_path} consistent "
              f"({len(in_reg)} kernels)")
    return rc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", default=None, help="write kernel_trust.json")
    ap.add_argument("--full", action="store_true",
                    help="full grid (default: quick CPU grid)")
    ap.add_argument("--kernels", default=None,
                    help="comma-separated subset of kernels")
    ap.add_argument("--baseline", default=None,
                    help="compare against a committed kernel_trust.json "
                         "with regression.KERNEL_TRUST_RULES")
    ap.add_argument("--check-registry", default=None, metavar="PATH",
                    help="no sweep: verify the committed trust document "
                         "and this registry list the same kernels")
    args = ap.parse_args(argv)
    if args.check_registry:
        return check_registry(args.check_registry)
    names = args.kernels.split(",") if args.kernels else None
    report = run_sweep(kernels=names, full=args.full)
    publish_metrics(report)
    print(format_report(report))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1, sort_keys=True)
        print(f"wrote {args.out}")
    rc = 0
    if args.baseline:
        from deeplearning4j_tpu.observability import regression
        with open(args.baseline) as f:
            base = json.load(f)
        rep = regression.compare(base, report,
                                 regression.KERNEL_TRUST_RULES)
        print(rep.format())
        rc = rep.exit_code
    if report["summary"]["untrusted"]:
        print(f"UNTRUSTED kernels: {report['summary']['untrusted']}",
              file=sys.stderr)
        rc = rc or 1
    return rc


if __name__ == "__main__":
    sys.exit(main())
