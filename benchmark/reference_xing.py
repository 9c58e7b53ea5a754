"""The plain reference for ``xing4.0-29b-a4b-pp6``: the DeepSeek-V3 block
(``reference_k2``: latent attention, SwiGLU, sigmoid top-k experts with a
shared one — imported, the same mathematics) with its residual path
replaced by manifold-constrained hyper-connections (mHC, arXiv:2512.24880;
hyper-connections, arXiv:2409.19606), in straightforward ``jax.numpy``,
float32, every product at ``highest`` precision, no cache, no kernels, no
batching.  It imports nothing of the program.

A token's state is ``X`` [n, C], n = ``hc_mult`` residual streams.
``X_0`` = n copies of its embedding row; after the last layer
``x = sum_i X_i``, then RMSNorm and the untied head.

One sub-layer ``F`` (attention, then the FFN: two a decoder layer, each with
leaves of its own, ``attn_hc.*`` and ``ffn_hc.*``), per token:
  r = 1 / sqrt(mean(vec(X)^2) + rms_norm_eps);   m = (vec(X) r) phi
      phi [n C, 2n + n^2]
  H_pre  = sigmoid(a_pre m[:n] + b_pre)                                [n]
  H_post = 2 sigmoid(a_post m[n:2n] + b_post)                          [n]
  Z = clip(a_res mat(m[2n:]) + B_res, mhc_h_res_clamp_min, .._max);  M = exp(Z)
  ``hc_sinkhorn_iters`` times:  M <- M / (column sums + hc_eps),
                                M <- M / (row sums + hc_eps);   H_res = M
  u = sum_j H_pre[j] X_j;   y = F(RMSNorm_g(u))
  X'_i = sum_j H_res[i, j] X_j + H_post[i] y
``alpha`` [3] = (a_pre, a_post, a_res); ``beta`` [2n + n^2] = b_pre, b_post
and B_res row by row.  All of it in float32 in every ``precision`` (as the
router is): ``precision`` rounds the operands of ``F``'s linear layers.

Readings of keys or of the paper's defaults, not of a published modelling
file (each also in the configuration file under ``assumed``): the streams
start as copies and end as a sum; the coefficients' norm has no gain of its
own (the paper folds it into phi); Sinkhorn divides columns first, then
rows, with ``hc_eps`` in the denominators (the paper's T_r(T_c(.))); the
sub-layer keeps its own pre-norm, as Kimi's block has it; the multi-token
prediction module (``num_nextn_predict_layers`` 1) is not instantiated.
Leaves: ``reference_k2``'s rule, and for the mHC leaves ``phi`` N(0, 1 / (n
C)) so that m is of order 1 at any width, ``alpha`` 0.7 (1 + 0.1 N(0, 1)),
``beta`` 0.5 N(0, 1) — with these, 20 iterations leave the column sums
within ~1e-3 of 1 and H varies by token — every leaf rounded to bfloat16.

The model is never held whole: ``hidden_states`` makes one layer's leaves
(2.98 GB in float32 for an expert layer at the published widths), pushes
every sequence through it, and frees them; ``logits_in_blocks`` applies the
head (3,584 x 131,072) to a few hundred positions at a time.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from benchmark import reference_k2 as k2
from benchmark.reference_k2 import (  # noqa: F401  (the reference's surface)
    HIGHEST, head_leaves, is_dense, leaf_key, logits_of, rms_norm,
    router_width,
)

HC_ALPHA, HC_ALPHA_SPREAD, HC_BETA_STD = 0.7, 0.1, 0.5
POST_SCALE = 2.0
HEAD_BLOCK = 512


# ------------------------------------------------------------------ shapes
def hc_shapes(cfg: dict) -> dict:
    n = cfg["hc_mult"]
    width, coeffs = n * cfg["hidden_size"], 2 * n + n * n
    return {"phi": (width, coeffs), "alpha": (3,), "beta": (coeffs,)}


def layer_shapes(cfg: dict, i: int) -> dict:
    out = dict(k2.layer_shapes(cfg, i))
    for sub in ("attn_hc.", "ffn_hc."):
        out.update({sub + k: s for k, s in hc_shapes(cfg).items()})
    return out


def leaf_shapes(cfg: dict) -> dict:
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    out = {"emb.W": (v, h), "emb.b": (h,)}
    for i in range(cfg["num_hidden_layers"]):
        out.update({f"L{i}.{k}": s for k, s in layer_shapes(cfg, i).items()})
    out.update({"norm.g": (h,), "head.W": (h, v), "head.b": (v,)})
    return out


# ----------------------------------------------------------------- weights
def make_leaf(cfg: dict, seed: int, name: str, shape, dtype=jnp.float32):
    """One leaf from ``(seed, name)``, rounded to the stored dtype."""
    if "_hc." not in name:
        return k2.make_leaf(cfg, seed, name, shape, dtype)
    w = jax.random.normal(leaf_key(seed, name), tuple(shape), jnp.float32)
    kind = name.rsplit(".", 1)[1]
    if kind == "phi":
        w = w * shape[0] ** -0.5
    elif kind == "alpha":
        w = HC_ALPHA * (1.0 + HC_ALPHA_SPREAD * w)
    else:
        w = HC_BETA_STD * w
    return w.astype(k2._DTYPES[cfg["torch_dtype"]]).astype(dtype)


def make_leaves(cfg, seed, prefix, shapes, dtype=jnp.float32) -> dict:
    return {k: make_leaf(cfg, seed, prefix + k, s, dtype)
            for k, s in shapes.items()}


def make_weights(cfg: dict, seed: int) -> dict:
    """Every leaf at once, under its full name (toy sizes only)."""
    return {k: make_leaf(cfg, seed, k, s)
            for k, s in leaf_shapes(cfg).items()}


# ----------------------------------------------------------------- forward
def mhc_coefficients(x, w, cfg):
    """(H_pre [T, n], H_post [T, n], H_res [T, n, n]) of states x [T, n, C];
    ``w`` holds ``phi``, ``alpha``, ``beta``."""
    t, n = x.shape[0], cfg["hc_mult"]
    v = x.reshape(t, -1)
    r = 1.0 / jnp.sqrt(jnp.mean(jnp.square(v), -1, keepdims=True)
                       + cfg["rms_norm_eps"])
    m = jnp.matmul(v * r, w["phi"], precision=HIGHEST)
    a, b = w["alpha"], w["beta"]
    h_pre = jax.nn.sigmoid(a[0] * m[:, :n] + b[:n])
    h_post = POST_SCALE * jax.nn.sigmoid(a[1] * m[:, n:2 * n] + b[n:2 * n])
    z = jnp.clip(a[2] * m[:, 2 * n:].reshape(t, n, n)
                 + b[2 * n:].reshape(n, n),
                 cfg["mhc_h_res_clamp_min"], cfg["mhc_h_res_clamp_max"])
    mat = jnp.exp(z)
    for _ in range(cfg["hc_sinkhorn_iters"]):
        mat = mat / (jnp.sum(mat, axis=1, keepdims=True) + cfg["hc_eps"])
        mat = mat / (jnp.sum(mat, axis=2, keepdims=True) + cfg["hc_eps"])
    return h_pre, h_post, mat


def hyper(x, w, cfg, f):
    """One sub-layer ``f`` ([T, C] -> [T, C]) around the streams x [T, n, C]."""
    h_pre, h_post, h_res = mhc_coefficients(x, w, cfg)
    y = f(jnp.einsum("tj,tjc->tc", h_pre, x, precision=HIGHEST))
    return (jnp.einsum("tij,tjc->tic", h_res, x, precision=HIGHEST)
            + h_post[:, :, None] * y[:, None, :])


def _sub(w, prefix):
    return {k[len(prefix):]: a for k, a in w.items() if k.startswith(prefix)}


def block(x, w, cfg, precision):
    """One layer on one sequence's streams x [T, n, C]; dense or MoE by its
    leaves."""
    eps = cfg["rms_norm_eps"]
    x = hyper(x, _sub(w, "attn_hc."), cfg, lambda u: k2.mla(
        rms_norm(u, w["in_norm.g"], eps), w, cfg, precision))

    def ffn(u):
        hdn = rms_norm(u, w["post_norm.g"], eps)
        if "router.W" in w:
            return k2.moe(hdn, w, cfg, precision)
        return k2.swiglu(hdn, w["w_gate"], w["w_up"], w["w_down"], precision)

    return hyper(x, _sub(w, "ffn_hc."), cfg, ffn)


HC_KEYS = ("hc_mult", "hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp_min",
           "mhc_h_res_clamp_max")


def freeze(cfg: dict):
    """The sizes the reference reads, hashable for ``static_argnums``."""
    return k2.freeze(cfg) + tuple((k, cfg[k]) for k in HC_KEYS)


@partial(jax.jit, static_argnums=(2, 3))
def block_of(x, w, cfg_items, precision="f32"):
    return block(x, w, k2._thaw(cfg_items), precision)


def streams_in(emb_rows, cfg):
    return jnp.repeat(emb_rows[:, None, :], cfg["hc_mult"], axis=1)


def logits_in_blocks(h, head_w, head_b, precision="f32"):
    """``logits_of`` over ``HEAD_BLOCK`` positions at a time."""
    return jnp.concatenate([
        logits_of(h[i:i + HEAD_BLOCK], head_w, head_b, precision)
        for i in range(0, h.shape[0], HEAD_BLOCK)])


def forward(w: dict, ids, cfg: dict, precision="f32"):
    """Logits [T, V] of one sequence ``ids`` [T], all leaves given."""
    x = streams_in(w["emb.W"][ids] + w["emb.b"], cfg)
    items = freeze(cfg)
    for i in range(cfg["num_hidden_layers"]):
        x = block_of(x, _sub(w, f"L{i}."), items, precision)
    x = rms_norm(jnp.sum(x, axis=1), w["norm.g"], cfg["rms_norm_eps"])
    return logits_of(x, w["head.W"], w["head.b"], precision)


def hidden_states(cfg: dict, seed: int, seqs, precisions=("f32",)) -> dict:
    """``{precision: [final-normed hidden [T, h] of each sequence]}``, one
    layer's leaves alive at a time."""
    h = cfg["hidden_size"]
    emb = make_leaves(cfg, seed, "emb.", {"W": (cfg["vocab_size"], h),
                                          "b": (h,)})
    xs = {p: [streams_in(emb["W"][jnp.asarray(s)] + emb["b"], cfg)
              for s in seqs] for p in precisions}
    del emb
    items = freeze(cfg)
    for i in range(cfg["num_hidden_layers"]):
        w = make_leaves(cfg, seed, f"L{i}.", layer_shapes(cfg, i))
        for p in precisions:
            xs[p] = [block_of(x, w, items, p) for x in xs[p]]
        jax.block_until_ready(xs)
        del w
    g = make_leaf(cfg, seed, "norm.g", (h,))
    return {p: [rms_norm(jnp.sum(x, axis=1), g, cfg["rms_norm_eps"])
                for x in xs[p]] for p in precisions}
