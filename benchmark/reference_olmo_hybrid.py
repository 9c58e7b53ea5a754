"""The plain reference for ``olmo-hybrid-7b-pp4`` (Olmo-Hybrid-7B, ``model_type
olmo_hybrid``; the linear layers are Gated DeltaNet, Yang, Kautz and
Hatamizadeh, arXiv:2412.06464, as FLA's ``GatedDeltaNet`` computes it):
straightforward ``jax.numpy``, float32, every product at ``highest``
precision, the recurrence a ``lax.scan`` a position at a time from zero state
(not the chunked form the program runs), no cache, no batching.  It imports
nothing of the program; the small helpers it shares with the other
references (``linear``, ``rms_norm``, ``swiglu``, the leaf key, the rounding
of a ``precision``) come from ``reference_k2``, the leaf draw from
``reference_jamba``.

Decoder layer ``i`` (Olmo's post-sub-layer norms, no bias anywhere):
``x <- x + RMSNorm(mixer_i(x))``, ``x <- x + RMSNorm(SwiGLU(x))``
(``intermediate_size`` 11008, silu).  ``mixer_i`` is full attention where
``layer_types[i]`` is ``full_attention`` (layers 3 and 7 of the 8 here),
else Gated DeltaNet.  After the last layer a final RMSNorm; logits ``= x
W_head`` (untied).  ``rms_norm_eps`` 1e-6.

Gated DeltaNet (``H`` = 30 heads, ``d_k`` = 96, ``d_v`` = 192, convolution
4), for a sequence ``u`` [T, 3840]:
  1. ``q = u W_q``, ``k = u W_k`` [T, 2880]; ``v = u W_v`` [T, 5760];
     ``a = u W_a``, ``b = u W_b`` [T, 30]; ``z = u W_g`` [T, 5760].
  2. ``q``, ``k``, ``v`` each ``silu(conv(.))``: causal depthwise convolution
     of width 4, no bias (``x_t`` from ``x_{t-3..t}``).
  3. per head, ``q <- q / sqrt(|q|^2 + 1e-6) / sqrt(96)``, ``k <- k /
     sqrt(|k|^2 + 1e-6)``; ``beta = 2 sigmoid(b)`` (``allow_neg_eigval``);
     ``g = -exp(A_log) softplus(a + dt_bias)``.
  4. per head, ``S_t = exp(g_t) (S_{t-1} - beta_t k_t (k_t^T S_{t-1})) +
     beta_t k_t v_t^T`` (``S`` [96, 192], ``S_{-1}`` = 0); ``o_t = S_t^T q_t``.
  5. ``y = RMSNorm_192(o) * w * silu(z)`` (``w`` [192] shared by the heads,
     eps ``rms_norm_eps``), out ``= y W_o``.

Full attention (layers 3, 7): ``q = RMSNorm_3840(u W_q)``, ``k =
RMSNorm_3840(u W_k)`` (Olmo 3's whole-width norms, each with a gain), ``v =
u W_v``; 30 heads of 128 on both sides, causal softmax(``q k^T / sqrt(128)``)
``v``, ``W_o``; NO rotary (``rope_theta`` null; the configuration's
``assumed``).

Departures from the published model, each also in the configuration file:
the depth (8 of 32 layers, stage 1 of four with the embedding and head
beside it) and the weights (random from the seed).  Leaves: ``A`` uniform in
(0, 16] and ``A_log = log A``; ``dt_bias`` the inverse softplus of a step
drawn log-uniform in [1e-3, 1e-1]; the others N(0, ``initializer_range``),
norm gains 1 + N(0, range); every leaf rounded to bfloat16, the stored dtype.

``precision``: ``f32`` | ``bf16`` | ``fp8`` round the operands of the linear
layers (the mixers' projections, the FFN's three, the head); the
convolutions, the norms, the gates and the recurrence stay float32 in every
precision.

The model is never held whole: ``hidden_states`` makes one layer's leaves
(0.86 GB in float32 for a linear layer), pushes every sequence through it,
and frees them; ``logits_in_blocks`` applies the head to a few hundred
positions at a time.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from benchmark.reference_jamba import _draw, logits_in_blocks  # noqa: F401
from benchmark.reference_k2 import (  # noqa: F401  (the reference's surface)
    HIGHEST, _DTYPES, leaf_key, linear, logits_of, rms_norm, swiglu,
)

A_MAX = 16.0
DT_MIN, DT_MAX = 1e-3, 1e-1
L2_EPS = 1e-6
KEYS = ("hidden_size", "intermediate_size", "num_attention_heads",
        "num_key_value_heads", "linear_num_key_heads",
        "linear_num_value_heads", "linear_key_head_dim",
        "linear_value_head_dim", "linear_conv_kernel_dim",
        "linear_allow_neg_eigval", "rms_norm_eps")


# ------------------------------------------------------------------ shapes
def is_full(cfg: dict, i: int) -> bool:
    return cfg["layer_types"][i] == "full_attention"


def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or (cfg["hidden_size"]
                                   // cfg["num_attention_heads"])


def linear_widths(cfg: dict):
    """(heads, d_k, d_v, q/k width, v width)."""
    h, dk = cfg["linear_num_value_heads"], cfg["linear_key_head_dim"]
    dv = cfg["linear_value_head_dim"]
    return h, dk, dv, h * dk, h * dv


def layer_shapes(cfg: dict, i: int) -> dict:
    """The leaves of layer ``i`` by name (without the ``L<i>.`` prefix)."""
    h, inter = cfg["hidden_size"], cfg["intermediate_size"]
    if is_full(cfg, i):
        q = cfg["num_attention_heads"] * head_dim(cfg)
        kv = cfg["num_key_value_heads"] * head_dim(cfg)
        out = {"wq": (h, q), "wk": (h, kv), "wv": (h, kv), "wo": (q, h),
               "q_norm.g": (q,), "k_norm.g": (kv,)}
    else:
        heads, _, dv, qk, vw = linear_widths(cfg)
        kc = cfg["linear_conv_kernel_dim"]
        out = {"wq": (h, qk), "wk": (h, qk), "wv": (h, vw), "wa": (h, heads),
               "wb": (h, heads), "wg": (h, vw), "wo": (vw, h),
               "conv_q.W": (qk, kc), "conv_k.W": (qk, kc),
               "conv_v.W": (vw, kc), "A_log": (heads,), "dt_bias": (heads,),
               "o_norm.g": (dv,)}
    out.update({"mixer_norm.g": (h,), "w_gate": (h, inter), "w_up": (h, inter),
                "w_down": (inter, h), "ffn_norm.g": (h,)})
    return out


def leaf_shapes(cfg: dict) -> dict:
    """Every leaf by name, in a fixed order."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    out = {"emb.W": (v, h), "emb.b": (h,)}
    for i in range(cfg["num_hidden_layers"]):
        out.update({f"L{i}.{k}": s for k, s in layer_shapes(cfg, i).items()})
    out.update({"norm.g": (h,), "head.W": (h, v), "head.b": (v,)})
    return out


# ----------------------------------------------------------------- weights
def make_leaf(cfg: dict, seed: int, name: str, shape, dtype=jnp.float32):
    """One leaf from ``(seed, name)``, rounded to the stored dtype the
    configuration states, in ``dtype``."""
    stored = _DTYPES[cfg["torch_dtype"]]
    kind = name.rsplit(".", 1)[-1]
    if name in ("emb.b", "head.b"):
        return jnp.zeros(shape, dtype)
    if kind == "A_log":
        u = jax.random.uniform(leaf_key(seed, name), tuple(shape), jnp.float32)
        return jnp.log(A_MAX * (1.0 - u)).astype(stored).astype(dtype)
    if kind == "dt_bias":
        u = jax.random.uniform(leaf_key(seed, name), tuple(shape), jnp.float32)
        step = jnp.exp(u * (math.log(DT_MAX) - math.log(DT_MIN))
                       + math.log(DT_MIN))
        return (step + jnp.log(-jnp.expm1(-step))).astype(stored).astype(dtype)
    w = _draw(leaf_key(seed, name), tuple(shape),
              float(cfg.get("initializer_range", 0.02)), name.endswith(".g"),
              stored)
    return w.astype(dtype)


def make_leaves(cfg, seed, prefix, shapes, dtype=jnp.float32) -> dict:
    return {k: make_leaf(cfg, seed, prefix + k, s, dtype)
            for k, s in shapes.items()}


def make_weights(cfg: dict, seed: int) -> dict:
    """Every leaf at once, under its full name (toy sizes only)."""
    return {k: make_leaf(cfg, seed, k, s)
            for k, s in leaf_shapes(cfg).items()}


# ----------------------------------------------------------------- forward
def causal_conv(x, w):
    """``x`` [T, d], ``w`` [d, K]: ``y_t = sum_k w[:, k] x_{t - K + 1 + k}``,
    zeros ahead of the sequence, no bias."""
    t, k = x.shape[0], w.shape[1]
    xp = jnp.pad(x, ((k - 1, 0), (0, 0)))
    return sum(xp[j:j + t] * w[:, j] for j in range(k))


def delta_rule(q, k, v, g, beta, s0=None):
    """Step 4, one position a trip: ``q``, ``k`` [T, H, d_k]; ``v`` [T, H,
    d_v]; ``g``, ``beta`` [T, H].  Returns ``(o [T, H, d_v], S_T [H, d_k,
    d_v])``."""
    h, dk, dv = q.shape[1], q.shape[2], v.shape[2]
    s0 = jnp.zeros((h, dk, dv), jnp.float32) if s0 is None else s0

    def step(s, inp):
        q_t, k_t, v_t, g_t, b_t = inp
        ks = jnp.einsum("hk,hkv->hv", k_t, s, precision=HIGHEST)
        s = jnp.exp(g_t)[:, None, None] * (
            s - b_t[:, None, None] * k_t[:, :, None] * ks[:, None, :])
        s = s + b_t[:, None, None] * k_t[:, :, None] * v_t[:, None, :]
        return s, jnp.einsum("hk,hkv->hv", q_t, s, precision=HIGHEST)

    s, o = jax.lax.scan(step, s0, (q, k, v, g, beta))
    return o, s


def _l2(x):
    return x / jnp.sqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + L2_EPS)


def gated_deltanet(u, w, cfg, precision, s0=None):
    """The linear mixer on one sequence ``u`` [T, h] from zero state (or
    ``s0``); ``(out [T, h], S_T [H, d_k, d_v])``."""
    t = u.shape[0]
    heads, dk, dv, _, _ = linear_widths(cfg)
    q = jax.nn.silu(causal_conv(linear(u, w["wq"], precision), w["conv_q.W"]))
    k = jax.nn.silu(causal_conv(linear(u, w["wk"], precision), w["conv_k.W"]))
    v = jax.nn.silu(causal_conv(linear(u, w["wv"], precision), w["conv_v.W"]))
    q = _l2(q.reshape(t, heads, dk)) / math.sqrt(dk)
    k = _l2(k.reshape(t, heads, dk))
    beta = jax.nn.sigmoid(linear(u, w["wb"], precision))
    if cfg["linear_allow_neg_eigval"]:
        beta = 2.0 * beta
    g = -jnp.exp(w["A_log"]) * jax.nn.softplus(linear(u, w["wa"], precision)
                                               + w["dt_bias"])
    o, s = delta_rule(q, k, v.reshape(t, heads, dv), g, beta, s0)
    y = rms_norm(o, w["o_norm.g"], cfg["rms_norm_eps"]).reshape(t, -1)
    y = y * jax.nn.silu(linear(u, w["wg"], precision))
    return linear(y, w["wo"], precision), s


def attention(u, w, cfg, precision):
    """Causal multi-head attention of one sequence ``u`` [T, h] with Olmo's
    whole-width q/k norms, no position term."""
    t, heads, kvh = (u.shape[0], cfg["num_attention_heads"],
                     cfg["num_key_value_heads"])
    eps = cfg["rms_norm_eps"]
    q = rms_norm(linear(u, w["wq"], precision), w["q_norm.g"], eps)
    k = rms_norm(linear(u, w["wk"], precision), w["k_norm.g"], eps)
    q = q.reshape(t, heads, -1)
    k = k.reshape(t, kvh, -1)
    v = linear(u, w["wv"], precision).reshape(t, kvh, -1)
    causal = jnp.tril(jnp.ones((t, t), bool))
    scale = q.shape[-1] ** -0.5

    def one_head(args):            # the scores held are one head's [T, T]
        qh, kh, vh = args
        s = jnp.matmul(qh, kh.T, precision=HIGHEST) * scale
        p = jax.nn.softmax(jnp.where(causal, s, -1e30), axis=-1)
        return jnp.matmul(p, vh, precision=HIGHEST)

    group = heads // kvh
    o = jax.lax.map(one_head, (q.transpose(1, 0, 2),
                               jnp.repeat(k.transpose(1, 0, 2), group, 0),
                               jnp.repeat(v.transpose(1, 0, 2), group, 0)))
    return linear(o.transpose(1, 0, 2).reshape(t, -1), w["wo"], precision)


def block(x, w, cfg, precision):
    """One layer on one sequence x [T, h]; linear or full by its leaves."""
    eps = cfg["rms_norm_eps"]
    mixed = (attention(x, w, cfg, precision) if "q_norm.g" in w
             else gated_deltanet(x, w, cfg, precision)[0])
    x = x + rms_norm(mixed, w["mixer_norm.g"], eps)
    ffn = swiglu(x, w["w_gate"], w["w_up"], w["w_down"], precision)
    return x + rms_norm(ffn, w["ffn_norm.g"], eps)


def freeze(cfg: dict):
    """The sizes the reference reads, hashable for ``static_argnums``."""
    return tuple((k, cfg[k]) for k in KEYS)


@partial(jax.jit, static_argnums=(2, 3))
def block_of(x, w, cfg_items, precision="f32"):
    return block(x, w, dict(cfg_items), precision)


def _sub(w, prefix):
    return {k[len(prefix):]: a for k, a in w.items() if k.startswith(prefix)}


def forward(w: dict, ids, cfg: dict, precision="f32"):
    """Logits [T, V] of one sequence ``ids`` [T], all leaves given."""
    x = w["emb.W"][ids] + w["emb.b"]
    items = freeze(cfg)
    for i in range(cfg["num_hidden_layers"]):
        x = block_of(x, _sub(w, f"L{i}."), items, precision)
    x = rms_norm(x, w["norm.g"], cfg["rms_norm_eps"])
    return logits_of(x, w["head.W"], w["head.b"], precision)


def hidden_states(cfg: dict, seed: int, seqs, precisions=("f32",)) -> dict:
    """``{precision: [final-normed hidden [T, h] of each sequence]}``, one
    layer's leaves alive at a time."""
    h = cfg["hidden_size"]
    emb = make_leaves(cfg, seed, "emb.", {"W": (cfg["vocab_size"], h),
                                          "b": (h,)})
    xs = {p: [emb["W"][jnp.asarray(s)] + emb["b"] for s in seqs]
          for p in precisions}
    del emb
    items = freeze(cfg)
    for i in range(cfg["num_hidden_layers"]):
        w = make_leaves(cfg, seed, f"L{i}.", layer_shapes(cfg, i))
        for p in precisions:
            xs[p] = [block_of(x, w, items, p) for x in xs[p]]
        jax.block_until_ready(xs)
        del w
    g = make_leaf(cfg, seed, "norm.g", (h,))
    return {p: [rms_norm(x, g, cfg["rms_norm_eps"]) for x in xs[p]]
            for p in precisions}


def head_leaves(cfg: dict, seed: int):
    """(the head's weight, its zero bias): untied."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    return (make_leaf(cfg, seed, "head.W", (h, v)),
            make_leaf(cfg, seed, "head.b", (v,)))
