"""The plain reference for ``jamba2-3b`` (AI21-Jamba2-3B, ``model_type
jamba``; Jamba, arXiv:2403.19887; the mixer is Mamba-1, arXiv:2312.00752,
as HF's ``JambaMambaMixer`` computes it): straightforward ``jax.numpy``,
float32, every product at ``highest`` precision, the recurrence a
``lax.scan`` over time with no chunking, no cache, no batching.  It imports
nothing of the program; the small helpers it shares with the other
references (``linear``, ``rms_norm``, ``swiglu``, the leaf key, the rounding
of a ``precision``) come from ``reference_k2``.

Decoder layer ``i`` (pre-norm, no bias anywhere but ``conv1d`` and
``dt_proj``): ``x <- x + mixer_i(RMSNorm(x))``, ``x <- x +
SwiGLU(RMSNorm(x))`` (``intermediate_size`` 8192, silu; ``num_experts`` 1,
so every FFN is dense).  ``mixer_i`` is attention iff ``i mod
attn_layer_period == attn_layer_offset`` (layers 7 and 21), else Mamba.
After the last layer a final RMSNorm; logits ``= x E^T`` with ``E`` the
embedding (tied).  ``rms_norm_eps`` 1e-6.

Mamba mixer (``d = mamba_expand * hidden_size`` = 5120 channels, ``N =
mamba_d_state`` = 16, ``mamba_d_conv`` 4, ``R = mamba_dt_rank`` = 160), for
a sequence ``u`` [T, 2560]:
  1. ``[x, z] = u W_in`` (each [T, d]).
  2. ``x = silu(conv(x) + b_conv)``: causal depthwise convolution of width 4
     (``x_t`` from ``x_{t-3..t}``).
  3. ``[dt_r, B, C] = x W_x`` (R, N, N); JAMBA'S OWN STEP: ``dt_r``, ``B``,
     ``C`` each through an RMSNorm with a gain.
  4. ``dt = softplus(dt_r W_dt + b_dt)`` [T, d]; ``A = -exp(A_log)`` [d, N].
  5. ``h_t = exp(dt_t (x) A) * h_{t-1} + (dt_t * x_t) (x) B_t`` (``h`` [d, N],
     ``h_{-1}`` = 0); ``y_t = h_t C_t + D * x_t``.
  6. ``out = (y * silu(z)) W_out``.

Attention (layers 7, 21): ``q = u W_q`` (20 heads x 128), ``k = u W_k``, ``v
= u W_v`` (1 head x 128, shared by all 20), causal
softmax(``q k^T / sqrt(128)``) ``v``, ``W_o``; NO rotary, no position term.

Leaves (each also in the configuration file under ``assumed``): ``A_log[c,
n] = log(n + 1)`` and ``D = 1`` (Mamba's S4D-real start); ``dt_proj.b`` the
inverse softplus of a step drawn log-uniform in [1e-3, 1e-1]; the others
N(0, ``initializer_range``), norm gains 1 + N(0, range); every leaf rounded
to bfloat16, the stored dtype.  The head IS the embedding: ``head.W`` is
``emb.W`` transposed, the same numbers (the program serves a second leaf
holding them).

``precision``: ``f32`` | ``bf16`` | ``fp8`` round the operands of the linear
layers (the mixer's four, attention's four, the FFN's three, the head); the
convolution, the norms, softplus and the recurrence stay float32 in every
precision.

The model is never held whole: ``hidden_states`` makes one layer's leaves
(0.42 GB in float32 for a Mamba layer), pushes every sequence through it,
and frees them; ``logits_in_blocks`` applies the head to a few hundred
positions at a time.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from benchmark.reference_k2 import (  # noqa: F401  (the reference's surface)
    HIGHEST, _DTYPES, leaf_key, linear, logits_of, rms_norm, swiglu,
)

DT_MIN, DT_MAX = 1e-3, 1e-1
HEAD_BLOCK = 512
KEYS = ("hidden_size", "intermediate_size", "num_attention_heads",
        "num_key_value_heads", "mamba_expand", "mamba_d_state",
        "mamba_d_conv", "mamba_dt_rank", "rms_norm_eps")


# ------------------------------------------------------------------ shapes
def is_attention(cfg: dict, i: int) -> bool:
    return i % cfg["attn_layer_period"] == cfg["attn_layer_offset"]


def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or (cfg["hidden_size"]
                                   // cfg["num_attention_heads"])


def layer_shapes(cfg: dict, i: int) -> dict:
    """The leaves of layer ``i`` by name (without the ``L<i>.`` prefix)."""
    h, inter = cfg["hidden_size"], cfg["intermediate_size"]
    out = {"in_norm.g": (h,)}
    if is_attention(cfg, i):
        hd = head_dim(cfg)
        q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
        out.update({"wq": (h, q), "wk": (h, kv), "wv": (h, kv), "wo": (q, h)})
    else:
        d, n = cfg["mamba_expand"] * h, cfg["mamba_d_state"]
        r, k = cfg["mamba_dt_rank"], cfg["mamba_d_conv"]
        out.update({"in_proj": (h, 2 * d), "conv.W": (d, k), "conv.b": (d,),
                    "x_proj": (d, r + 2 * n), "dt_norm.g": (r,),
                    "b_norm.g": (n,), "c_norm.g": (n,), "dt_proj.W": (r, d),
                    "dt_proj.b": (d,), "A_log": (d, n), "D": (d,),
                    "out_proj": (d, h)})
    out.update({"post_norm.g": (h,), "w_gate": (h, inter), "w_up": (h, inter),
                "w_down": (inter, h)})
    return out


def leaf_shapes(cfg: dict) -> dict:
    """Every leaf by name, in a fixed order; ``head.W`` is the embedding
    again (tied) and counts no parameter of its own."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    out = {"emb.W": (v, h), "emb.b": (h,)}
    for i in range(cfg["num_hidden_layers"]):
        out.update({f"L{i}.{k}": s for k, s in layer_shapes(cfg, i).items()})
    out.update({"norm.g": (h,), "head.W": (h, v), "head.b": (v,)})
    return out


# ----------------------------------------------------------------- weights
@partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _draw(key, shape, std, gain, stored):
    w = std * jax.random.normal(key, shape, jnp.float32)
    return ((1.0 + w) if gain else w).astype(stored)


def make_leaf(cfg: dict, seed: int, name: str, shape, dtype=jnp.float32):
    """One leaf from ``(seed, name)``, rounded to the stored dtype the
    configuration states, in ``dtype``."""
    stored = _DTYPES[cfg["torch_dtype"]]
    kind = name.rsplit(".", 1)[-1]
    if name in ("emb.b", "head.b"):
        return jnp.zeros(shape, dtype)
    if name == "head.W":                       # tied: the embedding itself
        return make_leaf(cfg, seed, "emb.W", shape[::-1], dtype).T
    if kind == "A_log":
        a = jnp.log(jnp.arange(1, shape[1] + 1, dtype=jnp.float32))
        return jnp.broadcast_to(a, shape).astype(stored).astype(dtype)
    if kind == "D":
        return jnp.ones(shape, dtype)
    if name.endswith("dt_proj.b"):
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
def causal_conv(x, w, b):
    """``x`` [T, d], ``w`` [d, K], ``b`` [d]: ``y_t = sum_k w[:, k] x_{t - K
    + 1 + k} + b``, zeros ahead of the sequence."""
    t, k = x.shape[0], w.shape[1]
    xp = jnp.pad(x, ((k - 1, 0), (0, 0)))
    return sum(xp[j:j + t] * w[:, j] for j in range(k)) + b


def selective_scan(x, dt, a, b, c, d_skip, h0=None):
    """Steps 5: ``x``, ``dt`` [T, d]; ``a`` [d, N]; ``b``, ``c`` [T, N].
    Returns ``(y [T, d], h_T [d, N])``, one time step a trip."""
    h0 = jnp.zeros(a.shape, jnp.float32) if h0 is None else h0

    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp
        h = jnp.exp(dt_t[:, None] * a) * h + (dt_t * x_t)[:, None] * b_t[None]
        return h, jnp.matmul(h, c_t, precision=HIGHEST) + d_skip * x_t

    h, y = jax.lax.scan(step, h0, (x, dt, b, c))
    return y, h


def mamba(u, w, cfg, precision, h0=None):
    """The mixer on one sequence ``u`` [T, h] from zero state (or ``h0``);
    ``(out [T, h], h_T [d, N])``."""
    d = cfg["mamba_expand"] * cfg["hidden_size"]
    r, n, eps = cfg["mamba_dt_rank"], cfg["mamba_d_state"], cfg["rms_norm_eps"]
    xz = linear(u, w["in_proj"], precision)
    x, z = xz[:, :d], xz[:, d:]
    x = jax.nn.silu(causal_conv(x, w["conv.W"], w["conv.b"]))
    sel = linear(x, w["x_proj"], precision)
    dt_r = rms_norm(sel[:, :r], w["dt_norm.g"], eps)
    b = rms_norm(sel[:, r:r + n], w["b_norm.g"], eps)
    c = rms_norm(sel[:, r + n:], w["c_norm.g"], eps)
    dt = jax.nn.softplus(linear(dt_r, w["dt_proj.W"], precision)
                         + w["dt_proj.b"])
    y, h = selective_scan(x, dt, -jnp.exp(w["A_log"]), b, c, w["D"], h0)
    return linear(y * jax.nn.silu(z), w["out_proj"], precision), h


def attention(u, w, cfg, precision):
    """Causal multi-query attention of one sequence ``u`` [T, h], no
    position term; the kv heads are shared by groups of query heads."""
    t, heads, kvh = (u.shape[0], cfg["num_attention_heads"],
                     cfg["num_key_value_heads"])
    q = linear(u, w["wq"], precision).reshape(t, heads, -1)
    k = linear(u, w["wk"], precision).reshape(t, kvh, -1)
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
    """One layer on one sequence x [T, h]; Mamba or attention by its
    leaves."""
    eps = cfg["rms_norm_eps"]
    u = rms_norm(x, w["in_norm.g"], eps)
    if "wq" in w:
        x = x + attention(u, w, cfg, precision)
    else:
        x = x + mamba(u, w, cfg, precision)[0]
    return x + swiglu(rms_norm(x, w["post_norm.g"], eps), w["w_gate"],
                      w["w_up"], w["w_down"], precision)


def freeze(cfg: dict):
    """The sizes the reference reads, hashable for ``static_argnums``."""
    return tuple((k, cfg[k]) for k in KEYS)


@partial(jax.jit, static_argnums=(2, 3))
def block_of(x, w, cfg_items, precision="f32"):
    return block(x, w, dict(cfg_items), precision)


def logits_in_blocks(h, head_w, head_b, precision="f32"):
    """``logits_of`` over ``HEAD_BLOCK`` positions at a time."""
    return jnp.concatenate([
        logits_of(h[i:i + HEAD_BLOCK], head_w, head_b, precision)
        for i in range(0, h.shape[0], HEAD_BLOCK)])


def _sub(w, prefix):
    return {k[len(prefix):]: a for k, a in w.items() if k.startswith(prefix)}


def forward(w: dict, ids, cfg: dict, precision="f32"):
    """Logits [T, V] of one sequence ``ids`` [T], all leaves given; the
    head is the embedding (``head.W`` is not read)."""
    x = w["emb.W"][ids] + w["emb.b"]
    items = freeze(cfg)
    for i in range(cfg["num_hidden_layers"]):
        x = block_of(x, _sub(w, f"L{i}."), items, precision)
    x = rms_norm(x, w["norm.g"], cfg["rms_norm_eps"])
    return logits_of(x, w["emb.W"].T, w["head.b"], precision)


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
    """(the embedding transposed, a zero bias): the tied head."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    return (make_leaf(cfg, seed, "emb.W", (v, h)).T,
            make_leaf(cfg, seed, "head.b", (v,)))
