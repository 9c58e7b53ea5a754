"""The plain reference: a StarCoder2-style decoder in straightforward
``jax.numpy``, float32, every matrix product at ``highest`` precision.

It imports nothing of the program and takes nothing the program made: the
weights come from ``make_weights(cfg, seed)`` below, which is also what the
harness installs into the program, so both sides start from the same
numbers without either reading the other's.

Block, as published for StarCoder2 (BigCode, arXiv:2402.19173; HF
``modeling_starcoder2.py``): ``x += attn(LN(x))``, ``x += mlp(LN(x))``;
LayerNorm with bias, eps 1e-5; biased q/k/v/o projections, grouped-query
attention, rotary embedding in the rotate-half pairing over the whole head;
``c_fc -> gelu(tanh) -> c_proj`` with biases; final LayerNorm; linear head.

Departures from the published model (each also in the configuration files
under ``assumed``): the head is untied from the embedding and carries a
bias, and the embedding adds a bias vector, because the program's DSL
layers have them (both start at zero, so the first forward equals the
published one); the sliding window (4096) is not applied, because no cell
here has a sequence longer than the window.

``precision`` selects how the linear layers multiply:
  ``f32``   the reference: float32 operands, ``highest``
  ``bf16``  operands rounded to bfloat16 (what the configurations state)
  ``fp8``   operands rounded to float8_e4m3 with a per-tensor scale — the
            step below bfloat16, used only by the control of ``correct``
Rounded operands are multiplied in float32 with a straight-through
gradient, so the same code serves the training control.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
BETA1, BETA2, ADAM_EPS = 0.9, 0.999, 1e-8
LOSS_ROWS = 1024      # rows of logits the training loss holds at once


# ------------------------------------------------------------------ weights
def leaf_shapes(cfg: dict) -> dict:
    """Every parameter of the cut model, by name, in a fixed order."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    hd = cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    inter = cfg["intermediate_size"]
    out = {"emb.W": (v, h), "emb.b": (h,)}
    for i in range(cfg["num_hidden_layers"]):
        p = f"L{i}."
        out.update({
            p + "ln1.g": (h,), p + "ln1.b": (h,),
            p + "wq": (h, nq), p + "bq": (nq,),
            p + "wk": (h, nkv), p + "bk": (nkv,),
            p + "wv": (h, nkv), p + "bv": (nkv,),
            p + "wo": (nq, h), p + "bo": (h,),
            p + "ln2.g": (h,), p + "ln2.b": (h,),
            p + "w1": (h, inter), p + "b1": (inter,),
            p + "w2": (inter, h), p + "b2": (h,)})
    out.update({"lnf.g": (h,), "lnf.b": (h,),
                "head.W": (h, v), "head.b": (v,)})
    return out


def seed_key(seed: int, stream: int = 0) -> jax.Array:
    """A raw threefry key from a seed of up to 64 bits (``--seed`` may
    pass 2**31) and a small stream number."""
    seed = int(seed)
    return jnp.asarray(np.array(
        [((seed >> 32) ^ (stream * 0x9E3779B1)) & 0xFFFFFFFF,
         seed & 0xFFFFFFFF], np.uint32))


def make_weights(cfg: dict, seed: int) -> dict:
    """All weights on the device in one jitted call from the seed, float32:
    matrices and biases N(0, initializer_range), LayerNorm gains
    1 + N(0, range); the two biases the published model lacks start at 0."""
    return _make_weights(seed_key(seed), _freeze(leaf_shapes(cfg)),
                         float(cfg.get("initializer_range", 0.02)))


def _freeze(shapes: dict):
    return tuple((k, tuple(v)) for k, v in shapes.items())


@partial(jax.jit, static_argnums=(1, 2))
def _make_weights(key, shapes, std):
    out = {}
    for i, (name, shape) in enumerate(shapes):
        if name in ("emb.b", "head.b"):
            out[name] = jnp.zeros(shape, jnp.float32)
            continue
        w = std * jax.random.normal(jax.random.fold_in(key, i), shape,
                                    jnp.float32)
        out[name] = 1.0 + w if name.endswith(".g") else w
    return out


# ------------------------------------------------------------------ forward
def _round_to(x, precision):
    if precision == "bf16":
        q = x.astype(jnp.bfloat16).astype(jnp.float32)
    elif precision == "fp8":
        top = float(jnp.finfo(jnp.float8_e4m3fn).max)
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
        q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    else:
        raise ValueError(f"unknown precision {precision!r}")
    return x + jax.lax.stop_gradient(q - x)     # straight-through


def linear(x, w, b, precision="f32"):
    if precision != "f32":
        x, w = _round_to(x, precision), _round_to(w, precision)
    return jnp.matmul(x, w, precision=HIGHEST) + b


def layer_norm(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def rotary(x, positions, theta):
    """Rotate-half RoPE on [B, T, H, D] at integer ``positions`` [T]."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * inv          # [T, half]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v):
    """Causal grouped-query attention; q [B,T,Hq,D], k/v [B,T,Hkv,D].
    One sequence's KV head (with its group of query heads) at a time, so
    the score matrix held is [G, T, T] and never the whole layer's."""
    b, t, hq, d = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    qg = q.reshape(b, t, hkv, g, d).transpose(0, 2, 3, 1, 4)
    qg = qg.reshape(b * hkv, g, t, d)
    kg = k.transpose(0, 2, 1, 3).reshape(b * hkv, t, d)
    vg = v.transpose(0, 2, 1, 3).reshape(b * hkv, t, d)
    causal = jnp.tril(jnp.ones((t, t), bool))

    @jax.checkpoint         # the backward pass rebuilds a group's scores
    def one_group(args):
        qh, kh, vh = args                       # [G,T,D], [T,D], [T,D]
        s = jnp.einsum("gqd,kd->gqk", qh, kh, precision=HIGHEST)
        s = jnp.where(causal, s / np.sqrt(d), -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("gqk,kd->gqd", p, vh, precision=HIGHEST)

    o = jax.lax.map(one_group, (qg, kg, vg))    # [B*Hkv, G, T, D]
    o = o.reshape(b, hkv, g, t, d).transpose(0, 3, 1, 2, 4)
    return o.reshape(b, t, hq * d)


def block(x, w, p, cfg, precision):
    b, t, _ = x.shape
    hd, eps = cfg["head_dim"], cfg["norm_epsilon"]
    h = layer_norm(x, w[p + "ln1.g"], w[p + "ln1.b"], eps)
    q = linear(h, w[p + "wq"], w[p + "bq"], precision).reshape(b, t, -1, hd)
    k = linear(h, w[p + "wk"], w[p + "bk"], precision).reshape(b, t, -1, hd)
    v = linear(h, w[p + "wv"], w[p + "bv"], precision).reshape(b, t, -1, hd)
    pos = jnp.arange(t)
    q, k = rotary(q, pos, cfg["rope_theta"]), rotary(k, pos, cfg["rope_theta"])
    x = x + linear(attention(q, k, v), w[p + "wo"], w[p + "bo"], precision)
    h = layer_norm(x, w[p + "ln2.g"], w[p + "ln2.b"], eps)
    h = jax.nn.gelu(linear(h, w[p + "w1"], w[p + "b1"], precision),
                    approximate=True)
    return x + linear(h, w[p + "w2"], w[p + "b2"], precision)


def hidden(w, ids, cfg, precision="f32", remat=False):
    """The final-norm output [B, T, H] of integer ``ids`` [B, T]."""
    x = w["emb.W"][ids] + w["emb.b"]
    for i in range(cfg["num_hidden_layers"]):
        p = f"L{i}."
        layer = {k: a for k, a in w.items() if k.startswith(p)}
        f = partial(block, p=p, cfg=cfg, precision=precision)
        x = (jax.checkpoint(f) if remat else f)(x, layer)
    return layer_norm(x, w["lnf.g"], w["lnf.b"], cfg["norm_epsilon"])


def forward(w, ids, cfg, precision="f32"):
    """Logits [B, T, V] of integer ``ids`` [B, T]."""
    return linear(hidden(w, ids, cfg, precision), w["head.W"], w["head.b"],
                  precision)


@partial(jax.jit, static_argnums=(2, 3))
def logits_of(w, ids, cfg_items, precision="f32"):
    return forward(w, ids, dict(cfg_items), precision)


# ----------------------------------------------------------------- training
def loss_of(w, x_ids, y_ids, cfg, precision="f32", rows=None):
    """Cross-entropy summed over the time axis and averaged over the batch
    (the program's reduction for sequences without a mask).  ``rows``, a
    boolean [B, T], plants the "half of the batch left out" fault: the sum
    runs over those rows only and is rescaled to the full count."""
    h = hidden(w, x_ids, cfg, precision, remat=True)
    b, t, width = h.shape
    n = LOSS_ROWS if (b * t) % LOSS_ROWS == 0 else b * t

    @jax.checkpoint
    def rows_loss(args):        # the logits of LOSS_ROWS rows at a time
        hr, yr = args
        logp = jax.nn.log_softmax(
            linear(hr, w["head.W"], w["head.b"], precision), axis=-1)
        return -jnp.take_along_axis(logp, yr[:, None], axis=-1)[:, 0]

    per = jax.lax.map(rows_loss, (h.reshape(-1, n, width),
                                  y_ids.reshape(-1, n))).reshape(b, t)
    if rows is not None:
        per = per * rows * (rows.size / jnp.sum(rows))
    return jnp.mean(jnp.sum(per, axis=1))


@partial(jax.jit, static_argnums=(6, 7, 8), donate_argnums=(0, 1, 2))
def train_step(w, m, v, step, x_ids, y_ids, cfg_items, lr, precision="f32",
               rows=None):
    """One Adam step (bias-corrected, eps outside the root) in float32.
    ``step`` counts from 0.  Returns the new state, the loss, and the norm
    of every leaf's gradient."""
    cfg = dict(cfg_items)
    loss, g = jax.value_and_grad(loss_of)(w, x_ids, y_ids, cfg, precision,
                                          rows)
    t = step.astype(jnp.float32) + 1.0
    new_w, new_m, new_v, gnorm = {}, {}, {}, {}
    for k in w:
        new_m[k] = BETA1 * m[k] + (1 - BETA1) * g[k]
        new_v[k] = BETA2 * v[k] + (1 - BETA2) * g[k] * g[k]
        mhat = new_m[k] / (1 - BETA1 ** t)
        vhat = new_v[k] / (1 - BETA2 ** t)
        new_w[k] = w[k] - lr * mhat / (jnp.sqrt(vhat) + ADAM_EPS)
        gnorm[k] = jnp.sqrt(jnp.sum(jnp.square(g[k])))
    return new_w, new_m, new_v, loss, gnorm


@jax.jit
def leaf_norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
            for k, a in tree.items()}


@jax.jit
def change_norms(new, old):
    return {k: jnp.sqrt(jnp.sum(jnp.square(new[k] - old[k]))) for k in new}


def cfg_items(cfg: dict):
    """The sizes the reference reads, hashable for ``static_argnums``."""
    keys = ("hidden_size", "vocab_size", "head_dim", "num_attention_heads",
            "num_key_value_heads", "intermediate_size", "num_hidden_layers",
            "norm_epsilon", "rope_theta")
    return tuple((k, cfg[k]) for k in keys)
