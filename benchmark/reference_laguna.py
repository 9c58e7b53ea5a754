"""The plain reference for ``laguna-s-2.1-ep8``: poolside's Laguna-S-2.1
language model (``model_type laguna``; the equations below are read from its
published ``config.json``, key by key), in straightforward ``jax.numpy``,
float32, every matrix product at ``highest`` precision, no cache, no
kernels, no batching, the window as a mask.

It imports nothing of the program and takes nothing the program made: each
leaf is drawn alone from ``(seed, leaf name)`` by ``make_leaf`` below, which
is also what the harness installs into the program.

Block, pre-norm, no bias anywhere (``attention_bias`` false), eps 1e-6:
``h = x + Attn_l(RMSNorm(x))``, ``y = h + FFN_l(RMSNorm(h))``; final
RMSNorm; untied head.

Attention of layer ``l``, ``u`` its normed input, ``D = head_dim``:
  ``H_l = num_attention_heads_per_layer[l]`` query heads (48 on full layers,
  72 on sliding ones), ``num_key_value_heads`` kv heads of ``D`` columns, so
  ``H_l * D`` is not the hidden size: ``q = u W_q`` (h -> H_l D), ``k = u
  W_k``, ``v = u W_v`` (h -> Hkv D), groups of ``H_l / Hkv`` query heads a
  kv head.
  ``layer_types[l] == "sliding_attention"``: plain RoPE (theta 10,000) on
  all ``D`` columns (``partial_rotary_factor`` 1); query ``i`` sees keys
  ``i - sliding_window < j <= i``.
  ``"full_attention"``: RoPE on the first ``D * partial_rotary_factor``
  columns (64 of 128), YaRN: each pair's frequency blended between
  ``theta^(-2i/d)`` and the same over ``factor`` by a linear ramp between
  the pairs that turn ``beta_fast`` and ``beta_slow`` times in
  ``original_max_position_embeddings``; cos and sin times
  ``attention_factor``; causal over everything.
  Scores ``q.k / sqrt(D)``, softmax in float32.
  Gate (``gating: per-head``): ``g = sigmoid(u W_g)`` (h -> H_l), head
  ``h``'s output times ``g_h`` ahead of ``W_o`` (H_l D -> h).

FFN: layers in ``mlp_only_layers`` have a SwiGLU of width
``intermediate_size``; the others a mixture, in float32 in every
``precision``: ``p = softmax(u W_r)`` over all the router's experts, the
``num_experts_per_tok`` largest chosen, ``w_i = p_i / sum of the chosen p``
(``norm_topk_prob``), no soft cap, the weight on the output,
``y = moe_routed_scaling_factor * sum_i w_i E_i(u) + E_shared(u)``,
``E(u) = W_d (silu(W_g u) * W_u u)``.  No capacity, no dropped token.

THE SHARE: the configuration holds ``num_experts`` experts (ids
``first_expert_held`` ..) of the ``published`` count; the router keeps the
published width; what the absent experts would add is left out, and that
partial result goes on.  The vocabulary is the held slice: embedding and
head have ``vocab_size`` rows.

Departures from the published model and readings of its keys (each also in
the configuration file under ``assumed``): the router scores by softmax (the
Qwen-MoE key family; no ``scoring_func`` key, no selection bias); the shared
expert is added ungated; ``gating: per-head`` is the head-wise sigmoid
output gate from the layer's normed input; no q/k norm (no key names one);
rotate-half pairing inside the rotated columns; ``attention_factor`` on the
cos and sin of the rotated columns only; weights N(0,
``initializer_range``), norm gains 1 + N(0, range), every leaf rounded to
the stored dtype ``torch_dtype`` states; the embedding adds a bias vector
and the head carries one (both zero: the program's DSL layers have them).

``precision``: ``f32`` | ``bf16`` | ``fp8`` round the operands of the linear
layers (not of the router), as ``reference_k2.py`` has them.

The model is never held whole: ``hidden_states`` makes one layer's leaves
(1.5 GB in float32 at the published widths), pushes every sequence through
it, and frees them; attention takes its queries in blocks of ``Q_BLOCK``
(72 heads x 8,704 x 8,704 float32 scores are 21.8 GB whole).  ``forward``
over a dict of all leaves is for toy sizes.
"""

from __future__ import annotations

import math
import zlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 256
_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


# ------------------------------------------------------------------ shapes
def router_width(cfg: dict) -> int:
    """Experts the router scores: the published count where the
    configuration holds a share, else all it has."""
    return int(cfg.get("published", {}).get("num_experts",
                                            cfg["num_experts"]))


def is_dense(cfg: dict, i: int) -> bool:
    return i in cfg["mlp_only_layers"]


def is_sliding(cfg: dict, i: int) -> bool:
    return cfg["layer_types"][i] == "sliding_attention"


def heads_of(cfg: dict, i: int) -> int:
    return int(cfg["num_attention_heads_per_layer"][i])


def layer_shapes(cfg: dict, i: int) -> dict:
    """The leaves of layer ``i`` by name (without the ``L<i>.`` prefix)."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    heads, kv = heads_of(cfg, i), cfg["num_key_value_heads"]
    out = {"in_norm.g": (h,), "wq": (h, heads * d), "wk": (h, kv * d),
           "wv": (h, kv * d), "wg": (h, heads), "wo": (heads * d, h),
           "post_norm.g": (h,)}
    if is_dense(cfg, i):
        inter = cfg["intermediate_size"]
        out.update({"w_gate": (h, inter), "w_up": (h, inter),
                    "w_down": (inter, h)})
        return out
    mi, held, n = (cfg["moe_intermediate_size"], cfg["num_experts"],
                   router_width(cfg))
    sh = cfg["shared_expert_intermediate_size"]
    out.update({"router.W": (h, n),
                "experts.w_gate": (held, h, mi), "experts.w_up": (held, h, mi),
                "experts.w_down": (held, mi, h),
                "shared.w_gate": (h, sh), "shared.w_up": (h, sh),
                "shared.w_down": (sh, h)})
    return out


def leaf_shapes(cfg: dict) -> dict:
    """Every parameter of the cut model, by name, in a fixed order."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    out = {"emb.W": (v, h), "emb.b": (h,)}
    for i in range(cfg["num_hidden_layers"]):
        out.update({f"L{i}.{k}": s for k, s in layer_shapes(cfg, i).items()})
    out.update({"norm.g": (h,), "head.W": (h, v), "head.b": (v,)})
    return out


# ----------------------------------------------------------------- weights
def leaf_key(seed: int, name: str) -> jax.Array:
    """A raw threefry key from a seed of up to 64 bits and the leaf's name."""
    seed, tag = int(seed), zlib.crc32(name.encode())
    return jnp.asarray(np.array(
        [((seed >> 32) ^ tag) & 0xFFFFFFFF, seed & 0xFFFFFFFF], np.uint32))


@partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _draw(key, shape, std, gain, stored):
    w = std * jax.random.normal(key, shape, jnp.float32)
    return ((1.0 + w) if gain else w).astype(stored)


def make_leaf(cfg: dict, seed: int, name: str, shape, dtype=jnp.float32):
    """One leaf from ``(seed, name)``, rounded to the stored dtype the
    configuration states, in ``dtype`` (float32 for the reference; the
    harness asks for the stored dtype itself to install it)."""
    if name in ("emb.b", "head.b"):
        return jnp.zeros(shape, dtype)
    w = _draw(leaf_key(seed, name), tuple(shape),
              float(cfg.get("initializer_range", 0.02)), name.endswith(".g"),
              _DTYPES[cfg["torch_dtype"]])
    return w.astype(dtype)


def make_leaves(cfg: dict, seed: int, prefix: str, shapes: dict,
                dtype=jnp.float32) -> dict:
    """``shapes``' leaves under their short names; ``prefix`` (``"L3."``)
    completes the name each is drawn from."""
    return {k: make_leaf(cfg, seed, prefix + k, s, dtype)
            for k, s in shapes.items()}


def make_weights(cfg: dict, seed: int) -> dict:
    """Every leaf at once, under its full name (toy sizes only)."""
    return {k: make_leaf(cfg, seed, k, s)
            for k, s in leaf_shapes(cfg).items()}


# ----------------------------------------------------------------- forward
def _round_to(x, precision):
    if precision == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if precision == "fp8":
        top = float(jnp.finfo(jnp.float8_e4m3fn).max)
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
        q = (x / scale).astype(jnp.float8_e4m3fn)
        return q.astype(jnp.float32) * scale
    raise ValueError(f"unknown precision {precision!r}")


def linear(x, w, precision="f32"):
    if precision != "f32":
        x, w = _round_to(x, precision), _round_to(w, precision)
    return jnp.matmul(x, w, precision=HIGHEST)


def rms_norm(x, g, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g


def rotary_frequencies(rp: dict, dim: int) -> np.ndarray:
    """[dim / 2] angular frequencies of ``dim`` rotated columns under one
    entry of ``rope_parameters``: plain RoPE, or YaRN's blend."""
    theta = float(rp["rope_theta"])
    plain = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rp["rope_type"] == "default":
        return plain
    if rp["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rp['rope_type']!r}")
    factor, orig = rp["factor"], rp["original_max_position_embeddings"]

    def pair_turning(rotations):
        return (dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(pair_turning(rp["beta_fast"])), 0)
    high = min(math.ceil(pair_turning(rp["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return plain / factor * ramp + plain * (1.0 - ramp)   # 1: interpolated


def rotary(x, rp: dict):
    """Rotate-half RoPE on the first ``D * partial_rotary_factor`` columns
    of x [T, H, D] at positions 0..T-1; the rest pass."""
    dim = int(x.shape[-1] * rp["partial_rotary_factor"])
    half = dim // 2
    ang = (jnp.arange(x.shape[0], dtype=jnp.float32)[:, None]
           * jnp.asarray(rotary_frequencies(rp, dim), jnp.float32))
    scale = float(rp.get("attention_factor", 1.0))
    cos, sin = jnp.cos(ang)[:, None] * scale, jnp.sin(ang)[:, None] * scale
    x1, x2 = x[..., :half], x[..., half:dim]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., dim:]], -1)


def attention(u, w, cfg, i, precision, q_block=Q_BLOCK):
    """Layer ``i``'s attention of one sequence u [T, h]."""
    t = u.shape[0]
    d, kv, heads = cfg["head_dim"], cfg["num_key_value_heads"], heads_of(cfg, i)
    sliding = is_sliding(cfg, i)
    rp = cfg["rope_parameters"]["sliding_attention" if sliding
                                else "full_attention"]
    q = rotary(linear(u, w["wq"], precision).reshape(t, heads, d), rp)
    k = rotary(linear(u, w["wk"], precision).reshape(t, kv, d), rp)
    v = linear(u, w["wv"], precision).reshape(t, kv, d)
    qg = q.reshape(t, kv, heads // kv, d)
    kpos = jnp.arange(t)

    def one_block(args):           # the scores held: [kv, group, block, T]
        qb, qpos = args
        s = jnp.einsum("qhgd,khd->hgqk", qb, k,
                       precision=HIGHEST) / math.sqrt(d)
        seen = kpos[None, :] <= qpos[:, None]
        if sliding:
            seen &= kpos[None, :] > qpos[:, None] - cfg["sliding_window"]
        p = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", p, v, precision=HIGHEST)

    if q_block and t % q_block == 0 and t > q_block:
        o = jax.lax.map(one_block, (
            qg.reshape(t // q_block, q_block, kv, heads // kv, d),
            kpos.reshape(t // q_block, q_block))).reshape(t, heads, d)
    else:
        o = one_block((qg, kpos)).reshape(t, heads, d)
    gate = jax.nn.sigmoid(linear(u, w["wg"], precision))       # [T, heads]
    return linear((o * gate[:, :, None]).reshape(t, heads * d), w["wo"],
                  precision)


def swiglu(x, w_gate, w_up, w_down, precision):
    return linear(jax.nn.silu(linear(x, w_gate, precision))
                  * linear(x, w_up, precision), w_down, precision)


def route(x, w, cfg):
    """(ids [T, k], weights [T, k]) over all the router's experts, float32."""
    p = jax.nn.softmax(jnp.matmul(x, w["router.W"], precision=HIGHEST),
                       axis=-1)
    chosen, ids = jax.lax.top_k(p, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        chosen = chosen / (jnp.sum(chosen, axis=1, keepdims=True) + 1e-20)
    return ids, chosen * cfg["moe_routed_scaling_factor"]


def moe(x, w, cfg, precision, shared=True):
    """The held experts' part and (``shared``) the shared expert, x [T, h]."""
    ids, weights = route(x, w, cfg)
    first = cfg.get("first_expert_held", 0)

    def one_expert(y, args):      # every token through it, weight 0 if not its
        e, wg, wu, wd = args
        mine = jnp.sum(jnp.where(ids == first + e, weights, 0.0), axis=1)
        return y + mine[:, None] * swiglu(x, wg, wu, wd, precision), None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(x), (
        jnp.arange(cfg["num_experts"]), w["experts.w_gate"],
        w["experts.w_up"], w["experts.w_down"]))
    if not shared:
        return y
    return y + swiglu(x, w["shared.w_gate"], w["shared.w_up"],
                      w["shared.w_down"], precision)


def block(x, w, cfg, i, precision, q_block=Q_BLOCK):
    """Layer ``i`` on one sequence x [T, h]."""
    eps = cfg["rms_norm_eps"]
    x = x + attention(rms_norm(x, w["in_norm.g"], eps), w, cfg, i, precision,
                      q_block)
    hdn = rms_norm(x, w["post_norm.g"], eps)
    if is_dense(cfg, i):
        return x + swiglu(hdn, w["w_gate"], w["w_up"], w["w_down"], precision)
    return x + moe(hdn, w, cfg, precision)


def freeze(cfg: dict):
    """The sizes the reference reads, hashable for ``static_argnums``."""
    keys = ("hidden_size", "head_dim", "num_key_value_heads", "rms_norm_eps",
            "sliding_window", "num_experts", "num_experts_per_tok",
            "norm_topk_prob", "moe_routed_scaling_factor")
    n = cfg["num_hidden_layers"]
    return (tuple((k, cfg[k]) for k in keys)
            + (("first_expert_held", cfg.get("first_expert_held", 0)),
               ("mlp_only_layers", tuple(cfg["mlp_only_layers"])),
               ("layer_types", tuple(cfg["layer_types"][:n])),
               ("num_attention_heads_per_layer",
                tuple(cfg["num_attention_heads_per_layer"][:n])),
               ("rope_parameters", tuple(
                   (kind, tuple(sorted(rp.items())))
                   for kind, rp in sorted(cfg["rope_parameters"].items())))))


def _thaw(items) -> dict:
    cfg = dict(items)
    cfg["rope_parameters"] = {kind: dict(rp)
                              for kind, rp in cfg["rope_parameters"]}
    return cfg


@partial(jax.jit, static_argnums=(2, 3, 4, 5))
def block_of(x, w, cfg_items, i, precision="f32", q_block=Q_BLOCK):
    return block(x, w, _thaw(cfg_items), i, precision, q_block)


@partial(jax.jit, static_argnums=(3,))
def logits_of(h, head_w, head_b, precision="f32"):
    return linear(h, head_w, precision) + head_b


def forward(w: dict, ids, cfg: dict, precision="f32", q_block=Q_BLOCK):
    """Logits [T, V] of one sequence ``ids`` [T], all leaves given."""
    x = w["emb.W"][ids] + w["emb.b"]
    items = freeze(cfg)
    for i in range(cfg["num_hidden_layers"]):
        p = f"L{i}."
        x = block_of(x, {k[len(p):]: a for k, a in w.items()
                         if k.startswith(p)}, items, i, precision, q_block)
    x = rms_norm(x, w["norm.g"], cfg["rms_norm_eps"])
    return logits_of(x, w["head.W"], w["head.b"], precision)


def hidden_states(cfg: dict, seed: int, seqs, precisions=("f32",),
                  q_block=Q_BLOCK) -> dict:
    """``{precision: [final-normed hidden [T, h] of each sequence]}``, one
    layer's leaves alive at a time."""
    emb = make_leaves(cfg, seed, "emb.", {"W": (cfg["vocab_size"],
                                                cfg["hidden_size"]),
                                          "b": (cfg["hidden_size"],)})
    xs = {p: [emb["W"][jnp.asarray(s)] + emb["b"] for s in seqs]
          for p in precisions}
    del emb
    items = freeze(cfg)
    for i in range(cfg["num_hidden_layers"]):
        w = make_leaves(cfg, seed, f"L{i}.", layer_shapes(cfg, i))
        for p in precisions:
            xs[p] = [block_of(x, w, items, i, p, q_block) for x in xs[p]]
        jax.block_until_ready(xs)
        del w
    g = make_leaf(cfg, seed, "norm.g", (cfg["hidden_size"],))
    return {p: [rms_norm(x, g, cfg["rms_norm_eps"]) for x in xs[p]]
            for p in precisions}


def head_leaves(cfg: dict, seed: int):
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    return (make_leaf(cfg, seed, "head.W", (h, v)),
            make_leaf(cfg, seed, "head.b", (v,)))
