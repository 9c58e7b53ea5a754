"""The plain reference for ``kimi-k2.5-ep32``: the DeepSeek-V3 block that
Kimi-K2 carries (DeepSeek-V2, arXiv:2405.04434; DeepSeek-V3,
arXiv:2412.19437; HF ``modeling_deepseek.py`` of the published checkpoint),
in straightforward ``jax.numpy``, float32, every matrix product at
``highest`` precision, no cache, no kernels, no batching.

It imports nothing of the program and takes nothing the program made: each
leaf is drawn alone from ``(seed, leaf name)`` by ``make_leaves`` below,
which is also what the harness installs into the program.

Block: ``x += MLA(RMSNorm(x))``, ``x += FFN(RMSNorm(x))``, no biases.
Layers below ``first_k_dense_replace`` have a dense SwiGLU of width
``intermediate_size``; the others are mixtures of experts.

MLA, always by the EXPANDED path over the whole sequence (the absorbed
path is the program's; their agreement is what is tested):
  c_q = RMSNorm(x W_qa);  q = c_q W_qb -> heads x (nope | rope)
  [c_kv | k_r] = x W_kva;  c_kv = RMSNorm(c_kv);  k_r = RoPE(k_r), one
  vector shared by all heads;  q_rope = RoPE(q_rope)
  [k_nope | v] = c_kv W_kvb -> heads x (nope | v)
  scores (q_nope . k_nope + q_rope . k_r) * s,  s = (nope + rope)^-0.5 * m^2,
  m = 0.1 * mscale_all_dim * ln(factor) + 1;  causal softmax;  o = P v;  o W_o
RoPE is YaRN: frequencies blended between theta^(-2i/d) and the same over
``factor`` by a linear ramp between the pairs that turn ``beta_fast`` and
``beta_slow`` times in ``original_max_position_embeddings``; cos and sin
times mscale/mscale_all_dim's ratio (1 as published).

MoE, ``noaux_tc`` in one group, in float32 in every ``precision``:
  s = sigmoid(x W_g) over all experts;  the ``num_experts_per_tok`` largest
  of s + b are chosen;  weights are the chosen s (without b) over their sum
  + 1e-20, times ``routed_scaling_factor``
  y = sum_i w_i E_i(x) + E_shared(x),  E(x) = W_d (silu(W_g' x) * W_u x)
No capacity, no dropped token.  THE SHARE: the configuration holds
``n_routed_experts`` experts (ids ``first_expert_held`` ..) of the
``published`` count; the router keeps the published width; what the absent
experts would add is left out, and that partial result goes on.  The
vocabulary is the held slice: embedding and head have ``vocab_size`` rows.

Departures from the published model (each also in the configuration file
under ``assumed``): rotate-half pairing on the rotary columns (the
checkpoint's interleaved layout is a permutation of random weights);
weights N(0, ``initializer_range``), norm gains 1 + N(0, range), the
router's selection bias N(0, 0.01), every leaf rounded to the stored dtype
``torch_dtype`` states; the embedding adds a bias vector and the head
carries one (both zero: the program's DSL layers have them); no vision
tower.

``precision`` as in ``reference.py``: ``f32`` | ``bf16`` | ``fp8`` round the
operands of the linear layers (not of the router).

The model is never held whole: ``hidden_states`` makes one layer's leaves
(2.7 GB in float32 at the published widths), pushes every sequence through
it, and frees them.  ``forward`` over a dict of all leaves is for toy sizes.
"""

from __future__ import annotations

import math
import zlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
ROUTER_BIAS_STD = 0.01
_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


# ------------------------------------------------------------------ shapes
def router_width(cfg: dict) -> int:
    """Experts the router scores: the published count where the
    configuration holds a share, else all it has."""
    return int(cfg.get("published", {}).get("n_routed_experts",
                                            cfg["n_routed_experts"]))


def is_dense(cfg: dict, i: int) -> bool:
    return i < cfg["first_k_dense_replace"]


def layer_shapes(cfg: dict, i: int) -> dict:
    """The leaves of layer ``i`` by name (without the ``L<i>.`` prefix)."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    qr, kvr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    out = {"in_norm.g": (h,), "wqa": (h, qr), "q_norm.g": (qr,),
           "wqb": (qr, heads * (nope + rope)), "wkva": (h, kvr + rope),
           "kv_norm.g": (kvr,), "wkvb": (kvr, heads * (nope + vd)),
           "wo": (heads * vd, h), "post_norm.g": (h,)}
    if is_dense(cfg, i):
        inter = cfg["intermediate_size"]
        out.update({"w_gate": (h, inter), "w_up": (h, inter),
                    "w_down": (inter, h)})
        return out
    mi, held, n = (cfg["moe_intermediate_size"], cfg["n_routed_experts"],
                   router_width(cfg))
    sh = mi * cfg["n_shared_experts"]
    out.update({"router.W": (h, n), "router.b": (n,),
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
    stored = _DTYPES[cfg["torch_dtype"]]
    if name in ("emb.b", "head.b"):
        return jnp.zeros(shape, dtype)
    std = (ROUTER_BIAS_STD if name.endswith("router.b")
           else float(cfg.get("initializer_range", 0.02)))
    w = _draw(leaf_key(seed, name), tuple(shape), std, name.endswith(".g"),
              stored)
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


def yarn_mscale(factor, mscale):
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def rotary_frequencies(cfg: dict) -> np.ndarray:
    """[rope/2] angular frequencies: plain RoPE, or YaRN's blend."""
    d, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    plain = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    sc = cfg.get("rope_scaling")
    if not sc:
        return plain
    factor, orig = sc["factor"], sc["original_max_position_embeddings"]

    def pair_turning(rotations):
        return (d * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(pair_turning(sc["beta_fast"])), 0)
    high = min(math.ceil(pair_turning(sc["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    return plain / factor * ramp + plain * (1.0 - ramp)   # 1: interpolated


def rotary(x, cfg):
    """Rotate-half RoPE on [T, H, D] at positions 0..T-1."""
    half = x.shape[-1] // 2
    ang = (jnp.arange(x.shape[0], dtype=jnp.float32)[:, None]
           * jnp.asarray(rotary_frequencies(cfg), jnp.float32))
    sc = cfg.get("rope_scaling")
    ratio = (yarn_mscale(sc["factor"], sc["mscale"])
             / yarn_mscale(sc["factor"], sc["mscale_all_dim"])) if sc else 1.0
    cos, sin = jnp.cos(ang)[:, None] * ratio, jnp.sin(ang)[:, None] * ratio
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def softmax_scale(cfg: dict) -> float:
    s = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    sc = cfg.get("rope_scaling")
    if sc:
        s *= yarn_mscale(sc["factor"], sc["mscale_all_dim"]) ** 2
    return s


def mla(x, w, cfg, precision):
    """Latent attention of one sequence x [T, h], expanded."""
    t = x.shape[0]
    heads, kvr = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, eps = cfg["qk_nope_head_dim"], cfg["rms_norm_eps"]
    cq = rms_norm(linear(x, w["wqa"], precision), w["q_norm.g"], eps)
    q = linear(cq, w["wqb"], precision).reshape(t, heads, -1)
    kv = linear(x, w["wkva"], precision)
    c_kv = rms_norm(kv[:, :kvr], w["kv_norm.g"], eps)
    k_r = rotary(kv[:, None, kvr:], cfg)[:, 0]                # [T, rope]
    q_nope, q_rope = q[..., :nope], rotary(q[..., nope:], cfg)
    kvb = linear(c_kv, w["wkvb"], precision).reshape(t, heads, -1)
    k_nope, v = kvb[..., :nope], kvb[..., nope:]
    causal = jnp.tril(jnp.ones((t, t), bool))
    scale = softmax_scale(cfg)

    def one_head(args):            # the scores held are one head's [T, T]
        qn, qr, kn, vh = args
        s = (jnp.matmul(qn, kn.T, precision=HIGHEST)
             + jnp.matmul(qr, k_r.T, precision=HIGHEST)) * scale
        p = jax.nn.softmax(jnp.where(causal, s, -1e30), axis=-1)
        return jnp.matmul(p, vh, precision=HIGHEST)

    o = jax.lax.map(one_head, tuple(
        a.transpose(1, 0, 2) for a in (q_nope, q_rope, k_nope, v)))
    return linear(o.transpose(1, 0, 2).reshape(t, -1), w["wo"], precision)


def swiglu(x, w_gate, w_up, w_down, precision):
    return linear(jax.nn.silu(linear(x, w_gate, precision))
                  * linear(x, w_up, precision), w_down, precision)


def route(x, w, cfg):
    """(ids [T, k], weights [T, k]) over all the router's experts, float32."""
    s = jax.nn.sigmoid(jnp.matmul(x, w["router.W"], precision=HIGHEST))
    _, ids = jax.lax.top_k(s + w["router.b"], cfg["num_experts_per_tok"])
    chosen = jnp.take_along_axis(s, ids, axis=1)
    if cfg["norm_topk_prob"]:
        chosen = chosen / (jnp.sum(chosen, axis=1, keepdims=True) + 1e-20)
    return ids, chosen * cfg["routed_scaling_factor"]


def moe(x, w, cfg, precision):
    """The held experts' part and the shared expert, x [T, h]."""
    ids, weights = route(x, w, cfg)
    first = cfg.get("first_expert_held", 0)

    def one_expert(y, args):      # every token through it, weight 0 if not its
        e, wg, wu, wd = args
        mine = jnp.sum(jnp.where(ids == first + e, weights, 0.0), axis=1)
        return y + mine[:, None] * swiglu(x, wg, wu, wd, precision), None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(x), (
        jnp.arange(cfg["n_routed_experts"]), w["experts.w_gate"],
        w["experts.w_up"], w["experts.w_down"]))
    return y + swiglu(x, w["shared.w_gate"], w["shared.w_up"],
                      w["shared.w_down"], precision)


def block(x, w, cfg, precision):
    """One layer on one sequence x [T, h]; dense or MoE by its leaves."""
    eps = cfg["rms_norm_eps"]
    x = x + mla(rms_norm(x, w["in_norm.g"], eps), w, cfg, precision)
    hdn = rms_norm(x, w["post_norm.g"], eps)
    if "router.W" in w:
        return x + moe(hdn, w, cfg, precision)
    return x + swiglu(hdn, w["w_gate"], w["w_up"], w["w_down"], precision)


def freeze(cfg: dict):
    """The sizes the reference reads, hashable for ``static_argnums``."""
    keys = ("hidden_size", "num_attention_heads", "q_lora_rank",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "rms_norm_eps", "rope_theta", "n_routed_experts",
            "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor")
    sc = cfg.get("rope_scaling")
    return (tuple((k, cfg[k]) for k in keys)
            + (("first_expert_held", cfg.get("first_expert_held", 0)),
               ("rope_scaling", tuple(sorted(sc.items())) if sc else None)))


def _thaw(items) -> dict:
    cfg = dict(items)
    if cfg["rope_scaling"]:
        cfg["rope_scaling"] = dict(cfg["rope_scaling"])
    return cfg


@partial(jax.jit, static_argnums=(2, 3))
def block_of(x, w, cfg_items, precision="f32"):
    return block(x, w, _thaw(cfg_items), precision)


@partial(jax.jit, static_argnums=(3,))
def logits_of(h, head_w, head_b, precision="f32"):
    return linear(h, head_w, precision) + head_b


def forward(w: dict, ids, cfg: dict, precision="f32"):
    """Logits [T, V] of one sequence ``ids`` [T], all leaves given."""
    x = w["emb.W"][ids] + w["emb.b"]
    items = freeze(cfg)
    for i in range(cfg["num_hidden_layers"]):
        p = f"L{i}."
        x = block_of(x, {k[len(p):]: a for k, a in w.items()
                         if k.startswith(p)}, items, precision)
    x = rms_norm(x, w["norm.g"], cfg["rms_norm_eps"])
    return logits_of(x, w["head.W"], w["head.b"], precision)


def hidden_states(cfg: dict, seed: int, seqs, precisions=("f32",)) -> dict:
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
            xs[p] = [block_of(x, w, items, p) for x in xs[p]]
        jax.block_until_ready(xs)
        del w
    g = make_leaf(cfg, seed, "norm.g", (cfg["hidden_size"],))
    return {p: [rms_norm(x, g, cfg["rms_norm_eps"]) for x in xs[p]]
            for p in precisions}


def head_leaves(cfg: dict, seed: int):
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    return (make_leaf(cfg, seed, "head.W", (h, v)),
            make_leaf(cfg, seed, "head.b", (v,)))
