"""The plain reference for ``ling-3.0-flash-ep8`` (Ling-3.0-flash, ``model_type
bailing_hybrid``; the linear layers are Kimi Delta Attention, Kimi Linear,
arXiv:2510.26692; the others DeepSeek-V3's latent attention and group-limited
``noaux_tc`` experts, arXiv:2412.19437): straightforward ``jax.numpy``,
float32, every product at ``highest`` precision, the recurrence a ``lax.scan``
a position at a time from zero state (not the chunked form the program
runs), latent attention by its expanded form (not the absorbed paged form
the program decodes by), the router per token with sorts (not the program's
``top_k``), no cache, no batching.  It imports nothing of the program; the
small helpers it shares with the other references (``linear``, ``rms_norm``,
``swiglu``, ``rotary``, the leaf key, the rounding of a ``precision``) come
from ``reference_k2``, the convolution and the leaf draw from
``reference_olmo_hybrid`` and ``reference_jamba``.

Decoder layer ``i`` (pre-norm, no bias anywhere): ``x <- x +
mixer_i(RMSNorm(x))``, ``x <- x + FFN_i(RMSNorm(x))``.  ``mixer_i`` is MLA
where ``(i + 1) % layer_group_size == 0`` (layer 5 of the 8 here), else KDA;
``FFN_i`` a dense SwiGLU of width ``intermediate_size`` 6144 below
``first_k_dense_replace`` (layers 0, 1), else the experts.  After the last
layer a final RMSNorm; logits ``= x W_head`` (untied).  ``rms_norm_eps``
1e-6.

KDA (32 heads, ``d_k = d_v`` = 128, convolution 4), for ``u`` [T, 2560]:
  1. ``q, k, v = silu(conv(u W_{q,k,v}))``, causal depthwise convolution of
     width 4, no bias.
  2. per head ``q <- q / sqrt(|q|^2 + 1e-6) / sqrt(128)``, ``k <- k /
     sqrt(|k|^2 + 1e-6)``; ``beta = sigmoid(u W_b)`` [T, 32]; ``g = -5
     sigmoid(exp(A_log_h) (u W_f + dt_bias))`` [T, 32, 128].
  3. per head, ``S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} +
     beta_t k_t v_t^T`` (``S_{-1}`` = 0); ``o_t = S_t^T q_t``.
  4. ``y_h = RMSNorm_128(o_h) w_h sigmoid(u W_og)_h``; out ``= y W_o``.

MLA (layer 5): ``q = u W_q`` -> 32 heads x (nope 128 | rope 64) (no query
latent); ``[c_kv | k_r] = u W_kva``, ``c_kv = RMSNorm(c_kv)``; rotate-half
RoPE at theta 6e6 on ``k_r`` (one vector shared by the heads) and on the
query's rotary part; ``[k_nope | v] = c_kv W_kvb``; causal
softmax((``q_nope . k_nope + q_rope . k_r``) / sqrt(192)) ``v``; head ``h``
times ``sigmoid(u W_g)_h``; ``W_o``.

Experts, in float32 in every ``precision``: ``s = sigmoid(x W_r)`` over 512
experts; a group of 64 consecutive ids scores the sum of its two largest
``s + b``; the 4 best groups are kept; the 8 largest ``s + b`` among their
experts are chosen; weights are the chosen ``s`` over their sum + 1e-20,
times 2.5.  ``y = sum_i w_i E_i(x) + E_shared(x)``, SwiGLU of width 768.
THE SHARE: the configuration holds ``num_experts`` experts (ids
``first_expert_held`` ..) of the ``published`` 512; what the others would
add is left out.  The vocabulary is the held slice.

Departures from the published model, each also in the configuration file:
the depth (8 of 42 layers), the experts held (64 of 512), the vocabulary
(19,648 of 157,184 rows), the weights (random from the seed), no
multi-token-prediction block.  Leaves: ``A`` uniform in (0, 16] and ``A_log
= log A``; ``dt_bias`` the inverse softplus of a step drawn log-uniform in
[1e-3, 1e-1]; the router's bias N(0, 0.01); the others N(0,
``initializer_range``), norm gains 1 + N(0, range); every leaf rounded to
bfloat16, the stored dtype.

``precision``: ``f32`` | ``bf16`` | ``fp8`` round the operands of the linear
layers (the mixers' projections, the FFNs, the head; not the router); the
convolutions, the norms, the gates and the recurrence stay float32.

The model is never held whole: ``hidden_states`` makes one layer's leaves
(1.8 GB in float32 for an expert layer), pushes every sequence through it,
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
    HIGHEST, ROUTER_BIAS_STD, _DTYPES, leaf_key, linear, logits_of, rms_norm,
    rotary, softmax_scale, swiglu,
)
from benchmark.reference_olmo_hybrid import causal_conv

A_MAX = 16.0
DT_MIN, DT_MAX = 1e-3, 1e-1
L2_EPS = 1e-6
KEYS = ("hidden_size", "intermediate_size", "num_attention_heads",
        "head_dim", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
        "v_head_dim", "rope_theta", "short_conv_kernel_size",
        "kda_lower_bound", "moe_intermediate_size", "num_experts",
        "num_experts_per_tok", "n_group", "topk_group", "norm_topk_prob",
        "routed_scaling_factor", "rms_norm_eps", "rope_scaling")


# ------------------------------------------------------------------ shapes
def is_mla(cfg: dict, i: int) -> bool:
    return (i + 1) % cfg["layer_group_size"] == 0


def is_dense(cfg: dict, i: int) -> bool:
    return i < cfg["first_k_dense_replace"]


def router_width(cfg: dict) -> int:
    """Experts the router scores: the published count."""
    return int(cfg.get("published", {}).get("num_experts",
                                            cfg["num_experts"]))


def kda_widths(cfg: dict):
    """(heads, d_k = d_v, q/k/v width)."""
    h, d = cfg["num_attention_heads"], cfg["head_dim"]
    return h, d, h * d


def layer_shapes(cfg: dict, i: int) -> dict:
    """The leaves of layer ``i`` by name (without the ``L<i>.`` prefix)."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    if is_mla(cfg, i):
        kvr, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
        nope, vd = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
        out = {"in_norm.g": (h,), "wq": (h, heads * (nope + rope)),
               "wkva": (h, kvr + rope), "kv_norm.g": (kvr,),
               "wkvb": (kvr, heads * (nope + vd)), "wg": (h, heads),
               "wo": (heads * vd, h)}
    else:
        _, _, w = kda_widths(cfg)
        kc = cfg["short_conv_kernel_size"]
        out = {"in_norm.g": (h,), "wq": (h, w), "wk": (h, w), "wv": (h, w),
               "wf": (h, w), "wb": (h, heads), "wog": (h, heads),
               "wo": (w, h), "conv_q.W": (w, kc), "conv_k.W": (w, kc),
               "conv_v.W": (w, kc), "A_log": (heads,), "dt_bias": (w,),
               "o_norm.g": (w,)}
    out["post_norm.g"] = (h,)
    if is_dense(cfg, i):
        inter = cfg["intermediate_size"]
        out.update({"w_gate": (h, inter), "w_up": (h, inter),
                    "w_down": (inter, h)})
        return out
    mi, held, n = (cfg["moe_intermediate_size"], cfg["num_experts"],
                   router_width(cfg))
    sh = cfg["moe_shared_expert_intermediate_size"] * cfg["num_shared_experts"]
    out.update({"router.W": (h, n), "router.b": (n,),
                "experts.w_gate": (held, h, mi), "experts.w_up": (held, h, mi),
                "experts.w_down": (held, mi, h),
                "shared.w_gate": (h, sh), "shared.w_up": (h, sh),
                "shared.w_down": (sh, h)})
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
    std = (ROUTER_BIAS_STD if name.endswith("router.b")
           else float(cfg.get("initializer_range", 0.02)))
    return _draw(leaf_key(seed, name), tuple(shape), std, name.endswith(".g"),
                 stored).astype(dtype)


def make_leaves(cfg, seed, prefix, shapes, dtype=jnp.float32) -> dict:
    return {k: make_leaf(cfg, seed, prefix + k, s, dtype)
            for k, s in shapes.items()}


def make_weights(cfg: dict, seed: int) -> dict:
    """Every leaf at once, under its full name (toy sizes only)."""
    return {k: make_leaf(cfg, seed, k, s)
            for k, s in leaf_shapes(cfg).items()}


# ----------------------------------------------------------------- forward
def _l2(x):
    return x / jnp.sqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + L2_EPS)


def kda_rule(q, k, v, g, beta, s0=None):
    """Step 3, one position a trip: ``q``, ``k``, ``v``, ``g`` [T, H, d];
    ``beta`` [T, H].  Returns ``(o [T, H, d], S_T [H, d_k, d_v])``."""
    h, dk, dv = q.shape[1], q.shape[2], v.shape[2]
    s0 = jnp.zeros((h, dk, dv), jnp.float32) if s0 is None else s0

    def step(s, inp):
        q_t, k_t, v_t, g_t, b_t = inp
        d = jnp.exp(g_t)[:, :, None] * s                  # Diag(exp(g)) S
        kd = jnp.einsum("hk,hkv->hv", k_t, d, precision=HIGHEST)
        s = (d - b_t[:, None, None] * k_t[:, :, None] * kd[:, None, :]
             + b_t[:, None, None] * k_t[:, :, None] * v_t[:, None, :])
        return s, jnp.einsum("hk,hkv->hv", q_t, s, precision=HIGHEST)

    s, o = jax.lax.scan(step, s0, (q, k, v, g, beta))
    return o, s


def kda(u, w, cfg, precision):
    """The KDA mixer on one sequence ``u`` [T, h] from zero state."""
    t = u.shape[0]
    heads, d, _ = kda_widths(cfg)
    q = jax.nn.silu(causal_conv(linear(u, w["wq"], precision), w["conv_q.W"]))
    k = jax.nn.silu(causal_conv(linear(u, w["wk"], precision), w["conv_k.W"]))
    v = jax.nn.silu(causal_conv(linear(u, w["wv"], precision), w["conv_v.W"]))
    q = _l2(q.reshape(t, heads, d)) / math.sqrt(d)
    k = _l2(k.reshape(t, heads, d))
    beta = jax.nn.sigmoid(linear(u, w["wb"], precision))
    f = (linear(u, w["wf"], precision) + w["dt_bias"]).reshape(t, heads, d)
    g = cfg["kda_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(w["A_log"])[:, None] * f)
    o, _ = kda_rule(q, k, v.reshape(t, heads, d), g, beta)
    y = rms_norm(o, w["o_norm.g"].reshape(heads, d), cfg["rms_norm_eps"])
    y = y * jax.nn.sigmoid(linear(u, w["wog"], precision))[:, :, None]
    return linear(y.reshape(t, -1), w["wo"], precision)


def mla(u, w, cfg, precision):
    """Latent attention of one sequence ``u`` [T, h], expanded, with no query
    latent and the head-wise gate."""
    t = u.shape[0]
    heads, kvr = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope = cfg["qk_nope_head_dim"]
    q = linear(u, w["wq"], precision).reshape(t, heads, -1)
    kv = linear(u, w["wkva"], precision)
    c_kv = rms_norm(kv[:, :kvr], w["kv_norm.g"], cfg["rms_norm_eps"])
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
    o = o.transpose(1, 0, 2) * jax.nn.sigmoid(
        linear(u, w["wg"], precision))[:, :, None]
    return linear(o.reshape(t, -1), w["wo"], precision)


def route(x, w, cfg):
    """(ids [T, k], weights [T, k]) over the router's experts, float32,
    group-limited: per token, sorts and masks with no ``top_k``."""
    s = jax.nn.sigmoid(jnp.matmul(x, w["router.W"], precision=HIGHEST))
    choice = s + w["router.b"]
    t, n = choice.shape
    groups = choice.reshape(t, cfg["n_group"], n // cfg["n_group"])
    score = jnp.sum(jnp.sort(groups, axis=-1)[..., -2:], axis=-1)  # [T, G]
    rank = jnp.argsort(jnp.argsort(-score, axis=-1), axis=-1)      # 0: best
    kept = (rank < cfg["topk_group"])[:, :, None]
    masked = jnp.where(kept, groups, -jnp.inf).reshape(t, n)
    ids = jnp.argsort(-masked, axis=-1)[:, :cfg["num_experts_per_tok"]]
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
        jnp.arange(cfg["num_experts"]), w["experts.w_gate"],
        w["experts.w_up"], w["experts.w_down"]))
    return y + swiglu(x, w["shared.w_gate"], w["shared.w_up"],
                      w["shared.w_down"], precision)


def block(x, w, cfg, precision):
    """One layer on one sequence x [T, h]: KDA or MLA, dense or experts, by
    its leaves."""
    eps = cfg["rms_norm_eps"]
    mixer = kda if "A_log" in w else mla
    x = x + mixer(rms_norm(x, w["in_norm.g"], eps), w, cfg, precision)
    hdn = rms_norm(x, w["post_norm.g"], eps)
    if "router.W" in w:
        return x + moe(hdn, w, cfg, precision)
    return x + swiglu(hdn, w["w_gate"], w["w_up"], w["w_down"], precision)


def freeze(cfg: dict):
    """The sizes the reference reads, hashable for ``static_argnums``."""
    return tuple((k, cfg[k]) for k in KEYS) + (
        ("first_expert_held", cfg.get("first_expert_held", 0)),)


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
