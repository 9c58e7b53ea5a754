"""``laguna-s-2.1-ep8``'s configuration file -> the program's
``MultiLayerNetwork``, through the config DSL, with the benchmark's own
weights (``reference_laguna.make_leaf``) installed in place of
``net.init()``'s — the numbers flow from the benchmark into the program,
never back.

The block (poolside's Laguna): ``RMSNorm -> SelfAttentionLayer`` — no bias,
a head width of its own, the per-head output gate, by ``layer_types`` either
a sliding layer (72 heads, window 512, plain RoPE) or a full one (48 heads,
YaRN on half the columns) — and ``RMSNorm -> GatedMLP`` (layers in
``mlp_only_layers``) or ``RMSNorm -> RoutedMoELayer`` with softmax scoring
(the others), each pair in a ``ResidualBlock``; final ``RMSNorm``; linear
head.  The expert layer is told its share: ``experts_held =
(first_expert_held, num_experts)`` of the ``published`` count.  The per-layer
lists of the file are as published (48 entries); the first
``num_hidden_layers`` are read.  Leaves are installed in the stored dtype the
configuration states (bfloat16), which is also the compute dtype, so the
serving snapshot is the net's own buffers.
"""

from __future__ import annotations

import math

from benchmark import reference_laguna as ref

_STORED = {"bfloat16": "bfloat16", "float32": None}

# the program's parameter names, by the reference's
_ATTN = {"Wq": "wq", "Wk": "wk", "Wv": "wv", "Wg": "wg", "Wo": "wo"}
_DENSE = {"W_gate": "w_gate", "W_up": "w_up", "W_down": "w_down"}
_MOE = {"W_router": "router.W",
        "W_gate": "experts.w_gate", "W_up": "experts.w_up",
        "W_down": "experts.w_down", "Ws_gate": "shared.w_gate",
        "Ws_up": "shared.w_up", "Ws_down": "shared.w_down"}


def attention_layer(cfg: dict, i: int):
    from deeplearning4j_tpu.nn.layers import SelfAttentionLayer

    h, d = cfg["hidden_size"], cfg["head_dim"]
    sliding = ref.is_sliding(cfg, i)
    rp = cfg["rope_parameters"]["sliding_attention" if sliding
                                else "full_attention"]
    kw = {}
    if rp["rope_type"] == "yarn":
        # the layer multiplies cos and sin by 0.1 ln(factor) + 1, which is
        # what the file's attention_factor states
        if abs(0.1 * math.log(rp["factor"]) + 1.0
               - rp["attention_factor"]) > 1e-9:
            raise ValueError("attention_factor is not 0.1 ln(factor) + 1")
        kw = dict(rope_factor=float(rp["factor"]),
                  rope_original_max=int(
                      rp["original_max_position_embeddings"]),
                  rope_beta_fast=float(rp["beta_fast"]),
                  rope_beta_slow=float(rp["beta_slow"]))
    rotary_dim = int(d * rp["partial_rotary_factor"])
    return SelfAttentionLayer(
        n_in=h, n_out=h, n_heads=ref.heads_of(cfg, i),
        n_kv_heads=cfg["num_key_value_heads"], head_dim=d, causal=True,
        bias=bool(cfg["attention_bias"]), rope=True,
        rope_theta=float(rp["rope_theta"]),
        rotary_dim=None if rotary_dim == d else rotary_dim,
        window=cfg["sliding_window"] if sliding else None,
        gate="per_head", **kw)


def build_network(cfg: dict):
    from deeplearning4j_tpu.models.sequential import MultiLayerNetwork
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import (
        EmbeddingLayer, GatedMLP, ResidualBlock, RMSNorm, RnnOutputLayer,
        RoutedMoELayer,
    )

    if (cfg["model_type"] != "laguna" or cfg["gating"] != "per-head"
            or cfg["attention_bias"] or cfg["tie_word_embeddings"]
            or cfg["moe_router_logit_softcapping"]
            or cfg["moe_apply_router_weight_on_input"]):
        raise ValueError("only the Laguna block is built here: per-head "
                         "gate, no bias, untied head, no soft cap, router "
                         "weight on the output")
    h, eps = cfg["hidden_size"], cfg["rms_norm_eps"]
    b = NeuralNetConfiguration.builder().seed(0).updater("sgd").list()
    if _STORED[cfg["torch_dtype"]]:
        b.compute_dtype(_STORED[cfg["torch_dtype"]])
    b.layer(EmbeddingLayer(n_in=cfg["vocab_size"], n_out=h,
                           collapse_column=False))
    for i in range(cfg["num_hidden_layers"]):
        b.layer(ResidualBlock(layers=(RMSNorm(n_in=h, eps=eps),
                                      attention_layer(cfg, i))))
        if ref.is_dense(cfg, i):
            ffn = GatedMLP(n_in=h, n_out=h, hidden=cfg["intermediate_size"])
        else:
            ffn = RoutedMoELayer(
                n_in=h, n_out=h, n_experts=ref.router_width(cfg),
                top_k=cfg["num_experts_per_tok"],
                hidden=cfg["moe_intermediate_size"],
                shared=cfg["shared_expert_intermediate_size"],
                experts_held=(cfg.get("first_expert_held", 0),
                              cfg["num_experts"]),
                norm_topk_prob=cfg["norm_topk_prob"],
                routed_scaling_factor=cfg["moe_routed_scaling_factor"],
                scoring="softmax")
        b.layer(ResidualBlock(layers=(RMSNorm(n_in=h, eps=eps), ffn)))
    b.layer(RMSNorm(n_in=h, eps=eps))
    b.layer(RnnOutputLayer(n_in=h, n_out=cfg["vocab_size"], loss="mcxent",
                           activation="softmax"))
    return MultiLayerNetwork(b.build())


def leaf_names(cfg: dict) -> dict:
    """The program's parameter tree with, at every leaf, the reference's
    name for it."""
    n = cfg["num_hidden_layers"]
    tree = {"layer_0": {"W": "emb.W", "b": "emb.b"}}
    for i in range(n):
        p = f"L{i}."
        ffn = _DENSE if ref.is_dense(cfg, i) else _MOE
        tree[f"layer_{1 + 2 * i}"] = {
            "sub0": {"gamma": p + "in_norm.g"},
            "sub1": {k: p + v for k, v in _ATTN.items()}}
        tree[f"layer_{2 + 2 * i}"] = {
            "sub0": {"gamma": p + "post_norm.g"},
            "sub1": {k: p + v for k, v in ffn.items()}}
    tree[f"layer_{2 * n + 1}"] = {"gamma": "norm.g"}
    tree[f"layer_{2 * n + 2}"] = {"W": "head.W", "b": "head.b"}
    return tree


def install_weights(net, cfg: dict, seed: int):
    """What ``net.init()`` does, with the benchmark's leaves, each drawn
    alone on the device in the stored dtype."""
    import jax
    import jax.numpy as jnp

    shapes = ref.leaf_shapes(cfg)
    stored = jnp.dtype(cfg["torch_dtype"])
    net.params = jax.tree_util.tree_map(
        lambda name: ref.make_leaf(cfg, seed, name, shapes[name], stored),
        leaf_names(cfg))
    net.net_state = {}
    net.updater_state = {}
    return net
