"""``ling-3.0-flash-ep8``'s configuration file -> the program's
``MultiLayerNetwork``, through the config DSL, with the benchmark's own
weights (``reference_ling.make_leaf``) installed in place of ``net.init()``'s
— the numbers flow from the benchmark into the program, never back.

The net: ``EmbeddingLayer``, then per decoder layer ``ResidualBlock(RMSNorm,
KimiDeltaAttentionLayer)`` — or, where ``(i + 1) % layer_group_size == 0``
(layer 5), ``ResidualBlock(RMSNorm, LatentAttentionLayer)`` with no query
latent (``q_rank=0``) and a head-wise gate — and ``ResidualBlock(RMSNorm,
GatedMLP)`` (layers below ``first_k_dense_replace``) or ``ResidualBlock(
RMSNorm, RoutedMoELayer)`` told its share (``experts_held``) and its groups
(``n_group``, ``topk_group``); a final ``RMSNorm``; ``RnnOutputLayer``, the
untied head.  Leaves are installed in the stored dtype the configuration
states (bfloat16), which is also the compute dtype, so the serving snapshot
is the net's own buffers.
"""

from __future__ import annotations

from benchmark import reference_ling as ref

_STORED = {"bfloat16": "bfloat16", "float32": None}

# the program's parameter names, by the reference's
_KDA = {"W_q": "wq", "W_k": "wk", "W_v": "wv", "W_a": "wf", "W_b": "wb",
        "W_g": "wog", "W_o": "wo", "conv_q": "conv_q.W",
        "conv_k": "conv_k.W", "conv_v": "conv_v.W", "A_log": "A_log",
        "dt_bias": "dt_bias", "o_norm": "o_norm.g"}
_MLA = {"Wq": "wq", "Wkva": "wkva", "kv_norm": "kv_norm.g", "Wkvb": "wkvb",
        "Wg": "wg", "Wo": "wo"}
_DENSE = {"W_gate": "w_gate", "W_up": "w_up", "W_down": "w_down"}
_MOE = {"W_router": "router.W", "b_router": "router.b",
        "W_gate": "experts.w_gate", "W_up": "experts.w_up",
        "W_down": "experts.w_down", "Ws_gate": "shared.w_gate",
        "Ws_up": "shared.w_up", "Ws_down": "shared.w_down"}


def build_network(cfg: dict):
    from deeplearning4j_tpu.models.sequential import MultiLayerNetwork
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import (
        EmbeddingLayer, GatedMLP, KimiDeltaAttentionLayer,
        LatentAttentionLayer, ResidualBlock, RMSNorm, RnnOutputLayer,
        RoutedMoELayer,
    )

    if (cfg["hidden_act"] != "silu" or cfg["scoring_func"] != "sigmoid"
            or cfg["topk_method"] != "noaux_tc" or cfg["use_bias"]
            or cfg["use_qkv_bias"] or cfg["tie_word_embeddings"]
            or cfg["q_lora_rank"] is not None or not cfg["kda_safe_gate"]
            or cfg["num_kv_heads_for_linear_attn"] not in (
                0, cfg["num_attention_heads"])
            or cfg["gated_attention_proj_granularity_type"] != "head_wise"
            or cfg["rope_scaling"] is not None):
        raise ValueError("only Ling-3.0-flash's block is built here: silu, "
                         "sigmoid noaux_tc routing, no bias, an untied head, "
                         "no query latent, the safe gate, no shared KDA "
                         "head, head-wise gates, plain RoPE")
    h, eps = cfg["hidden_size"], cfg["rms_norm_eps"]
    heads, d, _ = ref.kda_widths(cfg)
    b = NeuralNetConfiguration.builder().seed(0).updater("sgd").list()
    if _STORED[cfg["torch_dtype"]]:
        b.compute_dtype(_STORED[cfg["torch_dtype"]])
    b.layer(EmbeddingLayer(n_in=cfg["vocab_size"], n_out=h,
                           collapse_column=False))
    for i in range(cfg["num_hidden_layers"]):
        if ref.is_mla(cfg, i):
            mixer = LatentAttentionLayer(
                n_in=h, n_out=h, n_heads=heads, q_rank=0,
                kv_rank=cfg["kv_lora_rank"], nope_dim=cfg["qk_nope_head_dim"],
                rope_dim=cfg["qk_rope_head_dim"], v_dim=cfg["v_head_dim"],
                eps=eps, rope_theta=float(cfg["rope_theta"]),
                gate="per_head")
        else:
            mixer = KimiDeltaAttentionLayer(
                n_in=h, n_out=h, n_heads=heads, d_k=d, d_v=d,
                d_conv=cfg["short_conv_kernel_size"], eps=eps,
                lower_bound=float(cfg["kda_lower_bound"]))
        b.layer(ResidualBlock(layers=(RMSNorm(n_in=h, eps=eps), mixer)))
        if ref.is_dense(cfg, i):
            ffn = GatedMLP(n_in=h, n_out=h, hidden=cfg["intermediate_size"])
        else:
            ffn = RoutedMoELayer(
                n_in=h, n_out=h, n_experts=ref.router_width(cfg),
                top_k=cfg["num_experts_per_tok"],
                hidden=cfg["moe_intermediate_size"],
                shared=(cfg["moe_shared_expert_intermediate_size"]
                        * cfg["num_shared_experts"]),
                experts_held=(cfg.get("first_expert_held", 0),
                              cfg["num_experts"]),
                norm_topk_prob=cfg["norm_topk_prob"],
                routed_scaling_factor=cfg["routed_scaling_factor"],
                n_group=cfg["n_group"], topk_group=cfg["topk_group"])
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
        mixer = _MLA if ref.is_mla(cfg, i) else _KDA
        ffn = _DENSE if ref.is_dense(cfg, i) else _MOE
        tree[f"layer_{1 + 2 * i}"] = {
            "sub0": {"gamma": p + "in_norm.g"},
            "sub1": {k: p + v for k, v in mixer.items()}}
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
