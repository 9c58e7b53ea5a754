"""``kimi-k2.5-ep32``'s configuration file -> the program's
``MultiLayerNetwork``, through the config DSL, with the benchmark's own
weights (``reference_k2.make_leaf``) installed in place of ``net.init()``'s
— the numbers flow from the benchmark into the program, never back.

The block (DeepSeek-V3 / Kimi-K2): ``RMSNorm -> LatentAttentionLayer`` and
``RMSNorm -> GatedMLP`` (layers below ``first_k_dense_replace``) or
``RMSNorm -> RoutedMoELayer`` (the others), each pair in a
``ResidualBlock``; final ``RMSNorm``; linear head.  The expert layer is told
its share: ``experts_held = (first_expert_held, n_routed_experts)`` of the
``published`` count.  Leaves are installed in the stored dtype the
configuration states (bfloat16), which is also the compute dtype, so the
serving snapshot is the net's own buffers.
"""

from __future__ import annotations

from benchmark import reference_k2 as ref

_STORED = {"bfloat16": "bfloat16", "float32": None}

# the program's parameter names, by the reference's
_ATTN = {"Wqa": "wqa", "q_norm": "q_norm.g", "Wqb": "wqb", "Wkva": "wkva",
         "kv_norm": "kv_norm.g", "Wkvb": "wkvb", "Wo": "wo"}
_DENSE = {"W_gate": "w_gate", "W_up": "w_up", "W_down": "w_down"}
_MOE = {"W_router": "router.W", "b_router": "router.b",
        "W_gate": "experts.w_gate", "W_up": "experts.w_up",
        "W_down": "experts.w_down", "Ws_gate": "shared.w_gate",
        "Ws_up": "shared.w_up", "Ws_down": "shared.w_down"}


def build_network(cfg: dict):
    from deeplearning4j_tpu.models.sequential import MultiLayerNetwork
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import (
        EmbeddingLayer, GatedMLP, LatentAttentionLayer, ResidualBlock,
        RMSNorm, RnnOutputLayer, RoutedMoELayer,
    )

    if (cfg["hidden_act"] != "silu" or cfg["scoring_func"] != "sigmoid"
            or cfg["n_group"] != 1 or cfg["topk_group"] != 1
            or cfg.get("attention_bias")):
        raise ValueError("only the Kimi-K2 block is built here: silu, sigmoid "
                         "scores in one group, no attention bias")
    h, eps = cfg["hidden_size"], cfg["rms_norm_eps"]
    sc = cfg.get("rope_scaling") or {}
    b = NeuralNetConfiguration.builder().seed(0).updater("sgd").list()
    if _STORED[cfg["torch_dtype"]]:
        b.compute_dtype(_STORED[cfg["torch_dtype"]])
    b.layer(EmbeddingLayer(n_in=cfg["vocab_size"], n_out=h,
                           collapse_column=False))
    for i in range(cfg["num_hidden_layers"]):
        b.layer(ResidualBlock(layers=(
            RMSNorm(n_in=h, eps=eps),
            LatentAttentionLayer(
                n_in=h, n_out=h, n_heads=cfg["num_attention_heads"],
                q_rank=cfg["q_lora_rank"], kv_rank=cfg["kv_lora_rank"],
                nope_dim=cfg["qk_nope_head_dim"],
                rope_dim=cfg["qk_rope_head_dim"], v_dim=cfg["v_head_dim"],
                eps=eps, rope_theta=float(cfg["rope_theta"]),
                rope_factor=float(sc.get("factor", 1.0)),
                rope_original_max=int(sc.get(
                    "original_max_position_embeddings", 4096)),
                rope_beta_fast=float(sc.get("beta_fast", 32)),
                rope_beta_slow=float(sc.get("beta_slow", 1)),
                rope_mscale=float(sc.get("mscale", 1.0)),
                rope_mscale_all_dim=float(sc.get("mscale_all_dim", 0.0))))))
        if ref.is_dense(cfg, i):
            ffn = GatedMLP(n_in=h, n_out=h, hidden=cfg["intermediate_size"])
        else:
            ffn = RoutedMoELayer(
                n_in=h, n_out=h, n_experts=ref.router_width(cfg),
                top_k=cfg["num_experts_per_tok"],
                hidden=cfg["moe_intermediate_size"],
                shared=cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
                experts_held=(cfg.get("first_expert_held", 0),
                              cfg["n_routed_experts"]),
                norm_topk_prob=cfg["norm_topk_prob"],
                routed_scaling_factor=cfg["routed_scaling_factor"])
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
