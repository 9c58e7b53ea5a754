"""``xing4.0-29b-a4b-pp6``'s configuration file -> the program's
``MultiLayerNetwork``, through the config DSL, with the benchmark's own
weights (``reference_xing.make_leaf``) installed in place of ``net.init()``'s
— the numbers flow from the benchmark into the program, never back.

The block: ``model_k2``'s sub-layers — ``RMSNorm -> LatentAttentionLayer``
and ``RMSNorm -> GatedMLP`` (layers below ``first_k_dense_replace``) or
``RMSNorm -> RoutedMoELayer`` (the others) — each pair in a
``HyperConnectionBlock`` over ``hc_mult`` residual streams in place of a
``ResidualBlock``; ``HyperStreamExpand`` after the embedding and
``HyperStreamReduce`` ahead of the final ``RMSNorm``; linear head.

THE STREAMS ARE FLATTENED: between the two ends a token's state is
``[B, T, hc_mult * hidden_size]``, the streams side by side in the last
axis, under the DSL's ordinary ``InputType.recurrent``.  A rank-4 input
type would have to be taught to every preprocessor and shape rule of
``nn/conf`` for the sake of three layers; a stream is a whole-lane slice of
the last axis (3584 = 28 x 128), so the flattening costs the device nothing
and the coefficients' product (over all ``hc_mult * hidden_size`` entries)
runs over the axis as it lies.

The expert layer holds every expert: ``experts_held = (0, 64)`` of 64.
"""

from __future__ import annotations

from benchmark import model_k2, reference_xing as ref

def build_network(cfg: dict):
    from deeplearning4j_tpu.models.sequential import MultiLayerNetwork
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import (
        EmbeddingLayer, GatedMLP, HyperConnectionBlock, HyperStreamExpand,
        HyperStreamReduce, LatentAttentionLayer, RMSNorm, RnnOutputLayer,
        RoutedMoELayer,
    )

    if (cfg["hidden_act"] != "silu" or cfg["scoring_func"] != "sigmoid"
            or cfg["n_group"] != 1 or cfg["topk_group"] != 1
            or cfg.get("attention_bias") or cfg["tie_word_embeddings"]):
        raise ValueError("only Xing4.0's block is built here: silu, sigmoid "
                         "scores in one group, no attention bias, an untied "
                         "head")
    h, eps, n = cfg["hidden_size"], cfg["rms_norm_eps"], cfg["hc_mult"]
    sc = cfg["rope_scaling"]

    def hyper(sublayer):
        return HyperConnectionBlock(
            n_in=n * h, streams=n, sinkhorn_iters=cfg["hc_sinkhorn_iters"],
            sinkhorn_eps=cfg["hc_eps"], eps=eps,
            res_clamp=(float(cfg["mhc_h_res_clamp_min"]),
                       float(cfg["mhc_h_res_clamp_max"])),
            layers=(RMSNorm(n_in=h, eps=eps), sublayer))

    b = NeuralNetConfiguration.builder().seed(0).updater("sgd").list()
    if model_k2._STORED[cfg["torch_dtype"]]:
        b.compute_dtype(model_k2._STORED[cfg["torch_dtype"]])
    b.layer(EmbeddingLayer(n_in=cfg["vocab_size"], n_out=h,
                           collapse_column=False))
    b.layer(HyperStreamExpand(n_in=h, streams=n))
    for i in range(cfg["num_hidden_layers"]):
        b.layer(hyper(LatentAttentionLayer(
            n_in=h, n_out=h, n_heads=cfg["num_attention_heads"],
            q_rank=cfg["q_lora_rank"], kv_rank=cfg["kv_lora_rank"],
            nope_dim=cfg["qk_nope_head_dim"],
            rope_dim=cfg["qk_rope_head_dim"], v_dim=cfg["v_head_dim"],
            eps=eps, rope_theta=float(cfg["rope_theta"]),
            rope_factor=float(sc["factor"]),
            rope_original_max=int(sc["original_max_position_embeddings"]),
            rope_beta_fast=float(sc["beta_fast"]),
            rope_beta_slow=float(sc["beta_slow"]),
            rope_mscale=float(sc["mscale"]),
            rope_mscale_all_dim=float(sc["mscale_all_dim"]))))
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
        b.layer(hyper(ffn))
    b.layer(HyperStreamReduce(n_in=n * h, streams=n))
    b.layer(RMSNorm(n_in=h, eps=eps))
    b.layer(RnnOutputLayer(n_in=h, n_out=cfg["vocab_size"], loss="mcxent",
                           activation="softmax"))
    return MultiLayerNetwork(b.build())


def leaf_names(cfg: dict) -> dict:
    """The program's parameter tree with, at every leaf, the reference's
    name for it (the two ends of the streams have none)."""
    n = cfg["num_hidden_layers"]
    tree = {"layer_0": {"W": "emb.W", "b": "emb.b"}, "layer_1": {},
            f"layer_{2 * n + 2}": {}}
    for i in range(n):
        p = f"L{i}."
        ffn = model_k2._DENSE if ref.is_dense(cfg, i) else model_k2._MOE
        for at, norm, names, hc in (
                (2 + 2 * i, "in_norm.g", model_k2._ATTN, "attn_hc."),
                (3 + 2 * i, "post_norm.g", ffn, "ffn_hc.")):
            tree[f"layer_{at}"] = {
                "sub0": {"gamma": p + norm},
                "sub1": {k: p + v for k, v in names.items()},
                **{k: p + hc + k for k in ("phi", "alpha", "beta")}}
    tree[f"layer_{2 * n + 3}"] = {"gamma": "norm.g"}
    tree[f"layer_{2 * n + 4}"] = {"W": "head.W", "b": "head.b"}
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
