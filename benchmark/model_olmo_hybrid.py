"""``olmo-hybrid-7b-pp4``'s configuration file -> the program's
``MultiLayerNetwork``, through the config DSL, with the benchmark's own
weights (``reference_olmo_hybrid.make_leaf``) installed in place of
``net.init()``'s — the numbers flow from the benchmark into the program,
never back.

The net: ``EmbeddingLayer``, then per decoder layer ``ResidualBlock(
GatedDeltaNetLayer, RMSNorm)`` — or, where ``layer_types`` says
``full_attention`` (layers 3 and 7), ``ResidualBlock(SelfAttentionLayer,
RMSNorm)`` with 30 query and 30 kv heads of 128, no bias, NO rotary and
Olmo's whole-width q/k norm — and ``ResidualBlock(GatedMLP, RMSNorm)``: the
norm on each sub-layer's output (Olmo's order); a final ``RMSNorm``;
``RnnOutputLayer``, the untied head.  Leaves are installed in the stored
dtype the configuration states (bfloat16), which is also the compute dtype,
so the serving snapshot is the net's own buffers.
"""

from __future__ import annotations

from benchmark import reference_olmo_hybrid as ref

_STORED = {"bfloat16": "bfloat16", "float32": None}

# the program's parameter names, by the reference's
_LINEAR = {"W_q": "wq", "W_k": "wk", "W_v": "wv", "W_a": "wa", "W_b": "wb",
           "W_g": "wg", "W_o": "wo", "conv_q": "conv_q.W",
           "conv_k": "conv_k.W", "conv_v": "conv_v.W", "A_log": "A_log",
           "dt_bias": "dt_bias", "o_norm": "o_norm.g"}
_FULL = {"Wq": "wq", "Wk": "wk", "Wv": "wv", "Wo": "wo",
         "q_norm": "q_norm.g", "k_norm": "k_norm.g"}
_FFN = {"W_gate": "w_gate", "W_up": "w_up", "W_down": "w_down"}


def build_network(cfg: dict):
    from deeplearning4j_tpu.models.sequential import MultiLayerNetwork
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import (
        EmbeddingLayer, GatedDeltaNetLayer, GatedMLP, ResidualBlock, RMSNorm,
        RnnOutputLayer, SelfAttentionLayer,
    )

    if (cfg["hidden_act"] != "silu" or cfg["attention_bias"]
            or cfg["tie_word_embeddings"]
            or (cfg["rope_parameters"] or {}).get("rope_theta") is not None
            or cfg["linear_num_key_heads"] != cfg["linear_num_value_heads"]):
        raise ValueError("only Olmo-Hybrid's block is built here: silu, no "
                         "bias, an untied head, no rotary, as many key as "
                         "value heads in the linear layers")
    h, eps = cfg["hidden_size"], cfg["rms_norm_eps"]
    heads, dk, dv, _, _ = ref.linear_widths(cfg)
    b = NeuralNetConfiguration.builder().seed(0).updater("sgd").list()
    if _STORED[cfg["torch_dtype"]]:
        b.compute_dtype(_STORED[cfg["torch_dtype"]])
    b.layer(EmbeddingLayer(n_in=cfg["vocab_size"], n_out=h,
                           collapse_column=False))
    for i in range(cfg["num_hidden_layers"]):
        if ref.is_full(cfg, i):
            mixer = SelfAttentionLayer(
                n_in=h, n_out=h, n_heads=cfg["num_attention_heads"],
                n_kv_heads=cfg["num_key_value_heads"],
                head_dim=ref.head_dim(cfg), causal=True, bias=False,
                rope=False, qk_norm_eps=eps)
        else:
            mixer = GatedDeltaNetLayer(
                n_in=h, n_out=h, n_heads=heads, d_k=dk, d_v=dv,
                d_conv=cfg["linear_conv_kernel_dim"],
                allow_neg_eigval=cfg["linear_allow_neg_eigval"], eps=eps)
        b.layer(ResidualBlock(layers=(mixer, RMSNorm(n_in=h, eps=eps))))
        b.layer(ResidualBlock(layers=(
            GatedMLP(n_in=h, n_out=h, hidden=cfg["intermediate_size"]),
            RMSNorm(n_in=h, eps=eps))))
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
        mixer = _FULL if ref.is_full(cfg, i) else _LINEAR
        tree[f"layer_{1 + 2 * i}"] = {
            "sub0": {k: p + v for k, v in mixer.items()},
            "sub1": {"gamma": p + "mixer_norm.g"}}
        tree[f"layer_{2 + 2 * i}"] = {
            "sub0": {k: p + v for k, v in _FFN.items()},
            "sub1": {"gamma": p + "ffn_norm.g"}}
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
