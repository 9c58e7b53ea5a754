"""``jamba2-3b``'s configuration file -> the program's ``MultiLayerNetwork``,
through the config DSL, with the benchmark's own weights
(``reference_jamba.make_leaf``) installed in place of ``net.init()``'s — the
numbers flow from the benchmark into the program, never back.

The net: ``EmbeddingLayer``, then per decoder layer ``ResidualBlock(RMSNorm,
MambaLayer)`` — or, where ``reference_jamba.is_attention`` says so (layers 7
and 21), ``ResidualBlock(RMSNorm, SelfAttentionLayer)`` with 20 query heads
over 1 kv head of 128, no bias and NO rotary — and ``ResidualBlock(RMSNorm,
GatedMLP)``; a final ``RMSNorm``; ``RnnOutputLayer``.  The head is tied to
the embedding in the published model; the DSL shares no leaf, so the head is
a second leaf holding the embedding's values (``reference_jamba.make_leaf``
gives ``head.W`` as ``emb.W`` transposed).  Leaves are installed in the
stored dtype the configuration states (bfloat16), which is also the compute
dtype, so the serving snapshot is the net's own buffers.
"""

from __future__ import annotations

from benchmark import reference_jamba as ref

_STORED = {"bfloat16": "bfloat16", "float32": None}

# the program's parameter names, by the reference's
_MAMBA = {"W_in": "in_proj", "conv_W": "conv.W", "conv_b": "conv.b",
          "W_x": "x_proj", "dt_norm": "dt_norm.g", "b_norm": "b_norm.g",
          "c_norm": "c_norm.g", "W_dt": "dt_proj.W", "b_dt": "dt_proj.b",
          "A_log": "A_log", "D": "D", "W_out": "out_proj"}
_ATTN = {"Wq": "wq", "Wk": "wk", "Wv": "wv", "Wo": "wo"}
_FFN = {"W_gate": "w_gate", "W_up": "w_up", "W_down": "w_down"}


def build_network(cfg: dict):
    from deeplearning4j_tpu.models.sequential import MultiLayerNetwork
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import (
        EmbeddingLayer, GatedMLP, MambaLayer, ResidualBlock, RMSNorm,
        RnnOutputLayer, SelfAttentionLayer,
    )

    if (cfg["hidden_act"] != "silu" or cfg["num_experts"] != 1
            or cfg["mamba_proj_bias"] or not cfg["mamba_conv_bias"]
            or cfg.get("sliding_window") or not cfg["tie_word_embeddings"]):
        raise ValueError("only Jamba2's dense block is built here: silu, one "
                         "expert (a dense FFN), a biased convolution, no "
                         "projection bias, no window, a tied head")
    h, eps = cfg["hidden_size"], cfg["rms_norm_eps"]
    b = NeuralNetConfiguration.builder().seed(0).updater("sgd").list()
    if _STORED[cfg["torch_dtype"]]:
        b.compute_dtype(_STORED[cfg["torch_dtype"]])
    b.layer(EmbeddingLayer(n_in=cfg["vocab_size"], n_out=h,
                           collapse_column=False))
    for i in range(cfg["num_hidden_layers"]):
        if ref.is_attention(cfg, i):
            mixer = SelfAttentionLayer(
                n_in=h, n_out=h, n_heads=cfg["num_attention_heads"],
                n_kv_heads=cfg["num_key_value_heads"],
                head_dim=ref.head_dim(cfg), causal=True, bias=False,
                rope=False)
        else:
            mixer = MambaLayer(
                n_in=h, n_out=h, expand=cfg["mamba_expand"],
                d_state=cfg["mamba_d_state"], d_conv=cfg["mamba_d_conv"],
                dt_rank=cfg["mamba_dt_rank"], conv_bias=True,
                inner_norms=True, eps=eps)
        b.layer(ResidualBlock(layers=(RMSNorm(n_in=h, eps=eps), mixer)))
        b.layer(ResidualBlock(layers=(
            RMSNorm(n_in=h, eps=eps),
            GatedMLP(n_in=h, n_out=h, hidden=cfg["intermediate_size"]))))
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
        mixer = _ATTN if ref.is_attention(cfg, i) else _MAMBA
        tree[f"layer_{1 + 2 * i}"] = {
            "sub0": {"gamma": p + "in_norm.g"},
            "sub1": {k: p + v for k, v in mixer.items()}}
        tree[f"layer_{2 + 2 * i}"] = {
            "sub0": {"gamma": p + "post_norm.g"},
            "sub1": {k: p + v for k, v in _FFN.items()}}
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
