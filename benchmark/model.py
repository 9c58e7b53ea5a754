"""Configuration file -> the program's ``MultiLayerNetwork``, through the
config DSL (``NeuralNetConfiguration.builder()``), the normal entry point.

The benchmark's own weights (``reference.make_weights``) are installed in
place of ``net.init()``'s: the reference may take nothing the program has
made, so the numbers flow from the benchmark into the program.
"""

from __future__ import annotations

import json

from benchmark import reference


def load_config(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def build_network(cfg: dict, *, max_seq: int, updater: str = "adam",
                  lr: float = 1e-4, max_cache: int = 1024):
    """The StarCoder2 block in the program's layers.  ``max_seq`` is the
    longest sequence the cell will run: the sliding window is passed to the
    attention layers only when a sequence can exceed it."""
    from deeplearning4j_tpu.models.sequential import MultiLayerNetwork
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.layers import (
        DenseLayer, EmbeddingLayer, LayerNorm, ResidualBlock,
        RnnOutputLayer, SelfAttentionLayer,
    )

    if cfg["hidden_act"] != "gelu_pytorch_tanh" or not cfg["use_bias"]:
        raise ValueError("only the StarCoder2 block is built here")
    h, eps = cfg["hidden_size"], cfg["norm_epsilon"]
    if cfg["num_attention_heads"] * cfg["head_dim"] != h:
        raise ValueError("attention width must equal hidden_size")
    window = cfg["sliding_window"] if max_seq > cfg["sliding_window"] else None
    dtype = {"bfloat16": "bfloat16", "float32": None}[cfg["torch_dtype"]]
    b = (NeuralNetConfiguration.builder().seed(0)
         .updater(updater, learning_rate=lr).list())
    if dtype:
        b.compute_dtype(dtype)
    b.layer(EmbeddingLayer(n_in=cfg["vocab_size"], n_out=h,
                           collapse_column=False))
    for _ in range(cfg["num_hidden_layers"]):
        b.layer(ResidualBlock(layers=(
            LayerNorm(n_in=h, eps=eps),
            SelfAttentionLayer(
                n_in=h, n_out=h, n_heads=cfg["num_attention_heads"],
                n_kv_heads=cfg["num_key_value_heads"], causal=True,
                rope=True, rope_theta=float(cfg["rope_theta"]),
                window=window, max_cache=max_cache))))
        b.layer(ResidualBlock(layers=(
            LayerNorm(n_in=h, eps=eps),
            DenseLayer(n_in=h, n_out=cfg["intermediate_size"],
                       activation="gelu"),
            DenseLayer(n_in=cfg["intermediate_size"], n_out=h,
                       activation="identity"))))
    b.layer(LayerNorm(n_in=h, eps=eps))
    b.layer(RnnOutputLayer(n_in=h, n_out=cfg["vocab_size"], loss="mcxent",
                           activation="softmax"))
    return MultiLayerNetwork(b.build())


_ATTN = {"Wq": "wq", "bq": "bq", "Wk": "wk", "bk": "bk", "Wv": "wv",
         "bv": "bv", "Wo": "wo", "bo": "bo"}


def program_tree(flat: dict, n_layers: int) -> dict:
    """The reference's flat leaves, arranged as the program's parameter
    tree (the same device buffers, no copy)."""
    tree = {"layer_0": {"W": flat["emb.W"], "b": flat["emb.b"]}}
    for i in range(n_layers):
        p = f"L{i}."
        tree[f"layer_{1 + 2 * i}"] = {
            "sub0": {"gamma": flat[p + "ln1.g"], "beta": flat[p + "ln1.b"]},
            "sub1": {k: flat[p + v] for k, v in _ATTN.items()}}
        tree[f"layer_{2 + 2 * i}"] = {
            "sub0": {"gamma": flat[p + "ln2.g"], "beta": flat[p + "ln2.b"]},
            "sub1": {"W": flat[p + "w1"], "b": flat[p + "b1"]},
            "sub2": {"W": flat[p + "w2"], "b": flat[p + "b2"]}}
    tree[f"layer_{2 * n_layers + 1}"] = {"gamma": flat["lnf.g"],
                                         "beta": flat["lnf.b"]}
    tree[f"layer_{2 * n_layers + 2}"] = {"W": flat["head.W"],
                                         "b": flat["head.b"]}
    return tree


def flat_leaves(tree: dict, n_layers: int) -> dict:
    """Inverse of ``program_tree``: the program's tree under the
    reference's leaf names."""
    names = program_tree({k: k for k in _leaf_names(n_layers)}, n_layers)
    out = {}

    def walk(n, t):
        if isinstance(n, dict):
            for k in n:
                walk(n[k], t[k])
        else:
            out[n] = t
    walk(names, tree)
    return out


def _leaf_names(n_layers: int):
    cfg = dict(hidden_size=1, vocab_size=1, head_dim=1, num_attention_heads=1,
               num_key_value_heads=1, intermediate_size=1,
               num_hidden_layers=n_layers)
    return list(reference.leaf_shapes(cfg))


def install_weights(net, weights: dict, n_layers: int, with_updater: bool):
    """What ``net.init()`` does, with the benchmark's weights in place of
    the program's initialisers."""
    from deeplearning4j_tpu.optimize import updaters

    net.params = program_tree(weights, n_layers)
    net.net_state = {}
    net.updater_state = (updaters.init_state(net.conf.updater, net.params)
                         if with_updater else {})
    return net
