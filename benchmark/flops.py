"""Operations and bytes the algorithm needs, from shapes alone, and the
table of peaks.  Kept with the benchmark so that no later PR can move a
utilization by recounting.

Count by hand for ``starcoder2-3b`` (hidden 3072, 24 heads / 2 KV heads of
128, MLP 12288, vocabulary 49152), to hold ``train_step_mfu`` still:

  attention matrices  3072*3072 (q) + 2 * 3072*256 (k, v) + 3072*3072 (o)
                      = 20,447,232
  MLP matrices        2 * 3072*12288                     = 75,497,472
  one layer's matrices                                   = 95,944,704
  with biases (22,016) and two LayerNorms (12,288)       = 95,979,008  (96.0 M)
  head                3072*49152                         = 150,994,944
  matmul parameters at 3 layers: 3 * 95,944,704 + 150,994,944 = 438,829,056
  (the embedding is a lookup and multiplies nothing)

  trained token, T = 4096, no recompute counted:
    matmuls    6 * 438,829,056                           = 2.633 GFLOP
    attention  QK^T and PV, 2 FLOP a multiply-add, causal (mean context
               T/2), forward once and backward twice:
               3 * [2 * 2 * (4096/2) * 3072] = 75,497,472 a layer,
               * 3 layers                                = 0.226 GFLOP
    total                                                = 2.860 GFLOP/token
    one 4096-token sequence                              = 11.71 TFLOP
"""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks_for(device_kind: str) -> dict:
    """The published peaks of one chip; an unknown kind is an error."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device_kind {device_kind!r} in "
                       f"benchmark/peaks.json (has {sorted(table)})")
    return table[device_kind]


def layer_matmul_params(cfg: dict) -> int:
    h, hd = cfg["hidden_size"], cfg["head_dim"]
    nq = cfg["num_attention_heads"] * hd
    nkv = cfg["num_key_value_heads"] * hd
    return h * nq + 2 * h * nkv + nq * h + 2 * h * cfg["intermediate_size"]


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def matmul_params(cfg: dict) -> int:
    return (cfg["num_hidden_layers"] * layer_matmul_params(cfg)
            + head_params(cfg))


def attn_width(cfg: dict) -> int:
    return cfg["num_attention_heads"] * cfg["head_dim"]


def attention_forward_flops(cfg: dict, q_len: int, start: int = 0) -> float:
    """QK^T and PV of one layer for ``q_len`` new positions that begin at
    ``start``, each attending to itself and everything before it."""
    keys_seen = q_len * start + q_len * (q_len + 1) / 2
    return 2 * 2 * keys_seen * attn_width(cfg)


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward and backward of one trained token: 6 FLOP a matmul
    parameter, plus causal attention forward once and backward twice (the
    four backward products; the recomputed scores are not counted)."""
    attn = (3 * attention_forward_flops(cfg, seq_len) / seq_len
            * cfg["num_hidden_layers"])
    return 6 * matmul_params(cfg) + attn


def flash_attention_cost(cfg: dict, seq_len: int, sequences: int) -> dict:
    """Causal attention forward and backward over ``sequences`` sequences in
    every layer: operations as in ``train_flops_per_token``; bytes are one
    read of q, k, v, o and do, and one write of o, dq, dk, dv, in bfloat16,
    with k and v at their unexpanded width."""
    layers = cfg["num_hidden_layers"]
    flops = 3 * attention_forward_flops(cfg, seq_len) * layers * sequences
    wide = seq_len * attn_width(cfg)
    narrow = seq_len * cfg["num_key_value_heads"] * cfg["head_dim"]
    elems = (wide * 5 + narrow * 4) * layers * sequences
    return {"flops": flops, "bytes": 2.0 * elems}


def serve_forward_flops(cfg: dict, prompt_lens, decode_positions) -> float:
    """Forward operations the served tokens need: each prompt token passes
    the layers; each request needs the head once for its first token; each
    decoded token at position ``p`` passes layers and head and attends to
    ``p + 1`` positions.  Bucket padding is not work."""
    layers = cfg["num_hidden_layers"]
    per_tok = 2 * layers * layer_matmul_params(cfg)
    head = 2 * head_params(cfg)
    total = 0.0
    for n in prompt_lens:
        total += n * per_tok + head
        total += layers * attention_forward_flops(cfg, n)
    for p in decode_positions:
        total += per_tok + head
        total += layers * attention_forward_flops(cfg, 1, start=p)
    return total


def kv_bytes_read(cfg: dict, decode_positions, page_size: int,
                  bytes_per_el: int = 2) -> float:
    """Bytes of the resident K and V pages a decode step must read for
    rows at ``decode_positions`` (whole pages, every layer)."""
    per_pos = (2 * cfg["num_key_value_heads"] * cfg["head_dim"]
               * bytes_per_el * cfg["num_hidden_layers"])
    return float(sum(-(-(p + 1) // page_size) * page_size * per_pos
                     for p in decode_positions))


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peaks: dict) -> dict:
    """The least time the chip could take over the time it took, in
    percent, and which of the two peaks bounds it."""
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return {"share": 100.0 * max(t_flops, t_bytes) / seconds,
            "bound": "flops" if t_flops >= t_bytes else "bytes"}
