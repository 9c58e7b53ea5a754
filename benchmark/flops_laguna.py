"""Operations and bytes ``laguna-s-2.1-ep8`` needs, from shapes alone
(``flops.py`` counts StarCoder2's block, ``flops_k2.py`` Kimi's).  The peaks
stay in ``peaks.json`` (``flops.peaks_for``).

Count by hand at the published widths (hidden 3072; 8 kv heads of 128; 48
query heads on full layers, 72 on sliding ones; window 512; dense FFN 12288;
expert width 1024; router 256, top 10, 32 experts held; vocabulary slice
12544; layers 0..11 = full, sliding x 3, three times over, layer 0 dense), in
multiply-adds (MAC; one MAC is 2 FLOP):

  attention matrices, full     W_q 3072*6144 = 18,874,368;  W_k, W_v
                               2 * 3072*1024 = 6,291,456;  W_g 3072*48 =
                               147,456;  W_o 6144*3072 = 18,874,368
                                                          =  44,187,648
  attention matrices, sliding  W_q 3072*9216 = 28,311,552;  W_k, W_v
                               6,291,456;  W_g 3072*72 = 221,184;  W_o
                               28,311,552                 =  63,135,744
  dense FFN            3 * 3072*12288                     = 113,246,208
  shared expert        3 * 3072*1024                      =   9,437,184
  router               3072*256                           =     786,432
  routed experts held  10 * 32/256 = 1.25 assignments a token expected,
                       1.25 * 9,437,184                   =  11,796,480
  layer 0 (full, dense)     44,187,648 + 113,246,208      = 157,433,856
  full expert layer         44,187,648 + 9,437,184 + 786,432 + 11,796,480
                                                          =  66,207,744
  sliding expert layer      63,135,744 + 22,020,096       =  85,155,840
  one token, 12 layers      157,433,856 + 2 * 66,207,744 + 9 * 85,155,840
                                                          = 1,056,251,904
                                                            (2.1125 GFLOP)
  head                 3072*12544 = 38,535,168              (0.0771 GFLOP)

  attention proper, a (query, key) pair in one layer: heads * (128 q.k +
  128 p.v) MAC: full 48 * 256 = 12,288 MAC = 24,576 FLOP; sliding 72 * 256
  = 18,432 MAC = 36,864 FLOP.  Pairs of an n-token prompt: full n(n+1)/2;
  sliding BANDED, sum_i min(i+1, 512) = 131,328 + 512 (n - 512) for n >=
  512.  A token decoded at position p: full p+1 pairs, sliding min(p+1, 512).
  a 4096-token prompt: 4096 * 2.1125 G + 0.0771 G + 3 * 24,576 * 8,390,656
                       + 9 * 36,864 * 1,966,336
                       = 8.6528 T + 0.0001 T + 0.6186 T + 0.6524 T
                                                           = 9.924 TFLOP
  a token decoded at position 3000: 2.1125 G + 0.0771 G + 3 * 24,576 * 3001
                       + 9 * 36,864 * 512                  = 2.581 GFLOP

  K and V a decode step must read for a row at position p, bf16: a position
  of one layer is 2 * 8 * 128 * 2 B = 4,096 B.  Full layers: every resident
  page, ceil((p+1) / 64) pages of 64.  Sliding layers: the pages covering
  min(p+1, 512) keys, ceil(min(p+1, 512) / 64) pages (a window that straddles
  page boundaries touches one more; the count takes the least).
  p = 3000:  3 * 47 * 64 * 4,096 + 9 * 8 * 64 * 4,096 = 36,962,304 +
             18,874,368                                   = 55,836,672 B
"""

from __future__ import annotations

import math


def published_experts(cfg: dict) -> int:
    return int(cfg.get("published", {}).get("num_experts",
                                            cfg["num_experts"]))


def is_sliding(cfg: dict, i: int) -> bool:
    return cfg["layer_types"][i] == "sliding_attention"


def attention_macs(cfg: dict, i: int) -> int:
    h, d = cfg["hidden_size"], cfg["head_dim"]
    heads, kv = cfg["num_attention_heads_per_layer"][i], cfg["num_key_value_heads"]
    return h * heads * d + 2 * h * kv * d + h * heads + heads * d * h


def expert_macs(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def held_assignments_per_token(cfg: dict) -> float:
    """Expected token-to-expert assignments that fall on a held expert."""
    return (cfg["num_experts_per_tok"] * cfg["num_experts"]
            / published_experts(cfg))


def layer_macs(cfg: dict, i: int) -> float:
    """Matrix multiply-adds one token needs in layer ``i``."""
    h = cfg["hidden_size"]
    if i in cfg["mlp_only_layers"]:
        return attention_macs(cfg, i) + 3 * h * cfg["intermediate_size"]
    return (attention_macs(cfg, i)
            + 3 * h * cfg["shared_expert_intermediate_size"]
            + h * published_experts(cfg)
            + held_assignments_per_token(cfg) * expert_macs(cfg))


def token_macs(cfg: dict) -> float:
    return sum(layer_macs(cfg, i) for i in range(cfg["num_hidden_layers"]))


def head_macs(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def pair_flops(cfg: dict, i: int) -> int:
    """q.k and p.v of one (query, key) pair in layer ``i``."""
    return 2 * cfg["num_attention_heads_per_layer"][i] * 2 * cfg["head_dim"]


def prompt_pairs(cfg: dict, i: int, n: int) -> float:
    """(query, key) pairs layer ``i`` computes for an ``n``-token prompt:
    causal, banded to the window on sliding layers."""
    if not is_sliding(cfg, i):
        return n * (n + 1) / 2
    w = min(n, cfg["sliding_window"])
    return w * (w + 1) / 2 + (n - w) * cfg["sliding_window"]


def decode_pairs(cfg: dict, i: int, position: int) -> int:
    """Keys a token decoded at ``position`` attends to in layer ``i``."""
    seen = position + 1
    return min(seen, cfg["sliding_window"]) if is_sliding(cfg, i) else seen


def prompt_flops(cfg: dict, n: int) -> float:
    """Forward operations of one ``n``-token prompt prefilled whole, and the
    head once for its first token."""
    layers = range(cfg["num_hidden_layers"])
    return (2 * (n * token_macs(cfg) + head_macs(cfg))
            + sum(pair_flops(cfg, i) * prompt_pairs(cfg, i, n)
                  for i in layers))


def decode_flops(cfg: dict, position: int) -> float:
    """Forward operations of one token decoded at ``position``."""
    layers = range(cfg["num_hidden_layers"])
    return (2 * (token_macs(cfg) + head_macs(cfg))
            + sum(pair_flops(cfg, i) * decode_pairs(cfg, i, position)
                  for i in layers))


def serve_forward_flops(cfg: dict, prompt_lens, decode_positions) -> float:
    """Forward operations the served tokens need; bucket padding and idle
    slots are not work."""
    return (sum(prompt_flops(cfg, n) for n in prompt_lens)
            + sum(decode_flops(cfg, p) for p in decode_positions))


def kv_bytes_read(cfg: dict, decode_positions, page_size: int,
                  bytes_per_el: int = 2) -> float:
    """Bytes of K and V a decode step must read for rows at
    ``decode_positions``: on full layers every resident page of the row, on
    sliding layers the pages that cover the keys inside the window."""
    per_pos = 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * bytes_per_el
    layers = range(cfg["num_hidden_layers"])
    return float(sum(
        math.ceil(decode_pairs(cfg, i, p) / page_size) * page_size * per_pos
        for p in decode_positions for i in layers))
