"""Operations ``xing4.0-29b-a4b-pp6`` needs, from shapes alone: ``flops_k2``'s
count of the DeepSeek-V3 block (imported: latent attention, dense and shared
FFN, router, the held experts' share of the assignments — here all of them
— and the head) plus, per token and sub-layer, the manifold-constrained
hyper-connections.  The peaks stay in ``peaks.json`` (``flops.peaks_for``).

Count by hand at the published widths (hidden 3584; 32 heads; q rank 768,
kv rank 512; nope 128, rope 64, v 128; dense FFN 9216; expert width 1024;
router 64, top 4, all 64 held; vocabulary 131072; 4 streams; 2 dense + 5
expert layers), in multiply-adds (MAC; one MAC is 2 FLOP):

  attention matrices   W_qa 3584*768 = 2,752,512;  W_qb 768*32*192 =
                       4,718,592;  W_kva 3584*576 = 2,064,384;  W_kvb
                       512*32*256 = 4,194,304;  W_o 4096*3584 = 14,680,064
                                                          =  28,409,856
  dense FFN            3 * 3584*9216                      =  99,090,432
  shared expert        3 * 3584*1024                      =  11,010,048
  router               3584*64                            =     229,376
  routed experts held  4 * 64/64 = 4 assignments a token,
                       4 * 11,010,048                     =  44,040,192
  hyper-connections, one sub-layer, in FLOP: phi 2 * 14,336*24 = 688,128;
                       u = sum_j H_pre[j] X_j 2 * 4*3584 = 28,672;
                       X' = H_res X + H_post y 2 * 4*4*3584 + 2 * 4*3584
                       = 143,360;  Sinkhorn 20 * 2 * 16 = 640 (counted,
                       negligible)                        = 860,800 FLOP
                       two a layer: 1,721,600 FLOP        =     860,800 MAC
  dense layer          28,409,856 + 99,090,432 + 860,800  = 128,361,088
  expert layer         28,409,856 + 11,010,048 + 229,376 + 44,040,192
                       + 860,800                          =  84,550,272
  one token, 7 layers  2 * 128,361,088 + 5 * 84,550,272   = 679,473,536
                                                            (1.359 GFLOP)
  head                 3584*131072 = 469,762,048            (0.940 GFLOP)

  attention proper, a (query, key) pair in one layer:
    expanded  32 heads * (192 q.k + 128 p.v) = 10,240 MAC = 20,480 FLOP
    absorbed  32 heads * (576 q.k + 512 p.v) = 34,816 MAC = 69,632 FLOP
  a 1024-token prompt: 1024 * 1.359 G + 0.940 G + 7 * 20,480 * 1024*1025/2
                       = 1.3916 T + 0.0009 T + 0.0752 T     = 1.468 TFLOP
  a token decoded at position 1500: 1.359 G + 0.940 G + 7 * 69,632 * 1501
                                                            = 3.030 GFLOP
"""

from __future__ import annotations

from benchmark import flops_k2
from benchmark.flops_k2 import (  # noqa: F401  (the count's surface)
    absorbed_pair_flops, attention_macs, expanded_pair_flops, expert_macs,
    head_macs, held_assignments_per_token,
)


def mhc_sublayer_flops(cfg: dict) -> int:
    """One sub-layer's hyper-connections for one token, in FLOP."""
    n, c = cfg["hc_mult"], cfg["hidden_size"]
    return (2 * n * c * (2 * n + n * n)          # m = (vec(X) r) phi
            + 2 * n * c                          # u = sum_j H_pre[j] X_j
            + 2 * n * n * c + 2 * n * c          # H_res X + H_post y
            + cfg["hc_sinkhorn_iters"] * 2 * n * n)


def layer_macs(cfg: dict, i: int) -> float:
    """Multiply-adds one token needs in layer ``i``: the block's, and its
    two sub-layers' hyper-connections (FLOP / 2)."""
    return flops_k2.layer_macs(cfg, i) + mhc_sublayer_flops(cfg)


def token_macs(cfg: dict) -> float:
    return sum(layer_macs(cfg, i) for i in range(cfg["num_hidden_layers"]))


def prompt_flops(cfg: dict, n: int) -> float:
    """Forward operations of one ``n``-token prompt prefilled whole by the
    expanded path, and the head once for its first token."""
    pairs = n * (n + 1) / 2
    return (2 * (n * token_macs(cfg) + head_macs(cfg))
            + cfg["num_hidden_layers"] * expanded_pair_flops(cfg) * pairs)


def decode_flops(cfg: dict, position: int) -> float:
    """Forward operations of one token decoded at ``position`` by the
    absorbed path (it attends to ``position + 1`` cached rows)."""
    return (2 * (token_macs(cfg) + head_macs(cfg))
            + cfg["num_hidden_layers"] * absorbed_pair_flops(cfg)
            * (position + 1))


def serve_forward_flops(cfg: dict, prompt_lens, decode_positions) -> float:
    """Forward operations the served tokens need; bucket padding and idle
    slots are not work."""
    return (sum(prompt_flops(cfg, n) for n in prompt_lens)
            + sum(decode_flops(cfg, p) for p in decode_positions))
