"""Operations and bytes ``kimi-k2.5-ep32`` needs, from shapes alone
(``flops.py`` counts a GQA block and is kept for StarCoder2).  The peaks
stay in ``peaks.json`` (``flops.peaks_for``).

Count by hand at the published widths (hidden 7168; 64 heads; q rank 1536,
kv rank 512; nope 128, rope 64, v 128; dense FFN 18432; expert width 2048;
router 384, top 8, 12 experts held; vocabulary slice 20480; 1 dense + 6
expert layers), in multiply-adds (MAC; one MAC is 2 FLOP):

  attention matrices   W_qa 7168*1536 = 11,010,048;  W_qb 1536*64*192 =
                       18,874,368;  W_kva 7168*576 = 4,128,768;  W_kvb
                       512*64*256 = 8,388,608;  W_o 8192*7168 = 58,720,256
                                                          = 101,122,048
    (the absorbed path multiplies q_nope by W_kvb's key half, 64*128*512,
     and o_lat by its value half, 64*512*128: W_kvb's 8,388,608 again, so a
     decoded token costs the same projections as a prefilled one)
  dense FFN            3 * 7168*18432                     = 396,361,728
  shared expert        3 * 7168*2048                      =  44,040,192
  router               7168*384                           =   2,752,512
  routed experts held  8 * 12/384 = 0.25 assignments a token expected,
                       0.25 * 44,040,192                  =  11,010,048
  dense layer          101,122,048 + 396,361,728          = 497,483,776
  expert layer         101,122,048 + 44,040,192 + 2,752,512 + 11,010,048
                                                          = 158,924,800
  one token, 7 layers  497,483,776 + 6 * 158,924,800      = 1,451,032,576
                                                            (2.902 GFLOP)
  head                 7168*20480 = 146,800,640             (0.294 GFLOP)

  attention proper, a (query, key) pair in one layer:
    expanded  64 heads * (192 q.k + 128 p.v) = 20,480 MAC = 40,960 FLOP
    absorbed  64 heads * (576 q.k + 512 p.v) = 69,632 MAC = 139,264 FLOP
  a 2048-token prompt: 2048 * 2.902 G + 0.294 G + 7 * 40,960 * 2048*2049/2
                       = 5.943 T + 0.0003 T + 0.6016 T     = 6.545 TFLOP
  a token decoded at position 1900: 2.902 G + 0.294 G + 7 * 139,264 * 1901
                                                           = 5.049 GFLOP
"""

from __future__ import annotations


def published_experts(cfg: dict) -> int:
    return int(cfg.get("published", {}).get("n_routed_experts",
                                            cfg["n_routed_experts"]))


def attention_macs(cfg: dict) -> int:
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    qr, kvr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    return (h * qr + qr * heads * (nope + rope) + h * (kvr + rope)
            + kvr * heads * (nope + vd) + heads * vd * h)


def expert_macs(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def held_assignments_per_token(cfg: dict) -> float:
    """Expected token-to-expert assignments that fall on a held expert."""
    return (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
            / published_experts(cfg))


def layer_macs(cfg: dict, i: int) -> float:
    """Matrix multiply-adds one token needs in layer ``i``."""
    h = cfg["hidden_size"]
    if i < cfg["first_k_dense_replace"]:
        return attention_macs(cfg) + 3 * h * cfg["intermediate_size"]
    return (attention_macs(cfg) + cfg["n_shared_experts"] * expert_macs(cfg)
            + h * published_experts(cfg)
            + held_assignments_per_token(cfg) * expert_macs(cfg))


def token_macs(cfg: dict) -> float:
    return sum(layer_macs(cfg, i) for i in range(cfg["num_hidden_layers"]))


def head_macs(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def expanded_pair_flops(cfg: dict) -> int:
    return 2 * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"])


def absorbed_pair_flops(cfg: dict) -> int:
    return 2 * cfg["num_attention_heads"] * (
        2 * cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])


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
