"""Operations and bytes ``ling-3.0-flash-ep8`` needs, from shapes alone.  The
peaks stay in ``peaks.json`` (``flops.peaks_for``).

Count by hand at the published widths (hidden 2560; 32 heads; KDA d_k = d_v
128, convolution 4; MLA nope 128, rope 64, v 128, kv rank 512, no query
latent; dense FFN 6144; expert and shared-expert width 768; router 512, top
8 in 4 of 8 groups, 64 experts held; vocabulary slice 19,648; layers 0-7:
two dense KDA layers, five expert KDA layers, one expert MLA layer), in
multiply-adds (MAC; one MAC is 2 FLOP):

  KDA mixer            W_q, W_k, W_v, W_f 2560*4096 = 10,485,760 each; W_b,
                       W_og 2560*32 = 81,920 each; W_o 4096*2560
                                                          = 52,592,640
  MLA mixer            W_q 2560*6144 = 15,728,640; W_kva 2560*576 =
                       1,474,560; W_kvb 512*8192 = 4,194,304; W_g 2560*32 =
                       81,920; W_o 4096*2560 = 10,485,760  = 31,965,184
    (the absorbed decode multiplies q_nope by W_kvb's key half and o_lat by
     its value half: W_kvb's 4,194,304 again, so a decoded token costs the
     same projections as a prefilled one)
  dense FFN            3 * 2560*6144                      = 47,185,920
  shared expert        3 * 2560*768                       =  5,898,240
  router               2560*512                           =  1,310,720
  routed experts held  8 * 64/512 = 1 assignment a token expected
                                                          =  5,898,240
  dense KDA layer      52,592,640 + 47,185,920            = 99,778,560
  expert KDA layer     52,592,640 + 13,107,200            = 65,699,840
  expert MLA layer     31,965,184 + 13,107,200            = 45,072,384
  one token, 8 layers  2 * 99,778,560 + 5 * 65,699,840 + 45,072,384
                                                          = 573,128,704
                                                            (1.146 GFLOP)
  head                 2560*19648 = 50,298,880              (0.101 GFLOP)

  The KDA rule, counted apart (``kda_flops``): a token's step touches every
  state entry of every head, 32 * 128 * 128 = 524,288 a layer, with the
  decay (a multiply), ``S~^T k`` (a MAC), ``S~^T q`` (a MAC) and ``S~ + k
  w^T`` (a MAC): 7 FLOP an entry, 3,670,016 a layer, 25.7 MFLOP over the 7
  KDA layers (2.1% of the token's 1.146 GFLOP).  The chunked form a prefill
  runs does more (``chunk_flops``) and moves the state once a chunk
  (``chunk_bytes``).

  attention proper, a (query, key) pair in the MLA layer:
    expanded  32 heads * (192 q.k + 128 p.v) = 10,240 MAC = 20,480 FLOP
    absorbed  32 heads * (576 q.k + 512 p.v) = 34,816 MAC = 69,632 FLOP
  a 2048-token prompt: 2048 * (1.146 G + 0.0257 G) + 0.101 G + 20,480 *
                       2048*2049/2                      = 2.443 TFLOP
  a token decoded at position 2000: 1.146 G + 0.0257 G + 0.101 G + 69,632 *
                       2001                             = 1.412 GFLOP

Bytes of a decode step's KDA state: every decoded lane's float32 ``S`` read
AND written once (``kda_step_bytes``): 7 layers * 32 * 128 * 128 * 4 B =
14,680,064 B a lane each way (``kda_state_bytes_per_slot``), 7.52 GB at 256
lanes.
"""

from __future__ import annotations

from benchmark import reference_ling as ref

KDA_FLOPS_PER_ENTRY = 7
F32 = 4
# positions a chunk of the program's WY form (helpers/delta_rule.py)
CHUNK = 64


def n_kda(cfg: dict) -> int:
    return sum(not ref.is_mla(cfg, i) for i in range(cfg["num_hidden_layers"]))


def kda_macs(cfg: dict) -> int:
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    _, _, w = ref.kda_widths(cfg)
    return h * (4 * w + 2 * heads) + w * h


def mla_macs(cfg: dict) -> int:
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    kvr, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    nope, vd = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    return (h * heads * (nope + rope) + h * (kvr + rope)
            + kvr * heads * (nope + vd) + h * heads + heads * vd * h)


def expert_macs(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def held_assignments_per_token(cfg: dict) -> float:
    """Expected token-to-expert assignments that fall on a held expert."""
    return (cfg["num_experts_per_tok"] * cfg["num_experts"]
            / ref.router_width(cfg))


def layer_macs(cfg: dict, i: int) -> float:
    """Matrix multiply-adds one token needs in layer ``i``."""
    h = cfg["hidden_size"]
    mixer = mla_macs(cfg) if ref.is_mla(cfg, i) else kda_macs(cfg)
    if ref.is_dense(cfg, i):
        return mixer + 3 * h * cfg["intermediate_size"]
    shared = 3 * h * (cfg["moe_shared_expert_intermediate_size"]
                      * cfg["num_shared_experts"])
    return (mixer + shared + h * ref.router_width(cfg)
            + held_assignments_per_token(cfg) * expert_macs(cfg))


def token_macs(cfg: dict) -> float:
    return sum(layer_macs(cfg, i) for i in range(cfg["num_hidden_layers"]))


def head_macs(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def state_entries(cfg: dict) -> int:
    """Entries of one KDA layer's state a row: heads * d_k * d_v."""
    heads, d, _ = ref.kda_widths(cfg)
    return heads * d * d


def kda_flops(cfg: dict) -> int:
    """The KDA rule's operations of one token, every KDA layer, in its
    recurrent form (not counted among the matrix products)."""
    return n_kda(cfg) * KDA_FLOPS_PER_ENTRY * state_entries(cfg)


def n_mla(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - n_kda(cfg)


def expanded_pair_flops(cfg: dict) -> int:
    return 2 * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"])


def absorbed_pair_flops(cfg: dict) -> int:
    return 2 * cfg["num_attention_heads"] * (
        2 * cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])


def prompt_flops(cfg: dict, n: int) -> float:
    """Forward operations of one ``n``-token prompt prefilled whole (the MLA
    layer by the expanded path), and the head once for its first token."""
    return (n * (2 * token_macs(cfg) + kda_flops(cfg))
            + 2 * head_macs(cfg)
            + n_mla(cfg) * expanded_pair_flops(cfg) * n * (n + 1) / 2)


def decode_flops(cfg: dict, position: int) -> float:
    """Forward operations of one token decoded at ``position`` (the MLA
    layer by the absorbed path over ``position + 1`` cached rows)."""
    return (2 * (token_macs(cfg) + head_macs(cfg)) + kda_flops(cfg)
            + n_mla(cfg) * absorbed_pair_flops(cfg) * (position + 1))


def serve_forward_flops(cfg: dict, prompt_lens, decode_positions) -> float:
    """Forward operations the served tokens need; bucket padding and idle
    slots are not work."""
    return (sum(prompt_flops(cfg, n) for n in prompt_lens)
            + sum(decode_flops(cfg, p) for p in decode_positions))


def kda_state_bytes_per_slot(cfg: dict) -> int:
    """A slot's float32 KDA state over every KDA layer."""
    return n_kda(cfg) * state_entries(cfg) * F32


def kda_step_bytes(cfg: dict, lanes: int) -> int:
    """KDA state bytes a decode step needs for ``lanes`` decoded lanes: each
    row's ``S`` read and written once."""
    return 2 * lanes * kda_state_bytes_per_slot(cfg)


def chunk_flops(cfg: dict, n: int, chunk: int = CHUNK) -> float:
    """Operations of the chunked (WY) form over an ``n``-token prompt, every
    KDA layer: a chunk of ``c`` positions a head forms its decayed ``K K^T``
    and ``Q K^T`` (c^2 d_k MAC each, by sub-blocks against the whole chunk),
    the triangular solve for ``W`` and ``U`` (c^2 (d_k + d_v) / 2), ``Q K^T``
    times ``U - W S`` (c^2 d_v / 2) and the three state products ``W S``,
    ``Q S``, ``K^T (U - W S)`` (c d_k d_v each)."""
    heads, d, _ = ref.kda_widths(cfg)
    full, rest = divmod(n, chunk)
    macs = 0
    for c, count in ((chunk, full), (rest, 1 if rest else 0)):
        macs += count * (2 * c * c * d + c * c * 2 * d / 2 + c * c * d / 2
                         + 3 * c * d * d)
    return 2.0 * macs * heads * n_kda(cfg)


def chunk_bytes(cfg: dict, n: int, chunk: int = CHUNK) -> float:
    """Bytes the chunked form needs over an ``n``-token prompt, every KDA
    layer: ``q``, ``k``, ``v``, the per-channel ``g`` and ``beta`` read and
    ``o`` written in float32 once, and the state read and written once a
    chunk."""
    heads, _, w = ref.kda_widths(cfg)
    chunks = -(-n // chunk)
    per_token = F32 * (5 * w + heads)
    return n_kda(cfg) * (n * per_token
                         + chunks * 2 * state_entries(cfg) * F32)
