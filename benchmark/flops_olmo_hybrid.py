"""Operations and bytes ``olmo-hybrid-7b-pp4`` needs, from shapes alone.  The
peaks stay in ``peaks.json`` (``flops.peaks_for``).

Count by hand at the published widths (hidden 3840; linear layers: 30 heads,
d_k 96, d_v 192, convolution 4; full layers: 30 query and 30 kv heads x 128;
FFN 11008; vocabulary 100352, untied; 6 linear + 2 full layers), in
multiply-adds (MAC; one MAC is 2 FLOP):

  linear mixer, matrix products  W_q, W_k 3840*2880 = 11,059,200 each; W_v,
                       W_g 3840*5760 = 22,118,400 each; W_a, W_b 3840*30 =
                       115,200 each; W_o 5760*3840 = 22,118,400  = 88,704,000
  full mixer           W_q, W_k, W_v, W_o 3840*3840               = 58,982,400
  SwiGLU               3 * 3840*11008                             = 126,812,160
  linear layer         88,704,000 + 126,812,160                   = 215,516,160
  full layer           58,982,400 + 126,812,160                   = 185,794,560
  one token, 8 layers  6 * 215,516,160 + 2 * 185,794,560          = 1,664,686,080
                                                                 (3.329 GFLOP)
  head                 3840*100352 = 385,351,680                  (0.771 GFLOP)

  The delta rule, counted apart (``delta_flops``): a token's step touches
  every state entry of every head, 30 * 96 * 192 = 552,960 a layer, with
  ``S^T k`` (a MAC), ``S^T q`` (a MAC) and ``a S + k w^T`` (a multiply and a
  MAC): 7 FLOP an entry, 3,870,720 a layer, 23.2 MFLOP over the 6 linear
  layers (0.7% of the token's 3.33 GFLOP).  The chunked form a prefill runs
  does more (``chunk_flops``: the chunk's own ``K K^T``, ``Q K^T``, the
  triangular solve and ``Q K^T (U - W S)`` on top of the three state
  products) and moves the state once a chunk (``chunk_bytes``).

  attention proper, a (query, key) pair in one full layer: 30 heads * (128
  q.k + 128 p.v) = 7,680 MAC = 15,360 FLOP
  a 512-token prompt: 512 * (3.329 G + 0.0232 G) + 0.771 G + 2 * 15,360 *
                      512*513/2                              = 1.7213 TFLOP
  a token decoded at position 700: 3.329 G + 0.0232 G + 0.771 G + 2 *
                      15,360 * 701                           = 4.1448 GFLOP

Parameters (``parameter_count``): a linear mixer's products above + its
convolutions (2880 + 2880 + 5760) * 4 = 46,080 + A_log 30 + dt_bias 30 + the
gated norm 192 = 88,750,332; a full mixer's + its q and k norms 7,680 =
58,990,080; + SwiGLU + two norm gains 7,680: 215,570,172 a linear layer,
185,809,920 a full layer; 6 and 2 of them 1,665,040,872; + the embedding and
the head 385,351,680 each + the final norm 3,840 = 2,435,748,072.

Bytes of a decode step: the weights once (``weight_bytes``: 2 B a parameter,
4.87 GB); every decoded lane's state read AND written (``state_bytes_per_slot``:
6 layers * (96 * 5760 * 4 + 3 * 11520 * 2) = 13,685,760 B, of which the
float32 state ``delta_state_bytes_per_slot`` 13,271,040 B); the live KV pages
(``kv_bytes_per_token``: 2 layers * K and V * 30 heads * 128 * 2 B = 30,720 B
a cached token; ``kv_bytes_read`` counts whole pages, as the paged kernel
reads them).
"""

from __future__ import annotations

from benchmark import reference_olmo_hybrid as ref

DELTA_FLOPS_PER_ENTRY = 7
BYTES_PER_PARAM = 2
F32 = 4
# positions a chunk of the program's WY form (helpers/delta_rule.py)
CHUNK = 64


def n_layers(cfg: dict):
    """(linear layers, full layers)."""
    full = sum(ref.is_full(cfg, i) for i in range(cfg["num_hidden_layers"]))
    return cfg["num_hidden_layers"] - full, full


def linear_macs(cfg: dict) -> int:
    h = cfg["hidden_size"]
    heads, _, _, qk, vw = ref.linear_widths(cfg)
    return h * (2 * qk + 2 * vw + 2 * heads) + vw * h


def full_macs(cfg: dict) -> int:
    h, hd = cfg["hidden_size"], ref.head_dim(cfg)
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    return h * q + 2 * h * kv + q * h


def ffn_macs(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def head_macs(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def token_macs(cfg: dict) -> int:
    """Matrix-product multiply-adds of one token through every layer."""
    lin, full = n_layers(cfg)
    return (lin * (linear_macs(cfg) + ffn_macs(cfg))
            + full * (full_macs(cfg) + ffn_macs(cfg)))


def state_entries(cfg: dict) -> int:
    """Entries of one linear layer's state a row: heads * d_k * d_v."""
    heads, dk, dv, _, _ = ref.linear_widths(cfg)
    return heads * dk * dv


def delta_flops(cfg: dict) -> int:
    """The delta rule's operations of one token, every linear layer, in its
    recurrent form (not counted among the matrix products)."""
    return n_layers(cfg)[0] * DELTA_FLOPS_PER_ENTRY * state_entries(cfg)


def pair_flops(cfg: dict) -> int:
    """One (query, key) pair in one full layer, in FLOP."""
    return 2 * cfg["num_attention_heads"] * 2 * ref.head_dim(cfg)


def prompt_flops(cfg: dict, n: int) -> float:
    """Forward operations of one ``n``-token prompt prefilled whole, and the
    head once for its first token."""
    return (n * (2 * token_macs(cfg) + delta_flops(cfg))
            + 2 * head_macs(cfg)
            + n_layers(cfg)[1] * pair_flops(cfg) * n * (n + 1) / 2)


def decode_flops(cfg: dict, position: int) -> float:
    """Forward operations of one token decoded at ``position`` (it attends
    to ``position + 1`` cached rows in the full layers)."""
    return (2 * (token_macs(cfg) + head_macs(cfg)) + delta_flops(cfg)
            + n_layers(cfg)[1] * pair_flops(cfg) * (position + 1))


def serve_forward_flops(cfg: dict, prompt_lens, decode_positions) -> float:
    """Forward operations the served tokens need; bucket padding and idle
    slots are not work."""
    return (sum(prompt_flops(cfg, n) for n in prompt_lens)
            + sum(decode_flops(cfg, p) for p in decode_positions))


def parameter_count(cfg: dict) -> int:
    h = cfg["hidden_size"]
    heads, _, dv, qk, vw = ref.linear_widths(cfg)
    conv = (2 * qk + vw) * cfg["linear_conv_kernel_dim"]
    mixer = linear_macs(cfg) + conv + 2 * heads + dv
    full = (full_macs(cfg) + cfg["num_attention_heads"] * ref.head_dim(cfg)
            + cfg["num_key_value_heads"] * ref.head_dim(cfg))
    lin, nfull = n_layers(cfg)
    return (lin * (mixer + ffn_macs(cfg) + 2 * h)
            + nfull * (full + ffn_macs(cfg) + 2 * h)
            + 2 * head_macs(cfg) + h)


def weight_bytes(cfg: dict) -> int:
    """What a decode step reads of weights: every parameter once."""
    return BYTES_PER_PARAM * parameter_count(cfg)


def delta_state_bytes_per_slot(cfg: dict) -> int:
    """A slot's float32 delta-rule state over every linear layer."""
    return n_layers(cfg)[0] * state_entries(cfg) * F32


def state_bytes_per_slot(cfg: dict) -> int:
    """A slot's recurrent state over every linear layer: ``S`` in float32
    and the convolutions' tail in the stored dtype."""
    _, _, _, qk, vw = ref.linear_widths(cfg)
    tail = (cfg["linear_conv_kernel_dim"] - 1) * (2 * qk + vw)
    return (delta_state_bytes_per_slot(cfg)
            + n_layers(cfg)[0] * tail * BYTES_PER_PARAM)


def delta_step_bytes(cfg: dict, lanes: int) -> int:
    """Delta-rule state bytes a decode step needs for ``lanes`` decoded
    lanes: each row's ``S`` read and written once."""
    return 2 * lanes * delta_state_bytes_per_slot(cfg)


def kv_bytes_per_token(cfg: dict) -> int:
    return (n_layers(cfg)[1] * 2 * cfg["num_key_value_heads"]
            * ref.head_dim(cfg) * BYTES_PER_PARAM)


def kv_bytes_read(cfg: dict, decode_positions, page_size: int) -> float:
    """Bytes of K and V a decode step must read for rows at
    ``decode_positions``: every resident page of the row on the full layers
    alone (the linear layers hold no pages)."""
    return float(sum(-(-(p + 1) // page_size) * page_size
                     * kv_bytes_per_token(cfg) for p in decode_positions))


def chunk_flops(cfg: dict, n: int, chunk: int = CHUNK) -> float:
    """Operations of the chunked (WY) form over an ``n``-token prompt, every
    linear layer: a chunk of ``c`` positions a head does ``K K^T`` and ``Q
    K^T`` (c^2 d_k MAC each), the triangular solve for ``W`` and ``U``
    (c^2 (d_k + d_v) / 2), ``Q K^T`` times ``U - W S`` (c^2 d_v / 2) and the
    three state products ``W S``, ``Q S``, ``K^T (U - W S)`` (c d_k d_v
    each)."""
    heads, dk, dv, _, _ = ref.linear_widths(cfg)
    full, rest = divmod(n, chunk)
    macs = 0
    for c, count in ((chunk, full), (rest, 1 if rest else 0)):
        macs += count * (2 * c * c * dk + c * c * (dk + dv) / 2
                         + c * c * dv / 2 + 3 * c * dk * dv)
    return 2.0 * macs * heads * n_layers(cfg)[0]


def chunk_bytes(cfg: dict, n: int, chunk: int = CHUNK) -> float:
    """Bytes the chunked form needs over an ``n``-token prompt, every linear
    layer: ``q``, ``k``, ``v``, ``g`` and ``beta`` read and ``o`` written in
    float32 once, and the state read and written once a chunk."""
    heads, _, _, qk, vw = ref.linear_widths(cfg)
    chunks = -(-n // chunk)
    per_token = F32 * (2 * qk + vw + 2 * heads + vw)
    return n_layers(cfg)[0] * (n * per_token
                               + chunks * 2 * state_entries(cfg) * F32)
