"""Operations and bytes ``jamba2-3b`` needs, from shapes alone.  The peaks
stay in ``peaks.json`` (``flops.peaks_for``).

Count by hand at the published widths (hidden 2560; Mamba: 5120 channels,
16 state columns, convolution 4, dt rank 160; attention 20 query heads over 1
kv head x 128; FFN 8192; vocabulary 65536, tied; 26 Mamba + 2 attention
layers), in multiply-adds (MAC; one MAC is 2 FLOP):

  Mamba mixer, matrix products   in_proj 2560*10240 = 26,214,400;  x_proj
                       5120*192 = 983,040;  dt_proj 160*5120 = 819,200;
                       out_proj 5120*2560 = 13,107,200     =  41,123,840
  attention matrices   W_q 2560*2560 = 6,553,600;  W_k, W_v 2560*128 =
                       327,680 each;  W_o 6,553,600          =  13,762,560
  SwiGLU               3 * 2560*8192                         =  62,914,560
  Mamba layer          41,123,840 + 62,914,560               = 104,038,400
  attention layer      13,762,560 + 62,914,560               =  76,677,120
  one token, 28 layers 26 * 104,038,400 + 2 * 76,677,120     = 2,858,352,640
                                                            (5.717 GFLOP)
  head                 2560*65536 = 167,772,160             (0.336 GFLOP)

  NOT matrix products, counted apart (``scan_flops``): the recurrence of a
  Mamba layer for one token touches 5120 * 16 = 81,920 state entries, about
  9 elementwise operations each (dt A, exp, the decay's multiply, dt x B's
  two multiplies and the add, h C's multiply and add, the gate's share):
  737,280 FLOP a token a layer, 19.2 MFLOP over 26 layers (0.3% of the
  token's 5.7 GFLOP: the vector unit's time, not its operation count, is
  what the scan costs); the depthwise convolution 5120 * 4 MAC.

  attention proper, a (query, key) pair in one layer: 20 heads * (128 q.k +
  128 p.v) = 5,120 MAC = 10,240 FLOP
  a 512-token prompt: 512 * (5.717 G + 0.0192 G) + 0.336 G + 2 * 10,240 *
                      512*513/2                              = 2.940 TFLOP
  a token decoded at position 700: 5.717 G + 0.0192 G + 0.336 G + 2 * 10,240
                      * 701                                  = 6.086 GFLOP

Parameters (``parameter_count``): a Mamba mixer's products above + conv1d
5120*4 + 5120 + the three inner norms 192 + dt_proj's bias 5120 + A_log
81,920 + D 5120 = 41,241,792; + SwiGLU + two norm gains 5,120 =
104,161,472 a Mamba layer, 76,682,240 an attention layer; 26 and 2 of them
2,861,562,752; + the embedding 167,772,160 (tied, once) + the final norm
2,560 = 3,029,337,472.

Bytes of a decode step: the weights once (``weight_bytes``: 2 B a parameter,
the tied head held a second time, 6.39 GB); every decoded lane's state read
AND written (``state_bytes_per_slot``: 26 layers * (16*5120*4 + 3*5120*2) =
9,318,400 B); the live KV pages (``kv_bytes_per_token``: 2 layers * K and V
* 1 head * 128 * 2 B = 1,024 B a cached token).
"""

from __future__ import annotations

from benchmark import reference_jamba as ref

SCAN_FLOPS_PER_ENTRY = 9
BYTES_PER_PARAM = 2


def n_layers(cfg: dict):
    """(Mamba layers, attention layers)."""
    attn = sum(ref.is_attention(cfg, i)
               for i in range(cfg["num_hidden_layers"]))
    return cfg["num_hidden_layers"] - attn, attn


def d_inner(cfg: dict) -> int:
    return cfg["mamba_expand"] * cfg["hidden_size"]


def mamba_macs(cfg: dict) -> int:
    h, d = cfg["hidden_size"], d_inner(cfg)
    r, n = cfg["mamba_dt_rank"], cfg["mamba_d_state"]
    return h * 2 * d + d * (r + 2 * n) + r * d + d * h


def attention_macs(cfg: dict) -> int:
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
    mamba, attn = n_layers(cfg)
    return (mamba * (mamba_macs(cfg) + ffn_macs(cfg))
            + attn * (attention_macs(cfg) + ffn_macs(cfg)))


def scan_flops(cfg: dict) -> int:
    """The recurrences' elementwise operations of one token, every Mamba
    layer (not matrix products: counted apart)."""
    return (n_layers(cfg)[0] * SCAN_FLOPS_PER_ENTRY * d_inner(cfg)
            * cfg["mamba_d_state"])


def pair_flops(cfg: dict) -> int:
    """One (query, key) pair in one attention layer, in FLOP."""
    return 2 * cfg["num_attention_heads"] * 2 * ref.head_dim(cfg)


def prompt_flops(cfg: dict, n: int) -> float:
    """Forward operations of one ``n``-token prompt prefilled whole, and the
    head once for its first token."""
    return (n * (2 * token_macs(cfg) + scan_flops(cfg))
            + 2 * head_macs(cfg)
            + n_layers(cfg)[1] * pair_flops(cfg) * n * (n + 1) / 2)


def decode_flops(cfg: dict, position: int) -> float:
    """Forward operations of one token decoded at ``position`` (it attends
    to ``position + 1`` cached rows in the attention layers)."""
    return (2 * (token_macs(cfg) + head_macs(cfg)) + scan_flops(cfg)
            + n_layers(cfg)[1] * pair_flops(cfg) * (position + 1))


def serve_forward_flops(cfg: dict, prompt_lens, decode_positions) -> float:
    """Forward operations the served tokens need; bucket padding and idle
    slots are not work."""
    return (sum(prompt_flops(cfg, n) for n in prompt_lens)
            + sum(decode_flops(cfg, p) for p in decode_positions))


def parameter_count(cfg: dict) -> int:
    """The published model's parameters, the tied embedding once."""
    h, d = cfg["hidden_size"], d_inner(cfg)
    r, n, k = cfg["mamba_dt_rank"], cfg["mamba_d_state"], cfg["mamba_d_conv"]
    mixer = mamba_macs(cfg) + d * k + d + (r + 2 * n) + d + d * n + d
    mamba, attn = n_layers(cfg)
    return (mamba * (mixer + ffn_macs(cfg) + 2 * h)
            + attn * (attention_macs(cfg) + ffn_macs(cfg) + 2 * h)
            + head_macs(cfg) + h)


def weight_bytes(cfg: dict) -> int:
    """What a decode step reads of weights: every parameter, and the tied
    head's second leaf."""
    return BYTES_PER_PARAM * (parameter_count(cfg) + head_macs(cfg))


def state_bytes_per_slot(cfg: dict) -> int:
    """A slot's recurrent state over every Mamba layer: ``h`` in float32 and
    the convolution's tail in the stored dtype."""
    d = d_inner(cfg)
    return n_layers(cfg)[0] * (cfg["mamba_d_state"] * d * 4
                               + (cfg["mamba_d_conv"] - 1) * d
                               * BYTES_PER_PARAM)


def state_step_bytes(cfg: dict, lanes: int) -> int:
    """State bytes a decode step moves for ``lanes`` decoded lanes: each
    row read and written."""
    return 2 * lanes * state_bytes_per_slot(cfg)


def kv_bytes_per_token(cfg: dict) -> int:
    return (n_layers(cfg)[1] * 2 * cfg["num_key_value_heads"]
            * ref.head_dim(cfg) * BYTES_PER_PARAM)
