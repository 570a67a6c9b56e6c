"""Operations Uni-Mol's forward and backward passes require, from shapes.

Matrix products only, nothing recomputed, padding not counted: ``sum_n``
real tokens (atoms plus the two specials), ``sum_n2`` the sum over
molecules of their real length squared.  Besides the encoder, every pair
of positions passes the Gaussian-basis projection (K -> K -> H) on the way
in, and the coordinate head (H -> H -> 1) and the distance head
(H -> H -> 1) on the way out.  Backward costs twice the forward.
"""


def forward_per_token(cfg):
    d, f = cfg["encoder_embed_dim"], cfg["encoder_ffn_embed_dim"]
    layers, V = cfg["encoder_layers"], cfg["vocab_size"]
    per_layer = 2 * d * 3 * d + 2 * d * d + 2 * 2 * d * f
    head = 2 * d * d + 2 * d * V
    return layers * per_layer + head


def forward_per_pair(cfg):
    d, H, K = (cfg["encoder_embed_dim"], cfg["encoder_attention_heads"],
               cfg["gaussian_kernels"])
    attention = cfg["encoder_layers"] * 2 * 2 * d
    gbf_proj = 2 * K * K + 2 * K * H
    pair_heads = 2 * (2 * H * H + 2 * H)
    return attention + gbf_proj + pair_heads


def train_flops(cfg, sum_n, sum_n2, mask_prob):
    del mask_prob  # the atom head runs on every position
    return 3.0 * (sum_n * forward_per_token(cfg) + sum_n2 * forward_per_pair(cfg))
