"""Operations BERT-base's forward and backward passes require, from shapes.

Matrix products only (2 per multiply-add), nothing recomputed, padding
positions not counted: ``sum_n`` is the number of real tokens and
``sum_n2`` the sum over sequences of their real length squared.  Backward
costs twice the forward.  The LM head is counted on the masked share of
the tokens, which is all the loss needs.
"""


def forward_per_token(cfg):
    d, f = cfg["encoder_embed_dim"], cfg["encoder_ffn_embed_dim"]
    layers, V = cfg["encoder_layers"], cfg["vocab_size"]
    per_layer = 2 * d * 3 * d + 2 * d * d + 2 * 2 * d * f
    head = 2 * d * d + 2 * d * V
    return layers * per_layer, head


def forward_per_pair(cfg):
    """Per (query, key) pair of one sequence: scores and the weighted sum."""
    return cfg["encoder_layers"] * 2 * 2 * cfg["encoder_embed_dim"]


def train_flops(cfg, sum_n, sum_n2, mask_prob):
    body, head = forward_per_token(cfg)
    forward = sum_n * (body + mask_prob * head) + sum_n2 * forward_per_pair(cfg)
    return 3.0 * forward
