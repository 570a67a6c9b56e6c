"""Operations the forward and backward passes of the held share of
``mellum2_12b`` require, from shapes.

Matrix products only (2 per multiply-add), nothing recomputed (the layers'
rematerialization and the loss's chunks compute forwards twice: not
counted), backward twice the forward.  Per layer: the four attention
projections of the heads held; scores and weighted sum over the keys a
query may SEE (``min(i + 1, window)`` of them for the query at ``i``: the
window on a sliding layer, the row on a full one), not over what the
kernels' blocks compute; the router over all experts; the routed experts
at their EXPECTATION, ``num_experts_per_tok x held / num_experts`` (token,
expert) pairs a token (8 x 16 / 64 = 2), each through gate, up and down:
exact for routing that is even over the experts (the configuration's
``router_balancing`` holds this share's pairs to within half a percent
of it) and what a traced run's ``unicore:moe_route`` pairs can be held
against.  Head: ``hidden x
vocabulary`` (the held columns) on the predicted share of the tokens.
"""

import json


def held(cfg):
    n = int(cfg.get("attention_shares") or 1)
    kinds = cfg["layer_types"]
    kinds = json.loads(kinds) if isinstance(kinds, str) else kinds
    return dict(
        kinds=kinds[:int(cfg.get("layers_held") or cfg["num_hidden_layers"])],
        heads=cfg["num_attention_heads"] // n,
        kv_heads=max(1, cfg["num_key_value_heads"] // n),
        experts=int(cfg.get("num_experts_held") or cfg["num_experts"]),
    )


def visible_keys(length, window=None):
    """Summed over the queries of one row of ``length`` positions: the
    keys each may see, ``min(i + 1, window)``."""
    n, w = int(length), int(window or length)
    w = min(n, w)
    return w * (w + 1) // 2 + (n - w) * w


def forward_per_token(cfg):
    d, D, f = cfg["hidden_size"], cfg["head_dim"], cfg["moe_intermediate_size"]
    mine = held(cfg)
    H, KV = mine["heads"], mine["kv_heads"]
    attn = 2 * d * (H + 2 * KV) * D + 2 * H * D * d
    pairs = cfg["num_experts_per_tok"] * mine["experts"] / cfg["num_experts"]
    moe = 2 * d * cfg["num_experts"] + pairs * (2 * d * 2 * f + 2 * f * d)
    return len(mine["kinds"]) * (attn + moe), 2 * d * cfg["vocab_size"]


def forward_per_key(cfg):
    """Per (query, visible key) pair of one layer: the score and the
    weighted sum, over the held query heads."""
    return 2 * 2 * held(cfg)["heads"] * cfg["head_dim"]


def row_keys(cfg, length):
    """The visible (query, key) pairs of one row, summed over the held
    layers: ``(sliding layers', full layers')``."""
    kinds = held(cfg)["kinds"]
    sliding = kinds.count("sliding_attention")
    return (sliding * visible_keys(length, cfg["sliding_window"]),
            (len(kinds) - sliding) * visible_keys(length))


def train_flops(cfg, sum_n, sum_n2, mask_prob):
    """``sum_n`` real tokens in rows whose squared lengths sum to
    ``sum_n2``: the cell's rows are all one length, ``sum_n2 / sum_n``."""
    body, head = forward_per_token(cfg)
    length = sum_n2 / sum_n
    rows = sum_n / length
    keys = rows * sum(row_keys(cfg, round(length)))
    return 3.0 * (sum_n * (body + mask_prob * head)
                  + keys * forward_per_key(cfg))
