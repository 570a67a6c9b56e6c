"""Operations the forward and backward passes of the held share of
``laguna_s_2_1`` require, from shapes.

Matrix products only (2 per multiply-add), nothing recomputed (the layers'
rematerialization, the dense MLP's row chunks and the loss's chunks compute
forwards twice: not counted), backward twice the forward.  Per layer: the
four attention projections and the gate's product of the heads held ON
THAT LAYER (a full layer and a sliding one hold different numbers of query
heads); scores and weighted sum over the keys a query may SEE (``min(i +
1, window)`` of them for the query at ``i``: the window on a sliding layer,
the row on a full one), not over what the kernels' blocks compute, times
the layer's own heads; a dense layer's gate, up and down products; on a
sparse layer the router over all experts, the shared expert, and the routed
experts at their EXPECTATION, ``num_experts_per_tok x held / num_experts``
(token, expert) pairs a token (10 x 8 / 256 = 0.3125), each through gate,
up and down: exact for routing that is even over the experts (the
configuration's ``router_balancing`` holds this share's pairs close to it)
and what a traced run's ``unicore:moe_route`` pairs can be held against.
Head: ``hidden x vocabulary`` (the held columns) on the predicted share of
the tokens.
"""

import json


def _group(cfg, key):
    value = cfg[key]
    return json.loads(value) if isinstance(value, str) else value


def held(cfg):
    """Per held layer its attention kind, MLP kind and query heads; the KV
    heads and the experts held."""
    n = int(cfg.get("attention_shares") or 1)
    layers = int(cfg.get("layers_held") or cfg["num_hidden_layers"])
    return dict(
        kinds=_group(cfg, "layer_types")[:layers],
        mlps=_group(cfg, "mlp_layer_types")[:layers],
        heads=[h // n for h in
               _group(cfg, "num_attention_heads_per_layer")[:layers]],
        kv_heads=max(1, cfg["num_key_value_heads"] // n),
        experts=int(cfg.get("num_experts_held") or cfg["num_experts"]),
    )


def kind_heads(cfg):
    """``{attention kind: query heads held on a layer of that kind}``."""
    mine = held(cfg)
    return dict(zip(mine["kinds"], mine["heads"]))


def visible_keys(length, window=None):
    """Summed over the queries of one row of ``length`` positions: the
    keys each may see, ``min(i + 1, window)``."""
    n, w = int(length), int(window or length)
    w = min(n, w)
    return w * (w + 1) // 2 + (n - w) * w


def forward_per_token(cfg):
    d, D = cfg["hidden_size"], cfg["head_dim"]
    f, fs = cfg["moe_intermediate_size"], cfg["shared_expert_intermediate_size"]
    mine = held(cfg)
    KV = mine["kv_heads"]
    pairs = cfg["num_experts_per_tok"] * mine["experts"] / cfg["num_experts"]
    body = 0.0
    for H, mlp in zip(mine["heads"], mine["mlps"]):
        body += 2 * d * (H + 2 * KV) * D + 2 * d * H + 2 * H * D * d
        if mlp == "dense":
            body += 3 * 2 * d * cfg["intermediate_size"]
        else:
            body += (2 * d * cfg["num_experts"] + 3 * 2 * d * fs
                     + pairs * 3 * 2 * d * f)
    return body, 2 * d * cfg["vocab_size"]


def forward_per_key(cfg, kind):
    """Per (query, visible key) pair of one layer of ``kind``: the score
    and the weighted sum, over the query heads held on such a layer."""
    return 2 * 2 * kind_heads(cfg).get(kind, 0) * cfg["head_dim"]


def row_keys(cfg, length):
    """The visible (query, key) pairs of one row and head, summed over the
    held layers of each kind: ``{kind: pairs}``."""
    kinds = held(cfg)["kinds"]
    sliding = kinds.count("sliding_attention")
    return {
        "sliding_attention":
            sliding * visible_keys(length, cfg["sliding_window"]),
        "full_attention": (len(kinds) - sliding) * visible_keys(length),
    }


def train_flops(cfg, sum_n, sum_n2, mask_prob):
    """``sum_n`` real tokens in rows whose squared lengths sum to
    ``sum_n2``: the cell's rows are all one length, ``sum_n2 / sum_n``."""
    body, head = forward_per_token(cfg)
    length = sum_n2 / sum_n
    rows = sum_n / length
    band = sum(pairs * forward_per_key(cfg, kind)
               for kind, pairs in row_keys(cfg, round(length)).items())
    return 3.0 * (sum_n * (body + mask_prob * head) + rows * band)
