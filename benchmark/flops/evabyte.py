"""Operations the forward and backward passes of the held share of
``evabyte`` require, from shapes.

Matrix products only (2 per multiply-add), nothing recomputed (the layers'
rematerialization and the chunks of the feed-forward layers and of the loss
compute forwards twice: not counted), backward twice the forward.  Per
layer: the four attention projections of the heads held, the chunk
summaries (each position's key against ``mu`` and ``phi`` and its part of
the two weighted sums), the fused gate-and-up product and the down
product; scores and weighted sum over the keys a query may SEE (its
window's keys up to itself and one summary per chunk of the earlier
windows), not over what the dense windows of ``ops/eva_attention.py``
compute.  Head: ``hidden x (num_pred_heads x vocabulary)`` on every
position.
"""


def held(cfg):
    return dict(
        layers=int(cfg.get("layers_held") or cfg["num_hidden_layers"]),
        heads=cfg["num_attention_heads"] // int(cfg.get("attention_shares") or 1),
        head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],
    )


def visible_keys(length, window, chunk):
    """Summed over the queries of one row of ``length`` positions (whole
    windows, and a last partial one): the keys each may see."""
    full, rest = divmod(int(length), window)
    cpw = window // chunk
    total = 0
    for w, n in enumerate([window] * full + ([rest] if rest else [])):
        total += n * (n + 1) // 2 + n * w * cpw
    return total


def forward_per_token(cfg):
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    mine = held(cfg)
    inner = mine["heads"] * mine["head_dim"]
    attn = 2 * d * 3 * inner + 2 * inner * d + 4 * 2 * inner
    mlp = 2 * d * 2 * f + 2 * f * d
    head = 2 * d * cfg["num_pred_heads"] * cfg["vocab_size"]
    return mine["layers"] * (attn + mlp), head


def forward_per_key(cfg):
    """Per (query, visible key) pair: the score and the weighted sum."""
    mine = held(cfg)
    return mine["layers"] * 2 * 2 * mine["heads"] * mine["head_dim"]


def train_flops(cfg, sum_n, sum_n2, mask_prob):
    """``sum_n`` real tokens in rows whose squared lengths sum to
    ``sum_n2``: the cell's rows are all one length, ``sum_n2 / sum_n``."""
    body, head = forward_per_token(cfg)
    length = sum_n2 / sum_n
    rows = sum_n / length
    keys = rows * visible_keys(round(length), cfg["window_size"],
                               cfg["chunk_size"])
    return 3.0 * (sum_n * (body + mask_prob * head)
                  + keys * forward_per_key(cfg))
