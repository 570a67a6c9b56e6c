"""Operations the forward and backward passes of the held share of
``zaya1_8b`` require, from shapes.

Matrix products only (2 per multiply-add), nothing recomputed (the layers'
rematerialization and the loss's chunks compute forwards twice: not
counted), backward twice the forward.  Per layer, attention sublayer: the
projections DOWN into the latent (``q``, ``k`` and ``v`` of the heads held),
the second convolution (each held head's ``2 D x D`` taps; the depthwise
taps, the mean, the norms and the rotation are no products), scores and
weighted sum over the keys a query may SEE (``i + 1`` for the query at
``i``: the whole row, causal) times the query heads held, the projection UP
from the latent.  Expert sublayer: the router's network (``hidden x
router_hidden``, two ``router_hidden`` squares, ``router_hidden x (experts
+ 1)``) and the routed experts at their EXPECTATION under an even routing
over the ``experts + 1`` columns, ``held / (experts + 1)`` (token, expert)
pairs a token (8 / 17 = 0.47: top-1, and the skip column takes its share
and costs nothing), each through gate, up and down: what a traced run's
``unicore:moe_route`` pairs can be held against.  Head: ``hidden x
vocabulary`` (the held rows of the tied embedding) on the predicted share
of the tokens.
"""


def held(cfg):
    """The layers, query heads, KV heads and experts held."""
    shares = int(cfg.get("attention_shares") or 1)
    return dict(
        layers=int(cfg.get("layers_held") or cfg["num_hidden_layers"]),
        heads=cfg["num_attention_heads"] // shares,
        kv_heads=cfg["num_key_value_heads"] // shares,
        experts=int(cfg.get("num_experts_held") or cfg["num_experts"]),
    )


def visible_keys(length):
    """Summed over the queries of one row of ``length`` positions: the
    keys each may see, ``i + 1``."""
    n = int(length)
    return n * (n + 1) // 2


def router_per_token(cfg):
    d, R = cfg["hidden_size"], cfg["router_hidden_size"]
    return 2 * d * R + 2 * 2 * R * R + 2 * R * (cfg["num_experts"] + 1)


def forward_per_token(cfg):
    d, D, f = cfg["hidden_size"], cfg["head_dim"], cfg["moe_intermediate_size"]
    mine = held(cfg)
    H, KV = mine["heads"], mine["kv_heads"]
    attention = (2 * d * (H + 2 * KV) * D          # down
                 + 2 * (H + KV) * 2 * D * D        # the per-head convolution
                 + 2 * H * D * d)                  # up
    pairs = mine["experts"] / (cfg["num_experts"] + 1)
    experts = router_per_token(cfg) + pairs * 3 * 2 * d * f
    return mine["layers"] * (attention + experts), 2 * d * cfg["vocab_size"]


def forward_per_key(cfg):
    """Per (query, visible key) pair of one layer: the score and the
    weighted sum, over the query heads held."""
    return 2 * 2 * held(cfg)["heads"] * cfg["head_dim"]


def train_flops(cfg, sum_n, sum_n2, mask_prob):
    """``sum_n`` real tokens in rows whose squared lengths sum to
    ``sum_n2``: the cell's rows are all one length, ``sum_n2 / sum_n``."""
    body, head = forward_per_token(cfg)
    length = sum_n2 / sum_n
    rows = sum_n / length
    band = (held(cfg)["layers"] * visible_keys(round(length))
            * forward_per_key(cfg))
    return 3.0 * (sum_n * (body + mask_prob * head) + rows * band)
