"""Operations the forward and backward passes of the held share of
``joyai_llm_flash`` require, from shapes: the EQUATIONS' operations, not the
program's (the band kernels are handed values padded from 128 to the keys'
192 channels and run the second product of a pair 192 wide; counted here is
the pair at 192 + 128).

Matrix products only (2 per multiply-add), nothing recomputed (the layers'
rematerialization and the loss's chunks compute forwards twice: not
counted), backward twice the forward.  Per layer, attention sublayer: the
four low-rank products (``q_a`` into the query latent, ``q_b`` out of it to
the heads held, ``kv_a`` into the key/value latent and the shared rotary
key, ``kv_b`` out of it to the heads held) and ``o_proj`` from the heads
held; scores at ``N + R`` and weighted sum at ``Dv`` channels over the keys
a query may SEE (``i + 1`` for the query at ``i``: the whole row, causal)
times the heads held.  Feed-forward sublayer: layer 0's dense gated MLP
(three products at ``intermediate_size``), or the router over ALL experts,
the shared expert and the routed experts at their EXPECTATION under an even
routing, ``held * per_tok / experts`` (token, expert) pairs a token (16 x 8
/ 256 = 0.5), each through gate, up and down.  The prediction module: its
block (one more attention and one more expert sublayer), its ``2d -> d``
projection, and a second pass of the head.  Head: ``hidden x vocabulary``
(the held columns) on the predicted share of the tokens, once for the model
and once for the module.
"""


def held(cfg):
    """The attention and expert sublayers held, the module's among them,
    the dense layers, the heads and the experts held."""
    layers = int(cfg.get("layers_held") or cfg["num_hidden_layers"])
    leading = int(cfg["first_k_dense_replace"])
    dense = min(layers, leading)
    module = int(cfg.get("num_nextn_predict_layers") or 0)
    # the module's block is the model's LAST layer's kinds
    module_dense = module if cfg["num_hidden_layers"] <= leading else 0
    return dict(
        attention=layers + module, dense=dense + module_dense,
        sparse=layers - dense + module - module_dense, module=module,
        heads=cfg["num_attention_heads"] // int(
            cfg.get("attention_shares") or 1),
        experts=int(cfg.get("num_experts_held") or cfg["n_routed_experts"]),
    )


def visible_keys(length):
    """Summed over the queries of one row of ``length`` positions: the
    keys each may see, ``i + 1``."""
    n = int(length)
    return n * (n + 1) // 2


def mla_per_token(cfg):
    """One attention sublayer's five products, a token."""
    d, H = cfg["hidden_size"], held(cfg)["heads"]
    Cq, C = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    N, R, Dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                cfg["v_head_dim"])
    return (2 * d * Cq + 2 * Cq * H * (N + R) + 2 * d * (C + R)
            + 2 * C * H * (N + Dv) + 2 * H * Dv * d)


def sparse_per_token(cfg):
    """One expert sublayer, a token: the router, the shared expert, the
    routed experts at the even routing's share."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    E = cfg["n_routed_experts"]
    pairs = held(cfg)["experts"] * cfg["num_experts_per_tok"] / E
    return (2 * d * E + cfg["n_shared_experts"] * 3 * 2 * d * f
            + pairs * 3 * 2 * d * f)


def forward_per_token(cfg):
    """``(body, head)``: every held sublayer and the module's projection;
    one pass of the head."""
    d, mine = cfg["hidden_size"], held(cfg)
    body = (mine["attention"] * mla_per_token(cfg)
            + mine["dense"] * 3 * 2 * d * cfg["intermediate_size"]
            + mine["sparse"] * sparse_per_token(cfg)
            + mine["module"] * 2 * 2 * d * d)
    return body, 2 * d * cfg["vocab_size"]


def forward_per_key(cfg):
    """Per (query, visible key) pair of one layer: the score at ``N + R``
    channels and the weighted sum at ``Dv``, over the heads held."""
    return 2 * held(cfg)["heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"])


def train_flops(cfg, sum_n, sum_n2, mask_prob):
    """``sum_n`` real tokens in rows whose squared lengths sum to
    ``sum_n2``: the cell's rows are all one length, ``sum_n2 / sum_n``."""
    body, head = forward_per_token(cfg)
    mine = held(cfg)
    length = sum_n2 / sum_n
    rows = sum_n / length
    band = mine["attention"] * visible_keys(round(length)) * forward_per_key(cfg)
    passes = 1 + mine["module"]  # the head scores the module's stream too
    return 3.0 * (sum_n * (body + mask_prob * passes * head) + rows * band)
