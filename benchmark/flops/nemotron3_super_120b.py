"""Operations the forward and backward passes of the held share of
``nemotron3_super_120b`` require, from shapes.

Matrix products only (2 per multiply-add), nothing recomputed (the
layers' rematerialization and the loss's chunks compute forwards twice:
not counted), backward twice the forward.  Per layer kind:

* ``M``: ``in_proj`` and ``out_proj``; the scan's products at what the
  chunked algorithm needs: inside a chunk the causal half of the
  ``(Q x Q)`` scores (per group) and of their product with ``x`` (per
  head), and per token the chunk state's build and its read.
* ``*``: the four projections, and scores and weighted sum over the
  CAUSAL HALF of the square (``n (n + 1) / 2`` pairs of a sequence of
  ``n``).
* ``E``: router, both latent projections and the shared expert on every
  token; the routed experts at their EXPECTATION, ``num_experts_per_tok x
  held / n_routed`` (token, expert) pairs a token (22 x 8 / 512 = 0.34),
  which is exact for routing that is even over the experts and is what a
  traced run's ``unicore:moe_route`` pairs can be held against.
* head: ``hidden x vocabulary`` on the predicted share of the tokens
  (``mask_prob`` 1.0: every token predicts its successor).
"""


def held(cfg):
    """The layers, heads and experts held here, from the configuration's
    statements (``pattern_held``, ``mixer_shares``,
    ``n_routed_experts_held``; each defaults to the whole model)."""
    n = int(cfg.get("mixer_shares") or 1)
    return dict(
        pattern=cfg.get("pattern_held") or cfg["hybrid_override_pattern"],
        mamba_heads=cfg["mamba_num_heads"] // n, groups=cfg["n_groups"] // n,
        heads=cfg["num_attention_heads"] // n,
        kv_heads=max(1, cfg["num_key_value_heads"] // n),
        experts=cfg.get("n_routed_experts_held") or cfg["n_routed_experts"],
    )


def forward_per_token(cfg):
    d = cfg["hidden_size"]
    mine = held(cfg)
    pattern = mine["pattern"]
    H, P = mine["mamba_heads"], cfg["mamba_head_dim"]
    G, N, Q = mine["groups"], cfg["ssm_state_size"], cfg["chunk_size"]
    inner = H * P
    mamba = (2 * d * (2 * inner + 2 * G * N + H) + 2 * inner * d
             + scan_per_token(H, P, G, N, Q))
    Ha, KV, D = mine["heads"], mine["kv_heads"], cfg["head_dim"]
    attn = 2 * d * (Ha + 2 * KV) * D + 2 * Ha * D * d
    E, experts = cfg["n_routed_experts"], mine["experts"]
    lat, f = cfg["moe_latent_size"], cfg["moe_intermediate_size"]
    fs = cfg["moe_shared_expert_intermediate_size"]
    pairs = cfg["num_experts_per_tok"] * experts / E
    moe = (2 * d * E + 2 * 2 * d * lat + 2 * 2 * d * fs
           + pairs * 2 * 2 * lat * f)
    body = (pattern.count("M") * mamba + pattern.count("*") * attn
            + pattern.count("E") * moe)
    return body, 2 * d * cfg["vocab_size"]


def scan_per_token(H, P, G, N, Q):
    """The state-space scan's products per token, forward."""
    half = (Q + 1) / 2  # tokens at or before this one in its chunk, mean
    return (2 * G * N * half      # scores C_i . B_j
            + 2 * H * P * half    # their product with dt_j x_j
            + 2 * H * P * N       # the chunk state's build
            + 2 * H * P * N)      # its read


def forward_per_pair(cfg):
    """Per (query, key) pair of one sequence: scores and the weighted sum."""
    mine = held(cfg)
    return mine["pattern"].count("*") * 2 * 2 * mine["heads"] * cfg["head_dim"]


def train_flops(cfg, sum_n, sum_n2, mask_prob):
    body, head = forward_per_token(cfg)
    causal_pairs = (sum_n2 + sum_n) / 2
    forward = (sum_n * (body + mask_prob * head)
               + causal_pairs * forward_per_pair(cfg))
    return 3.0 * forward
