"""Operations and bytes one update requires of the two new mechanisms of
``nemotron3_super_120b``, from shapes: what their roofline shares are held
against (``layer_metrics/ssd_scan_roofline_pct.py``,
``moe_routed_roofline_pct.py``).

Both are XLA's own products under a ``jax.named_scope``, not Mosaic
kernels, so the counts are per SCOPE and per update: forward and backward
(twice the forward), nothing recomputed (each layer's rematerialized
forward runs under the same scope and its time is in the denominator: a
share says how far the scope is from what the work needs, not from what it
does).  Bytes are the least traffic with memory: each input read and each
output written once per pass, bf16.
"""


def tokens_per_update(run):
    return run["sum_n"] / run["updates"]


def ssd_scan(run):
    """The chunked scan of every ``M`` layer: ``flops/<config>``'s
    ``scan_per_token`` products; reads ``x, dt, B, C`` and writes ``y``
    forward, reads them and ``dy`` and writes four gradients backward."""
    from benchmark import harness

    cfg = run["config"]
    counts = harness.load_module("flops", cfg["flops"], run["base"])
    mine = counts.held(cfg)
    H, P = mine["mamba_heads"], cfg["mamba_head_dim"]
    per_token = counts.scan_per_token(
        H, P, mine["groups"], cfg["ssm_state_size"], cfg["chunk_size"],
    )
    layers = mine["pattern"].count("M")
    n = tokens_per_update(run)
    bc = mine["groups"] * cfg["ssm_state_size"]
    row = 2 * (H * P + H + 2 * bc)          # x, dt, B, C of one token, bf16
    out = 2 * H * P                         # y
    nbytes = n * layers * ((row + out) + (row + out) + row)
    return 3.0 * per_token * n * layers, nbytes


def moe_routed(run, pairs):
    """Dispatch, the held experts' two products and the combine of every
    ``E`` layer, for the ``pairs`` (token, held expert) pairs an update
    really routed to this chip, all layers together (the traced updates'
    ``pairs_here``: with seeded weights far fewer than the even share,
    ``num_experts_per_tok x held / n_routed`` a token, that
    ``flops/<config>.train_flops`` counts).  Operations: each pair through
    ``latent -> expert -> latent``; dispatch and combine need none (they
    move rows).  Bytes: the held experts' weights read forward and backward
    and their gradient written, each pair's latent row in and out, forward
    and backward."""
    from benchmark import harness

    cfg = run["config"]
    mine = harness.load_module("flops", cfg["flops"], run["base"]).held(cfg)
    lat, f = cfg["moe_latent_size"], cfg["moe_intermediate_size"]
    layers = mine["pattern"].count("E")
    ops = 3.0 * pairs * 2 * 2 * lat * f
    weights = mine["experts"] * 2 * lat * f * 2
    nbytes = layers * 3 * weights + 2 * 2 * pairs * lat * 2
    return ops, nbytes
