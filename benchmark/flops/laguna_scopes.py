"""Operations and bytes one update requires of the mechanisms of
``laguna_s_2_1``, from shapes: what their roofline shares are held against
(``layer_metrics/band_attn_heads_roofline_pct.py``,
``moe_gated_routed_roofline_pct.py``).

Per SCOPE and per update, as ``flops/mellum2_scopes.py`` counts: forward
and backward (twice the forward), nothing recomputed (each layer's
rematerialized forward runs under the same scope and its time is in the
denominator).  Bytes are the least traffic with memory: each input read
and each output written once per pass, bf16.  What differs from
``mellum2_scopes``: a layer's query heads are its KIND's (a full layer and
a sliding one hold different numbers), and the experts' weights are
counted over the SPARSE layers alone (the leading layer is dense).
"""


def _shape(run):
    from benchmark import harness

    cfg = run["config"]
    counts = harness.load_module("flops", cfg["flops"], run["base"])
    length = run["sum_n2"] / run["sum_n"]
    rows = run["sum_n"] / run["updates"] / length
    return cfg, counts, counts.held(cfg), length, rows


def band_attn(run):
    """The banded softmax of every attention layer: the score and
    weighted-sum products of the keys a query may SEE
    (``flops/<config>.row_keys`` by kind; the kernels' partly masked blocks
    score more) times the query heads held on a layer of that kind.
    Bytes: ``q, k, v`` read and the output written forward; those, the
    output and its cotangent read and three gradients written backward,
    with ``k, v`` at the query heads' count (the layer repeats them)."""
    cfg, counts, mine, length, rows = _shape(run)
    ops = 3.0 * rows * sum(
        pairs * counts.forward_per_key(cfg, kind)
        for kind, pairs in counts.row_keys(cfg, round(length)).items())
    tokens = rows * length
    row = 2 * cfg["head_dim"]                  # one head's position, bf16
    return ops, sum(mine["heads"]) * tokens * row * (4 + 8)


def band_heads(run):
    """``(window_heads, full_heads)``: the query heads this count takes for
    a sliding and a full layer, to be held against the program's
    ``unicore:attn_band`` mark."""
    heads = _shape(run)[1].kind_heads(run["config"])
    return (heads.get("sliding_attention", 0),
            heads.get("full_attention", 0))


def moe_gated_routed(run, pairs):
    """Dispatch, the held experts' three products and the combine of every
    SPARSE layer, for the ``pairs`` (token, held expert) pairs an update
    really routed to this chip, all layers together (the traced updates'
    ``pairs_here``).  Operations: each pair through gate, up and down;
    dispatch and combine need none (they move rows).  Bytes: the held
    experts' weights read forward and backward and their gradient written,
    over the sparse layers; each pair's row in and out, forward and
    backward."""
    cfg, _counts, mine, _length, _rows = _shape(run)
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    ops = 3.0 * pairs * 3 * 2 * d * f
    weights = mine["experts"] * 3 * d * f * 2
    sparse = mine["mlps"].count("sparse")
    return ops, sparse * 3 * weights + 2 * 2 * pairs * d * 2
