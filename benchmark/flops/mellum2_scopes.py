"""Operations and bytes one update requires of the two mechanisms of
``mellum2_12b``, from shapes: what their roofline shares are held against
(``layer_metrics/band_attn_roofline_pct.py``, ``moe_gated_roofline_pct.py``).

Per SCOPE and per update, as ``flops/nemotron_scopes.py`` counts: forward
and backward (twice the forward), nothing recomputed (each layer's
rematerialized forward runs under the same scope and its time is in the
denominator: a share says how far the scope is from what the work needs,
not from what it does).  Bytes are the least traffic with memory: each
input read and each output written once per pass, bf16.
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
    (``flops/<config>.row_keys``; the kernels' partly masked blocks score
    more).  Bytes: ``q, k, v`` read and the output written forward; those,
    the output and its cotangent read and three gradients written
    backward, with ``k, v`` at the query heads' count (the layer repeats
    them)."""
    cfg, counts, mine, length, rows = _shape(run)
    keys = rows * sum(counts.row_keys(cfg, round(length)))
    ops = 3.0 * keys * counts.forward_per_key(cfg)
    row = 2 * mine["heads"] * cfg["head_dim"]        # one position, bf16
    tokens = rows * length
    return ops, len(mine["kinds"]) * tokens * row * (4 + 8)


def moe_gated(run, pairs):
    """Dispatch, the held experts' three products and the combine of every
    expert layer, for the ``pairs`` (token, held expert) pairs an update
    really routed to this chip, all layers together (the traced updates'
    ``pairs_here``).  Operations: each pair through gate, up and down;
    dispatch and combine need none (they move rows).  Bytes: the held
    experts' weights read forward and backward and their gradient written,
    each pair's row in and out, forward and backward."""
    cfg, _counts, mine, _length, _rows = _shape(run)
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    ops = 3.0 * pairs * 3 * 2 * d * f
    weights = mine["experts"] * 3 * d * f * 2
    nbytes = len(mine["kinds"]) * 3 * weights + 2 * 2 * pairs * d * 2
    return ops, nbytes
