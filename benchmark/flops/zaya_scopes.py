"""Operations and bytes one update requires of ``zaya1_8b``'s routed
experts, from shapes: what ``layer_metrics/moe_top1_routed_roofline_pct.py``
holds the ``moe_routed`` scope against.

Per update, as ``flops/mellum2_scopes.py`` counts: forward and backward
(twice the forward), nothing recomputed; bytes the least traffic with
memory, bf16.  (``cca_mix_roofline_pct`` needs no count from shapes: what
the scope's operations move is stated by the traced program's own scope
table, ``work.bytes``.)
"""


def moe_top1_routed(run, pairs):
    """Dispatch, the held experts' three products and the combine of every
    expert sublayer, for the ``pairs`` (token, held expert) pairs an update
    really routed to this chip, all layers together (the traced updates'
    ``pairs_here``; a token that chose the skip column or an expert held
    elsewhere is no pair).  Operations: each pair through gate, up and
    down.  Bytes: the held experts' weights read forward and backward and
    their gradient written, every held layer; each pair's row in and out,
    forward and backward."""
    from benchmark import harness

    cfg = run["config"]
    mine = harness.load_module("flops", cfg["flops"], run["base"]).held(cfg)
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    ops = 3.0 * pairs * 3 * 2 * d * f
    weights = mine["experts"] * 3 * d * f * 2
    return ops, mine["layers"] * 3 * weights + 2 * 2 * pairs * d * 2
