"""``moe_gated_routed_roofline_pct`` for a top-1 router with a skip column:
the least time the chip could take for the routed gated experts of one
update (``flops/zaya_scopes.py``: the three products of the pairs the
traced updates really routed here, ``pairs_here`` of the program's
``unicore:moe_route`` marks, fewer than tokens, over the bf16 peak, or the
held weights' bytes over the held layers and those pairs' rows' bytes over
the memory bandwidth, whichever is larger) over the device time under
``moe_routed`` per update, in %.  A configuration whose ``flops`` file
counts no held layers of such experts is not this reader's (None)."""

from benchmark import harness, scope_shares


def read(run):
    pairs = scope_shares.route_stat(run, "pairs_here")
    if pairs is None:
        return None
    count = harness.load_module("flops", "zaya_scopes", run["base"])
    try:
        return scope_shares.scope_roofline_pct(
            run, "moe_routed", lambda r: count.moe_top1_routed(r, pairs)
        )
    except (KeyError, AttributeError):
        return None
