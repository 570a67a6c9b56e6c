"""The least time the chip could take for the routed experts of one update
(``flops/nemotron_scopes.py``: the products of the pairs the traced updates
really routed here, ``pairs_here`` of the program's ``unicore:moe_route``
marks, over the bf16 peak, or the held weights' and those pairs' rows'
bytes over the memory bandwidth, whichever is larger) over the device time
under ``moe_routed`` per update, in %.  Low while dispatch and combine
gather a worst-case buffer's rows to use the few that hold a pair: that
cost is what it shows."""

from benchmark import harness, scope_shares


def read(run):
    pairs = scope_shares.route_stat(run, "pairs_here")
    if pairs is None:
        return None
    count = harness.load_module("flops", "nemotron_scopes", run["base"])
    return scope_shares.scope_roofline_pct(
        run, "moe_routed", lambda r: count.moe_routed(r, pairs)
    )
