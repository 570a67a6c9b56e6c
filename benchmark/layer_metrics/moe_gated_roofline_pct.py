"""The least time the chip could take for the routed gated experts of one
update (``flops/mellum2_scopes.py``: the three products of the pairs the
traced updates really routed here, ``pairs_here`` of the program's
``unicore:moe_route`` marks, over the bf16 peak, or the held weights' and
those pairs' rows' bytes over the memory bandwidth, whichever is larger)
over the device time under ``moe_routed`` per update, in %.  A program
whose experts are not gated (no ``moe_intermediate_size`` beside a
``hidden_size`` in three matrices: another configuration's) is not this
reader's; it lists its own cell."""

from benchmark import harness, scope_shares


def read(run):
    pairs = scope_shares.route_stat(run, "pairs_here")
    if pairs is None:
        return None
    count = harness.load_module("flops", "mellum2_scopes", run["base"])
    return scope_shares.scope_roofline_pct(
        run, "moe_routed", lambda r: count.moe_gated(r, pairs)
    )
