"""``moe_gated_roofline_pct`` for a decoder whose leading layer is dense:
the least time the chip could take for the routed gated experts of one
update (``flops/laguna_scopes.py``: the three products of the pairs the
traced updates really routed here, ``pairs_here`` of the program's
``unicore:moe_route`` marks, over the bf16 peak, or the held weights' bytes
over the SPARSE layers and those pairs' rows' bytes over the memory
bandwidth, whichever is larger) over the device time under ``moe_routed``
per update, in %.  The shared expert runs under ``moe_shared`` and is not
in it.  A configuration whose ``flops`` file does not say which of its
layers are sparse is not this reader's (None)."""

from benchmark import harness, scope_shares


def read(run):
    pairs = scope_shares.route_stat(run, "pairs_here")
    if pairs is None:
        return None
    count = harness.load_module("flops", "laguna_scopes", run["base"])
    try:
        return scope_shares.scope_roofline_pct(
            run, "moe_routed", lambda r: count.moe_gated_routed(r, pairs)
        )
    except (KeyError, AttributeError):
        return None
