"""Device op time under the ``moe_router`` scope of a router that is a
network (``modules/zaya_moe.py``: the product into the router's state with
the state carried from the layer before, the norm and the two GELU layers,
the softmax, the balancing rule's rounds and ``top_k``'s set) over device
op time, in %."""

from benchmark import scope_shares


def read(run):
    return scope_shares.scope_pct(run, "moe_router")
