"""Device op time under the ``moe_routed`` scope (dispatch into the held
experts' buffers, the grouped products, the combine) over device op time,
in %."""

from benchmark import scope_shares


def read(run):
    return scope_shares.scope_pct(run, "moe_routed")
