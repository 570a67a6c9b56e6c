"""Device op time under the ``attn_gate`` scope (``modules/
multihead_attention.py``: the per-head gate's product, its sigmoid and the
multiply onto the heads' weighted sums; forward, rematerialized forward and
backward) over device op time, in %."""

from benchmark import scope_shares


def read(run):
    return scope_shares.scope_pct(run, "attn_gate")
