"""Device op time under the ``eva_agg`` scope (``ops/eva_attention.py``;
forward, rematerialized forward and backward) over device op time, in %."""

from benchmark import scope_shares


def read(run):
    return scope_shares.scope_pct(run, "eva_agg")
