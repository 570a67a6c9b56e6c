"""Device op time under the ``band_attn`` scope (``modules/
multihead_attention.py``: the banded attention's layout and kernels;
forward, rematerialized forward and backward) over device op time, in %."""

from benchmark import scope_shares


def read(run):
    return scope_shares.scope_pct(run, "band_attn")
