"""Device op time under the ``mla_attn`` scope (``modules/mla.py``: the
rotation of the queries' rotary channels and of the shared rotary key, that
key's broadcast to the heads, the values' padding to the keys' width and
the band kernels; forward, rematerialized forward and backward) over device
op time, in %; 0 where the program named its operations and none ran under
the scope."""

from benchmark import scope_shares


def read(run):
    return scope_shares.scope_pct(run, "mla_attn")
