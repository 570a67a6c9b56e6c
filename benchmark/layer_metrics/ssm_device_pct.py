"""Device op time under a ``mamba`` scope (a Mamba-2 mixer: projections,
convolution, scan, gated norm; forward, rematerialized forward and
backward) over device op time, in %."""

from benchmark import scope_shares


def read(run):
    return scope_shares.scope_pct(run, "mamba")
