"""Device op time under a ``moe`` scope (a LatentMoE layer: router, latent
projections, routed experts, shared expert; forward, rematerialized
forward and backward) over device op time, in %."""

from benchmark import scope_shares


def read(run):
    return scope_shares.scope_pct(run, "moe")
