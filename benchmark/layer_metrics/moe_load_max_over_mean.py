"""The most loaded held expert's tokens over the mean load, per layer and
update (the ``load_max`` over the ``load_mean`` stat of the program's
``unicore:moe_route`` annotation, each a mean over the traced updates):
1 is even routing among the held experts."""

from benchmark import scope_shares


def read(run):
    most = scope_shares.route_stat(run, "load_max")
    mean = scope_shares.route_stat(run, "load_mean")
    if most is None or mean is None:
        return None
    return most / mean if mean else 0.0  # 0: no update routed anything
