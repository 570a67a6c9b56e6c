"""The prediction module's mean NLL over the model's own: the ``mtp`` over
the ``main`` stat of the program's ``unicore:mtp_loss`` annotation
(``modules/mtp.loss_mark``, from what the loss logs of an update), each a
mean over the traced updates.  Near 1 on seeded weights (both passes score
noise against the same head); 0 where the program wrote its annotations and
none is such a mark (the module does not run); None where it wrote none."""

import statistics

from benchmark import scope_work


def read(run):
    work = scope_work.of(run)
    if not work or not work.get("host_spans"):
        return None  # not traced, or a program that writes no annotations
    stats = work["marks"].get("mtp_loss", {}).get("stats") or {}
    if not stats.get("mtp") or not stats.get("main"):
        return 0.0
    main = statistics.fmean(map(float, stats["main"]))
    return statistics.fmean(map(float, stats["mtp"])) / main if main else 0.0
