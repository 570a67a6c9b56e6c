"""The share of (token, expert layer) pairs that chose the skip expert: the
``skipped`` over the ``tokens`` stat of the program's ``unicore:moe_skip``
annotation (``modules/zaya_moe.skip_mark``, from what the model logs of an
update), summed over the traced updates.  An even routing over 16 experts
and the skip column reads 1 / 17 = 0.059; 0 where the program wrote its
annotations and none is such a mark; None where it wrote none."""

from benchmark import scope_work


def read(run):
    work = scope_work.of(run)
    if not work or not work.get("host_spans"):
        return None  # not traced, or a program that writes no annotations
    stats = work["marks"].get("moe_skip", {}).get("stats") or {}
    tokens = sum(float(x) for x in stats.get("tokens", ()))
    if not tokens:
        return 0.0
    return sum(float(x) for x in stats["skipped"]) / tokens
