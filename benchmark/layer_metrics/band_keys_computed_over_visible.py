"""Pairs the banded attention's kernels score over the pairs their queries
may see, the sliding-window and the full layers summed: the
``window_keys_computed + full_keys_computed`` over the ``window_keys_visible
+ full_keys_visible`` stats of the program's ``unicore:attn_band``
annotation (stated by the loss from the batch's shapes and the maps the
kernels are handed, ``LMCrossEntropyLoss.trace_marks``; one mark per traced
update), summed over the traced updates.  1 is a kernel that scores nothing
a query cannot see; 0 where the program wrote its annotations and no update
left such a mark.  The full layer's pairs outweigh the sliding layers' 30 to
1 at 32,768, so each kind has a metric of its own beside this one
(``band_window_...``, ``band_full_...``)."""

from benchmark import scope_work

KINDS = ("window", "full")


def read(run, kinds=KINDS):
    work = scope_work.of(run)
    if not work or not work.get("host_spans"):
        return None  # not traced, or a program that writes no annotations
    stats = work["marks"].get("attn_band", {}).get("stats")
    if not stats:
        return 0.0
    total = lambda what: sum(
        float(x) for kind in kinds for x in stats[f"{kind}_keys_{what}"])
    return total("computed") / total("visible")
