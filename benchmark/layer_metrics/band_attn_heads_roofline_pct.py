"""``band_attn_roofline_pct`` for a program whose sliding and full layers
hold DIFFERENT numbers of query heads: the least time the chip could take
for the banded softmax of one update (``flops/laguna_scopes.py``: the score
and weighted-sum products of the keys each query may SEE times the heads
of the layer's own kind, forward and backward, over the bf16 peak, or the
bytes of ``q, k, v, o`` over the memory bandwidth, whichever is larger)
over the device time under ``band_attn`` per update, in %.  The count's
heads are held against what the program states of itself, the
``window_heads`` / ``full_heads`` stats of its ``unicore:attn_band`` mark:
0 where the program wrote its annotations and no mark states heads (a
program with one head count for all layers has ``band_attn_roofline_pct``);
None where it states other heads than the configuration's count takes."""

from benchmark import harness, scope_shares, scope_work


def read(run):
    work = scope_work.of(run)
    if not work or not work.get("host_spans"):
        return None  # not traced, or a program that writes no annotations
    stats = work["marks"].get("attn_band", {}).get("stats") or {}
    if "window_heads" not in stats or "full_heads" not in stats:
        return 0.0
    count = harness.load_module("flops", "laguna_scopes", run["base"])
    stated = tuple({int(x) for x in stats[k]}
                   for k in ("window_heads", "full_heads"))
    try:
        counted = count.band_heads(run)
    except (KeyError, AttributeError):
        return None  # a configuration whose count knows no kinds of heads
    if stated != ({counted[0]}, {counted[1]}):
        return None
    return scope_shares.scope_roofline_pct(run, "band_attn", count.band_attn)
