"""The least time the chip could take for the shared expert's products
(``work.flops`` and ``work.bytes`` of the operations under the
``moe_shared`` scope, every pass of them) over their device time, in %."""

from benchmark import scope_work


def read(run):
    return scope_work.roofline_pct(
        run, lambda parts, row: row["flops"] > 0 and "moe_shared" in parts
    )
