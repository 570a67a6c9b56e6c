"""The least time the chip could take for the products XLA compiled under
``self_attn`` (the projections in and out, and whatever else of the
attention is a ``dot``; ``work.flops`` and ``work.bytes`` of the traced
program's scope table) over their device time, in %.  The Mosaic kernels
state no product and fall out (``attn_kernel_*_roofline_pct`` are theirs);
rotary, pooling and layout changes have none."""

from benchmark import scope_work


def read(run):
    return scope_work.roofline_pct(
        run, lambda parts, row: row["flops"] > 0 and "self_attn" in parts
    )
