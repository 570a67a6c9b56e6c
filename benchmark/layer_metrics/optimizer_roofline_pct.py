"""The least time the chip could take for the optimizer's operations (the
trainer's ``optimizer`` / ``clip-grads`` / ``multiply-grads`` scopes, the
ones ``optimizer_share_pct`` sums): ``work.bytes`` of the traced program's
scope table at the memory bandwidth (they hold no product), over their
device time, in %."""

from benchmark import scope_work, trace_scopes


def read(run):
    return scope_work.roofline_pct(
        run, lambda parts, row: any(
            p in trace_scopes.OPTIMIZER_SCOPES for p in parts
        )
    )
