"""The least time the chip could take for the products under ``fc1`` /
``fc2`` (``trace_scopes.group_of``'s ``ffn``; ``work.flops`` and
``work.bytes`` of the traced program's scope table, the activation fused
into them; forward, rematerialized and backward) over their device time,
in %.  ``ffn_device_pct`` says how much of the step they are."""

from benchmark import scope_work, trace_scopes


def read(run):
    return scope_work.roofline_pct(
        run, lambda parts, row: row["flops"] > 0
        and trace_scopes.group_of(row["path"]) == "ffn"
    )
