"""Keys the attention's kernel form scores over the keys its queries may
see: the ``keys_computed`` over the ``keys_visible`` stat of the program's
``unicore:eva_keys`` annotation (stated by the loss from the batch's
shapes, ``LMCrossEntropyLoss.trace_marks``; one mark per traced update),
summed over the traced updates.  1 is a kernel that scores nothing a query
cannot see; the dense windows of ``ops/eva_attention.py`` read about 2; 0
where the program wrote its annotations and no update left such a mark."""

from benchmark import reduce, scope_shares, trace_scopes


def marks_of(run):
    """The stats of this run's ``unicore:eva_keys`` marks; None where the
    run was not traced.  One pass over the trace file's host threads, kept
    on the run."""
    if "eva_key_marks" not in run:
        path = trace_scopes.find_trace() if run.get("trace") else None
        run["eva_key_marks"] = None if not path else [
            s[3] for spans in trace_scopes.host_spans(reduce._load(path)).values()
            for s in spans if s[2] == trace_scopes.PROGRAM + "eva_keys"
        ]
    return run["eva_key_marks"]


def read(run):
    shares = scope_shares.of(run)
    if not shares or not shares.get("host_spans"):
        return None  # not traced, or a program that writes no annotations
    marks = marks_of(run)
    if not marks:
        return 0.0
    visible = sum(float(m["keys_visible"]) for m in marks)
    return sum(float(m["keys_computed"]) for m in marks) / visible
