"""Operations and bytes one update requires of the two attention scopes of
``evabyte``, from shapes: what their roofline shares are held against
(``layer_metrics/eva_agg_roofline_pct.py``, ``eva_prep_kv_roofline_pct.py``).

Per SCOPE and per update, as ``flops/nemotron_scopes.py`` counts: forward
and backward (twice the forward), nothing recomputed (each layer's
rematerialized forward runs under the same scope and its time is in the
denominator: a share says how far the scope is from what the work needs,
not from what it does).  Bytes are the least traffic with memory: each
input read and each output written once per pass, bf16.
"""


def _shape(run):
    from benchmark import harness

    cfg = run["config"]
    counts = harness.load_module("flops", cfg["flops"], run["base"])
    mine = counts.held(cfg)
    length = run["sum_n2"] / run["sum_n"]
    rows = run["sum_n"] / run["updates"] / length
    return cfg, counts, mine, length, rows


def eva_agg(run):
    """The joint softmax of every layer: the products of the keys a query
    may SEE (``flops/<config>.visible_keys``; the dense windows score about
    twice as many).  Bytes: ``q, k, v`` read and the output written
    forward; those, the output and its cotangent read and three gradients
    written backward; the summaries are a ``chunk_size``-th of ``k, v``."""
    cfg, counts, mine, length, rows = _shape(run)
    keys = rows * counts.visible_keys(
        round(length), cfg["window_size"], cfg["chunk_size"])
    ops = 3.0 * keys * counts.forward_per_key(cfg)
    row = 2 * mine["heads"] * mine["head_dim"]       # one position, bf16
    share = 1 + 1.0 / cfg["chunk_size"]              # with its summaries
    tokens = rows * length
    forward = row * (1 + 2 * share + 1)
    backward = row * (1 + 2 * share + 2 + 1 + 2 * share)
    return ops, mine["layers"] * tokens * (forward + backward)


def eva_prep_kv(run):
    """The chunk summaries of every layer: reads ``k, v`` once and writes a
    ``chunk_size``-th of them forward; reads them and the summaries'
    cotangents and writes ``dk, dv`` backward.  Operations: each key
    against ``mu`` and ``phi`` and into the two weighted sums."""
    cfg, _counts, mine, length, rows = _shape(run)
    inner = mine["heads"] * mine["head_dim"]
    tokens = rows * length
    ops = 3.0 * mine["layers"] * tokens * 4 * 2 * inner
    row = 2 * inner
    small = 2.0 / cfg["chunk_size"]
    forward = row * (2 + small)
    backward = row * (2 + small + 2)
    return ops, mine["layers"] * tokens * (forward + backward)
