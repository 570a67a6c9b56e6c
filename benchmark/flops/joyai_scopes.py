"""Operations and bytes one update requires of ``joyai_llm_flash``'s
attention proper, from shapes: what ``layer_metrics/mla_attn_roofline_pct.py``
holds the ``mla_attn`` scope against.

Per update, as ``flops/mellum2_scopes.py`` counts: forward and backward
(twice the forward), nothing recomputed (each layer's rematerialized
forward runs under the same scope and its time is in the denominator).
The operations are the EQUATIONS': a (query, visible key) pair's score at
``N + R`` channels and its weighted sum at ``Dv``; the kernels, handed
values padded to ``N + R``, run the second product wider, and that surplus
is time in the denominator with no operation in the numerator.  Bytes the
least traffic with memory, bf16: ``q`` and ``k`` at ``N + R`` and ``v`` at
``Dv`` read and the output at ``Dv`` written forward; those, the output and
its cotangent read and three gradients written backward.
"""


def mla_attn(run):
    """Every attention sublayer held, the prediction module's among them,
    at the heads held."""
    from benchmark import harness

    cfg = run["config"]
    counts = harness.load_module("flops", cfg["flops"], run["base"])
    mine = counts.held(cfg)
    length = run["sum_n2"] / run["sum_n"]
    rows = run["sum_n"] / run["updates"] / length
    ops = 3.0 * rows * mine["attention"] * (
        counts.visible_keys(round(length)) * counts.forward_per_key(cfg))
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    forward = 2 * (2 * qk + 2 * cfg["v_head_dim"])   # q, k, v, o of a head
    backward = forward + 2 * cfg["v_head_dim"] + 2 * (2 * qk + cfg["v_head_dim"])
    tokens = rows * length
    return ops, mine["attention"] * mine["heads"] * tokens * (forward + backward)
