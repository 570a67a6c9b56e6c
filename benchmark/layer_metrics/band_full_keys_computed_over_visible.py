"""Pairs the full layers' kernels score over the pairs their queries
may see: ``full_keys_computed`` over ``full_keys_visible`` of the
program's ``unicore:attn_band`` annotation, summed over the traced updates
(``band_keys_computed_over_visible`` has both kinds summed, and the
annotation's source)."""

from benchmark import harness


def read(run):
    both = harness.load_module(
        "layer_metrics", "band_keys_computed_over_visible",
        run.get("base") or harness.HERE)
    return both.read(run, kinds=("full",))
