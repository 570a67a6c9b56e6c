"""Model FLOP/s utilization: the operations the forward and backward
passes require for the window's real tokens (``benchmark/flops/<config>``,
from shapes; nothing recomputed, no padding), over window seconds, chips
and the chip's bf16 peak from ``peaks.json``."""

from benchmark import harness


def read(run):
    if "sum_n" not in run:
        return None
    flops = harness.load_module(
        "flops", run["config"]["flops"], run["base"]
    ).train_flops(
        run["config"], run["sum_n"], run["sum_n2"], run["mask_prob"]
    )
    peak = run["peaks"]["bf16_flops_per_s"] * run["chips"]
    return 100.0 * flops / run["window_s"] / peak
