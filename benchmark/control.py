"""The control of ``correct``: the plain reference put in the program's
place and computed in the nearest precision below the one the
configuration states (int8 for bfloat16), on the cell's own batches at the
cell's own size, compared with the float32 reference by the very
comparison a run makes.  It has to come out as not correct.

    python3 -m benchmark.control --workload <name> --seeds 1,2,3

Not part of a benchmark run.  ``tests/benchmark/test_control.py`` keeps it
at a size a test run can hold.
"""

import argparse
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402
from benchmark.drivers import train  # noqa: E402


def control_checks(cell, seed, precision="int8"):
    """The checks of one seed: the control against the reference."""
    cfg, tr = cell.config, cell.traffic
    work = tempfile.mkdtemp(prefix="unicore_bench_")
    try:
        _args, task, batches, shaped, _ = train.open_feed(cell, seed, work)
        kept = [shaped(next(batches))[0] for _ in range(train.CHECKED_UPDATES)]
        hyper = train.hyper_of(cfg, task)
    finally:
        shutil.rmtree(work, ignore_errors=True)
    ref = harness.load_module("reference", cfg["reference"], cell.base)
    rows = int(tr.get("reference_rows", 4))
    sound = ref.train_check(cfg, hyper, kept, seed, rows)
    lower = ref.train_check(cfg, hyper, kept, seed, rows, precision=precision)
    return train.compare(lower, sound, tr["limits"], sound["names"])


def main(argv):
    p = argparse.ArgumentParser(prog="benchmark.control")
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--precision", default="int8")
    args = p.parse_args(argv)
    cell = harness.Cell(harness.load_manifest(), args.workload)
    harness.require_chips(cell.chips)
    from unicore_tpu.platform_utils import configure_compilation_cache

    configure_compilation_cache()
    seeds = [int(s) for s in args.seeds.split(",")]
    caught = 0
    for seed in seeds:
        harness.say(f"control seed {seed} precision {args.precision}")
        caught += not harness.report_checks(
            control_checks(cell, seed, args.precision)
        )
    harness.say(f"control: came out as not correct on {caught} seed(s)")
    return 0 if caught == len(seeds) else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
