"""One run of one cell:

    python3 -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process that finds the cell's files by name, refuses to run without
the chips the cell asks for, builds weights on the device and traffic on
the host from ``--seed``, warms the cell's own shapes (set-up), measures for
``--seconds``, checks what the timed path produced against the plain
reference outside the window, and prints one JSON object as the last line
of its standard output.
"""

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402  (starts the set-up clock)


def parse(argv):
    p = argparse.ArgumentParser(prog="benchmark.run")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def run(argv, require=harness.require_chips, root=harness.ROOT,
        base=harness.HERE):
    """Everything but the process exit.  ``require`` is the look for a chip;
    the tests under ``tests/benchmark`` hand in their own."""
    args = parse(argv)
    if not os.path.isdir(os.path.join(root, "unicore_tpu")):
        raise harness.Refused(
            f"{root} holds no program to measure (unicore_tpu/ is missing)"
        )
    cell = harness.Cell(harness.load_manifest(root), args.workload, base, root)
    device, peaks = require(cell.chips)

    from unicore_tpu.platform_utils import configure_compilation_cache

    configure_compilation_cache()

    driver = harness.load_module("drivers", cell.traffic["driver"], base)
    out = driver.run(
        cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        device=device, peaks=peaks,
    )
    out["device"] = device
    line = harness.result_line(cell, out, bool(args.trace))
    harness.say(line)
    return out


if __name__ == "__main__":
    run(sys.argv[1:])
