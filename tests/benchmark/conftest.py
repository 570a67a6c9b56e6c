"""The fixture that drives a tiny cell through the rest of a run."""

import json

import pytest

from bench_tiny import fake_chip, tiny_checkout


@pytest.fixture
def run_tiny(tmp_path, capsys):
    """Drive the rest of a run (everything but the look for a chip) on a
    tiny cell; returns (what the driver returned, the parsed last line)."""
    from benchmark import run

    def go(cell_name, seed=12345678901, seconds=1.0, float32=False, edit=None):
        root, base = tiny_checkout(tmp_path, cell_name, float32)
        if edit is not None:
            edit(root, base)
        out = run.run(
            ["--workload", cell_name, "--seed", str(seed), "--seconds",
             str(seconds), "--trace", "0"],
            require=fake_chip, root=root, base=base,
        )
        last = capsys.readouterr().out.strip().splitlines()[-1]
        return out, json.loads(last)

    return go
