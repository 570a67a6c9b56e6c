"""The fixtures the benchmark's tests share: the manifest as it is and with
an append, and the one that drives a tiny cell through the rest of a run."""

import json
import os
import shutil
from typing import NamedTuple

import pytest

from bench_tiny import BENCH, ROOT, fake_chip, load, tiny_checkout


class Checkout(NamedTuple):
    """A manifest with where its files are: ``root`` holds
    ``BENCHMARK.json`` and the configurations' files, ``base`` the
    benchmark's other files (``harness.find`` falls back to the
    benchmark's own for what ``base`` lacks)."""

    manifest: dict
    root: str
    base: str

    def cell(self, name):
        from benchmark import harness

        return harness.Cell(self.manifest, name, self.base, self.root)


#: what a later PR appends: a configuration, a cell that reports
#: ``train_tokens_per_s``, a per-layer metric that lists the cell and one
#: that lists none (so every training cell, old ones too, gets it)
APPENDED_CONFIG = "appended_config"
APPENDED_CELL = "appended_config.train_appended"
APPENDED_METRICS = ("appended_listed_ms", "appended.everywhere_per_s")


def with_an_append(tmp):
    """The real ``BENCHMARK.json`` grown the way a ``model_config``,
    ``tracing`` or ``perf_opt`` PR grows it: new entries at the end of each
    list, their files new files, nothing that is there edited (here under
    ``tmp``, beside copies of the configurations' files)."""
    manifest = load(os.path.join(ROOT, "BENCHMARK.json"))
    root, base = str(tmp), os.path.join(str(tmp), "benchmark")
    shutil.copytree(os.path.join(BENCH, "configs"), os.path.join(base, "configs"))
    for kind in ("workloads", "layer_metrics"):
        os.makedirs(os.path.join(base, kind))
    shutil.copy(os.path.join(base, "configs", "bert_base.json"),
                os.path.join(base, "configs", APPENDED_CONFIG + ".json"))
    shutil.copy(os.path.join(BENCH, "workloads", "bert_base.train_mlm512.json"),
                os.path.join(base, "workloads", APPENDED_CELL + ".json"))
    for name in APPENDED_METRICS:
        with open(os.path.join(base, "layer_metrics", name + ".py"), "w") as f:
            f.write("def read(run):\n    return run['updates'] / run['window_s']"
                    " if 'updates' in run else None\n")
    manifest["configs"].append({
        "name": APPENDED_CONFIG, "source": "a later PR's", "reduced": [],
        "file": f"benchmark/configs/{APPENDED_CONFIG}.json", "why": "appended"})
    manifest["workloads"].append({
        "name": APPENDED_CELL, "config": APPENDED_CONFIG,
        "traffic": "train_appended", "chips": 1, "why": "appended"})
    for metric in manifest["end_to_end"]:
        if metric["name"] == "train_tokens_per_s":
            metric["workloads"].append(APPENDED_CELL)
    for name, lists in zip(APPENDED_METRICS, ({"workloads": [APPENDED_CELL]}, {})):
        manifest["per_layer"].append({
            "name": name, "unit": "1/s", "better": "higher",
            "source": "host_clock", "layer": manifest["per_layer"][0]["layer"],
            "moves": "train_tokens_per_s", **lists})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f, indent=1)
    return Checkout(manifest, root, base)


@pytest.fixture(scope="module", params=("as_it_is", "with_an_append"))
def checkout(request, tmp_path_factory):
    """The manifest every manifest-level check of ``tests/benchmark`` runs
    on, twice: as the tree has it, and with an append.  A check that holds
    only until somebody appends (an entry's place counted from the end, a
    list's length) fails the second case here, in the PR that writes it,
    and not in the first PR that appends."""
    if request.param == "as_it_is":
        return Checkout(load(os.path.join(ROOT, "BENCHMARK.json")), ROOT, BENCH)
    return with_an_append(tmp_path_factory.mktemp("appended"))


@pytest.fixture(scope="module")
def manifest(checkout):
    return checkout.manifest


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
