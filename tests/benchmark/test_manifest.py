"""``BENCHMARK.json`` against the contract's rules that a CPU can check, and
the proof that a new cell, configuration or per-layer metric needs only new
files and new manifest entries.  Every check that takes ``manifest`` or
``checkout`` (``conftest.py``) runs twice: on the manifest as it is, and on
it with a configuration, a cell and two per-layer metrics appended."""

import json
import os
import re

import pytest

from benchmark import harness

from bench_tiny import fake_chip, load, tiny_checkout

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_keys_and_sizes(checkout):
    manifest = checkout.manifest
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(
        os.path.join(checkout.root, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= manifest["run_seconds"] <= 51
    assert (2 + 14 * 24) * (manifest["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert 1 <= len(manifest["workloads"]) <= 24
    assert 1 <= len(manifest["configs"]) <= 24
    assert 1 <= len(manifest["end_to_end"]) <= 16
    assert 1 <= len(manifest["per_layer"]) <= 128
    for word in manifest["command"]:
        assert not word.startswith("/") and ".." not in word


def test_names_units_and_sources(manifest):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in manifest[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group, entry["name"]))
    assert len(names) == len(set(names))
    metrics = manifest["end_to_end"] + manifest["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in manifest["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    assert any(m["name"] == "setup_s" for m in manifest["end_to_end"])


def test_every_named_file_exists(checkout):
    manifest, root, base = checkout
    files = [cfg["file"] for cfg in manifest["configs"]]
    assert len(set(files)) == len(files)  # no configuration's file is another's
    for cfg in manifest["configs"]:
        assert os.path.isfile(os.path.join(root, cfg["file"]))
        body = load(os.path.join(root, cfg["file"]))
        for key in cfg["reduced"]:
            assert key in body, (cfg["name"], key)
            assert not re.search(r"(_dim|_rank|hidden|intermediate|head)", key)
        assert any(w["config"] == cfg["name"] for w in manifest["workloads"])
    for w in manifest["workloads"]:
        cell = checkout.cell(w["name"])
        harness.find("drivers", cell.traffic["driver"] + ".py", base)
        for kind in ("reference", "flops"):
            harness.find(kind, cell.config[kind] + ".py", base)
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    for m in manifest["per_layer"]:
        harness.find("layer_metrics", m["name"] + ".py", base)


def test_every_moves_is_reported_where_the_metric_is(checkout):
    manifest = checkout.manifest
    end = {m["name"]: m for m in manifest["end_to_end"]}
    cells = [w["name"] for w in manifest["workloads"]]
    for m in manifest["per_layer"]:
        target = end[m["moves"]]
        reported_in = target.get("workloads", cells)
        for cell in m.get("workloads", reported_in):
            assert cell in reported_in, (m["name"], cell)
    for cell in cells:
        c = checkout.cell(cell)
        assert len(c.metrics("end_to_end")) >= 2
        assert len(c.metrics("per_layer")) >= 1


def test_unknown_device_kind_is_an_error():
    assert harness.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert harness.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SystemExit):
        harness.peaks_for("TPU v9 imaginary")
    with pytest.raises(SystemExit):
        harness.peaks_for("_source")


def test_no_tpu_no_result(capsys):
    """On this CPU the command refuses: non-zero exit, no result line."""
    from benchmark import run

    with pytest.raises(SystemExit) as stop:
        run.run(["--workload", "bert_base.train_mlm512", "--seed", "1",
                 "--seconds", "1", "--trace", "0"])
    assert stop.value.code != 0
    assert '"correct"' not in capsys.readouterr().out


def test_a_new_cell_config_and_metric_are_only_new_files(tmp_path, capsys):
    """A fifth cell, a third configuration and a new per-layer metric,
    registered from a temporary directory: new files and new manifest
    entries, no edit to a file that is there."""
    from benchmark import run

    root, base = tiny_checkout(tmp_path, "bert_base.train_mlm512")
    manifest = load(os.path.join(root, "BENCHMARK.json"))
    cfg = load(os.path.join(root, "benchmark/configs/bert_base.json"))
    cfg["encoder_layers"] = 1
    with open(os.path.join(root, "benchmark/configs/bert_one.json"), "w") as f:
        json.dump(cfg, f)
    tr = load(os.path.join(base, "workloads", "bert_base.train_mlm512.json"))
    tr["batch_size"] = 2
    with open(os.path.join(base, "workloads", "bert_one.train_small.json"), "w") as f:
        json.dump(tr, f)
    os.makedirs(os.path.join(base, "layer_metrics"))
    with open(os.path.join(base, "layer_metrics", "updates_per_s.new.py"), "w") as f:
        f.write("def read(run):\n    return run['updates'] / run['window_s']\n")
    manifest["configs"].append({
        "name": "bert_one", "source": "test", "reduced": ["encoder_layers"],
        "file": "benchmark/configs/bert_one.json", "why": "dummy"})
    manifest["workloads"].append({
        "name": "bert_one.train_small", "config": "bert_one",
        "traffic": "train_small", "chips": 1, "why": "dummy"})
    manifest["per_layer"].append({
        "name": "updates_per_s.new", "unit": "1/s", "better": "higher",
        "source": "host_clock", "layer": "train step (trainer.py)",
        "moves": "train_tokens_per_s", "workloads": ["bert_one.train_small"]})
    for metric in manifest["end_to_end"]:
        if metric["name"] == "train_tokens_per_s":
            metric["workloads"].append("bert_one.train_small")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)

    out = run.run(["--workload", "bert_one.train_small", "--seed", "5",
                   "--seconds", "0.5", "--trace", "0"],
                  require=fake_chip, root=root, base=base)
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last["correct"] is True and out["updates"] == last["attempted"]
    assert set(last["metrics"]) == {"train_tokens_per_s", "setup_s"}
    # the per-layer line of the same run (no profiler on a CPU: readers
    # that need the device trace find nothing and are left out)
    cell = harness.Cell(manifest, "bert_one.train_small", base, root)
    layer = json.loads(harness.result_line(cell, out, trace=True))["metrics"]
    assert layer["updates_per_s.new"]["value"] > 0
    assert "train_step_ms" in layer and "device_idle_pct" not in layer
