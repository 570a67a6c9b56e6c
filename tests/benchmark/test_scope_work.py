"""``benchmark/scope_work.py`` and the readers of the table's ``work``: on
a hand-built run (as ``test_trace_scopes.py`` builds one), on a recorded
run whose table states none, and, compiled here for a described v5e, the
``work`` of BERT's real step against the count by hand."""

import os

import pytest

import test_compile_v5e as rehearsal
from bench_tiny import BENCH, ROOT, load
from benchmark import harness, scope_work, trace_scopes
from test_compile_v5e import one_chip  # noqa: F401  (the module's fixture)

S = 1e9  # the trace's clock is in ns
PEAKS = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
FC1 = "%fusion.5 = bf16[8,8]{1,0} fusion(bf16[8,8]{1,0} %x), kind=kOutput, calls=%fc.1"
PROJ = "%fusion.6 = bf16[8,8]{1,0} fusion(bf16[8,8]{1,0} %x), kind=kOutput, calls=%fc.3"
FWD = ('%flash_fwd.3 = bf16[2,4,256,64]{3,2,1,0} custom-call(bf16[2,4,256,64]{3,2,1,0} %q), '
       'custom_call_target="tpu_custom_call"')
SHARED = "%fusion.7 = bf16[8,8]{1,0} fusion(bf16[8,8]{1,0} %x), kind=kOutput, calls=%fc.4"
ADAM = "%fusion.9 = f32[8]{0} fusion(f32[8]{0} %m), kind=kLoop, calls=%fc.2"
BARE = "%convert.1 = f32[8]{0} convert(bf16[8]{0} %y)"
WHILE = "%while.1 = (s32[], f32[2]{0}) while((s32[], f32[2]{0}) %t), body=%b"
PATHS = {
    "fusion.5": "jit(train_step)/jvp(forward)/M/enc/layers_1/fc1/dot_general",
    "fusion.6": "jit(train_step)/transpose(jvp(forward))/M/enc/layers_0/self_attn/q_proj/dot_general",
    "flash_fwd.3": "jit(train_step)/transpose(jvp(forward))/M/checkpoint/rematted_computation/layers_0/self_attn/flash_fwd",
    "fusion.7": "jit(train_step)/jvp(forward)/M/dec/moe/moe_shared/shared_fc1/dot_general",
    "fusion.9": "jit(train_step)/optimizer/mul",
    "convert.1": "",
}
WORK = {
    # 2 s an execution: 100 flops are 1 s at the peak, 5 bytes 0.5 s
    "fusion.5": {"flops": 100, "bytes": 5, "pass": "fwd"},
    # 1 s: bound by its bytes (8 / 10 = 0.8 s, its 30 flops 0.3 s)
    "fusion.6": {"flops": 30, "bytes": 8, "pass": "bwd"},
    # 3 s: a kernel states no product
    "flash_fwd.3": {"flops": 0, "bytes": 10, "pass": "remat"},
    # 1 s: 90 flops
    "fusion.7": {"flops": 90, "bytes": 1, "pass": "fwd"},
    # 2 s: 15 bytes are 1.5 s
    "fusion.9": {"flops": 0, "bytes": 15, "pass": ""},
}
EVENTS = [
    (0 * S, 20 * S, WHILE),               # a wrapper: its body is below
    (0 * S, 2 * S, FC1),
    (2 * S, 3 * S, PROJ),
    (3 * S, 6 * S, FWD),
    (6 * S, 7 * S, SHARED),
    (10 * S, 12 * S, ADAM),
    (12 * S, 13 * S, BARE),
    (14 * S, 16 * S, FC1),                # the second update's
]
MODULES = {"/device:TPU:0": [(0 * S, 13 * S, "jit_train_step"),
                             (14 * S, 16 * S, "jit_train_step")]}
THREADS = {
    ("/host:CPU", 1, "python3"): [
        (5 * S, 9 * S, "unicore:train_step", {"update": 7}),
        (9 * S, 9 * S, "unicore:moe_route", {"update": 4, "pairs_here": 10}),
        (9.5 * S, 9.5 * S, "unicore:moe_route", {"update": 5, "pairs_here": 30}),
    ],
    ("/host:CPU", 2, "python3"): [
        (1 * S, 4 * S, "unicore:data_produce", {}),
        (1.5 * S, 1.9 * S, "unicore:data_pack", {"block": 3}),
        (2 * S, 2.2 * S, "unicore:data_pack", {"block": 4}),
        (2.2 * S, 3.2 * S, "unicore:data_pack", {"block": 5}),
        (0 * S, 1 * S, "bench:data", {}),
    ],
}
#: what each new metric reads on the run above
EXPECTED = {
    # 5 s of 12 under a product (fusion.5 twice, fusion.6, fusion.7)
    "xla_matmul_device_pct": 100 * 6 / 12,
    # least: 1 + 1 + 0.8 + 0.9 of those 6 s
    "xla_matmul_roofline_pct": 100 * 3.7 / 6,
    "ffn_roofline_pct": 100 * 2.0 / 4,
    "attn_proj_roofline_pct": 100 * 0.8 / 1,
    "moe_shared_roofline_pct": 100 * 0.9 / 1,
    "optimizer_roofline_pct": 100 * 1.5 / 2,
    "remat_device_pct": 100 * 3 / 12,
    "data_pack_ms": 400.0,
}


def table(work=WORK, paths=PATHS):
    out = {"module": "jit_train_step", "instructions": dict(paths)}
    if work is not None:
        out["work"] = dict(work)
    return out


def run_with(tables):
    """A run as the readers are handed one, its two reductions made from
    the lists above."""
    per_device = {"/device:TPU:0": EVENTS}
    return {
        "trace": {}, "base": BENCH, "peaks": PEAKS,
        "program_trace": trace_scopes.reduce_events(
            per_device, MODULES, THREADS, tables),
        "scope_work": scope_work.reduce_work(
            per_device, MODULES, tables, THREADS),
    }


def test_the_pass_by_hand():
    work = scope_work.reduce_work(
        {"/device:TPU:0": EVENTS}, MODULES, [table()], THREADS)
    assert work["device_op_s"] == pytest.approx(12.0)
    assert work["program_runs"] == 2 and work["stated"]
    rows = {r["path"].split("/")[-2]: r for r in work["rows"] if r["path"]}
    assert rows["fc1"]["calls"] == 2 and rows["fc1"]["seconds"] == pytest.approx(4.0)
    assert rows["fc1"]["flops"] == 100 and rows["fc1"]["pass"] == "fwd"
    # an operation the table does not state has nothing to do
    (bare,) = [r for r in work["rows"] if not r["path"]]
    assert (bare["flops"], bare["bytes"], bare["pass"]) == (0, 0, "")
    # every annotation of the program's by name, the harness's left out
    marks = work["marks"]
    assert set(marks) == {"train_step", "moe_route", "data_produce", "data_pack"}
    assert marks["data_pack"]["n"] == 3
    assert marks["data_pack"]["ms"] == pytest.approx([400.0, 200.0, 1000.0])
    assert marks["moe_route"]["stats"]["pairs_here"] == [10, 30]
    # the line a builder reads: an update's seconds, flops and bytes by
    # scope component, seconds by pass, the annotations
    shown = scope_work.summary(work, PEAKS)
    assert shown["per_update"]["fc1"] == {
        "s": 2.0, "flops": 100, "bytes": 5, "least_s": 1.0}
    assert shown["per_update"]["self_attn"]["s"] == pytest.approx(2.0)
    assert shown["per_update"]["self_attn"]["flops"] == 15
    assert shown["pass_s_per_update"] == pytest.approx(
        {"fwd": 2.5, "bwd": 0.5, "remat": 1.5, "none": 1.5})
    assert shown["marks"]["moe_route"] == {
        "n": 2, "median_ms": 0.0, "update": 4.5, "pairs_here": 20.0}
    assert shown["marks"]["data_pack"]["median_ms"] == pytest.approx(400.0)


NEW_METRICS = sorted(EXPECTED)


def test_the_eight_entries_are_in_the_manifest(checkout):
    """Each of the eight is listed once, keeps the contract's rules a CPU
    can check, names a reader, and reaches the cells that have what it
    reads (``checkout``: conftest.py's, as it is and with an append)."""
    manifest = checkout.manifest
    mine = [m for m in manifest["per_layer"] if m["name"] in EXPECTED]
    assert sorted(m["name"] for m in mine) == NEW_METRICS
    # appended: the 32 entries the benchmark had stand first, as they were
    assert all(m["name"] not in EXPECTED for m in manifest["per_layer"][:32])
    layers = {m["layer"] for m in manifest["per_layer"] if m not in mine}
    cells = [w["name"] for w in manifest["workloads"]]
    for m in mine:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["layer"] in layers and m["moves"] == "train_tokens_per_s"
        assert m["better"] in ("lower", "higher")
        assert m["source"] == ("program_span" if m["name"] == "data_pack_ms"
                               else "device_trace")
        assert m["workloads"] and set(m["workloads"]) <= set(cells)
        harness.find("layer_metrics", m["name"] + ".py")
    lists = {
        cell: {m["name"] for m in
               checkout.cell(cell).metrics("per_layer")} & set(NEW_METRICS)
        for cell in cells
    }
    everywhere = {"xla_matmul_device_pct", "xla_matmul_roofline_pct",
                  "attn_proj_roofline_pct", "optimizer_roofline_pct",
                  "remat_device_pct"}
    assert lists["bert_base.train_mlm512"] == everywhere | {"ffn_roofline_pct"}
    assert lists["nemotron3_super_120b.train_pack8k"] == everywhere | {
        "moe_shared_roofline_pct", "data_pack_ms"}
    assert lists["evabyte.train_pack32k"] == everywhere | {
        "ffn_roofline_pct", "data_pack_ms"}


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_of_each_new_metric(name):
    """Known seconds, flops and bytes give the known share; an untraced
    run gives None; so does a program whose table states no ``work`` (the
    parent commit under this PR's benchmark files, or a recorded table)
    for the seven that read it."""
    reader = harness.load_module("layer_metrics", name)
    assert reader.read(run_with([table()])) == pytest.approx(EXPECTED[name])
    assert reader.read({"trace": None}) is None
    unstated = reader.read(run_with([table(work=None)]))
    if name == "data_pack_ms":  # the span is older than the field
        assert unstated == pytest.approx(400.0)
    else:
        assert unstated is None
    # a program that leaves no table at all: nothing is named
    assert name == "data_pack_ms" or reader.read(run_with([])) is None


def test_a_program_with_scopes_and_nothing_selected_reads_zero():
    """Operations named, ``work`` stated, and no ``fc1`` / ``moe_shared``
    among them (or no block packed): 0, not None."""
    paths = {k: v for k, v in PATHS.items() if k in ("fusion.9", "convert.1")}
    paths["fusion.5"] = "jit(train_step)/jvp(forward)/M/enc/emb_layer_norm/mul"
    run = run_with([table(paths=paths)])
    run["scope_work"]["marks"].pop("data_pack")
    for name in ("ffn_roofline_pct", "moe_shared_roofline_pct",
                 "attn_proj_roofline_pct", "remat_device_pct", "data_pack_ms"):
        assert harness.load_module("layer_metrics", name).read(run) == 0.0


def test_a_recorded_table_without_work_reads_none():
    """The recorded chip run of ``test_trace_scopes.py``: its table file
    predates ``work``, so the pass over the real trace states nothing."""
    from benchmark import reduce

    data = os.path.join(os.path.dirname(__file__), "data")
    path = os.path.join(data, "bert_program_step.xplane.pb")
    profile = reduce._load(path)
    tables, source = trace_scopes.scope_tables(path)
    assert source == "files" and "work" not in tables[0]
    work = scope_work.reduce_work(
        reduce.device_events(profile), trace_scopes.module_events(profile),
        tables, trace_scopes.host_spans(profile))
    assert not work["stated"] and work["program_runs"] == 1
    assert work["device_op_s"] == pytest.approx(0.112061, abs=1e-6)
    assert sum(r["seconds"] for r in work["rows"]) == pytest.approx(
        work["device_op_s"])
    run = {"trace": {}, "base": BENCH, "peaks": harness.peaks_for("TPU v5 lite"),
           "program_trace": trace_scopes.reduce_trace(path), "scope_work": work}
    assert harness.load_module("layer_metrics", "ffn_roofline_pct").read(run) is None
    assert harness.load_module("layer_metrics", "remat_device_pct").read(run) is None
    # with this program's table beside the same events, every product of
    # the step is found: the join is by instruction name
    stated = dict(tables[0], work={
        name: {"flops": 1, "bytes": 1, "pass": "fwd"}
        for name, path_ in tables[0]["instructions"].items() if "/fc1/" in path_
    })
    again = scope_work.reduce_work(
        reduce.device_events(profile), trace_scopes.module_events(profile),
        [stated], trace_scopes.host_spans(profile))
    assert again["stated"]
    assert sum(r["calls"] for r in again["rows"] if r["flops"]) > 12


def test_berts_real_step_states_the_count_by_hand(one_chip, monkeypatch):  # noqa: F811
    """The step of ``bert_base.train_mlm512`` compiled for a described
    v5e: its table's ``flops`` under ``fc1`` / ``fc2`` are three passes
    of twelve layers' two 768 x 3072 products over 16,384 tokens (the
    TPU's ``convolution`` form, counted without a chip); the projections
    the same with four 768 x 768; nothing is rematerialized; Adam's
    operations state bytes and no product."""
    from unicore_tpu.telemetry import hlo_scopes

    cell = harness.Cell(load(os.path.join(ROOT, "BENCHMARK.json")),
                        "bert_base.train_mlm512")
    compiled = rehearsal.compile_step(cell, 512, one_chip, monkeypatch)
    text = compiled.as_text()
    assert " while(" not in text  # no scanned layers: no trip count to apply
    made = hlo_scopes.scope_table(text)
    flops = {"ffn": 0, "attention": 0}
    passes = set()
    optimizer_bytes = 0
    for name, does in made["work"].items():
        path = made["instructions"][name]
        group = trace_scopes.group_of(path)
        if group in flops:
            flops[group] += does["flops"]
        if group == "optimizer":
            assert does["flops"] == 0
            optimizer_bytes += does["bytes"]
        passes.add(does["pass"])
    tokens = 32 * 512
    assert flops["ffn"] == 3 * 12 * 2 * (2 * 768 * 3072) * tokens
    assert flops["ffn"] == 5_566_277_615_616
    assert flops["attention"] == 3 * 12 * 4 * (2 * 768 * 768) * tokens
    assert passes == {"fwd", "bwd", ""}
    # 110 M parameters: fp32 master and two moments read and written,
    # bf16 parameters written; some of it from the core's own memory
    assert 1.5e9 < optimizer_bytes < 4.0e9
