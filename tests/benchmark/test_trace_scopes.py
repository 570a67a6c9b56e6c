"""``benchmark/trace_scopes.py`` and what it reads: on a hand-built event
list, on one program run cut from this PR's first traced chip run of
``bert_base.train_mlm512`` (TPU v5e; ``data/bert_program_step.xplane.pb``
with its scope table ``data/hlo_scopes_jit_train_step.json``: the device's
``Steps`` / ``XLA Modules`` / ``XLA Ops`` lines of one run and the
``unicore:`` / ``bench:`` host spans over it, everything else dropped), on
the annotations a tiny trainer leaves in a CPU capture, and
``flops/kernels.py`` against a hand count."""

import os

import pytest

from bench_tiny import BENCH, ROOT, load, tiny_checkout
from benchmark import harness, reduce, trace_scopes

DATA = os.path.join(os.path.dirname(__file__), "data")
TRACE = os.path.join(DATA, "bert_program_step.xplane.pb")
NEW_METRICS = [
    m["name"] for m in load(os.path.join(ROOT, "BENCHMARK.json"))["per_layer"]
    if m.get("workloads")
]

S = 1e9  # the trace's clock is in ns
FWD = ('%flash_fwd.3 = (bf16[2,4,256,64]{3,2,1,0}, f32[2,4,256,1]{3,2,1,0}) '
       'custom-call(s32[1]{0} %seed, bf16[2,4,256,64]{3,2,1,0} %q, '
       'bf16[2,4,128,64]{3,2,1,0} %k, bf16[2,4,128,64]{3,2,1,0} %v), '
       'custom_call_target="tpu_custom_call", operand_layout_constraints={s32[1]{0}}')
FC1 = "%fusion.5 = bf16[8,8]{1,0} fusion(bf16[8,8]{1,0} %x), kind=kOutput, calls=%fc.1"
ADAM = "%fusion.9 = f32[8]{0} fusion(f32[8]{0} %m), kind=kLoop, calls=%fc.2"
COPY = "%copy.4 = bf16[2,4,256,64]{3,2,1,0} copy(bf16[2,256,4,64]{3,2,1,0} %p)"
BARE = "%convert.1 = f32[8]{0} convert(bf16[8]{0} %y)"
WHILE = "%while.1 = (s32[], f32[2]{0}) while((s32[], f32[2]{0}) %t), body=%b"
TABLE = {"module": "jit_train_step", "instructions": {
    "flash_fwd.3": "jit(train_step)/jvp(forward)/M/enc/layers_0/self_attn/flash_fwd",
    "fusion.5": "jit(train_step)/jvp(forward)/M/enc/layers_1/fc1/dot_general",
    "fusion.9": "jit(train_step)/optimizer/mul",
    "copy.4": "jit(train_step)/jvp(forward)/M/enc/layers_0/self_attn/transpose",
    "convert.1": "",
}}


def test_group_of_and_labels():
    g = trace_scopes.group_of
    assert g("jit(s)/optimizer/mul") == g("jit(s)/clip-grads/x") == "optimizer"
    assert g("jit(s)/multiply-grads/div") == "optimizer"
    assert g("jit(s)/transpose(jvp(forward))/M/e/layers_3/self_attn/in_proj/dot_general") == "attention"
    assert g("jit(s)/jvp(forward)/M/e/layers_3/fc2/dot_general") == "ffn"
    assert g("jit(s)/jvp(forward)/M/lm_head/dense/erf") == "lm_head_loss"
    assert g("jit(s)/jvp(forward)/loss/log_softmax") == "lm_head_loss"
    assert g("jit(s)/jvp(forward)/M/e/emb_layer_norm/mul") == "rest"
    assert g("") == "unattributed"
    assert trace_scopes.scope_label(
        "jit(s)/jvp(forward)/M/e/layers_11/self_attn/transpose"
    ) == "jvp(forward)/M/e/layers_*/self_attn"
    assert trace_scopes.operand_shapes(FWD, "custom-call") == (
        (1,), (2, 4, 256, 64), (2, 4, 128, 64), (2, 4, 128, 64))


def test_innermost_pieces_by_hand():
    spans = [(0.0, 10.0, "a", {}), (1.0, 4.0, "b", {}), (2.0, 3.0, "c", {}),
             (6.0, 7.0, "d", {}), (12.0, 13.0, "e", {})]
    assert trace_scopes.innermost(spans) == [
        (0.0, 1.0, "a"), (1.0, 2.0, "b"), (2.0, 3.0, "c"), (3.0, 4.0, "b"),
        (4.0, 6.0, "a"), (6.0, 7.0, "d"), (7.0, 10.0, "a"), (12.0, 13.0, "e")]


def test_reduction_by_hand():
    events = [
        (0 * S, 20 * S, WHILE),               # wrapper: its body is below
        (0 * S, 2 * S, FC1),
        (2 * S, 5 * S, FWD),
        (5 * S, 6 * S, COPY),                 # gap 6..10
        (10 * S, 12 * S, ADAM),
        (12 * S, 13 * S, BARE),               # gap 13..19
        (19 * S, 20 * S, FWD),
    ]
    modules = {"/device:TPU:0": [(0 * S, 20 * S, "jit_train_step")]}
    main = [
        (5.5 * S, 9 * S, "bench:dispatch", {}),
        (6 * S, 8.5 * S, "unicore:train_step", {"update": 7}),
        (6.5 * S, 7.5 * S, "unicore:prepare", {"update": 7}),
        (6.6 * S, 7.0 * S, "unicore:h2d", {}),
        (7.5 * S, 8.5 * S, "unicore:launch", {"program": "train_step"}),
        (9 * S, 9.5 * S, "unicore:data_next", {"depth": 3}),
        (13 * S, 18 * S, "bench:wait_device", {}),
    ]
    worker = [(1 * S, 4 * S, "unicore:data_produce", {}),
              (6.6 * S, 6.8 * S, "unicore:h2d", {})]  # a prefetcher's: not main's
    threads = {("/host:CPU", 1, "python3"): main,
               ("/host:CPU", 2, "python3"): worker}
    out = trace_scopes.reduce_events(
        {"/device:TPU:0": events}, modules, threads, [TABLE])
    assert out["device_op_s"] == pytest.approx(10.0)
    assert out["program_runs"] == 1
    assert out["groups_s"] == pytest.approx({
        "optimizer": 2.0, "attention": 5.0, "ffn": 2.0, "lm_head_loss": 0.0,
        "rest": 0.0, "unattributed": 1.0})
    assert sum(out["groups_s"].values()) == pytest.approx(out["device_op_s"])
    assert out["kernels_s"] == pytest.approx({"flash_fwd": 4.0})
    assert out["kernel_calls"]["flash_fwd"] == [
        [[[1], [2, 4, 256, 64], [2, 4, 128, 64], [2, 4, 128, 64]], 2]]
    assert out["copy_owners"] == [
        ["jvp(forward)/M/enc/layers_*/self_attn", pytest.approx(1.0)]]
    # gap 6..10: train_step's own 0.5 (6..6.5), prepare 0.6 (less its h2d
    # 0.4), launch 1.0, the harness's dispatch 0.5 (8.5..9), data_next 0.5,
    # nothing 0.5; gap 13..19: wait_device 5, nothing 1
    assert out["idle_s"] == pytest.approx({
        "bench:wait_device": 5.0, "(no span)": 1.5, "unicore:launch": 1.0,
        "unicore:prepare": 0.6, "unicore:train_step": 0.5,
        "bench:dispatch": 0.5, "unicore:data_next": 0.5, "unicore:h2d": 0.4})
    assert out["host"] == pytest.approx({
        "updates": 1, "train_step_ms": 2500.0, "prepare_ms": 1000.0,
        "h2d_ms": 400.0, "launch_ms": 1000.0, "data_depth": 3,
        "data_produce_ms": 3000.0})
    # a program that leaves no table and no spans (the parent commit):
    # kernels by name if it names them, no groups, nothing of the host
    bare = trace_scopes.reduce_events(
        {"/device:TPU:0": events}, modules,
        {("/host:CPU", 1, "python3"): [(13 * S, 18 * S, "bench:wait_device", {})]},
        [])
    assert bare["groups_s"] == {} and bare["host"] == {}
    assert bare["idle_s"] == pytest.approx({"bench:wait_device": 5.0,
                                            "(no span)": 5.0})


@pytest.fixture(scope="module")
def recorded():
    return trace_scopes.reduce_trace(TRACE)


def test_recorded_v5e_run_against_a_hand_reading(recorded):
    """Read by hand from the same file (``reduce.py --describe`` and sums
    over the ``XLA Ops`` events by name): one run of ``jit_train_step``,
    112.18 ms; 4,561 operations, 112.061 ms of them; twelve calls each of
    ``%fullrow_attn_fwd.N`` (4.624 ms) and ``%fullrow_attn_bwd.N``
    (10.324 ms) — BERT-base at 512 takes the full-row kernels, not flash;
    169 ``%copy.N`` (12.851 ms), every one under a ``self_attn`` scope;
    4.708 ms under ``/optimizer/``."""
    out = recorded
    assert out["scope_source"] == "files" and out["program_runs"] == 1
    assert out["device_op_s"] == pytest.approx(0.112061, abs=1e-6)
    assert out["kernels_s"]["fullrow_attn_fwd"] == pytest.approx(4.624e-3, abs=1e-6)
    assert out["kernels_s"]["fullrow_attn_bwd"] == pytest.approx(10.324e-3, abs=1e-6)
    assert [c for _shapes, c in out["kernel_calls"]["fullrow_attn_fwd"]] == [12]
    assert sum(out["groups_s"].values()) == pytest.approx(out["device_op_s"], rel=1e-9)
    assert out["groups_s"]["optimizer"] >= 4.708e-3
    assert out["groups_s"]["unattributed"] < 0.05 * out["device_op_s"]
    assert out["groups_s"]["attention"] > 0.3 * out["device_op_s"]
    assert sum(v for _k, v in out["copy_owners"]) == pytest.approx(12.851e-3, abs=2e-6)
    assert all("self_attn" in k or k == "(no scope)" for k, _v in out["copy_owners"])
    # the host side of that run: one update, its phases nested in it
    host = out["host"]
    assert host["updates"] == 1 and host["data_depth"] == 8
    assert host["h2d_ms"] <= host["prepare_ms"]
    assert host["prepare_ms"] + host["launch_ms"] <= host["train_step_ms"]
    # the gaps and the kernel shares agree with the reducer that is
    # already there
    old = reduce.reduce(TRACE)
    assert sum(out["idle_s"].values()) == pytest.approx(
        old["window_s"] - old["busy_s"], rel=1e-6)
    assert out["idle_s"].get("(no span)", 0.0) < 0.01 * sum(out["idle_s"].values())
    share = (out["kernels_s"]["fullrow_attn_fwd"]
             + out["kernels_s"]["fullrow_attn_bwd"]) / out["device_op_s"]
    assert share == pytest.approx(old["pallas_share"], abs=1e-9)


def test_kernel_flops_by_hand():
    count = harness.load_module("flops", "kernels")
    q, k = (32, 12, 512, 64), (32, 12, 512, 64)
    one = 2.0 * 32 * 12 * 512 * 512 * 64  # one (512 x 64) x (64 x 512) per row and head
    shapes = ((1,), q, k, k, (1, 12, 512, 512), (32, 1, 512))
    assert count.matmul_flops("fullrow_attn_fwd", shapes) == 2 * one
    assert count.matmul_flops("fullrow_attn_bwd", shapes + (q,)) == 5 * one
    assert count.matmul_flops("flash_fwd", shapes) == 2 * one
    assert count.matmul_flops("flash_bwd_dq", shapes) == 3 * one
    assert count.matmul_flops("flash_bwd_dkv", shapes) == 4 * one
    assert count.matmul_flops("flash_bwd_dbias", shapes) == 2 * one
    # keys shorter than queries: Lq x Lk, not Lq squared
    assert count.matmul_flops(
        "flash_fwd", trace_scopes.operand_shapes(FWD, "custom-call")
    ) == 2 * 2.0 * 2 * 4 * 256 * 128 * 64
    with pytest.raises(KeyError):
        count.matmul_flops("softmax_dropout_fwd", shapes)


# EVA's windows as PR 32 hands them to the kernels: 16 windows of 2,048
# queries on 4,096 keys, 16 heads of 128; the map's flat list after the seed
MAPPED_Q, MAPPED_K = (16, 16, 2048, 128), (16, 16, 4096, 128)
MAPPED = ('%flash_fwd.7 = (bf16[16,16,2048,128]{3,2,1,0}, f32[16,16,2048,1]{3,2,1,0}) '
          'custom-call(s32[1]{0} %seed, s32[608]{0} %constant.9, '
          'bf16[16,16,2048,128]{3,2,1,0} %q, bf16[16,16,4096,128]{3,2,1,0} %k, '
          'bf16[16,16,4096,128]{3,2,1,0} %v, bf16[16,1,2048,4096]{3,2,1,0} %bias), '
          'custom_call_target="tpu_custom_call"')
PAIRS = 608 * 256 * 512  # what unicore:eva_keys states: visited blocks, whole


@pytest.mark.parametrize("kernel,products,items", [
    ("flash_fwd", 2, 608), ("flash_bwd_dq", 3, 608), ("flash_bwd_dkv", 4, 636),
])
def test_kernel_flops_of_a_mapped_and_an_unmapped_call(kernel, products, items):
    """A call under a block map counts the pairs the program states it
    scores, whatever the list's dead items; the same operands without the
    map count every block, as before; a mapped call nothing is stated of,
    or of which more is stated than the dense call has, counts nothing."""
    count = harness.load_module("flops", "kernels")
    bias = (16, 1, 2048, 4096)
    dense = ((1,), MAPPED_Q, MAPPED_K, MAPPED_K, bias)
    mapped = ((1,), (items,), MAPPED_Q, MAPPED_K, MAPPED_K, bias)
    every = 2.0 * 16 * 16 * 2048 * 4096 * 128 * products
    assert count.map_items(dense) is None and count.map_items(mapped) == items
    assert count.matmul_flops(kernel, dense) == every
    assert count.matmul_flops(kernel, dense, PAIRS) == every  # no map: unused
    got = count.matmul_flops(kernel, mapped, PAIRS)
    assert got == 2.0 * 16 * PAIRS * 128 * products
    assert got / every == pytest.approx(608 / 1024)
    assert count.matmul_flops(kernel, mapped) is None
    assert count.matmul_flops(kernel, mapped, 2 * 16 * 2048 * 4096) is None


def test_kernels_roofline_takes_a_mapped_calls_pairs_from_the_programs_mark():
    events = [(0 * S, 2 * S, MAPPED), (2 * S, 3 * S, FWD)]
    modules = {"/device:TPU:0": [(0 * S, 3 * S, "jit_train_step")]}
    mark = lambda update: (
        4 * S, 4 * S, "unicore:eva_keys",
        {"update": update, "keys_computed": str(PAIRS), "keys_visible": 1})
    threads = {("/host:CPU", 1, "python3"): [
        (3 * S, 5 * S, "unicore:train_step", {"update": 7}), mark(4), mark(5)]}
    out = trace_scopes.reduce_events(
        {"/device:TPU:0": events}, modules, threads, [])
    assert out["mapped_pairs"] == PAIRS
    assert trace_scopes.operand_shapes(MAPPED, "custom-call")[:3] == (
        (1,), (608,), MAPPED_Q)
    run = {"program_trace": out, "base": BENCH,
           "peaks": {"bf16_flops_per_s": 1e12}}
    flops = (2.0 * 16 * PAIRS * 128 * 2          # the mapped call
             + 2.0 * 2 * 4 * 256 * 128 * 64 * 2)  # the unmapped one, whole
    fwd = harness.load_module("layer_metrics", "attn_kernel_fwd_roofline_pct")
    assert fwd.read(run) == pytest.approx(100 * flops / 3.0 / 1e12)
    # no mark states the map's pairs (another program's trace): nothing is
    # reported, never the dense count
    unstated = trace_scopes.reduce_events(
        {"/device:TPU:0": events}, modules, {}, [])
    assert unstated["mapped_pairs"] is None
    assert fwd.read(dict(run, program_trace=unstated)) is None
    # without a mapped call the same run reads as before
    plain = trace_scopes.reduce_events(
        {"/device:TPU:0": events[1:]}, modules, {}, [])
    assert fwd.read(dict(run, program_trace=plain)) == pytest.approx(
        100 * 2.0 * 2 * 4 * 256 * 128 * 64 * 2 / 1.0 / 1e12)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_of_each_new_metric(recorded, name):
    """With the recorded run's reduction every reader finds its number;
    with no trace, or a program that leaves nothing to read (the parent
    commit under this PR's benchmark files), it returns None."""
    reader = harness.load_module("layer_metrics", name)
    peaks = harness.peaks_for("TPU v5 lite")
    run = {"trace": {}, "program_trace": recorded, "base": BENCH, "peaks": peaks}
    value = reader.read(run)
    assert value is not None and value >= 0
    if name.endswith("_pct"):
        assert value <= 100
    assert reader.read({"trace": None}) is None
    empty = dict(recorded, groups_s={}, kernels_s={}, kernel_calls={}, host={})
    assert reader.read(dict(run, program_trace=empty)) is None


def test_recorded_run_in_the_metrics_own_terms(recorded):
    peaks = harness.peaks_for("TPU v5 lite")
    run = {"trace": {}, "program_trace": recorded, "base": BENCH, "peaks": peaks}
    read = {n: harness.load_module("layer_metrics", n).read(run)
            for n in NEW_METRICS}
    parts = ["attention_device_pct", "ffn_device_pct", "lm_head_loss_device_pct",
             "optimizer_share_pct", "unattributed_device_pct"]
    rest = 100.0 * recorded["groups_s"]["rest"] / recorded["device_op_s"]
    assert sum(read[p] for p in parts) + rest == pytest.approx(100.0, abs=1e-6)
    # 12 calls x 2 products x 2 x 32 x 12 x 512 x 512 x 64 operations in
    # 4.624 ms: 66.9 TFLOP/s of 197; backward 5 products in 10.324 ms: 74.9
    assert read["attn_kernel_fwd_roofline_pct"] == pytest.approx(33.95, abs=0.05)
    assert read["attn_kernel_bwd_roofline_pct"] == pytest.approx(38.01, abs=0.05)
    assert read["attn_kernel_fwd_device_pct"] + read["attn_kernel_bwd_device_pct"] \
        == pytest.approx(13.338, abs=0.01)
    assert read["step_h2d_ms"] + read["step_launch_ms"] <= read["step_host_ms"]
    assert read["data_buffer_depth"] == 8


# -- what a tiny trainer leaves in a capture on this CPU ------------------------

@pytest.fixture(scope="module")
def captured(tmp_path_factory):
    """Three warm updates, then three more under ``jax.profiler``: the
    trace through ``ProfileData`` and the scope tables the program left."""
    import jax
    import tempfile

    from benchmark.drivers import train
    from unicore_tpu import telemetry
    from unicore_tpu.losses import LOSS_REGISTRY
    from unicore_tpu.models import ARCH_MODEL_REGISTRY
    from unicore_tpu.trainer import Trainer

    tmp = tmp_path_factory.mktemp("capture")
    root, base = tiny_checkout(tmp, "bert_base.train_mlm512")
    cell = harness.Cell(harness.load_manifest(root), "bert_base.train_mlm512",
                        base, root)
    work = tempfile.mkdtemp(dir=str(tmp))
    args, task, batches, shaped, _pad = train.open_feed(cell, 5, work)
    model = train.seeded_model_class(
        ARCH_MODEL_REGISTRY[cell.config["arch"]], 5).build_model(args, task)
    trainer = Trainer(args, task, model, LOSS_REGISTRY[cell.config["loss"]](task))
    telemetry.hlo_scopes.reset()
    for _ in range(3):
        trainer.train_step([shaped(next(batches))[0]])
    totals_before = dict(telemetry.spans.recorder()._totals)
    jax.profiler.start_trace(str(tmp / "trace"))
    try:
        for _ in range(3):
            trainer.train_step([shaped(next(batches))[0]])
        jax.block_until_ready(trainer.state["params"])
    finally:
        jax.profiler.stop_trace()
    assert telemetry.spans.recorder()._totals == totals_before == {}
    tables = telemetry.hlo_scopes.tables()
    text = telemetry.hlo_scopes._texts[0]
    telemetry.hlo_scopes.reset()
    found = [os.path.join(d, f) for d, _s, fs in os.walk(str(tmp / "trace"))
             for f in fs if f.endswith(".xplane.pb")]
    return {"threads": trace_scopes.host_spans(reduce._load(found[0])),
            "tables": tables, "hlo": text}


def test_the_program_spans_reach_the_capture(captured):
    threads = captured["threads"]
    main = trace_scopes.training_thread(threads)
    spans = threads[main]
    steps = [s for s in spans if s[2] == "unicore:train_step"]
    assert [s[3]["update"] for s in steps] == [3, 4, 5]
    for a, b, _name, _stats in steps:
        inside = {s[2] for s in spans if a <= s[0] and s[1] <= b}
        assert {"unicore:prepare", "unicore:h2d", "unicore:launch"} <= inside
    launches = [s for s in spans if s[2] == "unicore:launch"]
    assert {s[3]["program"] for s in launches} == {"train_step"}
    nexts = [s for s in spans if s[2] == "unicore:data_next"]
    assert len(nexts) == 3 and all(s[3]["depth"] >= 0 for s in nexts)
    host = trace_scopes.host_phases(threads, main)
    assert host["updates"] == 3
    assert host["h2d_ms"] <= host["prepare_ms"] <= host["train_step_ms"]
    assert host["launch_ms"] <= host["train_step_ms"]
    # update by update the two phases fit inside their step (their MEDIANS
    # over three updates need not add up under a loaded host: 10.8 + 25.0
    # against 28.4 ms was read here once)
    for a, b, _name, _stats in steps:
        phases = sum(s[1] - s[0] for s in spans if a <= s[0] and s[1] <= b
                     and s[2] in ("unicore:prepare", "unicore:launch"))
        assert phases <= b - a


def test_the_scope_table_maps_every_entry_instruction(captured):
    import re

    (table,) = captured["tables"]
    assert table["module"] == "jit_train_step"
    entry = captured["hlo"][captured["hlo"].index("\nENTRY "):]
    entry = entry[:entry.index("\n}")]
    names = re.findall(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=", entry, re.MULTILINE)
    assert len(names) > 100
    assert set(names) <= set(table["instructions"])
    groups = {trace_scopes.group_of(p) for p in table["instructions"].values()}
    assert {"optimizer", "attention", "ffn", "lm_head_loss", "rest"} <= groups
