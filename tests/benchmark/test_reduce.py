"""``benchmark/reduce.py``: on intervals built by hand, and on one program
run cut from PR 24's first traced chip run of ``bert_base.train_mlm512``
(TPU v5e; ``data/bert_one_step.xplane.pb``: the device's ``XLA Ops`` line
and the harness's host spans, everything else dropped)."""

import os

import pytest

from benchmark import reduce

TRACE = os.path.join(os.path.dirname(__file__), "data", "bert_one_step.xplane.pb")

MOSAIC = ('%self_attn.3 = bf16[2,2]{1,0} custom-call(bf16[2,2]{1,0} %x), '
          'custom_call_target="tpu_custom_call"')
FUSION = "%fusion.12 = (f32[2]{0}, bf16[2]{0:T(8,128)(2,1)}) fusion(f32[2]{0} %custom-call.9), kind=kLoop"
WHILE = "%while.1 = (s32[], f32[2]{0}) while((s32[], f32[2]{0}) %tuple.1), body=%b"


def test_parse_op():
    assert reduce.parse_op(MOSAIC) == ("self_attn", "custom-call", True)
    # a fusion that merely takes a custom call's result is not a kernel,
    # and a tuple result type does not hide its opcode
    assert reduce.parse_op(FUSION) == ("fusion", "fusion", False)
    assert reduce.parse_op(WHILE)[1] == "while" and reduce.is_wrapper(WHILE)
    assert reduce.parse_op("%copy.570 = s32[4]{0} copy(s32[4]{0} %p)")[0] == "copy"


def test_union_gaps_and_attribution_by_hand():
    s = 1e9  # the trace's clock is in ns
    events = [
        (0 * s, 10 * s, WHILE),              # wrapper: its body is below
        (0 * s, 2 * s, FUSION),
        (1 * s, 3 * s, MOSAIC),              # overlaps the fusion: union 0..3
        (5 * s, 6 * s, FUSION),              # gap 3..5
        (9 * s, 10 * s, MOSAIC),             # gap 6..9
    ]
    spans = [(2.5 * s, 4.9 * s, "data"), (5.5 * s, 7 * s, "dispatch"),
             (7 * s, 9.5 * s, "wait_device")]
    out = reduce.reduce_events({"/device:TPU:0": events}, spans)
    assert out["busy_s"] == pytest.approx(5.0)       # 0..3, 5..6, 9..10
    assert out["window_s"] == pytest.approx(10.0)
    assert dict(map(tuple, out["idle_gaps"])) == pytest.approx(
        {"data": 2.0, "wait_device": 3.0}            # 6..9: wait covers 2 of 3
    )
    assert dict(map(tuple, out["device_ops"])) == pytest.approx(
        {"fusion": 3.0, "pallas:self_attn": 3.0}
    )
    assert out["pallas_share"] == pytest.approx(0.5)
    # two chips: every figure is the mean over the chips used
    two = reduce.reduce_events(
        {"/device:TPU:0": events, "/device:TPU:1": [(0, 10 * s, FUSION)]}, spans
    )
    assert two["busy_s"] == pytest.approx(7.5) and two["devices"] == 2


def test_recorded_v5e_step_against_a_hand_reading():
    """Read by hand from the same file with ``--describe``: the ``Steps``
    line gives one run of ``jit_train_step`` as 112.17 ms; its operations
    leave 0.11 ms uncovered; the flash kernels (``%self_attn.N``, Mosaic
    custom calls) take 14.95 ms of it, plain ``fusion`` operations the
    most."""
    out = reduce.reduce(TRACE)
    assert out["devices"] == 1
    assert out["window_s"] == pytest.approx(0.11217, abs=2e-4)
    assert out["busy_s"] == pytest.approx(0.11206, abs=2e-4)
    assert 0.0 < 1 - out["busy_s"] / out["window_s"] < 0.003
    ops = dict(map(tuple, out["device_ops"]))
    assert out["device_ops"][0][0] == "fusion"
    assert ops["pallas:self_attn"] == pytest.approx(0.01495, abs=3e-4)
    assert out["pallas_share"] == pytest.approx(0.1334, abs=3e-3)
    assert {name for name, _ in out["idle_gaps"]} <= {
        "dispatch", "wait_device", "data", "fetch", "(no span)"}


def test_a_trace_without_device_operations_is_refused(tmp_path):
    import jax

    with jax.profiler.trace(str(tmp_path)):
        jax.numpy.ones(4).sum().block_until_ready()
    path = next(
        os.path.join(d, f) for d, _s, fs in os.walk(tmp_path) for f in fs
        if f.endswith(".xplane.pb")
    )
    with pytest.raises(ValueError, match="no device operations"):
        reduce.reduce(path)
