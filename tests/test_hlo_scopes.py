"""``telemetry/hlo_scopes.py``: the table from a compiled program's HLO
text, what is kept inside and outside a profiler capture, and the files an
operator's capture leaves."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from unicore_tpu.telemetry import hlo_scopes

HLO = """HloModule jit_step, entry_computation_layout={(f32[4]{0})->f32[4]{0}}

%fused_computation (p.1: f32[4]) -> f32[4] {
  %p.1 = f32[4]{0} parameter(0)
  ROOT %inner.1 = f32[4]{0} multiply(%p.1, %p.1), metadata={op_name="jit(step)/inside/mul"}
}

%add_f32 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %sum = f32[] add(%a, %b)
}

%body (c: (s32[], f32[4])) -> (s32[], f32[4]) {
  %c = (s32[], f32[4]{0}) parameter(0)
  %in_loop.2 = f32[4]{0} negate(%x), metadata={op_name="jit(step)/forward/M/layers_0/fc1/neg"}
  ROOT %t = (s32[], f32[4]{0}) tuple(%i, %in_loop.2)
}

ENTRY %main.5 (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0)
  %fusion.7 = f32[4]{0} fusion(%x), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/optimizer/mul" source_file="a.py" source_line=3}
  %reduce.1 = f32[] reduce(%fusion.7, %zero), dimensions={0}, to_apply=%add_f32, metadata={op_name="jit(step)/clip-grads/reduce_sum"}
  %while.3 = (s32[], f32[4]{0}) while(%init), condition=%cond, body=%body
  ROOT %copy.9 = f32[4]{0} copy(%fusion.7)
}
"""


@pytest.fixture(autouse=True)
def fresh():
    hlo_scopes.reset()
    yield
    hlo_scopes.reset()


def test_table_by_hand():
    table = hlo_scopes.scope_table(HLO)
    assert table["module"] == "jit_step"
    ins = table["instructions"]
    # a fusion by its own metadata; a loop body's operations are the
    # device's own events; an instruction without metadata maps to ""
    assert ins["fusion.7"] == "jit(step)/optimizer/mul"
    assert ins["reduce.1"] == "jit(step)/clip-grads/reduce_sum"
    assert ins["in_loop.2"] == "jit(step)/forward/M/layers_0/fc1/neg"
    assert ins["copy.9"] == "" and ins["while.3"] == ""
    # the insides of fusions and reducers are their caller's
    assert "inner.1" not in ins and "sum" not in ins


def step(w, x):
    with jax.named_scope("forward"):
        loss = jnp.sum(jnp.tanh(x @ w))
    with jax.named_scope("optimizer"):
        return w - 0.1 * loss


def test_outside_a_capture_nothing_is_kept():
    fn = jax.jit(step)
    args = (jnp.ones((4, 4)), jnp.ones((2, 4)))
    fn(*args)
    hlo_scopes.note_launch("step", fn, args)
    assert hlo_scopes.tables() == []


def test_in_a_capture_each_program_is_kept_once(tmp_path):
    fn = jax.jit(step)
    small = (jnp.ones((4, 4)), jnp.ones((2, 4)))
    wide = (jnp.ones((4, 4)), jnp.ones((8, 4)))
    fn(*small), fn(*wide)
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        for args in (small, small, wide, small):
            hlo_scopes.note_launch("step", fn, args)
    finally:
        jax.profiler.stop_trace()
    tables = hlo_scopes.tables()
    assert [t["module"] for t in tables] == ["jit_step", "jit_step"]
    scopes = set(tables[0]["instructions"].values())
    assert any("/optimizer/" in s for s in scopes)
    assert any("/forward/" in s for s in scopes)
    # the files an operator's capture leaves: one per program
    paths = hlo_scopes.write_tables(str(tmp_path))
    assert [os.path.basename(p) for p in paths] == [
        "hlo_scopes_jit_step.json", "hlo_scopes_jit_step.1.json"]
    assert json.load(open(paths[0])) == tables[0]
    # the capture is over: the next one keeps its programs anew
    hlo_scopes.note_launch("step", fn, small)
    jax.profiler.start_trace(str(tmp_path / "trace2"))
    try:
        hlo_scopes.note_launch("step", fn, small)
    finally:
        jax.profiler.stop_trace()
    assert len(hlo_scopes.tables()) == 1
