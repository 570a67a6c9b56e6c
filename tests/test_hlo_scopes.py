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


# -- ``work``: what each operation has to do ------------------------------------

TILED = "{1,0:T(8,128)(2,1)}"
WORK_HLO = f"""HloModule jit_work, entry_computation_layout={{(f32[4,8]{{1,0}})->f32[4,16]{{1,0}}}}

%as_kernel (p.9: bf16[8,16]) -> bf16[8,16,1] {{
  %p.9 = bf16[8,16]{TILED} parameter(0)
  ROOT %b.9 = bf16[8,16,1]{{1,0,2:T(8,128)(2,1)}} bitcast(%p.9)
}}

%dot_body (a: f32[4,8], w: f32[8,16]) -> f32[4,16] {{
  %a = f32[4,8]{{1,0}} parameter(0)
  %w = f32[8,16]{{1,0}} parameter(1)
  %d = f32[4,16]{{1,0}} dot(%a, %w), lhs_contracting_dims={{1}}, rhs_contracting_dims={{0}}
  ROOT %t = f32[4,16]{{1,0}} tanh(%d)
}}

%conv_body (x: bf16[2,32,8], k: bf16[8,16]) -> bf16[2,32,16] {{
  %x = bf16[2,32,8]{{2,1,0:T(8,128)(2,1)}} parameter(0)
  %k = bf16[8,16]{TILED} parameter(1)
  %kb = bf16[8,16,1]{{1,0,2:T(8,128)(2,1)}} fusion(%k), kind=kLoop, calls=%as_kernel
  ROOT %convolution.1 = bf16[2,32,16]{{2,1,0:T(8,128)(2,1)}} convolution(%x, %kb), window={{size=1}}, dim_labels=0bf_io0->0bf
}}

%two_results (g: f32[4]) -> (f32[4], bf16[4]) {{
  %g = f32[4]{{0}} parameter(0)
  %h = bf16[4]{{0}} convert(%g)
  ROOT %both = (f32[4]{{0}}, bf16[4]{{0}}) tuple(%g, %h)
}}

%one_layer (stack: bf16[5,8,16], i: s32[]) -> bf16[8,16] {{
  %stack = bf16[5,8,16]{{2,1,0:T(8,128)(2,1)}} parameter(0)
  %i = s32[]{{:T(128)S(6)}} parameter(1)
  %zero = s32[]{{:T(128)}} constant(0)
  %cut = bf16[1,8,16]{{2,1,0:T(8,128)(2,1)}} dynamic-slice(%stack, %i, %zero, %zero), dynamic_slice_sizes={{1,8,16}}
  ROOT %layer = bf16[8,16]{TILED} bitcast(%cut)
}}

%into_stack (out: f32[5,4,16], i.1: s32[], y: f32[4,16]) -> f32[5,4,16] {{
  %out = f32[5,4,16]{{2,1,0}} parameter(0)
  %i.1 = s32[]{{:T(128)S(6)}} parameter(1)
  %y = f32[4,16]{{1,0}} parameter(2)
  %row = f32[1,4,16]{{2,1,0}} bitcast(%y)
  %zero.1 = s32[]{{:T(128)}} constant(0)
  ROOT %put = f32[5,4,16]{{2,1,0}} dynamic-update-slice(%out, %row, %i.1, %zero.1, %zero.1)
}}

%body (c: (s32[], bf16[5,8,16], f32[5,4,16], f32[4,16])) -> (s32[], bf16[5,8,16], f32[5,4,16], f32[4,16]) {{
  %c = (s32[]{{:T(128)}}, bf16[5,8,16]{{2,1,0:T(8,128)(2,1)}}, f32[5,4,16]{{2,1,0}}, /*index=3*/f32[4,16]{{1,0}}) parameter(0)
  %n = s32[]{{:T(128)}} get-tuple-element(%c), index=0
  %ws = bf16[5,8,16]{{2,1,0:T(8,128)(2,1)}} get-tuple-element(%c), index=1
  %ys = f32[5,4,16]{{2,1,0}} get-tuple-element(%c), index=2
  %act = f32[4,16]{{1,0}} get-tuple-element(%c), index=3
  %slice_fusion.1 = bf16[8,16]{TILED} fusion(%ws, %n), kind=kLoop, calls=%one_layer, metadata={{op_name="jit(work)/jvp(forward)/M/while/body/squeeze"}}
  %stack_fusion.1 = f32[5,4,16]{{2,1,0}} fusion(%ys, %n, %act), kind=kLoop, calls=%into_stack, metadata={{op_name="jit(work)/jvp(forward)/M/while/body/dynamic_update_slice"}}
  ROOT %next = (s32[]{{:T(128)}}, bf16[5,8,16]{{2,1,0:T(8,128)(2,1)}}, f32[5,4,16]{{2,1,0}}, /*index=3*/f32[4,16]{{1,0}}) tuple(%n, %ws, %stack_fusion.1, %act)
}}

ENTRY %main (a.1: f32[4,8], w.1: f32[8,16], x.1: bf16[2,32,8], k.1: bf16[8,16], g.1: f32[4]) -> f32[4,16] {{
  %a.1 = f32[4,8]{{1,0}} parameter(0)
  %w.1 = f32[8,16]{{1,0}} parameter(1)
  %x.1 = bf16[2,32,8]{{2,1,0:T(8,128)(2,1)}} parameter(2)
  %k.1 = bf16[8,16]{{1,0:T(8,128)(2,1)S(1)}} parameter(3)
  %g.1 = f32[4]{{0}} parameter(4)
  %fusion.1 = f32[4,16]{{1,0}} fusion(%a.1, %w.1), kind=kOutput, calls=%dot_body, metadata={{op_name="jit(work)/jvp(forward)/M/layers_0/fc1/dot_general" stack_frame_id=3}}
  %fusion.2 = bf16[2,32,16]{{2,1,0:T(8,128)(2,1)}} fusion(%x.1, %k.1), kind=kOutput, calls=%conv_body, metadata={{op_name="jit(work)/transpose(jvp(forward))/M/checkpoint/layers_0/fc2/dot_general"}}
  %flash_fwd.3 = bf16[2,32,8]{{2,1,0:T(8,128)(2,1)}} custom-call(%x.1, %x.1), custom_call_target="tpu_custom_call", metadata={{op_name="jit(work)/transpose(jvp(forward))/M/checkpoint/rematted_computation/layers_0/self_attn/flash_fwd"}}
  %fusion.4 = (f32[4]{{0}}, bf16[4]{{0:T(512)S(1)}}) fusion(%g.1), kind=kLoop, calls=%two_results, metadata={{op_name="jit(work)/optimizer/convert_element_type"}}
  %while.5 = (s32[]{{:T(128)}}, bf16[5,8,16]{{2,1,0:T(8,128)(2,1)}}, f32[5,4,16]{{2,1,0}}, /*index=3*/f32[4,16]{{1,0}}) while(%init), condition=%cond, body=%body
  ROOT %copy.6 = f32[4,16]{{1,0}} copy(%fusion.1)
}}
"""


def test_work_by_hand():
    table = hlo_scopes.scope_table(WORK_HLO)
    work = table["work"]
    assert set(work) <= set(table["instructions"])
    # a ``dot`` in a fusion: 2 x 4 x 16 x 8; operands and result in bytes
    assert work["fusion.1"] == {
        "flops": 2 * 4 * 16 * 8, "bytes": 4 * (4 * 8 + 8 * 16 + 4 * 16),
        "pass": "fwd"}
    # the TPU's form, through a nested fusion and past the tiling suffix:
    # result 2 x 32 x 16, contraction 8, the window's one position; the
    # kernel operand is in the core's memory (``S(1)``) and moves nothing;
    # a bare ``checkpoint`` component is backward
    assert work["fusion.2"] == {
        "flops": 2 * 2 * 32 * 16 * 8, "bytes": 2 * (2 * 32 * 8 + 2 * 32 * 16),
        "pass": "bwd"}
    # a Mosaic kernel: no product the program can see, an operand used
    # twice counted once, ``rematted_computation`` before ``transpose(``
    assert work["flash_fwd.3"] == {
        "flops": 0, "bytes": 2 * 2 * (2 * 32 * 8), "pass": "remat"}
    # a tuple result: its leaves summed, the one in HBM only; no pass
    assert work["fusion.4"] == {"flops": 0, "bytes": 4 * 4 + 4 * 4, "pass": ""}
    # a scan's body: the layer cut out of the stacked parameter counts
    # (not the five layers), the layer written into the stacked output
    # counts (and the buffer it is written into is not read); 4 bytes of
    # loop index each
    assert work["slice_fusion.1"]["bytes"] == 2 * (8 * 16) * 2 + 4
    assert work["stack_fusion.1"]["bytes"] == 4 * (4 * 16) * 2 + 4
    assert work["slice_fusion.1"]["pass"] == "fwd"
    # wrappers and plumbing state nothing; a copy without metadata its bytes
    assert "while.5" not in work and "n" not in work and "next" not in work
    assert work["copy.6"] == {"flops": 0, "bytes": 2 * 4 * 4 * 16, "pass": ""}
    # the insides of fusions are their caller's
    assert "convolution.1" not in work and "d" not in work
    # what an older table held is still there, and the file round-trips
    assert table["instructions"]["fusion.4"] == "jit(work)/optimizer/convert_element_type"
    assert json.loads(json.dumps(table)) == table


def test_bytes_of_what_reads_or_writes_a_part():
    """A static ``slice`` and a ``gather`` read what they return, a
    ``scatter`` reads and writes the rows it adds to, and the compiler's
    own buffer bookkeeping moves nothing."""
    text = """HloModule parts

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %s = f32[] add(%a, %b)
}

%rows_out (table: f32[64,8], idx: s32[4]) -> f32[4,8] {
  %table = f32[64,8]{1,0} parameter(0)
  %idx = s32[4]{0} parameter(1)
  ROOT %g = f32[4,8]{1,0} gather(%table, %idx), offset_dims={1}, collapsed_slice_dims={0}, start_index_map={0}, index_vector_dim=1, slice_sizes={1,8}
}

%rows_in (acc: f32[64,8], idx.1: s32[4], upd: f32[4,8]) -> f32[64,8] {
  %acc = f32[64,8]{1,0} parameter(0)
  %idx.1 = s32[4]{0} parameter(1)
  %upd = f32[4,8]{1,0} parameter(2)
  ROOT %sc = f32[64,8]{1,0} scatter(%acc, %idx.1, %upd), update_window_dims={1}, inserted_window_dims={0}, scatter_dims_to_operand_dims={0}, index_vector_dim=1, to_apply=%add
}

ENTRY %main (big: f32[64,8], i: s32[4], u: f32[4,8]) -> f32[64,8] {
  %big = f32[64,8]{1,0} parameter(0)
  %i = s32[4]{0} parameter(1)
  %u = f32[4,8]{1,0} parameter(2)
  %slice.1 = f32[4,8]{1,0} slice(%big), slice={[0:4], [0:8]}
  %fusion.2 = f32[4,8]{1,0} fusion(%big, %i), kind=kCustom, calls=%rows_out
  %custom-call.3 = f32[64,8]{1,0} custom-call(), custom_call_target="AllocateBuffer"
  ROOT %fusion.4 = f32[64,8]{1,0} fusion(%big, %i, %u), kind=kCustom, calls=%rows_in
}
"""
    work = hlo_scopes.scope_table(text)["work"]
    rows = 4 * 8 * 4  # four rows of eight float32
    assert work["slice.1"]["bytes"] == 2 * rows
    assert work["fusion.2"]["bytes"] == rows + 16 + rows
    assert work["fusion.4"]["bytes"] == rows + 16 + rows + rows
    assert "custom-call.3" not in work


def test_bytes_a_prefetch_brings_count_for_who_waits_for_them():
    """An array the compiler's asynchronous slices bring from HBM into the
    core's memory (``S(1)``) crosses the memory's interface for the first
    operation that uses it: the consumer states those bytes and the
    ``-start`` halves none.  An array another fusion made in the core's
    memory moves nothing, and a copy within HBM stays on its ``-start``:
    read once, written once."""
    core = "{1,0:T(8,128)(2,1)S(1)}"
    text = f"""HloModule fetch

%quarter (p: bf16[8,16]) -> bf16[2,16] {{
  %p = bf16[8,16]{TILED} parameter(0)
  ROOT %s = bf16[2,16]{core} slice(%p), slice={{[0:2], [0:16]}}
}}

%adam (g: f32[4,16], m: bf16[4,16]) -> f32[4,16] {{
  %g = f32[4,16]{{1,0}} parameter(0)
  %m = bf16[4,16]{core} parameter(1)
  %mf = f32[4,16]{{1,0}} convert(%m)
  ROOT %new = f32[4,16]{{1,0}} add(%g, %mf)
}}

%in_core (g.2: f32[4,16]) -> f32[4,16] {{
  %g.2 = f32[4,16]{{1,0}} parameter(0)
  ROOT %sq = f32[4,16]{{1,0:S(1)}} multiply(%g.2, %g.2)
}}

ENTRY %main (m.1: bf16[8,16], g.1: f32[4,16]) -> f32[4,16] {{
  %m.1 = bf16[8,16]{TILED} parameter(0)
  %g.1 = f32[4,16]{{1,0}} parameter(1)
  %slice-start.1 = ((bf16[8,16]{TILED}), bf16[2,16]{core}, s32[]{{:S(2)}}) async-start(%m.1), calls=%quarter
  %slice-start.2 = ((bf16[8,16]{TILED}), bf16[2,16]{core}, s32[]{{:S(2)}}) async-start(%m.1), calls=%quarter
  %copy-start.3 = (f32[4,16]{{1,0}}, f32[4,16]{{1,0}}, u32[]{{:S(2)}}) copy-start(%g.1)
  %slice-done.1 = bf16[2,16]{core} async-done(%slice-start.1)
  %slice-done.2 = bf16[2,16]{core} async-done(%slice-start.2)
  %custom-call.1 = bf16[4,16]{core} custom-call(%slice-done.1, %slice-done.2), custom_call_target="ConcatBitcast"
  %fusion.1 = f32[4,16]{{1,0}} fusion(%g.1, %custom-call.1), kind=kLoop, calls=%adam, metadata={{op_name="jit(f)/optimizer/add"}}
  %fusion.2 = f32[4,16]{{1,0:S(1)}} fusion(%g.1), kind=kLoop, calls=%in_core
  %fusion.3 = f32[4,16]{{1,0}} fusion(%fusion.2, %custom-call.1), kind=kLoop, calls=%adam
  ROOT %copy-done.3 = f32[4,16]{{1,0}} copy-done(%copy-start.3)
}}
"""
    work = hlo_scopes.scope_table(text)["work"]
    f32, quarter = 4 * 4 * 16, 2 * 2 * 16
    # the gradient read, the result written, and the two quarters fetched
    assert work["fusion.1"]["bytes"] == 2 * f32 + 2 * quarter
    assert "slice-start.1" not in work and "slice-start.2" not in work
    assert "slice-done.1" not in work and "custom-call.1" not in work
    # made in the core's memory: its maker writes nothing to HBM, and its
    # user, the second to use the fetched array, reads nothing from it
    assert work["fusion.2"]["bytes"] == f32
    assert work["fusion.3"]["bytes"] == f32
    assert work["copy-start.3"]["bytes"] == 2 * f32
    assert "copy-done.3" not in work


@pytest.mark.parametrize("seed", range(6))
def test_window_pairs_in_closed_form(seed):
    """The count per window position agrees with stepping through every
    output position, whatever the stride, padding and dilations."""
    import random

    rng = random.Random(seed)
    for _ in range(300):
        lhs, out, size = (rng.randint(1, 24) for _ in range(3))
        stride, ldil, rdil = rng.randint(1, 7), rng.randint(1, 6), rng.randint(1, 4)
        pad = rng.randint(-3, 9)
        top, want = (lhs - 1) * ldil, 0
        for k in range(size):
            for o in range(out):
                at = k * rdil - pad + o * stride
                want += 0 <= at <= top and at % ldil == 0
        assert hlo_scopes._window_pairs(
            lhs, out, size, stride, pad, ldil, rdil) == want
    # a long sequence under a wide filter costs what a short one does
    assert hlo_scopes._window_pairs(10 ** 9, 10 ** 9, 4, 1, 3, 1, 1) \
        == 4 * 10 ** 9 - 6


def _conv(out, lhs, rhs, attrs):
    """The flops of one ``convolution`` line with operands of those types."""
    text = f"""HloModule m

ENTRY %main (l: {lhs}, r: {rhs}) -> {out} {{
  %l = {lhs}{{1,0:T(8,128)(2,1)}} parameter(0)
  %r = {rhs}{{1,0:T(8,128)(2,1)}} parameter(1)
  ROOT %c.1 = {out}{{1,0:T(8,128)(2,1)}} convolution(%l, %r), {attrs}
}}
"""
    return hlo_scopes.scope_table(text)["work"]["c.1"]["flops"]


@pytest.mark.parametrize("out,lhs,rhs,attrs,macs", [
    # x @ w as the TPU compiler writes it, and dw = x^T @ dy (the
    # contraction is then the lhs's batch-labelled 32)
    ("bf16[32,16]", "bf16[32,8]", "bf16[8,16]", "dim_labels=bf_io->bf",
     32 * 16 * 8),
    ("bf16[8,16]", "bf16[32,8]", "bf16[32,16]", "dim_labels=fb_io->bf",
     8 * 16 * 32),
    # a contraction written as a window: 3 positions x 8 features
    ("bf16[32,16,1]", "bf16[3,32,8]", "bf16[3,8,16]",
     "window={size=3}, dim_labels=0bf_0io->bf0", 32 * 16 * 8 * 3),
    # a batch of 4 products written as a window over a dilated input: one
    # pair per batch entry, not 4 x 4
    ("bf16[4,32,16]", "bf16[4,32,8]", "bf16[4,8,16]",
     "window={size=4 stride=3 lhs_dilate=4}, dim_labels=0bf_0io->0bf",
     4 * 32 * 16 * 8),
    # a grouped convolution: the kernel's ``i`` extent is one group's
    ("bf16[32,16]", "bf16[32,8]", "bf16[4,16]",
     "dim_labels=bf_io->bf, feature_group_count=2", 32 * 16 * 4),
    # a causal depthwise filter of 4 taps over 8 positions, padded both
    # ways: 8 real inputs under each tap, not 11 outputs x 4 taps
    ("bf16[11,2,6]", "bf16[8,2,6]", "bf16[4,1,6]",
     "window={size=4 pad=3_3 rhs_reversal=1}, dim_labels=0bf_0io->0bf, "
     "feature_group_count=6", 2 * 6 * 1 * 4 * 8),
])
def test_convolution_products(out, lhs, rhs, attrs, macs):
    assert _conv(out, lhs, rhs, attrs) == 2 * macs


def test_a_product_the_parser_cannot_read_states_nothing(caplog):
    """A diagnostic must not stop whoever writes the tables: a window with
    fewer dimensions than its labels name keeps the instruction's path and
    leaves its work out, with one warning."""
    text = """HloModule odd

ENTRY %main (l: bf16[4,32,8], r: bf16[4,8,16]) -> bf16[4,32,16] {
  %l = bf16[4,32,8]{2,1,0} parameter(0)
  %r = bf16[4,8,16]{2,1,0} parameter(1)
  ROOT %c.1 = bf16[4,32,16]{2,1,0} convolution(%l, %r), window={size=4}, dim_labels=01bf_01io->01bf, metadata={op_name="jit(f)/jvp(forward)/fc1/dot_general"}
}
"""
    with caplog.at_level("WARNING"):
        table = hlo_scopes.scope_table(text)
    assert table["instructions"]["c.1"] == "jit(f)/jvp(forward)/fc1/dot_general"
    assert "c.1" not in table["work"]
    assert "no work read for 1 instruction(s) of odd" in caplog.text


@pytest.mark.parametrize("broken", ["flops_of", "bytes_of", "__init__"])
def test_no_error_in_the_work_parse_costs_a_path(monkeypatch, caplog, broken):
    """Whatever goes wrong where the work is read (``ProfileWindow._finish``
    writes the tables inside the training loop), every instruction keeps
    its path; the ones not read state no work."""
    def fail(self, *args):
        raise TypeError("a form nobody has met")

    sound = hlo_scopes.scope_table(WORK_HLO)
    monkeypatch.setattr(hlo_scopes._Work, broken, fail)
    with caplog.at_level("WARNING"):
        table = hlo_scopes.scope_table(WORK_HLO)
    assert table["instructions"] == sound["instructions"]
    assert table["instructions"]["fusion.1"].endswith("fc1/dot_general")
    assert sound["work"] and table["work"] == {}
    assert "hlo-scopes:" in caplog.text


def test_each_kept_program_is_parsed_once(monkeypatch):
    """A traced benchmark run asks for the tables once a pass; the text of
    a program is parsed the first time only, and a program kept later in
    the capture is parsed then."""
    parsed = []
    real = hlo_scopes.scope_table
    monkeypatch.setattr(
        hlo_scopes, "scope_table", lambda text: parsed.append(1) or real(text)
    )
    hlo_scopes._texts.append(HLO)
    first = hlo_scopes.tables()
    assert hlo_scopes.tables()[0] is first[0] and len(parsed) == 1
    hlo_scopes._texts.append(WORK_HLO)
    assert [t["module"] for t in hlo_scopes.tables()] == ["jit_step", "jit_work"]
    assert len(parsed) == 2
    hlo_scopes.reset()
    assert hlo_scopes.tables() == [] and len(parsed) == 2


@pytest.mark.parametrize("path,want", [
    ("jit(f)/jvp(forward)/M/layers_0/fc1/dot_general", "fwd"),
    ("jit(f)/forward/M/layers_0/fc1/dot_general", "fwd"),
    ("jit(f)/transpose(jvp(forward))/M/layers_0/fc1/dot_general", "bwd"),
    ("jit(f)/transpose(jvp(forward))/jvp(forward)/checkpoint/fc2/transpose", "bwd"),
    ("jit(f)/transpose(jvp(forward))/checkpoint/rematted_computation/fc1/dot_general",
     "remat"),
    ("jit(f)/optimizer/mul", ""),
    ("", ""),
])
def test_pass_of_a_path(path, want):
    assert hlo_scopes.pass_of(path) == want


def test_a_checkpointed_block_on_this_backend():
    """The gradient of ``x @ w1`` then ``@ w2`` under ``jax.checkpoint``,
    compiled here: every product reads 2mnk; exactly one is the second
    forward (``fc1``'s; XLA drops the first forward nobody reads, and
    ``fc2``'s result is not needed again), the other three backward."""
    m, k, n = 8, 16, 32

    def f(w1, w2, x):
        @jax.checkpoint
        def block(x):
            with jax.named_scope("fc1"):
                h = jnp.tanh(x @ w1)
            with jax.named_scope("fc2"):
                return h @ w2

        with jax.named_scope("forward"):
            return jnp.sum(block(x))

    fn = jax.jit(jax.grad(f, argnums=(0, 1)))
    args = (jnp.ones((k, n)), jnp.ones((n, k)), jnp.ones((m, k)))
    table = hlo_scopes.scope_table(fn.lower(*args).compile().as_text())
    products = {
        name: w for name, w in table["work"].items() if w["flops"]
    }
    assert {w["flops"] for w in products.values()} == {2 * m * n * k}
    passes = sorted(w["pass"] for w in products.values())
    assert passes == ["bwd", "bwd", "bwd", "remat"]
    (again,) = [n_ for n_, w in products.items() if w["pass"] == "remat"]
    assert "/rematted_computation/fc1/" in table["instructions"][again]
    assert all(w["bytes"] > 0 for w in products.values())
