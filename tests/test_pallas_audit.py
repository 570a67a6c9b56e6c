"""Pallas kernel auditor (unicore-tpu-lint --kernels): fixture kernels
per defect class, the tree-is-clean gate, and the site inventory pin.

Each fixture is ONE canned kernel module written to tmp_path and audited
in isolation — flagged fixtures must produce the named rule, clean
fixtures must produce nothing — plus a regression fixture reproducing
the PR-9 ring-attention loop-invariant-seed bug that the per-axis seed
check must catch.
"""

import os
import textwrap

import pytest

from unicore_tpu.analysis import pallas_audit as pa
from unicore_tpu.analysis.core import ModuleInfo, iter_py_files
from unicore_tpu.ops import _pallas


# ---------------------------------------------------------------------------
# harness
# ---------------------------------------------------------------------------

_PRELUDE = """
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from unicore_tpu.ops._pallas import audit_case, pallas_call as _pallas_call
"""


def audit_fixture(tmp_path, name, body):
    """Write one fixture kernel module, audit it alone, return findings
    as a {rule: [messages]} dict."""
    path = tmp_path / f"{name}.py"
    path.write_text(_PRELUDE + textwrap.dedent(body))
    module = ModuleInfo(str(path), path.read_text())
    pa._memo = (None, None)
    pa.KERNEL_AUDIT_ENABLED = True
    try:
        result = pa.run_kernel_audit([module])
    finally:
        pa.KERNEL_AUDIT_ENABLED = False
        pa._memo = (None, None)
    return {
        rule: [v.message for v in vs]
        for rule, vs in result.findings.items()
        if vs
    }


# ---------------------------------------------------------------------------
# (a) block-bounds
# ---------------------------------------------------------------------------

def test_bounds_flags_grid_overrun(tmp_path):
    findings = audit_fixture(tmp_path, "fx_oob_grid", """
        def _kernel(x_ref, o_ref):
            o_ref[...] = x_ref[...]

        @audit_case("fx-oob-grid")
        def _case():
            x = jnp.zeros((128, 256), jnp.float32)
            _pallas_call(
                _kernel,
                name="fx",
                grid=(4,),
                in_specs=[pl.BlockSpec((64, 256), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((64, 256), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct((128, 256), jnp.float32),
            )(x)
    """)
    assert pa.RULE_BOUNDS in findings
    assert "outside extent 128" in findings[pa.RULE_BOUNDS][0]


def test_bounds_flags_shifted_index_map(tmp_path):
    findings = audit_fixture(tmp_path, "fx_oob_shift", """
        def _kernel(x_ref, o_ref):
            o_ref[...] = x_ref[...]

        @audit_case("fx-oob-shift")
        def _case():
            x = jnp.zeros((256, 256), jnp.float32)
            _pallas_call(
                _kernel,
                name="fx",
                grid=(2,),
                in_specs=[pl.BlockSpec((128, 256), lambda i: (i + 1, 0))],
                out_specs=pl.BlockSpec((128, 256), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct((256, 256), jnp.float32),
            )(x)
    """)
    assert pa.RULE_BOUNDS in findings
    assert "in[0]" in findings[pa.RULE_BOUNDS][0]


def test_bounds_clean_kernel_passes(tmp_path):
    findings = audit_fixture(tmp_path, "fx_bounds_ok", """
        def _kernel(x_ref, o_ref):
            o_ref[...] = x_ref[...]

        @audit_case("fx-bounds-ok")
        def _case():
            x = jnp.zeros((256, 256), jnp.float32)
            _pallas_call(
                _kernel,
                name="fx",
                grid=(2,),
                in_specs=[pl.BlockSpec((128, 256), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((128, 256), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct((256, 256), jnp.float32),
            )(x)
    """)
    assert findings == {}


_PREFETCH_MAPPED = """
        import numpy as np

        def _kernel(ids_ref, x_ref, o_ref):
            o_ref[...] = x_ref[...]

        def _gather(ids, jit=False):
            x = jnp.zeros((512, 256), jnp.float32)

            def run(ids):
                return _pallas_call(
                    _kernel,
                    name="fx",
                    grid_spec=pltpu.PrefetchScalarGridSpec(
                        num_scalar_prefetch=1,
                        grid=(3,),
                        in_specs=[pl.BlockSpec(
                            (128, 256), lambda i, ids_ref: (ids_ref[i], 0))],
                        out_specs=pl.BlockSpec(
                            (128, 256), lambda i, ids_ref: (i, 0)),
                    ),
                    out_shape=jax.ShapeDtypeStruct((384, 256), jnp.float32),
                )(ids, x)

            return (jax.jit(run) if jit else run)(ids)
"""


@pytest.mark.parametrize("ids,jit,rule,says", [
    ("[3, 0, 2]", False, None, None),
    # the blocks a map can NAME are checked, not only computed indices
    ("[3, 0, 4]", False, pa.RULE_BOUNDS, "outside extent 512"),
    # a traced map has no values to evaluate: opaque, not a silent pass
    ("[3, 0, 2]", True, pa.RULE_COVERAGE, "geometry not enumerable"),
])
def test_bounds_evaluates_maps_that_read_scalar_prefetch(
    tmp_path, ids, jit, rule, says
):
    findings = audit_fixture(tmp_path, "fx_prefetch_map", _PREFETCH_MAPPED + f"""
        @audit_case("fx-prefetch-map")
        def _case():
            _gather(np.asarray({ids}, np.int32), jit={jit})
    """)
    if rule is None:
        assert findings == {}
    else:
        assert says in findings[rule][0], findings


# ---------------------------------------------------------------------------
# (b) tiling legality
# ---------------------------------------------------------------------------

def test_tiling_flags_int8_sublane(tmp_path):
    # the PR-12-round-5 bug class: an int8 block on the fp32 8-row tile
    findings = audit_fixture(tmp_path, "fx_tile_int8", """
        def _kernel(x_ref, o_ref):
            o_ref[...] = x_ref[...]

        @audit_case("fx-tile-int8")
        def _case():
            x = jnp.zeros((64, 256), jnp.int8)
            _pallas_call(
                _kernel,
                name="fx",
                grid=(8,),
                in_specs=[pl.BlockSpec((8, 256), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((8, 256), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct((64, 256), jnp.int8),
            )(x)
    """)
    assert pa.RULE_TILING in findings
    assert "multiple of 32" in findings[pa.RULE_TILING][0]


def test_tiling_flags_lane_violation(tmp_path):
    findings = audit_fixture(tmp_path, "fx_tile_lane", """
        def _kernel(x_ref, o_ref):
            o_ref[...] = x_ref[...]

        @audit_case("fx-tile-lane")
        def _case():
            x = jnp.zeros((8, 192), jnp.float32)
            _pallas_call(
                _kernel,
                name="fx",
                grid=(2,),
                in_specs=[pl.BlockSpec((8, 96), lambda i: (0, i))],
                out_specs=pl.BlockSpec((8, 96), lambda i: (0, i)),
                out_shape=jax.ShapeDtypeStruct((8, 192), jnp.float32),
            )(x)
    """)
    assert pa.RULE_TILING in findings
    assert "last dim 96" in findings[pa.RULE_TILING][0]


def test_tiling_clean_full_dim_and_stat_blocks_pass(tmp_path):
    # short full-dim last blocks and (N, 1) stat columns are house idiom
    findings = audit_fixture(tmp_path, "fx_tile_ok", """
        def _kernel(x_ref, o_ref, s_ref):
            o_ref[...] = x_ref[...]
            s_ref[...] = jnp.zeros_like(s_ref)

        @audit_case("fx-tile-ok")
        def _case():
            x = jnp.zeros((32, 64), jnp.float32)
            _pallas_call(
                _kernel,
                name="fx",
                grid=(2,),
                in_specs=[pl.BlockSpec((16, 64), lambda i: (i, 0))],
                out_specs=[
                    pl.BlockSpec((16, 64), lambda i: (i, 0)),
                    pl.BlockSpec((16, 1), lambda i: (i, 0)),
                ],
                out_shape=[
                    jax.ShapeDtypeStruct((32, 64), jnp.float32),
                    jax.ShapeDtypeStruct((32, 1), jnp.float32),
                ],
            )(x)
    """)
    assert findings == {}


# ---------------------------------------------------------------------------
# (c) VMEM budget
# ---------------------------------------------------------------------------

def test_vmem_flags_oversized_io_block(tmp_path):
    findings = audit_fixture(tmp_path, "fx_vmem_io", """
        def _kernel(x_ref, o_ref):
            o_ref[...] = x_ref[...]

        @audit_case("fx-vmem-io")
        def _case():
            x = jnp.zeros((2048, 2048), jnp.float32)
            _pallas_call(
                _kernel,
                name="fx",
                grid=(1,),
                in_specs=[pl.BlockSpec((2048, 2048), lambda i: (0, 0))],
                out_specs=pl.BlockSpec((2048, 2048), lambda i: (0, 0)),
                out_shape=jax.ShapeDtypeStruct((2048, 2048), jnp.float32),
            )(x)
    """)
    assert pa.RULE_VMEM in findings
    assert "exceeds" in findings[pa.RULE_VMEM][0]


def test_vmem_flags_oversized_scratch(tmp_path):
    findings = audit_fixture(tmp_path, "fx_vmem_scratch", """
        def _kernel(x_ref, o_ref, acc_ref):
            o_ref[...] = x_ref[...]

        @audit_case("fx-vmem-scratch")
        def _case():
            x = jnp.zeros((8, 128), jnp.float32)
            _pallas_call(
                _kernel,
                name="fx",
                grid_spec=pltpu.PrefetchScalarGridSpec(
                    num_scalar_prefetch=0,
                    grid=(2,),
                    in_specs=[pl.BlockSpec((8, 128), lambda i: (0, 0))],
                    out_specs=pl.BlockSpec((8, 128), lambda i: (0, 0)),
                    scratch_shapes=[pltpu.VMEM((2048, 2048), jnp.float32)],
                ),
                out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
            )(x)
    """)
    assert pa.RULE_VMEM in findings
    # the constant-index output is guarded-free but accumulation-free too;
    # only the budget rule should fire (revisit needs a multi-step axis
    # the OUTPUT ignores while inputs vary — none here)
    assert "scratch" in findings[pa.RULE_VMEM][0]


def test_vmem_clean_modest_blocks_pass(tmp_path):
    findings = audit_fixture(tmp_path, "fx_vmem_ok", """
        def _kernel(x_ref, o_ref):
            o_ref[...] = x_ref[...]

        @audit_case("fx-vmem-ok")
        def _case():
            x = jnp.zeros((512, 512), jnp.float32)
            _pallas_call(
                _kernel,
                name="fx",
                grid=(2,),
                in_specs=[pl.BlockSpec((256, 512), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((256, 512), lambda i: (i, 0)),
                out_shape=jax.ShapeDtypeStruct((512, 512), jnp.float32),
            )(x)
    """)
    assert findings == {}


# ---------------------------------------------------------------------------
# (d) output write races on revisited blocks
# ---------------------------------------------------------------------------

def test_revisit_flags_unguarded_constant_output(tmp_path):
    findings = audit_fixture(tmp_path, "fx_race_const", """
        def _kernel(x_ref, o_ref):
            o_ref[...] = x_ref[...]

        @audit_case("fx-race-const")
        def _case():
            x = jnp.zeros((512, 128), jnp.float32)
            _pallas_call(
                _kernel,
                name="fx",
                grid=(4,),
                in_specs=[pl.BlockSpec((128, 128), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((128, 128), lambda i: (0, 0)),
                out_shape=jax.ShapeDtypeStruct((128, 128), jnp.float32),
            )(x)
    """)
    assert pa.RULE_REVISIT in findings
    assert "ignores grid axis 0" in findings[pa.RULE_REVISIT][0]


def test_revisit_flags_ignored_second_axis(tmp_path):
    findings = audit_fixture(tmp_path, "fx_race_axis1", """
        def _kernel(x_ref, o_ref):
            o_ref[...] = x_ref[...]

        @audit_case("fx-race-axis1")
        def _case():
            x = jnp.zeros((256, 256), jnp.float32)
            _pallas_call(
                _kernel,
                name="fx",
                grid=(2, 2),
                in_specs=[pl.BlockSpec((128, 128), lambda i, j: (i, j))],
                out_specs=pl.BlockSpec((128, 128), lambda i, j: (i, 0)),
                out_shape=jax.ShapeDtypeStruct((256, 128), jnp.float32),
            )(x)
    """)
    assert pa.RULE_REVISIT in findings
    assert "ignores grid axis 1" in findings[pa.RULE_REVISIT][0]


def test_revisit_clean_when_guarded(tmp_path):
    findings = audit_fixture(tmp_path, "fx_race_guarded", """
        def _kernel(x_ref, o_ref):
            i = pl.program_id(0)

            @pl.when(i == 0)
            def _init():
                o_ref[...] = x_ref[...]

        @audit_case("fx-race-guarded")
        def _case():
            x = jnp.zeros((512, 128), jnp.float32)
            _pallas_call(
                _kernel,
                name="fx",
                grid=(4,),
                in_specs=[pl.BlockSpec((128, 128), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((128, 128), lambda i: (0, 0)),
                out_shape=jax.ShapeDtypeStruct((128, 128), jnp.float32),
            )(x)
    """)
    assert pa.RULE_REVISIT not in findings


def test_revisit_clean_when_accumulating(tmp_path):
    # the fused_norm dwdb idiom: init on step 0, then read-modify-write
    findings = audit_fixture(tmp_path, "fx_race_accum", """
        def _kernel(x_ref, o_ref):
            i = pl.program_id(0)

            @pl.when(i == 0)
            def _init():
                o_ref[...] = jnp.zeros_like(o_ref)

            o_ref[...] += x_ref[...]

        @audit_case("fx-race-accum")
        def _case():
            x = jnp.zeros((512, 128), jnp.float32)
            _pallas_call(
                _kernel,
                name="fx",
                grid=(4,),
                in_specs=[pl.BlockSpec((128, 128), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((128, 128), lambda i: (0, 0)),
                out_shape=jax.ShapeDtypeStruct((128, 128), jnp.float32),
            )(x)
    """)
    assert pa.RULE_REVISIT not in findings


# ---------------------------------------------------------------------------
# (e) per-axis seed coverage
# ---------------------------------------------------------------------------

def test_seed_flags_ring_seed_regression(tmp_path):
    # the PR-9 ring-attention bug verbatim: a raw scalar-prefetch seed,
    # loop-invariant across a multi-axis grid — every block gets the SAME
    # PRNG stream although its data differs
    findings = audit_fixture(tmp_path, "fx_seed_ring", """
        def _kernel(seed_ref, x_ref, o_ref):
            pltpu.prng_seed(seed_ref[0])
            bits = pltpu.prng_random_bits(x_ref[...].shape)
            o_ref[...] = x_ref[...]

        @audit_case("fx-seed-ring")
        def _case():
            seed = jnp.zeros((1,), jnp.int32)
            x = jnp.zeros((256, 256), jnp.float32)
            _pallas_call(
                _kernel,
                name="fx",
                grid_spec=pltpu.PrefetchScalarGridSpec(
                    num_scalar_prefetch=1,
                    grid=(2, 2),
                    in_specs=[pl.BlockSpec((128, 128), lambda i, j, *_: (i, j))],
                    out_specs=pl.BlockSpec((128, 128), lambda i, j, *_: (i, j)),
                ),
                out_shape=jax.ShapeDtypeStruct((256, 256), jnp.float32),
            )(seed, x)
    """)
    assert pa.RULE_SEED in findings
    assert "[0, 1]" in findings[pa.RULE_SEED][0]


def test_seed_flags_partially_mixed_seed(tmp_path):
    findings = audit_fixture(tmp_path, "fx_seed_partial", """
        def _kernel(seed_ref, x_ref, o_ref):
            i = pl.program_id(0)
            pltpu.prng_seed(seed_ref[0] * 7 + i)
            o_ref[...] = x_ref[...]

        @audit_case("fx-seed-partial")
        def _case():
            seed = jnp.zeros((1,), jnp.int32)
            x = jnp.zeros((256, 256), jnp.float32)
            _pallas_call(
                _kernel,
                name="fx",
                grid_spec=pltpu.PrefetchScalarGridSpec(
                    num_scalar_prefetch=1,
                    grid=(2, 2),
                    in_specs=[pl.BlockSpec((128, 128), lambda i, j, *_: (i, j))],
                    out_specs=pl.BlockSpec((128, 128), lambda i, j, *_: (i, j)),
                ),
                out_shape=jax.ShapeDtypeStruct((256, 256), jnp.float32),
            )(seed, x)
    """)
    assert pa.RULE_SEED in findings
    assert "[1]" in findings[pa.RULE_SEED][0]


def test_seed_clean_when_every_axis_mixed(tmp_path):
    # the house _mix_seed idiom, including a one-hop helper call
    findings = audit_fixture(tmp_path, "fx_seed_ok", """
        def _mix(seed_ref, i, j):
            pltpu.prng_seed(seed_ref[0] * 1000003 + i * 7 + j)

        def _kernel(seed_ref, x_ref, o_ref):
            i, j = pl.program_id(0), pl.program_id(1)
            _mix(seed_ref, i, j)
            o_ref[...] = x_ref[...]

        @audit_case("fx-seed-ok")
        def _case():
            seed = jnp.zeros((1,), jnp.int32)
            x = jnp.zeros((256, 256), jnp.float32)
            _pallas_call(
                _kernel,
                name="fx",
                grid_spec=pltpu.PrefetchScalarGridSpec(
                    num_scalar_prefetch=1,
                    grid=(2, 2),
                    in_specs=[pl.BlockSpec((128, 128), lambda i, j, *_: (i, j))],
                    out_specs=pl.BlockSpec((128, 128), lambda i, j, *_: (i, j)),
                ),
                out_shape=jax.ShapeDtypeStruct((256, 256), jnp.float32),
            )(seed, x)
    """)
    assert pa.RULE_SEED not in findings


# ---------------------------------------------------------------------------
# coverage rule (always-on AST layer)
# ---------------------------------------------------------------------------

def test_coverage_flags_kernel_module_without_audit_case(tmp_path):
    path = tmp_path / "fx_nocase.py"
    path.write_text(_PRELUDE + textwrap.dedent("""
        def _kernel(x_ref, o_ref):
            o_ref[...] = x_ref[...]

        def run(x):
            return _pallas_call(
                _kernel,
                name="fx",
                grid=(1,),
                in_specs=[pl.BlockSpec((8, 128), lambda i: (0, 0))],
                out_specs=pl.BlockSpec((8, 128), lambda i: (0, 0)),
                out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
            )(x)
    """))
    module = ModuleInfo(str(path), path.read_text())
    violations = list(pa.PallasKernelCoverage().check_project([module]))
    assert violations and "no @audit_case" in violations[0].message


def test_coverage_passes_covered_kernel_module():
    tree = [
        ModuleInfo(p, open(p).read())
        for p in iter_py_files(["unicore_tpu/ops/"])
    ]
    assert list(pa.PallasKernelCoverage().check_project(tree)) == []


# ---------------------------------------------------------------------------
# site inventory: the count a new kernel cannot silently dodge
# ---------------------------------------------------------------------------

def _tree_modules():
    return [
        ModuleInfo(p, open(p).read())
        for p in iter_py_files(["unicore_tpu/", "unicore_tpu_cli/"])
    ]


def test_site_inventory_pins_every_kernel():
    inventory = pa.audit_inventory(_tree_modules())
    direct = {
        os.path.basename(p): len(lines)
        for p, lines in inventory["direct"].items()
    }
    assert direct == {
        "flash_attention.py": 4,
        "attention_fullrow.py": 2,
        "fused_norm.py": 3,
        "quant_matmul.py": 1,
        "softmax_dropout_pallas.py": 1,
        "decode_attention.py": 1,
        "rows_add.py": 1,
    }
    dispatch_files = {
        os.path.basename(p) for p in inventory["dispatch"]
    }
    # the cross-layer entries the ISSUE names explicitly
    assert {"ring_attention.py", "ulysses.py", "evoformer.py"} <= dispatch_files
    total = sum(len(v) for v in inventory["direct"].values()) + sum(
        len(v) for v in inventory["dispatch"].values()
    )
    assert total >= 13


def test_tree_audit_is_clean():
    """The acceptance gate: every kernel in the tree passes all five
    checks at its registered representative shapes, and every direct
    site is captured by some audit case."""
    modules = _tree_modules()
    pa._memo = (None, None)
    pa.KERNEL_AUDIT_ENABLED = True
    try:
        result = pa.run_kernel_audit(modules)
    finally:
        pa.KERNEL_AUDIT_ENABLED = False
        pa._memo = (None, None)
    flat = [v for vs in result.findings.values() for v in vs]
    assert flat == [], [v.format() for v in flat]
    # every registered case produced at least one capture, and the big
    # multi-kernel families (flash fwd + dq/dkv/dbias) all reported in
    assert result.captures >= 11
    assert result.cases >= 8


def test_the_mapped_flash_case_names_only_the_blocks_of_its_map():
    """``flash-attention-block-map``: the three walked kernels capture, and
    enumerating the key-side index map (it reads the map's ids from scalar
    prefetch) names, for the first window, no summary block, and for the
    last one all of them; the key-major kernel still writes every block."""
    from unicore_tpu.analysis import kernel_geometry as kg

    path = os.path.realpath("unicore_tpu/ops/flash_attention.py")
    captures, errors = pa.run_audit_cases({path})
    assert not errors, errors
    caps = {
        c.name: c for c in captures if c.case == "flash-attention-block-map"
    }
    assert set(caps) == {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}
    for cap in caps.values():
        assert kg.check_block_bounds(cap) == [], cap.name

    def named(cap, operand, batch_row):
        use = cap.inputs()[operand]
        idx = [use.index_map(*pid) for pid in kg._grid_points(cap.grid)]
        return {i[2] for i in idx if i[0] == batch_row}

    for name in ("flash_fwd", "flash_bwd_dq"):
        # the last axis walks the map's 20 visits, all groups in one list
        assert caps[name].grid == (1, 2, 1, 20)
        assert named(caps[name], 1, 0) == {0, 1}       # k: own keys only
        assert named(caps[name], 1, 3) == {0, 1, 2, 3}
    # key-major: four summary blocks (two of the first window) have no visitor and
    # keep a dead item each, which names query block 0
    dkv = caps["flash_bwd_dkv"]
    assert dkv.grid == (1, 2, 1, 24)
    assert named(dkv, 0, 0) == {0, 1}
    assert named(dkv, 1, 0) == {0, 1, 2, 3}            # every dk block written


# ---------------------------------------------------------------------------
# unified geometry helpers (ops/_pallas.py)
# ---------------------------------------------------------------------------

def test_pick_block_lane_stepped():
    assert _pallas.pick_block(1024, 512) == 512
    assert _pallas.pick_block(768, 512) == 384
    assert _pallas.pick_block(100, 512) == 100  # length <= preferred
    with pytest.raises(_pallas.KernelGeometryError):
        _pallas.pick_block(1000, 512)  # no 128-multiple divides 1000


def test_pick_block_pow2_never_raises():
    assert _pallas.pick_block_pow2(4096, 1024) == 1024
    assert _pallas.pick_block_pow2(96, 64) == 32
    assert _pallas.pick_block_pow2(7, 64) == 7
    assert _pallas.pick_block_pow2(10, 4) == 2


def test_vmem_footprint_doubles_io_only():
    io = [((256, 128), "float32")]
    scratch = [((256, 128), "float32")]
    one = 256 * 128 * 4
    assert _pallas.vmem_footprint(io) == 2 * one
    assert _pallas.vmem_footprint(io, scratch) == 3 * one
    with pytest.raises(_pallas.KernelGeometryError):
        _pallas.check_vmem_budget("t", [((2048, 2048), "float32")])


def test_quant_matmul_serving_shape_fits_budget():
    """The live finding the auditor caught: the serving-plane GEMM
    (M=512, K=N=4096) used to plan BK=4096 — ~16 MiB double-buffered,
    over the 12 MiB budget.  _plan_blocks must now halve BK."""
    import jax.numpy as jnp

    from unicore_tpu.ops import quant_matmul as qm

    BM, BN, BK = qm._plan_blocks(512, 4096, 4096, has_bias=True)
    assert BK < 4096
    io = [
        ((BM, BK), jnp.int8),
        ((BK, BN), jnp.int8),
        ((1, BN), jnp.float32),
        ((BM, BN), jnp.float32),
        ((1, BN), jnp.float32),
    ]
    assert _pallas.vmem_footprint(io) <= _pallas.VMEM_BUDGET


@pytest.mark.parametrize("shape", [(512, 4096, 4096), (512, 3072, 768),
                                   (8192, 768, 3072)])
def test_quant_matmul_plan_stays_on_the_int8_tiling(shape):
    """Every planned block divides its dim and stays on the (32, 128) int8
    tiling: the halving loop used to take 768 to 192, off the lane grid.
    The plan does not depend on the activation — what an epilogue keeps
    beside the io blocks (exact GELU: 21 MiB at the first shape, by
    Mosaic's account) is covered by the declared ``VMEM_LIMIT``, which
    tests/test_tpu_compile.py holds Mosaic to."""
    import jax.numpy as jnp

    from unicore_tpu.ops import quant_matmul as qm

    M, N, K = shape
    BM, BN, BK = qm._plan_blocks(M, N, K, has_bias=True)
    assert BM % 32 == 0 and BN % 128 == 0 and BK % 128 == 0
    assert M % BM == 0 and N % BN == 0 and K % BK == 0
    io = [((BM, BK), jnp.int8), ((BK, BN), jnp.int8),
          ((1, BN), jnp.float32), ((BM, BN), jnp.float32),
          ((1, BN), jnp.float32)]
    assert _pallas.vmem_footprint(io) <= _pallas.VMEM_BUDGET
    assert _pallas.VMEM_LIMIT >= 2 * _pallas.VMEM_BUDGET


def test_flash_attention_bias_errors_are_named():
    import jax.numpy as jnp

    from unicore_tpu.ops.flash_attention import flash_attention

    q = jnp.zeros((2, 2, 128, 64), jnp.float32)
    with pytest.raises(_pallas.KernelGeometryError, match="rank"):
        flash_attention(q, q, q, bias=jnp.zeros((128, 128), jnp.float32))
    with pytest.raises(_pallas.KernelGeometryError, match="divide batch"):
        flash_attention(
            q, q, q, bias=jnp.zeros((3, 2, 128, 128), jnp.float32)
        )


def test_fullrow_refusal_is_named():
    import jax.numpy as jnp

    from unicore_tpu.ops.attention_fullrow import fullrow_attention

    q = jnp.zeros((2, 2, 100, 64), jnp.float32)  # rows not 128-multiple
    with pytest.raises(_pallas.KernelGeometryError, match="fullrow"):
        fullrow_attention(q, q, q)
