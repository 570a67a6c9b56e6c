"""Quantized inference path (docs/serving.md "Quantized inference"):

- op-level parity: the Pallas int8 matmul / quantized softmax / quantized
  LayerNorm kernels run under interpret mode on CPU and must match their
  jnp oracles, and the whole int8 pipeline must track the fp32 oracle
  within the documented per-op bounds;
- the lazy interpret gate in ops/_pallas.py (env set AFTER import works);
- QuantDense's fp path is BIT-identical to nn.Dense (training checkpoints
  and the non-quantized serving path are untouched);
- calibration: determinism (same batch => bit-identical scales), the
  model-level parity sweep (int8/fp8 logits vs the fp32 oracle bounded
  per mode across bucket geometries), scale persistence round-trip with
  weights-digest verification;
- the fusion-audit dequant section: the detector flags a handcrafted
  unfused convert->multiply chain, and the COMPILED quantized serving
  program carries zero materialized fp32 dequant intermediates
  (device-free regression of the arXiv 2502.17728 fusion contract).

Documented error-bound contract asserted here and in the serve e2e
(tests/test_serve.py): int8 max |logit drift| <= 5% of the fp32 logit
absmax on the calibration batches; fp8 (weight-only fp8 rounding)
<= 15%.
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from unicore_tpu.ops import _pallas
from unicore_tpu.ops.quant_matmul import (
    dynamic_act_scale,
    quant_matmul,
    quant_matmul_pallas,
    quant_matmul_reference,
    quantize_to_int8,
    set_quant_matmul_mode,
)
from unicore_tpu.ops.quant_norm import (
    quant_layer_norm,
    quant_layer_norm_reference,
    set_quant_norm_mode,
)
from unicore_tpu.ops.quant_softmax_dropout import (
    quant_softmax_dropout,
    quant_softmax_dropout_reference,
    set_quant_softmax_dropout_mode,
)
from unicore_tpu.quant import QTensor, calibrate, check_mode
from unicore_tpu.quant.dense import QuantDense

#: the documented per-mode model-level error bound (rel_drift =
#: max |logit_q - logit_f32| / max |logit_f32| over calibration batches)
REL_DRIFT_BOUND = {"int8": 0.05, "fp8": 0.15}


@pytest.fixture
def pallas_on():
    """Force every quantized kernel onto its Pallas path under interpret
    mode (the CPU-CI way to run the real kernels)."""
    _pallas.set_interpret(True)
    set_quant_matmul_mode("on")
    set_quant_softmax_dropout_mode("on")
    set_quant_norm_mode("on")
    yield
    set_quant_matmul_mode(None)
    set_quant_softmax_dropout_mode(None)
    set_quant_norm_mode(None)


# ---------------------------------------------------------------------------
# satellite: the lazy interpret gate
# ---------------------------------------------------------------------------

def test_interpret_gate_resolves_lazily_per_call(monkeypatch):
    """UNICORE_TPU_PALLAS_INTERPRET set AFTER ops/_pallas.py imported must
    still take effect (the old import-time read silently ignored it)."""
    _pallas.set_interpret(None)
    monkeypatch.delenv("UNICORE_TPU_PALLAS_INTERPRET", raising=False)
    assert not _pallas.interpret_enabled()
    monkeypatch.setenv("UNICORE_TPU_PALLAS_INTERPRET", "1")
    assert _pallas.interpret_enabled()  # the module was imported long ago
    monkeypatch.setenv("UNICORE_TPU_PALLAS_INTERPRET", "0")
    assert not _pallas.interpret_enabled()
    # an explicit set_interpret overrides the env either way ...
    _pallas.set_interpret(True)
    assert _pallas.interpret_enabled()
    # ... and None hands control back to the env
    _pallas.set_interpret(None)
    assert not _pallas.interpret_enabled()


# ---------------------------------------------------------------------------
# op parity: quant_matmul
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(8, 128, 128), (16, 256, 384)])
@pytest.mark.parametrize("use_bias,act", [
    (False, ""), (True, "gelu"), (True, "relu"),
])
def test_quant_matmul_pallas_matches_reference(pallas_on, shape, use_bias,
                                               act):
    M, K, N = shape
    rng = np.random.RandomState(0)
    x = rng.randn(M, K).astype(np.float32)
    w = rng.randn(K, N).astype(np.float32) * 0.1
    x_scale = dynamic_act_scale(jnp.asarray(x))
    w_scale = jnp.maximum(jnp.abs(jnp.asarray(w)).max(axis=0) / 127.0, 1e-8)
    x_q = quantize_to_int8(jnp.asarray(x), x_scale)
    w_q = quantize_to_int8(jnp.asarray(w), w_scale)
    bias = jnp.asarray(rng.randn(N), jnp.float32) if use_bias else None
    scale = x_scale * w_scale
    got = quant_matmul_pallas(x_q, w_q, scale, bias=bias, activation=act)
    ref = quant_matmul_reference(x_q, w_q, scale, bias=bias, activation=act)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    # the whole int8 pipeline tracks the fp32 oracle within the
    # quantization budget (per-channel weights, per-tensor activations)
    oracle = np.asarray(x) @ np.asarray(w)
    if use_bias:
        oracle = oracle + np.asarray(bias)
    if act == "gelu":
        oracle = np.asarray(jax.nn.gelu(oracle, approximate=False))
    elif act == "relu":
        oracle = np.maximum(oracle, 0.0)
    err = np.abs(np.asarray(got) - oracle).max()
    assert err < 0.05 * max(np.abs(oracle).max(), 1.0), err


def test_quant_matmul_gelu_epilogue_error_bound():
    """The in-kernel exact-GELU epilogue is an Abramowitz-Stegun erf
    (Mosaic lowers neither erf nor erfc); the jnp oracle keeps lax.erf.
    They agree to the stated bound over the whole useful range, and the
    kernel's other activations ARE the oracle's functions."""
    from unicore_tpu.ops import quant_matmul as qm

    x = jnp.arange(-10.0, 10.0, 1e-3, dtype=jnp.float32)
    got = jax.jit(lambda v: qm._apply_activation_kernel(v, "gelu"))(x)
    ref = qm._apply_activation(x, "gelu")
    err = float(jnp.max(jnp.abs(got - ref)))
    assert err <= qm.GELU_EPILOGUE_MAX_ABS_ERR, err
    for act in ("", "relu", "gelu_fast", "silu", "tanh"):
        assert np.array_equal(
            np.asarray(qm._apply_activation_kernel(x, act)),
            np.asarray(qm._apply_activation(x, act)),
        ), act


def test_quant_matmul_dispatch_gates(pallas_on):
    """Geometry the Pallas kernel can't tile falls back to the jnp
    composition (and mode off always does), with identical results."""
    rng = np.random.RandomState(1)
    x = quantize_to_int8(jnp.asarray(rng.randn(5, 96), jnp.float32), 0.1)
    w = quantize_to_int8(jnp.asarray(rng.randn(96, 100), jnp.float32), 0.1)
    got = quant_matmul(x, w, 0.01)  # K=96, N=100: not 128-multiples
    ref = quant_matmul_reference(x, w, 0.01)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-6)
    set_quant_matmul_mode("off")
    off = quant_matmul(
        quantize_to_int8(jnp.asarray(rng.randn(8, 128), jnp.float32), 0.1),
        quantize_to_int8(jnp.asarray(rng.randn(128, 128), jnp.float32), 0.1),
        0.01,
    )
    assert off.shape == (8, 128)


def test_quant_matmul_fp8_reference_path():
    """fp8 operands ride the jnp path: values carry the fp8 rounding,
    the dot accumulates fp32."""
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(4, 64), jnp.float32)
    w = jnp.asarray(rng.randn(64, 32), jnp.float32) * 0.1
    x8 = (x / 0.1).astype(jnp.float8_e4m3fn)
    w8 = (w / 0.01).astype(jnp.float8_e4m3fn)
    got = quant_matmul(x8, w8, 0.1 * 0.01)
    oracle = np.asarray(x) @ np.asarray(w)
    assert np.abs(np.asarray(got) - oracle).max() < \
        0.15 * max(np.abs(oracle).max(), 1.0)


# ---------------------------------------------------------------------------
# op parity: quant_softmax_dropout
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("in_dtype", ["int8", "int32"])
@pytest.mark.parametrize("with_mask,with_bias", [
    (False, False), (True, False), (False, True), (True, True),
])
def test_quant_softmax_pallas_matches_reference(pallas_on, in_dtype,
                                                with_mask, with_bias):
    rng = np.random.RandomState(3)
    shape = (2, 4, 8, 128)
    if in_dtype == "int8":
        xq = rng.randint(-127, 128, size=shape).astype(np.int8)
        scale = 0.05
    else:
        xq = rng.randint(-4000, 4000, size=shape).astype(np.int32)
        scale = 1e-3
    mask = None
    if with_mask:
        mask = np.where(
            rng.rand(shape[0], 1, 1, shape[-1]) < 0.2, -1e9, 0.0
        ).astype(np.float32)
    bias = (
        rng.randn(shape[1], shape[2], shape[3]).astype(np.float32)
        if with_bias else None
    )
    got = quant_softmax_dropout(
        jnp.asarray(xq), scale, 0.0, is_training=False,
        mask=None if mask is None else jnp.asarray(mask),
        bias=None if bias is None else jnp.asarray(bias),
    )
    ref = quant_softmax_dropout_reference(
        jnp.asarray(xq), scale, 0.0, is_training=False,
        mask=None if mask is None else jnp.asarray(mask),
        bias=None if bias is None else jnp.asarray(bias),
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)
    rows = np.asarray(got).reshape(-1, shape[-1]).sum(axis=-1)
    np.testing.assert_allclose(rows, 1.0, atol=1e-5)  # it IS a softmax


# ---------------------------------------------------------------------------
# op parity: quant_layer_norm
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(32, 128), (4, 16, 256)])
def test_quant_norm_pallas_matches_reference(pallas_on, shape):
    rng = np.random.RandomState(4)
    xq = rng.randint(-127, 128, size=shape).astype(np.int8)
    D = shape[-1]
    scale = np.maximum(rng.rand(D).astype(np.float32) * 0.05, 1e-4)
    w = rng.randn(D).astype(np.float32)
    b = rng.randn(D).astype(np.float32)
    got = quant_layer_norm(jnp.asarray(xq), jnp.asarray(scale),
                           jnp.asarray(w), jnp.asarray(b))
    ref = quant_layer_norm_reference(jnp.asarray(xq), jnp.asarray(scale),
                                     jnp.asarray(w), jnp.asarray(b))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# QuantDense: the fp path is bit-identical to nn.Dense
# ---------------------------------------------------------------------------

def test_quant_dense_fp_path_bit_identical_to_nn_dense():
    import flax.linen as nn

    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(4, 32), jnp.float32)
    ref_mod = nn.Dense(16, kernel_init=nn.initializers.normal(0.02))
    q_mod = QuantDense(16, kernel_init=nn.initializers.normal(0.02))
    key = jax.random.PRNGKey(0)
    ref_vars = ref_mod.init(key, x)
    q_vars = q_mod.init(key, x)
    # same param names, same init stream
    assert jax.tree_util.tree_structure(ref_vars) == \
        jax.tree_util.tree_structure(q_vars)
    ref = ref_mod.apply(ref_vars, x)
    got = q_mod.apply(q_vars, x)
    assert np.array_equal(np.asarray(ref), np.asarray(got))  # BIT identical
    # the fused activation matches act(nn.Dense(x)) exactly
    act_mod = QuantDense(16, kernel_init=nn.initializers.normal(0.02),
                         activation="gelu")
    got_act = act_mod.apply(q_vars, x)
    assert np.array_equal(
        np.asarray(jax.nn.gelu(ref, approximate=False)), np.asarray(got_act)
    )
    # an explicit 'off' (the --serve-quantize default plumbed through)
    # is the fp path too, not a KeyError in the quantized branch
    off_mod = QuantDense(16, kernel_init=nn.initializers.normal(0.02),
                         quantize="off")
    assert np.array_equal(np.asarray(ref),
                          np.asarray(off_mod.apply(q_vars, x)))
    # ...and a typo'd mode fails loudly at trace time
    with pytest.raises(ValueError, match="quantize mode"):
        QuantDense(16, quantize="int4").apply(q_vars, x)


def test_check_mode_and_qtensor():
    assert check_mode("") == "off" and check_mode("int8") == "int8"
    with pytest.raises(ValueError):
        check_mode("int4")
    qt = QTensor(jnp.asarray([[10, -20]], jnp.int8), jnp.float32(0.5))
    np.testing.assert_allclose(np.asarray(qt.dequant()), [[5.0, -10.0]])


# ---------------------------------------------------------------------------
# calibration: the model-level sweep
# ---------------------------------------------------------------------------

def _tiny_bert(**kw):
    from unicore_tpu.models.bert import BertModel

    cfg = dict(
        vocab_size=100, padding_idx=1, encoder_layers=2,
        encoder_embed_dim=64, encoder_ffn_embed_dim=128,
        encoder_attention_heads=4, max_seq_len=32, post_ln=True,
        dropout=0.0, emb_dropout=0.0, attention_dropout=0.0,
    )
    cfg.update(kw)
    return BertModel(**cfg)


@pytest.fixture(scope="module")
def tiny_model_and_vars():
    m = _tiny_bert()
    toks = np.random.RandomState(0).randint(
        4, 100, size=(2, 16)
    ).astype(np.int32)
    variables = m.init_params(
        jax.random.PRNGKey(0), {"net_input": {"src_tokens": toks}}
    )
    return m, variables


def test_calibration_determinism_bit_identical(tiny_model_and_vars):
    m, variables = tiny_model_and_vars
    mq = m.clone(quantize="int8")
    batches = calibrate.calibration_batches(100, 1, [16, 32], 2)
    batches2 = calibrate.calibration_batches(100, 1, [16, 32], 2)
    for a, b in zip(batches, batches2):
        assert np.array_equal(a, b)  # the fixed-seed stream
    s1 = calibrate.collect_scales(mq, variables, batches)
    s2 = calibrate.collect_scales(mq, variables, batches)
    assert s1 == s2  # float-for-float identical, not just close
    assert all("act_absmax" in v for v in s1.values())
    # the lm-head dense is a quantize_output site: out_absmax sown too
    assert "out_absmax" in s1["lm_head/dense"]


@pytest.mark.parametrize("mode", ["int8", "fp8"])
@pytest.mark.parametrize("seq", [16, 32])
def test_model_parity_within_documented_bound(tiny_model_and_vars, mode,
                                              seq):
    """The parity sweep: quantized logits vs the fp32 oracle, bounded per
    mode, across bucket geometries (the error-bound contract the docs
    publish and the serve e2e re-asserts)."""
    m, variables = tiny_model_and_vars
    mq = m.clone(quantize=mode)
    prepared, info = calibrate.calibrate_for_serving(
        mq, m, variables, mode=mode, snapshot_path=None,
        vocab_size=100, pad_idx=1, bucket_edges=[seq], batch_size=2,
    )
    assert info["sites"] >= 9  # 2 layers x (in/out/fc1/fc2) + lm head
    assert info["rel_drift"] < REL_DRIFT_BOUND[mode], info
    # and an unseen batch stays within 2x the calibration bound (static
    # scales saturate out-of-range values; the margin covers it)
    toks = np.random.RandomState(7).randint(
        4, 100, size=(2, seq)
    ).astype(np.int32)
    ref = np.asarray(m.apply(variables, toks, train=False), np.float32)
    got = np.asarray(mq.apply(prepared, toks, train=False), np.float32)
    rel = np.abs(got - ref).max() / max(np.abs(ref).max(), 1e-8)
    assert rel < 2 * REL_DRIFT_BOUND[mode], rel


def test_prepare_leaves_fp32_tree_untouched(tiny_model_and_vars):
    m, variables = tiny_model_and_vars
    mq = m.clone(quantize="int8")
    batches = calibrate.calibration_batches(100, 1, [16], 2)
    sites = calibrate.collect_scales(mq, variables, batches)
    before = jax.tree_util.tree_map(np.asarray, variables)
    prepared = calibrate.prepare(variables, sites, "int8")
    after = jax.tree_util.tree_map(np.asarray, variables)
    assert jax.tree_util.tree_structure(before) == \
        jax.tree_util.tree_structure(after)
    for a, b in zip(jax.tree_util.tree_leaves(before),
                    jax.tree_util.tree_leaves(after)):
        assert np.array_equal(a, b)
    # the prepared tree swapped kernel -> kernel_q/kernel_scale/act_scale
    node = prepared["params"]["lm_head"]["dense"]
    assert set(node) >= {"kernel_q", "kernel_scale", "act_scale",
                         "out_scale", "bias"}
    assert node["kernel_q"].dtype == np.int8


def test_scale_round_trip_and_digest(tmp_path, tiny_model_and_vars):
    m, variables = tiny_model_and_vars
    mq = m.clone(quantize="int8")
    snap = str(tmp_path / "checkpoint_last.pt")
    kw = dict(mode="int8", snapshot_path=snap, vocab_size=100, pad_idx=1,
              bucket_edges=[16], batch_size=2)
    _, info1 = calibrate.calibrate_for_serving(mq, m, variables, **kw)
    assert info1["source"] == "calibrated"
    path = calibrate.scales_path(snap)
    assert os.path.exists(path)
    with open(path) as f:
        doc = json.load(f)
    assert doc["mode"] == "int8" and doc["sites"]
    # second start: digest matches -> scales re-used, verified
    prepared2, info2 = calibrate.calibrate_for_serving(mq, m, variables,
                                                      **kw)
    assert info2["source"] == "reused-verified"
    assert info2["weights_digest"] == info1["weights_digest"]
    # different weights -> digest mismatch -> re-derive, never re-use
    mutated = jax.tree_util.tree_map(np.asarray, variables)
    mutated["params"]["lm_head"]["dense"]["kernel"] = (
        mutated["params"]["lm_head"]["dense"]["kernel"] + 0.5
    )
    _, info3 = calibrate.calibrate_for_serving(mq, m, mutated, **kw)
    assert info3["source"] == "calibrated"
    assert info3["weights_digest"] != info1["weights_digest"]


def test_corrupt_scale_sidecar_rederives_not_crashes(
    tmp_path, tiny_model_and_vars
):
    """A bad sidecar beside a good checkpoint (torn write, old version,
    site naming a param the tree lacks) must RE-DERIVE — startup and hot
    reload treat re-calibration as the remedy, never a crash."""
    m, variables = tiny_model_and_vars
    mq = m.clone(quantize="int8")
    snap = str(tmp_path / "checkpoint_last.pt")
    kw = dict(mode="int8", snapshot_path=snap, vocab_size=100, pad_idx=1,
              bucket_edges=[16], batch_size=2)
    path = calibrate.scales_path(snap)
    # torn write
    with open(path, "w") as f:
        f.write("{not json")
    _, info = calibrate.calibrate_for_serving(mq, m, variables, **kw)
    assert info["source"] == "calibrated"
    # unsupported version
    with open(path, "w") as f:
        json.dump({"version": 99}, f)
    _, info = calibrate.calibrate_for_serving(mq, m, variables, **kw)
    assert info["source"] == "calibrated"
    # digest site absent from the candidate tree (arch/config mismatch)
    with open(path) as f:
        doc = json.load(f)
    doc["sites"]["nonexistent/site"] = {"w_absmax": 1.0}
    with open(path, "w") as f:
        json.dump(doc, f)
    _, info = calibrate.calibrate_for_serving(mq, m, variables, **kw)
    assert info["source"] == "calibrated"
    # ...and the re-derive healed the sidecar: next start re-uses it
    _, info = calibrate.calibrate_for_serving(mq, m, variables, **kw)
    assert info["source"] == "reused-verified"


def test_malformed_scale_file_is_a_calibration_error(tmp_path):
    path = str(tmp_path / "x.quant-scales.json")
    with open(path, "w") as f:
        f.write("{not json")
    with pytest.raises(calibrate.CalibrationError):
        calibrate.load_scales(path)
    with open(path, "w") as f:
        json.dump({"version": 99}, f)
    with pytest.raises(calibrate.CalibrationError):
        calibrate.load_scales(path)
    assert calibrate.load_scales(str(tmp_path / "absent.json")) is None


def test_moe_plus_quantize_is_refused():
    m = _tiny_bert(moe_experts=4, quantize="int8")
    toks = np.zeros((2, 16), np.int32)
    with pytest.raises(ValueError, match="MoE"):
        m.init_params(jax.random.PRNGKey(0),
                      {"net_input": {"src_tokens": toks}})


# ---------------------------------------------------------------------------
# fusion audit: the dequant section
# ---------------------------------------------------------------------------

def test_dequant_detector_flags_unfused_chain():
    from unicore_tpu.analysis.fusion_audit import audit_hlo

    hlo = """
ENTRY %main (p0: s8[8,128], p1: f32[1,128]) -> f32[8,128] {
  %p0 = s8[8,128]{1,0} parameter(0)
  %p1 = f32[1,128]{1,0} parameter(1)
  %convert.1 = f32[8,128]{1,0} convert(%p0)
  ROOT %multiply.1 = f32[8,128]{1,0} multiply(%convert.1, %p1)
}
"""
    d = audit_hlo(hlo)["dequant"]
    assert d["materialized_converts"] == 1
    assert d["unfused_chains"] == 1
    assert d["examples"] == ["convert.1->multiply.1"]
    # the fused form of the same computation is clean: the convert lives
    # in the fusion BODY (a called computation)
    fused = """
%dequant_body (a: s8[8,128], b: f32[1,128]) -> f32[8,128] {
  %a = s8[8,128]{1,0} parameter(0)
  %b = f32[1,128]{1,0} parameter(1)
  %convert.2 = f32[8,128]{1,0} convert(%a)
  ROOT %multiply.2 = f32[8,128]{1,0} multiply(%convert.2, %b)
}

ENTRY %main (p0: s8[8,128], p1: f32[1,128]) -> f32[8,128] {
  %p0 = s8[8,128]{1,0} parameter(0)
  %p1 = f32[1,128]{1,0} parameter(1)
  ROOT %fusion.1 = f32[8,128]{1,0} fusion(%p0, %p1), kind=kLoop, calls=%dequant_body
}
"""
    d2 = audit_hlo(fused)["dequant"]
    assert d2["materialized_converts"] == 0
    assert d2["unfused_chains"] == 0


def test_compiled_quant_program_has_no_materialized_dequant(
    tiny_model_and_vars
):
    """THE acceptance check: the compiled int8 serving program contains
    no computation-level dequant convert chains — every dequant multiply
    fused into its consumer, proven device-free on the CPU backend."""
    from unicore_tpu.analysis.fusion_audit import audit_compiled

    m, variables = tiny_model_and_vars
    mq = m.clone(quantize="int8")
    prepared, _ = calibrate.calibrate_for_serving(
        mq, m, variables, mode="int8", snapshot_path=None,
        vocab_size=100, pad_idx=1, bucket_edges=[16], batch_size=2,
    )

    def fwd(v, t):
        return mq.apply(v, t, train=False)

    toks = np.zeros((2, 16), np.int32)
    compiled = jax.jit(fwd).lower(prepared, toks).compile()
    report = audit_compiled(compiled)
    assert report is not None and "dequant" in report
    assert report["dequant"]["unfused_chains"] == 0, report["dequant"]
    assert report["dequant"]["materialized_converts"] == 0, \
        report["dequant"]
    assert report["fusions"] > 0  # the program did fuse, not degenerate
