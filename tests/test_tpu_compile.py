"""Mosaic compile rehearsal: every Pallas kernel family of the main paths,
compiled for a DESCRIBED TPU v5e (no chip attached) at BERT-base /
``transformer_lm`` widths.

Interpret-mode parity tests prove a kernel's arithmetic; they cannot show
what the chip's compiler refuses (block shapes off the (8, 128) tiling,
primitives with no TPU lowering, a kernel's real VMEM appetite).  These
compiles can, at ~2 s each and no chip time.  A compile that passes is not
a chip run — ``chip_smoke.py``'s ``kernels`` phase executes the same table
(``unicore_tpu/ops/kernel_cases.py``) on the chip against the jnp oracles.

The topology is described inside a module-scoped fixture (never at import:
only one process may load libtpu, and every xdist worker imports every
test file), the kernel entries are called directly (the ``auto`` gates see
the CPU backend here), and everything compiles in the test's own process
with the persistent compile cache off (a described-device executable
cannot be read back without a chip).
"""

import math
import os
import re

import jax
import pytest
from jax.sharding import SingleDeviceSharding

from unicore_tpu.ops import _pallas
from unicore_tpu.ops.kernel_cases import kernel_cases


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache as cc

    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    # other test files set the interpret override as they are imported;
    # put back exactly what was there, whichever file ran first
    interpret_was = _pallas._override
    _pallas.set_interpret(False)
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        _pallas.set_interpret(interpret_was)
        jax.config.update("jax_enable_compilation_cache", cache_was)
        cc.reset_cache()


CASES = {case.name: case for case in kernel_cases()}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip):
    case = CASES[name]
    args = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        for shape, dtype, _fill in case.specs
    ]
    compiled = jax.jit(case.kernel).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), name


B, L, E, V = 16, 512, 768, 30522


def _encoder_on_mesh(mesh, layers, monkeypatch):
    """A BERT-base-wide encoder + tied LM head as abstract values laid over
    ``mesh`` (params and embedding replicated, the token batch over the dp
    tier), and its mean-NLL loss.  The gates ask on_tpu() and see the CPU
    here, so the test steers them."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    import unicore_tpu.modules.multihead_attention as mha
    from unicore_tpu.modules import TransformerEncoder
    from unicore_tpu.parallel import mesh as mesh_mod

    jnp = jax.numpy
    monkeypatch.setattr(mha, "on_tpu", lambda: True)
    monkeypatch.setattr(mesh_mod, "_global_mesh", mesh)
    rows = NamedSharding(mesh, mesh_mod.batch_spec(mesh))
    everywhere = NamedSharding(mesh, P())

    enc = TransformerEncoder(
        encoder_layers=layers, embed_dim=E, ffn_embed_dim=3072,
        attention_heads=12, max_seq_len=L, rel_pos=True, post_ln=True,
    )
    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(
        lambda: enc.init({"params": key, "dropout": key},
                         jnp.zeros((B, L, E), jnp.bfloat16))
    )
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16,
                                       sharding=everywhere),
        params,
    )
    emb = jax.ShapeDtypeStruct((V, E), jnp.bfloat16, sharding=everywhere)
    tok = jax.ShapeDtypeStruct((B, L), jnp.int32, sharding=rows)

    def loss(params, emb, tok, key):
        out = enc.apply(params, emb[tok], padding_mask=(tok == 0),
                        train=True, rngs={"dropout": key})
        logits = (out @ emb.T).astype(jnp.float32)
        picked = jnp.take_along_axis(logits, tok[..., None], -1)[..., 0]
        return jnp.mean(jax.nn.logsumexp(logits, -1) - picked)

    return params, emb, tok, key, loss


def _assert_kernel_sees_a_quarter(compiled):
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # the kernel sees this device's quarter of the batch, not all 16 rows
    assert f"bf16[{B // 4},12,{L},64]" in text
    assert f"bf16[{B},12,{L},64]" not in text


def test_data_parallel_bert_base_compiles_for_four_chips(topo, one_chip,
                                                         monkeypatch):
    """The four-chip rehearsal of ``chip_smoke.py --four-chips``: BERT-base
    at full width AND depth (12 layers, rel-pos bias, tied 30,522-way LM
    head), forward+backward, batch 16 laid over the ``data`` axis of the
    four described chips, dropout on.  About a minute of compile, and it
    stands for two refusals that only a real multi-chip program showed:

    * XLA's SPMD pass cannot partition a Mosaic kernel ("wrap the call in a
      shard_map") — interpret mode on virtual CPU devices never showed it.
      The module router runs the kernel inside a data-parallel shard_map;
      each chip's kernel sees a quarter of the batch, not all of it.
    * at this depth the full-row attention backward needed 16.71 MiB of
      scoped VMEM against the 16 MiB default (it compiles alone, and in a
      6-layer program): every kernel now declares ``_pallas.VMEM_LIMIT``.
    """
    from unicore_tpu.parallel import mesh as mesh_mod

    mesh = mesh_mod.make_mesh(data=4, devices=topo.devices)
    params, emb, tok, key, loss = _encoder_on_mesh(mesh, 12, monkeypatch)

    def step(params, emb, tok):
        return jax.grad(loss, argnums=(0, 1))(params, emb, tok, key)

    compiled = jax.jit(step).lower(params, emb, tok).compile()
    _assert_kernel_sees_a_quarter(compiled)


def test_two_level_reduction_with_flash_compiles_for_pod_x_data(
        topo, one_chip, monkeypatch):
    """``--num-pods 2`` over the four described chips (pod 2 x data 2): the
    trainer's forward/backward runs inside ``parallel/hierarchy.py``'s
    full-manual shard_map over the dp tier.  The attention router must NOT
    open its own data-parallel shard_map there (JAX refuses a second one
    over axes that are already Manual); it calls the kernel on the local
    rows.  One layer: the refusal is at lowering, depth adds nothing."""
    from unicore_tpu.parallel import hierarchy, mesh as mesh_mod
    from unicore_tpu.parallel.plan import ParallelPlan

    mesh = mesh_mod.make_mesh(pods=2, data=2, devices=topo.devices)
    params, emb, tok, key, loss = _encoder_on_mesh(mesh, 1, monkeypatch)

    def fb(params, sample, rng, loss_scale, weight):
        value, grads = jax.value_and_grad(loss)(
            params, sample["emb"], sample["tok"], rng)
        return grads, jax.numpy.float32(sample["tok"].shape[0]), {
            "loss": value}

    wrapped = hierarchy.wrap_forward_backward(
        lambda p, s, *rest: fb(p["enc"], dict(s, emb=p["emb"]), *rest),
        mesh, ParallelPlan(pods=2, data=2))

    def step(params, emb, tok):
        return wrapped({"enc": params, "emb": emb}, {"tok": tok}, key,
                       1.0, 1.0)

    compiled = jax.jit(step).lower(params, emb, tok).compile()
    _assert_kernel_sees_a_quarter(compiled)


def _encoder_layer_grad_hlo(one_chip, monkeypatch, batch, length, embed,
                            heads, ffn, bias_shape, post_ln, return_attn):
    """Optimized HLO of one ``TransformerEncoderLayer`` forward + backward
    in bf16 on one described chip, dropout off as the benchmark's cells run
    it, the attention gate steered as ``_encoder_on_mesh`` steers it."""
    import unicore_tpu.modules.multihead_attention as mha
    from unicore_tpu.modules import TransformerEncoderLayer

    jnp = jax.numpy
    monkeypatch.setattr(mha, "on_tpu", lambda: True)
    layer = TransformerEncoderLayer(
        embed_dim=embed, ffn_embed_dim=ffn, attention_heads=heads,
        dropout=0.0, attention_dropout=0.0, post_ln=post_ln,
    )

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda a: spec(a.shape),
        jax.eval_shape(lambda: layer.init(
            jax.random.PRNGKey(0),
            jnp.zeros((batch, length, embed), jnp.bfloat16))),
    )

    def loss(params, x, bias, mask):
        out = layer.apply(params, x, attn_bias=bias, padding_mask=mask,
                          return_attn=return_attn, train=True)
        return sum(jnp.sum(a.astype(jnp.float32))
                   for a in jax.tree_util.tree_leaves(out))

    return jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        params, spec((batch, length, embed)), spec(bias_shape),
        spec((batch, length), jnp.bool_),
    ).compile().as_text()


def _copies(hlo_text):
    """(name, element count) of every ``copy`` instruction."""
    return [
        (m.group(1), math.prod(int(d) for d in m.group(2).split(",") if d))
        for m in re.finditer(
            r"^\s*(?:ROOT )?%(\S+) = \w+\[([\d,]*)\]\S* copy\(", hlo_text,
            re.M)
    ]


def test_attention_projections_leave_no_activation_copy(one_chip,
                                                        monkeypatch):
    """``bert_base.train_mlm512``'s attention block: the projections hand
    the full-row kernels their ``(B, H, L, D)`` operands and take the
    result back with no standalone layout ``copy`` of an activation between
    them (the flat projections left 14 per layer, 11.5% of the step's
    device time on the chip: PERF.md, PR 25 / PR 26).  What may remain is
    weight-sized.  A compile is not a chip run: it says the copies are
    gone, not what the step costs."""
    b, l, e, h = 32, 512, 768, 12
    text = _encoder_layer_grad_hlo(
        one_chip, monkeypatch, b, l, e, h, 3072, (1, h, l, l),
        post_ln=True, return_attn=False)
    big = [(n, size) for n, size in _copies(text) if size >= b * l * e]
    assert not big, f"activation-sized copies: {big}"
    assert "ble,ehd->bhld" in text and "bhld,hde->ble" in text
    for kernel in ("fullrow_attn_fwd", "fullrow_attn_bwd"):
        call = re.search(
            rf"%{kernel}\S* = .*? custom-call\((.*?)\), "
            r'custom_call_target="tpu_custom_call"', text)
        assert call, f"no Mosaic call named {kernel}"
        # (scalar-prefetch seed,) q, k, v: still (B, H, L, D), row-major
        for name in call.group(1).split(", ")[1:4]:
            name = re.escape(name.split("*/")[-1])
            assert re.search(
                rf"{name} = bf16\[{b},{h},{l},{e // h}\]\{{3,2,1,0[:}}]",
                text), f"{kernel} operand {name}"


def _computations(hlo_text):
    """{computation name -> its instruction lines}; the entry computation
    also under ``"ENTRY"`` (the fusion audit's own splitter)."""
    from unicore_tpu.analysis.fusion_audit import _split_computations

    split = _split_computations(hlo_text)
    comps = {c["name"]: c["lines"] for c in split}
    (comps["ENTRY"],) = [c["lines"] for c in split if c["entry"]]
    return comps


def _holds(comps, line, opcode):
    """Whether the instruction on ``line`` is an ``opcode`` or calls, to any
    depth (a fusion nested in a fusion), a computation that holds one."""
    from unicore_tpu.analysis.fusion_audit import _CALLED_RE

    return f" {opcode}(" in line or any(
        _holds(comps, inner, opcode)
        for called in _CALLED_RE.findall(line)
        for inner in comps.get(called, ())
    )


@pytest.mark.parametrize("post_ln", [True, False])
def test_ffn_evaluates_its_gelu_once_a_pass(one_chip, monkeypatch, post_ln):
    """``bert_base.train_mlm512``'s feed-forward layer, forward + backward:
    the exact GELU's value is made once, under ``fc1``'s forward product,
    and kept (``keep_ffn_activation``), so ``fc2``'s forward and
    weight-gradient products read a plain operand; the derivative stays the
    epilogue of ``fc2``'s ``dx`` product, ONE fusion (a barrier that
    autodiff mirrors on the cotangent cuts the two apart and gives the gain
    back).  Bare, XLA clones the evaluation into all three of ``fc2``'s
    products: 36 – 40% of the peak on the chip against ``fc1``'s 77 – 93%
    (PERF.md, PR 37 / PR 38).  A compile is not a chip run: it counts the
    evaluations, not what they cost."""
    b, l, e, h, f = 32, 512, 768, 12, 3072
    comps = _computations(_encoder_layer_grad_hlo(
        one_chip, monkeypatch, b, l, e, h, f, (1, h, l, l),
        post_ln=post_ln, return_attn=False))

    def op_name(line):
        found = re.search(r'op_name="([^"]*)"', line)
        return found.group(1) if found else ""

    evaluating = [line for line in comps["ENTRY"]
                  if _holds(comps, line, "exponential")]
    forward = [line for line in evaluating if "transpose(" not in op_name(line)]
    backward = [line for line in evaluating if "transpose(" in op_name(line)]
    assert len(forward) == len(backward) == 1, [
        op_name(line) for line in evaluating]
    # the value: fc1's product fusion, which writes h, erfc's value and act
    assert "/fc1/" in op_name(forward[0]), op_name(forward[0])
    assert _holds(comps, forward[0], "convolution")
    assert forward[0].count(f"bf16[{b},{l},{f}]") >= 3, forward[0][:400]
    # the derivative: the epilogue of fc2's dx product
    assert op_name(backward[0]).endswith("fc2/dot_general")
    assert _holds(comps, backward[0], "convolution")
    assert re.match(rf"\s*%\S+ = \(.*bf16\[{b},{l},{f}\]", backward[0])
    # fc2's other products (forward: bare it ended in fc2/dot_general or in
    # the residual add; dw by its (ffn, embed) result) read what was kept
    plain = [line for line in comps["ENTRY"]
             if _holds(comps, line, "convolution")
             and line is not backward[0]
             and op_name(line).endswith("fc2/dot_general")]
    assert any(re.match(rf"\s*%\S+ = bf16\[{f},{e}\]", line)
               for line in plain), [line[:200] for line in plain]
    assert not any(_holds(comps, line, "exponential") for line in plain)


def test_bert_cell_step_fits_the_chip(topo, one_chip, monkeypatch,
                                      record_property):
    """``bert_base.train_mlm512``'s own step (the trainer's jitted
    ``train_step``, 32 x 512, twelve layers) held to what the chip limits:
    the most it holds at one time leaves 1 GB of the chip's memory, and the
    sum the benchmark's cases take stays between a quarter and three
    quarters of it.  The kept activations (two ``(32, 512, 3072)`` bfloat16
    arrays a layer: ``keep_ffn_activation``) took that sum from
    7,744,550,400 to 10,268,964,864 and the peak from 7,640,483,840 to
    10,031,237,120, past the 8.5e9 ("bytes read when the batch was chosen"
    plus 1e9, no limit of the chip's) that the benchmark's own case holds
    the sum under: ``tests/conftest.py`` expects that case to fail until a
    ``benchmark`` PR asserts the peak there.  Every layer evaluates its
    GELU twice, the value and the derivative.  No chip, no chip time."""
    monkeypatch.syspath_prepend(
        os.path.join(os.path.dirname(__file__), "benchmark"))
    import test_compile_v5e as cells
    from bench_tiny import manifest_with_candidates
    from benchmark import harness

    cell = harness.Cell(manifest_with_candidates(), "bert_base.train_mlm512")
    compiled = cells.compile_step(cell, 512, topo.devices[0], monkeypatch)
    comps = _computations(compiled.as_text())
    ffn = [line for line in comps["ENTRY"]
           if re.search(r'op_name="[^"]*/fc[12]/', line)]
    assert sum(_holds(comps, line, "exponential") for line in ffn) == 2 * 12
    peak = compiled.memory_analysis().peak_memory_in_bytes
    total = cells.total_bytes(compiled)
    record_property("peak_memory_in_bytes", peak)
    record_property("total_bytes", total)
    assert peak + 1e9 < cells.HBM, peak
    assert 0.25 * cells.HBM < total < 0.75 * cells.HBM, total


def test_unimol_shaped_layer_compiles(one_chip, monkeypatch,
                                      record_property):
    """Uni-Mol's shape (64 heads of width 8, per-batch pair bias, scores
    handed on): no kernel pins a layout here, so the projections stay flat
    and XLA places the copies as it likes (the products that make the heads
    themselves cost this encoder 15% at L = 128 on the chip: PERF.md,
    PR 26); the count is recorded, not bounded."""
    b, l, e, h = 16, 256, 512, 64
    text = _encoder_layer_grad_hlo(
        one_chip, monkeypatch, b, l, e, h, 2048, (b, h, l, l),
        post_ln=False, return_attn=True)
    big = [n for n, size in _copies(text) if size >= b * l * e]
    record_property("activation_sized_copies", len(big))
    assert "ble,ehd->bhld" not in text, (
        f"head-major products; {len(big)} activation-sized copies: {big}")
