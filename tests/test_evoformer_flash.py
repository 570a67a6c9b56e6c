"""Grouped-bias flash kernel for Evoformer attention (round-4 verdict #2).

The MSA-row / triangle patterns share one layout: flattened batch N = G*R
where runs of R consecutive batches share a pair-bias slab (G groups).
The reference's fused softmax serves exactly this broadcast
(/root/reference/csrc/softmax_dropout/interface.cpp:37-48, shapes in
/root/reference/tests/test_softmax.py:81-170); here the whole attention is
blockwise-online with the grouped bias indexed in-kernel.

Kernel runs in interpret mode on CPU; the XLA fallback path of the very
same module is the reference — if the two ever diverge, routing is wrong.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from unicore_tpu.ops import flash_attention as fa
from unicore_tpu.ops._pallas import interpret_enabled
from unicore_tpu.platform_utils import on_tpu


@pytest.fixture()
def interpret_kernels():
    prev = interpret_enabled()
    fa.set_interpret(not on_tpu())
    yield
    fa.set_interpret(prev)


def test_flash_grouped_bias_matches_reference(interpret_kernels):
    """Raw op: grouped bias (G, H, L, L) with B = G*R, fwd + all grads."""
    B, G, H, L, D = 6, 3, 2, 256, 16
    r = np.random.RandomState(0)
    q, k, v = (jnp.asarray(r.randn(B, H, L, D), jnp.float32)
               for _ in range(3))
    bias = jnp.asarray(r.randn(G, H, L, L), jnp.float32)
    lens = r.randint(L // 2, L + 1, size=B)
    mask = jnp.asarray((np.arange(L)[None] >= lens[:, None]).astype(np.int32))

    out = fa.flash_attention(
        q, k, v, bias=bias, kv_padding_mask=mask, sm_scale=D ** -0.5
    )
    ref = fa.mha_reference(
        q, k, v, bias=bias, kv_padding_mask=mask, sm_scale=D ** -0.5
    )
    assert float(jnp.abs(out - ref).max()) < 2e-5

    def loss(fn, q, k, v, b):
        return jnp.sum(
            fn(q, k, v, bias=b, kv_padding_mask=mask, sm_scale=D ** -0.5) ** 2
        )

    gk = jax.jit(jax.grad(lambda *a: loss(fa.flash_attention, *a),
                          (0, 1, 2, 3)))(q, k, v, bias)
    gr = jax.jit(jax.grad(lambda *a: loss(fa.mha_reference, *a),
                          (0, 1, 2, 3)))(q, k, v, bias)
    for name, a, b in zip("q k v bias".split(), gk, gr):
        err = float(jnp.abs(a - b).max())
        scale = float(jnp.abs(b).max()) + 1e-6
        assert err / scale < 2e-4, (name, err, scale)
    # the grouped bias grad really has group shape, not batch shape
    assert gk[3].shape == (G, H, L, L)


def _force_xla_fallback():
    """Close GatedAttention's kernel gate regardless of backend — on a
    real TPU `set_interpret(False)` would NOT close it (backend_ok stays
    true), and the 'fallback' leg would silently rerun the kernel."""
    import contextlib

    import unicore_tpu.modules.evoformer as evo

    @contextlib.contextmanager
    def ctx():
        orig = evo._flash_ok
        evo._flash_ok = lambda *a, **k: False
        try:
            yield
        finally:
            evo._flash_ok = orig

    return ctx()


def _ga_both_paths(q_x, kv_x, bias, kv_mask, heads):
    """Run GatedAttention once on the kernel route, once on the XLA
    fallback (gate forced shut), same params."""
    from unicore_tpu.modules.evoformer import GatedAttention

    mod = GatedAttention(q_x.shape[-1], heads)
    params = mod.init(
        {"params": jax.random.PRNGKey(0)}, q_x, kv_x, bias, kv_mask
    )

    def run(p):
        return mod.apply(p, q_x, kv_x, bias, kv_mask)

    out_kernel = run(params)
    g_kernel = jax.grad(lambda p: jnp.sum(run(p) ** 2))(params)
    with _force_xla_fallback():
        out_xla = run(params)
        g_xla = jax.grad(lambda p: jnp.sum(run(p) ** 2))(params)
    return (out_kernel, g_kernel), (out_xla, g_xla)


def _assert_close(pair_kernel, pair_xla, tol=2e-4):
    out_k, g_k = pair_kernel
    out_x, g_x = pair_xla
    scale = float(jnp.abs(out_x).max()) + 1e-6
    assert float(jnp.abs(out_k - out_x).max()) / scale < tol
    for a, b in zip(
        jax.tree_util.tree_leaves(g_k), jax.tree_util.tree_leaves(g_x)
    ):
        s = float(jnp.abs(b).max()) + 1e-6
        assert float(jnp.abs(a - b).max()) / s < tol


def test_gated_attention_msa_row_layout(interpret_kernels):
    """MSA-row shape: lead (B, R), grouped bias per sequence + row mask."""
    B, R, L, Dm, H = 2, 3, 128, 32, 4
    r = np.random.RandomState(1)
    m = jnp.asarray(r.randn(B, R, L, Dm), jnp.float32)
    bias = jnp.asarray(r.randn(B, H, L, L), jnp.float32)
    mask = jnp.asarray(
        (r.rand(B, R, L) > 0.2).astype(np.float32)
    ).at[:, :, 0].set(1.0)  # no fully-masked rows (paths differ there)
    _assert_close(*_ga_both_paths(m, m, bias, mask, H))


def test_gated_attention_triangle_layout(interpret_kernels):
    """Triangle shape: lead (B, I), grouped bias per pair matrix."""
    B, L, Dz, H = 2, 128, 16, 4
    r = np.random.RandomState(2)
    z = jnp.asarray(r.randn(B, L, L, Dz), jnp.float32)
    bias = jnp.asarray(r.randn(B, H, L, L), jnp.float32)
    pm = jnp.asarray(
        (r.rand(B, L, L) > 0.2).astype(np.float32)
    ).at[:, :, 0].set(1.0)
    _assert_close(*_ga_both_paths(z, z, bias, pm, H))


def test_gated_attention_no_bias_mask_only(interpret_kernels):
    """MSA-column shape: no bias, kv mask only."""
    B, L, R, Dm, H = 2, 4, 128, 32, 4
    r = np.random.RandomState(3)
    mt = jnp.asarray(r.randn(B, L, R, Dm), jnp.float32)
    mask = jnp.asarray(
        (r.rand(B, L, R) > 0.2).astype(np.float32)
    ).at[:, :, 0].set(1.0)
    _assert_close(*_ga_both_paths(mt, mt, None, mask, H))


def test_evoformer_iteration_kernel_vs_fallback(interpret_kernels):
    """Whole EvoformerIteration at kernel-eligible L: the routed blocks
    (MSA row, triangle start/end) agree with the XLA-only forward."""
    from unicore_tpu.modules.evoformer import EvoformerIteration

    B, R, L = 1, 4, 128
    r = np.random.RandomState(4)
    msa = jnp.asarray(r.randn(B, R, L, 32), jnp.float32)
    pair = jnp.asarray(r.randn(B, L, L, 16), jnp.float32)
    msa_mask = jnp.ones((B, R, L))
    pair_mask = jnp.ones((B, L, L))
    block = EvoformerIteration(
        msa_dim=32, pair_dim=16, msa_heads=4, pair_heads=4, dropout=0.0
    )
    params = block.init(
        {"params": jax.random.PRNGKey(5)}, msa, pair, msa_mask, pair_mask,
        False,
    )

    m_k, z_k = block.apply(params, msa, pair, msa_mask, pair_mask, False)
    with _force_xla_fallback():
        m_x, z_x = block.apply(params, msa, pair, msa_mask, pair_mask, False)
    for a, b in ((m_k, m_x), (z_k, z_x)):
        s = float(jnp.abs(b).max()) + 1e-6
        assert float(jnp.abs(a - b).max()) / s < 2e-4


def test_gated_attention_pads_unaligned_length(interpret_kernels):
    """Non-128-multiple L (e.g. an AF2-style 250 crop) rides the kernel
    via router padding: padded keys mask out, padded query rows slice
    off — matches the XLA fallback, gradients included."""
    from unicore_tpu.modules.evoformer import _flash_ok

    B, R, L, Dm, H = 1, 2, 250, 32, 4
    assert _flash_ok(B * R, L, L, Dm // H, jnp.float32, None)
    r = np.random.RandomState(5)
    m = jnp.asarray(r.randn(B, R, L, Dm), jnp.float32)
    bias = jnp.asarray(r.randn(B, H, L, L), jnp.float32)
    mask = jnp.asarray(
        (r.rand(B, R, L) > 0.2).astype(np.float32)
    ).at[:, :, 0].set(1.0)
    _assert_close(*_ga_both_paths(m, m, bias, mask, H))
