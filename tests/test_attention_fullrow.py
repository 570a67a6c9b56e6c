"""Full-row attention kernel numerics vs the jnp reference (same sweep style
as tests/test_flash_attention.py — the analogue of the reference's
/root/reference/tests/test_softmax.py).  Interpret mode on CPU; compiled on
a real TPU."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from unicore_tpu.ops import flash_attention as fa
from unicore_tpu.ops import attention_fullrow as fr


def make_inputs(B, H, L, D, dtype, bias_shape=None, with_mask=False, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(keys[0], (B, H, L, D), dtype)
    k = jax.random.normal(keys[1], (B, H, L, D), dtype)
    v = jax.random.normal(keys[2], (B, H, L, D), dtype)
    bias = (
        jax.random.normal(keys[3], bias_shape, jnp.float32)
        if bias_shape is not None
        else None
    )
    mask = None
    if with_mask:
        lens = np.linspace(L // 2, L, B, dtype=np.int64)
        mask = jnp.asarray((np.arange(L)[None, :] >= lens[:, None]).astype(np.int32))
    return q, k, v, bias, mask


def test_supported_gate():
    assert fr.supported(512, 512, 64, None)
    assert fr.supported(512, 512, 64, 1)
    assert not fr.supported(512, 512, 64, 4)  # per-batch bias
    assert not fr.supported(2048, 2048, 64, None)  # beyond MAX_ROW
    assert not fr.supported(130, 128, 64, None)  # non-128-multiple


def test_group_picking():
    assert fr._pick_group(64, 8) == 8
    assert fr._pick_group(6, 8) == 6
    assert fr._pick_group(7, 4) == 1
    # f32 at L=512 must shrink below the bf16 group
    g_bf16 = fr._auto_group(64, 512, 512, 64, 2, 8, 8, 3)
    g_f32 = fr._auto_group(64, 512, 512, 64, 4, 8, 8, 3)
    assert g_f32 <= g_bf16


@pytest.mark.parametrize("L,D", [(128, 64), (256, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_forward_matches_reference(L, D, dtype):
    B, H = 4, 2
    q, k, v, bias, mask = make_inputs(
        B, H, L, D, dtype, bias_shape=(1, H, L, L), with_mask=True
    )
    out = fr.fullrow_attention(
        q, k, v, bias=bias, kv_padding_mask=mask, sm_scale=D ** -0.5
    )
    ref = fa.mha_reference(
        q, k, v, bias=bias, kv_padding_mask=mask, sm_scale=D ** -0.5
    )
    tol = 2e-2 if dtype == jnp.bfloat16 else 5e-3
    assert float(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32)).max()) < tol


@pytest.mark.parametrize(
    "bias_shape", [None, (1, 2, 128, 128), (1, 1, 128, 128)]
)
@pytest.mark.parametrize("with_mask", [False, True])
def test_gradients_match_reference(bias_shape, with_mask):
    B, H, L, D = 4, 2, 128, 32
    q, k, v, bias, mask = make_inputs(
        B, H, L, D, jnp.float32, bias_shape=bias_shape, with_mask=with_mask
    )
    do = jax.random.normal(jax.random.PRNGKey(9), q.shape, jnp.float32)

    argnums = (0, 1, 2) if bias is None else (0, 1, 2, 3)

    def loss_fr(q, k, v, b=None):
        return jnp.sum(
            fr.fullrow_attention(
                q, k, v, bias=b, kv_padding_mask=mask, sm_scale=D ** -0.5
            )
            * do
        )

    def loss_ref(q, k, v, b=None):
        return jnp.sum(
            fa.mha_reference(
                q, k, v, bias=b, kv_padding_mask=mask, sm_scale=D ** -0.5
            )
            * do
        )

    args = (q, k, v) if bias is None else (q, k, v, bias)
    g1 = jax.grad(loss_fr, argnums)(*args)
    g2 = jax.grad(loss_ref, argnums)(*args)
    for a, b in zip(g1, g2):
        assert float(jnp.abs(a - b).max()) < 5e-3


def test_matches_online_kernel():
    """Full-row and online kernels agree (no dropout, shared semantics)."""
    B, H, L, D = 2, 2, 256, 64
    q, k, v, bias, mask = make_inputs(
        B, H, L, D, jnp.float32, bias_shape=(1, H, L, L), with_mask=True
    )
    a = fr.fullrow_attention(
        q, k, v, bias=bias, kv_padding_mask=mask, sm_scale=D ** -0.5
    )
    b = fa.flash_attention(
        q, k, v, bias=bias, kv_padding_mask=mask, sm_scale=D ** -0.5
    )
    assert float(jnp.abs(a - b).max()) < 5e-3


def test_fully_masked_rows_zero():
    B, H, L, D = 2, 2, 128, 32
    q, k, v, _, _ = make_inputs(B, H, L, D, jnp.float32)
    mask = jnp.ones((B, L), jnp.int32)  # everything masked
    out = fr.fullrow_attention(q, k, v, kv_padding_mask=mask)
    assert float(jnp.abs(out).max()) == 0.0
