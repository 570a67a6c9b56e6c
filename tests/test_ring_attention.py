"""Ring attention (sequence parallelism) equivalence on an 8-device mesh."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from unicore_tpu.parallel import make_mesh
from unicore_tpu.parallel.ring_attention import ring_self_attention
from unicore_tpu.ops.flash_attention import mha_reference
from unicore_tpu.platform_utils import on_tpu


@pytest.mark.parametrize("with_mask", [False, True])
def test_ring_matches_full_attention(with_mask):
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    mesh = make_mesh(data=1, seq=8)
    B, H, L, D = 2, 4, 128, 32
    q = jax.random.normal(jax.random.PRNGKey(0), (B, H, L, D))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, H, L, D))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, H, L, D))
    mask = None
    if with_mask:
        lens = np.array([100, 128])
        mask = jnp.asarray(
            (np.arange(L)[None, :] >= lens[:, None]).astype(np.int32)
        )

    out = ring_self_attention(mesh, q, k, v, kv_padding_mask=mask, sm_scale=D ** -0.5)
    ref = mha_reference(q, k, v, kv_padding_mask=mask, sm_scale=D ** -0.5)
    err = float(jnp.abs(out - ref).max())
    assert err < 1e-5, err


@pytest.mark.parametrize("with_bias", [False, True])
def test_ring_gradients_match(with_bias):
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    mesh = make_mesh(data=1, seq=8)
    B, H, L, D = 1, 2, 64, 16
    q = jax.random.normal(jax.random.PRNGKey(0), (B, H, L, D))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, H, L, D))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, H, L, D))
    bias = (
        jax.random.normal(jax.random.PRNGKey(3), (H, L, L)) if with_bias else None
    )

    def loss_ring(q, k, v, b):
        return jnp.sum(
            ring_self_attention(mesh, q, k, v, bias=b, sm_scale=D ** -0.5) ** 2
        )

    def loss_ref(q, k, v, b):
        return jnp.sum(
            mha_reference(
                q, k, v, bias=None if b is None else b[None], sm_scale=D ** -0.5
            ) ** 2
        )

    argnums = (0, 1, 2, 3) if with_bias else (0, 1, 2)
    # jit: the eager shard_map ppermute chain is very slow on 1 core
    g1 = jax.jit(jax.grad(loss_ring, argnums=argnums))(q, k, v, bias)
    g2 = jax.jit(jax.grad(loss_ref, argnums=argnums))(q, k, v, bias)
    for name, a, b in zip(["dq", "dk", "dv", "dbias"], g1, g2):
        err = float(jnp.abs(a - b).max())
        assert err < 1e-4, f"{name}: {err}"


def test_ring_with_relpos_bias():
    """Rel-pos-style (H, L, L) bias rides the ring: key columns rotate with
    k/v and each device slices its query rows by ring position."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    mesh = make_mesh(data=1, seq=8)
    B, H, L, D = 2, 4, 128, 32
    q = jax.random.normal(jax.random.PRNGKey(0), (B, H, L, D))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, H, L, D))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, H, L, D))
    bias = jax.random.normal(jax.random.PRNGKey(3), (H, L, L))
    lens = np.array([100, 128])
    mask = jnp.asarray((np.arange(L)[None, :] >= lens[:, None]).astype(np.int32))

    out = ring_self_attention(
        mesh, q, k, v, kv_padding_mask=mask, bias=bias, sm_scale=D ** -0.5
    )
    ref = mha_reference(
        q, k, v, bias=bias[None], kv_padding_mask=mask, sm_scale=D ** -0.5
    )
    err = float(jnp.abs(out - ref).max())
    assert err < 1e-5, err


def test_ring_dropout_deterministic_and_mass_preserving():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    mesh = make_mesh(data=1, seq=8)
    B, H, L, D = 2, 4, 128, 32
    q = jax.random.normal(jax.random.PRNGKey(0), (B, H, L, D))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, H, L, D))
    v = jnp.ones((B, H, L, D))
    rng = jax.random.PRNGKey(7)
    ring = jax.jit(
        lambda q_, k_, v_, r: ring_self_attention(
            mesh, q_, k_, v_, dropout_rate=0.4, dropout_rng=r,
            sm_scale=D ** -0.5,
        )
    )
    o1 = ring(q, k, v, rng)
    o2 = ring(q, k, v, rng)
    o3 = ring(q, k, v, jax.random.PRNGKey(8))
    assert bool(jnp.all(o1 == o2))
    assert bool(jnp.any(o1 != o3))
    # v == ones: expected output is ~1 (inverted dropout preserves mass)
    assert abs(float(jnp.mean(o1)) - 1.0) < 0.05
    # grads flow
    g = jax.jit(jax.grad(
        lambda q_: jnp.sum(
            ring_self_attention(mesh, q_, k, v, dropout_rate=0.4,
                                dropout_rng=rng, sm_scale=D ** -0.5) ** 2
        )
    ))(q)
    assert bool(jnp.isfinite(g).all())


@pytest.mark.parametrize("with_bias", [False, True])
def test_pallas_ring_matches_reference(with_bias):
    """Flash-blocked ring (Pallas kernels per visiting chunk, interpret mode
    on CPU): forward and gradients match full attention."""
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    from unicore_tpu.ops import flash_attention as fa
    from unicore_tpu.ops._pallas import interpret_enabled
    from unicore_tpu.parallel.ring_attention import pallas_ring_supported

    prev_interpret = interpret_enabled()
    fa.set_interpret(not on_tpu())
    try:
        mesh = make_mesh(data=1, seq=4, devices=jax.devices()[:4])
        B, H, L, D = 1, 2, 512, 16  # Lc = 128: the pallas gate opens
        assert pallas_ring_supported(L // 4, D, jnp.float32)
        q = jax.random.normal(jax.random.PRNGKey(0), (B, H, L, D))
        k = jax.random.normal(jax.random.PRNGKey(1), (B, H, L, D))
        v = jax.random.normal(jax.random.PRNGKey(2), (B, H, L, D))
        lens = np.array([480])
        mask = jnp.asarray(
            (np.arange(L)[None, :] >= lens[:, None]).astype(np.int32)
        )
        bias = (
            jax.random.normal(jax.random.PRNGKey(3), (H, L, L))
            if with_bias
            else None
        )

        out = ring_self_attention(
            mesh, q, k, v, kv_padding_mask=mask, bias=bias, sm_scale=D ** -0.5
        )
        ref = mha_reference(
            q, k, v, kv_padding_mask=mask,
            bias=None if bias is None else bias[None], sm_scale=D ** -0.5,
        )
        err = float(jnp.abs(out - ref).max())
        assert err < 2e-5, err

        def loss_ring(q, k, v, b):
            return jnp.sum(
                ring_self_attention(
                    mesh, q, k, v, kv_padding_mask=mask, bias=b,
                    sm_scale=D ** -0.5,
                ) ** 2
            )

        def loss_ref(q, k, v, b):
            return jnp.sum(
                mha_reference(
                    q, k, v, kv_padding_mask=mask,
                    bias=None if b is None else b[None], sm_scale=D ** -0.5,
                ) ** 2
            )

        argnums = (0, 1, 2) if bias is None else (0, 1, 2, 3)
        g_ring = jax.jit(jax.grad(loss_ring, argnums))(q, k, v, bias)
        g_ref = jax.jit(jax.grad(loss_ref, argnums))(q, k, v, bias)
        for gr, gf in zip(g_ring, g_ref):
            err = float(jnp.abs(gr - gf).max())
            scale = float(jnp.abs(gf).max()) + 1e-6
            assert err / scale < 2e-4, (err, scale)
    finally:
        fa.set_interpret(prev_interpret)  # don't leak interpret mode
