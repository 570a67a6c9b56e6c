"""Fused softmax(+mask)(+bias)(+dropout) numerics — mirrors the reference's
single test file (/root/reference/tests/test_softmax.py): last-dim sweep
{64..2048} x dtypes {fp32, bf16}, forward AND gradients (incl. grad wrt
bias), plus the two 5-D broadcast layouts used by Uni-Fold triangle
attention (test_softmax.py:81-170).  Tolerance mirrors the reference's
1e-3 max-abs bound (scaled for bf16).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from unicore_tpu.ops.softmax_dropout import softmax_dropout
from unicore_tpu.platform_utils import on_tpu


def ref_softmax(x, mask=None, bias=None):
    x = x.astype(jnp.float32)
    if mask is not None:
        x = x + mask.astype(jnp.float32)
    if bias is not None:
        x = x + bias.astype(jnp.float32)
    return jax.nn.softmax(x, axis=-1)


@pytest.mark.parametrize("last_dim", [64, 128, 256, 512, 1024, 2048])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_forward_and_grads_dim_sweep(last_dim, dtype):
    B, Q = 4, 16
    x = jax.random.normal(jax.random.PRNGKey(0), (B, Q, last_dim), dtype)
    bias = jax.random.normal(jax.random.PRNGKey(1), (1, Q, last_dim), jnp.float32)
    mask = jnp.where(
        jax.random.bernoulli(jax.random.PRNGKey(2), 0.2, (B, 1, last_dim)),
        -1e9, 0.0,
    )

    out = softmax_dropout(x, 0.0, is_training=False, mask=mask, bias=bias)
    ref = ref_softmax(x, mask, bias).astype(dtype)
    tol = 1e-2 if dtype == jnp.bfloat16 else 1e-3
    assert float(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32)).max()) < tol

    if dtype == jnp.float32:
        g1 = jax.grad(
            lambda x_, b_: jnp.sum(
                softmax_dropout(x_, 0.0, is_training=False, mask=mask, bias=b_) ** 2
            ),
            argnums=(0, 1),
        )(x, bias)
        g2 = jax.grad(
            lambda x_, b_: jnp.sum(ref_softmax(x_, mask, b_) ** 2), argnums=(0, 1)
        )(x, bias)
        for name, a, r in zip(["dx", "dbias"], g1, g2):
            scale = max(1.0, float(jnp.abs(r).max()))
            assert float(jnp.abs(a - r).max()) / scale < 1e-5, name
            assert a.shape == r.shape  # bias grad reduced over broadcast dims


@pytest.mark.parametrize(
    "bias_shape",
    [
        # the two Uni-Fold triangle-attention layouts (reference
        # test_softmax.py:81-170): bias broadcast over a leading grouping dim
        (1, 4, 8, 32, 32),
        (2, 1, 8, 32, 32),
    ],
)
def test_unifold_5d_broadcast_layouts(bias_shape):
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 4, 8, 32, 32))
    bias = jax.random.normal(jax.random.PRNGKey(1), bias_shape)
    out = softmax_dropout(x, 0.0, is_training=False, bias=bias)
    ref = ref_softmax(x, bias=jnp.broadcast_to(bias, x.shape))
    assert float(jnp.abs(out - ref).max()) < 1e-5

    # bias grad keeps the broadcast shape (reference sums over repeat dims,
    # modules/softmax_dropout.py:44-48)
    db = jax.grad(
        lambda b_: jnp.sum(softmax_dropout(x, 0.0, is_training=False, bias=b_) ** 2)
    )(bias)
    assert db.shape == bias_shape
    db_ref = jax.grad(
        lambda b_: jnp.sum(ref_softmax(x, bias=jnp.broadcast_to(b_, x.shape)) ** 2)
    )(bias)
    assert float(jnp.abs(db - db_ref).max()) < 1e-4


def test_divisible_leading_bias_repeat():
    """The reference's (B*H) %% G == 0 repeat rule (interface.cpp:37-48)."""
    x = jax.random.normal(jax.random.PRNGKey(0), (6, 16, 64))
    bias = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 64))
    out = softmax_dropout(x, 0.0, is_training=False, bias=bias)
    ref = ref_softmax(x, bias=jnp.tile(bias, (3, 1, 1)))
    assert float(jnp.abs(out - ref).max()) < 1e-5


def test_dropout_statistics():
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 32, 128))
    rng = jax.random.PRNGKey(7)
    out = softmax_dropout(x, 0.5, is_training=True, dropout_rng=rng)
    zeros = float(jnp.mean(out == 0.0))
    assert 0.4 < zeros < 0.6
    # rows still sum to ~1 in expectation (inverted dropout)
    sums = jnp.sum(out, axis=-1)
    assert abs(float(jnp.mean(sums)) - 1.0) < 0.1
    # eval mode: no dropout applied
    out_eval = softmax_dropout(x, 0.5, is_training=False)
    assert float(jnp.mean(out_eval == 0.0)) < 0.01


# ===========================================================================
# Pallas kernel parity sweep (ops/softmax_dropout_pallas.py): fwd AND grad
# vs the jnp oracle across dtype x mask/bias broadcast layouts x
# training/eval, plus the determinism contract (same key => same mask in
# the forward and the RECOMPUTED backward).  Runs in interpret mode so the
# CPU suite exercises the real kernel code path; on a TPU backend the same
# tests compile (hardware PRNG replaces the interpret hash).
# ===========================================================================

import importlib

_sd_mod = importlib.import_module("unicore_tpu.ops.softmax_dropout")
_sd_ref = _sd_mod.softmax_dropout_reference


@pytest.fixture
def pallas_mode():
    from unicore_tpu.ops import _pallas

    prev = _pallas.interpret_enabled()
    _pallas.set_interpret(not on_tpu())
    _sd_mod.set_softmax_dropout_mode("on")
    try:
        yield
    finally:
        _sd_mod.set_softmax_dropout_mode(None)
        _pallas.set_interpret(prev)


def _layout(name, rng):
    """(input, mask, bias) for one broadcast layout (kernel-eligible
    geometry: last dim 128-multiple, rows multiple of 8)."""
    r = np.random.RandomState(rng)
    if name == "plain":
        return r.randn(4, 16, 128), None, None
    if name == "mask_bias":
        # mask broadcast over rows, bias shared over batch
        return (
            r.randn(4, 16, 128),
            np.where(r.rand(4, 1, 128) < 0.2, -1e9, 0.0),
            r.randn(1, 16, 128),
        )
    if name == "triangle_tile":
        # the Uni-Fold repeat rule: leading 2 divides leading 6 with EQUAL
        # trailing dims -> whole-slab tile (input row i reads bias row i%2)
        return r.randn(6, 16, 128), None, r.randn(2, 16, 128)
    if name == "evoformer_5d":
        # mixed per-dim broadcast: (G,1,H,Lq,Lk) against (G,N,H,Lq,Lk)
        return r.randn(2, 3, 4, 8, 128), None, r.randn(2, 1, 4, 8, 128)
    raise AssertionError(name)


_LAYOUTS = ["plain", "mask_bias", "triangle_tile", "evoformer_5d"]


@pytest.mark.parametrize("layout", _LAYOUTS)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("training", [False, True])
def test_pallas_parity_forward(pallas_mode, layout, dtype, training):
    """Eval mode (and training at rate 0) must match the jnp oracle to
    dtype tolerance on every supported layout."""
    x, mask, bias = _layout(layout, 0)
    x = jnp.asarray(x, dtype)
    mask = None if mask is None else jnp.asarray(mask, jnp.float32)
    bias = None if bias is None else jnp.asarray(bias, jnp.float32)
    out = softmax_dropout(x, 0.0, is_training=training, mask=mask, bias=bias)
    ref = _sd_ref(x, 0.0, is_training=training, mask=mask, bias=bias)
    tol = 1e-2 if dtype == jnp.bfloat16 else 1e-6
    err = float(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32)).max())
    assert err < tol, (layout, dtype, err)


@pytest.mark.parametrize("layout", _LAYOUTS)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_pallas_parity_grads(pallas_mode, layout, dtype):
    """dx / dmask / dbias vs the jnp oracle, original extra shapes kept."""
    x, mask, bias = _layout(layout, 1)
    x = jnp.asarray(x, dtype)
    mask = None if mask is None else jnp.asarray(mask, jnp.float32)
    bias = None if bias is None else jnp.asarray(bias, jnp.float32)

    diff = [x] + [e for e in (mask, bias) if e is not None]

    def run(impl, *args):
        i = 1
        m = args[i] if mask is not None else None
        i += int(mask is not None)
        b = args[i] if bias is not None else None
        out = impl(args[0], 0.0, is_training=False, mask=m, bias=b)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    argnums = tuple(range(len(diff)))
    gp = jax.grad(lambda *a: run(softmax_dropout, *a), argnums=argnums)(*diff)
    gr = jax.grad(lambda *a: run(_sd_ref, *a), argnums=argnums)(*diff)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    for a, r in zip(gp, gr):
        assert a.shape == r.shape and a.dtype == r.dtype
        scale = max(1.0, float(jnp.abs(r.astype(jnp.float32)).max()))
        err = float(
            jnp.abs(a.astype(jnp.float32) - r.astype(jnp.float32)).max()
        )
        assert err / scale < tol, (layout, dtype, err)


def test_pallas_dropout_determinism_contract(pallas_mode):
    """Same key => same mask, twice over: (a) two forwards agree bit for
    bit, (b) the BACKWARD regenerates the identical mask — grads through
    the kernel equal grads through an oracle that holds the realized keep
    mask constant."""
    x = jnp.asarray(np.random.RandomState(2).randn(4, 16, 128), jnp.float32)
    key = jax.random.PRNGKey(11)
    rate = 0.4

    o1 = softmax_dropout(x, rate, is_training=True, dropout_rng=key)
    o2 = softmax_dropout(x, rate, is_training=True, dropout_rng=key)
    assert bool((o1 == o2).all()), "same key must give the same mask"
    o3 = softmax_dropout(
        x, rate, is_training=True, dropout_rng=jax.random.PRNGKey(12)
    )
    assert not bool((o1 == o3).all()), "different keys must differ"

    # realized-mask oracle: if the recomputed backward mask matched the
    # forward's only approximately, these grads would diverge at kept/
    # dropped boundaries — they agree to float epsilon
    keep = o1 != 0
    w = jnp.asarray(np.random.RandomState(3).randn(4, 16, 128), jnp.float32)

    def oracle(x_):
        p = jax.nn.softmax(x_.astype(jnp.float32), -1)
        return jnp.where(keep, p / (1 - rate), 0.0)

    def kernel(x_):
        return softmax_dropout(x_, rate, is_training=True, dropout_rng=key)

    go = jax.grad(lambda x_: jnp.sum(oracle(x_) * w))(x)
    gk = jax.grad(lambda x_: jnp.sum(kernel(x_) * w))(x)
    assert float(jnp.abs(go - gk).max()) < 1e-6

    # rate + inverted-dropout scaling hold on the kernel path too
    zeros = float(jnp.mean(o1 == 0.0))
    assert rate - 0.1 < zeros < rate + 0.1
    assert abs(float(jnp.mean(jnp.sum(o1, axis=-1))) - 1.0) < 0.15


def test_pallas_training_dropout_with_bias_layouts(pallas_mode):
    """Training-mode dropout composes with the broadcast layouts: dropped
    positions are exact zeros, kept positions equal scaled probabilities."""
    for layout in ("mask_bias", "triangle_tile"):
        x, mask, bias = _layout(layout, 4)
        x = jnp.asarray(x, jnp.float32)
        mask = None if mask is None else jnp.asarray(mask, jnp.float32)
        bias = None if bias is None else jnp.asarray(bias, jnp.float32)
        key = jax.random.PRNGKey(5)
        out = softmax_dropout(
            x, 0.3, is_training=True, mask=mask, bias=bias, dropout_rng=key
        )
        probs = _sd_ref(x, 0.0, is_training=False, mask=mask, bias=bias)
        kept = out != 0
        assert float(
            jnp.abs(jnp.where(kept, out - probs / 0.7, 0.0)).max()
        ) < 1e-6, layout


def test_dispatch_fallback_and_gating(pallas_mode):
    """Geometry the kernel can't express falls back to the jnp oracle
    bit-for-bit; mode 'off'/'auto' (non-TPU) never touch Pallas."""
    # last dim not a 128-multiple -> jnp path
    x = jnp.asarray(np.random.RandomState(6).randn(4, 16, 96), jnp.float32)
    assert bool(
        (softmax_dropout(x, 0.0, is_training=False)
         == _sd_ref(x, 0.0, is_training=False)).all()
    )
    # rows not a multiple of 8 -> jnp path
    x2 = jnp.asarray(np.random.RandomState(7).randn(4, 9, 128), jnp.float32)
    assert bool(
        (softmax_dropout(x2, 0.0, is_training=False)
         == _sd_ref(x2, 0.0, is_training=False)).all()
    )
    # mode off: eligible geometry still takes the jnp path
    _sd_mod.set_softmax_dropout_mode("off")
    x3 = jnp.asarray(np.random.RandomState(8).randn(4, 16, 128), jnp.float32)
    assert bool(
        (softmax_dropout(x3, 0.0, is_training=False)
         == _sd_ref(x3, 0.0, is_training=False)).all()
    )
    _sd_mod.set_softmax_dropout_mode(None)
    if not on_tpu():
        # auto on a non-TPU backend = jnp (CPU numerics unchanged)
        assert _sd_mod._pallas_eligible(x3, None, None) is None
