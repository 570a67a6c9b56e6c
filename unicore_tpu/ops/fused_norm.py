"""Pallas fused LayerNorm / RMSNorm with custom VJP.

Counterpart of the reference's ``unicore_fused_layernorm`` /
``unicore_fused_layernorm_backward_gamma_beta`` / ``unicore_fused_rmsnorm``
CUDA extensions (/root/reference/csrc/{layernorm,rmsnorm}/): forward saves
(mean, rstd) and the backward splits into a per-row dx kernel and a separate
row-reduction kernel for dgamma/dbeta — the same kernel decomposition the
reference uses (its gamma/beta reduction is split out with its own launch,
layernorm_backward.cu:130-297).

XLA already fuses layer-norm chains well, so the modules default to the jnp
path; these kernels exist for parity benchmarking and as the fast path on
shapes where XLA's fusion is suboptimal.  Unlike the CUDA version there is
no supported-dim whitelist — any feature dim that fits VMEM works.

Statistics are fp32 regardless of input dtype (matching the CUDA
accumulator); outputs cast back to the input dtype.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._pallas import audit_case, pallas_call as _pallas_call


def _pick_rows(n, preferred=256):
    """n is always padded to a multiple of 8 by the wrappers."""
    b = min(preferred, n)
    while b > 8 and n % b != 0:
        b //= 2
    assert n % b == 0, (n, b)
    return b


def _pad_rows(x2):
    """Pad the row count to a multiple of 8 (zero rows; sliced off after).
    Zero dy rows contribute nothing to dw/db, and dx pad rows are dropped."""
    n = x2.shape[0]
    pad = (-n) % 8
    if pad:
        x2 = jnp.concatenate(
            [x2, jnp.zeros((pad, x2.shape[1]), x2.dtype)], axis=0
        )
    return x2, n


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _ln_fwd_kernel(x_ref, w_ref, b_ref, y_ref, mean_ref, rstd_ref, *, eps,
                   rms, scale_ref=None):
    # mean_ref/rstd_ref are None on the forward-only (inference) path
    x = x_ref[...].astype(jnp.float32)  # (BN, D)
    if scale_ref is not None:
        # quantized-input variant: x is int8, dequant is ONE fused
        # per-channel multiply on the fp32 rows (never a separate tensor)
        x = x * scale_ref[...].astype(jnp.float32)
    if rms:
        mean = jnp.zeros((x.shape[0], 1), jnp.float32)
        var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    else:
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = (x - mean) * rstd
    y = xhat * w_ref[...].astype(jnp.float32)
    if b_ref is not None:
        y = y + b_ref[...].astype(jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)
    if mean_ref is not None:
        mean_ref[...] = mean
        rstd_ref[...] = rstd


def _ln_fwd(x2, w, b, eps, rms, want_stats=True, scale=None, out_dtype=None):
    N, D = x2.shape
    BN = _pick_rows(N)
    grid = (N // BN,)
    in_specs = [
        pl.BlockSpec((BN, D), lambda i: (i, 0)),
        pl.BlockSpec((1, D), lambda i: (0, 0)),
    ]
    inputs = [x2, w.reshape(1, D)]
    if b is not None:
        in_specs.append(pl.BlockSpec((1, D), lambda i: (0, 0)))
        inputs.append(b.reshape(1, D))
    if scale is not None:
        in_specs.append(pl.BlockSpec((1, D), lambda i: (0, 0)))
        inputs.append(scale.reshape(1, D))

    def wrapped(*refs):
        n_out = 3 if want_stats else 1
        in_refs = refs[: len(inputs)]
        outs = refs[len(inputs): len(inputs) + n_out]
        x_ref, w_ref = in_refs[0], in_refs[1]
        i = 2
        b_ref = in_refs[i] if b is not None else None
        i += int(b is not None)
        s_ref = in_refs[i] if scale is not None else None
        y_ref = outs[0]
        m_ref = outs[1] if want_stats else None
        r_ref = outs[2] if want_stats else None
        _ln_fwd_kernel(x_ref, w_ref, b_ref, y_ref, m_ref, r_ref, eps=eps,
                       rms=rms, scale_ref=s_ref)

    out_specs = [pl.BlockSpec((BN, D), lambda i: (i, 0))]
    out_shape = [jax.ShapeDtypeStruct((N, D), out_dtype or x2.dtype)]
    if want_stats:
        out_specs += [
            pl.BlockSpec((BN, 1), lambda i: (i, 0)),
            pl.BlockSpec((BN, 1), lambda i: (i, 0)),
        ]
        out_shape += [
            jax.ShapeDtypeStruct((N, 1), jnp.float32),
            jax.ShapeDtypeStruct((N, 1), jnp.float32),
        ]

    outs = _pallas_call(
        wrapped,
        name="fused_norm_fwd",
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
    )(*inputs)
    if want_stats:
        return outs
    return outs[0], None, None


# ---------------------------------------------------------------------------
# backward: dx per row-block; dgamma/dbeta as a separate row reduction
# ---------------------------------------------------------------------------

def _ln_dx_kernel(x_ref, w_ref, m_ref, r_ref, dy_ref, dx_ref, *, rms):
    x = x_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    mean, rstd = m_ref[...], r_ref[...]
    xhat = (x - mean) * rstd
    wdy = dy * w
    if rms:
        c2 = jnp.mean(wdy * xhat, axis=-1, keepdims=True)
        dx = (wdy - xhat * c2) * rstd
    else:
        c1 = jnp.mean(wdy, axis=-1, keepdims=True)
        c2 = jnp.mean(wdy * xhat, axis=-1, keepdims=True)
        dx = (wdy - c1 - xhat * c2) * rstd
    dx_ref[...] = dx.astype(dx_ref.dtype)


def _ln_dwdb_kernel(x_ref, m_ref, r_ref, dy_ref, dw_ref, db_ref, *, has_bias):
    # the constant-index output blocks stay resident across the sequential
    # grid, so accumulation goes straight into the output refs (no scratch)
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        dw_ref[...] = jnp.zeros_like(dw_ref)
        if has_bias:
            db_ref[...] = jnp.zeros_like(db_ref)

    x = x_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    xhat = (x - m_ref[...]) * r_ref[...]
    dw_ref[...] += jnp.sum(dy * xhat, axis=0, keepdims=True)
    if has_bias:
        db_ref[...] += jnp.sum(dy, axis=0, keepdims=True)


def _ln_bwd(x2, w, b, eps, rms, mean, rstd, dy2):
    N, D = x2.shape
    BN = _pick_rows(N)
    grid = (N // BN,)

    dx = _pallas_call(
        functools.partial(_ln_dx_kernel, rms=rms),
        name="fused_norm_bwd_dx",
        grid=grid,
        in_specs=[
            pl.BlockSpec((BN, D), lambda i: (i, 0)),
            pl.BlockSpec((1, D), lambda i: (0, 0)),
            pl.BlockSpec((BN, 1), lambda i: (i, 0)),
            pl.BlockSpec((BN, 1), lambda i: (i, 0)),
            pl.BlockSpec((BN, D), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((BN, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, D), x2.dtype),
    )(x2, w.reshape(1, D), mean, rstd, dy2)

    has_bias = b is not None
    out_specs = [pl.BlockSpec((1, D), lambda i: (0, 0))]
    out_shape = [jax.ShapeDtypeStruct((1, D), jnp.float32)]
    if has_bias:
        out_specs.append(pl.BlockSpec((1, D), lambda i: (0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((1, D), jnp.float32))

    def dwdb_wrapped(*refs):
        x_ref, m_ref, r_ref, dy_ref = refs[:4]
        dw_ref = refs[4]
        db_ref = refs[5] if has_bias else None
        _ln_dwdb_kernel(x_ref, m_ref, r_ref, dy_ref, dw_ref, db_ref,
                        has_bias=has_bias)

    outs = _pallas_call(
        dwdb_wrapped,
        name="fused_norm_bwd_dwdb",
        grid=grid,
        in_specs=[
            pl.BlockSpec((BN, D), lambda i: (i, 0)),
            pl.BlockSpec((BN, 1), lambda i: (i, 0)),
            pl.BlockSpec((BN, 1), lambda i: (i, 0)),
            pl.BlockSpec((BN, D), lambda i: (i, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
    )(x2, mean, rstd, dy2)
    dw = outs[0].reshape(D)
    db = outs[1].reshape(D) if has_bias else None
    return dx, dw, db


# ---------------------------------------------------------------------------
# public ops
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _fused_norm(x, w, b, eps, rms):
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    if x2.shape[0] == 0:
        return x
    x2, n = _pad_rows(x2)
    # forward-only primal: skip the (N,1) stat outputs entirely
    y, _, _ = _ln_fwd(x2, w, b, eps, rms, want_stats=False)
    return y[:n].reshape(shape)


def _fused_norm_fwd(x, w, b, eps, rms):
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    if x2.shape[0] == 0:
        return x, (None, w, b, None, None, shape)
    x2p, n = _pad_rows(x2)
    y, mean, rstd = _ln_fwd(x2p, w, b, eps, rms)
    return y[:n].reshape(shape), (x2p, w, b, mean, rstd, shape)


def _fused_norm_bwd(eps, rms, residuals, dy):
    x2p, w, b, mean, rstd, shape = residuals
    if x2p is None:  # empty input
        return (
            dy,
            jnp.zeros_like(w),
            jnp.zeros_like(b) if b is not None else None,
        )
    dy2 = dy.reshape(-1, shape[-1])
    dy2p, n = _pad_rows(dy2)
    dx, dw, db = _ln_bwd(x2p, w, b, eps, rms, mean, rstd, dy2p)
    return dx[:n].reshape(shape), dw.astype(w.dtype), (
        db.astype(b.dtype) if b is not None else None
    )


_fused_norm.defvjp(_fused_norm_fwd, _fused_norm_bwd)


def fused_layer_norm(x, weight, bias, eps: float = 1e-5):
    """Fused LayerNorm over the last dim: y = (x - mu) * rstd * w + b."""
    return _fused_norm(x, weight, bias, eps, False)


def fused_rms_norm(x, weight, eps: float = 1e-6):
    """Fused RMSNorm over the last dim: y = x * rsqrt(mean(x^2)) * w."""
    return _fused_norm(x, weight, None, eps, True)


def quant_layer_norm_pallas(x_q, x_scale, weight, bias, eps: float = 1e-5,
                            out_dtype=jnp.float32):
    """Quantized-input LayerNorm: ``x_q`` int8, ``x_scale`` its dequant
    factor (scalar or per-channel ``(D,)``); the dequant multiply is
    fused into the row-statistics pass.  Forward-only (the serving
    plane's eval path; no VJP for a quantized input)."""
    shape = x_q.shape
    D = shape[-1]
    x2 = x_q.reshape(-1, D)
    if x2.shape[0] == 0:
        return jnp.zeros(shape, out_dtype)
    x2, n = _pad_rows(x2)
    scale = jnp.broadcast_to(
        jnp.asarray(x_scale, jnp.float32).reshape(-1), (D,)
    )
    y, _, _ = _ln_fwd(x2, weight, bias, eps, False, want_stats=False,
                      scale=scale, out_dtype=out_dtype)
    return y[:n].reshape(shape)


# ---------------------------------------------------------------------------
# representative audit shapes (unicore-tpu-lint --kernels; docs/lint.md)
# ---------------------------------------------------------------------------

@audit_case("fused-norm-fwd-bwd")
def _audit_fused_norm():
    x = jnp.zeros((4, 128, 1024), jnp.float32)
    w = jnp.ones((1024,), jnp.float32)
    b = jnp.zeros((1024,), jnp.float32)

    def loss(x, w, b):
        return jnp.sum(fused_layer_norm(x, w, b))

    jax.grad(loss, argnums=(0, 1, 2))(x, w, b)


@audit_case("quant-layer-norm")
def _audit_quant_layer_norm():
    x_q = jnp.zeros((256, 1024), jnp.int8)
    w = jnp.ones((1024,), jnp.float32)
    b = jnp.zeros((1024,), jnp.float32)
    quant_layer_norm_pallas(x_q, 0.05, w, b)
