"""Full-row Pallas TPU attention for moderate sequence lengths (L <= 1024).

Same capability surface as ops/flash_attention.py (additive bias with
in-kernel gradient, key-padding mask, in-kernel counter-based dropout) and
the same (B, H, L, D) layout, but specialized for the shapes the bundled
model families actually train at (BERT 512, Uni-Mol 256, Evoformer
rows/cols), where the whole key row fits in VMEM.  The specialization buys:

- **one-shot softmax** — the full score row is resident, so there is no
  online max/renormalization carry (fewer VPU passes than the online
  kernel) and no logsumexp residual is materialized to HBM;
- **G batch rows per grid invocation** — amortizes the grid/DMA overhead
  that dominates the online kernel at D=64 block shapes (the per-block
  matmul is far too small to feed the MXU);
- **grid (H, batch-groups) with batch innermost** — the (Lq, Lk) bias block
  is fetched once per head instead of once per (batch, head);
- **ONE fused backward pass** computing dq, dk, dv AND dbias with a single
  probability recompute and a single dropout-mask regeneration — the online
  kernel needs separate dq / dkv sweeps (3 regenerations) plus a third full
  recompute sweep for the bias gradient.

Dropout reuses the counter-based scheme of the online kernel: the keep mask
is regenerated from (seed, b, h) in both passes; nothing is stored
(reference softmax_dropout_kernel.cu:60-68 recomputes from Philox counters
the same way).

Falls back (at the module layer) to the online kernel for long sequences
and per-batch biases.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# MAX_ROW (full (L, L) fp32 score block must fit VMEM) and the VMEM
# budget now live in ops/_pallas.py — ONE copy shared with every other
# kernel's gate and with the --kernels auditor; the historical module
# names stay as aliases for callers/tests.
from ._pallas import (
    KernelGeometryError,
    MAX_ROW,
    VMEM_BUDGET as _VMEM_BUDGET,
    audit_case,
    pallas_call as _pallas_call,
)
from .flash_attention import NEG_INF, _keep_mask, _seed_block


def _pick_group(batch, preferred):
    """Largest divisor of ``batch`` that is <= preferred."""
    g = min(preferred, batch)
    while batch % g != 0:
        g -= 1
    return g


def _auto_group(B, Lq, Lk, D, itemsize, preferred, n_streams, bias_bufs):
    """Shrink the batch group until the kernel's VMEM footprint fits:
    ``n_streams`` double-buffered (G, L, D) blocks + ``bias_bufs``
    (Lq, Lk) fp32 bias buffers (fwd: the bias block; bwd: bias block +
    db scratch + db output block) + fp32 score/probability temporaries."""
    fixed = (bias_bufs + 4) * Lq * Lk * 4
    per_g = 2 * n_streams * max(Lq, Lk) * D * itemsize
    g = _pick_group(B, preferred)
    while g > 1 and fixed + g * per_g > _VMEM_BUDGET:
        g = _pick_group(B, g - 1)
    return g


def supported(Lq, Lk, D, bias_batch, has_bias=None) -> bool:
    if has_bias is None:
        has_bias = bias_batch is not None
    # the backward's FIXED VMEM footprint (bias block + db scratch/output +
    # fp32 score/probability temporaries) must fit even at group=1 —
    # otherwise _auto_group bottoms out and Mosaic fails at compile time
    # instead of this gate routing the shape to the online kernel
    fixed = ((3 if has_bias else 0) + 4) * Lq * Lk * 4
    per_g1 = 2 * 8 * max(Lq, Lk) * D * 4
    return (
        Lq % 128 == 0
        and Lk % 128 == 0
        and Lq <= MAX_ROW
        and Lk <= MAX_ROW
        and D <= 128
        and bias_batch in (None, 1)
        and fixed + per_g1 <= _VMEM_BUDGET
    )


def _softmax_row(s, kvm, has_mask):
    """One-shot fp32 softmax over the last dim; fully-masked rows -> zeros."""
    if has_mask:
        s = jnp.where(kvm, NEG_INF, s)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    if has_mask:
        p = jnp.where(kvm, 0.0, p)
    l = jnp.sum(p, axis=-1, keepdims=True)
    return p * jnp.where(l > 0.0, 1.0 / l, 0.0)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(
    seed_ref, q_ref, k_ref, v_ref, bias_ref, mask_ref, o_ref,
    *, sm_scale, dropout_rate, G, has_bias, has_mask,
):
    h, bg = pl.program_id(0), pl.program_id(1)
    if has_bias:
        bias = bias_ref[0, 0].astype(jnp.float32)  # (Lq, Lk)
    for g in range(G):
        q = q_ref[g, 0]  # (Lq, D)
        k = k_ref[g, 0]
        v = v_ref[g, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        s = s * sm_scale
        if has_bias:
            s = s + bias
        kvm = (mask_ref[g] != 0) if has_mask else None  # (1, Lk)
        p = _softmax_row(s, kvm, has_mask)
        if dropout_rate > 0.0:
            _seed_block(seed_ref, bg * G + g, h, jnp.int32(0), jnp.int32(0))
            keep = _keep_mask(p.shape, dropout_rate)
            p = jnp.where(keep, p * (1.0 / (1.0 - dropout_rate)), 0.0)
        o = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        o_ref[g, 0] = o.astype(o_ref.dtype)


def _io_specs(B, H, Lq, Lk, D, G, bias, kv_mask):
    """Shared q/k/v (+bias) (+mask) specs: blocks (G, 1, L, D) over
    (B, H, L, D), grid (H, n_batch_groups) with batch innermost."""
    qspec = pl.BlockSpec((G, 1, Lq, D), lambda h, bg, *_: (bg, h, 0, 0))
    kspec = pl.BlockSpec((G, 1, Lk, D), lambda h, bg, *_: (bg, h, 0, 0))
    specs = [qspec, kspec, kspec]
    if bias is not None:
        Hb = bias.shape[1]
        specs.append(
            pl.BlockSpec(
                (1, 1, Lq, Lk),
                (lambda h, bg, *_: (0, h, 0, 0)) if Hb > 1 else
                (lambda h, bg, *_: (0, 0, 0, 0)),
            )
        )
    if kv_mask is not None:
        specs.append(pl.BlockSpec((G, 1, Lk), lambda h, bg, *_: (bg, 0, 0)))
    return qspec, kspec, specs


def _fwd(q, k, v, bias, kv_mask, seed, sm_scale, dropout_rate, group):
    B, H, Lq, D = q.shape
    Lk = k.shape[2]
    has_bias = bias is not None
    has_mask = kv_mask is not None
    G = _auto_group(B, Lq, Lk, D, q.dtype.itemsize, group, 4, 1 if has_bias else 0)

    qspec, _, in_specs = _io_specs(B, H, Lq, Lk, D, G, bias, kv_mask)
    inputs = [q, k, v]
    if has_bias:
        inputs.append(bias)
    if has_mask:
        inputs.append(kv_mask)

    kernel = functools.partial(
        _fwd_kernel,
        sm_scale=sm_scale, dropout_rate=dropout_rate, G=G,
        has_bias=has_bias, has_mask=has_mask,
    )

    def wrapped(seed_ref, *refs):
        n = len(inputs)
        q_ref, k_ref, v_ref = refs[:3]
        i = 3
        bias_ref = refs[i] if has_bias else None
        i += int(has_bias)
        mask_ref = refs[i] if has_mask else None
        kernel(seed_ref, q_ref, k_ref, v_ref, bias_ref, mask_ref, refs[n])

    return _pallas_call(
        wrapped,
        name="fullrow_attn_fwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(H, B // G),
            in_specs=in_specs,
            out_specs=qspec,
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
    )(seed, *inputs)


# ---------------------------------------------------------------------------
# fused backward: dq, dk, dv, dbias in one pass
# ---------------------------------------------------------------------------

def _bwd_kernel(
    seed_ref, q_ref, k_ref, v_ref, bias_ref, mask_ref, do_ref,
    dq_ref, dk_ref, dv_ref, db_ref,
    db_s,
    *, sm_scale, dropout_rate, G, nbg, nh, has_bias, has_mask, bias_per_head,
):
    h, bg = pl.program_id(0), pl.program_id(1)

    if has_bias:
        first = (bg == 0) if bias_per_head else jnp.logical_and(h == 0, bg == 0)

        @pl.when(first)
        def _init():
            db_s[...] = jnp.zeros_like(db_s)

        bias = bias_ref[0, 0].astype(jnp.float32)

    for g in range(G):
        q = q_ref[g, 0]
        k = k_ref[g, 0]
        v = v_ref[g, 0]
        do = do_ref[g, 0]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        s = s * sm_scale
        if has_bias:
            s = s + bias
        kvm = (mask_ref[g] != 0) if has_mask else None
        p = _softmax_row(s, kvm, has_mask)

        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        if dropout_rate > 0.0:
            _seed_block(seed_ref, bg * G + g, h, jnp.int32(0), jnp.int32(0))
            keep = _keep_mask(p.shape, dropout_rate)
            inv = 1.0 / (1.0 - dropout_rate)
            pd = jnp.where(keep, p * inv, 0.0)
            dp_keep = jnp.where(keep, dp * inv, 0.0)
        else:
            pd = p
            dp_keep = dp

        # dv = dropout(p)^T @ do
        dv_ref[g, 0] = jax.lax.dot_general(
            pd.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(dv_ref.dtype)

        di = jnp.sum(pd * dp, axis=-1, keepdims=True)  # == rowsum(do * out)
        ds = p * (dp_keep - di)
        if has_mask:
            ds = jnp.where(kvm, 0.0, ds)

        dq_ref[g, 0] = (
            sm_scale
            * jax.lax.dot_general(
                ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        ).astype(dq_ref.dtype)
        dk_ref[g, 0] = (
            sm_scale
            * jax.lax.dot_general(
                ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        ).astype(dk_ref.dtype)
        if has_bias:
            db_s[...] += ds

    if has_bias:
        last = (
            (bg == nbg - 1) if bias_per_head
            else jnp.logical_and(h == nh - 1, bg == nbg - 1)
        )

        @pl.when(last)
        def _finish():
            db_ref[0, 0] = db_s[...].astype(db_ref.dtype)


def _bwd(q, k, v, bias, kv_mask, seed, sm_scale, dropout_rate, group, do):
    B, H, Lq, D = q.shape
    Lk = k.shape[2]
    has_bias = bias is not None
    has_mask = kv_mask is not None
    G = _auto_group(B, Lq, Lk, D, q.dtype.itemsize, group, 8, 3 if has_bias else 0)
    nbg = B // G
    Hb = bias.shape[1] if has_bias else 1
    bias_per_head = Hb > 1

    qspec, kspec, in_specs = _io_specs(B, H, Lq, Lk, D, G, bias, kv_mask)
    inputs = [q, k, v]
    if has_bias:
        inputs.append(bias)
    if has_mask:
        inputs.append(kv_mask)
    in_specs.append(qspec)  # do
    inputs.append(do)

    bias_spec = pl.BlockSpec(
        (1, 1, Lq, Lk),
        (lambda h, bg, *_: (0, h, 0, 0)) if bias_per_head else
        (lambda h, bg, *_: (0, 0, 0, 0)),
    )
    out_specs = [qspec, kspec, kspec]
    out_shapes = [
        jax.ShapeDtypeStruct(q.shape, q.dtype),
        jax.ShapeDtypeStruct(k.shape, k.dtype),
        jax.ShapeDtypeStruct(v.shape, v.dtype),
    ]
    if has_bias:
        out_specs.append(bias_spec)
        out_shapes.append(jax.ShapeDtypeStruct((1, Hb, Lq, Lk), jnp.float32))

    kernel = functools.partial(
        _bwd_kernel,
        sm_scale=sm_scale, dropout_rate=dropout_rate, G=G, nbg=nbg, nh=H,
        has_bias=has_bias, has_mask=has_mask, bias_per_head=bias_per_head,
    )

    n_outs = 3 + int(has_bias)

    def wrapped(seed_ref, *refs):
        n = len(inputs)
        q_ref, k_ref, v_ref = refs[:3]
        i = 3
        bias_ref = refs[i] if has_bias else None
        i += int(has_bias)
        mask_ref = refs[i] if has_mask else None
        i += int(has_mask)
        do_ref = refs[i]
        outs = refs[n:n + n_outs]
        db_ref = outs[3] if has_bias else None
        db_s = refs[n + n_outs] if has_bias else None
        kernel(
            seed_ref, q_ref, k_ref, v_ref, bias_ref, mask_ref, do_ref,
            outs[0], outs[1], outs[2], db_ref, db_s,
        )

    scratch = [pltpu.VMEM((Lq, Lk), jnp.float32)] if has_bias else []
    res = _pallas_call(
        wrapped,
        name="fullrow_attn_bwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(H, nbg),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=scratch,
        ),
        out_shape=out_shapes,
    )(seed, *inputs)
    dq, dk, dv = res[:3]
    dbias = res[3].astype(bias.dtype) if has_bias else None
    return dq, dk, dv, dbias


# ---------------------------------------------------------------------------
# public op
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _fullrow(q, k, v, bias, kv_mask, seed, sm_scale, dropout_rate, group):
    return _fwd(q, k, v, bias, kv_mask, seed, sm_scale, dropout_rate, group)


def _fullrow_fwd(q, k, v, bias, kv_mask, seed, sm_scale, dropout_rate, group):
    out = _fwd(q, k, v, bias, kv_mask, seed, sm_scale, dropout_rate, group)
    return out, (q, k, v, bias, kv_mask, seed)


def _fullrow_bwd(sm_scale, dropout_rate, group, residuals, do):
    q, k, v, bias, kv_mask, seed = residuals
    dq, dk, dv, dbias = _bwd(
        q, k, v, bias, kv_mask, seed, sm_scale, dropout_rate,
        max(1, group // 2), do,
    )
    return dq, dk, dv, dbias, None, None


_fullrow.defvjp(_fullrow_fwd, _fullrow_bwd)


def fullrow_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    bias: Optional[jnp.ndarray] = None,
    kv_padding_mask: Optional[jnp.ndarray] = None,
    dropout_rate: float = 0.0,
    dropout_seed: int = 0,
    sm_scale: float = 1.0,
    group: int = 8,
) -> jnp.ndarray:
    """softmax(q k^T * scale + bias, mask) v with q, k, v in (B, H, L, D).

    Requirements (checked by ``supported``; callers fall back to
    ops/flash_attention.py otherwise): Lq, Lk multiples of 128 and <= 1024,
    D <= 128, bias batch dim 1 (broadcast over batch).

    bias: (1|omitted, 1|H, Lq, Lk) additive; gradient (fp32-accumulated)
    reduced fully in-kernel.  kv_padding_mask: (B, Lk) nonzero = masked out.
    """
    bias_b = None
    if bias is not None:
        if bias.ndim == 3:
            bias = bias[None]
        if bias.ndim != 4 or bias.shape[0] != 1:
            raise KernelGeometryError(
                f"fullrow_attention bias must be (1, 1|H, Lq, Lk), "
                f"got shape {bias.shape}"
            )
        bias_b = bias.shape[0]
    B, H, Lq, D = q.shape
    Lk = k.shape[2]
    if not supported(Lq, Lk, D, bias_b):
        raise KernelGeometryError(
            f"fullrow_attention refused q={q.shape} k={k.shape}: needs "
            f"Lq/Lk 128-multiples <= {MAX_ROW}, D <= 128, bias batch 1, "
            f"and a group=1 footprint inside the VMEM budget — callers "
            f"fall back to flash_attention for these shapes"
        )
    if kv_padding_mask is not None:
        kv_padding_mask = kv_padding_mask.astype(jnp.int32)[:, None, :]
    seed = jnp.reshape(jnp.asarray(dropout_seed, dtype=jnp.int32), (1,))
    return _fullrow(
        q, k, v, bias, kv_padding_mask,
        # lint: host-sync-in-jit; dropout_rate is a static hyperparameter
        seed, sm_scale, float(dropout_rate), group,
    )


# ---------------------------------------------------------------------------
# representative audit shapes (unicore-tpu-lint --kernels; docs/lint.md)
# ---------------------------------------------------------------------------

@audit_case("fullrow-attention-fwd-bwd")
def _audit_fullrow():
    """Ulysses-leg geometry: full L=512 rows resident, shared bias,
    dropout on; B=8 so ``_auto_group`` lands G=4 forward / G=2 backward
    and the batch-group grid axis is real (size > 1) both ways."""
    q = jnp.zeros((8, 2, 512, 64), jnp.float32)
    kv = jnp.zeros((8, 2, 512, 64), jnp.float32)
    bias = jnp.zeros((1, 2, 512, 512), jnp.float32)
    mask = jnp.zeros((8, 512), jnp.int32)

    def loss(q, kv, bias):
        out = fullrow_attention(q, kv, kv, bias=bias, kv_padding_mask=mask,
                                dropout_rate=0.1, dropout_seed=11)
        return jnp.sum(out)

    jax.grad(loss, argnums=(0, 1, 2))(q, kv, bias)
