"""Pallas TPU fused softmax(+mask)(+bias)+dropout with hidden in-kernel RNG.

Device-side counterpart of the reference's ``unicore_fused_softmax_dropout``
CUDA extension (/root/reference/csrc/softmax_dropout/) for the cases that
MATERIALIZE probabilities — the ``return_attn`` consumers (Uni-Fold triangle
attention) and every module that reads the attention matrix — where the
flash-attention kernel does not apply.  The jnp composition in
``ops/softmax_dropout.py`` stays the oracle and the fallback; this kernel's
win over it is training mode: the dropout keep-mask is generated INSIDE the
kernel from a counter-based PRNG seeded per (row-group, row-block) —
overlapped with the row compute on the VPU, never written to HBM, and
REGENERATED (not stored) in the custom-VJP backward, mirroring the
reference's "recompute from Philox counters" design
(softmax_dropout_kernel.cu:60-68) and the separate-RNG-pass elimination of
"Reducing the Cost of Dropout in Flash-Attention" (PAPERS.md,
arXiv 2410.07531).  The jnp path pays one extra HBM round-trip for the
bernoulli mask; this path pays none.

Op surface (same contract as the jnp path, ops/softmax_dropout.py):

- input ``(..., M, L)``; softmax over the last dim in fp32 regardless of
  input dtype, output cast back;
- optional additive ``mask``/``bias`` under the reference's broadcast
  semantics (interface.cpp:37-48): either elementwise-broadcastable after
  left-padding with 1s (any mix of 1-vs-full leading dims — the Evoformer
  grouped layout), or the Uni-Fold triangle-attention TILE layout (leading
  batch ``b`` with ``rows % b == 0`` repeating whole ``(M, L)`` slabs,
  input row ``r`` reading extra row ``r % b``);
- gradients for input AND mask/bias (broadcast dims reduced in fp32);
- the forward output IS the (dropped) probability matrix, so ``return_attn``
  consumers need nothing extra materialized.

Seeding: the int32 seed is mixed with (row-group, row-block) program ids per
block — the PRNG stream VARIES across grid steps (the constant-seed bug
class the extended ``prng-key-reuse`` lint rule now flags).  Forward and
backward mix identically, so the recomputed mask is bit-identical to the
applied one (the determinism contract tests/test_softmax_dropout.py proves).

On non-TPU backends the kernels run under Pallas interpret mode with a
counter-based integer-hash PRNG (murmur3 finalizer) instead of the TPU
hardware generator — same determinism contract, different bits; real-TPU
runs use ``pltpu.prng_seed``/``prng_random_bits``.
"""

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._pallas import audit_case, interpret_enabled, pallas_call as _pallas_call

# VMEM budget per (rows x L) fp32 block buffer (~256 KiB): the kernel holds
# x, extras, probs and the random bits concurrently, so keep each modest.
_MAX_BLOCK_ELEMS = 64 * 1024
#: full-row softmax: the whole last dim must sit in one block
_MAX_L = 8192


def _pick_rows(m: int, limit: int) -> int:
    b = max(8, min(limit, m))
    while b > 8 and m % b != 0:
        b //= 2
    return b if m % b == 0 else 1


# ---------------------------------------------------------------------------
# extra (mask/bias) layout planning — all static python, done at trace time
# ---------------------------------------------------------------------------

def plan_extra(shape: Tuple[int, ...], ishape: Tuple[int, ...]):
    """Static layout plan for one mask/bias operand against ``ishape``.

    Returns ``('bcast', padded)`` (elementwise-broadcastable after
    left-padding — every dim 1 or full), ``('tile', rows)`` (the reference's
    triangle layout: trailing dims equal, flattened leading rows divide the
    input's), or ``None`` when the kernel can't express the layout (the
    dispatch then falls back to the jnp path)."""
    if len(shape) > len(ishape):
        return None
    padded = (1,) * (len(ishape) - len(shape)) + tuple(shape)
    if all(p == d or p == 1 for p, d in zip(padded, ishape)):
        return ("bcast", padded)
    # tile layout: whole trailing (M, L) slabs repeated over leading rows
    if padded[-2:] != tuple(ishape[-2:]):
        return None
    rows_in = 1
    for d in ishape[:-2]:
        rows_in *= d
    rows_x = 1
    for d in padded[:-2]:
        rows_x *= d
    if rows_x == 0 or rows_in % rows_x != 0:
        return None
    return ("tile", rows_x)


def _extra_3d(x: jnp.ndarray, plan, ishape) -> jnp.ndarray:
    """Reshape an extra to the kernel's 3-D (G, Mx, Lx) layout."""
    kind, info = plan
    if kind == "tile":
        return x.reshape((info,) + tuple(ishape[-2:]))
    padded = info
    g = 1
    for d in padded[:-2]:
        g *= d
    return x.reshape((g, padded[-2], padded[-1]))


def _extra_row_index(plan, ishape):
    """Index map (traced int arithmetic on the row program id) from the
    flattened input row ``r`` to the extra's leading (group) row.

    ``ishape`` is the ORIGINAL input shape: the bcast decomposition runs
    over its true leading dims, so mixed per-dim broadcast (the Evoformer
    ``(G, 1, H, ...)`` vs ``(G, N, H, ...)`` layout) maps exactly."""
    kind, info = plan
    if kind == "tile":
        rx = info
        return lambda r: r % rx
    padded = info
    lead_d = tuple(ishape[:-2])
    lead_e = tuple(padded[:-2])

    def idx(r):
        g = 0
        rem = r
        suffix = 1
        for d in lead_d:
            suffix *= d
        for d, e in zip(lead_d, lead_e):
            suffix = suffix // d
            c = rem // suffix
            rem = rem % suffix
            # e == d -> c, e == 1 -> 0; mixed per-dim broadcast supported
            g = g * e + (c % e)
        return g

    return idx


def _grad_reduce(ds3, plan, extra3_shape, ishape, dtype):
    """Reduce the fp32 cotangent over an extra's broadcast dims, producing
    the NORMALIZED 3-D cotangent (the wrapper's reshape VJP restores the
    caller's original shape)."""
    kind, info = plan
    if kind == "tile":
        rx = info
        t = ds3.shape[0] // rx
        red = ds3.reshape((t, rx) + ds3.shape[1:]).sum(axis=0)
        return red.astype(dtype)
    padded = info
    full = ds3.reshape(ishape)
    axes = tuple(i for i, (p, d) in enumerate(zip(padded, ishape)) if p == 1 and d != 1)
    red = full.sum(axis=axes, keepdims=True) if axes else full
    return red.reshape(extra3_shape).astype(dtype)


# ---------------------------------------------------------------------------
# in-kernel PRNG: hardware generator on TPU, integer hash under interpret
# ---------------------------------------------------------------------------

def _mix_seed(seed_ref, r, im):
    """One int32 stream id per (row-group, row-block) — varies across every
    grid step, identically derived in forward and backward."""
    mix = seed_ref[0]
    for coord in (r, im):
        mix = mix * jnp.int32(1000003) + coord.astype(jnp.int32)
    return mix


def _keep_mask(seed_ref, r, im, shape, rate, use_hw):
    """Counter-based keep mask, threshold compare on raw uint32 bits."""
    threshold = jnp.uint32(min(int(rate * (2 ** 32)), 2 ** 32 - 1))
    mix = _mix_seed(seed_ref, r, im)
    if use_hw:
        pltpu.prng_seed(mix)
        bits = pltpu.bitcast(pltpu.prng_random_bits(shape), jnp.uint32)
    else:
        # interpret-mode fallback: murmur3-finalized counter hash — the
        # TPU-only generator has no CPU lowering, and a deterministic
        # stream is required so the backward regenerates the same mask
        rows = jax.lax.broadcasted_iota(jnp.uint32, shape, 0)
        cols = jax.lax.broadcasted_iota(jnp.uint32, shape, 1)
        h = (
            mix.astype(jnp.uint32) * jnp.uint32(0x9E3779B9)
            ^ (rows + jnp.uint32(1)) * jnp.uint32(0x85EBCA6B)
            ^ (cols + jnp.uint32(1)) * jnp.uint32(0xC2B2AE35)
        )
        h = h ^ (h >> 16)
        h = h * jnp.uint32(0x85EBCA6B)
        h = h ^ (h >> 13)
        h = h * jnp.uint32(0xC2B2AE35)
        bits = h ^ (h >> 16)
    return bits >= threshold


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _row_probs(x_ref, mask_ref, bias_ref, scale_ref=None):
    """fp32 softmax over the last dim, shared by fwd and bwd so the
    recomputed probabilities are bit-identical to the applied ones.

    ``scale_ref`` (quantized-input variant): the input block is an int8
    or int32 quantized tensor; dequantization is ONE fused multiply on
    the fp32 row — never a separately materialized fp32 tensor."""
    x = x_ref[0].astype(jnp.float32)
    if scale_ref is not None:
        x = x * scale_ref[0]
    if mask_ref is not None:
        x = x + mask_ref[0].astype(jnp.float32)
    if bias_ref is not None:
        x = x + bias_ref[0].astype(jnp.float32)
    m = jnp.max(x, axis=-1, keepdims=True)
    e = jnp.exp(x - m)
    return e / jnp.sum(e, axis=-1, keepdims=True)


def _fwd_kernel(seed_ref, x_ref, mask_ref, bias_ref, o_ref, *, rate, use_hw,
                scale_ref=None):
    p = _row_probs(x_ref, mask_ref, bias_ref, scale_ref)
    y = p.astype(o_ref.dtype)
    if rate > 0.0:
        r, im = pl.program_id(0), pl.program_id(1)
        keep = _keep_mask(seed_ref, r, im, p.shape, rate, use_hw)
        y = jnp.where(keep, y / (1.0 - rate), 0.0).astype(o_ref.dtype)
    o_ref[0] = y


def _bwd_kernel(seed_ref, x_ref, mask_ref, bias_ref, do_ref, ds_ref, *,
                rate, use_hw, scale_ref=None):
    p = _row_probs(x_ref, mask_ref, bias_ref, scale_ref)
    dy = do_ref[0].astype(jnp.float32)
    if rate > 0.0:
        r, im = pl.program_id(0), pl.program_id(1)
        # identical (seed, r, im) mixing and block shape as the forward:
        # the mask is RECOMPUTED, never stored
        keep = _keep_mask(seed_ref, r, im, p.shape, rate, use_hw)
        dp = jnp.where(keep, dy * (1.0 / (1.0 - rate)), 0.0)
    else:
        dp = dy
    dot = jnp.sum(dp * p, axis=-1, keepdims=True)
    ds_ref[0] = p * (dp - dot)


# ---------------------------------------------------------------------------
# pallas_call plumbing shared by fwd and bwd
# ---------------------------------------------------------------------------

def _run(kernel, ishape, x3, plans, extras, seed, out_dtype, rate, use_hw,
         extra_in=None, scale3=None):
    M, L = ishape[-2], ishape[-1]
    R = x3.shape[0]
    BM = _pick_rows(M, max(8, _MAX_BLOCK_ELEMS // max(L, 1)))
    nm = M // BM

    in_specs = [pl.BlockSpec((1, BM, L), lambda r, im, *_: (r, im, 0))]
    inputs = [x3]
    for plan, x in zip(plans, extras):
        if x is None:
            continue
        Mx, Lx = x.shape[-2], x.shape[-1]
        BMx = BM if Mx == M else 1
        gi = _extra_row_index(plan, ishape)
        in_specs.append(
            pl.BlockSpec(
                (1, BMx, Lx),
                lambda r, im, *_, gi=gi, Mx=Mx: (gi(r), im if Mx > 1 else 0, 0),
            )
        )
        inputs.append(x)
    if scale3 is not None:  # quantized-input dequant scale, one scalar
        in_specs.append(pl.BlockSpec((1, 1, 1), lambda r, im, *_: (0, 0, 0)))
        inputs.append(scale3)
    if extra_in is not None:  # the backward's incoming cotangent
        in_specs.append(pl.BlockSpec((1, BM, L), lambda r, im, *_: (r, im, 0)))
        inputs.append(extra_in)

    has_mask = extras[0] is not None
    has_bias = extras[1] is not None
    has_scale = scale3 is not None

    def wrapped(seed_ref, *refs):
        x_ref = refs[0]
        i = 1
        mask_ref = refs[i] if has_mask else None
        i += int(has_mask)
        bias_ref = refs[i] if has_bias else None
        i += int(has_bias)
        scale_ref = refs[i] if has_scale else None
        i += int(has_scale)
        if extra_in is not None:
            do_ref = refs[i]
            i += 1
            kernel(seed_ref, x_ref, mask_ref, bias_ref, do_ref, refs[i],
                   rate=rate, use_hw=use_hw, scale_ref=scale_ref)
        else:
            kernel(seed_ref, x_ref, mask_ref, bias_ref, refs[i],
                   rate=rate, use_hw=use_hw, scale_ref=scale_ref)

    out = _pallas_call(
        wrapped,
        # one site, two programs: the backward brings a cotangent
        name="softmax_dropout_bwd" if extra_in is not None
        else "softmax_dropout_fwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(R, nm),
            in_specs=in_specs,
            out_specs=[pl.BlockSpec((1, BM, L), lambda r, im, *_: (r, im, 0))],
        ),
        out_shape=[jax.ShapeDtypeStruct((R, M, L), out_dtype)],
    )(seed, *inputs)[0]
    return out


# ---------------------------------------------------------------------------
# public op with custom VJP
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _sd(x3, mask3, bias3, seed, rate, cfg):
    out, _ = _sd_fwd(x3, mask3, bias3, seed, rate, cfg)
    return out


def _sd_fwd(x3, mask3, bias3, seed, rate, cfg):
    plans, ishape, use_hw = cfg
    out = _run(_fwd_kernel, ishape, x3, plans, (mask3, bias3), seed,
               x3.dtype, rate, use_hw)
    return out, (x3, mask3, bias3, seed)


def _sd_bwd(rate, cfg, residuals, do):
    x3, mask3, bias3, seed = residuals
    plans, ishape, use_hw = cfg
    # one fp32 cotangent pass: dx is its cast, mask/bias grads its broadcast
    # reductions — matching the jnp oracle's fp32 accumulation
    ds3 = _run(_bwd_kernel, ishape, x3, plans, (mask3, bias3), seed,
               jnp.float32, rate, use_hw, extra_in=do)
    dx = ds3.astype(x3.dtype)
    dmask = dbias = None
    if mask3 is not None:
        dmask = _grad_reduce(ds3, plans[0], mask3.shape, ishape, mask3.dtype)
    if bias3 is not None:
        dbias = _grad_reduce(ds3, plans[1], bias3.shape, ishape, bias3.dtype)
    return dx, dmask, dbias, None


_sd.defvjp(_sd_fwd, _sd_bwd)


# ---------------------------------------------------------------------------
# dispatch-facing API
# ---------------------------------------------------------------------------

_SUPPORTED_DTYPES = (jnp.float32, jnp.bfloat16)


def pallas_plan(input_shape, input_dtype, mask, bias) -> Optional[tuple]:
    """Static feasibility check.  Returns the (mask_plan, bias_plan) pair
    when the kernel can run this call, else None (jnp fallback)."""
    if len(input_shape) < 2:
        return None
    M, L = input_shape[-2], input_shape[-1]
    R = 1
    for d in input_shape[:-2]:
        R *= d
    if R == 0 or M == 0 or L == 0:
        return None
    if input_dtype not in _SUPPORTED_DTYPES:
        return None
    if L > _MAX_L or L % 128 != 0 or M % 8 != 0:
        return None
    plans = []
    for x in (mask, bias):
        if x is None:
            plans.append(None)
            continue
        p = plan_extra(tuple(x.shape), tuple(input_shape))
        if p is None:
            return None
        plans.append(p)
    return tuple(plans)


def _dispatch_prep(name, input, plan_dtype, mask, bias, plans,
                   dropout_prob, is_training, seed):
    """The shared dispatch body of the fp and quantized entry points:
    plan resolution, row-geometry flattening, extras prep, seed shaping —
    ONE copy so a future plan/layout change cannot skew the quantized
    path's geometry handling from the fp path's."""
    ishape = tuple(input.shape)
    if plans is None:
        plans = pallas_plan(ishape, plan_dtype, mask, bias)
    if plans is None:
        raise ValueError(
            f"{name} cannot express input {ishape} {plan_dtype} with mask "
            f"{None if mask is None else mask.shape} / bias "
            f"{None if bias is None else bias.shape}; use the jnp path"
        )
    M, L = ishape[-2], ishape[-1]
    R = 1
    for d in ishape[:-2]:
        R *= d
    # lint: host-sync-in-jit; dropout_prob is a static hyperparameter
    rate = float(dropout_prob) if is_training else 0.0
    use_hw = not interpret_enabled()
    x3 = input.reshape(R, M, L)
    mask3 = _extra_3d(mask, plans[0], ishape) if mask is not None else None
    bias3 = _extra_3d(bias, plans[1], ishape) if bias is not None else None
    seed = jnp.reshape(jnp.asarray(seed, dtype=jnp.int32), (1,))
    return plans, ishape, x3, mask3, bias3, seed, rate, use_hw


def softmax_dropout_pallas(
    input: jnp.ndarray,
    dropout_prob: float,
    is_training: bool = True,
    mask: Optional[jnp.ndarray] = None,
    bias: Optional[jnp.ndarray] = None,
    seed=0,
    plans: Optional[tuple] = None,
) -> jnp.ndarray:
    """Fused-kernel softmax(+mask)(+bias)(+dropout).

    Same semantics as the jnp ``softmax_dropout`` oracle; ``seed`` is an
    int32 scalar (the dispatch derives it from ``dropout_rng``).  Training
    dropout bits come from a DIFFERENT generator than the oracle's
    ``jax.random.bernoulli``, so masks are not comparable across paths —
    rate, scaling, determinism, and gradients are (tests prove all four).
    """
    plans, ishape, x3, mask3, bias3, seed, rate, use_hw = _dispatch_prep(
        "softmax_dropout_pallas", input, input.dtype, mask, bias, plans,
        dropout_prob, is_training, seed,
    )
    cfg = (plans, ishape, use_hw)
    out = _sd(x3, mask3, bias3, seed, rate, cfg)
    return out.reshape(ishape)


def quant_softmax_dropout_pallas(
    input_q: jnp.ndarray,
    x_scale,
    dropout_prob: float,
    is_training: bool = False,
    mask: Optional[jnp.ndarray] = None,
    bias: Optional[jnp.ndarray] = None,
    seed=0,
    plans: Optional[tuple] = None,
    out_dtype=jnp.float32,
) -> jnp.ndarray:
    """Quantized-input variant: ``input_q`` is an int8 (or int32
    accumulator) tensor and ``x_scale`` its scalar dequant factor; the
    dequant multiply is fused into the row softmax pass — the fp32 logits
    never exist as a tensor.  Forward-only (the serving plane's eval
    path; no VJP is defined for a quantized input)."""
    plans, ishape, x3, mask3, bias3, seed, rate, use_hw = _dispatch_prep(
        "quant_softmax_dropout_pallas", input_q, jnp.float32, mask, bias,
        plans, dropout_prob, is_training, seed,
    )
    scale3 = jnp.reshape(jnp.asarray(x_scale, jnp.float32), (1, 1, 1))
    out = _run(_fwd_kernel, ishape, x3, plans, (mask3, bias3), seed,
               out_dtype, rate, use_hw, scale3=scale3)
    return out.reshape(ishape)


# ---------------------------------------------------------------------------
# representative audit shapes (unicore-tpu-lint --kernels; docs/lint.md)
# ---------------------------------------------------------------------------

@audit_case("softmax-dropout-fwd-bwd")
def _audit_softmax_dropout():
    x = jnp.zeros((2, 4, 256, 512), jnp.float32)
    bias = jnp.zeros((1, 4, 256, 512), jnp.float32)
    mask = jnp.zeros((2, 1, 1, 512), jnp.float32)

    def loss(x, bias):
        out = softmax_dropout_pallas(x, 0.1, is_training=True, mask=mask,
                                     bias=bias, seed=11)
        return jnp.sum(out)

    jax.grad(loss, argnums=(0, 1))(x, bias)


@audit_case("quant-softmax-dropout")
def _audit_quant_softmax_dropout():
    x_q = jnp.zeros((2, 4, 256, 512), jnp.int8)
    mask = jnp.zeros((2, 1, 1, 512), jnp.float32)
    quant_softmax_dropout_pallas(x_q, 0.04, 0.0, mask=mask)
