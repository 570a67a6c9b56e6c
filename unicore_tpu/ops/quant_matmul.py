"""Quantized dense matmul: int8 x int8 -> int32 with the dequantization
FUSED into the epilogue (per-channel scale + bias + activation), never
materialized as an fp32 intermediate.

This is the serving plane's W8A8 kernel (docs/serving.md, "Quantized
inference"): weights are pre-quantized per OUTPUT channel at calibration
time (``unicore_tpu/quant/calibrate.py``), activations per tensor at the
call site, and the int32 accumulator is rescaled exactly once, inside the
kernel's epilogue — per the operation-fusion argument of arXiv 2502.17728
(PAPERS.md): a separate dequant pass would write the full fp32 activation
back to HBM only for the very next op to read it again.  The fusion audit
(``analysis/fusion_audit.dequant_chains``) regression-checks that the
compiled quantized program carries no unfused s8/s32 -> fp32 convert
chains, device-free.

Two implementations behind the ``ops/`` mode-gate pattern
(``softmax_dropout.py`` is the template):

- the **jnp composition** (oracle + universal fallback): an int32
  ``dot_general`` followed by scale/bias/activation — XLA fuses the
  epilogue into the matmul's consumer chain (the audit proves it);
- the **Pallas kernel**: blocked int8 matmul on the MXU
  (``preferred_element_type=jnp.int32``) with the epilogue applied to the
  resident accumulator block before it ever leaves VMEM.

Mode ``auto`` (default) uses Pallas on a real TPU backend when the
geometry allows (K and N 128-multiples, rows a multiple of 8); ``on``
forces Pallas wherever the geometry allows (the parity tests run it under
interpret mode on CPU); ``off`` is always jnp.  Set via
:func:`set_quant_matmul_mode` or ``UNICORE_TPU_PALLAS_QUANT_MATMUL``.

fp8: on backends whose XLA supports float8 dots the same entry point
accepts ``float8_e4m3fn`` operands through the jnp path (values carry the
fp8 quantization, the dot accumulates fp32); the Pallas kernel is
int8-only.  Inference-only: none of these ops define a VJP.
"""

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._pallas import (
    KernelGeometryError,
    LANE,
    ModeGate,
    VMEM_BUDGET,
    audit_case,
    interpret_enabled,
    pallas_call as _pallas_call,
    pick_block_pow2,
    vmem_footprint,
)
from unicore_tpu.platform_utils import on_tpu

_gate = ModeGate("quant_matmul", "UNICORE_TPU_PALLAS_QUANT_MATMUL")

#: int8 symmetric range (the -128 column is excluded so dequant is exact
#: under negation, matching the reference PTQ recipes)
INT8_QMAX = 127.0

#: VMEM budget: x block (BM, K) int8 + w block (K, BN) int8 + acc fp32
_MAX_BLOCK_K = 4096
_MAX_BLOCK_N = 1024
_MAX_BLOCK_M = 512


def set_quant_matmul_mode(mode: Optional[str]):
    """Select the dispatch mode (``auto``/``on``/``off``; None = auto)."""
    _gate.set(mode)


_resolved_mode = _gate.resolved


def _apply_activation(y, activation: str):
    """Epilogue activation — the SAME function table as
    ``utils.get_activation_fn`` so the quantized epilogue and the f32
    module path compute the identical nonlinearity."""
    if not activation or activation == "linear":
        return y
    from unicore_tpu.utils import get_activation_fn

    return get_activation_fn(activation)(y)


def quantize_to_dtype(x, scale, qmax: float, dtype):
    """Symmetric quantization against a static scale; values outside the
    calibrated range saturate (the standard PTQ contract).  THE one
    quantize step — ``QuantDense`` and the kernels share it so the
    call-site quantization can never drift from the oracle's."""
    v = jnp.clip(x.astype(jnp.float32) / scale, -qmax, qmax)
    if dtype == jnp.int8:
        v = jnp.round(v)
    return v.astype(dtype)


def quantize_to_int8(x, scale):
    """Symmetric int8 quantization: ``round(x / scale)`` clipped to
    [-127, 127].  ``scale`` is the dequant step (absmax / 127) — scalar
    for activations, per-output-channel vector for weights."""
    return quantize_to_dtype(x, scale, INT8_QMAX, jnp.int8)


def dynamic_act_scale(x):
    """Per-tensor dynamic activation scale (absmax / 127), floored so an
    all-zero tensor quantizes to zeros instead of NaN."""
    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)))
    return jnp.maximum(absmax / INT8_QMAX, jnp.float32(1e-8))


# ---------------------------------------------------------------------------
# jnp composition — the oracle and the universal fallback
# ---------------------------------------------------------------------------

def quant_matmul_reference(x_q, w_q, scale, bias=None, activation: str = "",
                           out_dtype=jnp.float32):
    """``(x_q @ w_q) * scale + bias`` with the int32 accumulator rescaled
    per output channel.  ``scale`` is the COMBINED dequant factor
    (act_scale * w_scale[col]), shape ``(N,)`` or scalar.

    int8 operands accumulate exactly in int32; float8 operands (the fp8
    serve mode) are upcast in-register and accumulate fp32 — XLA 0.4.x
    has no portable f8 dot on every backend, so the fp8 path carries the
    QUANTIZATION (values are fp8-rounded) with fp32 compute."""
    if x_q.dtype == jnp.int8:
        acc_t = jnp.int32
    else:
        acc_t = jnp.float32
        x_q = x_q.astype(jnp.float32)
        w_q = w_q.astype(jnp.float32)
    acc = jax.lax.dot_general(
        x_q, w_q,
        dimension_numbers=(((x_q.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=acc_t,
    )
    y = acc.astype(jnp.float32) * scale
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    y = _apply_activation(y, activation)
    return y.astype(out_dtype)


# ---------------------------------------------------------------------------
# Pallas kernel: blocked int8 matmul, epilogue on the resident acc block
# ---------------------------------------------------------------------------

#: Abramowitz & Stegun 7.1.26: erf(x) = 1 - (a1 t + ... + a5 t^5) e^{-x^2},
#: t = 1 / (1 + p x), x >= 0; |error| <= 1.5e-7 over the whole real line.
_AS_P = 0.3275911
_AS_A = (0.254829592, -0.284496736, 1.421413741, -1.453152027, 1.061405429)

#: max |gelu_kernel - exact gelu| in fp32, measured over [-10, 10] at 1e-3
#: spacing by tests/test_quant.py (the A&S bound times |x|/2 plus fp32
#: rounding of the polynomial); the parity tolerance and docs/serving.md's
#: int8 error-bound contract quote it.
GELU_EPILOGUE_MAX_ABS_ERR = 1e-6


def _erf_kernel(x):
    """erf from primitives Mosaic lowers (exp / mul / add / div / select):
    the TPU Pallas lowering implements neither ``erf`` nor ``erfc``, so the
    exact-GELU epilogue cannot call ``jax.nn.gelu`` in-kernel."""
    ax = jnp.abs(x)
    t = 1.0 / (1.0 + _AS_P * ax)
    poly = _AS_A[4]
    for a in _AS_A[3::-1]:
        poly = poly * t + a
    e = 1.0 - poly * t * jnp.exp(-ax * ax)
    return jnp.where(x < 0, -e, e)


def _apply_activation_kernel(y, activation: str):
    """The in-kernel epilogue table: identical to :func:`_apply_activation`
    except exact GELU, which goes through :func:`_erf_kernel` (the jnp
    oracle keeps ``lax.erf``; they agree to
    :data:`GELU_EPILOGUE_MAX_ABS_ERR`)."""
    if activation == "gelu":
        return 0.5 * y * (1.0 + _erf_kernel(y * 0.7071067811865476))
    return _apply_activation(y, activation)


def _qmm_kernel(x_ref, w_ref, s_ref, b_ref, o_ref, *, activation, n_k):
    """One (BM, BN) output block: accumulate int32 over the K grid axis,
    dequantize + bias + activation on the LAST k step only — the epilogue
    runs exactly once per output element, in VMEM."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    acc = jax.lax.dot_general(
        x_ref[...], w_ref[...],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    o_ref[...] += acc.astype(jnp.float32)

    @pl.when(k == n_k - 1)
    def _epilogue():
        y = o_ref[...] * s_ref[...].astype(jnp.float32)
        if b_ref is not None:
            y = y + b_ref[...].astype(jnp.float32)
        o_ref[...] = _apply_activation_kernel(y, activation)


def _pick_block(n, limit):
    """Largest block <= limit dividing n by halving (the shared
    power-of-two picker, ops/_pallas.py)."""
    return pick_block_pow2(n, limit)


def _plan_blocks(M, N, K, *, has_bias):
    """Halving-discipline blocks shrunk until one grid step's resident
    bytes fit the shared VMEM budget (ops/_pallas.py).

    The ``--kernels`` auditor caught the unbudgeted picker handing Mosaic
    a ~16 MiB step at serving lm-head shapes (M=512, K=N=4096, BK=4096
    double-buffered): shrink K first (cheapest — more grid steps over the
    same resident accumulator), then N, then M.

    The budget models the io blocks.  What the epilogue keeps beside them
    (the exact-GELU erf polynomial took the (512, 1024, 2048) plan to
    21.06 MiB by Mosaic's own account) is covered by the scoped-VMEM
    limit every kernel declares (``_pallas.VMEM_LIMIT``), not by a
    per-activation constant here.
    """
    BM = pick_block_pow2(M, _MAX_BLOCK_M)
    BN = pick_block_pow2(N, _MAX_BLOCK_N)
    BK = pick_block_pow2(K, _MAX_BLOCK_K)

    def fits(bm, bn, bk):
        io = [((bm, bk), jnp.int8), ((bk, bn), jnp.int8),
              ((1, bn), jnp.float32), ((bm, bn), jnp.float32)]
        if has_bias:
            io.append(((1, bn), jnp.float32))
        return vmem_footprint(io) <= VMEM_BUDGET

    while not fits(BM, BN, BK):
        # a halving is legal only while the half stays on the 128 lane
        # grid (BK, BN) or the int8 sublane grid (BM): 768 halves to 384
        # and stops there, never to 192
        if BK % (2 * LANE) == 0:
            BK //= 2
        elif BN % (2 * LANE) == 0:
            BN //= 2
        elif BM % 64 == 0:
            BM //= 2
        else:
            raise KernelGeometryError(
                f"quant_matmul: no block plan for (M={M}, N={N}, K={K}) "
                f"fits the {VMEM_BUDGET} B VMEM budget"
            )
    return BM, BN, BK


def quant_matmul_pallas(x_q, w_q, scale, bias=None, activation: str = "",
                        out_dtype=jnp.float32):
    """Pallas int8 matmul over a 2-D ``x_q``; the public dispatch flattens
    leading dims.  The fp32 accumulator doubles as the output buffer (one
    (BM, BN) block resident per grid step), so the epilogue's dequant
    never touches HBM as a separate tensor."""
    M, K = x_q.shape
    N = w_q.shape[1]
    BM, BN, BK = _plan_blocks(M, N, K, has_bias=bias is not None)
    n_k = K // BK
    grid = (M // BM, N // BN, n_k)

    scale = jnp.broadcast_to(
        jnp.asarray(scale, jnp.float32).reshape(1, -1), (1, N)
    )
    in_specs = [
        pl.BlockSpec((BM, BK), lambda i, j, k: (i, k)),
        pl.BlockSpec((BK, BN), lambda i, j, k: (k, j)),
        pl.BlockSpec((1, BN), lambda i, j, k: (0, j)),
    ]
    inputs = [x_q, w_q, scale]
    if bias is not None:
        in_specs.append(pl.BlockSpec((1, BN), lambda i, j, k: (0, j)))
        inputs.append(bias.reshape(1, N))

    def wrapped(*refs):
        x_ref, w_ref, s_ref = refs[0], refs[1], refs[2]
        b_ref = refs[3] if bias is not None else None
        _qmm_kernel(x_ref, w_ref, s_ref, b_ref, refs[-1],
                    activation=activation, n_k=n_k)

    out = _pallas_call(
        wrapped,
        name="quant_matmul",
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((BM, BN), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
    )(*inputs)
    return out.astype(out_dtype)


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------

def pallas_eligible(m: int, k: int, n: int, dtype) -> bool:
    """Static geometry gate for the Pallas path: int8 operands, K/N on
    the 128 lane grid, and M on the int8 sublane grid — real TPUs tile
    int8 as (32, 128), so rows must be a 32-multiple on hardware (then
    every block _pick_block can return is one too); interpret mode has
    no tiling constraint, same as the sibling quantized gates."""
    if dtype != jnp.int8:
        return False
    row_mult = 8 if interpret_enabled() else 32
    return m % row_mult == 0 and k % 128 == 0 and n % 128 == 0 and m > 0


def quant_matmul(x_q, w_q, scale, bias=None, activation: str = "",
                 out_dtype=jnp.float32):
    """Quantized dense: ``act(dequant(x_q @ w_q) + bias)``.

    ``x_q``: ``(..., K)`` int8 (or float8 on the jnp path); ``w_q``:
    ``(K, N)`` same dtype; ``scale``: combined per-channel dequant factor
    ``(N,)`` or scalar (fp32); ``bias``: ``(N,)`` or None.  Dispatches
    between the Pallas kernel and the jnp composition by mode + backend +
    geometry; numerics agree to fp32 rounding (the parity tests bound it).
    """
    lead = x_q.shape[:-1]
    K = x_q.shape[-1]
    N = w_q.shape[1]
    x2 = x_q.reshape(-1, K)
    mode = _resolved_mode()
    # 'auto' is strictly TPU-only, like every other gate in the suite —
    # interpret mode is a correctness tool (mode 'on'), not a fast path
    use_pallas = (
        mode != "off"
        and not (mode == "auto" and not on_tpu())
        and pallas_eligible(x2.shape[0], K, N, x2.dtype)
    )
    if use_pallas:
        out = quant_matmul_pallas(x2, w_q, scale, bias=bias,
                                  activation=activation, out_dtype=out_dtype)
    else:
        out = quant_matmul_reference(x2, w_q, scale, bias=bias,
                                     activation=activation,
                                     out_dtype=out_dtype)
    return out.reshape(lead + (N,))


# ---------------------------------------------------------------------------
# representative audit shapes (unicore-tpu-lint --kernels; docs/lint.md)
# ---------------------------------------------------------------------------

@audit_case("quant-matmul-serving")
def _audit_quant_matmul():
    """The serving lm-head geometry that exposed the unbudgeted block
    plan (BK=4096 -> ~16 MiB per double-buffered grid step); the planner
    must land inside the 12 MiB budget, epilogue branches populated."""
    x = jnp.zeros((512, 4096), jnp.int8)
    w = jnp.zeros((4096, 4096), jnp.int8)
    quant_matmul(x, w, jnp.ones((4096,), jnp.float32),
                 bias=jnp.zeros((4096,), jnp.float32), activation="gelu")
