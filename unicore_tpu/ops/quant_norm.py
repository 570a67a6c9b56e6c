"""Quantized-input LayerNorm — dispatch + jnp oracle.

Consumes the int8 activation a ``QuantDense(quantize_output=True)`` site
emits (the lm-head chain in quantized serving): the dequant multiply is
fused into the norm's fp32 row-statistics pass
(``fused_norm.quant_layer_norm_pallas``), so the fp32 activation between
the dense and the norm is never materialized — the int8 tensor is 4x
less HBM traffic than the fp32 one it replaces (arXiv 2502.17728).

Same dispatch contract as ``ops/softmax_dropout.py``: mode ``auto`` is
Pallas on a real TPU backend when the geometry allows, jnp elsewhere;
``on`` forces Pallas wherever the geometry allows (parity tests run it
under interpret mode on CPU); ``off`` is always jnp.  Set via
:func:`set_quant_norm_mode` or ``UNICORE_TPU_PALLAS_QUANT_NORM``.
Forward-only (no VJP for a quantized input).
"""

from typing import Optional

import jax
import jax.numpy as jnp

from ._pallas import ModeGate
from unicore_tpu.platform_utils import on_tpu

_gate = ModeGate("quant_norm", "UNICORE_TPU_PALLAS_QUANT_NORM")


def set_quant_norm_mode(mode: Optional[str]):
    """Select the dispatch mode (``auto``/``on``/``off``; None = auto)."""
    _gate.set(mode)


_resolved_mode = _gate.resolved


def quant_layer_norm_reference(x_q, x_scale, weight, bias,
                               eps: float = 1e-5, out_dtype=jnp.float32):
    """jnp oracle: dequantize + fp32 LayerNorm (the same statistics
    contract as modules/layer_norm.py — fp32 regardless of input dtype)."""
    x = x_q.astype(jnp.float32) * jnp.asarray(x_scale, jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    y = (x - mean) * jax.lax.rsqrt(var + eps)
    y = y * weight + bias
    return y.astype(out_dtype)


def _pallas_eligible(x_q) -> bool:
    from ._pallas import interpret_enabled

    mode = _resolved_mode()
    if mode == "off":
        return False
    if mode == "auto" and not on_tpu():
        return False
    if x_q.dtype != jnp.int8 or x_q.ndim < 2:
        return False
    rows = 1
    for d in x_q.shape[:-1]:
        rows *= d
    if rows == 0:
        return False
    if not interpret_enabled() and rows % 32 != 0:
        return False  # int8 sublane tiling on real TPUs is (32, 128)
    return True


def quant_layer_norm(x_q, x_scale, weight, bias, eps: float = 1e-5,
                     out_dtype=jnp.float32):
    """LayerNorm over the last dim of a quantized tensor:
    ``LN(dequant(x_q)) * weight + bias`` with fp32 statistics, dequant
    fused into the statistics pass on the Pallas path."""
    if _pallas_eligible(x_q):
        from .fused_norm import quant_layer_norm_pallas

        return quant_layer_norm_pallas(
            x_q, x_scale, weight, bias, eps=eps, out_dtype=out_dtype
        )
    return quant_layer_norm_reference(
        x_q, x_scale, weight, bias, eps=eps, out_dtype=out_dtype
    )
