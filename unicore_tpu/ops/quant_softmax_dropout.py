"""Quantized-input fused softmax(+mask)(+bias)(+dropout) — dispatch +
jnp oracle.

The serving plane's attention-score path: Q and K quantize to int8, the
score matmul accumulates int32, and THIS op consumes the quantized scores
directly — the dequant multiply happens inside the softmax row pass
(``softmax_dropout_pallas.quant_softmax_dropout_pallas``), so the fp32
score tensor is never materialized between the matmul and the softmax
(arXiv 2502.17728's operation-fusion argument; the fusion audit checks
the compiled program for stray convert chains).

Same dispatch contract as ``ops/softmax_dropout.py``: mode ``auto`` is
Pallas on a real TPU backend when the geometry allows, jnp elsewhere;
``on`` forces Pallas wherever the geometry allows (parity tests run it
under interpret mode on CPU); ``off`` is always the jnp composition.
Set via :func:`set_quant_softmax_dropout_mode` or the
``UNICORE_TPU_PALLAS_QUANT_SOFTMAX`` env var.  Inference-oriented: the
op is forward-only (no VJP for a quantized input).
"""

from typing import Optional

import jax
import jax.numpy as jnp

from .softmax_dropout import softmax_dropout_reference

from ._pallas import ModeGate
from unicore_tpu.platform_utils import on_tpu

_gate = ModeGate("quant_softmax_dropout", "UNICORE_TPU_PALLAS_QUANT_SOFTMAX")


def set_quant_softmax_dropout_mode(mode: Optional[str]):
    """Select the dispatch mode (``auto``/``on``/``off``; None = auto)."""
    _gate.set(mode)


_resolved_mode = _gate.resolved


def quant_softmax_dropout_reference(
    input_q: jnp.ndarray,
    x_scale,
    dropout_prob: float,
    is_training: bool = False,
    mask: Optional[jnp.ndarray] = None,
    bias: Optional[jnp.ndarray] = None,
    dropout_rng: Optional[jax.Array] = None,
    out_dtype=jnp.float32,
) -> jnp.ndarray:
    """jnp oracle: dequantize + the fp32 softmax composition.  XLA fuses
    the convert+multiply into the softmax chain (the audit proves it);
    the Pallas path makes the same fusion explicit."""
    x = input_q.astype(jnp.float32) * jnp.asarray(x_scale, jnp.float32)
    out = softmax_dropout_reference(
        x, dropout_prob, is_training=is_training, mask=mask, bias=bias,
        dropout_rng=dropout_rng,
    )
    return out.astype(out_dtype)


def _pallas_eligible(input_q, mask, bias) -> Optional[tuple]:
    from ._pallas import interpret_enabled
    from .softmax_dropout_pallas import pallas_plan

    mode = _resolved_mode()
    if mode == "off":
        return None
    if mode == "auto" and not on_tpu():
        return None
    if input_q.dtype not in (jnp.int8, jnp.int32):
        return None
    if input_q.dtype == jnp.int8 and not interpret_enabled() \
            and input_q.shape[-2] % 32 != 0:
        # int8 sublane tiling on real TPUs is (32, 128)
        return None
    # geometry/extras feasibility is dtype-independent: probe with fp32
    return pallas_plan(tuple(input_q.shape), jnp.float32, mask, bias)


def quant_softmax_dropout(
    input_q: jnp.ndarray,
    x_scale,
    dropout_prob: float = 0.0,
    is_training: bool = False,
    mask: Optional[jnp.ndarray] = None,
    bias: Optional[jnp.ndarray] = None,
    dropout_rng: Optional[jax.Array] = None,
    out_dtype=jnp.float32,
) -> jnp.ndarray:
    """softmax(dequant(input_q) [+ mask] [+ bias]) with optional dropout.

    ``input_q`` is int8 or an int32 matmul accumulator; ``x_scale`` its
    scalar dequant factor.  Output is ``out_dtype`` (the model's compute
    dtype, not the quantized input's).
    """
    training_dropout = is_training and dropout_prob > 0.0
    if training_dropout and dropout_rng is None:
        raise ValueError(
            "quant_softmax_dropout needs dropout_rng when training with "
            "dropout"
        )
    plans = _pallas_eligible(input_q, mask, bias)
    if plans is not None:
        from .softmax_dropout_pallas import quant_softmax_dropout_pallas

        seed = 0
        if training_dropout:
            seed = jax.random.randint(
                dropout_rng, (), 0, 2 ** 31 - 1, dtype=jnp.int32
            )
        return quant_softmax_dropout_pallas(
            input_q, x_scale, dropout_prob, is_training=is_training,
            mask=mask, bias=bias, seed=seed, plans=plans,
            out_dtype=out_dtype,
        )
    return quant_softmax_dropout_reference(
        input_q, x_scale, dropout_prob, is_training=is_training,
        mask=mask, bias=bias, dropout_rng=dropout_rng, out_dtype=out_dtype,
    )
