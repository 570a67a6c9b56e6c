"""Fused softmax(+mask)(+bias)(+dropout) — dispatch + jnp oracle.

TPU-native counterpart of the reference's ``unicore_fused_softmax_dropout``
CUDA extension (/root/reference/csrc/softmax_dropout/ and
unicore/modules/softmax_dropout.py): the same op surface — optional additive
mask and bias with the reference's broadcast semantics (_check_mask /
_check_bias, softmax_dropout.py:53-97).  Two implementations share it:

- the **jnp composition** below (the oracle and the universal fallback):
  XLA fuses the softmax chain well, but training-mode dropout pays a
  separate ``jax.random.bernoulli`` pass whose mask round-trips HBM;
- the **Pallas kernel** (ops/softmax_dropout_pallas.py): in-kernel
  counter-based PRNG hidden behind the row compute, recomputed — never
  stored — in the backward.

``softmax_dropout`` dispatches between them by backend and shape so callers
(modules/multihead_attention.py, modules/evoformer.py) change zero lines:

- mode ``auto`` (default): Pallas on a real TPU backend when
  ``pallas_plan`` accepts the geometry (last dim a 128-multiple <= 8192,
  rows a multiple of 8, fp32/bf16, expressible mask/bias layout); jnp
  everywhere else.  CPU/interpret stays on the jnp path so numerics of
  existing CPU runs are bit-identical to before.
- mode ``on``: Pallas whenever the geometry allows — used by the parity
  tests and benchmarks (with ops._pallas interpret mode on CPU).
- mode ``off``: always jnp.

Set via :func:`set_softmax_dropout_mode` or the
``UNICORE_TPU_PALLAS_SOFTMAX_DROPOUT`` env var (``auto``/``on``/``off``,
plus legacy ``0``/``1``).  The softmax runs in fp32 regardless of input
dtype (matching the CUDA kernel's accumulator) on BOTH paths.

This op is the API for modules that need materialized probabilities
(``return_attn`` consumers like Uni-Fold's triangle attention); the memory-
bound long-sequence cases are covered by the Pallas flash-attention kernel.
"""

from typing import Optional

import jax
import jax.numpy as jnp

from ._pallas import ModeGate
from unicore_tpu.platform_utils import on_tpu

_gate = ModeGate("softmax_dropout", "UNICORE_TPU_PALLAS_SOFTMAX_DROPOUT")


def set_softmax_dropout_mode(mode: Optional[str]):
    """Select the dispatch mode (``auto``/``on``/``off``; None = auto)."""
    _gate.set(mode)


_resolved_mode = _gate.resolved


def _broadcastable_to(shape, target):
    if len(shape) != len(target):
        return False
    return all(s == t or s == 1 for s, t in zip(shape, target))


def _expand_extra(x: jnp.ndarray, input_shape) -> Optional[jnp.ndarray]:
    """Broadcast mask/bias to the input shape under the reference's rules:
    trailing dims must match or be 1; a leading batch dim ``b`` with
    ``input.size(0) % b == 0`` repeats (the Uni-Fold triangle-attention
    layout, reference interface.cpp:37-48)."""
    if x is None:
        return None
    if x.ndim < len(input_shape):
        x = x.reshape((1,) * (len(input_shape) - x.ndim) + x.shape)
    if _broadcastable_to(x.shape, input_shape):
        return jnp.broadcast_to(x, input_shape)
    # reference semantics: flatten leading dims; input rows divisible by bias rows
    rows_in = 1
    for s in input_shape[:-2]:
        rows_in *= s
    rows_x = 1
    for s in x.shape[:-2]:
        rows_x *= s
    if rows_in % rows_x == 0:
        x = x.reshape((rows_x,) + x.shape[-2:])
        x = jnp.tile(x, (rows_in // rows_x, 1, 1))
        return x.reshape(input_shape)
    raise ValueError(
        f"mask/bias shape {x.shape} not broadcastable to input {input_shape}"
    )


def softmax_dropout_reference(
    input: jnp.ndarray,
    dropout_prob: float,
    is_training: bool = True,
    mask: Optional[jnp.ndarray] = None,
    bias: Optional[jnp.ndarray] = None,
    dropout_rng: Optional[jax.Array] = None,
) -> jnp.ndarray:
    """The jnp composition — the numerics oracle and universal fallback."""
    dtype = input.dtype
    x = input.astype(jnp.float32)
    if mask is not None:
        x = x + _expand_extra(mask.astype(jnp.float32), x.shape)
    if bias is not None:
        x = x + _expand_extra(bias.astype(jnp.float32), x.shape)
    probs = jax.nn.softmax(x, axis=-1)
    probs = probs.astype(dtype)
    if is_training and dropout_prob > 0.0:
        if dropout_rng is None:
            raise ValueError(
                "softmax_dropout needs dropout_rng when training with dropout"
            )
        keep = jax.random.bernoulli(dropout_rng, 1.0 - dropout_prob, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_prob), 0.0).astype(dtype)
    return probs


def _pallas_eligible(input, mask, bias) -> Optional[tuple]:
    """Return the static kernel plan when the dispatch mode + backend +
    geometry allow the Pallas path, else None."""
    mode = _resolved_mode()
    if mode == "off":
        return None
    if mode == "auto" and not on_tpu():
        return None
    from .softmax_dropout_pallas import pallas_plan

    return pallas_plan(tuple(input.shape), input.dtype, mask, bias)


def softmax_dropout(
    input: jnp.ndarray,
    dropout_prob: float,
    is_training: bool = True,
    mask: Optional[jnp.ndarray] = None,
    bias: Optional[jnp.ndarray] = None,
    dropout_rng: Optional[jax.Array] = None,
    inplace: bool = True,  # kept for API parity; functional arrays ignore it
) -> jnp.ndarray:
    """softmax(input [+ mask] [+ bias]) with optional dropout.

    Mirrors reference modules/softmax_dropout.py:100-144.  ``dropout_rng`` is
    required when ``is_training and dropout_prob > 0``.
    """
    training_dropout = is_training and dropout_prob > 0.0
    if training_dropout and dropout_rng is None:
        raise ValueError(
            "softmax_dropout needs dropout_rng when training with dropout"
        )
    plans = _pallas_eligible(input, mask, bias)
    if plans is not None:
        from .softmax_dropout_pallas import softmax_dropout_pallas

        seed = 0
        if training_dropout:
            # the key is consumed exactly once, into the kernel's int32
            # stream id (mixed with block coordinates in-kernel)
            seed = jax.random.randint(
                dropout_rng, (), 0, 2 ** 31 - 1, dtype=jnp.int32
            )
        return softmax_dropout_pallas(
            input, dropout_prob, is_training=is_training,
            mask=mask, bias=bias, seed=seed, plans=plans,
        )
    return softmax_dropout_reference(
        input, dropout_prob, is_training=is_training,
        mask=mask, bias=bias, dropout_rng=dropout_rng,
    )
