"""Post-training quantization for the serving plane.

The pieces (docs/serving.md, "Quantized inference"):

- :class:`QTensor` — an int8/fp8 tensor plus its dequant scale, the typed
  boundary between a ``QuantDense(quantize_output=True)`` site and the
  quantized-input op that consumes it (``ops/quant_norm.py``);
- :func:`calibration_scope` — a trace-time flag that makes every
  :class:`~unicore_tpu.quant.dense.QuantDense` site run the fp32 path and,
  where it was built with a mode, sow per-site activation absmax into the
  ``quant_calib`` collection;
- :mod:`~unicore_tpu.quant.calibrate` — the startup calibration pass:
  deterministic held-out batches through the warmed per-bucket programs,
  per-channel weight scales + per-site activation scales, persisted
  beside the snapshot (digest-tied to the exact weights) so hot reload
  re-verifies or re-derives them before any swap;
- :func:`~unicore_tpu.quant.calibrate.prepare` — transforms the fp32
  checkpoint tree into the quantized serving tree (``kernel`` ->
  ``kernel_q`` + ``kernel_scale`` + ``act_scale`` [+ ``out_scale``]).

Modes: ``int8`` (Pallas int8 kernels, ``ops/quant_matmul.py``) and
``fp8`` (float8_e4m3fn storage/rounding; fp32-accumulated compute on
backends without a native f8 dot).  Everything here is inference-only —
training precision is untouched.
"""

import contextlib
import threading
from typing import NamedTuple

MODES = ("off", "int8", "fp8")

#: symmetric quantization ranges per mode
QMAX = {"int8": 127.0, "fp8": 448.0}  # float8_e4m3fn finite max


class QTensor(NamedTuple):
    """A quantized tensor and its dequant scale (scalar or per-channel).
    ``dequant()`` is for oracles/tests — production consumers fuse the
    multiply into their own first pass instead."""

    values: object  # int8/fp8 ndarray
    scale: object   # fp32 scalar or (D,) vector

    def dequant(self):
        import jax.numpy as jnp

        return self.values.astype(jnp.float32) * self.scale


_state = threading.local()


def calibrating() -> bool:
    """True inside :func:`calibration_scope` — QuantDense sites trace the
    fp32 path and sow activation absmax (a trace-time flag: each apply is
    traced fresh, so the scope must wrap the ``model.apply`` call)."""
    return getattr(_state, "calibrating", False)


@contextlib.contextmanager
def calibration_scope():
    prev = calibrating()
    _state.calibrating = True
    try:
        yield
    finally:
        _state.calibrating = prev


def check_mode(mode: str) -> str:
    """Normalize/validate a ``--serve-quantize`` value; '' == 'off'."""
    mode = mode or "off"
    if mode not in MODES:
        raise ValueError(f"quantize mode {mode!r} not in {MODES}")
    return mode


from unicore_tpu.quant.dense import QuantDense  # noqa: E402

__all__ = [
    "MODES",
    "QMAX",
    "QTensor",
    "QuantDense",
    "calibrating",
    "calibration_scope",
    "check_mode",
]
