"""LayerNorm / RMSNorm (reference /root/reference/unicore/modules/
layer_norm.py, rms_norm.py).

The reference dispatches to a fused CUDA kernel for a fixed dim set; here
BOTH paths exist and ONE documented flag picks between them
(``--fused-norm {auto,on,off}``, wired through
:func:`configure_fused_norm`):

- ``auto`` (default): the jnp composition — XLA fuses the norm into the
  surrounding elementwise/matmul ops (against the standalone Pallas
  kernel: not measured on a cell);
- ``on``: the Pallas fused kernels (ops/fused_norm.py) — for parity
  benchmarking and for shapes where XLA's fusion falls over;
- ``off``: jnp unconditionally.

Precedence: ``UNICORE_TPU_PALLAS_NORM`` env (0/1, experiments) > the
module's explicit ``use_pallas`` attribute > the configured flag.  Each
module instance journals the path it chose ONCE per (kind, dim, path)
through the telemetry plane (kind ``fused-norm-path``) so a run's kernel
selection is in the event journal, not a silent import-time guard.

Semantics on every path: eps defaults (1e-5 LN / 1e-6 RMS), elementwise
affine (weight=1, bias=0 init), fp32 statistics regardless of input dtype
(the CUDA kernel's accumulator behavior), output cast back to input dtype.
"""

import os
from typing import Optional, Set, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

_MODES = ("auto", "on", "off")
_mode = "auto"
_journaled: Set[Tuple[str, int, str]] = set()


def configure_fused_norm(mode: Optional[str]):
    """Wire ``--fused-norm`` (None resets to ``auto``)."""
    global _mode
    if mode is None:
        mode = "auto"
    if mode not in _MODES:
        raise ValueError(f"--fused-norm {mode!r} not in {_MODES}")
    _mode = mode


def _use_pallas(use_pallas: Optional[bool], kind: str, dim: int) -> bool:
    env = os.environ.get("UNICORE_TPU_PALLAS_NORM")
    if env is not None:
        chosen = env not in ("0", "false", "")
        source = "env"
    elif use_pallas is not None:
        chosen = use_pallas
        source = "module"
    else:
        # 'auto' currently means jnp everywhere: XLA's fusion wins
        # end-to-end (module docstring); 'on' forces the Pallas kernels
        chosen = _mode == "on"
        source = f"flag:{_mode}"
    if chosen and not _pallas_runnable():
        # the kernels compile only on TPU (interpret mode covers other
        # backends for tests/benchmarks): degrade to jnp LOUDLY instead of
        # crashing a CPU run that set --fused-norm on
        if ("fallback:no-tpu",) not in _journaled:
            _journaled.add(("fallback:no-tpu",))
            import logging

            logging.getLogger(__name__).warning(
                "--fused-norm: Pallas norm kernels need a TPU backend (or "
                "interpret mode); falling back to the jnp path"
            )
        chosen = False
        source += ":no-tpu-fallback"
    _journal_choice(kind, dim, chosen, source)
    return chosen


def _pallas_runnable() -> bool:
    from unicore_tpu.ops._pallas import interpret_enabled
    from unicore_tpu.platform_utils import on_tpu

    return on_tpu() or interpret_enabled()


def _journal_choice(kind: str, dim: int, pallas: bool, source: str) -> None:
    """One-shot journal per (kind, dim, path): which norm implementation
    this module instance traces with (docs/performance.md).  A choice made
    BEFORE the journal is configured (library use, or between an elastic
    restart's reset and reconfigure) stays unmarked, so the first traced
    choice after configure still lands in the new journal."""
    path = "pallas" if pallas else "jnp"
    key = (kind, dim, path)
    if key in _journaled:
        return
    from unicore_tpu import telemetry
    from unicore_tpu.telemetry import journal as _journal_mod

    if _journal_mod.active() is None:
        return
    _journaled.add(key)
    telemetry.emit(
        "fused-norm-path", module=kind, dim=dim, path=path, source=source
    )


class LayerNorm(nn.Module):
    normalized_shape: int
    eps: float = 1e-5
    elementwise_affine: bool = True
    use_pallas: Optional[bool] = None  # None = follow --fused-norm

    @nn.compact
    def __call__(self, x):
        assert self.elementwise_affine
        weight = self.param(
            "weight", nn.initializers.ones, (self.normalized_shape,), jnp.float32
        )
        bias = self.param(
            "bias", nn.initializers.zeros, (self.normalized_shape,), jnp.float32
        )
        from unicore_tpu.quant import QTensor

        if isinstance(x, QTensor):
            # quantized serving: a QuantDense(quantize_output=True) site
            # feeds its int8 output straight in; the dequant multiply is
            # fused into the norm's fp32 statistics pass (ops/quant_norm.py)
            from unicore_tpu.ops.quant_norm import quant_layer_norm

            return quant_layer_norm(
                x.values, x.scale, weight, bias, eps=self.eps,
                out_dtype=jnp.float32,
            )
        if _use_pallas(self.use_pallas, "LayerNorm", self.normalized_shape):
            from unicore_tpu.ops.fused_norm import fused_layer_norm

            return fused_layer_norm(x, weight, bias, eps=self.eps)
        dtype = x.dtype
        xf = x.astype(jnp.float32)
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
        y = (xf - mean) * jax.lax.rsqrt(var + self.eps)
        y = y * weight + bias
        return y.astype(dtype)


class RMSNorm(nn.Module):
    """RMSNorm (reference /root/reference/unicore/modules/rms_norm.py):
    no mean subtraction, scale-only affine, fp32 statistics.

    With ``unit_offset`` the gain is ``1 + offset`` and the parameter is
    the offset, zero at the start (``norm_add_unit_offset`` of the Gemma /
    EvaByte kind: weight decay and a small initializer then pull the gain
    towards one, not towards zero)."""

    normalized_shape: int
    eps: float = 1e-6
    elementwise_affine: bool = True
    use_pallas: Optional[bool] = None  # None = follow --fused-norm
    unit_offset: bool = False

    @nn.compact
    def __call__(self, x):
        assert self.elementwise_affine
        if self.unit_offset:
            weight = 1.0 + self.param(
                "offset", nn.initializers.zeros, (self.normalized_shape,),
                jnp.float32,
            )
        else:
            weight = self.param(
                "weight", nn.initializers.ones, (self.normalized_shape,),
                jnp.float32,
            )
        if _use_pallas(self.use_pallas, "RMSNorm", self.normalized_shape):
            from unicore_tpu.ops.fused_norm import fused_rms_norm

            return fused_rms_norm(x, weight, eps=self.eps)
        dtype = x.dtype
        xf = x.astype(jnp.float32)
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf / jnp.sqrt(ms + self.eps)
        y = y * weight
        return y.astype(dtype)
