"""``QuantDense`` — the ONE quantize-aware dense entry point every wired
call site routes through (``modules/multihead_attention.py``,
``modules/transformer_encoder.py``, ``models/bert.py``).

Three behaviors behind one module, selected by the ``quantize`` attr and
the trace-time calibration flag:

- **fp32/bf16 path** (``quantize == ''`` or inside
  :func:`~unicore_tpu.quant.calibration_scope`): byte-for-byte the
  ``nn.Dense`` computation (same param names, same ``promote_dtype`` +
  ``lax.dot_general``), optionally followed by the module's fused
  ``activation`` — training and non-quantized serving are untouched;
- **calibration** (fp32 path inside the scope, at a site built with a
  ``quantize`` mode): additionally sows the per-site input absmax (and
  post-activation output absmax for ``quantize_output`` sites) into the
  ``quant_calib`` collection with a running-max reducer —
  ``calibrate.collect_scales`` reads them;
- **quantized path** (``quantize in ('int8', 'fp8')``, not calibrating):
  reads the PREPARED params (``kernel_q``/``kernel_scale``/``act_scale``
  [+ ``out_scale``], built by ``calibrate.prepare`` from the fp32
  checkpoint + calibrated scales), quantizes the incoming activation with
  the calibrated static scale, and runs ``ops/quant_matmul.py`` with
  dequant + bias + activation fused into the epilogue.  With
  ``quantize_output`` the result is re-quantized against the calibrated
  output scale and returned as a :class:`~unicore_tpu.quant.QTensor` for
  a quantized-input consumer (``ops/quant_norm.py``).

The quantized path is inference-only: no VJP, dropout-free call sites.

**Head-major sites** (``heads_out`` / ``heads_in``, the attention
projections) hand out and take ``(B, H, L, D)``.  Where a Mosaic attention
kernel takes the operands, its custom call fixes that layout, and a flat
``(B, L, E)`` product followed by reshape + transpose costs one standalone
activation-sized ``copy`` per tensor and direction on the chip; with
``heads_fused`` the head axes are visible to the product itself — the SAME
``(in, out)`` kernel parameter *viewed* as ``(E, T, H, D)`` or
``(H, D, E)`` — and XLA writes and reads the kernels' layout from inside the
matmul fusions (``tests/test_tpu_compile.py`` holds the compile that shows
it).  Where nothing pins a layout the flat product and the transposes stay:
XLA places them better than the fused form lets it (PERF.md, PR 26).
Parameters, checkpoints, sharding rules and the quantized path's flat
product are the same either way.
"""

import functools
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from flax.linen.dtypes import promote_dtype

from unicore_tpu import quant as _q

#: the mutable collection calibration sows into
CALIB_COLLECTION = "quant_calib"


def _running_max(acc, new):
    return jnp.maximum(acc, new)


def _absmax(x) -> jnp.ndarray:
    return jnp.max(jnp.abs(x.astype(jnp.float32)))


class QuantDense(nn.Dense):
    """Drop-in ``nn.Dense`` with a quantized serving path.

    Extra attrs on top of ``nn.Dense``:

    - ``quantize``: '' (fp32/bf16, the default — training checkpoints and
      numerics are bit-identical to ``nn.Dense``), 'int8', or 'fp8';
    - ``activation``: optional fused epilogue nonlinearity (the
      ``utils.get_activation_fn`` name table); applied on BOTH paths so
      the composition is identical;
    - ``quantize_output``: re-quantize the (post-activation) output with
      the calibrated ``out_scale`` and return a ``QTensor``;
    - ``heads_out``: ``(T, H)`` — the ``features`` columns are ``T``
      projections of ``H`` heads each (``[q | k | v]``, head-major); a
      ``(B, L, E)`` input returns ``T`` arrays of ``(B, H, L, D)`` (no
      ``activation`` at such a site);
    - ``heads_in``: ``H`` — the input arrives as ``(B, H, L, D)``, the
      kernel's rows are its ``H * D``; returns ``(B, L, features)``;
    - ``heads_fused``: at a head-major site, whether the product itself
      writes / reads ``(B, H, L, D)`` (the caller sets it where a kernel
      pins that layout) or a reshape + transpose around the flat product
      does (always so on the quantized path).
    """

    quantize: str = ""
    activation: str = ""
    quantize_output: bool = False
    heads_out: Optional[Tuple[int, int]] = None
    heads_in: Optional[int] = None
    heads_fused: bool = False

    @nn.compact
    def __call__(self, inputs):  # noqa: C901 — three documented paths
        # check_mode treats '' and 'off' the same (and rejects typos
        # loudly at trace time) — a plumbed-through --serve-quantize
        # default of 'off' must take the fp path, not KeyError
        has_mode = _q.check_mode(self.quantize) != "off"
        # a site built without a mode never reads kernel_q, so it sows
        # nothing either: calibrate.prepare() quantizes every site that sowed
        calibrating = has_mode and _q.calibrating()
        if has_mode and not calibrating:
            # the quantized product stays flat: (B, L, E) in, (B, L, out) out
            if self.heads_in:
                inputs = _merge_heads(inputs)
            y = self._quantized(inputs)
            return _split_heads(y, *self.heads_out) if self.heads_out else y

        # -- the nn.Dense computation, replicated byte-for-byte ----------
        in_dim = jnp.shape(inputs)[-1]
        if self.heads_in:
            in_dim *= self.heads_in
        kernel = self.param(
            "kernel",
            self.kernel_init,
            (in_dim, self.features),
            self.param_dtype,
        )
        bias = (
            self.param("bias", self.bias_init, (self.features,),
                       self.param_dtype)
            if self.use_bias
            else None
        )
        x, kernel, bias = promote_dtype(inputs, kernel, bias,
                                        dtype=self.dtype)
        if calibrating:
            self.sow(CALIB_COLLECTION, "act_absmax", _absmax(x),
                     init_fn=lambda: jnp.float32(0.0),
                     reduce_fn=_running_max)
        y = self._product(x, kernel, bias)
        if self.activation:
            from unicore_tpu.utils import get_activation_fn

            y = get_activation_fn(self.activation)(y)
        if calibrating and self.quantize_output:
            self.sow(CALIB_COLLECTION, "out_absmax", _absmax(y),
                     init_fn=lambda: jnp.float32(0.0),
                     reduce_fn=_running_max)
        return y

    @nn.nowrap  # no scope of its own: the trace's paths end at the site's name
    def _product(self, x, kernel, bias):
        if self.heads_out:
            if self.heads_fused:
                return _heads_out_product(
                    x, kernel, bias, *self.heads_out, self.precision)
            y = _flat_product(x, kernel, bias, self.precision)
            return _split_heads(y, *self.heads_out)
        if self.heads_in:
            if self.heads_fused:
                return _heads_in_product(x, kernel, bias, self.precision)
            x = _merge_heads(x)
        return _flat_product(x, kernel, bias, self.precision)

    # -- quantized serving path ------------------------------------------

    def _quantized(self, inputs):
        from unicore_tpu.ops.quant_matmul import quant_matmul

        mode = _q.check_mode(self.quantize)
        qmax = _q.QMAX[mode]
        in_dim = jnp.shape(inputs)[-1]
        # prepared params (calibrate.prepare) — init_fns exist only so a
        # stray init() fails loudly with sane shapes instead of cryptically
        kernel_q = self.param(
            "kernel_q", nn.initializers.zeros,
            (in_dim, self.features), _storage_dtype(mode),
        )
        kernel_scale = self.param(
            "kernel_scale", nn.initializers.ones, (self.features,),
            jnp.float32,
        )
        act_scale = self.param(
            "act_scale", nn.initializers.ones, (), jnp.float32
        )
        bias = (
            self.param("bias", self.bias_init, (self.features,),
                       self.param_dtype)
            if self.use_bias
            else None
        )
        x_q = _quantize(inputs, act_scale, qmax, _storage_dtype(mode))
        out_dtype = self.dtype or jnp.asarray(inputs).dtype
        y = quant_matmul(
            x_q, kernel_q,
            scale=act_scale * kernel_scale,
            bias=bias,
            activation=self.activation,
            out_dtype=out_dtype,
        )
        if self.quantize_output:
            out_scale = self.param(
                "out_scale", nn.initializers.ones, (), jnp.float32
            )
            return _q.QTensor(
                _quantize(y, out_scale, qmax, _storage_dtype(mode)),
                out_scale,
            )
        return y


def _flat_product(x, kernel, bias, precision):
    """``x @ kernel + bias``: the product is rounded to the compute dtype
    before the bias adds."""
    y = jax.lax.dot_general(
        x, kernel, (((x.ndim - 1,), (0,)), ((), ())), precision=precision
    )
    if bias is not None:
        y += jnp.reshape(bias, (1,) * (y.ndim - 1) + (-1,))
    return y


def _heads_out_product(x, kernel, bias, t, h, precision):
    """``_flat_product`` cut into ``T`` arrays of ``(B, H, L, D)``, the
    head axes made by the products themselves."""
    ys = _head_products(
        x, kernel.reshape(kernel.shape[0], t, h, -1), precision)
    if bias is not None:
        b = bias.reshape(t, h, 1, -1)
        ys = tuple(y + b[i] for i, y in enumerate(ys))
    return ys


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _head_products(x, w, precision):
    """``x (B, L, E)`` times each ``w[:, t]`` of ``w (E, T, H, D)``.  One
    product per projection, not one ``(T, B, H, L, D)`` product cut in ``T``
    afterwards: XLA on the chip copies the slices of the latter out again
    (PERF.md, PR 26).  The backward is written out because autodiff would
    sum ``dx`` from ``T`` products, each rounded to the compute dtype; here
    it is one contraction over ``(T, H, D)``, accumulated and rounded once
    as the flat product's is (XLA reads the ``T`` cotangents in place)."""
    return tuple(
        jnp.einsum("ble,ehd->bhld", x, w[:, i], precision=precision)
        for i in range(w.shape[1])
    )


def _head_products_fwd(x, w, precision):
    return _head_products(x, w, precision), (x, w)


def _head_products_bwd(precision, residuals, dys):
    x, w = residuals
    dx = jnp.einsum(
        "tbhld,ethd->ble", jnp.stack(dys), w, precision=precision
    )
    dw = jnp.stack(
        [jnp.einsum("ble,bhld->ehd", x, dy, precision=precision)
         for dy in dys],
        axis=1,
    )
    return dx, dw


_head_products.defvjp(_head_products_fwd, _head_products_bwd)


def _heads_in_product(x, kernel, bias, precision):
    """``_flat_product`` of a ``(B, H, L, D)`` input, both head axes
    contracted by the product itself."""
    _, h, _, d = x.shape
    y = jnp.einsum(
        "bhld,hde->ble", x, kernel.reshape(h, d, -1), precision=precision
    )
    return y if bias is None else y + bias


def _split_heads(y, t, h):
    """Flat ``(B, L, T*H*D)`` -> ``T`` arrays of ``(B, H, L, D)``."""
    b, l, _ = y.shape
    return tuple(
        part.reshape(b, l, h, -1).transpose(0, 2, 1, 3)
        for part in jnp.split(y, t, axis=-1)
    )


def _merge_heads(x):
    """``(B, H, L, D)`` -> flat ``(B, L, H*D)``."""
    b, h, l, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, l, h * d)


def _storage_dtype(mode: str):
    if mode == "int8":
        return jnp.int8
    return jnp.float8_e4m3fn


def _quantize(x, scale, qmax: float, dtype):
    """Symmetric quantization against a calibrated static scale — the
    shared ``quantize_to_dtype`` step, so QuantDense and the kernel
    oracles quantize identically by construction."""
    from unicore_tpu.ops.quant_matmul import quantize_to_dtype

    return quantize_to_dtype(x, scale, qmax, dtype)
