"""The kernel table: every Pallas kernel family of the main paths, entered
DIRECTLY (no ``auto`` gate can route around it), at BERT-base /
``transformer_lm`` widths, each beside its jnp oracle from this tree.

Two readers, one table:

* ``tests/test_tpu_compile.py`` lowers every case for a described TPU v5e
  (shapes only — what Mosaic refuses shows up on the CPU box);
* ``chip_smoke.py``'s ``kernels`` phase runs every case compiled on the
  chip and compares it with its oracle.

A case whose kernel draws in-kernel dropout has no comparable oracle (the
kernel's generator is not ``jax.random``'s): it carries ``oracle=None`` and
is checked for finite values only; its no-dropout twin carries the parity.
"""

import dataclasses
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_F32, _BF16, _I8, _I32 = jnp.float32, jnp.bfloat16, jnp.int8, jnp.int32


@dataclasses.dataclass(frozen=True)
class KernelCase:
    name: str
    #: ((shape, dtype, fill), ...) — fill names how make_inputs draws it
    specs: Tuple[tuple, ...]
    kernel: Callable
    oracle: Optional[Callable]
    #: bound on max|kernel - oracle| / max|oracle| over every output leaf
    tol: float
    #: in-kernel dropout draws from the TPU PRNG; interpret mode cannot
    tpu_prng: bool = False


def make_inputs(case: KernelCase, seed: int = 0):
    """Seeded concrete inputs for ``case`` (numpy draws, device arrays)."""
    rng = np.random.RandomState(seed)
    out = []
    for shape, dtype, fill in case.specs:
        if fill == "normal":
            a = rng.standard_normal(shape)
        elif fill == "int8":
            a = rng.randint(-127, 128, size=shape)
        elif fill == "pad_tail":  # (B, L) int: the last eighth is padding
            a = np.zeros(shape)
            a[..., -(shape[-1] // 8):] = 1
        elif fill == "neg_tail":  # additive mask: the last eighth is dead
            a = np.zeros(shape)
            a[..., -(shape[-1] // 8):] = -1e9
        elif fill == "positions":  # (B,) live-prefix ends inside the cache
            a = rng.randint(1, int(case.specs[1][0][2]), size=shape)
        elif fill == "scale":
            a = rng.uniform(0.5, 1.5, size=shape) * 0.02
        elif fill == "mm_scale":  # brings an int8 x int8 K-sum to O(1)
            k = int(case.specs[0][0][1])
            a = rng.uniform(0.5, 1.5, size=shape) * 3.0 / (73.0 ** 2 * k ** 0.5)
        elif fill == "affine":
            a = 1.0 + 0.1 * rng.standard_normal(shape)
        elif fill == "row_index":
            # distinct ascending rows of the first operand, three beyond it
            n = int(case.specs[0][0][0])
            a = np.sort(rng.choice(n, size=shape, replace=False))
            a[-3:] = n + np.arange(3)
        else:
            raise ValueError(f"unknown fill {fill!r}")
        out.append(jnp.asarray(a, dtype=dtype))
    return out


def all_finite(tree) -> bool:
    return all(
        bool(np.all(np.isfinite(np.asarray(leaf, np.float32))))
        for leaf in jax.tree_util.tree_leaves(tree)
    )


def max_rel_err(got, want) -> float:
    """max over output leaves of max|got - want| / max|want| (fp32);
    inf when the kernel produced a non-finite value."""
    if not all_finite(got):
        return float("inf")
    return max(
        float(np.max(np.abs(np.asarray(g, np.float32) - w))
              / (np.max(np.abs(w)) + 1e-6))
        for g, w in zip(
            jax.tree_util.tree_leaves(got),
            (np.asarray(w, np.float32)
             for w in jax.tree_util.tree_leaves(want)),
        )
    )


def _weighted(fn, argnums):
    """grad of <fn(...), cot> — a random cotangent, so no gradient term
    cancels the way sum(out) lets softmax rows cancel."""
    def run(cot, *args):
        def scalar(*a):
            return jnp.sum(fn(*a).astype(_F32) * cot.astype(_F32))

        return jax.grad(scalar, argnums=argnums)(*args)

    return run


def _attention_cases(B, H, L, D):
    from unicore_tpu.ops.attention_fullrow import fullrow_attention
    from unicore_tpu.ops.flash_attention import flash_attention, mha_reference

    scale = D ** -0.5
    qkv = lambda dt: (((B, H, L, D), dt, "normal"),) * 3
    cot = lambda dt: (((B, H, L, D), dt, "normal"),)
    extras = lambda dt: (((1, H, L, L), dt, "normal"),
                         ((B, L), _I32, "pad_tail"))

    def ref(q, k, v, bias=None, mask=None):
        return mha_reference(q, k, v, bias=bias, kv_padding_mask=mask,
                             sm_scale=scale)

    def flash(rate):
        def f(q, k, v, bias=None, mask=None):
            return flash_attention(
                q, k, v, bias=bias, kv_padding_mask=mask,
                dropout_rate=rate, dropout_seed=7, sm_scale=scale,
            )
        return f

    def fullrow(rate):
        def f(q, k, v, bias=None, mask=None):
            return fullrow_attention(
                q, k, v, bias=bias, kv_padding_mask=mask,
                dropout_rate=rate, dropout_seed=11, sm_scale=scale,
            )
        return f

    g3, g4 = (0, 1, 2), (0, 1, 2, 3)
    return [
        KernelCase("flash-fwd-bf16", qkv(_BF16), flash(0.0), ref, 2e-2),
        KernelCase("flash-fwd-bwd-bf16", cot(_BF16) + qkv(_BF16),
                   _weighted(flash(0.0), g3), _weighted(ref, g3), 3e-2),
        KernelCase("flash-fwd-bwd-bias-mask-bf16",
                   cot(_BF16) + qkv(_BF16) + extras(_BF16),
                   _weighted(flash(0.0), g4), _weighted(ref, g4), 3e-2),
        KernelCase("flash-fwd-bwd-dropout-bias-mask-bf16",
                   cot(_BF16) + qkv(_BF16) + extras(_BF16),
                   _weighted(flash(0.1), g4), None, 0.0, tpu_prng=True),
        KernelCase("fullrow-fwd-bwd-bias-mask-f32",
                   cot(_F32) + qkv(_F32) + extras(_F32),
                   _weighted(fullrow(0.0), g4), _weighted(ref, g4), 2e-2),
        KernelCase("fullrow-fwd-bwd-bias-mask-bf16",
                   cot(_BF16) + qkv(_BF16) + extras(_BF16),
                   _weighted(fullrow(0.0), g4), _weighted(ref, g4), 3e-2),
        KernelCase("fullrow-fwd-bwd-dropout-bias-mask-bf16",
                   cot(_BF16) + qkv(_BF16) + extras(_BF16),
                   _weighted(fullrow(0.1), g4), None, 0.0, tpu_prng=True),
    ]


def _softmax_cases(B, H, L):
    from unicore_tpu.ops.quant_softmax_dropout import (
        quant_softmax_dropout_reference,
    )
    from unicore_tpu.ops.softmax_dropout import softmax_dropout_reference
    from unicore_tpu.ops.softmax_dropout_pallas import (
        quant_softmax_dropout_pallas,
        softmax_dropout_pallas,
    )

    x = lambda dt: (((B, H, L, L), dt, "normal"),)
    extras = lambda dt: (((1, H, L, L), dt, "normal"),
                         ((B, 1, 1, L), dt, "neg_tail"))

    def fused(rate):
        def f(x, bias=None, mask=None):
            return softmax_dropout_pallas(
                x, rate, is_training=True, mask=mask, bias=bias, seed=11
            )
        return f

    def ref(x, bias=None, mask=None):
        return softmax_dropout_reference(
            x, 0.0, is_training=True, mask=mask, bias=bias
        )

    return [
        KernelCase("softmax-dropout-fwd-bwd-f32", x(_F32) + x(_F32),
                   _weighted(fused(0.0), (0,)), _weighted(ref, (0,)), 1e-4),
        KernelCase("softmax-dropout-fwd-bwd-bias-mask-bf16",
                   x(_BF16) + x(_BF16) + extras(_BF16),
                   _weighted(fused(0.0), (0, 1)), _weighted(ref, (0, 1)),
                   3e-2),
        KernelCase("softmax-dropout-fwd-bwd-dropout-bias-mask-f32",
                   x(_F32) + x(_F32) + extras(_F32),
                   _weighted(fused(0.1), (0, 1)), None, 0.0),
        KernelCase(
            "quant-softmax-dropout",
            (((B, H, L, L), _I8, "int8"), ((B, 1, 1, L), _F32, "neg_tail")),
            lambda xq, m: quant_softmax_dropout_pallas(xq, 0.04, 0.0, mask=m),
            lambda xq, m: quant_softmax_dropout_reference(
                xq, 0.04, 0.0, mask=m),
            1e-4,
        ),
    ]


def _norm_cases(B, L, dims):
    from unicore_tpu.modules.layer_norm import LayerNorm, RMSNorm
    from unicore_tpu.ops.fused_norm import (
        fused_layer_norm,
        fused_rms_norm,
        quant_layer_norm_pallas,
    )
    from unicore_tpu.ops.quant_norm import quant_layer_norm_reference

    def specs(dt, dim):
        return (((B, L, dim), dt, "normal"), ((B, L, dim), dt, "normal"),
                ((dim,), _F32, "affine"), ((dim,), _F32, "affine"))

    def ln_ref(dim):
        mod = LayerNorm(dim, use_pallas=False)
        return lambda x, w, b: mod.apply(
            {"params": {"weight": w, "bias": b}}, x)

    def rms_ref(dim):
        mod = RMSNorm(dim, use_pallas=False)
        return lambda x, w, b: mod.apply({"params": {"weight": w}}, x)

    rms = lambda x, w, b: fused_rms_norm(x, w)
    d0, d1 = dims
    return [
        KernelCase(f"layer-norm-fwd-bwd-f32-{d0}", specs(_F32, d0),
                   _weighted(fused_layer_norm, (0, 1, 2)),
                   _weighted(ln_ref(d0), (0, 1, 2)), 1e-4),
        KernelCase(f"layer-norm-fwd-bwd-bf16-{d1}", specs(_BF16, d1),
                   _weighted(fused_layer_norm, (0, 1, 2)),
                   _weighted(ln_ref(d1), (0, 1, 2)), 3e-2),
        KernelCase(f"rms-norm-fwd-bwd-f32-{d1}", specs(_F32, d1),
                   _weighted(rms, (0, 1)), _weighted(rms_ref(d1), (0, 1)),
                   1e-4),
        KernelCase(f"rms-norm-fwd-bwd-bf16-{d0}", specs(_BF16, d0),
                   _weighted(rms, (0, 1)), _weighted(rms_ref(d0), (0, 1)),
                   3e-2),
        KernelCase(
            "quant-layer-norm",
            (((B * L, d0), _I8, "int8"), ((d0,), _F32, "affine"),
             ((d0,), _F32, "affine")),
            lambda xq, w, b: quant_layer_norm_pallas(xq, 0.05, w, b),
            lambda xq, w, b: quant_layer_norm_reference(xq, 0.05, w, b),
            1e-4,
        ),
    ]


def _decode_cases(B, H, L, D):
    from unicore_tpu.ops.decode_attention import (
        _decode_pallas,
        decode_attention_reference,
    )

    def case(name, cache_dt, tol):
        quant = cache_dt == _I8
        fill = "int8" if quant else "normal"
        specs = (((B, H, D), _F32, "normal"),
                 ((B, H, L, D), cache_dt, fill),
                 ((B, H, L, D), cache_dt, fill), ((B,), _I32, "positions"))
        if quant:
            specs += (((H, D), _F32, "scale"),) * 2

        # the kernel's contract is a PRE-SCALED query (the module scales
        # q by D^-0.5 before the cache read)
        def kernel(q, k, v, pos, ks=None, vs=None):
            return _decode_pallas(q * D ** -0.5, k, v, pos, None, ks, vs)

        def oracle(q, k, v, pos, ks=None, vs=None):
            return decode_attention_reference(
                q * D ** -0.5, k, v, pos, k_scale=ks, v_scale=vs)

        return KernelCase(name, specs, kernel, oracle, tol)

    return [case("decode-f32-cache", _F32, 2e-2),
            case("decode-bf16-cache", _BF16, 2e-2),
            case("decode-int8-cache", _I8, 2e-2)]


def _quant_matmul_cases(shapes):
    from unicore_tpu.ops.quant_matmul import (
        quant_matmul_pallas,
        quant_matmul_reference,
    )

    cases = []
    for m, k, n in shapes:
        for act in ("", "gelu"):
            specs = (((m, k), _I8, "int8"), ((k, n), _I8, "int8"),
                     ((n,), _F32, "mm_scale"), ((n,), _F32, "normal"))
            cases.append(KernelCase(
                f"quant-matmul-{act or 'linear'}-{m}x{k}x{n}", specs,
                lambda x, w, s, b, act=act: quant_matmul_pallas(
                    x, w, s, bias=b, activation=act),
                lambda x, w, s, b, act=act: quant_matmul_reference(
                    x, w, s, bias=b, activation=act),
                1e-4,
            ))
    return cases


def _rows_add_cases(shapes):
    from unicore_tpu.ops.rows_add import add_rows_at, close_rows, open_rows

    def kernel(acc, index, rows):
        return close_rows(add_rows_at(open_rows(acc), index, rows))

    def oracle(acc, index, rows):
        return acc.at[index].add(rows, mode="drop")

    return [
        KernelCase(
            f"moe-rows-add-{count}x{width}",
            (((n, width), _F32, "normal"), ((count,), _I32, "row_index"),
             ((count, width), _F32, "normal")),
            kernel, oracle, 1e-6)
        for n, count, width in shapes
    ]


def kernel_cases(B=8, H=12, L=512, D=64, dims=(768, 1024),
                 matmul_shapes=((512, 768, 3072), (512, 4096, 4096)),
                 rows_add_shapes=((8192, 1024, 2304), (8192, 1024, 3072))):
    """The table.  Defaults are the real widths: BERT-base attention (bf16,
    batch 8, 12 heads, seq 512, head 64), norm dims 768/1024, the BERT FFN
    768 -> 3072 and the 4096^2 serving audit shape, a wide trip's 1,024
    rows of the gated experts at Mellum2's and Laguna's widths.  The CPU
    rehearsal of ``chip_smoke.py`` passes small ones."""
    return (
        _attention_cases(B, H, L, D)
        + _softmax_cases(B, H, L)
        + _norm_cases(B, L, dims)
        + _decode_cases(B, H, L, D)
        + _quant_matmul_cases(matmul_shapes)
        + _rows_add_cases(rows_add_shapes)
    )
