"""Single-query cache-reading attention — the decode step's kernel.

Incremental decode (docs/serving.md, "Incremental decode") attends ONE
query row per sequence against that sequence's K/V cache: q is
``(B, H, D)``, the gathered caches are ``(B, H, L, D)`` where ``L`` is
the cache-length bucket, and ``positions[b]`` names the current token's
row — rows beyond it are dead (pad junk or not-yet-written pages) and
mask out additively.  The int8-KV variant takes the caches quantized
(PR-12 ``quantize_to_dtype`` against static per-(head, channel) scales)
and fuses the dequant multiply into the attention read — the fp32 cache
is never materialized between HBM and the score matmul, the same
operation-fusion discipline as ``quant_softmax_dropout`` (arXiv
2502.17728; the fusion audit checks the compiled decode program).

Same dispatch contract as every gated kernel in ops/: mode ``auto`` is
Pallas on a real TPU backend when the geometry allows, jnp elsewhere;
``on`` forces Pallas wherever the geometry allows (parity tests run it
under interpret mode on CPU); ``off`` is always the jnp composition.
Set via :func:`set_decode_attention_mode` or the
``UNICORE_TPU_PALLAS_DECODE_ATTENTION`` env var.  Forward-only by
design — the cache read path never trains.
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._pallas import (
    KernelGeometryError,
    ModeGate,
    audit_case,
    check_vmem_budget,
    pallas_call as _pallas_call,
    sublane_multiple,
)

from unicore_tpu.platform_utils import on_tpu

#: finite stand-in for -inf: keeps masked rows NaN-free through softmax
#: (same constant family as flash_attention.NEG_INF / the decoder's
#: causal triu)
_NEG = -1e30

_gate = ModeGate("decode_attention", "UNICORE_TPU_PALLAS_DECODE_ATTENTION")


def set_decode_attention_mode(mode: Optional[str]):
    """Select the dispatch mode (``auto``/``on``/``off``; None = auto)."""
    _gate.set(mode)


_resolved_mode = _gate.resolved


# ---------------------------------------------------------------------------
# jnp composition — the oracle and the universal fallback
# ---------------------------------------------------------------------------

def decode_attention_reference(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    positions: jnp.ndarray,
    bias: Optional[jnp.ndarray] = None,
    k_scale: Optional[jnp.ndarray] = None,
    v_scale: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """jnp oracle: dequant (int8 caches) + fp32 row softmax over the live
    cache prefix.  XLA fuses the convert+multiply into the score/output
    matmuls (the fusion audit's dequant section proves it); the Pallas
    path makes the same fusion explicit."""
    L = k_cache.shape[2]
    kf = k_cache.astype(jnp.float32)
    vf = v_cache.astype(jnp.float32)
    if k_scale is not None:
        kf = kf * k_scale.astype(jnp.float32)[None, :, None, :]
    if v_scale is not None:
        vf = vf * v_scale.astype(jnp.float32)[None, :, None, :]
    s = jnp.einsum("bhd,bhld->bhl", q.astype(jnp.float32), kf)
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    dead = jnp.arange(L, dtype=jnp.int32)[None, None, :] > \
        positions.astype(jnp.int32)[:, None, None]
    s = jnp.where(dead, _NEG, s)
    # the query's own row is always live (positions[b] points at it), so
    # no fully-masked-row guard is needed
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhl,bhld->bhd", p, vf)
    return o.astype(q.dtype)


# ---------------------------------------------------------------------------
# Pallas kernel: grid (B, H), the whole cache row resident per program
# ---------------------------------------------------------------------------

def _decode_kernel(
    pos_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, bias_ref, o_ref,
    *, L, quant, has_bias,
):
    b = pl.program_id(0)
    q = q_ref[0, 0].astype(jnp.float32)  # (1, D)
    k = k_ref[0, 0].astype(jnp.float32)  # (L, D)
    v = v_ref[0, 0].astype(jnp.float32)
    if quant:
        k = k * ks_ref[0].astype(jnp.float32)  # (1, D) broadcast
        v = v * vs_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (1, L)
    if has_bias:
        s = s + bias_ref[0, 0].astype(jnp.float32)
    idx = jax.lax.broadcasted_iota(jnp.int32, (1, L), 1)
    s = jnp.where(idx > pos_ref[b], _NEG, s)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    o = jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (1, D)
    o_ref[0, 0] = o.astype(o_ref.dtype)


def _decode_pallas(q, k_cache, v_cache, positions, bias, k_scale, v_scale):
    B, H, L, D = k_cache.shape
    quant = k_scale is not None
    has_bias = bias is not None

    q4 = q[:, :, None, :]  # (B, H, 1, D)
    in_specs = [
        pl.BlockSpec((1, 1, 1, D), lambda b, h, *_: (b, h, 0, 0)),  # q
        pl.BlockSpec((1, 1, L, D), lambda b, h, *_: (b, h, 0, 0)),  # k
        pl.BlockSpec((1, 1, L, D), lambda b, h, *_: (b, h, 0, 0)),  # v
    ]
    inputs = [q4, k_cache, v_cache]
    if quant:
        # scales ride as (H, 1, D) with block (1, 1, D): Mosaic needs a
        # block's last two dims on the (8, 128) tiling OR equal to the
        # array's — a (1, D) block over (H, D) is neither
        in_specs += [
            pl.BlockSpec((1, 1, D), lambda b, h, *_: (h, 0, 0)),  # k_scale
            pl.BlockSpec((1, 1, D), lambda b, h, *_: (h, 0, 0)),  # v_scale
        ]
        inputs += [k_scale[:, None, :], v_scale[:, None, :]]
    if has_bias:
        in_specs.append(pl.BlockSpec((1, 1, 1, L), lambda b, h, *_: (b, h, 0, 0)))
        inputs.append(bias[:, :, None, :])

    kernel = functools.partial(
        _decode_kernel, L=L, quant=quant, has_bias=has_bias,
    )

    def wrapped(pos_ref, *refs):
        i = 3
        ks_ref = refs[i] if quant else None
        vs_ref = refs[i + 1] if quant else None
        i += 2 * int(quant)
        bias_ref = refs[i] if has_bias else None
        i += int(has_bias)
        kernel(pos_ref, refs[0], refs[1], refs[2], ks_ref, vs_ref,
               bias_ref, refs[i])

    out = _pallas_call(
        wrapped,
        name="decode_attn",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, 1, D), lambda b, h, *_: (b, h, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, 1, D), q.dtype),
    )(positions.astype(jnp.int32), *inputs)
    return out[:, :, 0, :]


def _pallas_eligible(q, k_cache, bias, k_scale) -> bool:
    mode = _resolved_mode()
    if mode == "off":
        return False
    if mode == "auto" and not on_tpu():
        return False
    B, H, L, D = k_cache.shape
    if q.dtype not in (jnp.float32, jnp.bfloat16):
        return False
    # the cache row loads whole: its sublane extent must land on the
    # cache dtype's native tile (8 fp32 / 16 bf16 / 32 int8) — decode
    # bucket edges are rounded to 32 (serve/kv_cache.py) so real caches
    # always pass; odd test shapes fall back to the oracle
    if L % sublane_multiple(k_cache.dtype) != 0:
        return False
    try:
        io = [((1, 1, 1, D), q.dtype),
              ((1, 1, L, D), k_cache.dtype), ((1, 1, L, D), k_cache.dtype)]
        if k_scale is not None:
            io += [((1, 1, D), jnp.float32)] * 2
        if bias is not None:
            io.append(((1, 1, 1, L), bias.dtype))
        io.append(((1, 1, 1, D), q.dtype))
        check_vmem_budget("decode_attention", io)
    except KernelGeometryError:
        return False
    return True


def decode_attention(
    q: jnp.ndarray,
    k_cache: jnp.ndarray,
    v_cache: jnp.ndarray,
    positions: jnp.ndarray,
    bias: Optional[jnp.ndarray] = None,
    k_scale: Optional[jnp.ndarray] = None,
    v_scale: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """One decode step of attention: ``softmax(q k^T + bias, live-mask) v``
    with ``q`` (B, H, D) pre-scaled, caches (B, H, L, D), and
    ``positions`` (B,) int32 naming each row's current token — cache rows
    beyond it are masked out (they hold pad junk or unwritten pages).

    ``k_scale``/``v_scale`` (H, D): static per-(head, channel) dequant
    scales for int8 caches; the dequant multiply fuses into the read.
    Scales must come paired with int8 caches and vice versa.
    """
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be passed together")
    if (k_cache.dtype == jnp.int8) != (k_scale is not None):
        raise ValueError(
            f"int8 caches need dequant scales (cache dtype "
            f"{k_cache.dtype}, k_scale {'set' if k_scale is not None else 'None'})"
        )
    if _pallas_eligible(q, k_cache, bias, k_scale):
        return _decode_pallas(
            q, k_cache, v_cache, positions, bias, k_scale, v_scale
        )
    return decode_attention_reference(
        q, k_cache, v_cache, positions, bias=bias,
        k_scale=k_scale, v_scale=v_scale,
    )


# ---------------------------------------------------------------------------
# representative audit shapes (unicore-tpu-lint --kernels; docs/lint.md)
# ---------------------------------------------------------------------------

@audit_case("decode-attention-fp32")
def _audit_decode_fp32():
    """Serving geometry: cache bucket 256 (an 8-row fp32 tile multiple),
    rel-pos bias row present, mixed positions so the live-mask iota is
    exercised across the grid."""
    B, H, L, D = 4, 4, 256, 64
    q = jnp.zeros((B, H, D), jnp.float32)
    cache = jnp.zeros((B, H, L, D), jnp.float32)
    bias = jnp.zeros((B, H, L), jnp.float32)
    pos = jnp.arange(B, dtype=jnp.int32) * 7
    return decode_attention(q, cache, cache, pos, bias=bias)


@audit_case("decode-attention-int8-kv")
def _audit_decode_int8():
    """int8-KV geometry: cache bucket 256 is a 32-row int8 tile multiple;
    per-(head, channel) dequant scales ride as (1, 1, D) blocks of an
    (H, 1, D) array."""
    B, H, L, D = 4, 4, 256, 64
    q = jnp.zeros((B, H, D), jnp.float32)
    cache = jnp.zeros((B, H, L, D), jnp.int8)
    scale = jnp.ones((H, D), jnp.float32)
    pos = jnp.full((B,), L - 1, jnp.int32)
    return decode_attention(q, cache, cache, pos, k_scale=scale,
                            v_scale=scale)
