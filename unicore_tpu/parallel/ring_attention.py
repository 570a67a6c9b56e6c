"""Ring attention over the mesh 'seq' axis — long-context sequence/context
parallelism (SURVEY.md §5.7: absent from the reference; first-class here).

Each device holds a sequence chunk of q/k/v.  K/V chunks rotate around the
ring via ``ppermute`` over ICI while every device accumulates its local
queries' attention online (flash-style running max/sum), so the full L x L
attention is computed with O(L/n) activation memory per device and
communication overlapped with compute by XLA's collective scheduler.

Additive biases (e.g. relative-position) are STATIONARY: each device holds
its own query rows of the (H, L, L) bias and slices the key columns that
match the k/v chunk currently visiting (derived from the ring step), so the
bias costs zero ICI traffic.

Usage: under ``shard_map`` with the sequence dim sharded over ``axis_name``,
or through :func:`ring_self_attention`, which wraps the shard_map given a
mesh.  Numerically equivalent to full softmax attention (see
tests/test_ring_attention.py, incl. gradients).
"""

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from unicore_tpu.platform_utils import on_tpu

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Pallas-blocked ring: each ring step runs the flash-attention kernel on the
# visiting chunk (per-chunk compute is MXU-blocked and never materializes the
# (Lc, Lc) score matrix in HBM), and the chunk results combine by logsumexp.
# The backward is a second ring calling the flash backward kernels per chunk:
# dq and dbias stay stationary; dk/dv ride WITH their k/v chunk and arrive
# home after a full cycle.
# ---------------------------------------------------------------------------


def pallas_ring_supported(Lc, head_dim, dtype):
    """Chunk shapes the flash kernels accept.  Unlike the module router's
    _flash_ok (which since round 4 PADS non-128-multiple lengths), the
    ring performs no padding — chunks rotate between devices, so padded
    columns would need masking on every visit — and keeps the strict
    Lc % 128 == 0 requirement; unaligned chunks use the jnp ring path."""
    from unicore_tpu.ops._pallas import interpret_enabled

    kernel_ok = on_tpu() or interpret_enabled()
    return (
        kernel_ok
        and Lc % 128 == 0
        and head_dim % 8 == 0
        and dtype in (jnp.float32, jnp.bfloat16)
    )


def _chunk_seed(seed, my_idx, src, n, dropout_rate):
    """Dropout stream id for the (query-chunk my_idx, key-chunk src) pair —
    a function of GLOBAL chunk identities, so the backward ring regenerates
    the identical in-kernel masks regardless of visit order.

    Without dropout the kernels never read the seed, so a constant is
    passed instead: the axis_index-derived value would otherwise ride the
    scalar-prefetch operand into XLA's SPMD partitioner, which rejects the
    resulting PartitionId instruction ("meaning is ambiguous") when the
    seed is the only axis_index consumer (the bias-free jit path)."""
    if dropout_rate <= 0.0:
        return jnp.zeros((1,), jnp.int32)
    return jnp.reshape(
        seed * jnp.int32(7919)
        + my_idx.astype(jnp.int32) * jnp.int32(n)
        + src.astype(jnp.int32),
        (1,),
    )


def _bias_cols(bias, src, Lc):
    """Stationary-bias slice for the visiting chunk: this device's query
    rows x the chunk's key columns, as the kernels' (1, Hb, Lc, Lc)."""
    cols = jax.lax.dynamic_slice_in_dim(bias, src * Lc, Lc, axis=2)
    return cols[None]


def _ring_flash_fwd_impl(axis_name, sm_scale, dropout_rate, q, k, v, kv_mask,
                         bias, seed):
    from unicore_tpu.ops import flash_attention as fa

    n = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    B, H, Lc, D = q.shape
    perm = [(i, (i + 1) % n) for i in range(n)]

    # accumulators derive from q so they inherit its device-varying axes
    zero = q.astype(jnp.float32) * 0.0
    m0 = zero[..., :1] + NEG_INF
    l0 = zero[..., :1]
    acc0 = zero

    def accumulate(k_blk, v_blk, mask_blk, t, m, l, acc):
        src = jnp.mod(my - t, n)
        bias4 = None if bias is None else _bias_cols(bias, src, Lc)
        mask3 = mask_blk.astype(jnp.int32)[:, None, :]
        o_t, lse_t = fa._fwd(
            q, k_blk, v_blk, bias4, mask3,
            _chunk_seed(seed, my, src, n, dropout_rate),
            sm_scale, dropout_rate, 256, 512,
        )
        # logsumexp combine of per-chunk results: exp(lse_t - m) * o_t is
        # the chunk's unnormalized contribution (o_t is chunk-normalized)
        m_new = jnp.maximum(m, lse_t)
        w_prev = jnp.exp(m - m_new)
        w_t = jnp.exp(lse_t - m_new)
        acc_new = acc * w_prev + w_t * o_t.astype(jnp.float32)
        l_new = l * w_prev + w_t
        return m_new, l_new, acc_new

    def step(carry, t):
        k_blk, v_blk, mask_blk, m, l, acc = carry
        m, l, acc = accumulate(k_blk, v_blk, mask_blk, t, m, l, acc)
        k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
        v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
        mask_blk = jax.lax.ppermute(mask_blk, axis_name, perm)
        return (k_blk, v_blk, mask_blk, m, l, acc), None

    (k_l, v_l, mask_l, m, l, acc), _ = jax.lax.scan(
        step, (k, v, kv_mask, m0, l0, acc0),
        jnp.arange(n - 1, dtype=jnp.int32),
    )
    m, l, acc = accumulate(k_l, v_l, mask_l, jnp.int32(n - 1), m, l, acc)
    inv_l = jnp.where(l > 0, 1.0 / l, 0.0)
    out = (acc * inv_l).astype(q.dtype)
    lse = m + jnp.log(jnp.maximum(l, 1e-37))
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _ring_flash(axis_name, sm_scale, dropout_rate, q, k, v, kv_mask, bias,
                seed):
    out, _ = _ring_flash_fwd_impl(
        axis_name, sm_scale, dropout_rate, q, k, v, kv_mask, bias, seed
    )
    return out


def _ring_flash_fwd(axis_name, sm_scale, dropout_rate, q, k, v, kv_mask, bias,
                    seed):
    out, lse = _ring_flash_fwd_impl(
        axis_name, sm_scale, dropout_rate, q, k, v, kv_mask, bias, seed
    )
    return out, (q, k, v, kv_mask, bias, seed, out, lse)


def _ring_flash_bwd(axis_name, sm_scale, dropout_rate, res, do):
    from unicore_tpu.ops import flash_attention as fa

    q, k, v, kv_mask, bias, seed, out, lse = res
    n = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    B, H, Lc, D = q.shape
    perm = [(i, (i + 1) % n) for i in range(n)]

    dq0 = q.astype(jnp.float32) * 0.0
    dk0 = k.astype(jnp.float32) * 0.0
    dv0 = v.astype(jnp.float32) * 0.0
    has_bias = bias is not None
    dbias0 = None if not has_bias else bias.astype(jnp.float32) * 0.0

    def step(carry, t):
        k_blk, v_blk, mask_blk, dk_blk, dv_blk, dq, dbias = carry
        src = jnp.mod(my - t, n)
        bias4 = None if bias is None else _bias_cols(bias, src, Lc)
        mask3 = mask_blk.astype(jnp.int32)[:, None, :]
        # global lse/out/do make the recomputed p the GLOBAL probabilities
        # restricted to this chunk's columns, so each chunk's contribution
        # is exact — no cross-chunk correction needed
        dq_c, dk_c, dv_c, db_c = fa._bwd(
            q, k_blk, v_blk, bias4, mask3,
            _chunk_seed(seed, my, src, n, dropout_rate),
            sm_scale, dropout_rate, 256, 512, out, lse, do,
        )
        dq = dq + dq_c.astype(jnp.float32)
        dk_blk = dk_blk + dk_c.astype(jnp.float32)
        dv_blk = dv_blk + dv_c.astype(jnp.float32)
        if has_bias:
            cur = jax.lax.dynamic_slice_in_dim(dbias, src * Lc, Lc, axis=2)
            dbias = jax.lax.dynamic_update_slice_in_dim(
                dbias, cur + db_c[0].astype(jnp.float32), src * Lc, axis=2
            )
        # dk/dv travel WITH their chunk: after the full cycle of n
        # rotations every chunk's gradient is complete and back home
        rotated = [
            jax.lax.ppermute(x, axis_name, perm)
            for x in (k_blk, v_blk, mask_blk, dk_blk, dv_blk)
        ]
        return (*rotated, dq, dbias), None

    (k_l, v_l, mask_l, dk, dv, dq, dbias), _ = jax.lax.scan(
        step, (k, v, kv_mask, dk0, dv0, dq0, dbias0),
        jnp.arange(n, dtype=jnp.int32),
    )
    return (
        dq.astype(q.dtype),
        dk.astype(k.dtype),
        dv.astype(v.dtype),
        None,  # kv_mask
        None if not has_bias else dbias.astype(bias.dtype),
        None,  # seed
    )


_ring_flash.defvjp(_ring_flash_fwd, _ring_flash_bwd)


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    axis_name: str,
    kv_mask: Optional[jnp.ndarray] = None,
    bias: Optional[jnp.ndarray] = None,
    sm_scale: float = 1.0,
    dropout_rate: float = 0.0,
    dropout_rng: Optional[jax.Array] = None,
    extra_rng_axes: tuple = (),
    use_pallas: Optional[bool] = None,
) -> jnp.ndarray:
    """Online-softmax attention with a ring exchange of k/v chunks.

    Args (all per-device chunks, inside shard_map):
        q, k, v: (B, H, Lc, D) — Lc = L / ring_size
        kv_mask: (B, Lc) nonzero = masked out (this device's key chunk)
        bias: (Hb, Lc, L) — THIS device's query rows over ALL key columns
            (Hb in {1, H}); stationary, zero communication
        sm_scale: applied to q @ k^T
        dropout_rate/dropout_rng: attention dropout on the probabilities;
            the key is folded per (device, ring step) so every block gets a
            decorrelated stream (normalization uses pre-dropout mass, same
            semantics as ops.softmax_dropout)
    Returns: (B, H, Lc, D) attention output for the local queries.
    """
    n = jax.lax.psum(1, axis_name)
    B, H, Lc, D = q.shape
    my_idx = jax.lax.axis_index(axis_name)

    if use_pallas is None:
        # in-kernel dropout uses TPU-only PRNG primitives (same gate as the
        # flash module path) — interpret mode can't run them with dropout
        dropout_backend_ok = dropout_rate == 0.0 or on_tpu()
        use_pallas = dropout_backend_ok and pallas_ring_supported(
            Lc, D, q.dtype
        )
    if use_pallas:
        # flash-blocked inner step (round-1 verdict item 7): per-chunk
        # compute runs the Pallas kernels; the jnp path below stays as the
        # fallback for unaligned chunks / non-TPU backends
        if bias is not None:
            assert (
                bias.ndim == 3 and bias.shape[1] == Lc
                and bias.shape[2] == n * Lc
            ), f"bias chunk must be (H|1, {Lc}, {n * Lc}), got {bias.shape}"
        seed = jnp.int32(0)
        if dropout_rate > 0.0:
            assert dropout_rng is not None, "dropout needs dropout_rng"
            seed = jax.random.randint(
                dropout_rng, (), 0, 2 ** 31 - 1, dtype=jnp.int32
            )
        for ax in extra_rng_axes:
            seed = seed * jnp.int32(65599) + jax.lax.axis_index(ax).astype(
                jnp.int32
            ) + jnp.int32(1)
        mask = (
            jnp.zeros((B, k.shape[2]), jnp.int32)
            if kv_mask is None
            else kv_mask.astype(jnp.int32)
        )
        return _ring_flash(
            axis_name, sm_scale, dropout_rate, q, k, v, mask, bias, seed
        )

    if dropout_rate > 0.0:
        assert dropout_rng is not None, "dropout needs dropout_rng"
        dropout_rng = jax.random.fold_in(dropout_rng, my_idx)
        # decorrelate across every other sharded mesh axis too (data shards
        # would otherwise reuse identical masks for their batch slices)
        for ax in extra_rng_axes:
            dropout_rng = jax.random.fold_in(
                dropout_rng, jax.lax.axis_index(ax)
            )
    if bias is not None:
        assert bias.ndim == 3 and bias.shape[1] == Lc and bias.shape[2] == n * Lc, (
            f"bias chunk must be (H|1, {Lc}, {n * Lc}), got {bias.shape}"
        )

    # derive the accumulators from q so they inherit its device-varying axes
    # (whatever mesh axes the enclosing shard_map shards over) — the scan
    # carry types must match the sharded-input-derived outputs
    zero_like_q = q.astype(jnp.float32) * 0.0
    m0 = zero_like_q[..., :1] + NEG_INF
    l0 = zero_like_q[..., :1]
    acc0 = zero_like_q
    if kv_mask is None:
        kv_mask = jnp.zeros((B, k.shape[2]), jnp.int32)

    perm = [(i, (i + 1) % n) for i in range(n)]

    def accumulate(k_blk, v_blk, mask_blk, step_t, m, l, acc):
        s = jnp.einsum(
            "bhqd,bhkd->bhqk", q.astype(jnp.float32), k_blk.astype(jnp.float32)
        ) * sm_scale
        if bias is not None:
            # after t rotations this device holds the chunk that STARTED at
            # ring position (my_idx - t) mod n, i.e. key columns
            # [(my_idx - t) mod n * Lc, ...): slice the stationary bias there
            src = jnp.mod(my_idx - step_t, n)
            cols = jax.lax.dynamic_slice_in_dim(bias, src * Lc, Lc, axis=2)
            s = s + cols[None].astype(jnp.float32)
        masked = mask_blk[:, None, None, :] != 0
        s = jnp.where(masked, NEG_INF, s)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, m_cur)
        p = jnp.exp(s - m_new)
        p = jnp.where(masked, 0.0, p)
        corr = jnp.exp(m - m_new)
        l_new = corr * l + jnp.sum(p, axis=-1, keepdims=True)
        p_use = p
        if dropout_rate > 0.0:
            key = jax.random.fold_in(dropout_rng, step_t)
            keep = jax.random.bernoulli(key, 1.0 - dropout_rate, p.shape)
            p_use = jnp.where(keep, p * (1.0 / (1.0 - dropout_rate)), 0.0)
        acc_new = corr * acc + jnp.einsum(
            "bhqk,bhkd->bhqd", p_use, v_blk.astype(jnp.float32)
        )
        return m_new, l_new, acc_new

    def step(carry, t):
        k_blk, v_blk, mask_blk, m, l, acc = carry
        m, l, acc = accumulate(k_blk, v_blk, mask_blk, t, m, l, acc)
        # rotate k/v/mask to the next device; XLA overlaps this with compute
        k_next = jax.lax.ppermute(k_blk, axis_name, perm)
        v_next = jax.lax.ppermute(v_blk, axis_name, perm)
        mask_next = jax.lax.ppermute(mask_blk, axis_name, perm)
        return (k_next, v_next, mask_next, m, l, acc), None

    # n-1 rotated steps + a final accumulate with no rotation (the result of
    # an n-th ppermute would never be consumed — pure wasted ICI bandwidth)
    (k_l, v_l, mask_l, m, l, acc), _ = jax.lax.scan(
        step, (k, v, kv_mask, m0, l0, acc0),
        jnp.arange(n - 1, dtype=jnp.int32),
    )
    m, l, acc = accumulate(k_l, v_l, mask_l, jnp.int32(n - 1), m, l, acc)
    inv_l = jnp.where(l > 0, 1.0 / l, 0.0)
    return (acc * inv_l).astype(q.dtype)


def ring_self_attention(
    mesh,
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    kv_padding_mask: Optional[jnp.ndarray] = None,
    bias: Optional[jnp.ndarray] = None,
    sm_scale: float = 1.0,
    dropout_rate: float = 0.0,
    dropout_rng: Optional[jnp.ndarray] = None,
    seq_axis: str = "seq",
):
    """Full-array entry point: shards the sequence dim over ``seq_axis`` and
    runs :func:`ring_attention` under shard_map.

    ``bias``: additive (H|1, L, L) bias (e.g. relative-position); sharded by
    QUERY rows (stationary per device, no communication).
    """
    from jax.sharding import PartitionSpec as P

    from .mesh import DATA_AXIS

    L = q.shape[2]
    # batch rides the data axis (when the mesh has one) so data-parallel
    # groups keep their own shards instead of all-gathering the batch
    batch_axis = DATA_AXIS if DATA_AXIS in mesh.shape else None
    qkv_spec = P(batch_axis, None, seq_axis, None)
    mask_spec = P(batch_axis, seq_axis)
    out_spec = qkv_spec

    if kv_padding_mask is None:
        kv_padding_mask = jnp.zeros((q.shape[0], L), jnp.int32)

    in_specs = [qkv_spec, qkv_spec, qkv_spec, mask_spec]
    operands = [q, k, v, kv_padding_mask]
    has_bias = bias is not None
    if has_bias:
        if bias.ndim == 2:
            bias = bias[None]
        assert bias.shape[-2:] == (L, L), (
            f"bias must be (H|1, {L}, {L}), got {bias.shape}"
        )
        in_specs.append(P(None, seq_axis, None))  # query rows sharded
        operands.append(bias)
    if dropout_rate > 0.0:
        assert dropout_rng is not None
        in_specs.append(P())  # replicated base key; folded per device inside
        operands.append(dropout_rng)

    def local_fn(q_, k_, v_, mask_, *rest):
        rest = list(rest)
        bias_ = rest.pop(0) if has_bias else None
        rng_ = rest.pop(0) if dropout_rate > 0.0 else None
        return ring_attention(
            q_, k_, v_, axis_name=seq_axis, kv_mask=mask_,
            bias=bias_, sm_scale=sm_scale,
            dropout_rate=dropout_rate, dropout_rng=rng_,
            extra_rng_axes=(batch_axis,) if batch_axis else (),
        )

    from unicore_tpu.parallel.compat import shard_map

    fn = shard_map(
        local_fn,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=out_spec,
        # pallas_call out_shapes carry no replication/vma annotation, so
        # checking is off on either API generation; replication
        # correctness is covered by the equivalence tests
        check_vma=False,  # lint: jax-version-pinned
    )
    return fn(*operands)
