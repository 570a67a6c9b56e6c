"""All-to-all (Ulysses-style) sequence parallelism over the mesh 'seq' axis.

The second long-context strategy next to ring attention
(parallel/ring_attention.py; SURVEY.md §5.7 — both absent from the
reference).  Instead of rotating k/v chunks around a ring, two all-to-alls
re-shard the SAME tensors from sequence-sharded to head-sharded and back:

    (B, H, L/P, D)  --all_to_all-->  (B, H/P, L, D)
        attention on FULL rows for this rank's head group
    (B, H/P, L, D)  --all_to_all-->  (B, H, L/P, D)

Each device then runs ordinary full-row attention for H/P heads, which
means the existing Pallas kernels run UNCHANGED (no per-chunk logsumexp
merging), and — unlike the ring, whose stationary-bias trick needs a
batch-independent bias — per-batch biases just ride along head-sliced.

Tradeoffs vs the ring (pick with --seq-parallel-impl):
- communication is 4 all-to-alls of the (B, L, D) activations per layer
  (2 fwd + 2 via autodiff) regardless of L, vs the ring's (P-1) k/v chunk
  hops; for moderate L the all-to-all usually wins on ICI,
- parallelism is bounded by the head count (needs H % P == 0), while the
  ring scales with L alone,
- peak activation memory holds full-L rows for H/P heads (the attention
  itself still never materializes L x L when the flash kernel is engaged).
"""

from typing import Optional

import jax
import jax.numpy as jnp

from unicore_tpu.platform_utils import on_tpu

from .mesh import DATA_AXIS, SEQ_AXIS

NEG_INF = -1e30


def ulysses_supported(mesh, bsz, num_heads, tgt_len, src_len,
                      seq_axis: str = SEQ_AXIS) -> bool:
    """Shape gate: a live seq axis whose size divides both the head count
    (the parallelism unit) and the sequence (the input sharding)."""
    if mesh is None or seq_axis not in mesh.shape:
        return False
    p = mesh.shape[seq_axis]
    return (
        p > 1
        and tgt_len == src_len
        and num_heads % p == 0
        and tgt_len % p == 0
    )


def _local_attention(q, k, v, bias, kv_mask, sm_scale, dropout_rate, seed):
    """Full-row attention for this rank's head group: Pallas flash kernel
    when the shapes allow, XLA softmax otherwise (same fallback semantics
    as the module router)."""
    from unicore_tpu.ops.flash_attention import flash_attention
    from unicore_tpu.ops._pallas import interpret_enabled

    B, Hl, L, D = q.shape
    real_tpu = on_tpu()
    kernel_ok = real_tpu or interpret_enabled()
    # in-kernel dropout uses TPU-only PRNG primitives — interpret mode can
    # run the kernel but NOT its dropout (same gate as the module router)
    dropout_backend_ok = dropout_rate == 0.0 or real_tpu
    if (
        kernel_ok
        and dropout_backend_ok
        and L % 128 == 0
        and D % 8 == 0
        and q.dtype in (jnp.float32, jnp.bfloat16)
    ):
        return flash_attention(
            q, k, v,
            bias=bias,
            kv_padding_mask=kv_mask,
            dropout_rate=dropout_rate,
            dropout_seed=seed,
            sm_scale=sm_scale,
        )
    s = jnp.einsum(
        "bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * sm_scale
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    if kv_mask is not None:
        s = jnp.where(kv_mask[:, None, None, :].astype(bool), NEG_INF, s)
    p = jax.nn.softmax(s, axis=-1)
    if kv_mask is not None:
        p = jnp.where(kv_mask[:, None, None, :].astype(bool), 0.0, p)
    if dropout_rate > 0.0:
        keep = jax.random.bernoulli(
            jax.random.PRNGKey(seed), 1.0 - dropout_rate, p.shape
        )
        p = jnp.where(keep, p / (1.0 - dropout_rate), 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(
        q.dtype
    )


def ulysses_self_attention(
    mesh,
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    kv_padding_mask: Optional[jnp.ndarray] = None,
    bias: Optional[jnp.ndarray] = None,
    sm_scale: float = 1.0,
    dropout_rate: float = 0.0,
    dropout_seed=0,
    seq_axis: str = SEQ_AXIS,
):
    """Full-array entry point: q/k/v (B, H, L, D) sharded over ``seq_axis``
    on the L dim (batch rides 'data' when the mesh has it); ``bias`` in the
    min-broadcast layout (1|B, 1|H, L, L), replicated — each rank slices its
    own head group, so per-batch biases are supported (the ring can't).
    """
    from jax.sharding import PartitionSpec as P

    B, H, L, D = q.shape
    p = mesh.shape[seq_axis]
    assert ulysses_supported(mesh, B, H, L, k.shape[2], seq_axis), (
        f"ulysses needs seq axis {p} | heads {H} and | L {L}"
    )
    batch_axis = DATA_AXIS if DATA_AXIS in mesh.shape else None
    qkv_spec = P(batch_axis, None, seq_axis, None)
    has_mask = kv_padding_mask is not None
    has_bias = bias is not None
    seed = jnp.reshape(jnp.asarray(dropout_seed, jnp.int32), ())

    def local(q_l, k_l, v_l, seed_r, *rest):
        i = 0
        mask_l = rest[i] if has_mask else None
        i += int(has_mask)
        bias_f = rest[i] if has_bias else None
        r = jax.lax.axis_index(seq_axis)

        def seq_to_heads(x):  # (B, H, L/P, D) -> (B, H/P, L, D)
            return jax.lax.all_to_all(
                x, seq_axis, split_axis=1, concat_axis=2, tiled=True
            )

        qh, kh, vh = seq_to_heads(q_l), seq_to_heads(k_l), seq_to_heads(v_l)
        mask_full = None
        if mask_l is not None:
            mask_full = jax.lax.all_gather(
                mask_l, seq_axis, axis=1, tiled=True
            )
        bias_l = None
        if bias_f is not None:
            if bias_f.shape[1] == 1:
                bias_l = bias_f
            else:
                hl = bias_f.shape[1] // p
                bias_l = jax.lax.dynamic_slice_in_dim(
                    bias_f, r * hl, hl, axis=1
                )
        # decorrelate the in-kernel dropout across head groups: the kernel
        # keys streams by LOCAL head index, identical on every rank
        seed_local = seed_r + r.astype(jnp.int32) * jnp.int32(7919)
        o = _local_attention(
            qh, kh, vh, bias_l, mask_full, sm_scale, dropout_rate,
            seed_local,
        )
        return jax.lax.all_to_all(  # heads back home, rows re-shard
            o, seq_axis, split_axis=2, concat_axis=1, tiled=True
        )

    in_specs = [qkv_spec, qkv_spec, qkv_spec, P()]
    operands = [q, k, v, seed]
    if has_mask:
        in_specs.append(P(batch_axis, seq_axis))
        operands.append(kv_padding_mask.astype(jnp.int32))
    if has_bias:
        if bias.ndim == 3:
            bias = bias[None]
        assert bias.ndim == 4
        # a real batch dim shards with the batch; broadcast dims replicate
        in_specs.append(
            P(batch_axis if bias.shape[0] != 1 else None, None, None, None)
        )
        operands.append(bias)

    from unicore_tpu.parallel.compat import shard_map

    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=qkv_spec,
        # pallas_call out_shapes carry no replication/vma annotation
        # (same caveat as ring_self_attention); equivalence tests cover it
        check_vma=False,  # lint: jax-version-pinned
    )
    return fn(*operands)
