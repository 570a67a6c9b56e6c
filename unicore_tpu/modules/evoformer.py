"""Evoformer building blocks (BASELINE.json config 4: 'Uni-Fold Evoformer
(MSA row/col attn + triangle multiplication)').

The reference framework serves Uni-Fold as a plugin whose triangle-attention
pattern is exactly what its fused softmax kernel's bias-broadcast mode exists
for (reference tests/test_softmax.py:81-170).  This module family provides
the same computational blocks TPU-natively:

- gated multi-head attention over arbitrary leading batch dims, routed
  through the Pallas flash kernel with GROUPED bias broadcast (bias slab
  per leading group, indexed in-kernel — ops/flash_attention.py); the
  L x L probability matrix then never reaches HBM.  Non-128-multiple L
  rides the kernel via router padding (masked keys, sliced query rows);
  under GSPMD seq sharding the kernel runs per-shard inside a shard_map
  (GatedAttention.seq_dim); the XLA softmax path remains as fallback only
  when padding would waste more compute than the kernel saves;
- MSA row attention with pair bias, MSA column attention;
- outer-product-mean MSA -> pair update;
- triangle multiplication (outgoing/incoming) and triangle attention
  (starting/ending node);
- pair/MSA transitions;
composed into EvoformerIteration / EvoformerStack.

All normalization statistics run fp32 (LayerNorm), matmuls accumulate fp32.
"""

from functools import partial
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from unicore_tpu import utils
from unicore_tpu.ops.softmax_dropout import softmax_dropout
from unicore_tpu.platform_utils import on_tpu
from .layer_norm import LayerNorm
from .multihead_attention import _flash_grouped
from .transformer_encoder import bert_init


class GatedAttention(nn.Module):
    """AF2-style gated MHA: out = Linear(sigmoid(gate) * attn(v)).

    Inputs may have arbitrary leading dims: (*B, Lq, D_q) x (*B, Lk, D_kv).
    ``bias`` is GROUPED over the flattened leading dims: shape
    (G, 1|H, Lq, Lk) with prod(lead) % G == 0 — consecutive runs of
    prod(lead)/G rows (the MSA rows of one sequence, the lead rows of one
    pair matrix) share a bias slab.  ``kv_mask`` (*B, Lk), 1 = valid.

    When shapes allow, the whole attention runs in the Pallas flash kernel
    with the grouped bias indexed in-kernel — the L x L probability matrix
    never reaches HBM (the reference fuses softmax+mask+bias around a
    materialized matrix instead, csrc/softmax_dropout/interface.cpp:37-48).

    Under GSPMD row sharding (EvoformerStack.seq_shard) a bare pallas_call
    can't be auto-partitioned; setting ``seq_dim`` to the q_x dim that is
    row-sharded over the mesh 'seq' axis instead drops into an explicit
    shard_map whose body runs the SAME kernel on each shard's rows (k/v
    gathered by XLA at the shard_map boundary when the attended dim is the
    sharded one), so sequence parallelism keeps the never-materialize
    property instead of surrendering to the O(L^2) XLA path.
    """

    embed_dim: int
    num_heads: int
    gating: bool = True
    # False forces the XLA softmax path (numerics fallback / tests)
    use_flash: bool = True
    # index into q_x's dims that is row-sharded over the mesh 'seq' axis
    # (a lead dim, or ndim-2 for the attended dim); None = unsharded.
    # When the per-shard kernel can't engage (waste gate, dtype, backend),
    # the partitionable XLA path runs — never a bare pallas_call.
    seq_dim: Optional[int] = None

    @nn.compact
    def __call__(
        self,
        q_x,
        kv_x,
        bias: Optional[jnp.ndarray] = None,
        kv_mask: Optional[jnp.ndarray] = None,
    ):
        head_dim = self.embed_dim // self.num_heads
        scale = head_dim ** -0.5
        H = self.num_heads
        if bias is not None and bias.ndim != 4:
            raise ValueError(
                f"GatedAttention bias must be GROUPED 4-d (G, 1|H, Lq, Lk) "
                f"over the flattened leading dims, got shape {bias.shape}; "
                "pre-broadcast layouts (e.g. (B, 1, H, L, L)) were retired "
                "when attention moved into the flash kernel — pass the "
                "group slab and the padding mask (kv_mask=) separately"
            )

        dense = partial(
            nn.Dense, use_bias=False, kernel_init=bert_init,
            dtype=q_x.dtype, param_dtype=jnp.float32,
        )
        q = dense(self.embed_dim, name="q_proj")(q_x) * scale
        k = dense(self.embed_dim, name="k_proj")(kv_x)
        v = dense(self.embed_dim, name="v_proj")(kv_x)

        *lead, Lq, _ = q.shape
        Lk = k.shape[-2]

        def split(t, L):
            return t.reshape(*lead, L, H, head_dim).swapaxes(-2, -3)

        q, k, v = split(q, Lq), split(k, Lk), split(v, Lk)  # (*B, H, L, hd)

        N = 1
        for d in lead:
            N *= d
        o = None
        if self.use_flash and self.seq_dim is not None and _seq_axis_live():
            plan = _seq_flash_plan(
                self.seq_dim, lead, Lq, Lk, head_dim, q.dtype, bias
            )
            if plan is not None:
                kvm = None
                if kv_mask is not None:
                    # kernel semantics: nonzero = masked OUT; flattened
                    # per-shard inside the shard_map body
                    kvm = 1 - kv_mask.astype(jnp.int32)
                _count_route("seq_flash")
                o = _sharded_flash(
                    plan, self.seq_dim, q, k, v, bias, kvm, H, head_dim
                )
        elif self.use_flash and _flash_ok(N, Lq, Lk, head_dim, q.dtype, bias):
            kvm = None
            if kv_mask is not None:
                # kernel semantics: nonzero = masked OUT
                kvm = 1 - kv_mask.reshape(N, Lk).astype(jnp.int32)
            _count_route("flash")
            o = _flash_grouped(
                q.reshape(N, H, Lq, head_dim),
                k.reshape(N, H, Lk, head_dim),
                v.reshape(N, H, Lk, head_dim),
                bias, kvm, Lq, Lk,
            ).reshape(*lead, H, Lq, head_dim)
        if o is None:
            _count_route("xla")
            s = jnp.einsum("...hqd,...hkd->...hqk", q, k)
            if bias is not None:
                G = bias.shape[0]
                b5 = bias[:, None]  # (G, 1, 1|H, Lq, Lk)
                if kv_mask is not None:
                    b5 = b5 + mask_to_bias(kv_mask).reshape(
                        G, N // G, 1, 1, Lk
                    )
                probs = softmax_dropout(
                    s.reshape(G, N // G, H, Lq, Lk), 0.0,
                    is_training=False, bias=b5,
                ).reshape(s.shape)
            elif kv_mask is not None:
                probs = softmax_dropout(
                    s, 0.0, is_training=False,
                    bias=mask_to_bias(kv_mask)[..., None, None, :],
                )
            else:
                probs = softmax_dropout(s, 0.0, is_training=False)
            o = jnp.einsum("...hqk,...hkd->...hqd", probs, v)
        o = o.swapaxes(-2, -3).reshape(*lead, Lq, self.embed_dim)

        if self.gating:
            g = nn.Dense(
                self.embed_dim, use_bias=True, name="gate_proj",
                kernel_init=nn.initializers.zeros,
                bias_init=nn.initializers.ones,
                dtype=q_x.dtype, param_dtype=jnp.float32,
            )(q_x)
            o = jax.nn.sigmoid(g) * o
        o = nn.Dense(
            self.embed_dim, use_bias=True, name="out_proj",
            kernel_init=nn.initializers.zeros,  # AF2 final-init zero
            dtype=q_x.dtype, param_dtype=jnp.float32,
        )(o)
        return o


def mask_to_bias(mask, dtype=jnp.float32):
    """(..., L) 1=valid -> additive (-inf on invalid)."""
    return (mask.astype(jnp.float32) - 1.0) * 1e9


def _flash_ok(N, Lq, Lk, head_dim, dtype, bias):
    """Gate for routing GatedAttention through the Pallas flash kernel:
    TPU (or interpret mode under test), padded-tile waste within budget
    (the caller pads non-128-multiple lengths, masking padded keys and
    slicing padded query rows), and a bias whose group count divides the
    flattened batch.  Dropout never gates — this module family applies
    dropout OUTSIDE attention (AF2 drop_row)."""
    from unicore_tpu.ops._pallas import interpret_enabled

    from .multihead_attention import _flash_pad_waste_ok

    backend_ok = on_tpu() or interpret_enabled()
    return (
        backend_ok
        and _flash_pad_waste_ok(Lq, Lk)
        and head_dim % 8 == 0
        and dtype in (jnp.float32, jnp.bfloat16)
        and (bias is None or N % bias.shape[0] == 0)
    )


# trace-time route counters keyed by 'flash' / 'seq_flash' / 'xla' — tests
# assert the kernel path engages under sharding (clear() between traces)
_ROUTE_STATS = {}


def _count_route(name):
    _ROUTE_STATS[name] = _ROUTE_STATS.get(name, 0) + 1


def _seq_axis_live() -> bool:
    """A global mesh exists and carries a >1 'seq' axis — only then does
    GatedAttention.seq_dim mean anything (without one, the direct flash
    route is safe: nothing is sharded)."""
    from unicore_tpu.parallel.mesh import SEQ_AXIS, get_global_mesh

    mesh = get_global_mesh()
    return mesh is not None and mesh.shape.get(SEQ_AXIS, 1) > 1


def _seq_flash_plan(seq_dim, lead, Lq, Lk, head_dim, dtype, bias):
    """Gate for running the flash kernel PER-SHARD under GSPMD row sharding
    (GatedAttention.seq_dim): the mesh 'seq' axis must divide the sharded
    dim, the PER-SHARD shapes must pass the same ``_flash_ok`` gate as the
    direct route (backend, head_dim, dtype, padding-waste budget), and the
    bias slab must stay indexable after the split (G in {1, lead[0]}).
    Returns (mesh, rows_mode, data_axis|None) or None.

    Per-shard HBM bound with S shards: the (N, H, Lq, Lk) probability
    matrix never materializes anywhere; each shard holds O(N*H*Lq/S*hd)
    output rows plus — in rows mode — one gathered O(N*H*Lk*hd) k/v copy,
    vs the XLA fallback's O(N*H*Lq/S*Lk) per-shard score matrix."""
    from unicore_tpu.parallel.mesh import (
        DATA_AXIS, SEQ_AXIS, get_global_mesh,
    )

    mesh = get_global_mesh()
    n_seq = 1 if mesh is None else mesh.shape.get(SEQ_AXIS, 1)
    if n_seq <= 1:
        return None
    nl = len(lead)
    if not 1 <= seq_dim <= nl:
        return None
    if bias is not None and bias.shape[0] not in (1, lead[0]):
        return None
    rows = seq_dim == nl  # the attended dim itself is sharded
    if rows and Lq % n_seq:
        return None
    if not rows and lead[seq_dim] % n_seq:
        return None
    lq_local = Lq // n_seq if rows else Lq
    # one eligibility predicate for both routes (bias group divisibility
    # was checked above in its stricter per-shard form, so skip it here)
    if not _flash_ok(1, lq_local, Lk, head_dim, dtype, None):
        return None
    n_data = mesh.shape.get(DATA_AXIS, 1)
    data_ax = (
        DATA_AXIS if n_data > 1 and lead[0] % n_data == 0 else None
    )
    return mesh, rows, data_ax


def _sharded_flash(plan, seq_dim, q, k, v, bias, kvm, H, head_dim):
    """shard_map runner for the seq-sharded flash route: splits the sharded
    q_x dim over 'seq' (and batch over 'data' when divisible) and runs
    :func:`_flash_grouped` on each shard.  In rows mode k/v/kv_mask ride
    replicated in_specs, so XLA gathers them once at the shard_map boundary
    and their cotangents are psummed by the shard_map transpose; the
    grouped bias splits on its query-row dim instead."""
    from jax.sharding import PartitionSpec as P

    from unicore_tpu.parallel.mesh import SEQ_AXIS

    mesh, rows, data_ax = plan
    nl = q.ndim - 3

    q_spec = [None] * (nl + 3)
    q_spec[0] = data_ax
    kv_spec = list(q_spec)
    if rows:
        q_spec[nl + 1] = SEQ_AXIS
    else:
        q_spec[seq_dim] = SEQ_AXIS
        kv_spec[seq_dim] = SEQ_AXIS
    specs = [P(*q_spec), P(*kv_spec), P(*kv_spec)]
    operands = [q, k, v]
    has_bias = bias is not None
    has_mask = kvm is not None
    if has_bias:
        b_spec = [None] * 4
        b_spec[0] = data_ax if bias.shape[0] == q.shape[0] else None
        if rows:
            b_spec[2] = SEQ_AXIS
        specs.append(P(*b_spec))
        operands.append(bias)
    if has_mask:
        m_spec = [None] * (nl + 1)
        m_spec[0] = data_ax
        if not rows:
            m_spec[seq_dim] = SEQ_AXIS
        specs.append(P(*m_spec))
        operands.append(kvm)

    def body(*ops):
        q_, k_, v_ = ops[:3]
        i = 3
        b_ = ops[i] if has_bias else None
        i += int(has_bias)
        m_ = ops[i] if has_mask else None
        lead_loc = q_.shape[:-3]
        n_loc = 1
        for d in lead_loc:
            n_loc *= d
        lq, lk = q_.shape[-2], k_.shape[-2]
        o = _flash_grouped(
            q_.reshape(n_loc, H, lq, head_dim),
            k_.reshape(n_loc, H, lk, head_dim),
            v_.reshape(n_loc, H, lk, head_dim),
            b_,
            None if m_ is None else m_.reshape(n_loc, lk),
            lq, lk,
        )
        return o.reshape(*lead_loc, H, lq, head_dim)

    from unicore_tpu.parallel.compat import shard_map

    fn = shard_map(
        body,
        mesh=mesh,
        in_specs=tuple(specs),
        out_specs=P(*q_spec),
        # pallas_call out_shapes carry no replication/vma annotation
        # (same caveat as ring_self_attention); equivalence tests cover it
        check_vma=False,  # lint: jax-version-pinned
    )
    return fn(*operands)


class MSARowAttentionWithPairBias(nn.Module):
    """Attention along the residue dim of each MSA row, biased by the pair
    representation.  ``seq_shard``: the residue dim (msa dim 2 — the
    attended dim) is row-sharded over the mesh 'seq' axis; attention runs
    per-shard in the flash kernel with k/v gathered at the shard_map
    boundary."""

    embed_dim: int
    pair_dim: int
    num_heads: int
    use_flash: bool = True
    seq_shard: bool = False

    @nn.compact
    def __call__(self, msa, pair, msa_mask=None):
        # msa: (B, R, L, D_m); pair: (B, L, L, D_z)
        m = LayerNorm(self.embed_dim, name="ln_m")(msa)
        z = LayerNorm(self.pair_dim, name="ln_z")(pair)
        pair_bias = nn.Dense(
            self.num_heads, use_bias=False, name="pair_bias",
            kernel_init=nn.initializers.normal(1.0 / (self.pair_dim ** 0.5)),
            dtype=msa.dtype, param_dtype=jnp.float32,
        )(z)  # (B, L, L, H)
        # grouped bias: all R rows of sequence b share slab b; the padding
        # mask rides separately so the kernel path never materializes the
        # per-row (B, R, H, L, L) combined bias the old layout implied
        bias = pair_bias.transpose(0, 3, 1, 2)  # (B, H, L, L)
        out = GatedAttention(
            self.embed_dim, self.num_heads, use_flash=self.use_flash,
            seq_dim=2 if self.seq_shard else None,
            name="attn",
        )(m, m, bias=bias, kv_mask=msa_mask)
        return out


class MSAColumnAttention(nn.Module):
    """Attention along the sequence (row) dim of each MSA column.
    ``seq_shard``: after the transpose the residue dim is LEAD dim 1 —
    column attention is embarrassingly parallel over the seq shards."""

    embed_dim: int
    num_heads: int
    use_flash: bool = True
    seq_shard: bool = False

    @nn.compact
    def __call__(self, msa, msa_mask=None):
        m = LayerNorm(self.embed_dim, name="ln_m")(msa)
        mt = m.swapaxes(1, 2)  # (B, L, R, D)
        col_mask = msa_mask.swapaxes(1, 2) if msa_mask is not None else None
        out = GatedAttention(
            self.embed_dim, self.num_heads, use_flash=self.use_flash,
            seq_dim=1 if self.seq_shard else None,
            name="attn",
        )(mt, mt, kv_mask=col_mask)
        return out.swapaxes(1, 2)


class OuterProductMean(nn.Module):
    """MSA -> pair update: mean over rows of outer products."""

    embed_dim: int
    pair_dim: int
    hidden: int = 32

    @nn.compact
    def __call__(self, msa, msa_mask=None):
        m = LayerNorm(self.embed_dim, name="ln")(msa)
        a = nn.Dense(self.hidden, name="proj_a", kernel_init=bert_init,
                     dtype=m.dtype, param_dtype=jnp.float32)(m)
        b = nn.Dense(self.hidden, name="proj_b", kernel_init=bert_init,
                     dtype=m.dtype, param_dtype=jnp.float32)(m)
        if msa_mask is not None:
            w = msa_mask.astype(m.dtype)[..., None]
            a = a * w
            b = b * w
            # max (not +eps) keeps an all-ones mask EXACTLY equal to the
            # unmasked R normalization — the pipelined stack relies on
            # ones-mask == identity — while still guarding empty pairs
            norm = jnp.maximum(
                jnp.einsum("bri,brj->bij", msa_mask.astype(jnp.float32),
                           msa_mask.astype(jnp.float32)),
                1e-3,
            )[..., None]
        else:
            norm = msa.shape[1]
        outer = jnp.einsum("brid,brje->bijde", a, b)
        outer = outer.reshape(*outer.shape[:3], -1) / norm
        out = nn.Dense(self.pair_dim, name="out_proj",
                       kernel_init=nn.initializers.zeros,
                       dtype=m.dtype, param_dtype=jnp.float32)(outer)
        return out


class TriangleMultiplication(nn.Module):
    """Triangle multiplicative update; ``outgoing=True`` uses edges (i,k),
    (j,k); ``False`` uses (k,i), (k,j)."""

    pair_dim: int
    hidden: int = 128
    outgoing: bool = True

    @nn.compact
    def __call__(self, pair, pair_mask=None):
        z = LayerNorm(self.pair_dim, name="ln_in")(pair)
        dense = partial(nn.Dense, kernel_init=bert_init, dtype=z.dtype,
                        param_dtype=jnp.float32)
        a = dense(self.hidden, name="a_proj")(z)
        b = dense(self.hidden, name="b_proj")(z)
        ag = jax.nn.sigmoid(
            nn.Dense(self.hidden, name="a_gate",
                     kernel_init=nn.initializers.zeros,
                     bias_init=nn.initializers.ones,
                     dtype=z.dtype, param_dtype=jnp.float32)(z))
        bg = jax.nn.sigmoid(
            nn.Dense(self.hidden, name="b_gate",
                     kernel_init=nn.initializers.zeros,
                     bias_init=nn.initializers.ones,
                     dtype=z.dtype, param_dtype=jnp.float32)(z))
        a = a * ag
        b = b * bg
        if pair_mask is not None:
            w = pair_mask.astype(z.dtype)[..., None]
            a = a * w
            b = b * w
        if self.outgoing:
            x = jnp.einsum("bikd,bjkd->bijd", a, b)
        else:
            x = jnp.einsum("bkid,bkjd->bijd", a, b)
        x = LayerNorm(self.hidden, name="ln_out")(x)
        x = dense(self.pair_dim, name="out_proj",
                  kernel_init=nn.initializers.zeros)(x)
        g = jax.nn.sigmoid(
            nn.Dense(self.pair_dim, name="out_gate",
                     kernel_init=nn.initializers.zeros,
                     bias_init=nn.initializers.ones,
                     dtype=z.dtype, param_dtype=jnp.float32)(z))
        return x * g


class TriangleAttention(nn.Module):
    """Triangle self-attention; ``starting=True`` attends along rows
    (starting node), ``False`` along columns (ending node)."""

    pair_dim: int
    num_heads: int
    starting: bool = True
    use_flash: bool = True
    # pair row-sharded on its lead dim 1 over the mesh 'seq' axis: for the
    # starting node that is GatedAttention's lead dim 1 (parallel rows);
    # for the ending node the swap moves it to the ATTENDED dim (rows mode,
    # k/v gathered at the shard_map boundary)
    seq_shard: bool = False

    @nn.compact
    def __call__(self, pair, pair_mask=None):
        z = pair if self.starting else pair.swapaxes(1, 2)
        z = LayerNorm(self.pair_dim, name="ln")(z)
        tri_bias = nn.Dense(
            self.num_heads, use_bias=False, name="tri_bias",
            kernel_init=nn.initializers.normal(1.0 / (self.pair_dim ** 0.5)),
            dtype=z.dtype, param_dtype=jnp.float32,
        )(z)  # (B, I, J, H)
        # grouped bias: every lead row i of pair matrix b shares slab b
        bias = tri_bias.transpose(0, 3, 1, 2)  # (B, H, I, J)
        pm = None
        if pair_mask is not None:
            pm = pair_mask if self.starting else pair_mask.swapaxes(1, 2)
        out = GatedAttention(
            self.pair_dim, self.num_heads, use_flash=self.use_flash,
            seq_dim=(
                None if not self.seq_shard else (1 if self.starting else 2)
            ),
            name="attn",
        )(z, z, bias=bias, kv_mask=pm)
        return out if self.starting else out.swapaxes(1, 2)


class Transition(nn.Module):
    """Pointwise 2-layer MLP with pre-LN (MSA and pair transitions)."""

    dim: int
    ratio: int = 4

    @nn.compact
    def __call__(self, x):
        y = LayerNorm(self.dim, name="ln")(x)
        y = nn.Dense(self.dim * self.ratio, name="fc1", kernel_init=bert_init,
                     dtype=y.dtype, param_dtype=jnp.float32)(y)
        y = jax.nn.relu(y)
        y = nn.Dense(self.dim, name="fc2", kernel_init=nn.initializers.zeros,
                     dtype=y.dtype, param_dtype=jnp.float32)(y)
        return y


class EvoformerIteration(nn.Module):
    msa_dim: int = 256
    pair_dim: int = 128
    msa_heads: int = 8
    pair_heads: int = 4
    dropout: float = 0.1
    use_flash: bool = True
    # streams row-sharded over the mesh 'seq' axis (msa residue dim 2,
    # pair lead dim 1): each attention runs the flash kernel per-shard
    # via shard_map instead of a (non-partitionable) bare pallas_call
    seq_shard: bool = False

    @nn.compact
    def __call__(self, msa, pair, msa_mask=None, pair_mask=None, train=False):
        drop_row = nn.Dropout(rate=self.dropout, broadcast_dims=(1,))
        det = not train

        msa = msa + drop_row(
            MSARowAttentionWithPairBias(
                self.msa_dim, self.pair_dim, self.msa_heads,
                use_flash=self.use_flash, seq_shard=self.seq_shard,
                name="msa_row_attn",
            )(msa, pair, msa_mask),
            deterministic=det,
        )
        msa = msa + MSAColumnAttention(
            self.msa_dim, self.msa_heads, use_flash=self.use_flash,
            seq_shard=self.seq_shard,
            name="msa_col_attn",
        )(msa, msa_mask)
        msa = msa + Transition(self.msa_dim, name="msa_transition")(msa)

        pair = pair + OuterProductMean(
            self.msa_dim, self.pair_dim, name="outer_product_mean"
        )(msa, msa_mask)
        pair = pair + drop_row(
            TriangleMultiplication(
                self.pair_dim, outgoing=True, name="tri_mul_out"
            )(pair, pair_mask),
            deterministic=det,
        )
        pair = pair + drop_row(
            TriangleMultiplication(
                self.pair_dim, outgoing=False, name="tri_mul_in"
            )(pair, pair_mask),
            deterministic=det,
        )
        pair = pair + drop_row(
            TriangleAttention(
                self.pair_dim, self.pair_heads, starting=True,
                use_flash=self.use_flash, seq_shard=self.seq_shard,
                name="tri_attn_start",
            )(pair, pair_mask),
            deterministic=det,
        )
        pair = pair + drop_row(
            TriangleAttention(
                self.pair_dim, self.pair_heads, starting=False,
                use_flash=self.use_flash, seq_shard=self.seq_shard,
                name="tri_attn_end",
            )(pair, pair_mask),
            deterministic=det,
        )
        pair = pair + Transition(self.pair_dim, name="pair_transition")(pair)
        return msa, pair


class EvoformerStack(nn.Module):
    num_blocks: int = 48
    msa_dim: int = 256
    pair_dim: int = 128
    msa_heads: int = 8
    pair_heads: int = 4
    dropout: float = 0.1
    remat: bool = True
    # activation-remat policy name (modules/remat.py): 'none', 'all',
    # 'dots', 'save-anything-pjit'; empty string defers to the boolean
    remat_policy: str = ""
    # GPipe pipeline parallelism over the mesh 'pipe' axis
    # (parallel/pipeline.py).  The 48-block stack is the model where PP
    # earns its keep: each pipe rank holds num_blocks/P blocks' params and
    # activations.  Requires num_blocks % stages == 0 and batch %
    # pipeline_microbatches == 0.  0 = off.
    pipeline_stages: int = 0
    pipeline_microbatches: int = 4
    # Sequence parallelism for the deep pair stack: both evolving streams
    # row-shard over the mesh 'seq' axis via GSPMD constraints — msa
    # (B, R, L, D) on its residue dim, pair (B, I, J, D) on its lead-row
    # dim — so the O(L^2) pair activations distribute across devices and
    # XLA inserts the gathers row-local attention needs.  Attention stays
    # in the Pallas flash kernel: each GatedAttention drops into a
    # shard_map over 'seq' whose body runs the kernel on that shard's rows
    # (GatedAttention.seq_dim), so the per-shard probability matrix never
    # materializes either; only kernel-ineligible shapes fall back to the
    # partitionable XLA path.
    seq_shard: bool = False

    @nn.compact
    def __call__(self, msa, pair, msa_mask=None, pair_mask=None, train=False):
        if self.pipeline_stages > 1:
            return self._pipeline_forward(
                msa, pair, msa_mask, pair_mask, train
            )
        from unicore_tpu.parallel.sharding import seq_row_constrainer

        L = msa.shape[2]
        if self.seq_shard:
            # the row constrainer is derived from L = msa.shape[2] and
            # applied to BOTH streams; a non-square pair would mis-shard
            # with an opaque GSPMD error downstream
            assert pair.shape[1] == pair.shape[2] == L, (
                f"seq_shard needs a square pair matching the msa residue "
                f"dim: msa L={L}, pair {pair.shape[1:3]}"
            )
        shard_rows = seq_row_constrainer(L, self.seq_shard, "evoformer")
        seq_on = shard_rows.engaged
        from .remat import remat_wrap

        # trade FLOPs for activation memory across the deep stack
        block_cls = remat_wrap(
            EvoformerIteration,
            self.remat_policy or ("all" if self.remat else "none"),
            static_argnums=(5,),
        )
        msa, pair = shard_rows(msa, 2), shard_rows(pair, 1)
        for i in range(self.num_blocks):
            msa, pair = block_cls(
                msa_dim=self.msa_dim,
                pair_dim=self.pair_dim,
                msa_heads=self.msa_heads,
                pair_heads=self.pair_heads,
                dropout=self.dropout,
                seq_shard=seq_on,
                name=f"block_{i}",
            )(msa, pair, msa_mask, pair_mask, train)
            # re-pin both streams each block so the layout survives the
            # transposing ops (column attention, triangle 'ending' swap)
            msa, pair = shard_rows(msa, 2), shard_rows(pair, 1)
        return msa, pair

    def _pipeline_forward(self, msa, pair, msa_mask, pair_mask, train):
        """GPipe schedule: blocks stacked on a leading axis sharded over
        'pipe'; the (msa, pair) pair streams ride each microbatch tree
        together (same shape every stage, so the ring buffer is uniform).

        Composes with seq_shard (dp x pp x sp): gpipe goes MANUAL over
        every mesh axis except 'seq', which stays AUTO, so the row
        sharding that serves the non-pipelined stack (msa residue rows,
        pair lead rows) runs inside each stage body via GSPMD.  Attention
        inside the composed pipeline uses the partitionable XLA path (the
        per-shard flash shard_map can't nest inside the partial-manual
        pipeline body yet)."""
        from unicore_tpu.parallel.pipeline import gpipe, plan_schedule
        from unicore_tpu.parallel.sharding import seq_pipeline_plan

        assert self.num_blocks % self.pipeline_stages == 0, (
            f"num_blocks {self.num_blocks} % stages {self.pipeline_stages}"
        )
        B, R, L, Dm = msa.shape
        if self.seq_shard:
            assert pair.shape[1] == pair.shape[2] == L, (
                f"seq_shard needs a square pair matching the msa residue "
                f"dim: msa L={L}, pair {pair.shape[1:3]}"
            )
        mesh, n_micro, mb, batched = plan_schedule(
            self.pipeline_stages, B, self.pipeline_microbatches
        )
        pin, pin_inside, manual_axes = seq_pipeline_plan(
            L, self.seq_shard, "evoformer"
        )

        template = EvoformerIteration(
            msa_dim=self.msa_dim,
            pair_dim=self.pair_dim,
            msa_heads=self.msa_heads,
            pair_heads=self.pair_heads,
            dropout=self.dropout,
            use_flash=not pin.engaged,
        )

        def stack_init(rng):
            dmsa = jnp.zeros((1, 2, 8, self.msa_dim), jnp.float32)
            dpair = jnp.zeros((1, 8, 8, self.pair_dim), jnp.float32)
            keys = jax.random.split(rng, self.num_blocks)
            per = [
                template.init({"params": k}, dmsa, dpair, None, None,
                              False)["params"]
                for k in keys
            ]
            return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per)

        stack = self.param("pipeline_stack", stack_init)

        # all-ones masks are the identity (mask_to_bias(1) == 0) and keep
        # the pipeline's zero-filled bubble ticks NaN-free
        if msa_mask is None:
            msa_mask = jnp.ones((B, R, L), msa.dtype)
        if pair_mask is None:
            pair_mask = jnp.ones((B, L, L), pair.dtype)
        mbs = {
            # residue rows / pair lead rows pinned to 'seq' (identity when
            # the composition isn't engaged); masks stay replicated over
            # seq — row-local attention needs all keys
            "msa": pin(msa.reshape(n_micro, mb, R, L, Dm), 3),
            "pair": pin(pair.reshape(n_micro, mb, L, L, pair.shape[-1]), 2),
            "mm": msa_mask.reshape(n_micro, mb, R, L),
            "pm": pair_mask.reshape(n_micro, mb, L, L),
        }
        rng = self.make_rng("dropout") if (train and self.dropout > 0) else None

        def stage_apply(p_stack, tree, step_rng):
            mb_tree, _consts = tree
            m, z = mb_tree["msa"], mb_tree["pair"]
            mm, pm = mb_tree["mm"], mb_tree["pm"]

            def body(carry, xs):
                p_block, li = xs
                m_, z_ = carry
                rngs = None
                if step_rng is not None:
                    rngs = {"dropout": jax.random.fold_in(step_rng, li)}
                apply = template.apply
                _policy = self.remat_policy or (
                    "all" if self.remat else "none"
                )
                if _policy != "none":
                    from .remat import policy_fn

                    apply = jax.checkpoint(
                        template.apply, static_argnums=(5,),
                        policy=policy_fn(_policy),
                    )
                m_, z_ = apply(
                    {"params": p_block}, m_, z_, mm, pm, train, rngs=rngs
                )
                # re-pin both streams block to block, mirroring the
                # non-pipelined loop (layout survives the transposing ops)
                return (pin_inside(m_, 2), pin_inside(z_, 1)), None

            n_local = jax.tree_util.tree_leaves(p_stack)[0].shape[0]
            (m, z), _ = jax.lax.scan(
                body, (m, z), (p_stack, jnp.arange(n_local, dtype=jnp.int32))
            )
            return {"msa": m, "pair": z, "mm": mm, "pm": pm}

        outs = gpipe(mesh, stage_apply, stack, mbs, {}, rng=rng,
                     mb_spec=batched, manual_axes=manual_axes)
        return (
            outs["msa"].reshape(B, R, L, Dm),
            outs["pair"].reshape(B, L, L, pair.shape[-1]),
        )
