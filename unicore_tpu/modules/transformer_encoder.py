"""Transformer encoder stack
(reference /root/reference/unicore/modules/transformer_encoder.py,
transformer_encoder_layer.py).

TPU-native notes:
- the bucketed relative-position table is a trace-time numpy constant (the
  reference registers a buffer and slices it per forward);
- the rel-pos bias stays (H, L, L) and broadcasts over batch inside the
  attention op instead of being ``repeat``-materialized per batch row
  (reference transformer_encoder.py:141 materializes (B*H, L, L) in HBM —
  skipping that repeat saves HBM bandwidth, the TPU bottleneck);
- padding + attention masks merge into one additive fp32 mask;
- BERT init (normal 0.02, zero bias) is built into the param initializers
  (replaces the reference's init_bert_params module walker).
"""

import math
from functools import partial
from typing import Optional

import numpy as np

import flax.linen as nn
import jax
import jax.numpy as jnp

from unicore_tpu.quant import QuantDense, check_mode
from .layer_norm import LayerNorm
from .multihead_attention import SelfMultiheadAttention

# BERT initialization (reference transformer_encoder.py:16-30): all linear /
# embedding weights N(0, 0.02), biases 0, pad embedding row 0.
bert_init = nn.initializers.normal(0.02)


# activations dear enough to evaluate that ``fc2``'s products should read
# them from memory: the exact GELU is some fifty vector operations, an
# exponential and two divides an element.  ``relu`` / ``silu`` / ``linear``
# cost a product's producer fusion nothing and stay unkept (a kept
# ``(B, L, ffn)`` array a layer would buy nothing).
KEPT_ACTIVATIONS = frozenset({"gelu", "gelu_fast", "gelu_accurate", "tanh"})


@jax.custom_vjp
def _pinned(x):
    return jax.lax.optimization_barrier(x)


def _pinned_fwd(x):
    return jax.lax.optimization_barrier(x), None


def _pinned_bwd(_, g):
    # transparent to the cotangent: autodiff's own rule mirrors the barrier
    # there, which cuts fc2's dx product from the activation's derivative
    # (one fusion otherwise) and hands back all the forward pass gained
    return (g,)


_pinned.defvjp(_pinned_fwd, _pinned_bwd)


def keep_ffn_activation(x, activation_fn: str):
    """What ``fc2`` is about to read, pinned in memory where it is dear.

    XLA keeps ``fc1``'s pre-activation only and clones the activation into
    every consumer: as the producer of ``fc2``'s forward operand, again for
    ``fc2``'s weight gradient, and as the epilogue with the derivative for
    ``dx``.  Behind a forward-only ``optimization_barrier`` the value is made
    once, under ``fc1``'s product, and both ``fc2`` products read a plain
    bfloat16 operand.  Same operations on the same values; the cost is the
    kept array (and the activation's own residual, now free to keep): two
    ``(B, L, ffn)`` arrays a layer.  Called after ``act_dropout`` so the
    array kept is the one ``fc2`` really reads.
    """
    return _pinned(x) if activation_fn in KEPT_ACTIVATIONS else x


def init_bert_params(rng, module, sample):
    """API-parity helper: flax modules in this package already build with
    BERT init; this exists for user models that want the same recipe."""
    return module.init(rng, **sample)


def relative_position_bucket(relative_position, num_buckets=32, max_distance=128):
    """Signed log-bucketed relative positions
    (reference transformer_encoder.py:33-48), numpy/jnp polymorphic."""
    xp = jnp if isinstance(relative_position, jnp.ndarray) else np
    sign = xp.sign(relative_position)
    num_buckets //= 2
    n = xp.abs(relative_position)

    # half of the buckets are for exact increments in positions
    max_exact = num_buckets // 2
    is_small = n < max_exact
    max_bucket_val = num_buckets - 1 - max_exact
    # the other half logarithmically covers positions up to max_distance
    # (clamp the log argument: n==0 rows are overwritten by the is_small branch)
    safe_n = xp.maximum(n, 1)
    val_if_large = max_exact + xp.ceil(
        xp.log(safe_n.astype(xp.float32) / max_exact)
        / math.log((max_distance - 1) / max_exact)
        * max_bucket_val
    ).astype(xp.int64 if xp is np else jnp.int32)
    val_if_large = xp.minimum(val_if_large, num_buckets - 1)
    ret = xp.where(is_small, n, val_if_large) * sign
    return ret


def make_rp_bucket(max_seq_len, rel_pos_bins, max_rel_pos):
    """Precompute the (L, L) bucket table as a host constant."""
    context_position = np.arange(max_seq_len, dtype=np.int64)[:, None]
    memory_position = np.arange(max_seq_len, dtype=np.int64)[None, :]
    relative_position = memory_position - context_position
    rp_bucket = relative_position_bucket(
        relative_position, num_buckets=rel_pos_bins, max_distance=max_rel_pos
    )
    rp_bucket -= rp_bucket.min()
    return rp_bucket


class TransformerEncoderLayer(nn.Module):
    """Pre-/post-LN encoder layer (reference transformer_encoder_layer.py:56)."""

    embed_dim: int = 768
    ffn_embed_dim: int = 3072
    attention_heads: int = 8
    dropout: float = 0.1
    attention_dropout: float = 0.1
    activation_dropout: float = 0.0
    activation_fn: str = "gelu"
    post_ln: bool = False
    use_ring: bool = False
    seq_impl: str = "ring"
    # inside a shard_map whose 'seq' axis shards the sequence dim (the
    # GPipe stage body): the attention runs ring collectives directly on
    # the local chunks (see SelfMultiheadAttention.seq_inside)
    seq_inside: bool = False
    # quantized serving ('int8'/'fp8'): dense call sites route through
    # QuantDense, '' is the training-precision path (bit-identical)
    quantize: str = ""

    @nn.compact
    def __call__(
        self,
        x,
        attn_bias: Optional[jnp.ndarray] = None,
        padding_mask: Optional[jnp.ndarray] = None,
        return_attn: bool = False,
        train: bool = False,
    ):
        dropout = partial(
            nn.Dropout(rate=self.dropout), deterministic=not train
        )
        act_dropout = partial(
            nn.Dropout(rate=self.activation_dropout), deterministic=not train
        )

        residual = x
        ln_attn = LayerNorm(self.embed_dim, name="self_attn_layer_norm")
        if not self.post_ln:
            x = ln_attn(x)
        x = SelfMultiheadAttention(
            self.embed_dim,
            self.attention_heads,
            dropout=self.attention_dropout,
            use_ring=self.use_ring,
            seq_impl=self.seq_impl,
            seq_inside=self.seq_inside,
            quantize=self.quantize,
            name="self_attn",
        )(
            x,
            key_padding_mask=padding_mask,
            attn_bias=attn_bias,
            return_attn=return_attn,
            train=train,
        )
        if return_attn:
            x, attn_weights, attn_probs = x
        x = dropout(x)
        x = residual + x
        if self.post_ln:
            x = ln_attn(x)

        residual = x
        ln_final = LayerNorm(self.embed_dim, name="final_layer_norm")
        if not self.post_ln:
            x = ln_final(x)
        # activation fused into fc1's epilogue: identical composition on
        # the fp path, one in-VMEM nonlinearity on the quantized path
        x = QuantDense(
            self.ffn_embed_dim,
            name="fc1",
            kernel_init=bert_init,
            dtype=x.dtype,
            param_dtype=jnp.float32,
            quantize=self.quantize,
            activation=self.activation_fn,
        )(x)
        x = act_dropout(x)
        if check_mode(self.quantize) == "off":
            x = keep_ffn_activation(x, self.activation_fn)
        x = QuantDense(
            self.embed_dim,
            name="fc2",
            kernel_init=bert_init,
            dtype=x.dtype,
            param_dtype=jnp.float32,
            quantize=self.quantize,
        )(x)
        x = dropout(x)
        x = residual + x
        if self.post_ln:
            x = ln_final(x)
        if not return_attn:
            return x
        else:
            return x, attn_weights, attn_probs


class TransformerEncoder(nn.Module):
    """Encoder stack with bucketed relative-position bias
    (reference transformer_encoder.py:51-162)."""

    encoder_layers: int = 6
    embed_dim: int = 768
    ffn_embed_dim: int = 3072
    attention_heads: int = 8
    emb_dropout: float = 0.1
    dropout: float = 0.1
    attention_dropout: float = 0.1
    activation_dropout: float = 0.0
    max_seq_len: int = 256
    activation_fn: str = "gelu"
    rel_pos: bool = True
    rel_pos_bins: int = 32
    max_rel_pos: int = 128
    post_ln: bool = False
    remat: bool = False  # deprecated boolean: remat_policy 'all' when set
                         # (reference utils.checkpoint_sequential, utils.py:306-333)
    # activation-remat policy name (modules/remat.py): 'none', 'all',
    # 'dots', 'save-anything-pjit'; empty string defers to the boolean
    remat_policy: str = ""
    use_ring: bool = False  # seq parallelism (mesh 'seq' axis)
    seq_impl: str = "ring"  # 'ring' or 'ulysses' (--seq-parallel-impl)
    # mixture-of-experts FFN (expert parallelism, modules/moe.py): every
    # moe_every-th layer swaps its dense FFN for num_experts routed experts
    moe_experts: int = 0
    moe_every: int = 2
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    # fixed f32 reduction order for the expert combine (modules/moe.py:
    # MoELayer.deterministic_reduction) — --moe-deterministic-reduction
    moe_deterministic: bool = False
    # pipeline parallelism (parallel/pipeline.py): layers stacked on a
    # leading axis sharded over the mesh 'pipe' axis, GPipe microbatch
    # schedule.  0 = off.  Requires encoder_layers % pipe == 0 and
    # batch % pipeline_microbatches == 0.
    pipeline_stages: int = 0
    pipeline_microbatches: int = 4
    # quantized serving ('int8'/'fp8', docs/serving.md): every layer's
    # dense call sites route through QuantDense; '' = training precision
    quantize: str = ""

    def setup(self):
        self.emb_layer_norm = LayerNorm(self.embed_dim, name="emb_layer_norm")
        self.emb_dropout_module = nn.Dropout(rate=self.emb_dropout)
        if not self.post_ln:
            self.final_layer_norm = LayerNorm(self.embed_dim, name="final_layer_norm")
        layer_cls = TransformerEncoderLayer
        moe_cls = None
        if self.moe_experts > 0:
            if self.quantize:
                raise ValueError(
                    "quantized serving does not support the MoE FFN yet "
                    "(routed expert denses are not QuantDense sites); "
                    "serve this checkpoint with --serve-quantize off"
                )
            from .moe import MoEEncoderLayer

            moe_cls = MoEEncoderLayer
        from .remat import remat_wrap

        policy = self.remat_policy or ("all" if self.remat else "none")
        # static argnums (incl. self at 0): return_attn=4, train=5
        layer_cls = remat_wrap(layer_cls, policy, static_argnums=(4, 5))
        if moe_cls is not None:
            moe_cls = remat_wrap(moe_cls, policy, static_argnums=(4, 5))

        def build_layer(i):
            common = dict(
                embed_dim=self.embed_dim,
                ffn_embed_dim=self.ffn_embed_dim,
                attention_heads=self.attention_heads,
                dropout=self.dropout,
                attention_dropout=self.attention_dropout,
                activation_dropout=self.activation_dropout,
                activation_fn=self.activation_fn,
                post_ln=self.post_ln,
                use_ring=self.use_ring,
                seq_impl=self.seq_impl,
                name=f"layers_{i}",
            )
            if moe_cls is None:
                # MoEEncoderLayer has no quantize attr (guarded above)
                common["quantize"] = self.quantize
            # every moe_every-th layer (starting at moe_every - 1, so layer 0
            # stays dense — the common interleaved-MoE recipe)
            if moe_cls is not None and i % self.moe_every == self.moe_every - 1:
                return moe_cls(
                    num_experts=self.moe_experts,
                    top_k=self.moe_top_k,
                    capacity_factor=self.moe_capacity_factor,
                    deterministic_reduction=self.moe_deterministic,
                    **common,
                )
            return layer_cls(**common)

        if self.pipeline_stages > 1:
            # stacked per-layer params for the GPipe schedule: leading dim
            # num_layers, sharded over 'pipe' by DEFAULT_PP_RULES
            assert self.moe_experts == 0, "MoE inside the pipeline: unsupported"
            assert not self.quantize, (
                "quantized serving inside the pipeline: unsupported "
                "(the single-process serving plane never pipelines)"
            )
            assert not (self.use_ring and self.seq_impl != "ring"), (
                "only the ring seq-parallel impl composes with the "
                "pipeline (its collectives run directly inside the stage "
                "shard_map); use --seq-parallel-impl ring or drop "
                "--pipeline-parallel-size"
            )
            self._pipe_template_kwargs = dict(
                embed_dim=self.embed_dim,
                ffn_embed_dim=self.ffn_embed_dim,
                attention_heads=self.attention_heads,
                dropout=self.dropout,
                attention_dropout=self.attention_dropout,
                activation_dropout=self.activation_dropout,
                activation_fn=self.activation_fn,
                post_ln=self.post_ln,
            )
            template = TransformerEncoderLayer(**self._pipe_template_kwargs)
            self._pipe_template = template
            # variant for stage bodies whose 'seq' mesh axis shards the
            # sequence dim (dp x pp x sp); same params, different routing —
            # flax requires module construction here, not at call time
            self._pipe_template_seq = TransformerEncoderLayer(
                **self._pipe_template_kwargs, seq_inside=True
            )

            def stack_init(rng):
                dummy = jnp.zeros((1, 8, self.embed_dim), jnp.float32)
                keys = jax.random.split(rng, self.encoder_layers)
                per = [
                    template.init({"params": k}, dummy, None, None, False,
                                  False)["params"]
                    for k in keys
                ]
                return jax.tree_util.tree_map(
                    lambda *xs: jnp.stack(xs), *per
                )

            self.pipeline_stack = self.param("pipeline_stack", stack_init)
            self.layers = []
        else:
            self.layers = [
                build_layer(i) for i in range(self.encoder_layers)
            ]
        if self.rel_pos:
            assert self.rel_pos_bins % 2 == 0
            self.relative_attention_bias = nn.Embed(
                self.rel_pos_bins,
                self.attention_heads,
                embedding_init=bert_init,
                name="relative_attention_bias",
                param_dtype=jnp.float32,
            )
            self._rp_bucket = make_rp_bucket(
                self.max_seq_len, self.rel_pos_bins, self.max_rel_pos
            )

    def get_rel_pos_bias(self, seq_len):
        # static (L, L) bucket constant -> (H, L, L) bias; batch broadcast is
        # left to the attention op (no HBM repeat).  The lookup is phrased as
        # one_hot @ table so BOTH directions are matmuls: a gather's backward
        # is a serial scatter-add on TPU (measured ~2.2 ms/step for the
        # (L*L)-row scatter into the (bins, H) table), while the one-hot
        # einsum's backward is an MXU reduction.
        rp_bucket = jnp.asarray(self._rp_bucket[:seq_len, :seq_len])
        table = self.relative_attention_bias.embedding  # (bins, H)
        onehot = (
            rp_bucket[..., None] == jnp.arange(self.rel_pos_bins)
        ).astype(table.dtype)  # (L, L, bins), folded into the matmul by XLA
        values = jnp.einsum("qkb,bh->hqk", onehot, table)
        return values

    def __call__(
        self,
        emb: jnp.ndarray,
        attn_mask: Optional[jnp.ndarray] = None,
        padding_mask: Optional[jnp.ndarray] = None,
        train: bool = False,
    ) -> jnp.ndarray:
        bsz, seq_len, _ = emb.shape
        x = self.emb_layer_norm(emb)
        x = self.emb_dropout_module(x, deterministic=not train)

        # account for padding while computing the representation
        if padding_mask is not None:
            x = x * (1 - padding_mask[..., None].astype(x.dtype))

        rel_pos_bias = self.get_rel_pos_bias(seq_len) if self.rel_pos else None
        if attn_mask is None:
            attn_bias = rel_pos_bias  # (H, L, L), broadcasts over batch
        elif rel_pos_bias is not None:
            attn_bias = attn_mask + rel_pos_bias
        else:
            attn_bias = attn_mask

        # the key-padding mask stays separate from the bias: the attention
        # paths apply it internally (the flash kernel as an in-kernel mask,
        # the fused path as an additive -inf) — unlike the reference, which
        # materializes a (B*H, L, L) merged tensor (transformer_encoder.py:147-155)

        if self.pipeline_stages > 1:
            x = self._pipeline_forward(x, attn_bias, padding_mask, train)
        else:
            for layer in self.layers:
                # positional: nn.remat requires static args positionally,
                # and the same form is valid for the plain layer
                x = layer(x, attn_bias, padding_mask, False, train)

        if not self.post_ln:
            x = self.final_layer_norm(x)
        return x

    def _pipeline_forward(self, x, attn_bias, padding_mask, train):
        """GPipe schedule over the mesh 'pipe' axis (parallel/pipeline.py).

        Composes with ring sequence parallelism (dp x pp x sp): when the
        mesh carries a live 'seq' axis dividing L, the microbatch sequence
        dim shards over it, the stationary bias shards by query rows, and
        the stage body's attention runs the ring collectives directly
        inside the pipe shard_map (TransformerEncoderLayer.seq_inside)."""
        from jax.sharding import PartitionSpec as P

        from unicore_tpu.parallel.mesh import DATA_AXIS, SEQ_AXIS
        from unicore_tpu.parallel.pipeline import gpipe, plan_schedule

        B, L, D = x.shape
        mesh, n_micro, mb, batched = plan_schedule(
            self.pipeline_stages, B, self.pipeline_microbatches
        )
        import logging

        from unicore_tpu.parallel.mesh import warn_once

        n_seq = mesh.shape.get(SEQ_AXIS, 1)
        seq_on = self.use_ring and n_seq > 1 and L % n_seq == 0
        if seq_on and attn_bias is not None and not (
            attn_bias.ndim == 3
            and attn_bias.shape[0] in (1, self.attention_heads)
        ):
            # mirror _ring_ok: the seq stage body treats the bias as ONE
            # batch-independent (H|1, L, L) stationary slab sliced by query
            # rows; a per-batch (B*H, L, L) bias would pass the ring's
            # shape asserts but silently drop every batch beyond the first
            seq_on = False
            warn_once(
                logging.getLogger(__name__),
                f"pipelined encoder: attention bias shape "
                f"{tuple(attn_bias.shape)} is not a batch-independent "
                f"(H|1, L, L) slab; running replicated over the seq axis",
            )
        if self.use_ring and n_seq > 1 and not seq_on and L % n_seq != 0:
            warn_once(
                logging.getLogger(__name__),
                f"pipelined encoder: seq axis {n_seq} does not divide "
                f"L={L}; running replicated over the seq axis",
            )
        if seq_on:
            template = self._pipe_template_seq
            data_ax = batched[1] if len(batched) > 1 else None
            mb_spec = P(None, data_ax, SEQ_AXIS)
            const_specs = (
                None if attn_bias is None
                else {"bias": P(None, SEQ_AXIS, None)}  # query rows
            )
        else:
            template = self._pipe_template
            mb_spec = batched
            const_specs = None

        if padding_mask is None:
            padding_mask = jnp.zeros((B, L), jnp.int32)
        mbs = {
            "x": x.reshape(n_micro, mb, L, D),
            "pm": padding_mask.reshape(n_micro, mb, L),
        }
        consts = {} if attn_bias is None else {"bias": attn_bias}
        has_dropout = train and (
            self.dropout > 0 or self.attention_dropout > 0
            or self.activation_dropout > 0
        )
        rng = self.make_rng("dropout") if has_dropout else None
        data_live = mesh.shape.get(DATA_AXIS, 1) > 1

        def stage_apply(p_stack, tree, step_rng):
            mb_tree, consts_ = tree
            h, pm = mb_tree["x"], mb_tree["pm"]
            bias = consts_.get("bias") if consts_ else None
            if step_rng is not None:
                # decorrelate dropout masks across the sharded axes: each
                # seq/data rank holds a DIFFERENT slice of the activations
                if seq_on:
                    step_rng = jax.random.fold_in(
                        step_rng, jax.lax.axis_index(SEQ_AXIS)
                    )
                if data_live:
                    step_rng = jax.random.fold_in(
                        step_rng, jax.lax.axis_index(DATA_AXIS)
                    )

            def body(carry, xs):
                p_layer, li = xs
                rngs = None
                if step_rng is not None:
                    rngs = {"dropout": jax.random.fold_in(step_rng, li)}
                out = template.apply(
                    {"params": p_layer}, carry, bias, pm, False, train,
                    rngs=rngs,
                )
                return out, None

            n_local = jax.tree_util.tree_leaves(p_stack)[0].shape[0]
            h, _ = jax.lax.scan(
                body, h, (p_stack, jnp.arange(n_local, dtype=jnp.int32))
            )
            return {"x": h, "pm": pm}

        outs = gpipe(
            mesh,
            stage_apply,
            self.pipeline_stack,
            mbs,
            consts,
            rng=rng,
            mb_spec=mb_spec,
            const_specs=const_specs,
        )
        return outs["x"].reshape(B, L, D)
