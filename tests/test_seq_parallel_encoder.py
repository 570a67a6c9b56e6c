"""End-to-end sequence parallelism: a Transformer encoder with
use_ring=True on a seq=8 mesh must match the dense encoder exactly
(rel-pos bias + padding mask included)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from unicore_tpu.modules import TransformerEncoder
from unicore_tpu.parallel import make_mesh, set_global_mesh
from unicore_tpu.platform_utils import on_tpu


@pytest.fixture(autouse=True)
def reset_mesh():
    yield
    set_global_mesh(None)


def test_ring_encoder_matches_dense():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    mesh = make_mesh(data=1, seq=8)
    set_global_mesh(mesh)

    B, L, E, H = 2, 128, 64, 4
    enc_ring = TransformerEncoder(
        encoder_layers=2, embed_dim=E, ffn_embed_dim=128, attention_heads=H,
        max_seq_len=L, use_ring=True, emb_dropout=0.0, dropout=0.0,
        attention_dropout=0.0,
    )
    enc_dense = TransformerEncoder(
        encoder_layers=2, embed_dim=E, ffn_embed_dim=128, attention_heads=H,
        max_seq_len=L, use_ring=False, emb_dropout=0.0, dropout=0.0,
        attention_dropout=0.0,
    )

    emb = jax.random.normal(jax.random.PRNGKey(0), (B, L, E))
    pm = jnp.asarray(
        (np.arange(L)[None, :] >= np.array([100, 128])[:, None]).astype(np.float32)
    )
    params = enc_ring.init({"params": jax.random.PRNGKey(1)}, emb)
    # jit: eager shard_map ppermute chains are pathologically slow on the
    # 1-core CI box; compiled, the whole test drops several-fold in wall
    o_ring = jax.jit(
        lambda p, e: enc_ring.apply(p, e, padding_mask=pm)
    )(params, emb)
    o_dense = jax.jit(
        lambda p, e: enc_dense.apply(p, e, padding_mask=pm)
    )(params, emb)
    err = float(jnp.abs(o_ring - o_dense).max())
    assert err < 1e-4, err

    # gradients flow through the ring path (incl. rel-pos bias params)
    g_ring = jax.jit(jax.grad(
        lambda p: jnp.sum(enc_ring.apply(p, emb, padding_mask=pm) ** 2)
    ))(params)
    g_dense = jax.jit(jax.grad(
        lambda p: jnp.sum(enc_dense.apply(p, emb, padding_mask=pm) ** 2)
    ))(params)
    for a, b in zip(
        jax.tree_util.tree_leaves(g_ring), jax.tree_util.tree_leaves(g_dense)
    ):
        scale = max(1.0, float(jnp.abs(b).max()))
        assert float(jnp.abs(a - b).max()) / scale < 1e-4


def test_ring_falls_back_without_seq_mesh():
    """No seq axis in the mesh (or no mesh): use_ring silently uses the
    regular paths — same output."""
    set_global_mesh(None)
    B, L, E, H = 1, 64, 32, 4
    enc = TransformerEncoder(
        encoder_layers=1, embed_dim=E, ffn_embed_dim=64, attention_heads=H,
        max_seq_len=L, use_ring=True, emb_dropout=0.0, dropout=0.0,
        attention_dropout=0.0,
    )
    emb = jax.random.normal(jax.random.PRNGKey(0), (B, L, E))
    params = enc.init({"params": jax.random.PRNGKey(1)}, emb)
    out = enc.apply(params, emb)
    assert bool(jnp.isfinite(out).all())


def test_ring_with_data_parallel_mesh():
    """data=2 x seq=4: batch rides the data axis, ring rides seq."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    from unicore_tpu.parallel import ring_self_attention
    from unicore_tpu.ops.flash_attention import mha_reference

    mesh = make_mesh(data=2, seq=4)
    B, H, L, D = 4, 2, 64, 16
    q = jax.random.normal(jax.random.PRNGKey(0), (B, H, L, D))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, H, L, D))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, H, L, D))
    bias = jax.random.normal(jax.random.PRNGKey(3), (H, L, L))
    out = ring_self_attention(mesh, q, k, v, bias=bias, sm_scale=D ** -0.5)
    ref = mha_reference(q, k, v, bias=bias[None], sm_scale=D ** -0.5)
    assert float(jnp.abs(out - ref).max()) < 1e-5


def test_ring_encoder_training_with_dropout():
    """attention_dropout > 0 now runs ON the ring (in-ring dropout)."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    set_global_mesh(make_mesh(data=1, seq=8))
    B, L, E, H = 2, 128, 64, 4
    enc = TransformerEncoder(
        encoder_layers=1, embed_dim=E, ffn_embed_dim=128, attention_heads=H,
        max_seq_len=L, use_ring=True, emb_dropout=0.0, dropout=0.0,
        attention_dropout=0.3,
    )
    emb = jax.random.normal(jax.random.PRNGKey(0), (B, L, E))
    params = enc.init(
        {"params": jax.random.PRNGKey(1), "dropout": jax.random.PRNGKey(2)}, emb
    )
    fwd = jax.jit(
        lambda p, e, r: enc.apply(p, e, train=True, rngs={"dropout": r})
    )
    o1 = fwd(params, emb, jax.random.PRNGKey(3))
    o2 = fwd(params, emb, jax.random.PRNGKey(3))
    o3 = fwd(params, emb, jax.random.PRNGKey(4))
    assert bool(jnp.all(o1 == o2))       # deterministic per rng
    assert bool(jnp.any(o1 != o3))       # varies across rngs
    assert bool(jnp.isfinite(o1).all())
    g = jax.jit(jax.grad(
        lambda p: jnp.sum(
            enc.apply(p, emb, train=True, rngs={"dropout": jax.random.PRNGKey(3)}) ** 2
        )
    ))(params)
    assert all(
        bool(jnp.isfinite(x).all()) for x in jax.tree_util.tree_leaves(g)
    )


def test_ulysses_encoder_matches_dense():
    """seq_impl='ulysses': the all-to-all path must match the dense encoder
    exactly (heads % seq axis == 0 engages it; same params)."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    set_global_mesh(make_mesh(data=2, seq=4))
    B, L, E, H = 2, 64, 64, 4  # H=4 divides seq=4
    mk = lambda impl: TransformerEncoder(
        encoder_layers=2, embed_dim=E, ffn_embed_dim=128, attention_heads=H,
        max_seq_len=L, use_ring=impl is not None, emb_dropout=0.0,
        dropout=0.0, attention_dropout=0.0,
        seq_impl=impl or "ring",
    )
    enc_u, enc_d = mk("ulysses"), mk(None)
    emb = jax.random.normal(jax.random.PRNGKey(0), (B, L, E))
    pm = jnp.asarray(
        (np.arange(L)[None, :] >= np.array([50, 64])[:, None]).astype(np.float32)
    )
    params = enc_u.init({"params": jax.random.PRNGKey(1)}, emb)
    o_u = jax.jit(lambda p, e: enc_u.apply(p, e, padding_mask=pm))(params, emb)
    o_d = jax.jit(lambda p, e: enc_d.apply(p, e, padding_mask=pm))(params, emb)
    assert float(jnp.abs(o_u - o_d).max()) < 1e-4

    g_u = jax.jit(jax.grad(
        lambda p: jnp.sum(enc_u.apply(p, emb, padding_mask=pm) ** 2)
    ))(params)
    g_d = jax.jit(jax.grad(
        lambda p: jnp.sum(enc_d.apply(p, emb, padding_mask=pm) ** 2)
    ))(params)
    for a, b in zip(
        jax.tree_util.tree_leaves(g_u), jax.tree_util.tree_leaves(g_d)
    ):
        scale = max(1.0, float(jnp.abs(b).max()))
        assert float(jnp.abs(a - b).max()) / scale < 1e-4


def test_ulysses_per_batch_bias():
    """The all-to-all path handles per-BATCH biases (the ring cannot):
    direct equivalence against the dense reference."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    from unicore_tpu.ops.flash_attention import mha_reference
    from unicore_tpu.parallel.ulysses import ulysses_self_attention

    mesh = make_mesh(data=2, seq=4)
    B, H, L, D = 4, 8, 64, 16
    r = np.random.RandomState(0)
    q, k, v = (jnp.asarray(r.randn(B, H, L, D), jnp.float32)
               for _ in range(3))
    bias = jnp.asarray(r.randn(B, H, L, L), jnp.float32)
    out = ulysses_self_attention(mesh, q, k, v, bias=bias,
                                 sm_scale=D ** -0.5)
    ref = mha_reference(q, k, v, bias=bias, sm_scale=D ** -0.5)
    assert float(jnp.abs(out - ref).max()) < 1e-5


def test_ulysses_flash_kernel_leg():
    """The Pallas-kernel branch inside the ulysses shard_map (interpret
    mode on CPU): mask + per-batch bias routed through the flash kernel
    must match the dense reference, gradients included.  Mirrors
    test_pallas_ring_matches_reference — without this, CPU CI only ever
    exercised the XLA fallback of _local_attention."""
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    from unicore_tpu.ops import flash_attention as fa
    from unicore_tpu.ops._pallas import interpret_enabled
    from unicore_tpu.parallel.ulysses import ulysses_self_attention

    prev_interpret = interpret_enabled()
    fa.set_interpret(not on_tpu())
    try:
        mesh = make_mesh(data=1, seq=4, devices=jax.devices()[:4])
        # L = 128, D = 16: the in-shard_map kernel gate (L % 128, D % 8)
        # opens, so the visiting head groups run the Pallas kernel
        B, H, L, D = 2, 4, 128, 16
        r = np.random.RandomState(0)
        q, k, v = (jnp.asarray(r.randn(B, H, L, D), jnp.float32)
                   for _ in range(3))
        bias = jnp.asarray(r.randn(B, H, L, L), jnp.float32)
        lens = np.array([100, 128])
        mask = jnp.asarray(
            (np.arange(L)[None, :] >= lens[:, None]).astype(np.int32)
        )
        from unicore_tpu.ops.flash_attention import mha_reference

        out = ulysses_self_attention(
            mesh, q, k, v, kv_padding_mask=mask, bias=bias,
            sm_scale=D ** -0.5,
        )
        ref = mha_reference(
            q, k, v, kv_padding_mask=mask, bias=bias, sm_scale=D ** -0.5
        )
        assert float(jnp.abs(out - ref).max()) < 2e-5

        def loss_u(q, k, v, b):
            return jnp.sum(
                ulysses_self_attention(
                    mesh, q, k, v, kv_padding_mask=mask, bias=b,
                    sm_scale=D ** -0.5,
                ) ** 2
            )

        def loss_ref(q, k, v, b):
            return jnp.sum(
                mha_reference(
                    q, k, v, kv_padding_mask=mask, bias=b,
                    sm_scale=D ** -0.5,
                ) ** 2
            )

        g_u = jax.jit(jax.grad(loss_u, (0, 1, 2, 3)))(q, k, v, bias)
        g_ref = jax.jit(jax.grad(loss_ref, (0, 1, 2, 3)))(q, k, v, bias)
        for gu, gf in zip(g_u, g_ref):
            err = float(jnp.abs(gu - gf).max())
            scale = float(jnp.abs(gf).max()) + 1e-6
            assert err / scale < 2e-4, (err, scale)
    finally:
        fa.set_interpret(prev_interpret)


def test_seq_parallel_cli_wiring():
    """--seq-parallel-size > 1 must actually reach the encoder: the model
    builder sets use_ring and the chosen impl (round-3 wiring-gap fix)."""
    from argparse import Namespace

    from unicore_tpu.models.bert import BertModel

    class _T:
        class _D:
            def pad(self):
                return 1

            def __len__(self):
                return 64

        dictionary = _D()

    args = Namespace(
        seq_parallel_size=4, seq_parallel_impl="ulysses",
        encoder_layers=2, encoder_embed_dim=64, encoder_ffn_embed_dim=128,
        encoder_attention_heads=4, max_seq_len=64, dropout=0.0,
        emb_dropout=0.0, attention_dropout=0.0, activation_dropout=0.0,
        pooler_dropout=0.0, post_ln=True,
    )
    model = BertModel.build_model(args, _T())
    assert model.use_ring is True
    assert model.seq_impl == "ulysses"
    args.seq_parallel_size = 1
    model = BertModel.build_model(args, _T())
    assert model.use_ring is False


def test_trainer_refuses_seq_axis_without_model_support():
    """A seq mesh axis with a model that can't use it would silently do
    replicated work — the Trainer must refuse loudly (round-3 review)."""
    from argparse import Namespace

    from unicore_tpu.losses import LOSS_REGISTRY
    from unicore_tpu.models.bert import BertModel
    from unicore_tpu.tasks.unicore_task import UnicoreTask
    from unicore_tpu.trainer import Trainer

    class _T(UnicoreTask):
        class _D:
            def pad(self):
                return 1

        dictionary = _D()

    args = Namespace(
        seed=1, bf16=False, fp16=False, bf16_sr=False,
        allreduce_fp32_grad=False, fp16_init_scale=4, fp16_scale_window=None,
        min_loss_scale=1e-4, clip_norm=0.0, per_sample_clip_norm=0.0,
        data_parallel_size=-1, model_parallel_size=1, seq_parallel_size=4,
        pipeline_parallel_size=1, expert_parallel_size=1,
        zero_shard_optimizer=False, optimizer="adam", lr_scheduler="fixed",
        lr=[1e-3], adam_betas="(0.9, 0.999)", adam_eps=1e-8,
        weight_decay=0.0, force_anneal=None, lr_shrink=0.1,
        warmup_updates=0, ema_decay=-1.0, validate_with_ema=False,
        max_update=10, update_freq=[1], donate_train_state=False,
        no_weight_decay_names="",
    )
    # a model that did NOT opt into sequence parallelism
    model = BertModel(
        vocab_size=64, padding_idx=1, encoder_layers=1,
        encoder_embed_dim=32, encoder_ffn_embed_dim=64,
        encoder_attention_heads=4, max_seq_len=32, post_ln=True,
    )
    with pytest.raises(ValueError, match="sequence parallelism"):
        Trainer(args, _T(args), model, LOSS_REGISTRY["masked_lm"](_T(args)))


@pytest.mark.slow  # tier-1 wall-clock budget (PR-4 convention): the deep-composition legs exceed the 'not slow' 870s ceiling on a 1-core CPU box
def test_ring_inside_pipeline_matches_plain_ring():
    """dp x pp x sp composition (round-4 verdict #3): pipelining the ring
    encoder must be a pure LAYOUT change — the GPipe stack with the
    sequence dim sharded over 'seq' and ring attention running INSIDE the
    stage shard_map matches the non-pipelined ring encoder, forward and
    gradients.  (Ring-vs-dense equivalence is covered separately by
    test_ring_encoder_matches_dense; comparing the pipelined ring against
    the DENSE path instead would conflate this test with the ring's own
    fp32 accumulation-order noise, which concentrates in token-summed
    projection-bias grads.)"""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    mesh = make_mesh(data=2, pipe=2, seq=2)
    set_global_mesh(mesh)

    B, L, E, H, LAYERS = 4, 64, 64, 4, 2
    mk = lambda pipeline: TransformerEncoder(
        encoder_layers=LAYERS, embed_dim=E, ffn_embed_dim=128,
        attention_heads=H, max_seq_len=L, use_ring=True,
        emb_dropout=0.0, dropout=0.0, attention_dropout=0.0,
        post_ln=True,
        pipeline_stages=2 if pipeline else 0, pipeline_microbatches=2,
    )
    enc_pipe, enc_plain = mk(True), mk(False)
    emb = jax.random.normal(jax.random.PRNGKey(0), (B, L, E))
    pm = jnp.asarray(
        (np.arange(L)[None, :] >= np.array([50, 64, 40, 64])[:, None])
        .astype(np.float32)
    )
    p_pipe = enc_pipe.init(
        {"params": jax.random.PRNGKey(1)}, emb, None, pm
    )["params"]
    p_plain = dict(enc_plain.init(
        {"params": jax.random.PRNGKey(2)}, emb, None, pm
    )["params"])
    stack = p_pipe["pipeline_stack"]
    for i in range(LAYERS):
        p_plain[f"layers_{i}"] = jax.tree_util.tree_map(
            lambda s, i=i: s[i], stack
        )
    for shared in ("emb_layer_norm", "relative_attention_bias"):
        if shared in p_pipe:
            p_plain[shared] = p_pipe[shared]

    o_pipe = jax.jit(
        lambda p, e: enc_pipe.apply({"params": p}, e, padding_mask=pm)
    )(p_pipe, emb)
    o_plain = jax.jit(
        lambda p, e: enc_plain.apply({"params": p}, e, padding_mask=pm)
    )(p_plain, emb)
    err = float(jnp.abs(o_pipe - o_plain).max())
    assert err < 1e-4, err

    # Gradients: the two programs schedule the SAME ring math differently
    # (scan-over-layers + pipe psum vs per-layer shard_maps), so fp32
    # reduction-order noise (~1e-6/element, the forward's level) reaches
    # early-layer grads through the later layers' ring backward and gets
    # amplified by cancellation in token-summed projection-bias grads
    # (measured ~5e-4 on this config; layer-1 leaves, whose cotangents
    # never cross a ring backward, agree to ~1e-6).  Hence the 1e-3 bound.
    g_pipe = jax.jit(jax.grad(
        lambda p: jnp.sum(enc_pipe.apply({"params": p}, emb,
                                         padding_mask=pm) ** 2)
    ))(p_pipe)
    g_plain = jax.jit(jax.grad(
        lambda p: jnp.sum(enc_plain.apply({"params": p}, emb,
                                          padding_mask=pm) ** 2)
    ))(p_plain)
    g_plain_stacked = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs),
        *[g_plain[f"layers_{i}"] for i in range(LAYERS)],
    )
    for a, b in zip(
        jax.tree_util.tree_leaves(g_pipe["pipeline_stack"]),
        jax.tree_util.tree_leaves(g_plain_stacked),
    ):
        scale = max(1.0, float(jnp.abs(b).max()))
        assert float(jnp.abs(a - b).max()) / scale < 1e-3
    a = g_pipe["relative_attention_bias"]["embedding"]
    b = g_plain["relative_attention_bias"]["embedding"]
    scale = max(1.0, float(jnp.abs(b).max()))
    assert float(jnp.abs(a - b).max()) / scale < 1e-3
    # the last stage's leaves see no ring backward between them and the
    # loss: they must agree at fp32-noise level, pinning that the looser
    # bound above only covers accumulation-order noise, not a math bug
    last = jax.tree_util.tree_map(
        lambda s: s[-1], g_pipe["pipeline_stack"]
    )
    last_plain = g_plain[f"layers_{LAYERS - 1}"]
    for a, b in zip(
        jax.tree_util.tree_leaves(last),
        jax.tree_util.tree_leaves(last_plain),
    ):
        scale = max(1.0, float(jnp.abs(b).max()))
        assert float(jnp.abs(a - b).max()) / scale < 5e-5


def test_unimol_pair_encoder_row_sharded_seq():
    """Uni-Mol-family SP (round-4 verdict #3): seq_shard=True row-shards
    the evolving (B, H, L, L) pair stream over the 'seq' axis via GSPMD
    constraints.  Sharding constraints are semantics-preserving, so the
    outputs must match the unsharded run; the win is distribution of the
    dominant activation, which the dryrun leg exercises."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    from unicore_tpu.modules.transformer_encoder_with_pair import (
        TransformerEncoderWithPair,
    )

    mesh = make_mesh(data=2, seq=4)
    set_global_mesh(mesh)
    B, L, D, H = 2, 32, 64, 8  # L % seq == 0
    mk = lambda shard: TransformerEncoderWithPair(
        encoder_layers=2, embed_dim=D, ffn_embed_dim=128,
        attention_heads=H, emb_dropout=0.0, dropout=0.0,
        attention_dropout=0.0, activation_dropout=0.0, max_seq_len=L,
        seq_shard=shard,
    )
    enc_s, enc_r = mk(True), mk(False)
    r = np.random.RandomState(0)
    emb = jnp.asarray(r.randn(B, L, D), jnp.float32)
    bias = jnp.asarray(r.randn(B, H, L, L), jnp.float32)
    pm = jnp.asarray(
        (np.arange(L)[None, :] >= np.array([25, 32])[:, None])
        .astype(np.float32)
    )
    params = enc_s.init({"params": jax.random.PRNGKey(0)}, emb, bias, pm)

    run_s = jax.jit(lambda p: enc_s.apply(p, emb, bias, pm))
    run_r = jax.jit(lambda p: enc_r.apply(p, emb, bias, pm))
    outs_s, outs_r = run_s(params), run_r(params)
    names = ("x", "pair_rep", "delta", "x_norm", "delta_norm")
    for name, a, b in zip(names, outs_s, outs_r):
        scale = max(1.0, float(jnp.abs(b).max()))
        assert float(jnp.abs(a - b).max()) / scale < 1e-5, name

    # gradients flow through the constrained program and match
    def loss(enc):
        def f(p):
            x, pr, d, xn, dn = enc.apply(p, emb, bias, pm)
            return jnp.sum(x ** 2) + jnp.sum(d ** 2) + xn + dn
        return f

    g_s = jax.jit(jax.grad(loss(enc_s)))(params)
    g_r = jax.jit(jax.grad(loss(enc_r)))(params)
    for a, b in zip(
        jax.tree_util.tree_leaves(g_s), jax.tree_util.tree_leaves(g_r)
    ):
        scale = max(1.0, float(jnp.abs(b).max()))
        assert float(jnp.abs(a - b).max()) / scale < 1e-5


def test_trainer_accepts_seq_shard_model():
    """The Trainer's seq-axis refusal must NOT fire for a model that opts
    into GSPMD pair-stream sharding (seq_shard) without use_ring — a REAL
    Trainer construction, so regressing the gate clause fails here."""
    from argparse import Namespace

    from unicore_tpu.losses import LOSS_REGISTRY
    from unicore_tpu.models.unimol import UniMolModel
    from unicore_tpu.tasks.unicore_task import UnicoreTask
    from unicore_tpu.trainer import Trainer

    class _T(UnicoreTask):
        class _D:
            def pad(self):
                return 0

        dictionary = _D()

    args = Namespace(
        seed=1, bf16=False, fp16=False, bf16_sr=False,
        allreduce_fp32_grad=False, fp16_init_scale=4, fp16_scale_window=None,
        min_loss_scale=1e-4, clip_norm=0.0, per_sample_clip_norm=0.0,
        data_parallel_size=-1, model_parallel_size=1, seq_parallel_size=4,
        pipeline_parallel_size=1, expert_parallel_size=1,
        zero_shard_optimizer=False, optimizer="adam", lr_scheduler="fixed",
        lr=[1e-3], adam_betas="(0.9, 0.999)", adam_eps=1e-8,
        weight_decay=0.0, force_anneal=None, lr_shrink=0.1,
        warmup_updates=0, ema_decay=-1.0, validate_with_ema=False,
        max_update=10, update_freq=[1], donate_train_state=False,
        no_weight_decay_names="",
        masked_token_loss=1.0, masked_coord_loss=1.0, masked_dist_loss=1.0,
        x_norm_loss=0.01, delta_pair_repr_norm_loss=0.01,
    )
    model = UniMolModel(
        vocab_size=16, padding_idx=0, encoder_layers=1,
        encoder_embed_dim=32, encoder_ffn_embed_dim=64,
        encoder_attention_heads=4, max_seq_len=16, gaussian_kernels=8,
        seq_shard=True,
    )
    # must construct without the seq-axis ValueError
    Trainer(args, _T(args), model, LOSS_REGISTRY["unimol"](_T(args)))


def test_pair_encoder_pipeline_composes_with_seq_shard():
    """dp x pp x sp for the unimol family (round-4 verdict #3): gpipe goes
    MANUAL over every mesh axis except 'seq', which stays AUTO, so the
    row-sharded pair stream rides the pipeline ring.  Same params with
    seq_shard on vs off (off = replicated over the live seq axis):
    outputs and gradients must match."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    from unicore_tpu.modules.transformer_encoder_with_pair import (
        TransformerEncoderWithPair,
    )

    mesh = make_mesh(data=2, pipe=2, seq=2)
    set_global_mesh(mesh)
    B, L, D, H = 4, 32, 64, 8
    mk = lambda shard: TransformerEncoderWithPair(
        encoder_layers=2, embed_dim=D, ffn_embed_dim=128,
        attention_heads=H, emb_dropout=0.0, dropout=0.0,
        attention_dropout=0.0, activation_dropout=0.0, max_seq_len=L,
        pipeline_stages=2, pipeline_microbatches=2, seq_shard=shard,
    )
    enc_s, enc_r = mk(True), mk(False)
    r = np.random.RandomState(0)
    emb = jnp.asarray(r.randn(B, L, D), jnp.float32)
    bias = jnp.asarray(r.randn(B, H, L, L), jnp.float32)
    pm = jnp.asarray(
        (np.arange(L)[None, :] >= np.array([25, 32, 30, 28])[:, None])
        .astype(np.float32)
    )
    params = enc_s.init({"params": jax.random.PRNGKey(0)}, emb, bias, pm)
    run = lambda enc: jax.jit(lambda p: enc.apply(p, emb, bias, pm))
    outs_s, outs_r = run(enc_s)(params), run(enc_r)(params)
    names = ("x", "pair_rep", "delta", "x_norm", "delta_norm")
    for name, a, b in zip(names, outs_s, outs_r):
        scale = max(1.0, float(jnp.abs(b).max()))
        assert float(jnp.abs(a - b).max()) / scale < 1e-5, name

    def loss(enc):
        def f(p):
            x, pr, d, xn, dn = enc.apply(p, emb, bias, pm)
            return jnp.sum(x ** 2) + jnp.sum(d ** 2) + xn + dn
        return f

    g_s = jax.jit(jax.grad(loss(enc_s)))(params)
    g_r = jax.jit(jax.grad(loss(enc_r)))(params)
    for a, b in zip(
        jax.tree_util.tree_leaves(g_s), jax.tree_util.tree_leaves(g_r)
    ):
        scale = max(1.0, float(jnp.abs(b).max()))
        assert float(jnp.abs(a - b).max()) / scale < 1e-5


@pytest.mark.slow  # tier-1 wall-clock budget (PR-4 convention): the deep-composition legs exceed the 'not slow' 870s ceiling on a 1-core CPU box
def test_evoformer_stack_row_sharded_seq():
    """Evoformer SP: seq_shard row-shards the msa (residue dim) and pair
    (lead-row dim) streams over 'seq' via GSPMD constraints — semantics
    preserved vs the unsharded stack, gradients included."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    from unicore_tpu.modules.evoformer import EvoformerStack

    mesh = make_mesh(data=2, seq=4)
    set_global_mesh(mesh)
    B, R, L = 2, 3, 16  # L % seq == 0
    mk = lambda shard: EvoformerStack(
        num_blocks=2, msa_dim=32, pair_dim=16, msa_heads=4, pair_heads=4,
        dropout=0.0, remat=False, seq_shard=shard,
    )
    enc_s, enc_r = mk(True), mk(False)
    r = np.random.RandomState(0)
    msa = jnp.asarray(r.randn(B, R, L, 32), jnp.float32)
    pair = jnp.asarray(r.randn(B, L, L, 16), jnp.float32)
    msa_mask = jnp.asarray((r.rand(B, R, L) > 0.2).astype(np.float32))
    pair_mask = jnp.asarray((r.rand(B, L, L) > 0.2).astype(np.float32))
    params = enc_s.init(
        {"params": jax.random.PRNGKey(0)}, msa, pair, msa_mask, pair_mask,
        False,
    )
    run = lambda enc: jax.jit(
        lambda p: enc.apply(p, msa, pair, msa_mask, pair_mask, False)
    )
    (m_s, z_s), (m_r, z_r) = run(enc_s)(params), run(enc_r)(params)
    for a, b in ((m_s, m_r), (z_s, z_r)):
        scale = max(1.0, float(jnp.abs(b).max()))
        assert float(jnp.abs(a - b).max()) / scale < 1e-5

    def loss(enc):
        def f(p):
            m, z = enc.apply(p, msa, pair, msa_mask, pair_mask, False)
            return jnp.sum(m ** 2) + jnp.sum(z ** 2)
        return f

    g_s = jax.jit(jax.grad(loss(enc_s)))(params)
    g_r = jax.jit(jax.grad(loss(enc_r)))(params)
    for a, b in zip(
        jax.tree_util.tree_leaves(g_s), jax.tree_util.tree_leaves(g_r)
    ):
        scale = max(1.0, float(jnp.abs(b).max()))
        assert float(jnp.abs(a - b).max()) / scale < 1e-5


@pytest.mark.slow  # tier-1 wall-clock budget (PR-4 convention): the deep-composition legs exceed the 'not slow' 870s ceiling on a 1-core CPU box
def test_evoformer_pipeline_composes_with_seq_shard():
    """dp x pp x sp for the evoformer family (round-4 verdict #3): the
    row-sharded msa/pair streams ride the GPipe ring with 'seq' left as
    an AUTO axis inside the pipeline shard_map.  Same params, seq_shard
    on vs off: outputs and gradients must match."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    from unicore_tpu.modules.evoformer import EvoformerStack

    mesh = make_mesh(data=2, pipe=2, seq=2)
    set_global_mesh(mesh)
    B, R, L = 4, 3, 16
    mk = lambda shard: EvoformerStack(
        num_blocks=2, msa_dim=32, pair_dim=16, msa_heads=4, pair_heads=4,
        dropout=0.0, remat=False, pipeline_stages=2,
        pipeline_microbatches=2, seq_shard=shard,
    )
    enc_s, enc_r = mk(True), mk(False)
    r = np.random.RandomState(0)
    msa = jnp.asarray(r.randn(B, R, L, 32), jnp.float32)
    pair = jnp.asarray(r.randn(B, L, L, 16), jnp.float32)
    msa_mask = jnp.asarray((r.rand(B, R, L) > 0.2).astype(np.float32))
    pair_mask = jnp.asarray((r.rand(B, L, L) > 0.2).astype(np.float32))
    params = enc_s.init(
        {"params": jax.random.PRNGKey(0)}, msa, pair, msa_mask, pair_mask,
        False,
    )
    run = lambda enc: jax.jit(
        lambda p: enc.apply(p, msa, pair, msa_mask, pair_mask, False)
    )
    (m_s, z_s), (m_r, z_r) = run(enc_s)(params), run(enc_r)(params)
    for a, b in ((m_s, m_r), (z_s, z_r)):
        scale = max(1.0, float(jnp.abs(b).max()))
        assert float(jnp.abs(a - b).max()) / scale < 1e-5

    def loss(enc):
        def f(p):
            m, z = enc.apply(p, msa, pair, msa_mask, pair_mask, False)
            return jnp.sum(m ** 2) + jnp.sum(z ** 2)
        return f

    g_s = jax.jit(jax.grad(loss(enc_s)))(params)
    g_r = jax.jit(jax.grad(loss(enc_r)))(params)
    for a, b in zip(
        jax.tree_util.tree_leaves(g_s), jax.tree_util.tree_leaves(g_r)
    ):
        scale = max(1.0, float(jnp.abs(b).max()))
        assert float(jnp.abs(a - b).max()) / scale < 1e-5


# ---------------------------------------------------------------------------
# seq-sharded flash route (round-4 verdict #2): with seq_shard on, evoformer
# attention keeps running the Pallas kernel — per shard, inside a shard_map
# over 'seq' — instead of surrendering to the O(L^2) XLA path.
# ---------------------------------------------------------------------------


@pytest.fixture()
def _interpret_kernels():
    from unicore_tpu.ops import flash_attention as fa
    from unicore_tpu.ops._pallas import interpret_enabled

    prev = interpret_enabled()
    # match _flash_ok's backend gate: on real hardware
    # these tests must exercise the actual Mosaic lowering, not interpret
    fa.set_interpret(not on_tpu())
    yield
    fa.set_interpret(prev)


def _gated_sharded_vs_xla(mod_sharded, mod_xla, inputs, tol=2e-4):
    """Init once, run the seq-sharded kernel route and the (route-proven)
    XLA fallback on the same params; outputs and grads wrt params AND
    array inputs must agree."""
    from unicore_tpu.modules import evoformer as evo

    params = mod_sharded.init({"params": jax.random.PRNGKey(0)}, *inputs)

    evo._ROUTE_STATS.clear()
    run_s = jax.jit(lambda p, *a: mod_sharded.apply(p, *a))
    out_s = run_s(params, *inputs)
    assert evo._ROUTE_STATS.get("seq_flash", 0) >= 1, evo._ROUTE_STATS
    out_x = jax.jit(lambda p, *a: mod_xla.apply(p, *a))(params, *inputs)
    scale = float(jnp.abs(out_x).max()) + 1e-6
    assert float(jnp.abs(out_s - out_x).max()) / scale < tol

    # grads wrt params and the differentiable array inputs (q_x/kv_x/bias)
    def loss(mod):
        def f(p, *a):
            return jnp.sum(mod.apply(p, *a) ** 2)
        return f

    n_diff = min(3, len(inputs)) + 1  # params, q_x, kv_x, maybe bias
    argnums = tuple(range(n_diff))
    g_s = jax.jit(jax.grad(loss(mod_sharded), argnums))(params, *inputs)
    g_x = jax.jit(jax.grad(loss(mod_xla), argnums))(params, *inputs)
    for a, b in zip(
        jax.tree_util.tree_leaves(g_s), jax.tree_util.tree_leaves(g_x)
    ):
        s = float(jnp.abs(b).max()) + 1e-6
        assert float(jnp.abs(a - b).max()) / s < tol


def test_gated_attention_seq_sharded_rows_mode(_interpret_kernels):
    """MSA-row layout: the ATTENDED dim is sharded (GatedAttention rows
    mode) — q splits by rows, k/v gather at the shard_map boundary, the
    grouped bias splits on its query-row dim."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    from unicore_tpu.modules.evoformer import GatedAttention

    mesh = make_mesh(data=2, seq=4)
    set_global_mesh(mesh)
    B, R, L, D, H = 2, 2, 512, 16, 2  # L/seq = 128: per-shard tiles fit
    r = np.random.RandomState(0)
    q_x = jnp.asarray(r.randn(B, R, L, D), jnp.float32)
    bias = jnp.asarray(r.randn(B, H, L, L), jnp.float32)  # G = B slabs
    kv_mask = jnp.asarray((r.rand(B, R, L) > 0.15).astype(np.float32))

    mk = lambda **kw: GatedAttention(D, H, **kw)
    _gated_sharded_vs_xla(
        mk(seq_dim=2),
        mk(use_flash=False),
        (q_x, q_x, bias, kv_mask),
    )


def test_gated_attention_seq_sharded_lead_mode(_interpret_kernels):
    """Triangle-starting layout: a LEAD dim is sharded — every operand
    (except the shared bias slab) splits, each shard runs the kernel on
    its own lead rows with full-length attention."""
    if len(jax.devices()) < 2:
        pytest.skip("needs 2 devices")
    from unicore_tpu.modules.evoformer import GatedAttention

    mesh = make_mesh(data=1, seq=2, devices=jax.devices()[:2])
    set_global_mesh(mesh)
    B, L, D, H = 1, 256, 8, 1  # pair (B, I=L, J=L, D), dim 1 sharded
    r = np.random.RandomState(0)
    q_x = jnp.asarray(r.randn(B, L, L, D), jnp.float32)
    bias = jnp.asarray(r.randn(B, H, L, L), jnp.float32)
    kv_mask = jnp.asarray((r.rand(B, L, L) > 0.15).astype(np.float32))

    mk = lambda **kw: GatedAttention(D, H, **kw)
    _gated_sharded_vs_xla(
        mk(seq_dim=1),
        mk(use_flash=False),
        (q_x, q_x, bias, kv_mask),
    )


@pytest.mark.slow  # tier-1 wall-clock budget (PR-4 convention): the deep-composition legs exceed the 'not slow' 870s ceiling on a 1-core CPU box
def test_evoformer_stack_seq_shard_keeps_kernel(_interpret_kernels):
    """Full block under seq_shard with kernel-eligible L: MSA-row,
    tri-start and tri-end attention all take the per-shard kernel route
    (route counter), column attention (R=2, waste-gated) falls back to
    XLA, and the whole sharded stack matches the unsharded XLA stack."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    from unicore_tpu.modules import evoformer as evo
    from unicore_tpu.ops import flash_attention as fa

    mesh = make_mesh(data=2, seq=4)
    set_global_mesh(mesh)
    B, R, L = 2, 2, 512
    mk = lambda shard: evo.EvoformerStack(
        num_blocks=1, msa_dim=16, pair_dim=8, msa_heads=2, pair_heads=1,
        dropout=0.0, remat=False, seq_shard=shard,
    )
    r = np.random.RandomState(0)
    msa = jnp.asarray(r.randn(B, R, L, 16), jnp.float32)
    pair = jnp.asarray(r.randn(B, L, L, 8), jnp.float32)
    msa_mask = jnp.asarray((r.rand(B, R, L) > 0.15).astype(np.float32))
    pair_mask = jnp.asarray((r.rand(B, L, L) > 0.15).astype(np.float32))
    enc_s = mk(True)
    params = enc_s.init(
        {"params": jax.random.PRNGKey(0)}, msa, pair, msa_mask, pair_mask,
        False,
    )

    evo._ROUTE_STATS.clear()
    m_s, z_s = jax.jit(
        lambda p: enc_s.apply(p, msa, pair, msa_mask, pair_mask, False)
    )(params)
    # msa_row (rows), tri_start (lead), tri_end (rows) ride the kernel;
    # col attention's tiny R is waste-gated onto XLA
    assert evo._ROUTE_STATS.get("seq_flash", 0) == 3, evo._ROUTE_STATS
    assert evo._ROUTE_STATS.get("xla", 0) == 1, evo._ROUTE_STATS

    # unsharded reference on the XLA path (interpret off closes the gate
    # on CPU; kernel-vs-XLA parity is test_evoformer_flash's job)
    fa.set_interpret(False)
    set_global_mesh(None)
    m_r, z_r = jax.jit(
        lambda p: mk(False).apply(p, msa, pair, msa_mask, pair_mask, False)
    )(params)
    for a, b in ((m_s, m_r), (z_s, z_r)):
        scale = max(1.0, float(jnp.abs(b).max()))
        assert float(jnp.abs(a - b).max()) / scale < 2e-4
