"""EvaByte's mechanisms at sizes a CPU holds: the chunk summaries and the
joint softmax against a per-query loop over the visible set (and window 0
against ``mha_reference`` under a causal mask), the flash form against
XLA's own softmax, rotary against the complex-number form, the shifted
targets at a row's end, the byte tokenizer's round trip, the norm's unit
offset, the gated layer in chunks, the shares of the heads adding up to
the whole layer, and the model through task and trainer."""

from argparse import Namespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from unicore_tpu.ops import _pallas
from unicore_tpu.ops.eva_attention import (
    NEG,
    eva_agg,
    eva_prep_kv,
    kernel_blocks,
    kernel_map,
    key_counts,
    visibility_bias,
    visible_blocks,
)


def _qkv(B, H, L, D, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    q, k, v = (jax.random.normal(s, (B, H, L, D), jnp.float32) for s in keys[:3])
    mu, phi = (jax.random.normal(s, (H, D), jnp.float32) for s in keys[3:])
    return q, k, v, mu, phi


def per_query(q, k, v, mu, phi, window, chunk, scale):
    """The equations, one query at a time, in float64 numpy."""
    q, k, v, mu, phi = (np.asarray(a, np.float64) for a in (q, k, v, mu, phi))
    B, H, L, D = q.shape
    out = np.zeros_like(q)

    def softmax(x):
        e = np.exp(x - x.max())
        return e / e.sum()

    for b in range(B):
        for h in range(H):
            ks, vs = [], []
            for c in range(L // chunk):
                kc = k[b, h, c * chunk:(c + 1) * chunk]
                vc = v[b, h, c * chunk:(c + 1) * chunk]
                ks.append(softmax(scale * kc @ mu[h]) @ kc)
                vs.append(softmax(scale * kc @ phi[h]) @ vc)
            for i in range(L):
                w = i // window
                keys = [k[b, h, j] for j in range(w * window, i + 1)]
                vals = [v[b, h, j] for j in range(w * window, i + 1)]
                for c in range(L // chunk):
                    if (c * chunk) // window < w:
                        keys.append(ks[c])
                        vals.append(vs[c])
                p = softmax(scale * np.asarray(keys) @ q[b, h, i])
                out[b, h, i] = p @ np.asarray(vals)
    return out


@pytest.mark.parametrize("B,H,L,window,chunk", [
    (1, 2, 96, 32, 4),    # three windows
    (2, 1, 64, 16, 8),    # four windows, two rows (window-major batch rows)
    (1, 1, 32, 32, 4),    # one window: plain causal attention
])
def test_summaries_and_joint_softmax_against_a_per_query_loop(B, H, L, window, chunk):
    q, k, v, mu, phi = _qkv(B, H, L, 8)
    scale = 8 ** -0.5
    k_sum, v_sum = eva_prep_kv(k, v, mu, phi, chunk, scale)
    got = eva_agg(q, k, v, k_sum, v_sum, window, chunk, scale)
    want = per_query(q, k, v, mu, phi, window, chunk, scale)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5, rtol=2e-5)


def test_window_zero_is_plain_causal_attention():
    from unicore_tpu.modules.multihead_attention import causal_bias
    from unicore_tpu.ops.flash_attention import mha_reference

    q, k, v, mu, phi = _qkv(2, 2, 96, 8, seed=3)
    scale = 8 ** -0.5
    k_sum, v_sum = eva_prep_kv(k, v, mu, phi, 4, scale)
    got = eva_agg(q, k, v, k_sum, v_sum, 32, 4, scale)[:, :, :32]
    want = mha_reference(
        q[:, :, :32], k[:, :, :32], v[:, :, :32],
        bias=causal_bias(32, jnp.float32)[None, None], sm_scale=scale,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("L,window,chunk,skipped", [
    (384, 128, 16, 0),   # summaries padded to the tile; one block a window
    (1024, 256, 2, 2),   # blocks of (256, 384): 2 of 8 are NEG throughout
])
def test_flash_form_and_its_gradients_match_xlas_softmax(L, window, chunk, skipped):
    """The grouped-bias, block-mapped route through the blockwise kernels
    (interpret mode) against the same operands through XLA's softmax."""
    q, k, v, mu, phi = _qkv(2, 2, L, 16, seed=1)
    scale = 16 ** -0.5
    seen = kernel_map(L, window, chunk)[2]
    assert seen.size - int(seen.sum()) == skipped

    def loss(q, k, v, mu, phi):
        k_sum, v_sum = eva_prep_kv(k, v, mu, phi, chunk, scale)
        o = eva_agg(q, k, v, k_sum, v_sum, window, chunk, scale)
        return jnp.sum(o * jnp.cos(jnp.arange(o.size).reshape(o.shape))), o

    plain = jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True)
    was = _pallas._override
    (want, o_want), g_want = plain(q, k, v, mu, phi)
    _pallas.set_interpret(True)
    try:
        (got, o_got), g_got = plain(q, k, v, mu, phi)
    finally:
        _pallas.set_interpret(was)
    np.testing.assert_allclose(np.asarray(o_got), np.asarray(o_want), atol=2e-5)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)


def test_visibility_bias_and_key_counts():
    bias = np.asarray(visibility_bias(3, 8, 2, 8, jnp.float32))
    assert bias.shape == (3, 1, 8, 16)
    seen = bias == 0
    for w in range(3):
        for i in range(8):
            assert list(seen[w, 0, i, :8]) == [j <= i for j in range(8)]
            # six chunks in the row (two a window) and two columns of padding
            assert list(seen[w, 0, i, 8:]) == [c < 2 * w for c in range(8)]
    counts = key_counts(24, 8, 4)
    assert counts["visible"] == int(seen[:, 0, :, :].sum())
    assert counts["windows"] == 3 and counts["chunks"] == 6
    # the kernel form pads the row's summaries to its tile of 128; a window
    # the tile does not divide is one block, scored whole
    assert counts["computed"] == 24 * (8 + 128)
    # the real row: the keys of the blocks the kernels are told to visit
    real = key_counts(32768, 2048, 16)
    assert kernel_blocks(2048, 4096) == (256, 512)
    assert real["computed"] == 608 * 256 * 512 == 79_691_776
    assert real["visible"] == 16 * 2048 * 2049 // 2 + 2048 * 128 * 120
    assert real["computed"] / real["visible"] == pytest.approx(1.2255, abs=5e-5)


@pytest.mark.parametrize("L,window,chunk,bq,bk", [
    (1024, 256, 16, 128, 128),
    (2048, 512, 16, 256, 128),
    (768, 256, 2, 128, 160),   # a key block that straddles keys and summaries
    (1024, 256, 16, 64, 96),
    (128, 128, 16, 128, 128),  # one window: no summary is seen
])
def test_the_block_map_is_the_bias_slab_by_slab(L, window, chunk, bq, bk):
    """A block is visited iff its slab of the bias is not NEG throughout."""
    W = L // window
    n_sum = 128 * -(-L // chunk // 128)
    bias = np.asarray(
        visibility_bias(W, window, window // chunk, n_sum, jnp.float32)
    )[:, 0]
    slabs = bias.reshape(W, window // bq, bq, (window + n_sum) // bk, bk)
    want = (slabs > NEG).any(axis=(2, 4))
    got = visible_blocks(L, window, chunk, bq, bk)
    assert got.dtype == bool and np.array_equal(got, want)
    assert 0 < got.sum() < got.size


def test_the_real_rows_map_from_shapes_alone():
    """16 windows of 2,048 at blocks of (256, 512): 608 of 1,024 blocks hold
    a visible key, and 28 of the 128 key blocks are seen by no query block
    (a window's own and later windows' summaries; the padding)."""
    bq, bk, seen = kernel_map(32768, 2048, 16)
    assert (bq, bk) == (256, 512) and seen.shape == (16, 8, 8)
    assert np.array_equal(seen, visible_blocks(32768, 2048, 16, 256, 512))
    assert int(seen.sum()) == 608
    assert int((~seen.any(axis=1)).sum()) == 28
    # every query block begins at a key all of its queries see
    assert seen[:, :, 0].all()


def test_rotary_is_the_complex_rotation():
    from unicore_tpu.modules.rotary import apply_rotary

    x = jax.random.normal(jax.random.PRNGKey(2), (2, 3, 40, 16), jnp.float32)
    got = np.asarray(apply_rotary(x, jnp.arange(40), 1e5))
    xn = np.asarray(x, np.float64)
    z = xn[..., :8] + 1j * xn[..., 8:]
    angle = np.arange(40)[:, None] * 1e5 ** (-np.arange(8) * 2.0 / 16)
    z = z * np.exp(1j * angle)
    np.testing.assert_allclose(got[..., :8], z.real, atol=1e-4)
    np.testing.assert_allclose(got[..., 8:], z.imag, atol=1e-4)
    # position 0 is left as it is, and a rotation keeps the norm
    np.testing.assert_allclose(got[:, :, 0], xn[:, :, 0], atol=1e-6)
    np.testing.assert_allclose(
        np.linalg.norm(got, axis=-1), np.linalg.norm(xn, axis=-1), rtol=1e-5)


def test_rotary_scores_depend_on_the_distance_only():
    from unicore_tpu.modules.rotary import apply_rotary

    q = jnp.tile(jax.random.normal(jax.random.PRNGKey(4), (1, 1, 1, 16)), (1, 1, 30, 1))
    k = jnp.tile(jax.random.normal(jax.random.PRNGKey(5), (1, 1, 1, 16)), (1, 1, 30, 1))
    pos = jnp.arange(30)
    s = np.asarray(jnp.einsum(
        "bhqd,bhkd->bhqk", apply_rotary(q, pos, 1e4), apply_rotary(k, pos, 1e4)))[0, 0]
    for d in (0, 1, 7):
        diag = np.diagonal(s, offset=-d)
        np.testing.assert_allclose(diag, diag[0], atol=1e-4)


def test_targets_shifted_by_one_to_m_and_dropped_past_the_rows_end():
    from unicore_tpu.losses.lm_cross_entropy import (
        chunked_lm_nll,
        shifted_targets,
    )

    target = jnp.asarray([[5, 6, 7, 8, 9], [4, 4, 0, 0, 0]])
    np.testing.assert_array_equal(
        shifted_targets(target, 1, 0), [[6, 7, 8, 9, 0], [4, 0, 0, 0, 0]])
    got = np.asarray(shifted_targets(target, 3, 0))
    assert got.shape == (2, 5, 3)
    np.testing.assert_array_equal(got[0, :, 0], [6, 7, 8, 9, 0])
    np.testing.assert_array_equal(got[0, :, 1], [7, 8, 9, 0, 0])
    np.testing.assert_array_equal(got[0, :, 2], [8, 9, 0, 0, 0])
    np.testing.assert_array_equal(got[1, 0], [4, 0, 0])
    # the chunked loss over (T, M) targets is the sum of M plain losses
    T, d, V, M = 10, 6, 11, 3
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((T, d)), jnp.float32)
    kernel = jnp.asarray(rng.standard_normal((d, M * V)), jnp.float32)
    tgt = jnp.asarray(rng.integers(1, V, (T, M)))
    valid = jnp.asarray(rng.random((T, M)) < 0.7)
    got = chunked_lm_nll(x, kernel, tgt, valid, 4)
    want = sum(
        chunked_lm_nll(x, kernel[:, m * V:(m + 1) * V], tgt[:, m], valid[:, m], 4)
        for m in range(M)
    )
    assert float(got) == pytest.approx(float(want), rel=1e-6)


def test_byte_tokenizer_round_trip():
    from unicore_tpu.data import ByteDictionary, ByteTokenizeDataset

    d = ByteDictionary()
    assert len(d) == 320 and d.pad() == 0 and d.eos() == 2
    text = "naïve bytes: 日本語 ok"
    ids = d.encode(text)
    assert ids.dtype == np.int64 and ids[-1] == d.eos()
    assert ids[:-1].min() >= 64 and ids.max() < 320
    assert len(ids) == len(text.encode("utf-8")) + 1
    assert d.decode(ids) == text
    data = ByteTokenizeDataset(["ab", "cdef"], max_seq_len=3)
    np.testing.assert_array_equal(data[0], [64 + 97, 64 + 98, 2])
    np.testing.assert_array_equal(data[1], [64 + 99, 64 + 100, 64 + 101])


def test_rms_norm_unit_offset():
    from unicore_tpu.modules import RMSNorm

    x = jax.random.normal(jax.random.PRNGKey(0), (3, 16), jnp.float32) * 4
    plain = RMSNorm(16, eps=1e-5)
    offset = RMSNorm(16, eps=1e-5, unit_offset=True)
    p0 = plain.init(jax.random.PRNGKey(1), x)
    p1 = offset.init(jax.random.PRNGKey(1), x)
    assert set(p1["params"]) == {"offset"} and not p1["params"]["offset"].any()
    np.testing.assert_allclose(plain.apply(p0, x), offset.apply(p1, x), rtol=1e-6)
    w = jnp.linspace(-0.5, 0.5, 16)
    got = offset.apply({"params": {"offset": w}}, x)
    want = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5) * (1 + w)
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_gated_mlp_in_chunks_is_the_whole_rows():
    from unicore_tpu.modules.gated_mlp import GatedMLP

    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, 16), jnp.float32)
    whole, chunked = GatedMLP(16, 40), GatedMLP(16, 40, row_chunk=12)
    params = whole.init(jax.random.PRNGKey(1), x)
    assert params["params"]["fc1"]["kernel"].shape == (16, 80)
    assert params["params"]["fc2"]["kernel"].shape == (40, 16)
    w1, w2 = params["params"]["fc1"]["kernel"], params["params"]["fc2"]["kernel"]
    want = (jax.nn.silu(x @ w1[:, :40]) * (x @ w1[:, 40:])) @ w2
    np.testing.assert_allclose(whole.apply(params, x), want, atol=1e-6)
    loss = lambda m: lambda p: jnp.sum(jnp.sin(m.apply(p, x)))
    a, ga = jax.value_and_grad(loss(whole))(params)
    b, gb = jax.value_and_grad(loss(chunked))(params)
    assert float(a) == pytest.approx(float(b), rel=1e-6)
    for u, v in zip(jax.tree_util.tree_leaves(ga), jax.tree_util.tree_leaves(gb)):
        np.testing.assert_allclose(u, v, atol=1e-5)
    with pytest.raises(ValueError, match="whole chunks"):
        GatedMLP(16, 40, row_chunk=10).apply(params, x)


@pytest.mark.parametrize("shares", [2, 4])
def test_the_shares_of_the_heads_add_up_to_the_whole_layer(shares):
    """Section 4's test of the cut: each share holds whole heads (their
    columns of q / k / v, their rows of the pooling vectors and of the
    output projection), and the shares' outputs add up to the whole
    layer's.  (The feed-forward layer is computed alike on every share and
    counted once: it is not divided.)"""
    from unicore_tpu.modules.eva_attention import EvaAttention

    H, D, d = 8, 8, 32
    sizes = dict(head_dim=D, window_size=16, chunk_size=4, rope_theta=1e4)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 48, d), jnp.float32)
    whole = EvaAttention(d, num_heads=H, **sizes)
    params = whole.init(jax.random.PRNGKey(1), x)["params"]
    params = jax.tree_util.tree_map(  # pooling vectors that matter
        lambda a: a * 20 if a.shape == (H, D) else a, params)
    want = whole.apply({"params": params}, x)
    held = H // shares
    total = 0.0
    for r in range(shares):
        cols = slice(r * held * D, (r + 1) * held * D)
        mine = {
            "q_proj": {"kernel": params["q_proj"]["kernel"][:, cols]},
            "k_proj": {"kernel": params["k_proj"]["kernel"][:, cols]},
            "v_proj": {"kernel": params["v_proj"]["kernel"][:, cols]},
            "adaptive_mu_k": params["adaptive_mu_k"][r * held:(r + 1) * held],
            "adaptive_phi": params["adaptive_phi"][r * held:(r + 1) * held],
            "out_proj": {"kernel": params["out_proj"]["kernel"][cols]},
        }
        total = total + EvaAttention(d, num_heads=held, **sizes).apply(
            {"params": mine}, x)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want), atol=2e-6)


def test_a_row_that_is_not_whole_windows_is_refused():
    from unicore_tpu.modules.eva_attention import EvaAttention

    layer = EvaAttention(16, num_heads=2, head_dim=8, window_size=16,
                         chunk_size=4, rope_theta=1e4)
    with pytest.raises(ValueError, match="whole windows"):
        layer.init(jax.random.PRNGKey(0), jnp.zeros((1, 40, 16)))


# -- the model through task, loss and trainer -----------------------------------

def _args(**over):
    args = Namespace(
        arch="evabyte_tiny", seed=1, data="/nonexistent", tokenizer="bytes",
        tokens_per_sample=96, seq_pad_multiple=8, max_seq_len=96,
    )
    for k, v in over.items():
        setattr(args, k, v)
    return args


def _tiny(**over):
    from unicore_tpu.models import ARCH_MODEL_REGISTRY
    from unicore_tpu.models.evabyte import evabyte_tiny_architecture
    from unicore_tpu.tasks.causal_lm import CausalLMTask

    args = _args(**over)
    evabyte_tiny_architecture(args)
    task = CausalLMTask.setup_task(args)
    return ARCH_MODEL_REGISTRY["evabyte_tiny"].build_model(args, task), task


def test_tiny_model_shapes_shares_and_loss():
    from unicore_tpu.losses import LOSS_REGISTRY

    model, task = _tiny(attention_shares=2, layers_held=2)
    assert len(task.dictionary) == 320 and model.vocab_size == 320
    assert model.heads_held == 2
    tokens = np.random.default_rng(0).integers(64, 320, (2, 96)).astype(np.int32)
    sample = {"net_input": {"src_tokens": tokens}, "target": tokens}
    params = model.init_params(jax.random.PRNGKey(0), sample)
    units = params["params"]["decoder"]["units"]
    assert units["layer_0"]["self_attn"]["q_proj"]["kernel"].shape == (2, 64, 32)
    assert units["layer_0"]["self_attn"]["adaptive_mu_k"].shape == (2, 2, 16)
    assert units["layer_1"]["mlp"]["fc1"]["kernel"].shape == (2, 64, 192)
    assert units["layer_0"]["norm"]["offset"].shape == (2, 64)
    assert params["params"]["lm_head"].shape == (64, 3 * 320)
    logits = model.apply(params, jnp.asarray(tokens))
    assert logits.shape == (2, 96, 960) and logits.dtype == jnp.float32
    loss = LOSS_REGISTRY["lm_cross_entropy"](task)
    total, size, log = loss.forward(model, params, sample, train=True)
    # head m has 96 - m targets in each of the two rows
    assert float(size) == 2 * (95 + 94 + 93)
    # the chunked loss is the plain one over the same logits
    lp = jax.nn.log_softmax(logits.reshape(2, 96, 3, 320), axis=-1)
    want = 0.0
    for m in range(1, 4):
        picked = jnp.take_along_axis(
            lp[:, :96 - m, m - 1], jnp.asarray(tokens)[:, m:, None], axis=-1)
        want = want - float(picked.sum())
    assert float(total) == pytest.approx(want, rel=1e-5)
    counts = key_counts(96, 32, 4)
    assert float(log["eva_keys_visible"]) == 2 * counts["visible"]
    marks = loss.trace_marks({k: float(v) for k, v in log.items()})
    assert marks["eva_keys"]["windows"] == 6 and marks["eva_keys"]["chunks"] == 48
    assert marks["eva_keys"]["keys_computed"] == 2 * counts["computed"]
    assert "moe_route" not in marks


def test_bad_shares_are_refused():
    with pytest.raises(ValueError, match="attention-shares"):
        _tiny(attention_shares=3)
    with pytest.raises(ValueError, match="layers-held"):
        _tiny(layers_held=9)


def test_tiny_model_trains_on_bytes_through_parser_task_and_trainer(tmp_path):
    """``unicore-tpu-train DATA --task causal_lm --tokenizer bytes --arch
    evabyte_tiny`` as its parser and task build it (no ``dict.txt`` in the
    data directory), through ``Trainer.train_step``: every block full of
    byte ids, a falling loss, the attention's key counts in the step's
    sums."""
    from unicore_tpu import options, tasks
    from unicore_tpu.data.indexed_dataset import make_builder
    from unicore_tpu.losses import LOSS_REGISTRY
    from unicore_tpu.models import build_model
    from unicore_tpu.trainer import Trainer

    rng = np.random.default_rng(0)
    words = ["alpha", "beta", "gamma", "delta", "épsilon"]
    builder = make_builder(str(tmp_path / "train"))
    for n in rng.integers(20, 120, 60):
        builder.add_item(" ".join(rng.choice(words, n)))
    builder.finalize()
    parser = options.get_training_parser()
    args = options.parse_args_and_arch(parser, [
        str(tmp_path), "--task", "causal_lm", "--tokenizer", "bytes",
        "--loss", "lm_cross_entropy", "--arch", "evabyte_tiny",
        "--tokens-per-sample", "96", "--attention-shares", "2",
        "--optimizer", "adam", "--lr-scheduler", "fixed", "--lr", "3e-3",
        "--no-weight-decay-names", "norm,adaptive_mu_k,adaptive_phi",
        "--batch-size", "1", "--max-update", "20", "--seed", "1",
    ])
    task = tasks.setup_task(args)
    task.load_dataset("train")
    model = build_model(args, task)
    assert model.heads_held == 2 and model.num_pred_heads == 3
    trainer = Trainer(args, task, model, LOSS_REGISTRY[args.loss](task))
    batches = task.get_batch_iterator(
        task.datasets["train"], batch_size=8, seed=1, epoch=1,
    ).next_epoch_itr(shuffle=True)
    sums = []
    for _, batch in zip(range(6), batches):
        tokens = np.asarray(batch["net_input"]["src_tokens"])
        assert tokens.shape == (8, 96)
        assert ((tokens >= 64) | (tokens == 2)).all() and (tokens == 2).any()
        trainer.train_step([batch])
        sums.append({k: float(v) for k, v in jax.device_get(trainer._macc).items()})
    per_update = np.diff([0.0] + [s["loss"] for s in sums])
    assert per_update[-1] < per_update[0]
    assert sums[-1]["sample_size"] == 6 * 8 * (95 + 94 + 93)
    counts = key_counts(96, 32, 4)
    assert sums[-1]["eva_rows"] == 6 * 8
    assert sums[-1]["eva_keys_visible"] == 6 * 8 * counts["visible"]
    assert sums[-1]["eva_windows"] == 6 * 8 * 3
