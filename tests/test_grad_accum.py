"""Grad-accumulation: the stacked-scan path (one compiled program) must
match sequential micro-steps bit-for-bit."""

import jax
import numpy as np
import jax.numpy as jnp
from argparse import Namespace
from unicore_tpu.losses import LOSS_REGISTRY
from unicore_tpu.models.bert import BertModel
from unicore_tpu.tasks.unicore_task import UnicoreTask
from unicore_tpu.trainer import Trainer

def mk_args():
    return Namespace(seed=1,bf16=False,fp16=False,bf16_sr=False,allreduce_fp32_grad=False,
        fp16_init_scale=4,fp16_scale_window=None,min_loss_scale=1e-4,clip_norm=1.0,
        per_sample_clip_norm=0.0,data_parallel_size=-1,model_parallel_size=1,seq_parallel_size=1,
        pipeline_parallel_size=1,expert_parallel_size=1,zero_shard_optimizer=False,
        optimizer="adam",lr_scheduler="fixed",lr=[1e-3],adam_betas="(0.9, 0.999)",adam_eps=1e-8,
        weight_decay=0.0,force_anneal=None,lr_shrink=0.1,warmup_updates=0,ema_decay=-1.0,
        validate_with_ema=False,max_update=100,update_freq=[2],donate_train_state=False)

class T(UnicoreTask):
    class _D:
        def pad(self): return 1
    dictionary=_D()

def mk(shape_seed, width=32):
    r = np.random.RandomState(shape_seed)
    tok = r.randint(4, 64, size=(8, width)).astype(np.int64)
    tgt = np.where(r.rand(8, width) < 0.2, tok, 1).astype(np.int64)
    return {"net_input": {"src_tokens": tok}, "target": tgt}

def run(force_seq):
    args = mk_args()
    model = BertModel(vocab_size=64,padding_idx=1,encoder_layers=2,encoder_embed_dim=32,
        encoder_ffn_embed_dim=64,encoder_attention_heads=4,max_seq_len=32,post_ln=True,
        dropout=0.0, emb_dropout=0.0, attention_dropout=0.0)
    tr = Trainer(args, T(args), model, LOSS_REGISTRY["masked_lm"](T(args)))
    tr.init_state(mk(1))
    if force_seq:
        tr._try_stack_microbatches = (
            lambda *a, **kw: None  # force micro-step path
        )
    tr.train_step([mk(1), mk(2)])
    leaf = jax.tree_util.tree_leaves(tr._state["params"])[0]
    macc = {k: float(v) for k, v in jax.device_get(tr._macc).items()}
    return np.asarray(jax.device_get(leaf)), macc


def test_scan_accumulation_matches_sequential():
    p_scan, m_scan = run(False)
    p_seq, m_seq = run(True)
    assert np.abs(p_scan - p_seq).max() < 1e-6
    for k in m_scan:
        assert abs(m_scan[k] - m_seq[k]) < 1e-3, k



def test_per_sample_clip_clips_each_sample():
    """--per-sample-clip-norm clips every SAMPLE's gradient before
    accumulation (reference per_sample_clip_grad_norm,
    optim/unicore_optimizer.py:110-130) — not the whole micro-batch."""
    from unicore_tpu import utils as U

    args = mk_args()
    args.per_sample_clip_norm = 0.01  # low enough that every sample clips
    model = BertModel(vocab_size=64, padding_idx=1, encoder_layers=1,
                      encoder_embed_dim=32, encoder_ffn_embed_dim=64,
                      encoder_attention_heads=4, max_seq_len=32, post_ln=True,
                      dropout=0.0, emb_dropout=0.0, attention_dropout=0.0)
    tr = Trainer(args, T(args), model, LOSS_REGISTRY["masked_lm"](T(args)))
    batch = mk(3)
    tr.init_state(batch)
    params = tr._state["params"]
    rng = jax.random.PRNGKey(0)

    got, got_ss, _ = tr._forward_backward(
        params, jax.tree_util.tree_map(jnp.asarray, batch), rng,
        jnp.ones((), jnp.float32), jnp.ones((), jnp.float32),
    )

    # manual: per-row grad, clip, sum (must match the vmapped path).
    # jitted once and reused per row — the eager per-row autodiff this
    # replaces dominated the test's wall time on the 1-core CI box
    rows = batch["net_input"]["src_tokens"].shape[0]
    rngs = jax.random.split(rng, rows)

    def loss_fn(p, s1, rng_i):
        loss, ss, _ = tr._loss_fn(p, s1, {"dropout": rng_i}, True)
        return loss.astype(jnp.float32), ss

    def row_step(p, s1, rng_i):
        (loss, ss), g = jax.value_and_grad(loss_fn, has_aux=True)(
            p, s1, rng_i
        )
        g = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), g)
        g, gn = U.clip_grad_norm(g, args.per_sample_clip_norm)
        return ss, g, gn

    row_step_j = jax.jit(row_step)
    acc = None
    ss_acc = 0.0
    for i in range(rows):
        s1 = {
            "net_input": {
                "src_tokens": jnp.asarray(batch["net_input"]["src_tokens"][i:i+1])
            },
            "target": jnp.asarray(batch["target"][i:i+1]),
        }
        ss, g, gn = row_step_j(params, s1, rngs[i])
        assert float(gn) > args.per_sample_clip_norm  # clipping is active
        acc = g if acc is None else jax.tree_util.tree_map(jnp.add, acc, g)
        ss_acc += float(ss)

    err = max(
        float(jnp.abs(a - b).max())
        for a, b in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(acc))
    )
    assert err < 1e-5, err
    assert abs(float(got_ss) - ss_acc) < 0.5


# ---------------------------------------------------------------------------
# one executable per program and shape: the running sums a step program is
# handed are never None, whatever the host's self._macc says
# ---------------------------------------------------------------------------

import os
import subprocess
import sys

import pytest


def tiny(**kw):
    """A one-layer trainer on the suite's eight-device mesh."""
    args = mk_args()
    args.update_freq = [1]
    for k, v in kw.items():
        setattr(args, k, v)
    model = BertModel(vocab_size=64, padding_idx=1, encoder_layers=1,
                      encoder_embed_dim=32, encoder_ffn_embed_dim=64,
                      encoder_attention_heads=4, max_seq_len=64, post_ln=True,
                      dropout=0.0, emb_dropout=0.0, attention_dropout=0.0)
    return Trainer(args, T(args), model, LOSS_REGISTRY["masked_lm"](T(args)))


def sums_of(tr):
    return {k: np.float32(v) for k, v in jax.device_get(tr._macc).items()}


def by_hand(per_update):
    """((0 + x1) + x2) + ... in float32, as the device adds them."""
    total = {k: np.float32(0.0) for k in per_update[0]}
    for upd in per_update:
        total = {k: np.float32(total[k] + upd[k]) for k in total}
    return total


#: case -> (trainer options, widths of an update's micro-batches, the
#: executables each train program must hold after nine updates)
STEP_CASES = {
    "train_step": ({}, (32,), {"train_step": 1}),
    "train_step-donated": (
        {"donate_train_state": True}, (32,), {"train_step": 1}),
    "scan_step": ({"update_freq": [2]}, (32, 32), {"scan_step": 1}),
    "scan_step_adama": (
        {"update_freq": [2], "zero_stage": 0, "grad_accum": "adama"},
        (32, 32), {"scan_step_adama": 1}),
    # micro_step's first call of an update has nothing to add to, by
    # design: one executable per shape and per "acc is None"
    "micro_step+apply_step": (
        {"update_freq": [2]}, (32, 48), {"micro_step": 2, "apply_step": 1}),
}


@pytest.mark.parametrize("case", sorted(STEP_CASES))
def test_step_programs_compile_once_per_shape(case):
    """Three updates, a flush, three more, a caller's ``_macc = None``,
    three more: every program holds one executable per shape (the parent
    held a second, for the calls that began from ``None``), and the sums
    are the per-update values added up by hand."""
    opts, widths, executables = STEP_CASES[case]

    def update(k):
        return [mk(10 * k + j, w) for j, w in enumerate(widths)]

    # per-update values: a second trainer on the same weights and batches
    # whose caller reads and drops the sums after every update
    reader, per_update = tiny(**opts), []
    for k in range(9):
        reader.train_step(update(k))
        per_update.append(sums_of(reader))
        reader._macc = None
    assert reader._compiled_programs() == executables

    tr = tiny(**opts)
    for k in range(3):
        tr.train_step(update(k))
    assert sums_of(tr) == by_hand(per_update[:3])
    tr.flush_metrics()
    assert tr._macc is None  # the host's spelling of "nothing yet" stays
    for k in range(3, 6):
        tr.train_step(update(k))
    assert sums_of(tr) == by_hand(per_update[3:6])
    tr._macc = None
    for k in range(6, 9):
        tr.train_step(update(k))
    assert sums_of(tr) == by_hand(per_update[6:])
    assert tr._compiled_programs() == executables
    assert tr._recompile_count == sum(executables.values())
    for a, b in zip(jax.tree_util.tree_leaves(tr.state["params"]),
                    jax.tree_util.tree_leaves(reader.state["params"])):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_an_update_kind_names_one_program():
    """What finishes an update of each kind, and on which arguments: the
    table the dispatch, the prefetcher's updates and the audit share."""
    state, data = {"params": 0}, object()
    for accum, scan in (("buffer", "scan_step"), ("adama", "scan_step_adama")):
        tr = tiny(zero_stage=0, grad_accum=accum)
        name, (s, d, scalars) = tr._update_program(state, "single", data, 0.5)
        assert (name, s, d) == ("train_step", state, data)
        assert scalars["weight"] == np.float32(0.5)
        for kind, want in (("scan", scan), ("micro", "apply_step")):
            name, (s, d, scalars) = tr._update_program(state, kind, data)
            assert (name, s, d) == (want, state, data)
            assert scalars["weight"] == 1.0 and scalars["micro_i"] == 0
        with pytest.raises(AssertionError):
            tr._update_program(state, "double", data)


def test_valid_step_compiles_once_per_shape():
    """Accumulating and plain validation, before and after a drain, run one
    executable; the drained sums are the per-batch values added up."""
    tr = tiny()
    tr.init_state(mk(0))
    per_batch = [
        {k: np.float32(v) for k, v in tr.valid_step(mk(k)).items()}
        for k in range(6)
    ]
    for k in range(3):
        assert tr.valid_step(mk(k), accumulate=True) is None
    assert tr.finish_valid_accum() == by_hand(per_batch[:3])
    assert tr._vacc is None and tr.finish_valid_accum() == {}
    for k in range(3, 6):
        tr.valid_step(mk(k), accumulate=True)
    assert tr.finish_valid_accum() == by_hand(per_batch[3:])
    assert tr._jit_cache["valid_step"]._cache_size() == 1
    tr.valid_step(mk(0, width=48))
    assert tr._jit_cache["valid_step"]._cache_size() == 2


def test_restored_snapshot_begins_the_sums_anew_in_the_same_program():
    tr = tiny()
    tr.train_step([mk(0)])
    snap = tr.capture_health_snapshot()
    first = None
    for attempt in range(2):
        tr.train_step([mk(1)])
        tr.train_step([mk(2)])
        got = sums_of(tr)
        assert got["_n"] == (3.0 if attempt == 0 else 2.0)
        tr.restore_health_snapshot(snap)
        assert tr._macc is None
        first = first or got
    assert tr._compiled_programs() == {"train_step": 1}
    # the replayed updates read what they read the first time, less the
    # update before the snapshot
    tr.train_step([mk(1)])
    tr.train_step([mk(2)])
    again = sums_of(tr)
    lone = tiny()
    lone.train_step([mk(0)])
    assert by_hand([sums_of(lone), again])["loss"] == pytest.approx(
        first["loss"], rel=1e-6
    )


_ONE_DEVICE = """
import sys
sys.path[:0] = [{repo!r}, {tests!r}]
from unicore_tpu.platform_utils import force_host_cpu
force_host_cpu(1)
import jax
from test_grad_accum import mk, tiny
assert jax.device_count() == 1
tr = tiny()
for k in range(3):
    tr.train_step([mk(k)])
tr.flush_metrics()
for k in range(3, 6):
    tr.train_step([mk(k)])
for k in range(3):
    tr.valid_step(mk(k), accumulate=True)
tr.finish_valid_accum()
tr.valid_step(mk(0), accumulate=True)
print("EXECUTABLES", tr._compiled_programs(),
      tr._jit_cache["valid_step"]._cache_size())
"""


def test_step_programs_compile_once_on_one_device():
    """The same on a one-device mesh, where a zero that is not committed to
    the device as the program's outputs are would be a second entry."""
    tests = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c",
         _ONE_DEVICE.format(repo=os.path.dirname(tests), tests=tests)],
        capture_output=True, text=True, timeout=300, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "EXECUTABLES {'train_step': 1} 1" in proc.stdout, proc.stdout
